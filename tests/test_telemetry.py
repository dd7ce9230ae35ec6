"""Telemetry subsystem tests (ISSUE 4): span tree + Timings views,
metrics registry, counter absorption from the existing subsystems,
exporters, and the telemetry-off no-op contract."""

import json

import numpy as np
import pytest

from oap_mllib_tpu import telemetry
from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.telemetry import metrics as tm
from oap_mllib_tpu.telemetry.spans import Span, current_span, enter
from oap_mllib_tpu.utils.timing import Timings, phase_timer


class TestSpans:
    def test_nesting_and_paths(self):
        root = Span("fit")
        root.node("a/b").record(1.0)
        root.node("a").record(2.0)
        root.node("a/b").record(0.5)
        a = root.child("a")
        assert [c.name for c in root.children] == ["a"]
        assert [c.name for c in a.children] == ["b"]
        assert a.duration_s == pytest.approx(2.0)
        assert a.child("b").duration_s == pytest.approx(1.5)
        assert a.child("b").count == 2

    def test_flat_excludes_unrecorded_containers(self):
        """Implicit path containers (count=0) must not appear in the
        flat view — the old record list only held explicit adds."""
        root = Span("fit")
        root.node("phase/compile").record(0.25)
        assert root.flat() == {"phase/compile": pytest.approx(0.25)}

    def test_walk_and_as_dict(self):
        root = Span("fit")
        root.node("x/y").record(1.0)
        paths = [p for p, _ in root.walk()]
        assert paths == ["fit", "fit/x", "fit/x/y"]
        d = root.as_dict()
        assert d["name"] == "fit"
        assert d["children"][0]["children"][0]["name"] == "y"

    def test_attributes_and_collective_notes(self):
        sp = Span("phase")
        sp.note_collective("allreduce_sum", 1024, 0.01)
        sp.note_collective("allreduce_sum", 1024, 0.02)
        sp.note_collective("broadcast", 64, 0.001)
        coll = sp.attrs["collectives"]
        assert coll["allreduce_sum"]["ops"] == 2
        assert coll["allreduce_sum"]["bytes"] == 2048
        assert coll["broadcast"]["ops"] == 1

    def test_enter_stack_and_timing(self):
        sp = Span("outer")
        inner = sp.child("inner")
        assert current_span() is None
        with enter(sp):
            assert current_span() is sp
            with enter(inner):
                assert current_span() is inner
            assert current_span() is sp
        assert current_span() is None
        assert sp.count == 1 and sp.duration_s > 0
        assert inner.duration_s <= sp.duration_s

    def test_enter_records_on_exception(self):
        sp = Span("s")
        with pytest.raises(RuntimeError):
            with enter(sp):
                raise RuntimeError("boom")
        assert sp.count == 1
        assert current_span() is None


class TestTimingsViews:
    """Timings accessors must return exactly what the flat record list
    returned (the backward-compat contract of the storage swap)."""

    def test_add_and_as_dict_sum_duplicates(self):
        t = Timings()
        t.add("a", 1.0)
        t.add("b/c", 0.5)
        t.add("a", 0.25)
        assert t.as_dict() == {
            "a": pytest.approx(1.25), "b/c": pytest.approx(0.5)
        }
        assert t.total() == pytest.approx(1.75)

    def test_subphases(self):
        t = Timings()
        t.add("lloyd_loop", 2.0)
        t.add("lloyd_loop/stage", 0.3)
        t.add("lloyd_loop/compute", 1.6)
        assert t.subphases("lloyd_loop") == {
            "stage": pytest.approx(0.3), "compute": pytest.approx(1.6)
        }

    def test_overlap_efficiency_matches_pre_span_formula(self):
        t = Timings()
        t.add("p/stage", 0.3)
        t.add("p/transfer", 0.2)
        t.add("p/compute", 0.9)
        t.add("p/stream_wall", 1.0)
        # wait = 1.0 - 0.9 = 0.1 of 0.5 staging -> 80% hidden
        assert t.overlap_efficiency("p") == pytest.approx(0.8)
        assert t.overlap_efficiency("absent") is None

    def test_compile_split(self):
        t = Timings()
        assert t.compile_split("p") is None
        t.add("p/compile", 0.7)
        assert t.compile_split("p") == {
            "compile": pytest.approx(0.7), "execute": 0.0
        }

    def test_phase_timer_records_into_tree(self):
        t = Timings("kmeans.fit")
        with phase_timer(t, "lloyd_loop"):
            pass
        assert t.root.name == "kmeans.fit"
        assert "lloyd_loop" in t.as_dict()
        assert t.root.child("lloyd_loop").count == 1

    def test_phase_log_names_owner_and_rank(self, caplog):
        """The ISSUE 4 satellite: concurrent fits' phase lines must be
        attributable — the root name (and the rank, multi-process) ride
        the log line."""
        import logging

        set_config(timing=True)
        t = Timings("pca.fit")
        with caplog.at_level(logging.INFO, logger="oap_mllib_tpu"):
            t.add("covariance", 0.5)
        assert "pca.fit" in caplog.text and "covariance" in caplog.text
        set_config(num_processes=4, process_id=2)
        with caplog.at_level(logging.INFO, logger="oap_mllib_tpu"):
            t.add("eigh", 0.1)
        assert "pca.fit[r2]" in caplog.text


class TestMetricsRegistry:
    def setup_method(self):
        tm.reset()

    def test_counter_and_gauge(self):
        tm.counter("t_total").inc()
        tm.counter("t_total").inc(2.5)
        tm.gauge("t_gauge").set(7)
        snap = tm.snapshot()
        assert snap["t_total"][""] == pytest.approx(3.5)
        assert snap["t_gauge"][""] == 7

    def test_labels_are_distinct_series(self):
        tm.counter("ops", {"op": "a"}).inc()
        tm.counter("ops", {"op": "b"}).inc(3)
        snap = tm.snapshot()
        assert snap["ops"] == {"op=a": 1, "op=b": 3}

    def test_histogram_bucket_edges(self):
        """Fixed log-scale bounds: a value equal to a bound lands IN
        that bound's bucket (le semantics); past the last bound lands
        in +Inf."""
        h = tm.histogram("h", bounds=(1.0, 4.0, 16.0))
        for v in (0.5, 1.0, 1.0001, 4.0, 16.0, 17.0):
            h.observe(v)
        assert h.counts == [2, 2, 1, 1]  # [<=1, <=4, <=16, +Inf]
        assert h.count == 6
        assert h.sum == pytest.approx(0.5 + 1.0 + 1.0001 + 4.0 + 16.0 + 17.0)

    def test_default_buckets_are_log_scale(self):
        bs = tm.DURATION_BUCKETS
        assert all(
            bs[i + 1] / bs[i] == pytest.approx(4.0)
            for i in range(len(bs) - 1)
        )

    def test_type_conflict_raises(self):
        tm.counter("conflicted")
        with pytest.raises(ValueError, match="already registered"):
            tm.gauge("conflicted")

    def test_prometheus_rendering(self):
        tm.counter("c_total", {"algo": "kmeans"}, help="a counter").inc(2)
        h = tm.histogram("lat_seconds", bounds=(0.1, 1.0))
        h.observe(0.05)
        h.observe(5.0)
        text = tm.render_prometheus()
        assert "# HELP c_total a counter" in text
        assert "# TYPE c_total counter" in text
        assert 'c_total{algo="kmeans"} 2' in text
        assert "# TYPE lat_seconds histogram" in text
        # cumulative buckets: 1 at <=0.1, still 1 at <=1.0, 2 at +Inf
        assert 'lat_seconds_bucket{le="0.1"} 1' in text
        assert 'lat_seconds_bucket{le="1"} 1' in text
        assert 'lat_seconds_bucket{le="+Inf"} 2' in text
        assert "lat_seconds_count 2" in text


class TestPrometheusRoundTrip:
    """ISSUE 11 satellite: the text exposition must hold the promtext
    spec — verified by PARSING it back and cross-checking against the
    registry, not by substring spot checks."""

    def setup_method(self):
        tm.reset()

    @staticmethod
    def _parse(text):
        """Minimal promtext parser: {family: {"type", "help",
        "samples": {(suffix, labels-str): value}}}.  Raises on any line
        that fits neither comment nor sample grammar."""
        import re

        fams = {}
        sample_re = re.compile(
            r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (\S+)$"
        )
        for line in text.splitlines():
            if not line:
                continue
            if line.startswith("# HELP "):
                _, _, name, help_ = line.split(" ", 3)
                fams.setdefault(name, {"samples": {}})["help"] = help_
            elif line.startswith("# TYPE "):
                _, _, name, kind = line.split(" ", 3)
                fams.setdefault(name, {"samples": {}})["type"] = kind
            else:
                m = sample_re.match(line)
                assert m, f"unparsable exposition line: {line!r}"
                name, labels, value = m.groups()
                base = name
                for suffix in ("_bucket", "_sum", "_count"):
                    if name.endswith(suffix) and name[: -len(suffix)] in fams:
                        base = name[: -len(suffix)]
                        break
                fams.setdefault(base, {"samples": {}})["samples"][
                    (name, labels or "")
                ] = float(value.replace("+Inf", "inf"))
        return fams

    def test_every_family_has_help_and_type(self):
        tm.counter("rt_total", help="with help").inc()
        tm.counter("rt_helpless_total").inc()  # registered help-less
        fams = self._parse(tm.render_prometheus())
        for name, fam in fams.items():
            assert "type" in fam, f"{name} missing # TYPE"
            assert "help" in fam, f"{name} missing # HELP"
        assert fams["rt_total"]["help"] == "with help"
        # help-less registration gets the self-naming fallback
        assert fams["rt_helpless_total"]["help"]

    def test_help_upgraded_when_richer_site_registers(self):
        tm.counter("rt_lazy_total").inc()
        tm.counter("rt_lazy_total", help="the real help").inc()
        fams = self._parse(tm.render_prometheus())
        assert fams["rt_lazy_total"]["help"] == "the real help"

    def test_histogram_cumulative_inf_count_sum_consistent(self):
        h = tm.histogram("rt_seconds", bounds=(0.1, 1.0, 10.0),
                         help="hist")
        values = [0.05, 0.1, 0.5, 2.0, 50.0, 50.0]
        for v in values:
            h.observe(v)
        fams = self._parse(tm.render_prometheus())
        samples = fams["rt_seconds"]["samples"]
        buckets = {
            labels: v for (name, labels), v in samples.items()
            if name == "rt_seconds_bucket"
        }
        # cumulative and non-decreasing in le order, +Inf == _count
        ordered = [buckets[f'{{le="{le}"}}']
                   for le in ("0.1", "1", "10", "+Inf")]
        assert ordered == sorted(ordered)
        assert ordered[0] == 2  # 0.05 and the le-inclusive 0.1
        assert ordered[-1] == len(values)
        assert samples[("rt_seconds_count", "")] == len(values)
        assert samples[("rt_seconds_sum", "")] == pytest.approx(
            sum(values)
        )

    def test_label_values_escaped(self):
        tm.counter(
            "rt_esc_total",
            {"path": 'a"b\\c', "msg": "two\nlines"},
            help="escapes",
        ).inc()
        text = tm.render_prometheus()
        assert '\\"' in text and "\\\\" in text and "\\n" in text
        fams = self._parse(text)  # the escaped line still parses
        assert any(
            name == "rt_esc_total"
            for (name, _) in fams["rt_esc_total"]["samples"]
        )

    def test_registry_values_round_trip(self):
        tm.counter("rt_c_total", {"op": "a"}, help="c").inc(3)
        tm.gauge("rt_g", help="g").set(2.5)
        fams = self._parse(tm.render_prometheus())
        assert fams["rt_c_total"]["samples"][
            ("rt_c_total", '{op="a"}')
        ] == 3
        assert fams["rt_g"]["samples"][("rt_g", "")] == 2.5
        assert fams["rt_c_total"]["type"] == "counter"
        assert fams["rt_g"]["type"] == "gauge"

    def test_family_total_sums_across_labels_and_histograms(self):
        tm.counter("rt_f_total", {"op": "a"}).inc(1)
        tm.counter("rt_f_total", {"op": "b"}).inc(2)
        h = tm.histogram("rt_f_seconds")
        h.observe(0.5)
        h.observe(1.5)
        assert tm.family_total("rt_f_total") == 3
        assert tm.family_total("rt_f_seconds") == pytest.approx(2.0)
        assert tm.family_total("rt_missing") == 0.0

    def test_live_registry_exposition_parses_after_a_fit(self, rng):
        """The whole live registry (every subsystem's families) must
        parse — the scrape-surface contract behind /metrics."""
        from oap_mllib_tpu.models.kmeans import KMeans

        x = rng.normal(size=(256, 4)).astype(np.float32)
        KMeans(k=2, max_iter=2, seed=0).fit(x)
        fams = self._parse(tm.render_prometheus())
        assert "oap_fit_total" in fams
        for name, fam in fams.items():
            assert "type" in fam and "help" in fam, name


class TestCounterAbsorption:
    """The pre-existing stats objects must mirror into the registry at
    their native increment points."""

    def setup_method(self):
        tm.reset()

    def test_progcache_feeds_registry(self):
        from oap_mllib_tpu.utils.progcache import ProgramCache

        pc = ProgramCache()
        pc.note("algoX", (1,))
        pc.note("algoX", (1,))
        pc.get_or_build("algoX", (2,), lambda: "prog")
        snap = tm.snapshot()
        assert snap["oap_progcache_misses_total"]["algo=algoX"] == 2
        assert snap["oap_progcache_hits_total"]["algo=algoX"] == 1

    def test_prefetch_feeds_registry(self):
        from oap_mllib_tpu.data.prefetch import Prefetcher, PrefetchStats

        stats = PrefetchStats()
        chunks = [np.zeros((16, 4), np.float32) for _ in range(3)]
        with Prefetcher(chunks, depth=2, stats=stats) as pf:
            list(pf)
        stats.finalize(None, "test_phase", wall=0.5)
        snap = tm.snapshot()
        assert snap["oap_prefetch_chunks_total"]["phase=test_phase"] == 3
        assert snap["oap_stream_rows_total"]["phase=test_phase"] == 48
        assert (
            snap["oap_stream_bytes_staged_total"]["phase=test_phase"]
            == 3 * 16 * 4 * 4
        )
        assert stats.bytes_staged == 3 * 16 * 4 * 4
        assert stats.rows == 48

    def test_resilience_feeds_registry(self):
        from oap_mllib_tpu.utils.resilience import ResilienceStats

        stats = ResilienceStats()
        stats.record("site", "transient", RuntimeError("x"))
        stats.note_retry(0.25)
        stats.note_degradation()
        snap = tm.snapshot()
        assert snap["oap_resilience_faults_total"]["kind=transient"] == 1
        assert snap["oap_resilience_retries_total"][""] == 1
        assert snap["oap_resilience_backoff_seconds_total"][""] == 0.25
        assert snap["oap_resilience_degradations_total"][""] == 1
        # the per-fit object kept its own view too
        assert stats.retries == 1 and stats.backoff_s == 0.25

    def test_collective_facade_feeds_registry_and_span(self, rng):
        import jax.numpy as jnp

        from oap_mllib_tpu.parallel.collective import allreduce_sum
        from oap_mllib_tpu.parallel.mesh import get_mesh

        x = jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))
        sp = Span("phase")
        with enter(sp, annotate=False):
            allreduce_sum(x, get_mesh())
        snap = tm.snapshot()
        assert snap["oap_collective_ops_total"]["op=allreduce_sum"] == 1
        assert (
            snap["oap_collective_bytes_total"]["op=allreduce_sum"]
            == x.nbytes
        )
        assert sp.attrs["collectives"]["allreduce_sum"]["ops"] == 1


class TestFitSummaryTelemetry:
    def test_in_memory_fit_exposes_span_tree_and_metrics(self, rng):
        from oap_mllib_tpu import KMeans

        x = rng.normal(size=(256, 6)).astype(np.float32)
        m = KMeans(k=3, max_iter=3, seed=0).fit(x)
        tele = m.summary.telemetry
        assert tele["fit"] == "kmeans.fit"
        names = {c["name"] for c in tele["spans"]["children"]}
        assert {"table_convert", "init_centers", "lloyd_loop"} <= names
        assert tele["spans"]["duration_s"] > 0
        assert "oap_fit_total" in tele["metrics"]
        # the flat views still work off the same storage
        assert m.summary.timings.total() > 0

    def test_pca_and_streamed_fit_summaries(self, rng):
        from oap_mllib_tpu import PCA, KMeans
        from oap_mllib_tpu.data.stream import ChunkSource

        x = rng.normal(size=(400, 6)).astype(np.float32)
        p = PCA(k=2).fit(x)
        assert p.summary["telemetry"]["fit"] == "pca.fit"
        src = ChunkSource.from_array(x, chunk_rows=128)
        m = KMeans(k=3, max_iter=2, seed=0).fit(src)
        paths = {
            pth for pth, _ in
            _tree_paths(m.summary.telemetry["spans"])
        }
        assert "kmeans.fit/lloyd_loop/stage" in paths
        assert "kmeans.fit/lloyd_loop/compute" in paths


SUB_SPANS = (
    "table_convert/host_copy", "table_convert/upload",
    "init_centers/rounds", "init_centers/kmeanspp_host",
)


class TestSubSpans:
    """The host-bound phases of a fit are split where the work happens
    (ISSUE 25): data/table.py and ops/kmeans_ops.py open sub-spans of
    the running phase through ``spans.child``."""

    @pytest.fixture
    def kmeans_fit(self, rng, monkeypatch):
        """(model, the DenseTable the fit built)."""
        from oap_mllib_tpu import KMeans
        from oap_mllib_tpu.data.table import DenseTable

        built = []
        make = DenseTable.from_numpy.__func__

        def spy(cls, *a, **kw):
            built.append(make(cls, *a, **kw))
            return built[-1]

        monkeypatch.setattr(DenseTable, "from_numpy", classmethod(spy))
        x = rng.normal(size=(700, 6)).astype(np.float32)
        model = KMeans(k=4, max_iter=3, seed=0).fit(x)
        assert model.summary.accelerated and len(built) == 1
        return model, built[0]

    @pytest.mark.parametrize("path", SUB_SPANS)
    def test_kmeans_fit_records_sub_span(self, kmeans_fit, path):
        flat = kmeans_fit[0].summary.timings.as_dict()
        assert flat[path] > 0

    @pytest.mark.parametrize("parent", ["table_convert", "init_centers"])
    def test_children_cover_their_parent(self, kmeans_fit, parent):
        timings = kmeans_fit[0].summary.timings
        whole = timings.as_dict()[parent]
        parts = sum(
            sec for sub, sec in timings.subphases(parent).items()
            if parent + "/" + sub in SUB_SPANS
        )
        assert parts <= whole
        assert whole - parts <= max(0.05 * whole, 2e-3)

    def test_counters_at_the_boundaries(self, kmeans_fit):
        model, table = kmeans_fit
        root = model.summary.timings.root
        assert root.node("table_convert/upload").attrs["bytes"] == (
            table.data.nbytes + table.mask.nbytes
        )
        # 700 rows are off their bucket: ONE host pass wrote the padded
        # table (the zero-pass route reads 0: tests/test_table_staging.py)
        copy = root.node("table_convert/host_copy")
        assert copy.attrs["copied_bytes"] == table.data.nbytes
        rounds = root.node("init_centers/rounds").attrs
        assert rounds["rounds"] == 2
        cand = root.node("init_centers/kmeanspp_host").attrs["candidates"]
        assert 4 < cand <= 1 + 2 * 16  # one seed row + 4k slots a round
        # what the rounds folded: 4k = 16 slots are ONE chunk a round, so
        # sum(ceil(filled_r / 16)) counts the rounds that picked a row
        assert rounds["slots_filled"] == cand - 1
        assert rounds["slot_chunks_cap"] == 2
        assert 1 <= rounds["slot_chunks"] <= rounds["slot_chunks_cap"]
        assert rounds["slot_chunks"] >= -(-rounds["slots_filled"] // 16)
        host = root.node("init_centers/kmeanspp_host")
        assert host.attrs["reduced_on"] == "device"
        # the tree the exporters serialize carries them too
        tree = dict(_tree_paths(model.summary.telemetry["spans"]))
        up = tree["kmeans.fit/table_convert/upload"]
        assert up["attrs"]["bytes"] == table.data.nbytes + table.mask.nbytes
        copy = tree["kmeans.fit/table_convert/host_copy"]
        assert copy["attrs"]["copied_bytes"] == table.data.nbytes

    def test_too_few_candidates_are_topped_up_on_the_host(self):
        """Four rows cannot give more than k = 4 candidates: the top-up
        branch stays on the host and says so."""
        from oap_mllib_tpu import KMeans

        x = np.eye(4, 6, dtype=np.float32)
        model = KMeans(k=4, max_iter=2, seed=0).fit(x)
        host = model.summary.timings.root.node("init_centers/kmeanspp_host")
        assert 1 <= host.attrs["candidates"] <= 4
        assert host.attrs["reduced_on"] == "host"
        assert model.cluster_centers_.shape == (4, 6)

    def test_pca_fit_records_the_staging_pair(self, rng):
        from oap_mllib_tpu import PCA

        x = rng.normal(size=(400, 6)).astype(np.float32)
        timings = PCA(k=2).fit(x).summary["timings"]
        flat = timings.as_dict()
        copy = timings.root.node("table_convert/host_copy")
        assert copy.attrs["copied_bytes"] > x.nbytes  # padded to the bucket
        assert flat["table_convert/host_copy"] > 0
        assert flat["table_convert/upload"] > 0
        assert (
            flat["table_convert/host_copy"] + flat["table_convert/upload"]
            <= flat["table_convert"]
        )

    def test_child_outside_a_fit_is_a_no_op(self):
        from oap_mllib_tpu.telemetry import spans

        assert current_span() is None
        with spans.child("upload") as sp:
            assert current_span() is None
            sp.attrs["bytes"] = 1  # call sites never ask whether it is live
        assert current_span() is None
        assert sp.count == 0 and sp.duration_s == 0.0

    def test_child_nests_under_the_active_span(self):
        from oap_mllib_tpu.telemetry import spans

        t = Timings("kmeans.fit")
        with phase_timer(t, "table_convert"):
            with spans.child("upload") as sp:
                assert current_span() is sp
                with spans.child("dma") as inner:
                    pass
        assert sp.path == "table_convert/upload"
        assert inner.path == "table_convert/upload/dma"
        assert set(t.as_dict()) == {
            "table_convert", "table_convert/upload",
            "table_convert/upload/dma",
        }

    def test_trace_annotations_are_named_by_path(self, monkeypatch):
        """Under a live trace a sub-span's annotation says whose it is;
        a top-level phase keeps its bare name (the benchmark's trace
        reduction finds the phases by it)."""
        import contextlib

        import jax

        from oap_mllib_tpu.telemetry import spans
        from oap_mllib_tpu.utils import profiling

        seen = []
        monkeypatch.setattr(
            jax.profiler, "TraceAnnotation",
            lambda name: seen.append(name) or contextlib.nullcontext(),
        )
        t = Timings("kmeans.fit")
        with phase_timer(t, "table_convert"), spans.child("upload"):
            pass
        assert seen == []  # no trace running: no annotation at all
        monkeypatch.setattr(profiling, "_active", 1)
        with phase_timer(t, "table_convert"), spans.child("upload"):
            pass
        with phase_timer(t, "lloyd_loop"):
            pass
        with enter(Span("bare")):
            pass
        assert seen == [
            "table_convert", "table_convert/upload", "lloyd_loop", "bare",
        ]


class TestCompatSurfaces:
    def test_drop_in_summary_exposes_telemetry(self, rng):
        """The compat layers proxy the inner summaries, so the span tree
        + metrics snapshot must reach unmodified user code through the
        drop-in surface too (the ISSUE 4 contract)."""
        from oap_mllib_tpu.compat import KMeans as CompatKMeans

        x = rng.normal(size=(256, 5)).astype(np.float32)
        m = CompatKMeans().setK(3).setSeed(1).fit({"features": x})
        assert m.summary.telemetry["fit"] == "kmeans.fit"
        assert "oap_fit_total" in m.summary.telemetry["metrics"]
        names = {
            c["name"] for c in m.summary.telemetry["spans"]["children"]
        }
        assert "lloyd_loop" in names


def _tree_paths(tree, prefix=""):
    path = prefix + tree["name"]
    yield path, tree
    for c in tree.get("children", []):
        yield from _tree_paths(c, path + "/")


class TestExporters:
    def test_jsonl_round_trip(self, rng, tmp_path):
        from oap_mllib_tpu import KMeans

        sink = tmp_path / "t.jsonl"
        set_config(telemetry_log=str(sink))
        x = rng.normal(size=(128, 4)).astype(np.float32)
        m = KMeans(k=2, max_iter=2, seed=0).fit(x)
        lines = sink.read_text().splitlines()
        assert lines
        records = [json.loads(ln) for ln in lines]  # every line parses
        spans = [r for r in records if r["type"] == "span"]
        metrics_recs = [r for r in records if r["type"] == "metrics"]
        assert len(metrics_recs) == 1
        assert all(r["rank"] == 0 for r in records)
        seqs = [r["seq"] for r in records]
        assert seqs == sorted(seqs)
        # the span records reproduce the summary tree exactly
        summary_paths = {
            p: n["duration_s"]
            for p, n in _tree_paths(m.summary.telemetry["spans"])
        }
        jsonl_paths = {r["path"]: r["duration_s"] for r in spans}
        assert jsonl_paths == summary_paths

    def test_multi_process_sink_is_rank_suffixed(self, tmp_path):
        from oap_mllib_tpu.telemetry.export import sink_path

        set_config(telemetry_log=str(tmp_path / "w.jsonl"))
        assert sink_path() == str(tmp_path / "w.jsonl")
        set_config(num_processes=4, process_id=3)
        assert sink_path() == str(tmp_path / "w.jsonl") + ".rank3"

    def test_report_renders_fit_and_process_views(self, rng):
        from oap_mllib_tpu import KMeans

        x = rng.normal(size=(128, 4)).astype(np.float32)
        m = KMeans(k=2, max_iter=2, seed=0).fit(x)
        text = telemetry.report(m.summary)
        assert "kmeans.fit" in text and "lloyd_loop" in text
        proc = telemetry.report()
        assert "process metrics" in proc

    def test_render_prometheus_reexport(self):
        tm.counter("oap_reexport_check_total").inc()
        assert "oap_reexport_check_total 1" in telemetry.render_prometheus()


class TestOrderedShutdown:
    """ISSUE 14 satellite: interpreter-exit work is ONE ordered hook —
    flight-recorder drain + final snapshot into the sink first, fleet
    endpoint teardown last — instead of independent atexit racers (the
    oaplint atexit-outside-shutdown rule keeps it unique)."""

    def test_shutdown_sequences_sink_before_server(self, tmp_path,
                                                   monkeypatch):
        from oap_mllib_tpu.telemetry import export, fleet

        order = []
        real_write = export._write_lines
        monkeypatch.setattr(
            export, "_write_lines",
            lambda path, recs: (order.append("sink"),
                                real_write(path, recs)),
        )
        monkeypatch.setattr(
            fleet, "stop_server", lambda: order.append("server"))
        set_config(telemetry_log=str(tmp_path / "s.jsonl"),
                   flight_recorder=32)
        from oap_mllib_tpu.telemetry import flightrec

        flightrec._reset_for_tests()  # a prior test's drain cursor
        flightrec.record("chunk", "probe", "#0")
        export.shutdown()
        assert order == ["sink", "server"]
        records = [json.loads(ln) for ln in
                   (tmp_path / "s.jsonl").read_text().splitlines()]
        kinds = [r["type"] for r in records]
        # the recorder tail and the final snapshot land in ONE batch,
        # drain first so post-mortem tooling sees a complete stream
        assert kinds == ["flightrec", "metrics"]
        assert all(r.get("final") for r in records)
        flightrec._reset_for_tests()

    def test_sink_failure_still_stops_the_server(self, tmp_path,
                                                 monkeypatch):
        from oap_mllib_tpu.telemetry import export, fleet

        stopped = []
        monkeypatch.setattr(
            fleet, "stop_server", lambda: stopped.append(True))
        monkeypatch.setattr(
            export, "_emit_final_snapshot",
            lambda: (_ for _ in ()).throw(RuntimeError("torn fs")),
        )
        with pytest.raises(RuntimeError):
            export.shutdown()
        assert stopped == [True]

    def test_register_shutdown_is_idempotent(self, monkeypatch):
        import atexit

        from oap_mllib_tpu.telemetry import export

        registered = []
        monkeypatch.setattr(
            atexit, "register", lambda fn: registered.append(fn))
        monkeypatch.setattr(export, "_shutdown_registered", False)
        export.register_shutdown()
        export.register_shutdown()
        assert registered == [export.shutdown]


class TestTelemetryOff:
    def test_no_sink_no_file(self, rng, tmp_path, monkeypatch):
        """With telemetry_log empty nothing is written anywhere and the
        fit still carries its summary telemetry (the in-memory layer is
        the accounting the summary always paid for)."""
        from oap_mllib_tpu import KMeans
        from oap_mllib_tpu.telemetry import export

        monkeypatch.chdir(tmp_path)
        calls = []
        monkeypatch.setattr(
            export, "_write_lines",
            lambda *a, **k: calls.append(a),
        )
        x = rng.normal(size=(128, 4)).astype(np.float32)
        m = KMeans(k=2, max_iter=2, seed=0).fit(x)
        assert calls == []  # sink off -> the writer is never invoked
        assert list(tmp_path.iterdir()) == []
        assert m.summary.telemetry["fit"] == "kmeans.fit"

    def test_span_annotation_guard_off_by_default(self):
        from oap_mllib_tpu.utils import profiling

        assert profiling.trace_active() is False

    def test_off_overhead_is_bounded(self, rng):
        """20 tiny fits with telemetry fully off: the span/registry layer
        must not dominate the fit wall.  This is a smoke bound (the real
        ≤2% gate is a bench comparison, not a unit test): the telemetry
        bookkeeping for a fit is a handful of dict ops, so 20 fits'
        TOTAL finalize+span cost must stay far under one fit's wall."""
        import time

        from oap_mllib_tpu import KMeans

        x = rng.normal(size=(64, 4)).astype(np.float32)
        KMeans(k=2, max_iter=2, seed=0).fit(x)  # warm compile
        t0 = time.perf_counter()
        for _ in range(20):
            KMeans(k=2, max_iter=2, seed=0).fit(x)
        fit_wall = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(2000):
            t = Timings("kmeans.fit")
            with phase_timer(t, "lloyd_loop"):
                pass
            telemetry.finalize_fit({"timings": t})
        tele_wall = (time.perf_counter() - t0) / 100  # per-20-fits cost
        assert tele_wall < max(0.02 * fit_wall, 0.005), (
            tele_wall, fit_wall
        )
