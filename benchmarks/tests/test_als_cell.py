"""The cell ``als_implicit_r10_kddcup11.fit_loop`` (PR 38): that
``BENCHMARK.json`` names it and every file of it, its rehearsal through
``run.py``, its four readers on made-up spans, its generator, and the
controls that must come out not correct.

    python -m pytest benchmarks/tests -q        (CPU)
"""

import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402

CELL = "als_implicit_r10_kddcup11.fit_loop"
NEW = ["als_group_build_s", "als_pad_edges_pct", "als_iter_ms", "als_update_roofline"]
ACCEPTED = ["kmeans_d256_k1000.fit_loop", "kmeans_d256_k1000_host4.fit_loop",
            "pca_d512_k10.fit_loop", "kmeans_d256_k1000_f64rows.fit_loop"]
BUILT = {"ratings": 126_400_138, "padded_edges_user": 210_485_504,
         "padded_edges_item": 237_511_424, "group_size": [256, 256],
         "groups_user": 1 << 20, "groups_item": 1 << 20, "threads": 12,
         "bytes": 6_450_839_552, "pieces": 26, "arrays": 8}
PHASES = {"table_convert": 2.5, "table_convert/group_edges": 1.5,
          "table_convert/upload": 0.8, "als_iterations": 4.0,
          "als_iterations/fetch": 3.9}


def _fit(phases=PHASES, staging=BUILT, iterations=5):
    info = {"phases": dict(phases), "iterations": iterations}
    if staging is not None:
        info["staging"] = dict(staging)
    return {"index": 0, "wall_s": 7.0, "result": {}, "error": None, "info": info}


def _ctx(fits, **kw):
    fits = list(fits) + [{"index": 9, "wall_s": 9.0, "result": None, "info": {},
                          "error": "RuntimeError: boom"}]
    return harness.Context(run={"fits": fits, "elapsed_s": 30.0}, **kw)


def _read(metric, ctx):
    return harness._module("metrics", metric).read(ctx)


def _cell():
    bench, cell, cfg, traffic = harness.load_cell(CELL)
    adapter = harness._module("estimators", cfg["estimator"])
    return bench, cell, cfg, traffic, adapter


def test_benchmark_json_names_the_cell_and_every_file_of_it():
    bench, cell, cfg, traffic, adapter = _cell()
    assert cell == {"name": CELL, "config": "als_implicit_r10_kddcup11",
                    "traffic": "fit_loop", "chips": 1, "why": cell["why"]}
    assert bench["workloads"][-1] == cell and len(cell["why"]) <= 200
    entry = bench["configs"][-1]
    assert entry["name"] == cfg["name"] == cell["config"]
    assert entry["reduced"] == ["users", "ratings"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["file"] == "benchmarks/configs/als_implicit_r10_kddcup11.json"
    for kind, name in (("estimators", cfg["estimator"]), ("drivers", traffic["driver"]),
                       ("reference", adapter.REFERENCE)):
        assert os.path.isfile(os.path.join(BENCH, kind, name + ".py")), name
    assert os.path.isfile(os.path.join(BENCH, "README.als.md"))
    reported = [m["name"] for m in harness.metrics_of(bench, cell, "per_layer")]
    assert set(NEW) <= set(reported)
    assert {"table_convert_s", "host_copy_s", "upload_s", "upload_put_s",
            "upload_land_s", "host_gap_s", "estimator_other_s", "device_idle_pct",
            "peak_hbm_gb", "fit_mfu_pct", "window_compiles", "program_ready_s",
            "programs_compiled"} <= set(reported)
    # a dense table's bytes: the four accepted cells', its reader untouched
    assert "upload_gb_per_s" not in reported
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert entries["upload_gb_per_s"]["workloads"] == ACCEPTED
    for name in reported:
        assert callable(harness._module("metrics", name).read)
    for name in NEW:
        assert entries[name]["workloads"] == [CELL] and entries[name]["moves"] == "fit_s"
    assert [m["name"] for m in harness.metrics_of(bench, cell, "end_to_end")] == [
        "fit_s", "setup_s"]


def test_the_configuration_keeps_the_published_shape():
    _, _, cfg, _, adapter = _cell()
    assert (cfg["users"], cfg["items"], cfg["rows_per_chip"]) == (
        500_495, 624_961, 126_400_138)
    assert (cfg["rank"], cfg["max_iter"], cfg["alpha"], cfg["implicit_prefs"]) == (
        10, 5, 40.0, True)
    pub = cfg["published"]
    assert (pub["users"], pub["items"], pub["training_ratings"]) == (
        1_000_990, 624_961, 252_800_275)
    assert cfg["users"] == pub["users"] // 2  # the users with even id
    assert cfg["expect_kernel"] == "grouped"
    assert cfg["phases"] == ["table_convert", "als_iterations"]
    assert set(cfg["limits"]) == {"half_step_gap", "replay_gap", "objective_gap",
                                  "shape_gap"}
    assert set(cfg["assumed"]) >= {"reg_param", "user_degrees", "item_popularity",
                                   "scores", "pairs", "order"}
    settings = adapter.program_settings(cfg)
    assert settings["device"] == "tpu" and settings["fallback"] is False
    assert settings["als_kernel"] == "auto" and settings["compute_precision"] == "f32"
    assert settings["matmul_precision"] == "highest"


def test_make_data_follows_the_seed_and_the_stated_laws():
    _, _, cfg, _, adapter = _cell()
    small = dict(cfg, users=5000, items=6000)
    rows = 5000 * 252
    x = adapter.make_data(small, rows, 2_147_500_123)
    users, items, ratings = x
    assert [a.dtype for a in x] == [np.int32, np.int32, np.float32]
    assert all(len(a) == rows and a.flags.c_contiguous for a in x)
    again = adapter.make_data(small, rows, 2_147_500_123)
    assert all(a.tobytes() == b.tobytes() for a, b in zip(x, again))
    other = adapter.make_data(small, rows, 2_147_500_124)
    assert items.tobytes() != other[1].tobytes()
    degrees = np.bincount(users, minlength=5000)
    assert degrees.min() >= 10 and degrees.sum() == rows and degrees.max() > 2000
    assert np.all(np.diff(users) >= 0)  # each user's ratings together
    assert 0 <= items.min() and items.max() < 6000
    popularity = np.sort(np.bincount(items, minlength=6000))[::-1]
    assert popularity[0] > 20 * popularity[3000]  # a head and a long tail
    assert set(np.unique(ratings)) <= set(np.arange(101, dtype=np.float32))
    assert abs((ratings == 0).mean() - 0.15) < 0.01
    assert abs((ratings % 10 == 0).mean() - 0.85) < 0.01
    table = adapter.score_table(cfg["data"]["scores"])
    assert len(table) == adapter.SCORE_SLOTS and (table == 0).sum() == round(0.15 * 4096)
    x0, y0 = adapter.init_factors(small, 7)
    assert x0.shape == (5000, 10) and y0.shape == (6000, 10) and x0.dtype == np.float32
    assert np.allclose(np.linalg.norm(y0, axis=1), 1.0, atol=1e-5)
    assert adapter.init_factors(small, 7)[0].tobytes() == x0.tobytes()
    assert adapter.init_factors(small, 8)[0].tobytes() != x0.tobytes()


def test_the_four_readers_on_made_up_spans():
    _, cell, cfg, _, adapter = _cell()
    rows = cfg["rows_per_chip"]
    slow = _fit(dict(PHASES, **{"table_convert/group_edges": 2.5, "als_iterations": 6.0}))
    ctx = _ctx([_fit(), slow], cfg=cfg, cell=cell, rows=rows, adapter=adapter)
    assert _read("als_group_build_s", ctx) == pytest.approx(2.0)
    assert _read("als_iter_ms", ctx) == pytest.approx(1000.0)
    padded = BUILT["padded_edges_user"] + BUILT["padded_edges_item"]
    assert _read("als_pad_edges_pct", ctx) == pytest.approx(
        100.0 * (padded - 2 * rows) / padded)
    assert round(_read("als_pad_edges_pct", ctx), 2) == 43.57


def test_als_update_roofline_is_required_work_over_busy_time():
    from lib import trace_reduce

    _, cell, cfg, _, adapter = _cell()
    rows = cfg["rows_per_chip"]
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    work = adapter.phase_work(cfg, rows, {"iterations": 5})["als_iterations"]
    r, n = 10, cfg["users"] + cfg["items"]
    assert work["flops"] == pytest.approx(
        5 * (2 * rows * (r * (r + 1) + 2 * r) + n * r ** 3 / 3 + 2 * n * r * r))
    assert work["bytes"] == pytest.approx(5 * (2 * rows * (12 + 4 * r) + 2 * 4 * r * n))
    least = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    assert least == work["bytes"] / 819e9  # the bytes bind at rank 10
    trace = trace_reduce.Trace(
        {"/device:TPU:0": [(1.0, 2.0, "fusion.1"), (2.5, 3.0, "fusion.2"),
                           (7.0, 8.0, "outside")]},
        [(0.5, 4.5, "als_iterations"), (0.0, 0.4, "table_convert")],
    )
    ctx = _ctx([_fit()], cfg=cfg, cell=cell, rows=rows, adapter=adapter,
               trace=trace, peaks=peaks)
    assert _read("als_update_roofline", ctx) == pytest.approx(100.0 * least / 1.5)
    # a trace with device operations but no annotation: the phase's wall
    bare = trace_reduce.Trace({"/device:TPU:0": [(1.0, 2.0, "fusion.1")]}, [])
    ctx = _ctx([_fit()], cfg=cfg, cell=cell, rows=rows, adapter=adapter,
               trace=bare, peaks=peaks)
    assert _read("als_update_roofline", ctx) == pytest.approx(100.0 * least / 4.0)
    assert adapter.fit_work(cfg, rows, {"iterations": 5}) == work


@pytest.mark.parametrize("fits", [
    [], [_fit(phases={}, staging=None)],
    # a program from before PR 38: two phases, nothing below them
    [_fit(phases={"table_convert": 50.0, "als_iterations": 9.0}, staging={},
          iterations=None)],
])
@pytest.mark.parametrize("metric", NEW + ["upload_s", "host_gap_s", "host_copy_s"])
def test_readers_find_nothing_where_the_program_says_nothing(metric, fits):
    _, cell, cfg, _, adapter = _cell()
    ctx = _ctx(fits, cfg=cfg, cell=cell, rows=cfg["rows_per_chip"], adapter=adapter,
               trace=None, peaks=None)
    assert _read(metric, ctx) is None


def test_an_upload_that_is_not_in_pieces_cannot_run_the_configuration():
    _, _, cfg, _, adapter = _cell()
    assert cfg["expect_upload"]["piece_bytes_max"] == 256 << 20
    assert adapter.upload_breach(cfg, BUILT) is None
    assert "no table_convert/upload span" in adapter.upload_breach(cfg, None)
    assert "no table_convert/upload span" in adapter.upload_breach(cfg, {})
    whole = dict(BUILT, pieces=8)
    assert "8 piece(s) of 806354944" in adapter.upload_breach(cfg, whole)
    assert adapter.upload_breach({}, None) is None
    assert adapter.EXIT_CANNOT_STAGE == 4


@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal_runs_the_cell_and_names_what_it_would_report(trace):
    line, code = harness.drive(CELL, 2_147_500_123, 0.5, trace, rehearse=True,
                               log=open(os.devnull, "w"))
    assert code == 1 and line["rehearsal"] and line["metrics"] == {}
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["checks"]["shape_gap"] == {"value": 0.0, "limit": 0.0}
    if trace:
        assert set(NEW) <= set(line["would_report"])
        assert "upload_gb_per_s" not in line["would_report"]
    else:
        assert line["would_report"] == ["fit_s", "setup_s"]
    json.dumps(line)


class TestControls:
    @pytest.fixture(scope="class")
    def made(self):
        _, _, cfg, _ = harness.load_cell(CELL, rehearse=True)
        adapter = harness._module("estimators", cfg["estimator"])
        ref = harness._module("reference", adapter.REFERENCE)
        return cfg, ref, adapter.make_data(cfg, cfg["rows_per_chip"], 5)

    @staticmethod
    def _over(numbers, limits):
        return {n for n, v in numbers.items() if not v <= limits[n]}

    def test_the_reference_imports_nothing_of_the_program(self, made):
        _, ref, _ = made
        source = open(ref.__file__).read()
        assert "import oap_mllib_tpu" not in source and "from oap_mllib_tpu" not in source

    def test_the_reference_in_the_programs_place_is_correct(self, made):
        cfg, ref, x = made
        sound = ref.fit_plain(x, cfg, 6, "highest")
        numbers = ref.judge(x, cfg, [sound], 5)
        assert not self._over(numbers, cfg["limits"]), numbers

    def test_bf16_stored_edges_and_factors_are_not(self, made):
        cfg, ref, x = made
        stored = ref.fit_plain(x, cfg, 6, "bfloat16")
        assert self._over(ref.judge(x, cfg, [stored], 5), cfg["limits"])

    def test_each_planted_fault_is_not(self, made):
        cfg, ref, x = made
        assert set(cfg["control_faults"]) == {"no_iteration", "zero_scores_preferred"}
        for fault, override in cfg["control_faults"].items():
            result = ref.fit_plain(x, dict(cfg, **override), 6, "highest")
            assert self._over(ref.judge(x, cfg, [result], 5), cfg["limits"]), fault

    def test_a_rating_dropped_or_counted_twice_is_not(self, made):
        """Every rating once on each side: the sound fit of a table that
        lacks its last thousand ratings, or holds them twice, judged on the
        table as handed over."""
        cfg, ref, x = made
        for other in (tuple(a[:-1000] for a in x),
                      tuple(np.concatenate([a, a[-1000:]]) for a in x)):
            result = ref.fit_plain(other, cfg, 6, "highest")
            assert "half_step_gap" in self._over(
                ref.judge(x, cfg, [result], 5), cfg["limits"])

    def test_a_malformed_answer_is_not(self, made):
        cfg, ref, x = made
        result = ref.fit_plain(x, cfg, 6, "highest")
        result["item_factors"] = result["item_factors"][:-1]
        assert ref.judge(x, cfg, [result], 5)["shape_gap"] == 1.0
