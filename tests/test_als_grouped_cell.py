"""Implicit ALS as the benchmark's ``als_implicit_r10_kddcup11`` drives it
(ISSUE 38): the grouped build over host threads on int32 ids, the layouts'
upload in pieces, the group-count bucket, the spans of the fit, and the
share of the deployment one chip holds — against the benchmark's plain
reference, on the CPU at small sizes."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oap_mllib_tpu import ALS, native, telemetry
from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.data import table as table_mod
from oap_mllib_tpu.ops import als_ops
from oap_mllib_tpu.utils import membudget, progcache

CELL = "als_implicit_r10_kddcup11.fit_loop"
BUILD_SPAN = "table_convert/group_edges"


@pytest.fixture(scope="module")
def bench():
    """(``benchmarks/run.py`` as a module, the configuration at its
    rehearse size, its adapter, its reference)."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "run.py",
    )
    spec = importlib.util.spec_from_file_location("oap_bench_run_als", path)
    harness = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(harness)
    _, _, cfg, _ = harness.load_cell(CELL, rehearse=True)
    adapter = harness._module("estimators", cfg["estimator"])
    ref = harness._module("reference", adapter.REFERENCE)
    return harness, cfg, adapter, ref


def _skewed(seed, nnz=20000, n_users=300, n_items=500, empty_item=7):
    """A long-tailed table with scores of 0, repeated pairs and one item
    nobody rated; int32 ids, float32 scores."""
    rng = np.random.default_rng(seed)
    users = np.minimum((rng.pareto(1.2, nnz) * 3).astype(np.int32), n_users - 1)
    items = rng.integers(0, n_items, nnz).astype(np.int32)
    items[items == empty_item] = empty_item + 1
    ratings = rng.integers(0, 5, nnz).astype(np.float32) * 25.0
    users[:50], items[:50] = users[50:100], items[50:100]  # repeated pairs
    return users, items, ratings


def _fit(x, n_users, n_items, seed=3, **kw):
    users, items, ratings = x
    return ALS(
        rank=4, max_iter=3, implicit_prefs=True, alpha=40.0, seed=seed,
        num_user_blocks=1, **kw,
    ).fit(users, items, ratings, n_users=n_users, n_items=n_items)


class TestThreadedBuild:
    @pytest.mark.parametrize("threads", [1, 3, 8])
    @pytest.mark.parametrize("side", ["user", "item"])
    def test_the_layout_is_the_one_thread_and_the_numpy_builds(
            self, monkeypatch, threads, side):
        if not native.available():
            pytest.skip("no native library here")
        users, items, ratings = _skewed(1)
        dst, src, n_dst = (users, items, 300) if side == "user" else (items, users, 500)
        got = als_ops.build_grouped_edges(dst, src, ratings, n_dst, 16,
                                          threads=threads)
        one_thread = native.als_group_edges(dst, src, ratings, n_dst, 16)
        monkeypatch.setenv("OAP_MLLIB_TPU_PURE_PYTHON", "1")
        oracle = als_ops.build_grouped_edges(dst, src, ratings, n_dst, 16)
        for a, b, c in zip(got, one_thread, oracle):
            assert a.dtype == c.dtype and a.shape == c.shape
            assert a.tobytes() == b.tobytes() == c.tobytes()

    @pytest.mark.parametrize("threads", [1, 3, 8])
    def test_a_bucket_of_groups_ends_in_pad_groups(self, monkeypatch, threads):
        users, items, ratings = _skewed(2)
        exact = als_ops.build_grouped_edges(users, items, ratings, 300, 16,
                                            threads=threads)
        g = exact[0].shape[0]
        padded = als_ops.build_grouped_edges(users, items, ratings, 300, 16,
                                             groups=g + 40, threads=threads)
        monkeypatch.setenv("OAP_MLLIB_TPU_PURE_PYTHON", "1")
        oracle = als_ops.build_grouped_edges(users, items, ratings, 300, 16,
                                             groups=g + 40)
        for a, b, c in zip(exact, padded, oracle):
            assert b.shape[0] == g + 40 and b.tobytes() == c.tobytes()
            assert np.array_equal(a, b[:g])
        assert not padded[2][g:].any() and not padded[0][g:].any()
        assert (padded[3][g:] == 299).all()  # sorted still
        assert int(als_ops.live_group_count(jnp.asarray(padded[2]))) == g

    def test_int64_ids_build_the_same_layout(self):
        users, items, ratings = _skewed(3)
        a = als_ops.build_grouped_edges(users, items, ratings, 300)
        b = als_ops.build_grouped_edges(users.astype(np.int64),
                                        items.astype(np.int64), ratings, 300)
        assert all(x.tobytes() == y.tobytes() for x, y in zip(a, b))

    @pytest.mark.parametrize("pure", [False, True])
    def test_counts_feed_the_guard_and_reject_bad_ids(self, monkeypatch, pure):
        if pure:
            monkeypatch.setenv("OAP_MLLIB_TPU_PURE_PYTHON", "1")
        users, items, ratings = _skewed(4)
        counts = als_ops.count_edges(users, 300, 3)
        assert counts.dtype == np.int32 and counts.shape[1] == 300
        assert np.array_equal(counts.sum(axis=0), np.bincount(users, minlength=300))
        assert als_ops.padded_edges(counts, 16) == als_ops.grouped_padded_edges(
            users, 300, 16)
        with pytest.raises(ValueError):
            als_ops.count_edges(np.array([0, 300], np.int32), 300)
        with pytest.raises(ValueError):
            als_ops.count_edges(np.array([-1, 3], np.int32), 300)


class TestGroupBucket:
    def test_the_bucket_is_on_the_row_series_and_splits_into_its_blocks(self):
        for groups in (1, 255, 256, 257, 822_501, 927_730):
            g = als_ops.group_bucket(groups)
            assert g >= groups and g % 256 == 0 and g & (g - 1) == 0
            blocks = als_ops._grouped_block_count(g, 256, 10)
            assert g % blocks == 0
        assert als_ops.group_bucket(822_501) == als_ops.group_bucket(927_730) == 1 << 20
        set_config(shape_bucketing="off")
        assert als_ops.group_bucket(257) == 512  # exact, on the multiple

    def test_bucketed_groups_give_the_factors_of_the_unbucketed_program(self):
        x = _skewed(5)
        on = _fit(x, 300, 500)
        attrs = on.summary["timings"].root.node(BUILD_SPAN).attrs
        assert attrs["groups_user"] > attrs["padded_edges_user"] // attrs["group_size"][0]
        set_config(shape_bucketing="off")
        off = _fit(x, 300, 500)
        attrs = off.summary["timings"].root.node(BUILD_SPAN).attrs
        assert attrs["groups_item"] == -(
            -attrs["padded_edges_item"] // attrs["group_size"][1] // 256) * 256
        assert np.array_equal(on.user_factors_, off.user_factors_)
        assert np.array_equal(on.item_factors_, off.item_factors_)

    def test_the_walk_stops_behind_the_last_live_block(self, monkeypatch):
        """Several blocks a side: the bucketed program walks the live ones
        only and agrees with the exact-G program's scan."""
        monkeypatch.setattr(als_ops, "_GROUPED_BUDGET_ELEMS", 1 << 16)
        users, items, ratings = _skewed(6)
        exact = als_ops.build_grouped_edges(users, items, ratings, 300, 16)
        g = exact[0].shape[0]
        bucket = als_ops.group_bucket(g)
        assert als_ops._grouped_block_count(bucket, 16, 4) > 2
        padded = als_ops.build_grouped_edges(users, items, ratings, 300, 16,
                                             groups=bucket)
        y = jnp.asarray(np.random.default_rng(0).standard_normal((500, 4)), jnp.float32)
        want = als_ops.normal_eq_partials_grouped(
            *map(jnp.asarray, exact), y, 300, 40.0, True)
        live = als_ops.live_group_count(jnp.asarray(padded[2]))
        got = als_ops.normal_eq_partials_grouped(
            *map(jnp.asarray, padded), y, 300, 40.0, True, "f32", live)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-6, atol=1e-3)

    def test_a_warm_cache_serves_a_table_never_seen(self):
        first = _fit(_skewed(7), 300, 500)
        compiles = progcache.xla_compile_count()
        second = _fit(_skewed(8), 300, 500, seed=9)
        a, b = (m.summary["timings"].root.node(BUILD_SPAN).attrs
                for m in (first, second))
        assert a["padded_edges_user"] != b["padded_edges_user"]  # another table
        assert (a["groups_user"], a["groups_item"]) == (b["groups_user"], b["groups_item"])
        assert progcache.xla_compile_count() == compiles
        assert second.summary["progcache"]["misses"] == 0


class TestTheFit:
    def test_the_system_agrees_with_the_plain_reference(self, bench):
        _, cfg, _, ref = bench
        x = _skewed(9)
        assert np.bincount(x[1], minlength=500)[7] == 0 and (x[2] == 0).any()
        small = dict(cfg, users=300, items=500, rank=4, max_iter=3)
        set_config(als_kernel="auto")
        x0, y0 = ref.init_factors(small, 21)
        model = ALS(rank=4, max_iter=3, implicit_prefs=True, alpha=cfg["alpha"],
                    reg_param=cfg["reg_param"], num_user_blocks=1).fit(
            *x, n_users=300, n_items=500, init=(x0, y0))
        assert model.summary["als_kernel"] == "grouped"
        assert not model.item_factors_[7].any()  # nobody rated it
        result = {"user_factors": model.user_factors_,
                  "item_factors": model.item_factors_, "seed": 21}
        numbers = ref.judge(x, small, [result], 0)
        limits = cfg["limits"]
        assert set(numbers) == set(limits)
        assert not {n: v for n, v in numbers.items() if not v <= limits[n]}, numbers

    def test_the_span_tree_and_its_attributes(self):
        before = telemetry.snapshot().get("oap_fit_total", {})
        x = _skewed(10)
        model = _fit(x, 300, 500)
        root = model.summary["timings"].root
        flat = model.summary["timings"].as_dict()
        for path in ("table_convert", "table_convert/host_copy", BUILD_SPAN,
                     "table_convert/upload", "table_convert/upload/put",
                     "table_convert/upload/land", "als_iterations",
                     "als_iterations/fetch"):
            assert path in flat, path
        assert root.name == "als.fit"
        assert root.attrs["kernel"] == "grouped" and root.attrs["precision"] == "f32"
        assert root.node("table_convert/host_copy").attrs == {"copied_bytes": 0}
        build = root.node(BUILD_SPAN).attrs
        assert build["ratings"] == 20000 and build["threads"] >= 1
        counts = [als_ops.count_edges(x[0], 300), als_ops.count_edges(x[1], 500)]
        assert build["group_size"] == als_ops.group_sizes_for(
            counts, 4, membudget.als_grouped_room(300, 500, 4))
        assert build["group_size_by_mean"] == [als_ops.auto_group_size(20000, 300),
                                               als_ops.auto_group_size(20000, 500)]
        assert all(p <= m for p, m in zip(build["group_size"],
                                          build["group_size_by_mean"]))
        assert build["padded_edges_user"] == als_ops.grouped_padded_edges(
            x[0], 300, build["group_size"][0])
        assert build["padded_edges_item"] == als_ops.grouped_padded_edges(
            x[1], 500, build["group_size"][1])
        assert build["sheet_bytes"] == membudget.als_sheet_bytes(
            max(build["groups_user"], build["groups_item"]), 4) > 0
        upload = root.node("table_convert/upload").attrs
        slots = (build["groups_user"] * build["group_size"][0]
                 + build["groups_item"] * build["group_size"][1])
        assert upload["bytes"] == 12 * slots + 4 * (
            build["groups_user"] + build["groups_item"])
        assert upload["arrays"] == 8 and upload["pieces"] == 8
        assert root.node("table_convert/upload/put").attrs["bytes"] == upload["bytes"]
        assert root.node("als_iterations").attrs == {
            "iterations": 3, "solve_kernel": "xla", "rank": 4, "implicit": True,
            "gather_kernel": "xla", "gather_table_bytes": [0, 0]}
        assert root.node("als_iterations/fetch").attrs["bytes"] == (300 + 500) * 4 * 4
        after = telemetry.snapshot()["oap_fit_total"]
        assert sum(after.values()) == sum(before.values()) + 1

    def test_int32_ids_go_in_uncopied_and_int64_ids_are_cast_once(self):
        users, items, ratings = _skewed(11)
        got = ALS._validate_resolve(users, items, ratings, 300, 500)
        assert got[0] is users and got[1] is items and got[2] is ratings
        a = _fit((users, items, ratings), 300, 500)
        b = _fit((users.astype(np.int64), list(items), ratings), 300, 500)
        copied = b.summary["timings"].root.node("table_convert/host_copy").attrs
        assert copied == {"copied_bytes": 2 * 20000 * 4}
        assert np.array_equal(a.user_factors_, b.user_factors_)

    def test_the_plan_prices_the_padded_edges_the_fit_counted(self):
        model = _fit(_skewed(12), 300, 500)
        build = model.summary["timings"].root.node(BUILD_SPAN).attrs
        layouts = [(build["groups_user"], build["group_size"][0]),
                   (build["groups_item"], build["group_size"][1])]
        slots = sum(g * p for g, p in layouts)
        priced = membudget.plan_als(20000, 300, 500, 4, grouped=layouts)
        constant = membudget.plan_als(20000, 300, 500, 4)
        route = model.summary["route"]
        assert route["route"] == "in-memory"
        assert route["estimates"][0]["hbm_bytes"] == priced.estimates[0].hbm_bytes
        # the layouts' slots and the sheet of the side with more groups:
        # (4+1)(4+2) = 30 floats a group, as 32 sublanes and as one
        # 128-lane row
        assert build["sheet_bytes"] == max(g for g, _ in layouts) * (32 + 128) * 4
        # on the device a row of fewer than 128 slots takes 128 lanes
        lanes = sum(g * max(p, 128) for g, p in layouts)
        assert lanes > slots
        assert priced.estimates[0].hbm_bytes - constant.estimates[0].hbm_bytes == int(
            (12 * lanes + build["sheet_bytes"] - 2 * 20000 * 12 * 2.0) * 1.25)
        # the host holds the layouts, never the sheet
        assert priced.estimates[0].host_bytes == 12 * slots + 3 * 20000 * 8


class TestGroupWidthRule:
    """``als_ops.group_sizes_for``: the width of a grouped side from the
    degrees the fit has counted (ISSUE 39)."""

    @staticmethod
    def _cell_counts(bench, seed=1):
        _, cfg, adapter, _ = bench
        users, items, _ = adapter.make_data(cfg, cfg["rows_per_chip"], seed)
        return cfg, [als_ops.count_edges(users, cfg["users"]),
                     als_ops.count_edges(items, cfg["items"])]

    @pytest.mark.parametrize("degree", [250, 256, 200])
    def test_a_side_of_equal_degrees_keeps_the_widest(self, degree):
        counts = np.full((1, 4000), degree, np.int32)
        assert als_ops.auto_group_size(degree * 4000, 4000) == 256
        assert als_ops.group_sizes_for([counts], 10) == [256]

    def test_the_cells_laws_get_a_narrower_width_than_their_means(self, bench):
        cfg, counts = self._cell_counts(bench)
        sizes = als_ops.group_sizes_for(counts, cfg["rank"])
        by_mean = [als_ops.auto_group_size(cfg["rows_per_chip"], cfg[n])
                   for n in ("users", "items")]
        assert by_mean == [128, 128]  # means 100 and 75
        for c, p, m in zip(counts, sizes, by_mean):
            assert p in als_ops._GROUP_SIZES and p < m
            assert als_ops.padded_edges(c, p) < als_ops.padded_edges(c, m)

    def test_the_sides_choose_apart(self):
        flat = np.full((1, 2000), 250, np.int32)
        tail = np.minimum(
            10 + np.random.default_rng(0).lognormal(3.0, 1.5, 2000), 1e5
        ).astype(np.int32)[None, :]
        wide, narrow = als_ops.group_sizes_for([flat, tail], 10)
        assert wide == 256 and narrow < 256
        assert als_ops.group_sizes_for([tail, flat], 10) == [narrow, wide]

    def test_a_tie_goes_to_the_wider(self, monkeypatch):
        # nothing costs anything: every width ties
        for name in ("_SLOT_NS", "_LANE_SLOT_NS", "_BUCKET_GROUP_NS"):
            monkeypatch.setattr(als_ops, name, 0.0)
        counts = als_ops.count_edges(_skewed(14)[0], 300)
        assert als_ops.group_sizes_for([counts], 4) == [256]

    @pytest.mark.parametrize("slack", [1.0, 1.1, 1.5, 4.0])
    def test_the_plan_never_refuses_what_the_means_width_would_have_fitted(
            self, bench, slack):
        """A budget that just admits the mean's widths resident: the
        chosen widths are admitted too (the narrower width's sheet is
        larger, so without the bound the cheapest would not be)."""
        cfg, counts = self._cell_counts(bench)
        r, nnz, nu, ni = cfg["rank"], cfg["rows_per_chip"], cfg["users"], cfg["items"]

        def layouts(sizes):
            return [(als_ops.group_bucket(als_ops.padded_edges(c, p) // p), p)
                    for c, p in zip(counts, sizes)]

        by_mean = [als_ops.auto_group_size(nnz, nu), als_ops.auto_group_size(nnz, ni)]
        needs = membudget.plan_als(nnz, nu, ni, r, grouped=layouts(by_mean))
        budget = int(needs.estimates[0].hbm_bytes * slack) + 1
        set_config(memory_budget_hbm=str(budget))
        free = als_ops.group_sizes_for(counts, r)
        sizes = als_ops.group_sizes_for(
            counts, r, membudget.als_grouped_room(nu, ni, r))
        plan = membudget.plan_als(nnz, nu, ni, r, grouped=layouts(sizes))
        assert plan.route == "in-memory" and not plan.estimates[0].reject
        if slack == 1.0:
            # the unbounded choice would have been sent to the streamed route
            unbounded = membudget.plan_als(nnz, nu, ni, r, grouped=layouts(free))
            assert unbounded.route == "streamed" and sizes != free
        if slack == 4.0:
            assert sizes == free

    def test_where_nothing_fits_the_cheapest_runs_streamed(self, bench):
        cfg, counts = self._cell_counts(bench)
        assert als_ops.group_sizes_for(counts, cfg["rank"], 0) == (
            als_ops.group_sizes_for(counts, cfg["rank"]))

    @pytest.mark.parametrize("width", [256, 128, 64, 8])
    def test_an_explicit_group_size_is_obeyed(self, width):
        users, items, ratings = _skewed(15)
        layout = als_ops.build_grouped_edges(items, users, ratings, 500, width)
        assert layout[0].shape[1] == width
        counts = als_ops.count_edges(items, 500)
        assert layout[0].size == als_ops.padded_edges(counts, width) == (
            als_ops.grouped_padded_edges(items, 500, width))
        assert int(layout[2].sum()) == len(items)

    @pytest.fixture(scope="class")
    def by_width(self, bench):
        """One table fitted at P = 256 / 128 / 64 on both sides (the rule
        left one candidate), from the same initial factors."""
        _, cfg, _, ref = bench
        x = _skewed(16)
        small = dict(cfg, users=300, items=500, rank=4, max_iter=3)
        init = ref.init_factors(small, 33)
        fits = {}
        for width in (256, 128, 64):
            with pytest.MonkeyPatch.context() as patch:
                patch.setattr(als_ops, "_GROUP_SIZES", (width,))
                set_config(als_kernel="grouped")
                fits[width] = ALS(
                    rank=4, max_iter=3, implicit_prefs=True, alpha=cfg["alpha"],
                    reg_param=cfg["reg_param"], num_user_blocks=1,
                ).fit(*x, n_users=300, n_items=500, init=init)
        return x, small, fits

    @pytest.mark.parametrize("width", [256, 128, 64])
    def test_every_width_counts_each_rating_once_and_holds_the_reference(
            self, bench, by_width, width):
        _, cfg, _, ref = bench
        x, small, fits = by_width
        model = fits[width]
        build = model.summary["timings"].root.node(BUILD_SPAN).attrs
        assert model.summary["als_kernel"] == "grouped"
        assert build["group_size"] == [width, width]
        for dst, n_dst in ((x[0], 300), (x[1], 500)):
            layout = als_ops.build_grouped_edges(
                dst, x[2], x[2], n_dst, width)
            assert int(layout[2].sum()) == len(dst) == 20000
        for got, want in ((model.user_factors_, fits[256].user_factors_),
                          (model.item_factors_, fits[256].item_factors_)):
            # another order of summation, no other arithmetic: a fifth of
            # what the cell allows three iterations' replay (3e-5 here)
            assert np.linalg.norm(got - want) <= 2e-4 * np.linalg.norm(want)
        result = {"user_factors": model.user_factors_,
                  "item_factors": model.item_factors_, "seed": 33}
        numbers = ref.judge(x, small, [result], 0)
        assert numbers["half_step_gap"] <= cfg["limits"]["half_step_gap"], numbers
        assert numbers["shape_gap"] == 0


class TestUploadArrays:
    def test_pieces_of_a_quarter_of_the_bound_written_in_place(self, monkeypatch):
        monkeypatch.setattr(table_mod, "_UPLOAD_PIECE_BYTES", 4096)
        rng = np.random.default_rng(0)
        hosts = [rng.integers(0, 9, (40, 16)).astype(np.int32),
                 rng.standard_normal((40, 16)).astype(np.float32),
                 np.arange(40, dtype=np.int32)]
        sharding = jax.sharding.SingleDeviceSharding(jax.local_devices()[0])
        from oap_mllib_tpu.telemetry import spans
        from oap_mllib_tpu.utils.timing import Timings

        timings = Timings("als.fit")
        with timings.span("table_convert"):
            out = table_mod.upload_arrays(hosts, sharding)
        for host, dev in zip(hosts, out):
            assert dev.dtype == host.dtype and np.array_equal(np.asarray(dev), host)
        node = timings.root.node("table_convert/upload")
        # 1 KiB a piece = 16 rows of 64 bytes: 3 pieces an array, the ids
        # whole; the third piece is rows 24-40, a whole piece over 8 rows
        # the second sent
        assert node.attrs == {"bytes": 2 * 48 * 64 + 160,
                              "device_bytes": sum(h.nbytes for h in hosts),
                              "pieces": 7, "arrays": 3}
        put = timings.root.node("table_convert/upload/put")
        assert put.count == 7 and put.attrs["bytes"] == node.attrs["bytes"]
        assert timings.root.node("table_convert/upload/launch").count == 2 + 6
        assert spans.current_span() is None

    # 32 rows of 16 int32 / float32 slots a piece (8 KiB in flight)
    PIECE_ROWS, WIDTH = 32, 16

    def _layout(self, monkeypatch, live, seed=0):
        """(a grouped side of exactly ``live`` groups built at its bucket
        with pieces of ``PIECE_ROWS`` rows, the bucket, the padded
        layout: the same with the bucket's pad groups on the host)."""
        monkeypatch.setattr(table_mod, "_UPLOAD_PIECE_BYTES",
                            4 * self.PIECE_ROWS * self.WIDTH * 4)
        rng = np.random.default_rng(seed)
        # one group a destination: 1 to WIDTH edges each
        dst = np.repeat(np.arange(live, dtype=np.int32),
                        rng.integers(1, self.WIDTH + 1, live))
        rng.shuffle(dst)
        src = rng.integers(0, 50, len(dst)).astype(np.int32)
        conf = rng.integers(0, 5, len(dst)).astype(np.float32) * 25.0
        bucket = als_ops.group_bucket(live)
        layout = als_ops.build_grouped_edges(dst, src, conf, live, self.WIDTH,
                                             groups=bucket)
        exact = als_ops.build_grouped_edges(dst, src, conf, live, self.WIDTH)
        assert exact[0].shape[0] == live < bucket
        padded = [np.zeros((bucket, self.WIDTH), a.dtype) for a in exact[:3]]
        for pad, a in zip(padded, exact):
            pad[:live] = a
        padded.append(np.full((bucket,), live - 1, np.int32))
        padded[3][:live] = exact[3]
        return layout, bucket, padded

    def _upload(self, layout, bucket):
        from oap_mllib_tpu.utils.timing import Timings

        timings = Timings("als.fit")
        with timings.span("table_convert"):
            out = table_mod.upload_arrays(
                layout, jax.sharding.SingleDeviceSharding(jax.local_devices()[0]),
                rows=[bucket] * 4)
        return out, timings.root.node("table_convert/upload").attrs

    @pytest.mark.parametrize("live,held", [
        (20, 32),   # under one piece: one piece's rows, zeros behind them
        (64, 64),   # on a multiple of the piece
        (65, 65),   # one row past it: the last piece starts at row 33
    ])
    def test_the_live_groups_go_up_into_the_buckets_zeros(
            self, monkeypatch, live, held):
        layout, bucket, padded = self._layout(monkeypatch, live)
        assert [a.shape[0] for a in layout] == [held] * 3 + [bucket]
        out, attrs = self._upload(layout, bucket)
        for dev, want in zip(out, padded):
            assert dev.dtype == want.dtype and dev.shape == want.shape
            assert np.asarray(dev).tobytes() == want.tobytes()
        assert int(als_ops.live_group_count(out[2])) == live
        pieces = -(-held // self.PIECE_ROWS)
        piece_bytes = self.PIECE_ROWS * self.WIDTH * 4
        assert attrs == {
            "bytes": 3 * pieces * piece_bytes + bucket * 4,
            "device_bytes": sum(a.nbytes for a in padded),
            "pieces": 3 * pieces + 1, "arrays": 4,
        }

    def test_another_live_count_in_the_bucket_compiles_nothing(
            self, monkeypatch, bench):
        adapter = bench[2]
        first, bucket, _ = self._layout(monkeypatch, 65, seed=1)
        second, bucket_2, padded = self._layout(monkeypatch, 90, seed=2)
        assert bucket == bucket_2
        self._upload(first, bucket)
        compiles = progcache.xla_compile_count()
        out, attrs = self._upload(second, bucket)
        assert progcache.xla_compile_count() == compiles
        assert all(np.asarray(d).tobytes() == w.tobytes()
                   for d, w in zip(out, padded))
        assert attrs["pieces"] == 3 * 3 + 1 and attrs["bytes"] < attrs["device_bytes"]
        piece_bytes = self.PIECE_ROWS * self.WIDTH * 4
        for limit in (None, piece_bytes):
            cfg = bench[1] if limit is None else dict(
                bench[1], expect_upload={"piece_bytes_max": limit})
            assert adapter.upload_breach(cfg, attrs) is None
        assert adapter.upload_breach(
            dict(bench[1], expect_upload={"piece_bytes_max": piece_bytes // 2}),
            attrs) is not None

    def test_a_fit_of_live_groups_gives_the_host_padded_layouts_factors(
            self, monkeypatch):
        """A whole fit on the grouped route, its layouts uploaded in
        pieces of 32 groups: the same factors, bit for bit, as when the
        bucket's pad groups go up from the host."""
        monkeypatch.setattr(als_ops, "_GROUP_SIZES", (self.WIDTH,))
        monkeypatch.setattr(table_mod, "_UPLOAD_PIECE_BYTES",
                            4 * self.PIECE_ROWS * self.WIDTH * 4)
        set_config(als_kernel="grouped")
        x = _skewed(17)
        live = _fit(x, 300, 500)
        build = als_ops.build_grouped_edges

        def padded_on_the_host(dst, src, conf, n_dst, p, *, groups=0, **kw):
            layout = build(dst, src, conf, n_dst, p, groups=groups, **kw)
            pad = [np.zeros((groups, p), a.dtype) for a in layout[:3]]
            for full, a in zip(pad, layout):
                full[:a.shape[0]] = a
            return (*pad, layout[3])

        monkeypatch.setattr(als_ops, "build_grouped_edges", padded_on_the_host)
        padded = _fit(x, 300, 500)
        up = [m.summary["timings"].root.node("table_convert/upload").attrs
              for m in (live, padded)]
        assert up[0]["device_bytes"] == up[1]["device_bytes"] == up[1]["bytes"]
        assert up[0]["bytes"] < up[0]["device_bytes"] and up[0]["pieces"] > 8
        for got, want in ((live.user_factors_, padded.user_factors_),
                          (live.item_factors_, padded.item_factors_)):
            assert got.tobytes() == want.tobytes()


class TestTheDeploymentsShare:
    """Two user blocks (even and odd ids, renumbered densely within their
    block), item factors replicated: what one chip computes of the whole."""

    @pytest.fixture(scope="class")
    def blocks(self):
        users, items, ratings = _skewed(13, n_users=300)
        rng = np.random.default_rng(1)
        x = rng.standard_normal((300, 4)).astype(np.float32)
        y = rng.standard_normal((500, 4)).astype(np.float32)
        parts = []
        for b in (0, 1):
            own = users % 2 == b
            parts.append((users[own] // 2, items[own], ratings[own], x[b::2]))
        return (users, items, ratings), x, y, parts

    @staticmethod
    def _partials(dst, src, ratings, n_dst, factors):
        layout = als_ops.build_grouped_edges(dst, src, ratings, n_dst, 16)
        a, b, n = als_ops.normal_eq_partials_grouped(
            *map(jnp.asarray, layout), jnp.asarray(factors), n_dst, 40.0, True)
        return np.asarray(a, np.float64), np.asarray(b, np.float64), np.asarray(n)

    def test_the_blocks_item_side_partials_add_up_to_the_whole_tables(self, blocks):
        (users, items, ratings), x, _, parts = blocks
        whole = self._partials(items, users, ratings, 500, x)
        summed = [sum(t) for t in zip(*(
            self._partials(i_b, u_b, r_b, 500, x_b) for u_b, i_b, r_b, x_b in parts))]
        for got, want in zip(summed, whole):
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-2)
        assert np.array_equal(summed[2], whole[2])  # the counts exactly

    def test_a_blocks_user_update_is_the_wholes_rows_for_its_users(self, blocks):
        (users, items, ratings), _, y, parts = blocks
        eye = jnp.eye(4, dtype=jnp.float32)
        gram = jnp.asarray(y).T @ jnp.asarray(y)

        def update(u, i, r, n):
            a, b, n_reg = (jnp.asarray(t, jnp.float32)
                           for t in self._partials(u, i, r, n, y))
            return np.asarray(als_ops.regularized_solve(a, b, n_reg, 0.1, eye, gram))

        whole = update(users, items, ratings, 300)
        for b, (u_b, i_b, r_b, _) in enumerate(parts):
            np.testing.assert_allclose(update(u_b, i_b, r_b, 150), whole[b::2],
                                       rtol=1e-5, atol=1e-6)
