"""Memory-budget planner tests (utils/membudget.py — ISSUE 12).

Covers the tentpole contracts: the budget grammar and detection
fallbacks, the per-algorithm decision table (footprint x budget ->
route), summary.route exposure with every candidate's estimate and
rejection reason, strict-mode BudgetError, pin: overrides, the
estimate-vs-actual bytes-staged cross-check on real fits, and the
oap_route_* metric surface.
"""

import numpy as np
import pytest

from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.data.stream import ChunkSource
from oap_mllib_tpu.telemetry import metrics as tm
from oap_mllib_tpu.utils import membudget as mb


@pytest.fixture(autouse=True)
def _clean_budgets():
    set_config(
        memory_budget_hbm="unlimited", memory_budget_host="unlimited",
        scale_policy="auto",
    )
    mb.reset_calibration()
    yield
    set_config(
        memory_budget_hbm="", memory_budget_host="", scale_policy="auto"
    )
    mb.reset_calibration()


def _blobs(rng, n=600, d=6):
    proto = rng.normal(size=(3, d)).astype(np.float32) * 4.0
    return (proto[rng.integers(3, size=n)]
            + rng.normal(size=(n, d)).astype(np.float32) * 0.2)


class TestBudgetGrammar:
    def test_parse_sizes(self):
        assert mb.parse_budget("") is None  # auto-detect
        assert mb.parse_budget("0") == 0  # unbounded
        assert mb.parse_budget("unlimited") == 0
        assert mb.parse_budget("1024") == 1024
        assert mb.parse_budget("4K") == 4096
        assert mb.parse_budget("512m") == 512 << 20
        assert mb.parse_budget("2G") == 2 << 30
        assert mb.parse_budget("1.5g") == int(1.5 * (1 << 30))

    def test_typo_raises(self):
        with pytest.raises(ValueError, match="K/M/G/T"):
            mb.parse_budget("12Q")
        with pytest.raises(ValueError, match=">= 0"):
            mb.parse_budget("-5M")

    def test_detection_fallbacks_never_raise(self):
        assert mb.detect_hbm_bytes() >= 0
        assert mb.detect_host_bytes() >= 0

    def test_budgets_resolve_sources(self):
        set_config(memory_budget_hbm="64M", memory_budget_host="")
        b = mb.Budgets.resolve()
        assert b.hbm == 64 << 20 and b.hbm_source == "config"
        assert b.host_source == "detected"

    def test_scale_policy_grammar(self):
        set_config(scale_policy="strict")
        assert mb.scale_policy_cfg() == ("strict", None)
        set_config(scale_policy="pin:streamed")
        assert mb.scale_policy_cfg() == ("pin", "streamed")
        set_config(scale_policy="pin:bogus")
        with pytest.raises(ValueError, match="pin route"):
            mb.scale_policy_cfg()
        set_config(scale_policy="sometimes")
        with pytest.raises(ValueError, match="scale_policy"):
            mb.scale_policy_cfg()


# footprint x budget -> route: the planner's decision table, pinned.
# Budgets are synthetic so the decisions are deterministic everywhere.
KMEANS_TABLE = [
    # (n, d, k, hbm_budget, expected_route)
    (1_000, 8, 3, "unlimited", mb.ROUTE_IN_MEMORY),
    (1_000_000, 256, 1000, "unlimited", mb.ROUTE_CHUNKED),
    (200_000, 64, 8, "120M", mb.ROUTE_STREAMED),  # table > budget
    (1_000, 8, 3, "1G", mb.ROUTE_IN_MEMORY),
]


class TestDecisionTable:
    @pytest.mark.parametrize("n,d,k,budget,route", KMEANS_TABLE)
    def test_kmeans_routes(self, n, d, k, budget, route):
        set_config(memory_budget_hbm=budget)
        from oap_mllib_tpu.ops.kmeans_ops import auto_row_chunks

        plan = mb.plan_kmeans(
            n, d, k, row_chunks_hint=auto_row_chunks(n, k)
        )
        assert plan.route == route, plan.as_dict()

    def test_pca_routes(self):
        plan = mb.plan_pca(2_000, 16)
        assert plan.route == mb.ROUTE_IN_MEMORY
        set_config(memory_budget_hbm="100M")
        plan = mb.plan_pca(2_000_000, 128)
        assert plan.route == mb.ROUTE_STREAMED
        rejected = plan.estimate_for(mb.ROUTE_IN_MEMORY)
        assert "hbm estimate" in rejected.reject

    def test_als_routes(self):
        plan = mb.plan_als(10_000, 500, 300, 8)
        assert plan.route == mb.ROUTE_IN_MEMORY
        # grouped layouts past the budget -> streamed kernels
        set_config(memory_budget_hbm="90M")
        plan = mb.plan_als(50_000_000, 100_000, 50_000, 16)
        assert plan.route == mb.ROUTE_STREAMED
        # a mesh world plans the block route
        plan = mb.plan_als(10_000, 500, 300, 8, world=4)
        assert plan.route == mb.ROUTE_STREAMED_BLOCK

    def test_source_inputs_stream_naturally(self):
        plan = mb.plan_kmeans(
            1_000, 8, 3, source_backing="memory", chunk_rows=128
        )
        assert plan.route == mb.ROUTE_STREAMED
        assert plan.natural == mb.ROUTE_STREAMED
        assert not plan.degraded_scale

    def test_over_budget_is_recorded_not_silent(self):
        set_config(memory_budget_hbm="1M")
        plan = mb.plan_kmeans(1_000_000, 256, 100)
        assert plan.route == mb.ROUTE_STREAMED  # most scale-capable
        assert plan.over_budget
        assert all(e.reject for e in plan.estimates)

    def test_budget_narrows_streamed_chunks(self):
        set_config(memory_budget_hbm="32M")
        plan = mb.plan_kmeans(10_000_000, 256, 100)
        from oap_mllib_tpu.data.stream import DEFAULT_CHUNK_ROWS

        assert plan.chunk_rows < DEFAULT_CHUNK_ROWS
        from oap_mllib_tpu.utils.resilience import OOM_CHUNK_FLOOR_ROWS

        assert plan.chunk_rows >= OOM_CHUNK_FLOOR_ROWS


class TestPerDevicePricing:
    """On a mesh the HBM budget is ONE device's, and so is the price: its
    row shard plus the buffers of the Lloyd that runs over it (a walk or
    chunked scan of the shard — no whole-table sheet)."""

    ROWS, D, K = 8_388_608, 256, 1000  # the four-chip benchmark cell

    def _chunked(self, n, shards):
        from oap_mllib_tpu.ops.kmeans_ops import auto_row_chunks

        plan = mb.plan_kmeans(
            n, self.D, self.K,
            row_chunks_hint=auto_row_chunks(-(-n // shards), self.K),
            shards=shards,
        )
        assert plan.natural == mb.ROUTE_CHUNKED
        return plan, plan.estimate_for(mb.ROUTE_CHUNKED)

    def test_a_device_of_four_holds_a_quarter_table_and_the_buffers(self):
        from oap_mllib_tpu.ops.kmeans_ops import SCORE_BUDGET_ELEMS

        _, four = self._chunked(self.ROWS, 4)
        shard = (self.ROWS // 4) * (self.D + 1) * 4  # rows + mask lane, f32
        buffers = (
            SCORE_BUDGET_ELEMS * 4  # the live (chunk, k) score block
            + 3 * self.K * self.D * 4 + mb._PROGRAM_BYTES  # centres, sums
        )
        assert four.hbm_bytes == int((shard + buffers) * mb._OVERHEAD)
        # the host still holds the whole array
        assert four.host_bytes == self.ROWS * self.D * 4

    def test_one_device_prices_as_before(self):
        _, whole = self._chunked(self.ROWS // 4, 1)
        _, four = self._chunked(self.ROWS, 4)
        # a quarter of the rows on one device IS a device's share of four
        assert whole.hbm_bytes == four.hbm_bytes
        default = mb.plan_kmeans(self.ROWS // 4, self.D, self.K,
                                 row_chunks_hint=64)
        assert default.estimate_for(mb.ROUTE_CHUNKED).hbm_bytes == (
            whole.hbm_bytes
        )

    def test_the_budget_is_held_against_the_shard(self):
        # 16 GB a device: the whole 8.6 GB table fits one device's budget
        # either way, four times the rows only as four shards
        set_config(memory_budget_hbm="16G")
        plan, _ = self._chunked(4 * self.ROWS, 4)
        assert plan.route == mb.ROUTE_CHUNKED
        from oap_mllib_tpu.ops.kmeans_ops import auto_row_chunks

        alone = mb.plan_kmeans(
            4 * self.ROWS, self.D, self.K,
            row_chunks_hint=auto_row_chunks(4 * self.ROWS, self.K),
        )
        assert alone.route == mb.ROUTE_STREAMED


class TestPolicy:
    def test_strict_raises_instead_of_degrading(self):
        set_config(memory_budget_hbm="120M", scale_policy="strict")
        with pytest.raises(mb.BudgetError, match="strict"):
            mb.plan_kmeans(200_000, 64, 8)

    def test_strict_passes_when_natural_fits(self):
        set_config(scale_policy="strict")
        plan = mb.plan_kmeans(1_000, 8, 3)
        assert plan.route == mb.ROUTE_IN_MEMORY

    def test_budget_error_names_candidates(self):
        set_config(memory_budget_hbm="120M", scale_policy="strict")
        with pytest.raises(mb.BudgetError, match="in-memory.*hbm"):
            mb.plan_kmeans(200_000, 64, 8)

    def test_pin_overrides_budget(self):
        set_config(memory_budget_hbm="1", scale_policy="pin:in-memory")
        plan = mb.plan_kmeans(10_000, 16, 4)
        assert plan.route == mb.ROUTE_IN_MEMORY and plan.forced

    def test_pin_streams_small_fits(self):
        set_config(scale_policy="pin:streamed")
        plan = mb.plan_kmeans(100, 4, 2)
        assert plan.route == mb.ROUTE_STREAMED

    def test_pin_inapplicable_route_raises(self):
        set_config(scale_policy="pin:streamed-block")
        with pytest.raises(ValueError, match="does not apply"):
            mb.plan_kmeans(100, 4, 2)

    def test_downgrade_strict_vs_auto(self):
        plan = mb.plan_kmeans(
            1_000, 8, 3, source_backing="memory", chunk_rows=128
        )
        set_config(scale_policy="strict")
        with pytest.raises(mb.BudgetError, match="downgrading"):
            plan.downgrade(mb.ROUTE_IN_MEMORY, "test downgrade")
        set_config(scale_policy="auto")
        plan.downgrade(mb.ROUTE_IN_MEMORY, "test downgrade")
        assert plan.route == mb.ROUTE_IN_MEMORY
        assert plan.downgrades and "test downgrade" in plan.downgrades[0]


class TestFitIntegration:
    """summary.route on real fits: decision + inputs, strict raising at
    fit entry, pin overrides actually changing the executed route."""

    def test_kmeans_summary_route(self, rng):
        from oap_mllib_tpu.models.kmeans import KMeans

        m = KMeans(k=3, seed=1, max_iter=2).fit(_blobs(rng))
        r = m.summary.route
        assert r["route"] == mb.ROUTE_IN_MEMORY
        assert r["policy"] == "auto"
        assert {e["route"] for e in r["estimates"]} == {
            mb.ROUTE_IN_MEMORY, mb.ROUTE_CHUNKED, mb.ROUTE_STREAMED
        }
        assert r["budgets"]["hbm_source"] == "config"

    def test_budget_forces_array_fit_onto_streamed(self, rng):
        from oap_mllib_tpu.models.kmeans import KMeans

        x = _blobs(rng)
        baseline = KMeans(k=3, seed=1, max_iter=25).fit(x)
        set_config(memory_budget_hbm="3M")
        m = KMeans(k=3, seed=1, max_iter=25).fit(x)
        assert m.summary.route["route"] == mb.ROUTE_STREAMED
        assert m.summary.route["degraded_scale"] is True
        assert getattr(m.summary, "streamed", False)
        # the streamed route converges to the same optimum on blobs
        # (init RNG streams legitimately differ: reservoir vs in-memory)
        np.testing.assert_allclose(
            m.summary.training_cost, baseline.summary.training_cost,
            rtol=1e-4,
        )

    def test_strict_raises_at_fit_entry(self, rng):
        from oap_mllib_tpu.models.kmeans import KMeans

        set_config(memory_budget_hbm="3M", scale_policy="strict")
        with pytest.raises(mb.BudgetError, match="strict"):
            KMeans(k=3, seed=1, max_iter=2).fit(_blobs(rng))

    def test_pin_streamed_executes_streamed(self, rng):
        from oap_mllib_tpu.models.kmeans import KMeans

        set_config(scale_policy="pin:streamed")
        m = KMeans(k=3, seed=1, max_iter=2).fit(_blobs(rng))
        assert m.summary.route["route"] == mb.ROUTE_STREAMED
        assert m.summary.route["forced"] is True
        assert getattr(m.summary, "streamed", False)

    def test_pca_and_als_summaries_carry_route(self, rng):
        from oap_mllib_tpu.models.als import ALS
        from oap_mllib_tpu.models.pca import PCA

        p = PCA(k=2).fit(_blobs(rng))
        assert p.summary["route"]["route"] == mb.ROUTE_IN_MEMORY
        u = rng.integers(30, size=300)
        i = rng.integers(20, size=300)
        r = rng.random(300).astype(np.float32)
        a = ALS(rank=3, max_iter=1, seed=3).fit(u, i, r)
        # the suite mesh has 8 virtual devices -> the block route is
        # both natural and chosen; a 1-device world fits in-memory
        from oap_mllib_tpu.parallel.mesh import get_mesh

        mesh = get_mesh()
        expected = (
            mb.ROUTE_STREAMED_BLOCK
            if mesh.shape[mesh.axis_names[0]] > 1 else mb.ROUTE_IN_MEMORY
        )
        assert a.summary["route"]["route"] == expected
        assert a.summary["route"]["natural"] == expected

    def test_scale_policy_typo_raises_at_fit(self, rng):
        from oap_mllib_tpu.models.kmeans import KMeans

        set_config(scale_policy="bogus")
        with pytest.raises(ValueError, match="scale_policy"):
            KMeans(k=2, max_iter=1).fit(_blobs(rng))

    def test_route_span_node_annotated(self, rng):
        from oap_mllib_tpu.models.kmeans import KMeans

        m = KMeans(k=3, seed=1, max_iter=2).fit(_blobs(rng))
        route_span = m.summary.timings.root.node("route")
        assert route_span.attrs["route"] == m.summary.route["route"]


class TestCalibration:
    def test_estimate_vs_actual_cross_check_on_real_fit(self, rng):
        """A streamed fit records the observed bytes/row next to the
        planner's estimate, and the two agree within the calibration
        clamp (the estimate is analytic, not a guess)."""
        from oap_mllib_tpu.models.kmeans import KMeans

        x = _blobs(rng, n=512, d=6)
        m = KMeans(k=3, seed=1, max_iter=3).fit(
            ChunkSource.from_array(x, chunk_rows=128)
        )
        r = m.summary.route
        assert r["actual_bytes_staged"] > 0
        assert r["staged_bytes_per_row"] > 0
        ratio = r["staged_bytes_per_row"] / r["estimated_bytes_per_row"]
        assert 0.25 <= ratio <= 4.0
        assert 0.25 <= r["calibration"] <= 4.0
        # the EMA moved off 1.0 toward the observation
        assert mb.calibration_factor("kmeans") == pytest.approx(
            1.0 + 0.3 * (max(min(ratio, 4.0), 0.25) - 1.0), rel=1e-6
        )

    def test_calibration_scales_next_plan(self):
        mb._note_calibration("kmeans", 100.0, 200.0)  # ratio 2 -> EMA 1.3
        f = mb.calibration_factor("kmeans")
        assert f == pytest.approx(1.3)
        lo = mb.plan_kmeans(1_000, 8, 3, source_backing="memory",
                            chunk_rows=128)
        mb.reset_calibration()
        base = mb.plan_kmeans(1_000, 8, 3, source_backing="memory",
                              chunk_rows=128)
        est_cal = lo.estimate_for(mb.ROUTE_STREAMED).hbm_bytes
        est_base = base.estimate_for(mb.ROUTE_STREAMED).hbm_bytes
        assert est_cal == pytest.approx(est_base * f, rel=0.01)


class TestMetricsSurface:
    def test_route_metrics_fire(self, rng):
        from oap_mllib_tpu.models.kmeans import KMeans

        before = tm.family_total("oap_route_decisions_total")
        KMeans(k=3, seed=1, max_iter=1).fit(_blobs(rng))
        assert tm.family_total("oap_route_decisions_total") == before + 1

    def test_spill_metric_fires(self, rng):
        from oap_mllib_tpu.models.kmeans import KMeans
        from oap_mllib_tpu.utils import faults

        set_config(fault_spec="prefetch.stage:oomhost=1",
                   retry_backoff=0.001)
        faults.reset()
        before = tm.family_total("oap_route_spills_total")
        KMeans(k=3, seed=1, max_iter=2).fit(
            ChunkSource.from_array(_blobs(rng), chunk_rows=128)
        )
        assert tm.family_total("oap_route_spills_total") == before + 1
        set_config(fault_spec="")
        faults.reset()


class TestBeyondHostBudget:
    """The ISSUE 12 acceptance leg: a dataset whose STAGED footprint
    exceeds the configured host-RAM budget fits end-to-end from a
    disk-backed ChunkSource through the prefetch pipeline on all three
    estimators — parity <= 1e-5 vs the in-memory route on identical
    data, summary.route naming the decision and its inputs — and strict
    mode does NOT raise (the disk route genuinely fits the budget)."""

    def _make(self, rng, tmp_path):
        # 40k x 8 f32 = 1.28 MB dense: past the synthetic 1 MB host
        # budget, trivially within O(chunk) when disk-backed
        proto = rng.normal(size=(3, 8)).astype(np.float32) * 4.0
        x = (proto[rng.integers(3, size=40_000)]
             + rng.normal(size=(40_000, 8)).astype(np.float32) * 0.2)
        path = str(tmp_path / "big.npy")
        np.save(path, x)
        return x, path

    def test_kmeans_pca_als_fit_from_disk_under_host_budget(
        self, rng, tmp_path
    ):
        from oap_mllib_tpu.models.als import ALS
        from oap_mllib_tpu.models.kmeans import KMeans
        from oap_mllib_tpu.models.pca import PCA

        x, path = self._make(rng, tmp_path)
        km_mem = KMeans(k=3, seed=5, max_iter=15).fit(x)
        pca_mem = PCA(k=2).fit(x)
        u = rng.integers(50, size=3000).astype(np.float64)
        i = rng.integers(40, size=3000).astype(np.float64)
        r = rng.random(3000)
        tri = np.stack([u, i, r], axis=1)
        tri_path = str(tmp_path / "tri.npy")
        np.save(tri_path, tri)
        als_mem = ALS(rank=3, max_iter=2, seed=3).fit(
            u.astype(np.int64), i.astype(np.int64), r.astype(np.float32)
        )

        set_config(memory_budget_host="1M", scale_policy="strict")
        km = KMeans(k=3, seed=5, max_iter=15).fit(
            ChunkSource.from_npy(path, chunk_rows=4096)
        )
        assert km.summary.route["route"] == mb.ROUTE_STREAMED
        assert km.summary.route["budgets"]["host"] == 1 << 20
        np.testing.assert_allclose(
            km.summary.training_cost, km_mem.summary.training_cost,
            rtol=1e-5,
        )
        pca = PCA(k=2).fit(ChunkSource.from_npy(path, chunk_rows=4096))
        assert pca.summary["route"]["route"] == mb.ROUTE_STREAMED
        np.testing.assert_allclose(
            np.abs(pca.components_), np.abs(pca_mem.components_),
            atol=1e-5,
        )
        np.testing.assert_allclose(
            pca.explained_variance_, pca_mem.explained_variance_,
            atol=1e-5,
        )
        set_config(scale_policy="auto")  # ALS ingest keeps host O(nnz):
        # the triples materialize to host arrays (executor-partition
        # semantics), so strict under a 1 MB host budget rightly refuses
        als = ALS(rank=3, max_iter=2, seed=3).fit(
            ChunkSource.from_npy(tri_path, chunk_rows=1024)
        )
        assert als.summary["route"]["route"] in (
            mb.ROUTE_STREAMED, mb.ROUTE_STREAMED_BLOCK
        )
        np.testing.assert_allclose(
            als.user_factors_, als_mem.user_factors_, atol=1e-5,
            rtol=1e-5,
        )
