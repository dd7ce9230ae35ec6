"""ISSUE 17 autotuner + double-buffered-walk tests.

The tuner's determinism contract is CACHE-mediated, not timing-mediated:
a sweep's winner persists under ``Config.tuning_cache_dir`` and every
later resolution (same process or a fresh one) reads it back — so the
tests assert cache behavior and geometry identity, never wall clocks.
The kernel-geometry legs pin the load-bearing invariant instead: every
(tile_rows, depth, batch) choice routes through the same per-tile math,
so geometry may move overlap but never a result bit (K-Means/ALS exact,
PCA within 1e-6 for the XLA-walk tile order).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.ops.pallas import autotune


@pytest.fixture(autouse=True)
def _clean_tuning():
    autotune.clear()
    set_config(tuning="auto", tuning_cache_dir="")
    yield
    autotune.clear()
    set_config(tuning="auto", tuning_cache_dir="")


# ---------------------------------------------------------------------------
# mode parsing / validation
# ---------------------------------------------------------------------------


class TestParseMode:
    def test_plain_modes(self):
        for m in autotune.MODES:
            assert autotune.parse_mode(m) == (m, None)

    def test_typo_raises(self):
        with pytest.raises(ValueError, match="tuning"):
            autotune.parse_mode("onn")

    def test_pin_parses(self):
        mode, pins = autotune.parse_mode(
            'pin:{"kmeans": {"tile_rows": 1024}}'
        )
        assert mode == "pin"
        assert pins == {"kmeans": {"tile_rows": 1024}}

    def test_pin_bad_json_raises(self):
        with pytest.raises(ValueError, match="JSON"):
            autotune.parse_mode("pin:{nope")

    def test_pin_unknown_kernel_raises(self):
        with pytest.raises(ValueError, match="kmean"):
            autotune.parse_mode('pin:{"kmean": {"tile_rows": 512}}')

    def test_pin_unknown_knob_raises(self):
        with pytest.raises(ValueError, match="tile_row"):
            autotune.parse_mode('pin:{"kmeans": {"tile_row": 512}}')

    def test_pin_non_integer_raises(self):
        with pytest.raises(ValueError, match="integer"):
            autotune.parse_mode('pin:{"kmeans": {"tile_rows": "big"}}')

    def test_typo_raises_at_fit_entry(self, rng):
        """The repo's dispatch-knob contract: a Config.tuning typo must
        raise at fit entry (utils/dispatch.should_accelerate), never
        silently tune nothing."""
        from oap_mllib_tpu.models.kmeans import KMeans

        set_config(tuning="onn")
        x = rng.normal(size=(64, 4)).astype(np.float32)
        with pytest.raises(ValueError, match="tuning"):
            KMeans(k=2, init_mode="random", max_iter=1).fit(x)


# ---------------------------------------------------------------------------
# shape buckets
# ---------------------------------------------------------------------------


class TestShapeBucket:
    def test_rounds_up_to_pow2(self):
        assert autotune.shape_bucket(3) == (4,)
        assert autotune.shape_bucket(129, 256) == (256, 256)
        assert autotune.shape_bucket(1) == (1,)

    def test_nearby_shapes_share_a_bucket(self):
        assert autotune.shape_bucket(100, 33) == autotune.shape_bucket(
            65, 64
        )


# ---------------------------------------------------------------------------
# the resolve ladder
# ---------------------------------------------------------------------------


def _sweep_count(kernel):
    from oap_mllib_tpu.telemetry import metrics as tm

    return tm.counter("oap_tuning_sweeps_total", {"kernel": kernel}).value


class TestResolveLadder:
    def test_unknown_kernel_raises(self):
        with pytest.raises(ValueError, match="kernel"):
            autotune.resolve("kmean", (64, 64))

    def test_auto_never_sweeps(self):
        before = _sweep_count("kmeans")
        geo = autotune.resolve("kmeans", (64, 64))
        assert geo == autotune.DEFAULTS["kmeans"]
        assert _sweep_count("kmeans") == before
        d = autotune.delta(autotune.mark() - 1)
        assert d["decisions"][-1]["decision"] == "default"

    def test_off_ignores_cache(self, tmp_path):
        set_config(tuning="on", tuning_cache_dir=str(tmp_path))
        tuned = autotune.resolve("kmeans", (64, 64), interpret=True)
        assert autotune._valid_geometry("kmeans", tuned)
        set_config(tuning="off")
        geo = autotune.resolve("kmeans", (64, 64), interpret=True)
        assert geo == autotune.DEFAULTS["kmeans"]

    def test_pin_overlays_defaults_verbatim(self):
        set_config(tuning='pin:{"kmeans": {"tile_rows": 1024}}')
        geo = autotune.resolve("kmeans", (64, 64))
        assert geo == {"tile_rows": 1024,
                       "depth": autotune.DEFAULTS["kmeans"]["depth"]}
        # a pinned kernel never consults cache or sweeps; unpinned
        # kernels fall through the normal ladder
        assert autotune.resolve("pca", (64,)) == autotune.DEFAULTS["pca"]

    def test_on_sweeps_once_then_hits(self, tmp_path):
        set_config(tuning="on", tuning_cache_dir=str(tmp_path))
        before = _sweep_count("kmeans")
        g1 = autotune.resolve("kmeans", (64, 64), interpret=True)
        assert _sweep_count("kmeans") == before + 1
        g2 = autotune.resolve("kmeans", (64, 64), interpret=True)
        assert g2 == g1
        assert _sweep_count("kmeans") == before + 1  # hit, no re-sweep
        mark = autotune.mark()
        autotune.resolve("kmeans", (64, 64), interpret=True)
        assert autotune.delta(mark)["hits"] == 1

    def test_disk_round_trip_across_clear(self, tmp_path):
        """The cross-process determinism contract, in-process: the
        persisted winner survives a full in-memory wipe (what a fresh
        interpreter sees) and resolves with ZERO additional sweeps."""
        set_config(tuning="on", tuning_cache_dir=str(tmp_path))
        g1 = autotune.resolve("kmeans", (64, 64), interpret=True)
        files = os.listdir(tmp_path)
        assert len(files) == 1 and files[0].startswith("tune-")
        with open(tmp_path / files[0]) as f:
            entry = json.load(f)
        assert entry["kernel"] == "kmeans"
        assert {k: int(v) for k, v in entry["geometry"].items()} == g1

        autotune.clear()  # fresh-process stand-in
        before = _sweep_count("kmeans")
        g2 = autotune.resolve("kmeans", (64, 64), interpret=True)
        assert g2 == g1
        assert _sweep_count("kmeans") == before  # disk hit, zero sweeps

    def test_corrupt_cache_warns_and_resweeps(self, tmp_path, caplog):
        set_config(tuning="on", tuning_cache_dir=str(tmp_path))
        g1 = autotune.resolve("kmeans", (64, 64), interpret=True)
        (path,) = [tmp_path / f for f in os.listdir(tmp_path)]
        path.write_text("{ not json")
        autotune.clear()
        before = _sweep_count("kmeans")
        with caplog.at_level("WARNING", logger="oap_mllib_tpu"):
            g2 = autotune.resolve("kmeans", (64, 64), interpret=True)
        assert any("unreadable" in r.message for r in caplog.records)
        assert _sweep_count("kmeans") == before + 1  # fresh sweep
        # determinism is cache-mediated, not timing-mediated: the fresh
        # sweep re-persists a valid winner (which one depends on walls)
        assert autotune._valid_geometry("kmeans", g2)
        assert g1 is not g2
        assert json.loads(path.read_text())["geometry"] == g2

    def test_stale_key_reads_as_miss(self, tmp_path, caplog):
        """An entry whose recorded key does not match (e.g. a cache dir
        shared across backends) is ignored with a warning, never
        misapplied."""
        set_config(tuning="on", tuning_cache_dir=str(tmp_path))
        autotune.resolve("kmeans", (64, 64), interpret=True)
        (path,) = [tmp_path / f for f in os.listdir(tmp_path)]
        entry = json.loads(path.read_text())
        entry["key"] = "('other-backend',)"
        path.write_text(json.dumps(entry))
        autotune.clear()
        before = _sweep_count("kmeans")
        with caplog.at_level("WARNING", logger="oap_mllib_tpu"):
            autotune.resolve("kmeans", (64, 64), interpret=True)
        assert _sweep_count("kmeans") == before + 1

    def test_tier_is_part_of_the_key(self, tmp_path):
        set_config(tuning="on", tuning_cache_dir=str(tmp_path))
        autotune.resolve("kmeans", (64, 64), "highest", interpret=True)
        before = _sweep_count("kmeans")
        autotune.resolve("kmeans", (64, 64), "default", interpret=True)
        assert _sweep_count("kmeans") == before + 1  # distinct key


# ---------------------------------------------------------------------------
# fit-summary integration
# ---------------------------------------------------------------------------


class TestSummaryTuning:
    def test_kmeans_summary_records_tuning(self, rng):
        from oap_mllib_tpu.models.kmeans import KMeans

        x = rng.normal(size=(256, 8)).astype(np.float32)
        m = KMeans(k=3, init_mode="random", max_iter=2).fit(x)
        t = m.summary.tuning
        assert t["mode"] == "auto"
        assert t["sweeps"] == 0  # auto NEVER sweeps
        assert any(d["kernel"] == "kmeans" for d in t["decisions"])

    def test_pca_and_als_summaries_record_tuning(self, rng):
        from oap_mllib_tpu.models.als import ALS
        from oap_mllib_tpu.models.pca import PCA

        x = rng.normal(size=(128, 6)).astype(np.float32)
        assert PCA(k=2).fit(x).summary["tuning"]["mode"] == "auto"
        u = rng.integers(0, 30, 300)
        i = rng.integers(0, 20, 300)
        r = (rng.random(300) * 4 + 1).astype(np.float32)
        m = ALS(rank=3, max_iter=1).fit(u, i, r)
        assert m.summary["tuning"]["mode"] == "auto"

    def test_second_fit_same_bucket_zero_sweeps(self, rng, tmp_path):
        """Mode "on": the first fit sweeps, the second fit on the same
        (backend, bucket) resolves entirely from cache."""
        from oap_mllib_tpu.models.kmeans import KMeans

        set_config(tuning="on", tuning_cache_dir=str(tmp_path))
        x = rng.normal(size=(256, 8)).astype(np.float32)
        m1 = KMeans(k=3, init_mode="random", max_iter=2).fit(x)
        m2 = KMeans(k=3, init_mode="random", max_iter=2).fit(x)
        assert m2.summary.tuning["sweeps"] == 0
        assert m1.summary.tuning["sweeps"] >= m2.summary.tuning["sweeps"]


# ---------------------------------------------------------------------------
# cross-process determinism (the acceptance leg; slow — subprocess + jax)
# ---------------------------------------------------------------------------


_CHILD = r"""
import json, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.ops.pallas import autotune
from oap_mllib_tpu.telemetry import metrics as tm

set_config(tuning="on", tuning_cache_dir=sys.argv[1])
geo = autotune.resolve("kmeans", (64, 64), interpret=True)
print(json.dumps({
    "geometry": geo,
    "sweeps": tm.counter(
        "oap_tuning_sweeps_total", {"kernel": "kmeans"}
    ).value,
}))
"""


@pytest.mark.slow
class TestCrossProcessDeterminism:
    def test_fresh_process_reuses_the_persisted_winner(self, tmp_path):
        """Two FRESH interpreters sharing one tuning_cache_dir: the
        first sweeps once, the second resolves the identical geometry
        with zero sweeps — rank-uniformity (R16) and restart-stability
        both hang off this."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = []
        for _ in range(2):
            p = subprocess.run(
                [sys.executable, "-c", _CHILD, str(tmp_path)],
                capture_output=True, text=True, env=env,
                cwd=os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                timeout=300,
            )
            assert p.returncode == 0, p.stderr[-2000:]
            out.append(json.loads(p.stdout.strip().splitlines()[-1]))
        assert out[0]["sweeps"] == 1.0
        assert out[1]["sweeps"] == 0.0  # cache-mediated, no re-sweep
        assert out[1]["geometry"] == out[0]["geometry"]


# ---------------------------------------------------------------------------
# geometry moves overlap, never bits
# ---------------------------------------------------------------------------


GEOMETRIES = [(256, 2), (512, 2), (512, 3), (1024, 3)]


class TestGeometryParity:
    def test_kmeans_walk_bit_identical_across_depth_and_route(self, rng):
        """At a FIXED tile partition, buffering depth and dispatch route
        (interpret DMA walk vs the schedule-identical XLA scan) change
        overlap only — the f32 sums must be bit-identical.  Across
        different tile_rows the chunk reduction reorders, so that axis
        gets a scaled 1e-6 bound instead."""
        from oap_mllib_tpu.ops.pallas.kmeans_kernel import (
            lloyd_accumulate_walk,
        )

        x = jnp.asarray(rng.normal(size=(700, 9)).astype(np.float32))
        w = jnp.ones((700,), jnp.float32)
        c = jnp.asarray(rng.normal(size=(5, 9)).astype(np.float32))
        refs = {}
        for tile_rows, depth in GEOMETRIES:
            for interp in (True, False):
                out = lloyd_accumulate_walk(
                    x, w, c, interpret=interp, tile_rows=tile_rows,
                    depth=depth,
                )
                out = tuple(np.asarray(o) for o in out)
                if tile_rows not in refs:
                    refs[tile_rows] = out
                for a, b in zip(out, refs[tile_rows]):
                    assert np.array_equal(a, b), (tile_rows, depth, interp)
        # across tile partitions: same values up to f32 reassociation
        vals = list(refs.values())
        for other in vals[1:]:
            for a, b in zip(other, vals[0]):
                scale = max(1.0, float(np.abs(b).max()))
                np.testing.assert_allclose(a, b, atol=1e-6 * scale)

    def test_pca_moments_within_1e6_across_geometry(self, rng):
        from oap_mllib_tpu.ops.pallas.pca_kernel import pca_moments_pallas

        x = jnp.asarray(rng.normal(size=(900, 17)).astype(np.float32))
        m = jnp.ones((900,), jnp.float32)
        g_ref, cs_ref, n_ref = pca_moments_pallas(x, m, interpret=True)
        scale = max(1.0, float(np.abs(np.asarray(g_ref)).max()))
        for tile_rows, depth in GEOMETRIES:
            for interp in (True, False):
                g, cs, n = pca_moments_pallas(
                    x, m, interpret=interp, tile_rows=tile_rows,
                    depth=depth,
                )
                np.testing.assert_allclose(
                    np.asarray(g), np.asarray(g_ref), atol=1e-6 * scale,
                    err_msg=f"geometry {(tile_rows, depth, interp)}",
                )
                np.testing.assert_allclose(
                    np.asarray(cs), np.asarray(cs_ref), atol=1e-6 * scale,
                )
                assert float(n) == float(n_ref)

    def test_als_solve_bit_identical_across_batch(self, rng):
        """The batched solve is row-independent — batch geometry cannot
        move a bit."""
        from oap_mllib_tpu.ops.pallas.als_kernel import (
            solve_normal_eq_pallas,
        )

        n, r = 300, 8
        mm = rng.normal(size=(n, r, r)).astype(np.float32)
        a = jnp.asarray(
            np.einsum("nij,nkj->nik", mm, mm) + 0.5 * np.eye(r)
        )
        b = jnp.asarray(rng.normal(size=(n, r)).astype(np.float32))
        n_reg = jnp.asarray(np.ones((n,), np.float32) * 3)
        ref = solve_normal_eq_pallas(a, b, n_reg, 0.1, interpret=True)
        for batch, depth in ((128, 2), (256, 3), (512, 2)):
            out = solve_normal_eq_pallas(
                a, b, n_reg, 0.1, interpret=True, batch=batch, depth=depth
            )
            assert np.array_equal(np.asarray(out), np.asarray(ref)), (
                batch, depth,
            )

    def test_als_gram_bit_identical_across_geometry(self, rng):
        from oap_mllib_tpu.ops.pallas.als_kernel import factor_gram_pallas

        f = jnp.asarray(rng.normal(size=(777, 10)).astype(np.float32))
        refs = {}
        for tile_rows, depth in GEOMETRIES:
            out = np.asarray(factor_gram_pallas(
                f, interpret=True, tile_rows=tile_rows, depth=depth
            ))
            # depth never moves a bit at a fixed partition
            if tile_rows in refs:
                assert np.array_equal(out, refs[tile_rows]), (
                    tile_rows, depth,
                )
            refs[tile_rows] = out
        vals = list(refs.values())
        scale = max(1.0, float(np.abs(vals[0]).max()))
        for other in vals[1:]:
            np.testing.assert_allclose(other, vals[0], atol=1e-6 * scale)

    def test_tuned_kmeans_fit_matches_untuned(self, rng, tmp_path):
        """End to end: a pinned non-default geometry fit must agree with
        the default-geometry fit (1e-6 — the XLA route re-chunks the
        Lloyd scan, which reorders the f32 chunk reduction)."""
        from oap_mllib_tpu.models.kmeans import KMeans

        x = rng.normal(size=(512, 8)).astype(np.float32)
        kw = dict(k=3, init_mode="random", max_iter=3, seed=7)
        m1 = KMeans(**kw).fit(x)
        set_config(tuning='pin:{"kmeans": {"tile_rows": 256, "depth": 3}}')
        m2 = KMeans(**kw).fit(x)
        np.testing.assert_allclose(
            m1.cluster_centers_, m2.cluster_centers_, atol=1e-6, rtol=1e-6
        )
        assert m2.summary.tuning["decisions"][-1]["decision"] == "pin"
