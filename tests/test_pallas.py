"""Pallas fused-kernel tests (interpret mode on the CPU pseudo-cluster).

Compiled-mode (non-interpret) coverage on real TPU hardware lives in
``tests_tpu/`` — run by dev/ci.sh whenever a TPU backend is present — so a
Mosaic lowering regression cannot ship green on the CPU suite alone.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oap_mllib_tpu.ops.kmeans_ops import _accumulate, lloyd_run
from oap_mllib_tpu.ops.pallas._tiers import dot_f32, split3_bf16
from oap_mllib_tpu.ops.pallas.kmeans_kernel import (
    MXU_PASSES,
    _cluster_sums,
    _tile_update,
    lloyd_accumulate_walk,
)


class TestFusedAccumulate:
    def test_matches_xla_accumulate(self, rng):
        n, d, k = 700, 20, 7
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        w = jnp.asarray((rng.random(n) < 0.9).astype(np.float32))
        c = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
        s1, c1, t1 = _accumulate(x, w, c)
        s2, c2, t2 = lloyd_accumulate_walk(x, w, c, interpret=True)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=0)
        np.testing.assert_allclose(float(t1), float(t2), rtol=1e-5)

    def test_weighted_rows(self, rng):
        n, d, k = 600, 8, 3
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        w = jnp.asarray(rng.random(n).astype(np.float32))  # fractional weights
        c = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
        s1, c1, t1 = _accumulate(x, w, c)
        s2, c2, t2 = lloyd_accumulate_walk(x, w, c, interpret=True)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-5)

    @pytest.mark.parametrize("mode,sums_atol", [("high", 5e-3), ("default", 2e-1)])
    def test_fast_tiers_close(self, rng, mode, sums_atol):
        """bf16 tiers: "high" sums stay ~f32-exact via the hi/lo split (the
        one-hot is exactly representable); "default" is single-pass all
        -bf16 — the XLA default tier's ~1e-3-relative envelope.  Distances
        may flip near-ties only."""
        n, d, k = 640, 24, 9
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        w = jnp.asarray((rng.random(n) + 0.5).astype(np.float32))
        c = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
        s1, c1, t1 = _accumulate(x, w, c)
        s2, c2, t2 = lloyd_accumulate_walk(x, w, c, mode=mode, interpret=True)
        # well-separated random clusters: assignments identical
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-3)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=sums_atol)
        np.testing.assert_allclose(float(t1), float(t2), rtol=1e-3)

    def test_bad_mode_raises(self, rng):
        x = jnp.zeros((8, 4), jnp.float32)
        w = jnp.ones((8,), jnp.float32)
        c = jnp.zeros((2, 4), jnp.float32)
        with pytest.raises(ValueError, match="mode"):
            lloyd_accumulate_walk(x, w, c, mode="fast", interpret=True)

    def test_unaligned_shapes_padded(self, rng):
        """n, k, d all unaligned to blocks/lanes: padding must be invisible."""
        n, d, k = 333, 5, 3
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        w = jnp.ones((n,), jnp.float32)
        c = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
        s1, c1, _ = _accumulate(x, w, c)
        s2, c2, _ = lloyd_accumulate_walk(x, w, c, interpret=True)
        assert float(jnp.sum(c2)) == n  # no row lost to padding
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)


class TestFusedLloydLoop:
    def test_matches_xla_lloyd(self, rng):
        n, d, k = 640, 6, 4
        x = rng.normal(size=(n, d)).astype(np.float32)
        init = x[rng.choice(n, k, replace=False)]
        xj, wj = jnp.asarray(x), jnp.ones((n,), jnp.float32)
        cj = jnp.asarray(init)
        tol = jnp.asarray(1e-6, jnp.float32)
        c1, i1, t1, n1 = lloyd_run(xj, wj, cj, 25, tol)
        c2, i2, t2, n2 = lloyd_run(
            xj, wj, cj, 25, tol, accumulate="pallas", interpret=True
        )
        assert int(i1) == int(i2)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-3)
        np.testing.assert_allclose(float(t1), float(t2), rtol=1e-3)
        np.testing.assert_allclose(np.asarray(n1), np.asarray(n2), atol=1e-5)


F32_STEP = 2.0 ** -23  # one float32 rounding step, relative


def _split_inputs(kind):
    rng = np.random.default_rng(30)
    if kind == "normals":
        return rng.normal(size=4096)
    if kind == "all_24_bits":
        # odd multiples of 2^-23 above 1 (the last significand bit set),
        # all-ones significands just under a power of two (bf16 rounds
        # them UP into the next binade), and random full-width integers
        odd = 1.0 + np.arange(1, 4096, 2) * 2.0 ** -23
        ones = np.nextafter(
            np.float32(2.0) ** np.arange(-20, 21), np.float32(0)
        )
        ints = rng.integers(2 ** 23, 2 ** 24, size=2048)
        return np.concatenate([odd, ones, ints])
    if kind == "wide_magnitudes":
        # 1e-30 ... 1e30: from 2^-103 up a value's last bit is itself a
        # normal float32, so no backend's subnormal flush can touch a part
        return rng.choice([-1.0, 1.0], size=4096) * (
            1.0 + rng.random(size=4096)
        ) * 10.0 ** rng.uniform(-30, 30, size=4096)
    if kind == "negatives":
        return -np.abs(rng.normal(size=4096)) * 10.0 ** rng.uniform(
            -6, 6, size=4096
        )
    assert kind == "zeros"
    return np.array([0.0, -0.0, 1.0, -1.0, 0.0, 2.0 ** -100])


def _tile(kind, rows=512, k=128, d=128):
    """One tile's 0/1 one-hot and ``w*x`` (as _tile_update forms them)."""
    rng = np.random.default_rng(31)
    x = rng.normal(size=(rows, d)).astype(np.float32)
    w = (0.5 + rng.random((rows, 1))).astype(np.float32)  # all differ
    assign = rng.integers(0, k, rows)
    if kind == "rows_pick_one_centre":
        assign = np.full(rows, 5)
    elif kind == "wide_magnitudes":
        x *= (10.0 ** rng.uniform(-6, 6, size=(rows, 1))).astype(np.float32)
    else:
        assert kind == "weights_all_differ"
        assert len(np.unique(w)) > rows * 0.99
    one_hot = np.zeros((rows, k), np.float32)
    one_hot[np.arange(rows), assign] = 1.0
    return one_hot, w * x


class TestExactSplitSums:
    """The ``highest`` cluster sums: three single bf16 passes over the
    exact three-way split of ``w*x`` against the 0/1 one-hot, in place of
    a six-pass Precision.HIGHEST product."""

    @pytest.mark.parametrize(
        "kind",
        ["normals", "all_24_bits", "wide_magnitudes", "negatives", "zeros"],
    )
    def test_three_way_split_rebuilds_the_f32(self, kind):
        a = _split_inputs(kind).astype(np.float32)
        hi, mid, lo = (
            np.asarray(p.astype(jnp.float32))
            for p in split3_bf16(jnp.asarray(a))
        )
        for p in (hi, mid, lo):
            # each part IS a bf16: the low 16 bits of its f32 are clear
            assert not np.any(p.view(np.uint32) & 0xFFFF)
        # bit for bit, in f32 arithmetic, in the kernel's order and in
        # the reading order
        np.testing.assert_array_equal((lo + mid) + hi, a)
        np.testing.assert_array_equal((hi + mid) + lo, a)

    @pytest.mark.parametrize(
        "kind",
        ["weights_all_differ", "rows_pick_one_centre", "wide_magnitudes"],
    )
    def test_highest_sums_within_f32_accumulation_error(self, kind):
        one_hot, wx = _tile(kind)
        oh64, wx64 = one_hot.astype(np.float64), wx.astype(np.float64)
        truth = oh64.T @ wx64
        mass = np.maximum(oh64.T @ np.abs(wx64), 1e-300)  # sum |wx| a centre

        def worst(got):
            return float(np.max(np.abs(np.asarray(got) - truth) / mass))

        err = worst(_cluster_sums(jnp.asarray(one_hot), jnp.asarray(wx),
                                  "highest"))
        err_f32 = worst(dot_f32(jnp.asarray(one_hot), jnp.asarray(wx),
                                (((0,), (0,)), ((), ()))))
        # f32 accumulation over a tile_rows-long sum
        assert err <= 1e-6
        # and not worse than the six-pass product it replaces, give or
        # take one float32 step (the two sum in different orders)
        assert err <= err_f32 + F32_STEP

    @pytest.mark.parametrize("mode,dots", [
        ("highest", [("f32", "HIGHEST")] + [("bf16", None)] * 3),
        ("high", [("bf16", None)] * 5),  # + the two count products
        ("default", [("bf16", None)] * 4),
    ])
    def test_tile_update_issues_the_stated_passes(self, mode, dots):
        """The tile program's products, read from its jaxpr: at
        ``highest`` ONE Precision.HIGHEST product (the cross term: six
        passes, the assignment the tier promises) and three single bf16
        passes for the sums — MXU_PASSES says the same."""
        x = jnp.zeros((512, 128), jnp.float32)
        w = jnp.ones((512, 1), jnp.float32)
        c = jnp.zeros((128, 128), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda x, w, c: _tile_update(x, w, c, mode, False)[:2]
        )(x, w, c)
        found = []
        for eqn in jaxpr.jaxpr.eqns:
            if eqn.primitive.name != "dot_general":
                continue
            dtype = {"float32": "f32", "bfloat16": "bf16"}[
                str(eqn.invars[0].aval.dtype)
            ]
            prec = eqn.params["precision"]
            if prec is not None:
                prec = {str(p).split(".")[-1] for p in np.ravel(prec)}
                assert prec == {"HIGHEST"}
                prec = "HIGHEST"
            found.append((dtype, prec))
        assert found == dots
        passes = {"f32": 6, "bf16": 1}
        issued = sum(passes[d] for d, _ in found)
        counts = 0 if mode == "highest" else 2  # the (1, rows) count products
        assert issued - counts == sum(MXU_PASSES[mode].values())
