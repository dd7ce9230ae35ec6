"""Fused Lloyd accumulate: distance + argmin + cluster sums in one kernel.

The XLA path (ops/kmeans_ops._accumulate) materializes the (n, k) distance
matrix and an (n, k) one-hot in HBM each iteration — 2*n*k*4 bytes of
traffic on top of reading X.  This kernel streams X once per iteration:
for each row tile, it computes the (bn, k) distances in VMEM, reduces
min/argmin on the VPU, forms the tile one-hot in VMEM, and accumulates
``one_hot.T @ x`` into the (k, d) sums, which stay VMEM-resident for the
whole walk (tiles are visited strictly in order).  HBM traffic per
iteration drops from O(n*d + 2*n*k) to O(n*d + k*d).

Precision tiers (``mode``) — shared vocabulary in ops/pallas/_tiers.py
(Mosaic only lowers Precision.HIGHEST/DEFAULT, so split tiers are
implemented by hand with bf16 splits).  The cluster sums rest on one
fact of the kernel's own construction: the unweighted one-hot is 0/1 —
exactly representable in bf16 (the weights fold into ``w*x``) — so every
bf16 part of ``w*x`` multiplies it exactly and the MXU accumulates in
f32.  Per tier, with its bf16 passes of the MXU for the cross term + the
sums (:data:`MXU_PASSES`; a pass is ``2 * rows * k * d`` operations):

- ``highest`` (6 + 3): distance cross-term f32 Precision.HIGHEST — the
  assignment the tier promises; cluster sums from the EXACT three-way
  split of ``w*x`` (``split3_bf16``: hi+mid+lo, 8+8+8 significand bits)
  in three single passes.  Nothing is lost against a HIGHEST product,
  which splits BOTH operands three ways and runs six passes (hi*hi,
  hi*mid, mid*hi, hi*lo, lo*hi, mid*mid): the one-hot's mid and lo
  parts are identically zero, so three of those six multiply by zero
  and the other three are the ones issued here.  Parity default.
- ``high`` (1 + 2): cross-term single-pass bf16 (the tier contract —
  kmeans_ops._assign_prec — runs the assignment matmul at bf16: argmin is
  decision-only); sums from the hi+lo split of ``w*x``, TWO passes,
  accurate to ~f32, meeting the XLA "high" tier's error envelope.
- ``default`` (1 + 1): bf16 assignment + SINGLE-pass bf16 sums — the XLA
  default tier's ~1e-3 error envelope at its speed.

One Pallas form: the double-buffered tile walk
(``_pallas_accumulate_dbuf``: x stays in HBM, each ``(tile_rows, d)`` tile
streams into a rotating VMEM buffer while the previous tile's update runs)
on the TPU or under ``interpret``, and its schedule-identical ``fori_loop``
twin (``_xla_walk``) elsewhere, both over ``_tile_update``.  The walk ends
at the last tile that holds a row: its trip count is :func:`live_tiles`,
read on the device from the weights (one past the last tile with a
non-zero weight), not the static tile count — a tile past it would add
exact zeros to the sums, the counts and the cost, so the result's bits
are those of a walk over every tile.

Caller contract (``_pad_operands_traced``): rows padded to the tile size
with weight 0; k and d padded to lane multiples (128) — dummy centers get
+inf-like coordinates so no row ever selects them.  Rows past the last
weighted tile are never read (whatever they hold, a NaN included, stays
out of the result); a zero-weight row inside a live tile is read and
must be finite.  The Lloyd loop over
this accumulate, its row sharding and its reductions are
ops/kmeans_ops.lloyd_run's; this module holds the tile program and the
single-shot :func:`lloyd_accumulate_walk` (pad + walk + slice in one
jitted program) that the autotuner and the tests call.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from oap_mllib_tpu.ops.pallas import _dbuf
from oap_mllib_tpu.ops.pallas._tiers import (
    LANE,
    VMEM_LIMIT_BYTES,
    check_mode,
    compiled_kwargs,
    dot_bf16,
    dot_f32,
    kernel_launch,
    pad_to,
    split3_bf16,
    split_bf16,
)
from oap_mllib_tpu.utils import progcache

_BLOCK_ROWS = 512

# bf16 passes of the MXU a tile, by tier: the cross term (_cross_term) and
# the cluster sums (_cluster_sums).  What kmeans_ops.lloyd_run reports as
# lloyd_loop.attrs["mxu_passes"]: passes * 2*n*k_pad*d_pad / the bf16
# peak is the walk's own ceiling an iteration.
MXU_PASSES = {
    "highest": {"cross": 6, "sums": 3},
    "high": {"cross": 1, "sums": 2},
    "default": {"cross": 1, "sums": 1},
}


def _cross_term(x, c, mode):
    """x @ c.T (bn, k) at the requested precision tier.

    "high" and "default" share the single-pass bf16 path: the tier
    definition (kmeans_ops._assign_prec) runs the ASSIGNMENT matmul at
    bf16 for both — argmin is a discrete decision, and the tiers differ
    only in the cluster-sums accuracy (which this kernel's exact-split
    sums exceed in both modes)."""
    dn = (((1,), (1,)), ((), ()))
    if mode == "highest":
        return dot_f32(x, c, dn)
    # high/default: single-pass bf16 — argmin only flips on near-ties
    return dot_bf16(x.astype(jnp.bfloat16), c.astype(jnp.bfloat16), dn)


def _cluster_sums(one_hot01, wx, mode):
    """one_hot.T @ (w*x) (k, d) in single bf16 passes with f32
    accumulation.  one_hot is exactly 0/1 in bf16, so no tier loses
    anything on it: "highest" splits wx exactly three ways and sums the
    three products smallest first — what Precision.HIGHEST computes,
    without its three passes against the one-hot's zero mid/lo parts;
    "high" hi/lo-splits wx for ~f32 accuracy (2 passes); "default" is
    single-pass all-bf16 — the same error envelope as the XLA default
    tier (~1e-3)."""
    dn = (((0,), (0,)), ((), ()))
    oh = one_hot01.astype(jnp.bfloat16)  # exact
    if mode == "highest":
        wx_hi, wx_mid, wx_lo = split3_bf16(wx)
        return (
            dot_bf16(oh, wx_lo, dn) + dot_bf16(oh, wx_mid, dn)
            + dot_bf16(oh, wx_hi, dn)
        )
    if mode == "default":
        return dot_bf16(oh, wx.astype(jnp.bfloat16), dn)
    wx_hi, wx_lo = split_bf16(wx)
    return dot_bf16(oh, wx_hi, dn) + dot_bf16(oh, wx_lo, dn)


def _tile_update(x, w, c, mode, need_cost):
    """One resident tile's full fused update: assignment + moment
    accumulation with the one-hot/centered intermediates living and
    dying in VMEM (never HBM).  Shared by the double-buffered walk
    kernel and its schedule-identical XLA twin, so the two cannot drift
    a bit.  Returns
    ``(sums_inc (k, d), counts_inc (1, k), cost_inc | None)``."""
    k = c.shape[0]
    c_sq = jnp.sum(c * c, axis=1)[None, :]  # (1, k)
    cross = _cross_term(x, c, mode)  # (bn, k)  <- MXU

    if need_cost:
        # squared distances via the matmul identity (MXU)
        x_sq = jnp.sum(x * x, axis=1, keepdims=True)  # (bn, 1)
        d2 = jnp.maximum(x_sq + c_sq - 2.0 * cross, 0.0)
        assign = jnp.argmin(d2, axis=1)  # (bn,)
        min_d2 = jnp.min(d2, axis=1, keepdims=True)  # (bn, 1)
    else:
        # loop mode: argmin is invariant to the per-row |x|^2 term, so
        # rank on the half-score x.c - c_sq/2 (argMAX) — no d2 assembly,
        # no maximum, no min pass (cost is dead inside the Lloyd loop:
        # the caller recomputes it at "highest" after convergence).
        # NB keep the (bn, k) term on the LEFT of the subtract: with the
        # broadcast (1, k) operand first, Mosaic's lowering allocates a
        # ~32 MB scoped-vmem temp and fails to compile (argmax of
        # cross - c_sq/2 selects the same center, same first-index
        # tie-break as argmin of the negation)
        assign = jnp.argmax(cross - 0.5 * c_sq, axis=1)  # (bn,)

    # unweighted 0/1 one-hot (VPU compare against 2-D iota); weights fold
    # into w*x so the one-hot stays exactly representable in bf16
    col_ids = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], k), 1)
    one_hot = jnp.where(col_ids == assign[:, None], 1.0, 0.0)  # (bn, k)

    sums_inc = _cluster_sums(one_hot, w * x, mode)
    if mode == "highest":
        # strict-parity tier: exact f32 VPU reduction
        counts_inc = jnp.sum(one_hot * w, axis=0, keepdims=True)
    else:
        # fast tiers: counts as (1, bn) @ (bn, k) bf16 matmuls with
        # f32 accumulation — the one-hot is exact 0/1 and w rides a
        # hi/lo split, so counts stay ~f32-exact for ANY weights
        # while the two VPU passes over (bn, k) disappear (measured
        # -1.1 ms/iter at 1M x 256 k=1000).  NB bf16 single-pass at
        # this shape compiles where the f32-HIGHEST variant blew
        # Mosaic's scoped vmem (see the assignment note above).
        oh = one_hot.astype(jnp.bfloat16)
        w_hi, w_lo = split_bf16(w)
        dn = (((1,), (0,)), ((), ()))
        counts_inc = dot_bf16(w_hi.T, oh, dn) + dot_bf16(w_lo.T, oh, dn)
    cost_inc = jnp.sum(min_d2 * w) if need_cost else None
    return sums_inc, counts_inc, cost_inc


# -- double-buffered walk (explicit DMA overlap; ROADMAP item 4) -------------


def _make_dbuf_kernel(mode, need_cost, tile_rows, depth, num_tiles):
    def _kernel(live_ref, x_hbm, w_hbm, c_ref, sums_ref, counts_ref,
                cost_ref, xbuf, wbuf, xsem, wsem):
        """Single-invocation walk: x/w stay in HBM, each (tile_rows, d)
        tile streams into the rotation buffer while the previous tile's
        fused update runs — the accumulators are VMEM-resident for the
        whole walk, which ends at tile ``live_ref[0]`` (SMEM)."""
        sums_ref[:] = jnp.zeros_like(sums_ref)
        counts_ref[:] = jnp.zeros_like(counts_ref)
        cost_ref[0, 0] = jnp.float32(0.0)
        c = c_ref[:]

        def body(t, views):
            x, w = views
            sums_inc, counts_inc, cost_inc = _tile_update(
                x, _dbuf.column(w), c, mode, need_cost
            )
            sums_ref[:] += sums_inc
            counts_ref[:] += counts_inc
            if need_cost:
                cost_ref[0, 0] += cost_inc

        _dbuf.tile_walk(
            [x_hbm, w_hbm], [xbuf, wbuf], [xsem, wsem],
            tile_rows, num_tiles, depth, body, axes=(0, None),
            live=live_ref[0],
        )

    return _kernel


def _pallas_accumulate_dbuf(x, w, centers, mode, interpret, need_cost,
                            tile_rows, depth, live):
    """Raw double-buffered pallas_call on pre-padded operands (rows a
    multiple of ``tile_rows``), over tiles ``[0, live)``
    (:func:`live_tiles`; an int32 scalar the kernel reads from SMEM).
    The weight column rides lane-dense (``_dbuf.lane_dense``): Mosaic
    refuses a ``(tile_rows, 1)`` DMA window on an ``(n, 1)`` HBM
    operand."""
    _dbuf.check_tile_rows(tile_rows)
    n, d = x.shape
    k = centers.shape[0]
    num_tiles = n // tile_rows
    sums, counts, cost = pl.pallas_call(
        _make_dbuf_kernel(mode, need_cost, tile_rows, depth, num_tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=_dbuf.rotation_scratch(
            depth, [(tile_rows, d), (tile_rows // LANE, LANE)]
        ),
        interpret=interpret,
        name="kmeans_accumulate_walk",
        **compiled_kwargs(
            interpret, vmem_limit_bytes=VMEM_LIMIT_BYTES,
            has_side_effects=True,
        ),
    )(
        jnp.asarray(live, jnp.int32).reshape(1), x,
        _dbuf.lane_dense(w, tile_rows), centers,
    )
    return sums, counts, cost


def _xla_walk(x_p, w_p, c_p, mode, need_cost, tile_rows, live):
    """Schedule-identical XLA fallback for the double-buffered walk: a
    ``fori_loop`` over the SAME (tile_rows, d) tiles ``[0, live)`` in the
    SAME order through the SAME ``_tile_update``, so the CPU tier-1 suite
    exercises the exact program structure (and bits) the DMA kernel
    produces.  Not a program for the TPU: XLA:TPU keeps excess precision
    across a convert to bf16 and back, which voids ``_cluster_sums``'
    exact splits (Mosaic rounds as written; tests_tpu/ compiles the twin
    with ``xla_allow_excess_precision`` off)."""
    n, d = x_p.shape
    k = c_p.shape[0]
    num_tiles = n // tile_rows
    xt = x_p.reshape(num_tiles, tile_rows, d)
    wt = w_p.reshape(num_tiles, tile_rows, 1)

    def step(t, carry):
        sums, counts, cost = carry
        sums_inc, counts_inc, cost_inc = _tile_update(
            jax.lax.dynamic_index_in_dim(xt, t, keepdims=False),
            jax.lax.dynamic_index_in_dim(wt, t, keepdims=False),
            c_p, mode, need_cost,
        )
        cost = cost + cost_inc if need_cost else cost
        return sums + sums_inc, counts + counts_inc, cost

    init = (
        jnp.zeros((k, d), jnp.float32),
        jnp.zeros((1, k), jnp.float32),
        jnp.float32(0.0),
    )
    sums, counts, cost = jax.lax.fori_loop(0, live, step, init)
    return sums, counts, cost.reshape(1, 1)


def live_tiles(w_p, tile_rows):
    """The walk's bound, read from the padded weight column itself: one
    past the last tile of ``tile_rows`` rows that holds a non-zero
    weight (int32 scalar; 0 where none does).  Every tile past it would
    add exact zeros (``w*x``, ``one_hot*w`` and ``min_d2*w`` are all 0
    there), so a walk that ends at it returns the bits of a walk over
    every tile.  Computed once a program, outside any loop."""
    held = jnp.any(w_p.reshape(-1, tile_rows) != 0, axis=1)
    ends = jnp.arange(1, held.shape[0] + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(held, ends, 0))


def _accumulate_walk_any(x_p, w_p, c_p, mode, interpret, need_cost,
                         tile_rows, depth, live):
    """Backend dispatch for the walk over tiles ``[0, live)`` of
    pre-padded operands: the DMA kernel on TPU (or under interpret), the
    schedule-identical XLA loop elsewhere."""
    if interpret or jax.default_backend() == "tpu":
        return _pallas_accumulate_dbuf(
            x_p, w_p, c_p, mode, interpret, need_cost, tile_rows, depth,
            live,
        )
    return _xla_walk(x_p, w_p, c_p, mode, need_cost, tile_rows, live)


def walk_tiles(n: int, tile_rows: int) -> int:
    """Tiles the walk's padded layout holds for ``n`` rows (at least
    one)."""
    return pad_to(max(n, tile_rows), tile_rows) // tile_rows


def _pad_operands_traced(x, weights, centers, block_rows=_BLOCK_ROWS):
    """The walk's operand layout (traced, never eager — the caller's
    jitted program pads once, before its loop): rows to the tile
    multiple, k and d to lane multiples.  Dummy
    centers sit at 1e15 so no real row selects them; dummy feature
    columns of real centers are 0 (matching padded x columns)."""
    n, d = x.shape
    k = centers.shape[0]
    n_pad = walk_tiles(n, block_rows) * block_rows
    d_pad = pad_to(d, LANE)
    k_pad = pad_to(k, LANE)
    x_p = jnp.zeros((n_pad, d_pad), jnp.float32).at[:n, :d].set(x.astype(jnp.float32))
    w_p = jnp.zeros((n_pad, 1), jnp.float32).at[:n, 0].set(weights.astype(jnp.float32))
    c_p = jnp.full((k_pad, d_pad), 1e15, jnp.float32).at[:k, :d].set(
        centers.astype(jnp.float32)
    )
    c_p = c_p.at[:k, d:].set(0.0)
    return x_p, w_p, c_p


@functools.partial(
    jax.jit,
    static_argnames=("mode", "interpret", "need_cost", "tile_rows", "depth"),
)
def _walk_jit(x, weights, centers, mode, interpret, need_cost, tile_rows,
              depth):
    k, d = centers.shape[0], x.shape[1]
    x_p, w_p, c_p = _pad_operands_traced(
        x, weights, centers, block_rows=tile_rows
    )
    sums, counts, cost = _accumulate_walk_any(
        x_p, w_p, c_p, mode, interpret, need_cost, tile_rows, depth,
        live_tiles(w_p, tile_rows),
    )
    return sums[:k, :d], counts[0, :k], cost[0, 0]


def lloyd_accumulate_walk(
    x: jax.Array,
    weights: jax.Array,
    centers: jax.Array,
    mode: str = "highest",
    interpret: bool = False,
    tile_rows: int = _BLOCK_ROWS,
    depth: int = 2,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-shot fused accumulate, a drop-in for
    ops.kmeans_ops._accumulate (f32 only): ``(sums (k, d), counts (k,),
    cost)``.  One registry-tracked jitted program per input signature,
    padding included; ``tile_rows``/``depth`` are the tunable geometry
    (ops/pallas/autotune.py)."""
    mode = check_mode(mode)
    _dbuf.check_depth(depth)
    progcache.note(
        "kmeans.pallas_walk",
        (progcache.backend_fingerprint(),
         progcache.array_key(x, weights, centers), mode, interpret,
         tile_rows, depth),
    )
    with kernel_launch("kmeans.accumulate_walk"):
        return _walk_jit(
            x, weights, centers, mode, interpret, True, int(tile_rows),
            int(depth),
        )
