"""Fused streaming PCA moments: centered Gram + column sums in one kernel.

The XLA covariance pass (ops/pca_ops._covariance_jit and the streamed
``_gram_chunk``) materializes the centered, mask-scaled copy ``xc = (x -
mean) * mask`` in HBM before the Gram matmul — an extra O(n*d) write +
read per pass on top of streaming X.  This kernel fuses center + mask +
Gram per row tile in VMEM: each (bn, d) block is centered on the VPU,
contracted on the MXU into the (d, d) Gram accumulator, and its raw
masked column sums + weighted row count accumulate alongside (the
"X-tile -> X^T X partial + colsum in VMEM" shape of ISSUE 9) —
exploiting the TPU grid's sequential execution for read-modify-write
accumulation exactly like the K-Means kernel.  HBM traffic per pass
drops from O(2*n*d + d^2) to O(n*d + d^2).

Two-pass numerics are preserved: the covariance wrapper
(ops/pca_ops.covariance) first runs the kernel with ``need_gram=False``
(column sums only — the mean pass), then with the mean and
``need_gram=True`` (the centered Gram pass).  The raw-moment one-pass
form stays banned (catastrophic cancellation — see pca_ops).

Precision tiers (``mode``, shared vocabulary in ops/pallas/_tiers.py):
``highest`` = f32 Precision.HIGHEST Gram (parity tier; column sums and
the row count ALWAYS reduce f32 on the VPU at every tier); ``high`` =
hand-rolled bf16_3x — both Gram operands hi/lo-split, three bf16 passes,
~1e-5 of full f32; ``default`` = single-pass all-bf16 with f32
accumulation (~1e-3).  Policy aliases (f32/tf32/bf16) map through
``check_mode``, which is what prices the bf16 compute policy ON Pallas
(utils/precision.kernel_tier — the ISSUE 9 workaround retirement).

Caller contract (``pca_moments_pallas``): rows pad to the 512-row block
with mask 0, d pads to lane multiples with zero columns (zero in x, mean
and therefore in every output slice).
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
    kernel_launch,
    pad_to,
    tiered_dot,
)
from oap_mllib_tpu.utils import progcache

_BLOCK_ROWS = 512

# bf16 passes of the MXU a tile for the centred Gram (_tile_moments), by
# tier; the column sums are VPU work at every tier.  What models/pca
# reports as covariance.attrs["mxu_passes"]: a pass is 2 * rows * d_pad^2
# operations.
MXU_PASSES = {
    "highest": {"gram": 6},
    "high": {"gram": 3},
    "default": {"gram": 1},
}


def _tile_moments(x, m, mean, mode, need_gram):
    """One resident tile's moment update — center + mask + Gram with the
    centered intermediate living and dying in VMEM.  Shared by the grid
    kernel, the double-buffered walk, and the schedule-identical XLA
    fallback.  Returns (gram_inc | None, colsum_inc (1, d), count_inc)."""
    xm = x * m
    # raw masked column sums + weighted row count: always exact f32
    # VPU reductions (the mean numerator must not carry tier rounding)
    colsum_inc = jnp.sum(xm, axis=0, keepdims=True)
    count_inc = jnp.sum(m)
    gram_inc = None
    if need_gram:
        xc = (x - mean) * m  # centered in f32, masked
        # (d, d) += xc^T @ xc — contract the row axis on the MXU at
        # the requested tier (hi/lo splits round xc ONCE per operand)
        gram_inc = tiered_dot(xc, xc, (((0,), (0,)), ((), ())), mode)
    return gram_inc, colsum_inc, count_inc


def _make_kernel(mode, need_gram):
    def _kernel(x_ref, m_ref, mean_ref, gram_ref, colsum_ref, count_ref):
        """One grid step: fold a (bn, d) row block into the moments."""
        @pl.when(pl.program_id(0) == 0)
        def _init():
            gram_ref[:] = jnp.zeros_like(gram_ref)
            colsum_ref[:] = jnp.zeros_like(colsum_ref)
            count_ref[0, 0] = jnp.float32(0.0)

        gram_inc, colsum_inc, count_inc = _tile_moments(
            x_ref[:], m_ref[:], mean_ref[:], mode, need_gram
        )
        colsum_ref[:] += colsum_inc
        count_ref[0, 0] += count_inc
        if need_gram:
            gram_ref[:] += gram_inc

    return _kernel


def _pallas_moments(x, m, mean, mode, interpret, need_gram,
                    block_rows=_BLOCK_ROWS):
    """Raw pallas_call on pre-padded operands (traced inside the jitted
    wrappers — no jit of its own)."""
    n, d = x.shape
    grid = (n // block_rows,)
    gram, colsum, count = pl.pallas_call(
        _make_kernel(mode, need_gram),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((d, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
        name="pca_moments_grid",
        **compiled_kwargs(interpret, vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(x, m, mean)
    return gram, colsum, count


# -- double-buffered walk (explicit DMA overlap; ROADMAP item 4) -------------


def _make_dbuf_kernel(mode, need_gram, tile_rows, depth, num_tiles):
    def _kernel(x_hbm, m_hbm, mean_ref, gram_ref, colsum_ref, count_ref,
                xbuf, mbuf, xsem, msem):
        gram_ref[:] = jnp.zeros_like(gram_ref)
        colsum_ref[:] = jnp.zeros_like(colsum_ref)
        count_ref[0, 0] = jnp.float32(0.0)
        mean = mean_ref[:]

        def body(t, views):
            x, m = views
            gram_inc, colsum_inc, count_inc = _tile_moments(
                x, _dbuf.column(m), mean, mode, need_gram
            )
            colsum_ref[:] += colsum_inc
            count_ref[0, 0] += count_inc
            if need_gram:
                gram_ref[:] += gram_inc

        _dbuf.tile_walk(
            [x_hbm, m_hbm], [xbuf, mbuf], [xsem, msem],
            tile_rows, num_tiles, depth, body, axes=(0, None),
        )

    return _kernel


def _pallas_moments_dbuf(x, m, mean, mode, interpret, need_gram,
                         tile_rows, depth):
    """Raw double-buffered pallas_call on pre-padded operands; the mask
    column rides lane-dense (``_dbuf.lane_dense`` — see the K-Means
    walk)."""
    _dbuf.check_tile_rows(tile_rows)
    n, d = x.shape
    num_tiles = n // tile_rows
    gram, colsum, count = pl.pallas_call(
        _make_dbuf_kernel(mode, need_gram, tile_rows, depth, num_tiles),
        in_specs=[
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
            jax.ShapeDtypeStruct((d, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=_dbuf.rotation_scratch(
            depth, [(tile_rows, d), (tile_rows // LANE, LANE)]
        ),
        interpret=interpret,
        name="pca_moments_walk",
        **compiled_kwargs(
            interpret, vmem_limit_bytes=VMEM_LIMIT_BYTES,
            has_side_effects=True,
        ),
    )(x, _dbuf.lane_dense(m, tile_rows), mean)
    return gram, colsum, count


def _xla_walk(x_p, m_p, mean_p, mode, need_gram, tile_rows):
    """Schedule-identical XLA fallback: ``lax.scan`` over the same tiles
    in the same order through the same ``_tile_moments``."""
    n, d = x_p.shape
    num_tiles = n // tile_rows
    xt = x_p.reshape(num_tiles, tile_rows, d)
    mt = m_p.reshape(num_tiles, tile_rows, 1)

    def step(carry, tile):
        gram, colsum, count = carry
        xi, mi = tile
        gram_inc, colsum_inc, count_inc = _tile_moments(
            xi, mi, mean_p, mode, need_gram
        )
        gram = gram + gram_inc if need_gram else gram
        return (gram, colsum + colsum_inc, count + count_inc), None

    init = (
        jnp.zeros((d, d), jnp.float32),
        jnp.zeros((1, d), jnp.float32),
        jnp.float32(0.0),
    )
    (gram, colsum, count), _ = jax.lax.scan(step, init, (xt, mt))
    return gram, colsum, count.reshape(1, 1)


def _moments_any(x_p, m_p, mean_p, mode, interpret, need_gram, tile_rows,
                 depth):
    """Kernel-variant dispatch on pre-padded operands: grid pipeline at
    depth < 2, double-buffered walk at depth >= 2 (DMA kernel on
    TPU/interpret, XLA scan elsewhere)."""
    if depth >= 2:
        if interpret or jax.default_backend() == "tpu":
            return _pallas_moments_dbuf(
                x_p, m_p, mean_p, mode, interpret, need_gram, tile_rows,
                depth,
            )
        return _xla_walk(x_p, m_p, mean_p, mode, need_gram, tile_rows)
    return _pallas_moments(
        x_p, m_p, mean_p, mode, interpret, need_gram, tile_rows
    )


def _pad_rows_cols(x, mask, mean, block_rows=_BLOCK_ROWS):
    """Pad rows to the block multiple (mask 0) and d to the lane multiple
    (zero columns — zero in x AND mean, so they vanish from every
    output).  Traced only (inside the jitted wrappers)."""
    n, d = x.shape
    n_pad = pad_to(max(n, block_rows), block_rows)
    d_pad = pad_to(d, LANE)
    x_p = jnp.zeros((n_pad, d_pad), jnp.float32).at[:n, :d].set(
        x.astype(jnp.float32)
    )
    m_p = jnp.zeros((n_pad, 1), jnp.float32).at[:n, 0].set(
        mask.astype(jnp.float32)
    )
    mean_p = jnp.zeros((1, d_pad), jnp.float32).at[0, :d].set(
        mean.astype(jnp.float32)
    )
    return x_p, m_p, mean_p


def _static_geometry(tile_rows, depth):
    """None -> the hand-picked defaults (grid kernel, 512-row block)."""
    tile_rows = _BLOCK_ROWS if tile_rows is None else int(tile_rows)
    depth = 0 if depth is None else int(depth)
    if depth >= 2:
        _dbuf.check_depth(depth)
    return tile_rows, depth


def moments_traced(x, mask, mean, mode, interpret, need_gram,
                   tile_rows=None, depth=None):
    """Traced pad + kernel + slice (no jit of its own) — the seam the
    streamed per-chunk accumulators jit around (ops/stream_ops)."""
    tile_rows, depth = _static_geometry(tile_rows, depth)
    d = x.shape[1]
    x_p, m_p, mean_p = _pad_rows_cols(x, mask, mean, block_rows=tile_rows)
    gram, colsum, count = _moments_any(
        x_p, m_p, mean_p, mode, interpret, need_gram, tile_rows, depth
    )
    return gram[:d, :d], colsum[0, :d], count[0, 0]


@functools.partial(
    jax.jit,
    static_argnames=("mode", "interpret", "need_gram", "tile_rows", "depth"),
)
def _moments_jit(x, mask, mean, mode, interpret, need_gram,
                 tile_rows=_BLOCK_ROWS, depth=0):
    """Pad + kernel + slice in ONE jitted program (progcache sees one
    program per input signature, never eager padding dispatches)."""
    return moments_traced(
        x, mask, mean, mode, interpret, need_gram, tile_rows, depth
    )


def pca_moments_pallas(
    x: jax.Array,
    mask: jax.Array,
    mean: jax.Array = None,
    mode: str = "highest",
    interpret: bool = False,
    need_gram: bool = True,
    tile_rows: int = None,
    depth: int = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused PCA moments over one table/chunk: returns (gram (d, d),
    colsum (d,), wcount scalar), all f32.

    ``gram`` is the CENTERED masked Gram ``((x - mean) * mask)^T @ ...``
    (zeros when ``need_gram=False`` — the mean pass, which skips the MXU
    work entirely); ``colsum``/``wcount`` are the raw masked column sums
    and total mask weight, tier-independent f32.  ``mean=None`` means a
    zero vector (pass-1 usage).
    """
    mode = check_mode(mode)
    tile_rows, depth = _static_geometry(tile_rows, depth)
    if mean is None:
        mean = jnp.zeros((x.shape[1],), jnp.float32)
    progcache.note(
        "pca.pallas_moments",
        (progcache.backend_fingerprint(),
         progcache.array_key(x, mask), mode, interpret, need_gram,
         tile_rows, depth),
    )
    with kernel_launch("pca.moments"):
        return _moments_jit(
            x, mask, mean, mode, interpret, need_gram, tile_rows, depth
        )


@functools.partial(
    jax.jit, static_argnames=("mode", "interpret", "tile_rows", "depth")
)
def _covariance_pallas_jit(x, mask, n_rows, mode, interpret,
                           tile_rows=_BLOCK_ROWS, depth=0):
    """Both covariance passes — colsum/mean then centered Gram — over ONE
    padded copy of the table, in one jitted program.  Numerics match
    pca_ops._covariance_jit's two-pass mean-centered form (the raw-moment
    form stays banned; see that docstring)."""
    d = x.shape[1]
    x_p, m_p, zero_mean = _pad_rows_cols(
        x, mask, jnp.zeros((d,), jnp.float32), block_rows=tile_rows
    )
    _, colsum, _ = _moments_any(
        x_p, m_p, zero_mean, mode, interpret, False, tile_rows, depth
    )
    mean_p = colsum / n_rows  # (1, d_pad); padded columns stay 0
    gram, _, _ = _moments_any(
        x_p, m_p, mean_p, mode, interpret, True, tile_rows, depth
    )
    cov = gram[:d, :d] / jnp.maximum(n_rows - 1.0, 1.0)
    # numerical symmetry guard before eigh (same as the XLA pass)
    return 0.5 * (cov + cov.T), mean_p[0, :d]


def covariance_pallas(
    x: jax.Array, mask: jax.Array, n_rows: jax.Array,
    mode: str = "highest", interpret: bool = False,
    tile_rows: int = None, depth: int = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused-kernel replacement for pca_ops._covariance_jit: (cov (d, d),
    mean (d,)) — same two-pass centered numerics, one padded table copy,
    no HBM-materialized centered temp.  ``tile_rows``/``depth`` carry
    tuned geometry (depth >= 2 = the double-buffered walk)."""
    mode = check_mode(mode)
    tile_rows, depth = _static_geometry(tile_rows, depth)
    progcache.note(
        "pca.pallas_covariance",
        (progcache.backend_fingerprint(),
         progcache.array_key(x, mask), mode, interpret, tile_rows, depth),
    )
    with kernel_launch("pca.covariance"):
        return _covariance_pallas_jit(
            x, mask, n_rows, mode, interpret, tile_rows, depth
        )


def pallas_gram_preferred(d: int, precision: str) -> bool:
    """Shape/tier rule for pca_kernel="auto": the fused kernel holds the
    full (d, d) Gram block in VMEM next to its matmul temporaries, so it
    runs up to d_pad = 2048 (a 16 MB Gram; the "high" tier's three split
    passes bring the kernel to 94 MB under the plane's scoped-VMEM
    ceiling, ops/pallas/_tiers.VMEM_LIMIT_BYTES —
    tests/test_tpu_compile.py holds this edge) and wider fits stay on
    the XLA pass.  All three tiers qualify (the kernel ships the same
    hand-rolled hi/lo split tiers as the K-Means kernel, so the bf16
    policy prices ON Pallas)."""
    d_pad = pad_to(d, LANE)
    if d_pad * d_pad > (1 << 22):
        return False
    return precision in ("highest", "high", "default")
