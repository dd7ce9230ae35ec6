"""Batched ALS normal-equation kernels: per-user Gram assembly + rank-r
Cholesky solve, and the streamed factor Gram.

The XLA half-update (ops/als_ops.regularized_solve) assembles the batched
(n_dst, r, r) systems — ALS-WR regularization + the implicit-feedback
Gram term — as separate HBM-materialized intermediates before the
unrolled batch-wide solve (``_chol_solve_unrolled``), paying ~3 extra
reads/writes of the (n_dst, r, r) tensor.  ``solve_normal_eq_pallas``
fuses the whole consumer: each grid step loads one batch tile of flat
moments into VMEM, assembles A = moments + reg*n_reg*I (+ Gram) in
registers, runs the unrolled rank-r Cholesky + both substitutions, masks
empty rows, and writes only the (r, batch) factor tile back — one HBM
read of the moments, one write of the factors.

Layout: batch on the 128-LANE axis throughout (the grouped-path lesson,
als_ops module notes: a (B, r, r) layout pads every r-minor buffer ~13x
to the vreg tile at r=10).  Inputs arrive as one flat (r*r + r + 1, B)
moment sheet — A row-major, then b, then n_reg — so every unrolled
Cholesky step is a (1, B) lane-wide VPU op.

Numerics: the solve is pinned f32 at EVERY tier, matching the package
contract that Grams and solves never run reduced (utils/precision.py —
the solve's conditioning is what the policy protects); ``mode`` is
validated through the shared tier vocabulary so policy aliases pass
through uniformly, and governs only :func:`factor_gram_pallas` (the
(r, r) Gram streamed over the factor table with the hand-rolled hi/lo
split tiers).  The elimination sequence replicates
``_chol_solve_unrolled`` operation-for-operation (lower triangle only —
the reference's masked upper-triangle work feeds only zeroed columns),
so results are bit-identical to the XLA path on the same backend.

Caller contract: rank r <= MAX_RANK (``als_ops.UNROLLED_RANK_MAX``, the
unrolled-solve bound shared with masked_solve), batch pads to the
256-column tile with n_reg = 0 rows (masked to zero factors, sliced off
by the wrapper).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from oap_mllib_tpu.ops.als_ops import UNROLLED_RANK_MAX as MAX_RANK
from oap_mllib_tpu.ops.pallas import _dbuf
from oap_mllib_tpu.ops.pallas._tiers import (
    LANE,
    SUBLANE,
    check_mode,
    compiled_kwargs,
    kernel_launch,
    note_emitted,
    pad_to,
    tiered_dot,
)
from oap_mllib_tpu.utils import progcache

_BATCH = 256  # solve batch tile (lane axis)
_GRAM_BLOCK_ROWS = 512
# double-buffered solve keeps the whole (r, n) factor sheet VMEM-resident;
# past this element budget the walk falls back to the grid pipeline
_DBUF_SOLVE_BUDGET = 1 << 22


def _solve_tile(m, gram, reg, r: int, use_gram: bool):
    """One batch tile's assemble + unrolled Cholesky + substitutions on
    a resident (r*r + r + 1, B) moment sheet.  Shared by the grid
    kernel, the double-buffered walk, and the schedule-identical XLA
    fallback.  Returns the r masked (1, B) factor rows."""
    w_a = r * r  # flat-sheet row offsets: A row-major, then b, then n_reg
    nr = m[w_a + r : w_a + r + 1, :]  # n_reg (1, B)

    # assemble the lower triangle of A: moments + ALS-WR reg
    # (reg * n_reg on the diagonal) + the implicit Gram term, in the
    # exact addition order of als_ops.regularized_solve
    # (a + reg*n*I first, gram added second) so bits match
    at = {}
    for i in range(r):
        for j in range(i + 1):
            a_ij = m[i * r + j : i * r + j + 1, :]
            if i == j:
                a_ij = a_ij + reg * nr
            if use_gram:
                a_ij = gram[i, j] + a_ij
            at[(i, j)] = a_ij

    # unrolled batch-wide Cholesky via rank-1 Schur downdates —
    # operation-for-operation the sequence of
    # als_ops._chol_solve_unrolled, lower triangle only (the
    # reference's masked upper-triangle entries feed only zeroed
    # columns and never change a result bit)
    cols = {}
    for j in range(r):
        d = jnp.sqrt(at[(j, j)])
        for i in range(j, r):
            cols[(i, j)] = at[(i, j)] / d
        for i1 in range(j + 1, r):
            for i2 in range(j + 1, i1 + 1):
                at[(i1, i2)] = at[(i1, i2)] - cols[(i1, j)] * cols[(i2, j)]

    rhs = [m[w_a + j : w_a + j + 1, :] for j in range(r)]
    z = [None] * r
    for j in range(r):  # forward: L z = b
        z[j] = rhs[j] / cols[(j, j)]
        for i in range(j + 1, r):
            rhs[i] = rhs[i] - cols[(i, j)] * z[j]
    w = [None] * r
    for j in reversed(range(r)):  # back: L^T w = z
        acc = z[j]
        for k in range(j + 1, r):
            acc = acc - cols[(k, j)] * w[k]
        w[j] = acc / cols[(j, j)]

    # empty rows (n_reg == 0) get zero factors
    return [jnp.where(nr > 0, jnp.nan_to_num(w[j]), 0.0) for j in range(r)]


def _make_solve_kernel(r: int, use_gram: bool):
    def _kernel(m_ref, gram_ref, reg_ref, out_ref):
        rows = _solve_tile(
            m_ref[:], gram_ref[:], reg_ref[0, 0], r, use_gram
        )
        for j in range(r):
            out_ref[j : j + 1, :] = rows[j]

    return _kernel


def _pallas_solve(m_t, gram, reg, r, use_gram, interpret, batch=_BATCH):
    """Raw pallas_call on the pre-packed (W, B) moment sheet (traced
    inside the jitted wrappers — no jit of its own)."""
    w_rows, n = m_t.shape
    grid = (n // batch,)
    out = pl.pallas_call(
        _make_solve_kernel(r, use_gram),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (w_rows, batch), lambda i: (0, i), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec((r, r), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(
            (r, batch), lambda i: (0, i), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.float32),
        interpret=interpret,
    )(m_t, gram, reg)
    return out


# -- double-buffered solve walk (explicit DMA overlap; ROADMAP item 4) -------


def _make_dbuf_solve_kernel(r, use_gram, batch, depth, num_tiles):
    def _kernel(m_hbm, gram_ref, reg_ref, out_ref, mbuf, msem):
        """Column walk over the HBM moment sheet: the next batch tile
        streams into the rotation buffer while the current tile's
        assemble + Cholesky runs; factor rows write straight into the
        VMEM-resident (r, n) output."""
        reg = reg_ref[0, 0]
        gram = gram_ref[:]

        def body(t, views):
            (m,) = views  # (w_rows, batch)
            rows = _solve_tile(m, gram, reg, r, use_gram)
            for j in range(r):
                out_ref[j : j + 1, pl.ds(t * batch, batch)] = rows[j]

        _dbuf.tile_walk(
            [m_hbm], [mbuf], [msem], batch, num_tiles, depth, body,
            axes=(1,),
        )

    return _kernel


def _pallas_solve_dbuf(m_t, gram, reg, r, use_gram, interpret, batch,
                       depth):
    w_rows, n = m_t.shape
    num_tiles = n // batch
    return pl.pallas_call(
        _make_dbuf_solve_kernel(r, use_gram, batch, depth, num_tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.float32),
        scratch_shapes=_dbuf.rotation_scratch(depth, [(w_rows, batch)]),
        interpret=interpret,
        **compiled_kwargs(interpret, has_side_effects=True),
    )(m_t, gram, reg)


def _xla_solve_walk(m_t, gram, reg, r, use_gram, batch):
    """Schedule-identical XLA fallback: scan the same batch tiles through
    the same ``_solve_tile`` (tiles are independent, so order is for
    structure, not numerics)."""
    w_rows, n = m_t.shape
    num_tiles = n // batch
    tiles = jnp.moveaxis(m_t.reshape(w_rows, num_tiles, batch), 1, 0)

    def step(_, m):
        rows = _solve_tile(m, gram, reg, r, use_gram)
        return 0, jnp.concatenate(rows, axis=0)  # (r, batch)

    _, out = jax.lax.scan(step, 0, tiles)  # (num_tiles, r, batch)
    return jnp.moveaxis(out, 0, 1).reshape(r, n)


def _solve_any(m_t, gram, reg, r, use_gram, interpret, batch, depth):
    """Kernel-variant dispatch on the packed sheet: grid pipeline at
    depth < 2 (or when the walk's VMEM-resident (r, n) output exceeds
    its budget), double-buffered walk otherwise."""
    if depth >= 2 and m_t.shape[1] * r <= _DBUF_SOLVE_BUDGET:
        if interpret or jax.default_backend() == "tpu":
            return _pallas_solve_dbuf(
                m_t, gram, reg, r, use_gram, interpret, batch, depth
            )
        return _xla_solve_walk(m_t, gram, reg[0, 0], r, use_gram, batch)
    return _pallas_solve(m_t, gram, reg, r, use_gram, interpret, batch)


def solve_traced(a, b, n_reg, reg, gram=None, interpret=False, batch=None,
                 depth=None):
    """Traced pack + kernel + slice (no jit of its own) — the seam the
    ALS runners' jitted bodies call through (als_ops.regularized_solve
    with kernel="pallas").  Returns (n_dst, r) factors, f32.
    ``batch``/``depth`` carry tuned geometry (depth >= 2 = the
    double-buffered column walk)."""
    note_emitted("als.solve")
    batch = _BATCH if batch is None else int(batch)
    depth = 0 if depth is None else int(depth)
    if depth >= 2:
        _dbuf.check_depth(depth)
    n, r = b.shape
    if r > MAX_RANK:
        raise ValueError(
            f"pallas ALS solve supports rank <= {MAX_RANK}, got {r} "
            "(the unrolled-solve bound; larger ranks use the XLA path)"
        )
    n_pad = pad_to(max(n, batch), batch)
    # flat moment sheet: A row-major | b | n_reg, batch on lanes —
    # padding columns carry n_reg 0 so they solve to masked zeros
    m = jnp.concatenate(
        [
            a.astype(jnp.float32).reshape(n, r * r),
            b.astype(jnp.float32),
            n_reg.astype(jnp.float32)[:, None],
        ],
        axis=1,
    )
    # sheet rows pad to the f32 sublane tile: the column walk DMAs
    # whole-height (rows, batch) windows, and Mosaic refuses a window
    # whose row extent (111 at r=10) is not a multiple of 8; the zero
    # rows sit past every offset _solve_tile reads
    w_rows = r * r + r + 1
    m_t = jnp.zeros((pad_to(w_rows, SUBLANE), n_pad), jnp.float32).at[
        :w_rows, :n
    ].set(m.T)
    use_gram = gram is not None
    g = (
        gram.astype(jnp.float32)
        if use_gram
        else jnp.zeros((r, r), jnp.float32)
    )
    reg_arr = jnp.full((1, 1), reg, jnp.float32)
    out = _solve_any(m_t, g, reg_arr, r, use_gram, interpret, batch, depth)
    return out[:, :n].T


@functools.partial(
    jax.jit, static_argnames=("use_gram", "interpret", "batch", "depth")
)
def _solve_jit(a, b, n_reg, reg, gram, use_gram, interpret, batch=None,
               depth=None):
    return solve_traced(
        a, b, n_reg, reg, gram if use_gram else None, interpret, batch,
        depth,
    )


def solve_normal_eq_pallas(
    a: jax.Array,
    b: jax.Array,
    n_reg: jax.Array,
    reg,
    gram: jax.Array = None,
    mode: str = "highest",
    interpret: bool = False,
    batch: int = None,
    depth: int = None,
) -> jax.Array:
    """Standalone entry over :func:`solve_traced`: one registry-tracked
    jitted program (pack + kernel + slice).  ``mode`` is validated for
    API uniformity with the other kernels but the solve always runs f32
    (module docstring: the package pins solves full-precision under
    every policy)."""
    check_mode(mode)
    use_gram = gram is not None
    progcache.note(
        "als.pallas_solve",
        (progcache.backend_fingerprint(),
         progcache.array_key(a, b), use_gram, interpret, batch, depth),
    )
    with kernel_launch("als.solve"):
        return _solve_jit(
            a, b, n_reg, jnp.asarray(reg, jnp.float32),
            gram if use_gram else jnp.zeros((b.shape[1],) * 2, jnp.float32),
            use_gram, interpret, batch, depth,
        )


# -- streamed factor Gram ----------------------------------------------------


def _make_gram_kernel(mode):
    def _kernel(f_ref, gram_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            gram_ref[:] = jnp.zeros_like(gram_ref)

        f = f_ref[:]  # (bn, r_pad)
        gram_ref[:] += tiered_dot(f, f, (((0,), (0,)), ((), ())), mode)

    return _kernel


def _pallas_factor_gram(f_p, mode, interpret, block_rows=_GRAM_BLOCK_ROWS):
    n, r_pad = f_p.shape
    grid = (n // block_rows,)
    return pl.pallas_call(
        _make_gram_kernel(mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (block_rows, r_pad), lambda i: (i, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (r_pad, r_pad), lambda i: (0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((r_pad, r_pad), jnp.float32),
        interpret=interpret,
    )(f_p)


def _make_dbuf_gram_kernel(mode, tile_rows, depth, num_tiles):
    def _kernel(f_hbm, gram_ref, fbuf, fsem):
        gram_ref[:] = jnp.zeros_like(gram_ref)

        def body(t, views):
            (f,) = views
            gram_ref[:] += tiered_dot(f, f, (((0,), (0,)), ((), ())), mode)

        _dbuf.tile_walk(
            [f_hbm], [fbuf], [fsem], tile_rows, num_tiles, depth, body
        )

    return _kernel


def _pallas_factor_gram_dbuf(f_p, mode, interpret, tile_rows, depth):
    n, r_pad = f_p.shape
    num_tiles = n // tile_rows
    return pl.pallas_call(
        _make_dbuf_gram_kernel(mode, tile_rows, depth, num_tiles),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((r_pad, r_pad), jnp.float32),
        scratch_shapes=_dbuf.rotation_scratch(depth, [(tile_rows, r_pad)]),
        interpret=interpret,
        **compiled_kwargs(interpret, has_side_effects=True),
    )(f_p)


def _xla_gram_walk(f_p, mode, tile_rows):
    """Schedule-identical XLA fallback for the Gram walk."""
    n, r_pad = f_p.shape
    num_tiles = n // tile_rows
    tiles = f_p.reshape(num_tiles, tile_rows, r_pad)

    def step(gram, f):
        return gram + tiered_dot(f, f, (((0,), (0,)), ((), ())), mode), None

    gram, _ = jax.lax.scan(
        step, jnp.zeros((r_pad, r_pad), jnp.float32), tiles
    )
    return gram


def factor_gram_traced(factors, mode="highest", interpret=False,
                       tile_rows=None, depth=None):
    """Traced pad + kernel + slice: the (r, r) factor Gram ``F^T F``
    streamed over the factor table in row tiles — the implicit-feedback
    Gram term of the ALS half-update, with the shared hi/lo split tiers.
    Production call sites pin mode="highest" (solves and the Grams that
    condition them never run reduced — utils/precision.py contract); the
    split tiers exist for parity tests and shapes where a caller
    explicitly prices them.  ``tile_rows``/``depth`` carry tuned
    geometry (depth >= 2 = the double-buffered walk)."""
    note_emitted("als.factor_gram")
    tile_rows = _GRAM_BLOCK_ROWS if tile_rows is None else int(tile_rows)
    depth = 0 if depth is None else int(depth)
    if depth >= 2:
        _dbuf.check_depth(depth)
    n, r = factors.shape
    n_pad = pad_to(max(n, tile_rows), tile_rows)
    r_pad = pad_to(r, LANE)
    f_p = jnp.zeros((n_pad, r_pad), jnp.float32).at[:n, :r].set(
        factors.astype(jnp.float32)
    )
    if depth >= 2:
        if interpret or jax.default_backend() == "tpu":
            gram = _pallas_factor_gram_dbuf(
                f_p, mode, interpret, tile_rows, depth
            )
        else:
            gram = _xla_gram_walk(f_p, mode, tile_rows)
    else:
        gram = _pallas_factor_gram(f_p, mode, interpret, tile_rows)
    return gram[:r, :r]


@functools.partial(
    jax.jit, static_argnames=("mode", "interpret", "tile_rows", "depth")
)
def _factor_gram_jit(factors, mode, interpret, tile_rows=None, depth=None):
    return factor_gram_traced(factors, mode, interpret, tile_rows, depth)


def factor_gram_pallas(
    factors: jax.Array, mode: str = "highest", interpret: bool = False,
    tile_rows: int = None, depth: int = None,
) -> jax.Array:
    """Standalone registry-tracked entry over :func:`factor_gram_traced`."""
    mode = check_mode(mode)
    progcache.note(
        "als.pallas_factor_gram",
        (progcache.backend_fingerprint(),
         progcache.array_key(factors), mode, interpret, tile_rows, depth),
    )
    with kernel_launch("als.factor_gram"):
        return _factor_gram_jit(factors, mode, interpret, tile_rows, depth)


def pallas_solve_preferred(r: int) -> bool:
    """Shape rule for als_solve_kernel="auto": the fused assembly+solve
    covers the unrolled-rank regime (r <= MAX_RANK, Spark's default is 10);
    larger ranks keep the library Cholesky path."""
    return r <= MAX_RANK
