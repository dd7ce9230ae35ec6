"""The two kernels of ALS's destination-blocked half-update
(``als_ops.als_run_dst_blocked``): moments accumulated straight into each
destination's tile, and a batched Cholesky solve of a block of tiles.

At rank 100 the grouped route's sheet of per-group moments is
``(r+1)(r+2)`` floats a group — 107 GB a side at the KDD-Cup'11 cell's
1.3M groups — and the whole side's ``(n_dst, r, r)`` equations are
25 GB.  The destination-blocked route holds neither: a half-update walks
its destinations ``D`` at a time, and for each block accumulates the
block's tiles, solves them and writes the block's factors.

- **Moments** (:func:`dst_moments`, ``pallas_call`` name
  ``als_dst_moments``): one call takes a chunk of ``Gc`` consecutive
  groups of the destination-sorted layout — their source ids ``(Gc, P)``,
  their weights ``(Gc, 8, P)`` (rows ``a_w``, ``b_w``, ``n_w``) and
  each group's destination within the block — and the source table
  ``(n_src, R)`` (``R`` = ``r_pad``: the rank rounded up to whole lanes
  with room for two more columns; column ``r`` of every row is 1, see
  :func:`lane_rows`), which stays in HBM.  The kernel fetches the rows
  itself: one 512-byte DMA a slot into a sublane of a double-buffered
  ``(2, K·P, R)`` VMEM buffer, a step's copies issued during the step
  before, as straight-line code beside its products (copies issued from
  a loop are not overlapped with the MXU, and cost more than a gather
  by XLA), and waited for in one wait a step.  Every slot is fetched,
  pad slots too (source 0, weight 0): skipping them takes a
  data-dependent trip count, which costs more than it saves.  For each
  group it forms the ``(R, R)`` tile
  ``[Ys | 1]^T [a_w Ys | b_w | n_w]`` on the MXU — ``A`` in
  ``[:r, :r]``, ``b`` in column ``r`` and the count at ``[r, r+1]`` —
  and adds it to a VMEM tile that stays put while the destination does.
  Groups come sorted by destination, so a destination's tile is written
  once a call, by one DMA into the block's ``(D, R, R)`` accumulator in
  HBM (aliased in and out, two tiles in flight).  The call first loads
  the tile of its first group's destination: that is the one
  destination whose groups may have begun in the chunk before.
- **Solve** (:func:`block_cholesky`, name ``als_block_cholesky``):
  ``(r, r, B)`` systems, batch on the lanes, ``B = SOLVE_LANES`` a grid
  step — a right-looking Cholesky by rank-1 Schur downdates, each a
  loop over the rows below the pivot that updates one ``(r, B)`` row of
  the trailing matrix at a time from the row's own entry in the pivot
  column (the matrix stays symmetric, so that entry IS the column's),
  then the forward and back substitutions; rows whose count is 0 get
  zeros.  Everything lives in VMEM: the systems are read once and the
  factors written once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from oap_mllib_tpu.ops.pallas._tiers import (
    LANE,
    SUBLANE,
    VMEM_LIMIT_BYTES,
    compiled_kwargs,
    note_emitted,
    pad_to,
    tiered_dot,
)

# groups a moments grid step takes (its (K * P, R) half of the rows
# buffer: 512 KiB at P = R = 128)
STEP_GROUPS = 8
# systems a solve grid step takes, on the lanes
SOLVE_LANES = 128
# the weights' rows (a_w, b_w, n_w) padded to a sublane tile
WEIGHT_ROWS = SUBLANE


def r_pad(r: int) -> int:
    """Lanes of a gathered row and sides of a moment tile: the rank and
    the two columns of ``b_w`` and ``n_w``, rounded up to whole lanes."""
    return pad_to(r + 2, LANE)


def lane_rows(factors: jax.Array) -> jax.Array:
    """``(n_src, r)`` factors -> the ``(n_src, r_pad)`` rows the route
    reads: the factors, then a column of ones (the ``[Ys | 1]`` of
    every tile), then zeros."""
    n_src, r = factors.shape
    return (
        jnp.zeros((n_src, r_pad(r)), jnp.float32)
        .at[:, :r].set(factors.astype(jnp.float32))
        .at[:, r].set(1.0)
    )


def tile_of(rows, w, r: int, mode: str):
    """One group's ``(R, R)`` moment tile from its ``(P, R)`` source rows
    and ``(8, P)`` weights — the kernel's product, and the XLA route's
    (``als_ops``) through the same rows."""
    yt = rows.T  # (R, P): the slots on the lanes
    row = lax.broadcasted_iota(jnp.int32, yt.shape, 0)
    rhs = jnp.where(
        row == r, w[1:2, :], jnp.where(row == r + 1, w[2:3, :], yt * w[0:1, :])
    )
    return tiered_dot(yt, rhs, (((1,), (1,)), ((), ())), mode)


def _make_moments_kernel(r: int, p: int, steps: int, mode: str,
                         interpret: bool):
    K = STEP_GROUPS

    def _kernel(ldst, first_ids, next_ids, rows, w_ref, acc_in, acc_out,
                ys, tile, state, sem):
        del acc_in  # the same buffer as acc_out
        s = pl.program_id(0)

        def copy(slot, d):
            return pltpu.make_async_copy(
                tile.at[slot], acc_out.at[d], sem.at[slot]
            )

        def fetch(b, k, ids):
            """Start the copies of group ``k``'s rows (``ids`` holds its
            step's source ids) into buffer ``b``, one DMA a row; the
            copies of a buffer share its semaphore.  Straight-line code
            on the chip; the interpreter takes the same copies in a loop,
            which it traces in seconds where the unrolled ones take
            minutes."""
            def start(j):
                pltpu.make_async_copy(
                    rows.at[pl.ds(ids[j], 1)], ys.at[b, pl.ds(j, 1)],
                    sem.at[3 + b],
                ).start()

            if interpret:
                def turn(j, c):
                    start(j)
                    return c

                lax.fori_loop(k * p, (k + 1) * p, turn, 0)
            else:
                for j in range(k * p, (k + 1) * p):
                    start(j)

        def landed(b):
            """Wait for a step's rows in buffer ``b``: a DMA semaphore
            counts bytes, so one wait the buffer's size takes them all."""
            pltpu.make_async_copy(ys.at[b], ys.at[b], sem.at[3 + b]).wait()

        @pl.when(s == 0)
        def _start():
            for k in range(K):
                fetch(0, k, first_ids)
            d = ldst[0]
            load = pltpu.make_async_copy(acc_out.at[d], tile.at[0], sem.at[2])
            load.start()
            load.wait()
            state[0] = d  # the destination of the open tile
            state[1] = 0  # its slot
            state[2] = 0  # slot 0 has a write in flight
            state[3] = 0  # slot 1 has a write in flight

        def accumulate(k, group_rows):
            d = ldst[s * K + k]

            @pl.when(d != state[0])
            def _flush():
                slot = state[1]
                copy(slot, state[0]).start()
                state[2 + slot] = 1
                other = 1 - slot

                @pl.when(state[2 + other] == 1)
                def _():
                    copy(other, 0).wait()
                    state[2 + other] = 0

                tile[other] = jnp.zeros(tile.shape[1:], jnp.float32)
                state[0] = d
                state[1] = other

            slot = state[1]
            tile[slot] = tile[slot] + tile_of(group_rows, w_ref[k], r, mode)

        def step(b):
            """A step whose rows are in buffer ``b`` (a static index: the
            copies into the other buffer and the products from this one
            are one block of code, which the scheduler interleaves): the
            next step's rows go into the other buffer a group at a time
            while this step's groups are multiplied.  The last step
            fetches its own rows again, so that every step is the same
            code, and waits for them."""
            landed(b)
            for k in range(K):
                fetch(1 - b, k, next_ids)
                accumulate(k, ys[b, k * p:(k + 1) * p])

            @pl.when(s == steps - 1)
            def _():
                landed(1 - b)

        for b in range(2):
            pl.when(lax.rem(s, 2) == b)(functools.partial(step, b))

        @pl.when(s == steps - 1)
        def _end():
            slot = state[1]
            copy(slot, state[0]).start()
            copy(slot, 0).wait()
            other = 1 - slot

            @pl.when(state[2 + other] == 1)
            def _():
                copy(other, 0).wait()

    return _kernel


def dst_moments(rows, src, w, ldst, acc, r: int, mode: str = "highest",
                interpret: bool = False):
    """``acc`` (``(D, R, R)`` f32, the block's tiles) with the moments of
    one chunk of groups added.  ``rows`` ``(n_src, R)``: the source
    table (:func:`lane_rows`), read where it lies in HBM; ``src``
    ``(Gc, P)`` int32 source ids; ``w`` ``(Gc, 8, P)`` weights, 0 on
    every pad slot; ``ldst`` ``(Gc,)`` int32 destinations within the
    block, non-decreasing.  ``Gc`` is a multiple of :data:`STEP_GROUPS`.
    A grid step copies the rows of the next step's groups, one DMA a
    row, into the other half of a double buffer while it multiplies its
    own.  A group of zero weights adds exact zeros wherever it points,
    but a run of them may only point at its neighbours' destinations or
    at one no group of the block names (the caller clamps).  Traced
    inside the ALS programs (no jit of its own); ``acc`` is updated in
    place."""
    gc, p = src.shape
    lanes = rows.shape[1]
    steps = gc // STEP_GROUPS
    ids_block = (STEP_GROUPS * p,)
    note_emitted("als.dst_moments")
    ids = src.reshape(gc * p).astype(jnp.int32)
    return pl.pallas_call(
        _make_moments_kernel(r, p, steps, mode, bool(interpret)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(steps,),
            in_specs=[
                # the first step's ids, and the next step's: a step
                # starts the copies of the one after it
                pl.BlockSpec(ids_block, lambda s, ld: (0,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(ids_block,
                             lambda s, ld: (jnp.minimum(s + 1, steps - 1),),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((STEP_GROUPS, WEIGHT_ROWS, p),
                             lambda s, ld: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((2, STEP_GROUPS * p, lanes), jnp.float32),
                pltpu.VMEM((2, lanes, lanes), jnp.float32),
                pltpu.SMEM((4,), jnp.int32),
                # the tiles' two writes, the first tile's load, the two
                # rows buffers
                pltpu.SemaphoreType.DMA((5,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        # operands count the scalar-prefetch one: acc is the sixth
        input_output_aliases={5: 0},
        interpret=interpret,
        name="als_dst_moments",
        # a destination's tile stays open across steps: they run in order
        **compiled_kwargs(interpret, dimension_semantics=("arbitrary",)),
    )(ldst.astype(jnp.int32), ids, ids, rows, w, acc)


def _make_solve_kernel(r: int):
    def _kernel(a_ref, b_ref, n_ref, out_ref, w):
        w[...] = a_ref[...]
        rows = lax.broadcasted_iota(jnp.int32, (r, a_ref.shape[2]), 0)
        for j in range(r):
            inv = lax.rsqrt(w[j, j:j + 1, :])  # (1, B)
            col = jnp.where(rows >= j, w[j] * inv, 0.0)
            w[j] = col  # row j now holds column j of L
            lo = (j + 1) // SUBLANE * SUBLANE

            def downdate(k, carry, j=j, lo=lo, inv=inv):
                lk = w[k, j:j + 1, :] * inv  # L[k, j]: the matrix is symmetric
                w[k, lo:, :] = w[k, lo:, :] - lk * w[j, lo:, :]
                return carry

            if j + 1 < r:
                lax.fori_loop(j + 1, r, downdate, 0)
        z = b_ref[...]  # (r, B)
        for j in range(r):  # L z = b
            zj = z[j:j + 1, :] / w[j, j:j + 1, :]
            z = jnp.where(rows == j, zj, z - w[j] * zj)
        x = jnp.zeros_like(z)
        for j in reversed(range(r)):  # L^T x = z; L^T[j, k] = w[j, k]
            acc = jnp.sum(w[j] * x, axis=0, keepdims=True)
            x = jnp.where(rows == j, (z[j:j + 1, :] - acc) / w[j, j:j + 1, :], x)
        x = jnp.where(jnp.isfinite(x), x, 0.0)
        out_ref[...] = jnp.where(n_ref[...] > 0, x, 0.0)

    return _kernel


def block_cholesky(a_t, b_t, n, interpret: bool = False):
    """``(r, B)`` solutions of the ``(r, r, B)`` symmetric positive
    definite systems ``a_t`` (batch last; a row below a pivot gives its
    entry of the pivot's column, so both triangles are read and the
    systems are taken as symmetric) with right-hand sides ``b_t``
    ``(r, B)``; a column whose count ``n`` ``(1, B)`` is not positive,
    or whose system is not positive definite, gets zeros.  ``B`` is
    padded to whole grid steps here."""
    r, _, b = a_t.shape
    bp = pad_to(b, SOLVE_LANES)
    if bp != b:
        a_t = jnp.pad(a_t, ((0, 0), (0, 0), (0, bp - b)))
        b_t = jnp.pad(b_t, ((0, 0), (0, bp - b)))
        n = jnp.pad(n, ((0, 0), (0, bp - b)))
    note_emitted("als.block_cholesky")
    out = pl.pallas_call(
        _make_solve_kernel(r),
        grid=(bp // SOLVE_LANES,),
        in_specs=[
            pl.BlockSpec((r, r, SOLVE_LANES), lambda i: (0, 0, i)),
            pl.BlockSpec((r, SOLVE_LANES), lambda i: (0, i)),
            pl.BlockSpec((1, SOLVE_LANES), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((r, SOLVE_LANES), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((r, bp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((r, r, SOLVE_LANES), jnp.float32)],
        interpret=interpret,
        name="als_block_cholesky",
        **compiled_kwargs(
            interpret, dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
    )(a_t.astype(jnp.float32), b_t.astype(jnp.float32),
      n.astype(jnp.float32))
    return out[:, :b] if bp != b else out
