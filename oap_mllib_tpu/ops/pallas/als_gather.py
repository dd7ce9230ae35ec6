"""The factor-row gather of the grouped ALS moments: a walk over a packed
source-factor table held whole in VMEM.

``als_ops.grouped_block_moments`` reads, for every slot of a block of
groups, the rank-r row of the source factor its slot names:
``src_factors.T[:, src_b]``, an ``(r, Gb, P)`` array.  XLA compiles that
as a gather from an ``(8, 128)``-tiled ``f32[r, n_src]`` table in HBM and
pays about 4.5 ns a slot on a v5e whatever the bytes (PERF.md section 5).
Here the table goes into VMEM once a call and each slot costs one scalar
read of its row from SMEM, one single-row load of 512 B and one
single-row store (compiled for a v5e, 237 bundles a group of 128 slots,
its tail included; 2.2 ns a slot on the chip, PERF.md section 6):

- **Packed table** (:func:`pack_table`, once a half-update, outside the
  block loop): source ``s`` sits in row ``s // spr`` of an
  ``f32[rows, 128]`` table, lanes ``L * (s % spr) ... + r``, where
  ``L`` is the least power of two >= ``max(r, 8)`` and ``spr = 128 / L``
  sources share a row (eight at rank 10: 40 MB for 624,961 sources).
  It is copied into a VMEM scratch by ONE DMA at the first grid step —
  a constant-index input would be double-buffered by the pipeline.
- **Walk**: a grid over steps of ``gc`` groups; a step reads its slots'
  packed rows ``s // spr`` into SMEM and their lane groups ``s % spr``
  into VMEM, both as ``(gc, P)`` blocks made by XLA beside the call (so
  the scalar side does no shift a slot).  For each group, ``P`` rows are
  copied into a ``(P, 128)`` buffer, transposed so that the slots lie on
  lanes, and each slot's lane group is picked by ``spr - 1`` selects.
- **Output**: the kernel writes ``(Gb, r, P)``, which the caller
  transposes to ``(r, Gb, P)``: XLA lays that transpose out as a bitcast
  into the operand layout the batched moment product reads, so no
  relayout copy follows the kernel (the ``(r, Gb, P)`` order written
  directly would need one).

The walk copies bits: its output equals ``src_factors.T[:, src_b]`` bit
for bit.  Indices must lie in ``[0, n_src)`` (the grouped layouts' pad
slots name source 0, as XLA's gather reads them).
"""

from __future__ import annotations

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
)

# The largest packed table the walk takes: 1,048,576 sources at rank 10
# (the whole KDD-Cup'11 table's 1,000,990 users); the walk's other
# buffers are a few MiB, inside VMEM_LIMIT_BYTES beside it
# (tests/test_tpu_compile.py compiles the walk at this bound)
TABLE_BOUND_BYTES = 64 * 2**20
# slots whose rows a grid step reads into SMEM (64 KiB; the pipeline
# holds two blocks in the 1 MiB there), and the most groups a step
# writes (a lane-padded (gc, r, P) output block stays a few MiB for
# narrow groups)
_STEP_SLOTS = 16384
_STEP_GROUPS_MAX = 256


def row_width(r: int) -> int:
    """Lanes a source takes in a packed row: the least power of two that
    holds ``r`` and a whole sublane tile's worth of select rows."""
    return max(SUBLANE, 1 << (r - 1).bit_length())


def table_rows(n_src: int, r: int) -> int:
    """Rows of the packed table, whole sublane tiles."""
    return pad_to(max(1, -(-n_src // (LANE // row_width(r)))), SUBLANE)


def table_bytes(n_src: int, r: int) -> int:
    """Bytes of the packed table of ``n_src`` rank-``r`` float32 rows —
    what the walk holds in VMEM."""
    return table_rows(n_src, r) * LANE * 4


def fits(n_src: int, r: int) -> bool:
    """Whether the walk takes this table: a source fits one 128-lane
    row and the packed table the VMEM bound."""
    return r <= LANE and table_bytes(n_src, r) <= TABLE_BOUND_BYTES


def pack_table(factors: jax.Array) -> jax.Array:
    """``(n_src, r)`` float32 factors -> the ``f32[rows, 128]`` packed
    table :func:`gather_walk` reads; pad lanes and pad sources are 0."""
    n_src, r = factors.shape
    width = row_width(r)
    rows = table_rows(n_src, r)
    spr = LANE // width
    # built from the transposed factors: an (n_src, width) intermediate
    # would be padded to 128 lanes in HBM (a table-sized 320 MB at the
    # cell's items for a 40 MB table)
    return (
        jnp.zeros((width, rows * spr), jnp.float32)
        .at[:r, :n_src].set(factors.astype(jnp.float32).T)
        .reshape(width, rows, spr)
        .transpose(1, 2, 0)
        .reshape(rows, LANE)
    )


def _step_groups(groups: int, p: int) -> int:
    """Groups a grid step walks: about ``_STEP_SLOTS`` slots, whole
    sublane tiles of groups, and no more than the block's groups need."""
    want = min(_STEP_SLOTS // p, _STEP_GROUPS_MAX) // SUBLANE * SUBLANE
    return min(max(SUBLANE, want), pad_to(groups, SUBLANE))


def _make_walk_kernel(r: int, p: int, gc: int):
    width = row_width(r)
    spr = LANE // width

    def _kernel(row_smem, pick_vmem, table_hbm, out_ref, table, rows, sem):
        @pl.when(pl.program_id(0) == 0)
        def _fill():
            copy = pltpu.make_async_copy(table_hbm, table, sem)
            copy.start()
            copy.wait()

        def group(g, carry):
            for j in range(p):  # unrolled: one load and one store a slot
                rows[pl.ds(j, 1), :] = table[pl.ds(row_smem[g, j], 1), :]
            lanes = rows[...].T  # (128, P): slot j's packed row in column j
            pick = pick_vmem[pl.ds(g, 1), :]  # (1, P)
            ys = lanes[0:r, :]
            for k in range(1, spr):
                ys = jnp.where(
                    pick == k, lanes[k * width:k * width + r, :], ys
                )
            out_ref[g] = ys
            return carry

        lax.fori_loop(0, gc, group, 0)

    return _kernel


def _walk(table, src_b, r, interpret):
    groups, p = src_b.shape
    spr = LANE // row_width(r)
    gc = _step_groups(groups, p)
    padded = pad_to(groups, gc)
    if padded != groups:
        # pad groups name source 0 and are cut off below
        src_b = jnp.pad(src_b, ((0, padded - groups), (0, 0)))
    out = pl.pallas_call(
        _make_walk_kernel(r, p, gc),
        grid=(padded // gc,),
        in_specs=[
            pl.BlockSpec((gc, p), lambda i: (i, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((gc, p), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (gc, r, p), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((padded, r, p), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM(table.shape, jnp.float32),
            pltpu.VMEM((p, LANE), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
        interpret=interpret,
        name="als_gather_walk",
        # the table is filled at the first step: the steps run in order
        **compiled_kwargs(
            interpret, dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
    )(src_b >> (spr.bit_length() - 1), src_b & (spr - 1), table)
    return out[:groups] if padded != groups else out


def gather_walk(table: jax.Array, src_b: jax.Array, r: int,
                interpret: bool = False) -> jax.Array:
    """``(r, Gb, P)`` float32 rows of the packed ``table``
    (:func:`pack_table`) at the ``(Gb, P)`` int32 indices ``src_b``:
    bit for bit ``factors.T[:, src_b]``.  Traced inside the ALS programs
    (no jit of its own)."""
    note_emitted("als.gather_walk")
    return jnp.transpose(
        _walk(table, src_b.astype(jnp.int32), r, interpret), (1, 0, 2)
    )
