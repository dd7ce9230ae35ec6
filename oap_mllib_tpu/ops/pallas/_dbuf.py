"""Shared double-buffered tile-walk plumbing for the Pallas kernel plane.

The grid-pipelined kernels (pca/als ``pallas_call`` grids) lean on
the Mosaic pipeline to stage the next block while the current one
computes.  The communication-avoiding restructure (ROADMAP item 4, the
rank-k-update formulation of arXiv:2601.17136) makes that overlap
explicit instead: inputs stay in HBM (``memory_space=ANY``), each kernel
walks its tiles with a *rotating* VMEM buffer of static ``depth``, and
the DMA for tile ``t + depth - 1`` is in flight while tile ``t``
computes — the SNIPPETS [1] async-copy pattern applied within a rank.
Accumulators live in VMEM for the whole walk, so intermediates (the
K-Means one-hot, the centered PCA tile) never round-trip HBM.

This module owns the two pieces every kernel shares, so the rotation
arithmetic cannot drift between them:

- :func:`rotation_scratch` — the ``scratch_shapes`` entries for one
  walk: a ``(depth, *tile)`` VMEM buffer plus a ``(depth,)`` DMA
  semaphore per input.
- :func:`tile_walk` — the in-kernel driver: warm-up starts for the
  first ``depth - 1`` tiles, then a ``fori_loop`` that prefetches tile
  ``t + depth - 1`` into its rotation slot, waits tile ``t``'s DMA, and
  hands the resident views to the kernel's tile body.  Tiles are
  visited strictly in order, so the accumulation order — and therefore
  every result bit — matches the grid-pipelined kernels and the
  schedule-identical XLA fallbacks (``lax.scan`` over the same tiles in
  the same order; see each kernel's ``_xla_walk``).

Depth is a tuned knob (ops/pallas/autotune.py): 2 = classic double
buffering, 3+ trades VMEM for slack against DMA-latency jitter.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from oap_mllib_tpu.ops.pallas._tiers import LANE

# supported rotation depths.  Below 2 PCA's and ALS's entries switch to
# their grid kernels (ROADMAP D3); K-Means has the walk alone, and
# autotune.parse_mode refuses any other depth for it
DEPTHS = (2, 3, 4)


def check_depth(depth: int) -> int:
    depth = int(depth)
    if depth not in DEPTHS:
        raise ValueError(
            f"rotation depth must be one of {DEPTHS}, got {depth!r}"
        )
    return depth


def check_tile_rows(tile_rows: int) -> int:
    """Row-tile extent of a walk that carries a per-row column (weights,
    mask): the column travels lane-dense (:func:`lane_dense`), so the
    tile must be whole 128-row lane groups."""
    tile_rows = int(tile_rows)
    if tile_rows < LANE or tile_rows % LANE:
        raise ValueError(
            f"walk tile_rows must be a positive multiple of {LANE}, got "
            f"{tile_rows!r}"
        )
    return tile_rows


def lane_dense(col, tile_rows: int):
    """Per-row column ``(n, 1)`` -> ``(n // tile_rows, tile_rows // 128,
    128)``: row ``i`` of tile ``t`` sits at ``[t, i // 128, i % 128]``.
    Mosaic cannot slice a ``(tile_rows, 1)`` window out of an ``(n, 1)``
    HBM operand (the minor dim is tiled to 128 lanes), and a lane-padded
    column would stream 128x its bytes; this layout DMAs one whole
    ``(tile_rows // 128, 128)`` slab per tile by leading index."""
    return col.reshape(-1, tile_rows // LANE, LANE)


def column(dense):
    """In-kernel inverse of :func:`lane_dense` for one resident tile:
    ``(tile_rows // 128, 128)`` -> the ``(tile_rows, 1)`` column the tile
    bodies broadcast against.  Pure data movement (one small transpose,
    static lane picks, a sublane concat), so every value — and therefore
    every downstream bit — equals the column the grid kernels and the
    XLA twins see."""
    t = dense.T  # (128, groups)
    return jnp.concatenate(
        [t[:, g : g + 1] for g in range(dense.shape[0])], axis=0
    )


def rotation_scratch(depth: int, tile_shapes):
    """``scratch_shapes`` for one rotating walk over ``len(tile_shapes)``
    inputs: the VMEM rotation buffers first, then one (depth,) DMA
    semaphore array per input (kernel scratch refs arrive in this
    order)."""
    shapes = [
        pltpu.VMEM((depth,) + tuple(ts), jnp.float32) for ts in tile_shapes
    ]
    shapes += [pltpu.SemaphoreType.DMA((depth,)) for _ in tile_shapes]
    return shapes


def tile_walk(inputs, bufs, sems, tile, num_tiles, depth, body, axes=None,
              live=None):
    """Drive one double-buffered walk inside a kernel body.

    ``inputs`` are HBM (``ANY``) refs, ``bufs``/``sems`` the matching
    rotation scratch from :func:`rotation_scratch`, ``tile`` the static
    tile extent along each input's walk axis (``axes``, default 0 —
    the ALS solve walks axis 1; ``None`` marks a pre-tiled operand such
    as a :func:`lane_dense` column, indexed whole by its leading axis),
    ``num_tiles`` the static tile count.
    ``body(t, views)`` receives the tile index and the resident
    ``(tile, ...)`` views; it mutates the kernel's accumulator refs.

    ``live``: a traced int32 scalar ``<= num_tiles`` (read from SMEM by
    the kernel) bounds the walk to tiles ``[0, live)``: the loop's trip
    count, the prefetch guard and the warm-up starts all follow it, so
    every DMA that is started is waited for and ``live == 0`` starts
    none.  Tiles past it are never read.  ``None`` walks all
    ``num_tiles`` on static bounds.

    The start/wait pair rebuilds the same copy descriptor (the async
    copy contract), keyed by rotation slot ``t % depth``.
    """
    if axes is None:
        axes = (0,) * len(inputs)

    def _dma(ref, buf, sem, ax, slot, t):
        if ax is None:
            src = ref.at[t]
        elif ax == 0:
            src = ref.at[pl.ds(t * tile, tile)]
        else:
            src = ref.at[:, pl.ds(t * tile, tile)]
        return pltpu.make_async_copy(src, buf.at[slot], sem.at[slot])

    def _start(t):
        slot = lax.rem(t, depth)
        for ref, buf, sem, ax in zip(inputs, bufs, sems, axes):
            _dma(ref, buf, sem, ax, slot, t).start()

    def _wait(t):
        slot = lax.rem(t, depth)
        for ref, buf, sem, ax in zip(inputs, bufs, sems, axes):
            _dma(ref, buf, sem, ax, slot, t).wait()

    bound = num_tiles if live is None else live

    # warm-up: fill the pipeline with the first depth-1 tiles
    for t in range(min(depth - 1, num_tiles)):
        if live is None:
            _start(jnp.int32(t))
        else:
            pl.when(t < live)(functools.partial(_start, jnp.int32(t)))

    def _step(t, carry):
        nxt = t + depth - 1

        @pl.when(nxt < bound)
        def _prefetch():
            _start(nxt)

        _wait(t)
        slot = lax.rem(t, depth)
        body(t, [buf[slot] for buf in bufs])
        return carry

    lax.fori_loop(0, bound, _step, jnp.int32(0))
