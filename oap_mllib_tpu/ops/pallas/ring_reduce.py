"""Ring-overlapped cross-device reduction of per-pass moment buffers.

Every model-sharded and multi-chip pass used to finish with SEPARATE XLA
psums of its moment buffers — the K-Means accumulate alone paid three
(centroid sums, counts, cost), each a standalone allreduce serialized
behind the pass's compute (the pattern *Communication-Avoiding Linear
Algebraic Kernel K-Means on GPUs* — PAPERS.md, arXiv:2601.17136 —
identifies as the dominant distributed-Lloyd cost, and the map-reduce
partial-sums formulation of arXiv:1610.05601 makes overlappable).  This
module replaces them with ONE ring reduction of the PACKED moments:

- **Schedule** (shared by both backends, so numerics cannot diverge):
  bandwidth-optimal ring allreduce — the buffer splits into ``world``
  row segments; W-1 reduce-scatter steps rotate partial segments around
  the ring (each device adds the arriving segment into its running
  copy), then W-1 all-gather steps rotate the fully-reduced segments
  back.  Per-link traffic is 2·(W-1)/W of the buffer — the optimum —
  and each segment's additions happen in a fixed ring order, so results
  are deterministic and identical on every device.
- **TPU backend**: a Pallas kernel drives the rotation with
  ``pltpu.make_async_remote_copy`` ICI DMAs (SNIPPETS [1] pattern: HBM
  ``memory_space=ANY`` operands, VMEM communication buffers and DMA
  semaphores in scratch, a neighbor barrier before first contact,
  ``collective_id`` compiler param).  The segment ADD of ring step s
  overlaps the in-flight DMA of the opposite-direction half (the
  buffer's columns split into a clockwise and a counter-clockwise half,
  the guide's bi-directional ring), so both ICI links carry traffic
  while the VPU folds — the communication-overlap half of ISSUE 9.
- **Everywhere else** (CPU pseudo-cluster, interpret-mode tests, and
  the parity reference on TPU): the identical schedule expressed as
  ``collective.ppermute`` steps — same segment rotation, same addition
  order, so the CPU tier-1 suite exercises the exact reduction the TPU
  kernel performs.

Fallback contract: a mesh with fewer than 2 devices on the reduce axis
routes to a plain ``collective.psum`` (the pre-ring path) — resolved
STATICALLY at program build (kmeans_ops.ring_enabled), so single-device
fits never trace ring code.
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
    compiled_kwargs,
    note_emitted,
    pad_to,
)
from oap_mllib_tpu.parallel import collective


def _rot(i, s: int, world: int):
    """(i - s) mod world for a traced non-negative ``i`` and static s —
    offset into the positive range first (lax.rem keeps the dividend's
    sign, so a bare ``(i - s) % world`` could go negative)."""
    return lax.rem(i - s + 2 * world, world)


# -- ppermute schedule (CPU / parity path) -----------------------------------


def _ring_dir_ppermute(buf, axis_name: str, world: int, me, sign: int):
    """One direction's ring over one column half: ``world - 1``
    reduce-scatter + ``world - 1`` all-gather ppermute steps.  ``sign``
    +1 sends clockwise (to the right neighbor), -1 counter-clockwise —
    the same rotation the TPU kernel's two DMA directions drive, so the
    per-segment addition order is identical across backends."""
    seg = buf.shape[0] // world
    acc = buf.reshape(world, seg, buf.shape[1])
    perm = [(i, (i + sign) % world) for i in range(world)]
    for s in range(world - 1):  # reduce-scatter: rotate + add
        send_idx = _rot(me, sign * s, world)
        recv_idx = _rot(me, sign * (s + 1), world)
        b = lax.dynamic_index_in_dim(acc, send_idx, 0, keepdims=False)
        recv = collective.ppermute(b, axis_name, perm)
        cur = lax.dynamic_index_in_dim(acc, recv_idx, 0, keepdims=False)
        acc = lax.dynamic_update_index_in_dim(acc, cur + recv, recv_idx, 0)
    for s in range(world - 1):  # all-gather: rotate the reduced segments
        send_idx = _rot(me, sign * (s - 1), world)
        recv_idx = _rot(me, sign * s, world)
        b = lax.dynamic_index_in_dim(acc, send_idx, 0, keepdims=False)
        recv = collective.ppermute(b, axis_name, perm)
        acc = lax.dynamic_update_index_in_dim(acc, recv, recv_idx, 0)
    return acc.reshape(world * seg, buf.shape[1])


def _ring_ppermute(x, axis_name: str, world: int):
    """The bi-directional ring schedule as ppermute steps: the clockwise
    half of the columns and the counter-clockwise half rotate in
    opposite directions (the TPU kernel's two-link schedule), then
    reassemble.  ``x`` is the (seg * world, cols) padded buffer with an
    even column split; returns the fully-summed buffer (identical on
    every rank)."""
    half = x.shape[1] // 2
    me = lax.axis_index(axis_name)
    cw = _ring_dir_ppermute(x[:, :half], axis_name, world, me, 1)
    ccw = _ring_dir_ppermute(x[:, half:], axis_name, world, me, -1)
    return jnp.concatenate([cw, ccw], axis=1)


# -- Pallas remote-DMA kernel (TPU path) -------------------------------------


def _make_ring_kernel(axis_name: str, world: int, seg: int, cols: int):
    half = cols // 2  # bi-directional: column halves travel opposite ways

    def _kernel(x_ref, out_ref, comm, send_sem, recv_sem, copy_sem):
        # x_ref/out_ref live in ANY (HBM); comm is the (2 dirs, 2 slots,
        # seg, half) VMEM rotation buffer; semaphores index [dir, slot].
        # neighbours are named by their coordinate on the ring's own
        # mesh axis; every other axis keeps this device's coordinate, so
        # a (data, model) mesh runs one ring per model column
        me = lax.axis_index(axis_name)
        right = lax.rem(me + 1, world)
        left = lax.rem(me + world - 1, world)

        # local copy input -> output (the running accumulator)
        cp = pltpu.make_async_copy(x_ref, out_ref, copy_sem)
        cp.start()
        cp.wait()

        # neighbor barrier: nobody DMAs into a peer's comm buffer before
        # that peer has entered the kernel
        barrier = pltpu.get_barrier_semaphore()
        for nb in (left, right):
            pltpu.semaphore_signal(
                barrier, inc=1, device_id={axis_name: nb},
                device_id_type=pltpu.DeviceIdType.MESH,
            )
        pltpu.semaphore_wait(barrier, 2)

        def load(idx, dir_, slot):
            # acc segment -> VMEM staging half (dir 0 = clockwise carries
            # columns [:half], dir 1 = counter-clockwise carries [half:])
            c0 = dir_ * half
            cp = pltpu.make_async_copy(
                out_ref.at[pl.ds(idx * seg, seg), pl.ds(c0, half)],
                comm.at[dir_, slot],
                copy_sem,
            )
            cp.start()
            cp.wait()

        def store(idx, dir_, slot, add: bool):
            c0 = dir_ * half
            tgt = out_ref.at[pl.ds(idx * seg, seg), pl.ds(c0, half)]
            if add:
                # fold the arrived segment into the running copy: pull
                # current to the spare slot, add on the VPU, push back —
                # the fold of one direction overlaps the other
                # direction's in-flight DMA
                spare = 1 - slot
                cp = pltpu.make_async_copy(tgt, comm.at[dir_, spare], copy_sem)
                cp.start()
                cp.wait()
                comm[dir_, spare] = comm[dir_, spare] + comm[dir_, slot]
                cp2 = pltpu.make_async_copy(comm.at[dir_, spare], tgt, copy_sem)
                cp2.start()
                cp2.wait()
            else:
                cp = pltpu.make_async_copy(comm.at[dir_, slot], tgt, copy_sem)
                cp.start()
                cp.wait()

        def ring_step(send_idx_cw, send_idx_ccw, recv_idx_cw, recv_idx_ccw,
                      add: bool):
            # stage both directions, fire both remote DMAs (opposite ICI
            # links), then fold — adds overlap the other link's transfer
            load(send_idx_cw, 0, 0)
            load(send_idx_ccw, 1, 0)
            rdma_cw = pltpu.make_async_remote_copy(
                src_ref=comm.at[0, 0],
                dst_ref=comm.at[0, 1],
                send_sem=send_sem.at[0],
                recv_sem=recv_sem.at[0],
                device_id={axis_name: right},
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            rdma_ccw = pltpu.make_async_remote_copy(
                src_ref=comm.at[1, 0],
                dst_ref=comm.at[1, 1],
                send_sem=send_sem.at[1],
                recv_sem=recv_sem.at[1],
                device_id={axis_name: left},
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            rdma_cw.start()
            rdma_ccw.start()
            rdma_cw.wait()
            store(recv_idx_cw, 0, 1, add)
            rdma_ccw.wait()
            store(recv_idx_ccw, 1, 1, add)
            # per-step neighbor barrier: slot reuse in the next step must
            # not race a slow peer's in-flight read (conservative — the
            # overlap win is within a step, across the two directions)
            for nb in (left, right):
                pltpu.semaphore_signal(
                    barrier, inc=1, device_id={axis_name: nb},
                    device_id_type=pltpu.DeviceIdType.MESH,
                )
            pltpu.semaphore_wait(barrier, 2)

        # same index schedule as _ring_dir_ppermute (sign +1 = cw half,
        # sign -1 = ccw half) — numerics identical across backends
        for s in range(world - 1):  # reduce-scatter
            ring_step(
                _rot(me, s, world), _rot(me, -s, world),
                _rot(me, s + 1, world), _rot(me, -(s + 1), world),
                add=True,
            )
        for s in range(world - 1):  # all-gather
            ring_step(
                _rot(me, s - 1, world), _rot(me, -(s - 1), world),
                _rot(me, s, world), _rot(me, -s, world),
                add=False,
            )

    return _kernel


def _ring_pallas(x, axis_name: str, world: int):
    """shard_map-body entry for the TPU remote-DMA kernel; ``x`` is the
    (seg * world, cols) padded buffer with cols an even lane multiple."""
    seg = x.shape[0] // world
    cols = x.shape[1]
    return pl.pallas_call(
        _make_ring_kernel(axis_name, world, seg, cols),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, 2, seg, cols // 2), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,
        ],
        # no interpreter branch: off the TPU the ppermute twin runs
        **compiled_kwargs(False, collective_id=7, has_side_effects=True),
    )(x)


# -- dispatch ----------------------------------------------------------------


def ring_allreduce(x, axis_name: str, world: int, interpret: bool = False,
                   segments: int = 1):
    """Sum an identically-shaped per-device 2-D f32 buffer across
    ``axis_name`` with the ring schedule; call INSIDE shard_map/jit
    bodies (the collective.psum seam's in-jit contract).  ``world`` is
    the static axis size.  ``world < 2`` falls back to the psum path —
    the clean degradation the acceptance contract requires.  The
    ``interpret`` static forces the ppermute schedule (tier-1's CPU leg
    runs it regardless, by backend).

    ``segments`` > 1 is the segmented-start epilogue (ROADMAP item 4,
    tuned by ops/pallas/autotune.py): the rows split into ``segments``
    INDEPENDENT ring reductions, each fenced on only its own row slice.
    A segment's reduce-scatter may therefore dispatch while the local
    walk is still accumulating later rows, and a consumer of an early
    segment's output may start before the last segment's all-gather
    completes — the data dependence is per segment, which is exactly
    what lets the XLA scheduler overlap the ring with the surrounding
    pass.  Row-disjoint segments mean the set of additions per row is
    unchanged; only the rotation's starting owner moves, so results
    stay within the ring parity envelope (<= 1e-5) and the trace-time
    census is unchanged (one ``ring.allreduce`` per call, zero
    standalone psums).  Rows pad to ``8 * world * segments``."""
    note_emitted("ring.allreduce")
    if world < 2:
        return collective.psum(x, axis_name)
    segments = max(1, int(segments))
    rows, cols = x.shape
    # every ring segment a whole number of f32 sublane tiles (Mosaic
    # refuses a DMA window of 500 rows), on BOTH paths so the segment
    # boundaries — and with them each row's addition order — agree
    seg_mult = SUBLANE * world * segments
    rows_pad = pad_to(max(rows, seg_mult), seg_mult)
    use_pallas = jax.default_backend() == "tpu" and not interpret
    # even lane-multiple columns on BOTH paths so the bi-directional
    # halves split at the same column — cross-backend bit identity
    cols_pad = pad_to(max(cols, 2 * LANE), 2 * LANE)
    xp = x.astype(jnp.float32)
    if rows_pad != rows or cols_pad != cols:
        xp = jnp.zeros((rows_pad, cols_pad), jnp.float32).at[
            :rows, :cols
        ].set(xp)
    ring_one = _ring_pallas if use_pallas else _ring_ppermute
    if segments == 1:
        out = ring_one(xp, axis_name, world)
    else:
        seg_rows = rows_pad // segments
        out = jnp.concatenate(
            [
                ring_one(
                    xp[g * seg_rows : (g + 1) * seg_rows], axis_name, world
                )
                for g in range(segments)
            ],
            axis=0,
        )
    return out[:rows, :cols]


# -- eager/hosted entry for the streamed multi-host reductions ---------------


def stacked_ring_fn(mesh, axis_name: str, interpret: bool = False,
                    segments: int = 1):
    """Registry-cached jitted ring program for host-driven paths
    (ops/stream_ops): takes a (world, rows, cols) f32 array sharded one
    slot per device over ``axis_name`` (each process contributes its
    per-pass moments in its first local slot, zeros elsewhere) and
    returns it with every slot holding the full sum.  ``segments`` is
    the segmented-start epilogue knob (see :func:`ring_allreduce`)."""
    from oap_mllib_tpu.utils import progcache
    from oap_mllib_tpu.utils.jax_compat import shard_map
    from jax.sharding import PartitionSpec as P

    world = mesh.shape[axis_name]
    segments = max(1, int(segments))

    def build():
        def body(blk):  # (1, rows, cols) per device slot
            return ring_allreduce(
                blk[0], axis_name, world, interpret, segments=segments
            )[None]

        return jax.jit(
            shard_map(
                body, mesh=mesh,
                in_specs=P(axis_name, None, None),
                out_specs=P(axis_name, None, None),
                check_vma=False,
            )
        )

    key = (
        progcache.mesh_fingerprint(mesh), axis_name, world, interpret,
        segments,
    )
    return progcache.get_or_build("ring.stacked", key, build)
