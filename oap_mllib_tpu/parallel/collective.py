"""Collective facade: named-axis collectives over the mesh.

The reference funnels every cross-rank exchange through four oneCCL
primitives carrying serialized oneDAL archives: ``broadcast`` (2-phase,
length then payload — KMeansDALImpl.cpp:49-59), ``allgatherv``
(KMeansDALImpl.cpp:97-99, PCADALImpl.cpp:111-113), and
``alltoall``/``alltoallv`` (ALSShuffle.cpp:92-109).  Because XLA programs
have static shapes, the TPU-native facade exchanges fixed-shape tensors
(padded where sizes differ per rank) and compiles to ICI/DCN collectives.

These wrappers are `shard_map`-based so they can be called eagerly on
sharded arrays (useful in drivers and tests); inside jitted estimator
kernels the same collectives are emitted implicitly by XLA from sharding
annotations, or explicitly via `lax.psum` etc. under `shard_map`.

Every facade dispatch is instrumented (ISSUE 4): per-op invocation
counts, payload bytes, and dispatch wall go to the process metrics
registry (``oap_collective_*``, telemetry/metrics.py) and onto the
thread's active span (telemetry/spans.current_span) — DrJAX and the
array-redistribution work (PAPERS.md) both name collectives as the
dominant, hardest-to-see cost at scale, and scattered wall prints can't
see them at all.  The wall is dispatch time (trace + compile on the
first shape, async dispatch after), not on-wire DMA — the profiler
trace layer owns that.
"""

from __future__ import annotations

import time

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from oap_mllib_tpu.config import get_config
from oap_mllib_tpu.telemetry import flightrec
from oap_mllib_tpu.telemetry import metrics as _tm
from oap_mllib_tpu.telemetry.spans import current_span
from oap_mllib_tpu.utils import faults, recovery, sanitizers
from oap_mllib_tpu.utils.jax_compat import shard_map


def _shard_map(f, mesh, in_specs, out_specs):
    # check_vma=False: outputs of all_gather/psum ARE replicated over the
    # data axis, but the static replication checker can't always prove it
    # for P(None, ...) out_specs on a multi-axis mesh.
    return shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def _payload_bytes(x) -> int:
    """Per-PROCESS payload bytes of one facade operand: the fraction of
    the global array whose shards live on this process's devices — the
    bytes this rank actually contributes to the wire.  Booking the full
    unsharded ``nbytes`` (the pre-ISSUE-7 behavior) over-counted
    shard_map-inner traffic world-fold: every rank claimed the whole
    array, so a 2-process world's byte counters summed to 2x the global
    payload.  Host arrays (no sharding) and single-process worlds book
    the full size, unchanged."""
    nbytes = int(getattr(x, "nbytes", 0) or 0)
    sharding = getattr(x, "sharding", None)
    if sharding is None or nbytes == 0:
        return nbytes
    try:
        devs = sharding.device_set
        total = len(devs)
        pidx = jax.process_index()
        local = sum(1 for d in devs if d.process_index == pidx)
        if total:
            return (nbytes * local) // total
    except Exception:
        pass  # exotic shardings fall back to the global size
    return nbytes


def _instrumented(op: str, x: jax.Array, dispatch):
    """Run one facade dispatch with telemetry: invocation count, payload
    bytes (this process's shard share — see :func:`_payload_bytes`),
    and dispatch wall, booked to the registry and the active span; with
    the ``collective`` sanitizer armed, the dispatch signature is also
    fingerprinted and cross-checked across ranks first
    (utils/sanitizers.note_collective).  The dispatch itself is a fault
    site (``collective.dispatch`` — where a dead peer surfaces) and runs
    under the recovery plane's deadline watchdog when
    ``Config.collective_timeout`` is armed (utils/recovery
    .guarded_dispatch; disarmed = one config check)."""
    faults.maybe_fault("collective.dispatch")
    nbytes = _payload_bytes(x)
    axis = get_config().data_axis
    if flightrec.enabled():
        # the dispatch fingerprint lands in the event ring BEFORE the
        # cross-check/dispatch, so a divergence diagnosis or a timeout
        # post-mortem can point at this exact event's seq
        flightrec.record(
            "collective", op,
            f"{axis}|{tuple(getattr(x, 'shape', ()))}"
            f"|{getattr(x, 'dtype', '')}",
        )
    sanitizers.note_collective(
        op, axis, getattr(x, "shape", ()), getattr(x, "dtype", ""),
    )
    t0 = time.perf_counter()
    out = recovery.guarded_dispatch(op, axis, dispatch)
    dt = time.perf_counter() - t0
    _tm.histogram("oap_collective_dispatch_seconds", {"op": op},
                  help="Per-dispatch wall (compile included on first shape)"
                  ).observe(dt)
    _book(op, 1, nbytes, dt)
    return out


def _book(op: str, ops: int, nbytes: int, dispatch_s: float) -> None:
    """Count ``ops`` collectives of ``nbytes`` in all into the registry
    and onto the thread's active span."""
    lab = {"op": op}
    _tm.counter("oap_collective_ops_total", lab,
                help="Collective facade dispatches by op").inc(ops)
    _tm.counter("oap_collective_bytes_total", lab,
                help="Operand bytes through the collective facade"
                ).inc(nbytes)
    sp = current_span()
    if sp is not None:
        sp.note_collective(op, nbytes, dispatch_s, ops=ops)


# -- in-jit collective seam --------------------------------------------------
# Estimator kernels running under shard_map cannot call the eager facade
# below (it would nest shard_map), so they route their named-axis
# collectives through these thin wrappers instead — every collective in
# the package is then emitted at one seam (oaplint rule R3,
# raw-collective; the DrJAX argument that the map-reduce primitives are
# THE explicit composition point, PAPERS.md arXiv:2403.07128).  The
# counter increments at TRACE time — once per compiled program, not per
# dispatch — so ``oap_collective_emitted_total`` is a census of
# collectives emitted into programs, complementing the facade's
# per-dispatch ``oap_collective_ops_total``.


def _note_emitted(op: str) -> None:
    _tm.counter(
        "oap_collective_emitted_total", {"op": op},
        help="Collective ops emitted into compiled programs "
             "(trace-time census, not a dispatch count)",
    ).inc()


def note_in_program(op: str, ops: int, nbytes: int) -> None:
    """Book ``ops`` collectives that ran INSIDE one compiled program
    (a ``while_loop`` that reduces every iteration), with the ``nbytes``
    this process's devices each handed to them: the seam below counts
    at trace time only, so the caller counts from what it knows once
    the program has returned (iterations x payload).  Same registry
    names and span note as a facade dispatch; no dispatch wall — the
    reductions' time is the device trace's to tell."""
    _book(op, ops, nbytes, 0.0)


def psum(x, axis_name):
    """``lax.psum`` at the collective seam (shard_map/jit bodies)."""
    _note_emitted("psum")
    return lax.psum(x, axis_name)


def pmean(x, axis_name):
    """``lax.pmean`` at the collective seam."""
    _note_emitted("pmean")
    return lax.pmean(x, axis_name)


def all_gather(x, axis_name, **kwargs):
    """``lax.all_gather`` at the collective seam (axis/tiled kwargs
    pass through unchanged)."""
    _note_emitted("all_gather")
    return lax.all_gather(x, axis_name, **kwargs)


def ppermute(x, axis_name, perm):
    """``lax.ppermute`` at the collective seam."""
    _note_emitted("ppermute")
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name, **kwargs):
    """``lax.all_to_all`` at the collective seam (split/concat axis
    kwargs pass through unchanged)."""
    _note_emitted("all_to_all")
    return lax.all_to_all(x, axis_name, **kwargs)


def broadcast(x: jax.Array, mesh: Mesh, root: int = 0) -> jax.Array:
    """Replicate the root shard of a row-sharded array to all devices.

    Analog of the reference's serialized-centroid broadcast
    (KMeansDALImpl.cpp:49-59); here it is one compiled collective, no
    length pre-exchange needed.
    """
    cfg = get_config()
    axis = cfg.data_axis

    def _bcast(shard):
        full = lax.all_gather(shard, axis, tiled=True)
        size = shard.shape[0]
        return lax.dynamic_slice_in_dim(full, root * size, size, axis=0)

    spec = P(axis, *([None] * (x.ndim - 1)))
    return _instrumented(
        "broadcast", x,
        lambda: _shard_map(_bcast, mesh, (spec,), spec)(x),
    )


def allgather_rows(x: jax.Array, mesh: Mesh) -> jax.Array:
    """Gather row shards onto every device (replicated result).

    Analog of allgatherv of serialized partials (PCADALImpl.cpp:111-113),
    with fixed-shape shards instead of variable-length archives.
    """
    cfg = get_config()
    axis = cfg.data_axis

    def _ag(shard):
        return lax.all_gather(shard, axis, tiled=True)

    in_spec = P(axis, *([None] * (x.ndim - 1)))
    return _instrumented(
        "allgather_rows", x,
        lambda: _shard_map(_ag, mesh, (in_spec,), P(*([None] * x.ndim)))(x),
    )


def allreduce_sum(x: jax.Array, mesh: Mesh) -> jax.Array:
    """Sum identically-shaped per-device values; replicated result.

    The reference has no direct allreduce — it emulates one with
    allgatherv + a root-side master step (KMeansDALImpl.cpp:97-131); on
    TPU a psum rides ICI directly.
    """
    cfg = get_config()
    axis = cfg.data_axis

    def _ar(shard):
        return lax.psum(shard, axis)

    in_spec = P(axis, *([None] * (x.ndim - 1)))
    out_spec = P(*([None] * x.ndim))
    return _instrumented(
        "allreduce_sum", x,
        lambda: _shard_map(_ar, mesh, (in_spec,), out_spec)(x),
    )


def alltoall_rows(x: jax.Array, mesh: Mesh) -> jax.Array:
    """All-to-all exchange of equal row blocks.

    Each device's shard is viewed as ``world_size`` equal sub-blocks along
    rows; sub-block j goes to device j.  Analog of the reference's rating
    shuffle ``alltoallv`` (ALSShuffle.cpp:92-109) after padding each bucket
    to the max bucket size (survey §7.3 variable-length-exchange note).
    """
    cfg = get_config()
    axis = cfg.data_axis
    world = mesh.shape[axis]

    def _a2a(shard):
        blocks = shard.reshape((world, shard.shape[0] // world) + shard.shape[1:])
        out = lax.all_to_all(blocks, axis, split_axis=0, concat_axis=0)
        return out.reshape(shard.shape)

    spec = P(axis, *([None] * (x.ndim - 1)))
    return _instrumented(
        "alltoall_rows", x,
        lambda: _shard_map(_a2a, mesh, (spec,), spec)(x),
    )
