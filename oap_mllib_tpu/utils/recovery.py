"""Live-world recovery plane: collective deadlines + coordinated abort.

The sanitizer plane (utils/sanitizers.py) catches the *rank-divergent*
collective — every rank is alive, they just disagree.  This module
catches the other multi-rank failure mode: a peer that is **gone** (a
preempted host, a SIGKILLed worker, a hung device).  Without it every
survivor blocks inside ``process_allgather``/the facade dispatch until
the distributed runtime's own timeout kills the world minutes later,
with no diagnosis and nothing machine-readable for a supervisor to act
on.  Two mechanisms close the gap, both off by default:

- **Collective deadlines** (``Config.collective_timeout`` > 0): every
  host-level collective dispatch runs under :func:`guarded_dispatch` —
  the blocking call moves to a daemon thread and the caller waits with a
  deadline.  Expiry raises :class:`CollectiveTimeoutError` on every
  surviving rank, naming the op, axis, elapsed wall, and the
  last-completed dispatch fingerprint (plus the collective sanitizer's
  sequence digest when armed) so the hang converts into a diagnosis.
  Disarmed (the default) the seam is one config check per dispatch.

- **Coordinated abort** (``Config.crash_dir`` non-empty): a rank's
  fatal fault writes a machine-readable *crash record*
  (``crash.rank<r>.json`` — rank, site, fault class, last durable
  checkpoint step, final telemetry snapshot) into the shared sideband
  directory.  Ranks waiting inside a deadline-armed collective poll the
  sideband and raise :class:`PeerAbortError` promptly when a peer's
  record appears — the generalization of the streamed pass's riding
  error flag (ops/stream_ops._PassGuard) to faults that never reach a
  common reduction.  The supervisor (utils/supervisor.py) reads the
  records to classify the exit and decide relaunch/shrink.

This is the detect half of the detect → abort → relaunch →
resharded-resume loop (the elastic-training pattern of PAPERS.md
arXiv:2112.01075); utils/checkpoint.py owns the resume half and
utils/supervisor.py the relaunch half.  The reference framework cannot
express any of it: its oneCCL communicator is static — one lost rank
wedges the world (survey §7.3).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Any, Dict, Optional

from oap_mllib_tpu.config import get_config
from oap_mllib_tpu.telemetry import metrics as _tm
from oap_mllib_tpu.telemetry.spans import current_span

log = logging.getLogger("oap_mllib_tpu")

# v2 (ISSUE 11): records gained the ``flight_recorder`` field — the
# tail of the per-rank event ring (telemetry/flightrec.py, [] when the
# recorder is off), so every post-mortem shows the last N events on
# every rank, not just a final snapshot.
CRASH_RECORD_VERSION = 2
_CRASH_PREFIX = "crash.rank"

# sideband poll cadence while blocked inside a guarded dispatch: fast
# enough that a poisoned world aborts in well under a second, slow
# enough that the listdir cost is invisible next to any real collective
_POLL_S = 0.05

FAULT_TIMEOUT = "collective_timeout"
FAULT_PEER_ABORT = "peer_abort"


class RecoveryError(RuntimeError):
    """Base class for recovery-plane aborts."""


class CollectiveTimeoutError(RecoveryError):
    """A peer never arrived at a collective within the deadline.

    ``op``/``axis``/``elapsed_s`` carry the dispatch that expired;
    ``last_completed`` is (count, signature) of the newest dispatch this
    rank finished — the point up to which the world was provably in
    step."""

    def __init__(self, msg: str, *, op: str = "", axis: str = "",
                 elapsed_s: float = 0.0, last_completed=None):
        super().__init__(msg)
        self.op = op
        self.axis = axis
        self.elapsed_s = elapsed_s
        self.last_completed = last_completed


class PeerAbortError(RecoveryError):
    """A peer's crash record appeared while this rank was blocked in a
    collective; ``record`` is the peer's parsed crash record."""

    def __init__(self, msg: str, record: Optional[Dict[str, Any]] = None):
        super().__init__(msg)
        self.record = dict(record or {})


def collective_timeout_cfg(cfg=None) -> float:
    """Validated ``Config.collective_timeout`` — negative must raise,
    not silently disarm (the kmeans_kernel/fault_spec contract)."""
    timeout = float((cfg or get_config()).collective_timeout)
    if timeout < 0:
        raise ValueError(
            f"collective_timeout must be >= 0 seconds (0 = disarmed), "
            f"got {timeout}"
        )
    return timeout


def _world() -> int:
    import jax

    return jax.process_count()


def _rank() -> int:
    import jax

    return jax.process_index()


# -- last-completed dispatch fingerprint --------------------------------------
# Updated on every guarded dispatch that finishes, whatever the armed
# state of the sanitizer plane — the timeout diagnosis must be able to
# say "the world was in step through dispatch #N [sig]" even when
# fingerprint cross-checking is off.

_fp_lock = threading.Lock()
_completed = {"count": 0, "last": ""}


def _note_completed(sig: str) -> None:
    with _fp_lock:
        _completed["count"] += 1
        _completed["last"] = sig


def last_completed() -> Dict[str, Any]:
    """(count, signature) of the newest host-level dispatch this rank
    completed under the watchdog."""
    with _fp_lock:
        return dict(_completed)


def _sanitizer_digest() -> str:
    """The collective sanitizer's fit-window fingerprint when armed
    ('' otherwise) — the richer sequence digest rides the diagnosis."""
    try:
        from oap_mllib_tpu.utils import sanitizers

        if sanitizers.enabled("collective"):
            count, digest = sanitizers.fingerprint()
            return f"{count}:{digest}"
    except Exception:  # noqa: BLE001 — diagnosis must never mask the fault
        pass
    return ""


# -- crash records + poison sideband ------------------------------------------


def crash_record_path(crash_dir: str, rank: int) -> str:
    return os.path.join(crash_dir, f"{_CRASH_PREFIX}{rank}.json")


def write_crash_record(site: str, fault_class: str, error: str, *,
                       op: str = "", elapsed_s: float = 0.0) -> Optional[str]:
    """Write this rank's machine-readable crash record into the sideband
    (atomic tmp+rename, so peers and the supervisor never read a torn
    file); no-op returning None when ``Config.crash_dir`` is empty.
    Never raises — the record is the diagnosis channel for a fault
    already in flight, and a second failure here must not mask it."""
    cfg = get_config()
    if not cfg.crash_dir:
        return None
    try:
        from oap_mllib_tpu.data import io as _io
        from oap_mllib_tpu.telemetry import flightrec
        from oap_mllib_tpu.utils import checkpoint as _ckpt

        # the crash itself becomes the ring's final event, so the
        # embedded tail always ends with what killed this rank
        if flightrec.enabled():
            flightrec.record("crash", site, fault_class)
        rank = _rank()
        record = {
            "version": CRASH_RECORD_VERSION,
            "rank": rank,
            "world": _world(),
            "site": site,
            "fault_class": fault_class,
            "error": str(error)[:4000],
            "op": op,
            "elapsed_s": round(float(elapsed_s), 3),
            "last_completed": last_completed(),
            "sanitizer_fingerprint": _sanitizer_digest(),
            "last_checkpoint_step": _ckpt.last_durable_step(),
            "flight_recorder": flightrec.tail(
                flightrec.CRASH_TAIL_EVENTS
            ),
            "telemetry": _tm.snapshot(),
        }
        os.makedirs(cfg.crash_dir, exist_ok=True)
        path = crash_record_path(cfg.crash_dir, rank)
        _io.atomic_write_json(path, record)
        _tm.counter(
            "oap_recovery_aborts_total", {"cause": fault_class},
            help="Coordinated aborts by fault class (crash records written)",
        ).inc()
        sp = current_span()
        if sp is not None:
            sp.node("recovery").attrs.update({
                "fault_class": fault_class, "site": site, "op": op,
            })
        return path
    except Exception as e:  # noqa: BLE001
        log.warning("recovery: failed to write crash record (%s)", e)
        return None


def check_poison(crash_dir: str, my_rank: int) -> Optional[Dict[str, Any]]:
    """The first PEER crash record in the sideband, parsed (an unparsable
    record still counts — a half-dead peer is still dead; it returns
    with only the rank filled in), or None when the world looks
    healthy."""
    try:
        names = os.listdir(crash_dir)
    except OSError:
        return None
    for name in sorted(names):
        if not (name.startswith(_CRASH_PREFIX) and name.endswith(".json")):
            continue
        try:
            rank = int(name[len(_CRASH_PREFIX):-len(".json")])
        except ValueError:
            continue
        if rank == my_rank:
            continue
        try:
            with open(os.path.join(crash_dir, name)) as f:
                return json.load(f)
        except Exception:  # noqa: BLE001 — torn/corrupt: peer is dead anyway
            return {"rank": rank}
    return None


def clear_crash_records(crash_dir: str) -> int:
    """Remove every crash record in the sideband (the supervisor calls
    this between attempts so a stale record cannot poison the relaunched
    world); returns how many were removed."""
    removed = 0
    try:
        for name in os.listdir(crash_dir):
            if name.startswith(_CRASH_PREFIX) and name.endswith(".json"):
                os.unlink(os.path.join(crash_dir, name))
                removed += 1
    except OSError:
        pass
    return removed


def list_crash_records(crash_dir: str) -> list:
    """Paths of every crash record currently in the sideband, sorted by
    filename (i.e. by rank).  The serving plane uses this to NAME the
    culprit when accepted work cannot complete after an eviction — a
    ``ServeError(reason="eviction")`` carries these paths so the
    operator lands on the exact crash record, not a generic timeout."""
    try:
        names = os.listdir(crash_dir)
    except OSError:
        return []
    return [
        os.path.join(crash_dir, name)
        for name in sorted(names)
        if name.startswith(_CRASH_PREFIX) and name.endswith(".json")
    ]


def record_fatal(site: str, exc: BaseException) -> None:
    """Coordinated-abort hook for a fatal fault outside any collective:
    classify it (utils/resilience.classify_fault) and poison the world
    via the sideband.  Called by ``resilient_fit``'s multi-process path
    before the exception propagates; no-op when ``Config.crash_dir`` is
    empty or the world is single-process (the ladder owns recovery
    there)."""
    if not get_config().crash_dir or _world() <= 1:
        return
    if isinstance(exc, RecoveryError):
        return  # the watchdog already wrote this rank's record
    from oap_mllib_tpu.utils.resilience import classify_fault

    kind = classify_fault(exc) or "unclassified"
    write_crash_record(site, kind, repr(exc))


# -- the collective watchdog ---------------------------------------------------


def guarded_dispatch(op: str, axis: str, fn):
    """Run one host-level collective dispatch under the recovery plane.

    Disarmed (``collective_timeout == 0``, the default) or
    single-process, this is ``fn()`` behind one config check — the
    <1%-overhead contract dev/chaos_gate.py asserts.  Armed in a
    multi-process world, ``fn`` runs in a daemon thread while this
    thread waits with a deadline, polling the crash sideband: the
    dispatch completing wins; a peer crash record raises
    :class:`PeerAbortError`; deadline expiry writes this rank's crash
    record and raises :class:`CollectiveTimeoutError` naming
    op/axis/elapsed/last-completed-fingerprint.  The blocked worker
    thread is abandoned (daemon) — after a timeout the process is
    expected to exit and be relaunched by the supervisor."""
    cfg = get_config()
    if cfg.collective_timeout == 0 or _world() <= 1:
        if cfg.collective_timeout:  # validate only when armed at all
            collective_timeout_cfg(cfg)
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 — re-raised unless a peer died
            _raise_if_peer_lost(e, op, axis, 0.0)
            raise
    timeout = collective_timeout_cfg(cfg)
    crash_dir = cfg.crash_dir
    my_rank = _rank()

    done = threading.Event()
    box: Dict[str, Any] = {"out": None, "exc": None}

    def _run():
        try:
            box["out"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised in caller
            box["exc"] = e
        finally:
            done.set()

    worker = threading.Thread(
        target=_run, daemon=True, name=f"oap-collective-{op}"
    )
    t0 = time.monotonic()
    worker.start()
    while not done.wait(_POLL_S):
        elapsed = time.monotonic() - t0
        if crash_dir:
            peer = check_poison(crash_dir, my_rank)
            if peer is not None:
                _count_peer_abort()
                write_crash_record(
                    "collective.dispatch", FAULT_PEER_ABORT,
                    f"peer rank {peer.get('rank')} aborted: "
                    f"{peer.get('fault_class', '?')} at "
                    f"{peer.get('site', '?')}",
                    op=op, elapsed_s=elapsed,
                )
                raise PeerAbortError(
                    f"collective '{op}' over axis '{axis}' aborted after "
                    f"{elapsed:.1f}s: rank {peer.get('rank')} poisoned the "
                    f"world ({peer.get('fault_class', 'unknown fault')} at "
                    f"{peer.get('site', '?')}: "
                    f"{peer.get('error', 'no detail')[:500]}); its last "
                    "durable checkpoint step was "
                    f"{peer.get('last_checkpoint_step', -1)}",
                    record=peer,
                )
        if elapsed >= timeout:
            _tm.counter(
                "oap_recovery_timeouts_total", {"op": op},
                help="Collective dispatches that expired the deadline "
                     "(a peer never arrived)",
            ).inc()
            last = last_completed()
            digest = _sanitizer_digest()
            write_crash_record(
                "collective.dispatch", FAULT_TIMEOUT,
                f"{op} over '{axis}' exceeded collective_timeout="
                f"{timeout}s", op=op, elapsed_s=elapsed,
            )
            raise CollectiveTimeoutError(
                f"collective '{op}' over axis '{axis}' did not complete "
                f"within collective_timeout={timeout}s (elapsed "
                f"{elapsed:.1f}s, rank {my_rank} of {_world()}): a peer "
                "likely died or hung.  Last completed dispatch on this "
                f"rank: #{last['count']}"
                + (f" [{last['last']}]" if last["last"] else " (none)")
                + (f"; collective-sanitizer fingerprint {digest}"
                   if digest else "")
                + ".  Recovery: relaunch under utils/supervisor (resume="
                "auto restores the last durable checkpoint — docs/"
                "distributed.md 'Recovery runbook').",
                op=op, axis=axis, elapsed_s=elapsed, last_completed=last,
            )
    if box["exc"] is not None:
        _raise_if_peer_lost(box["exc"], op, axis, time.monotonic() - t0)
        raise box["exc"]
    _note_completed(f"{op}|{axis}")
    return box["out"]


def _count_peer_abort() -> None:
    _tm.counter(
        "oap_recovery_peer_aborts_total",
        help="Dispatches aborted because a peer's crash record appeared "
             "in the sideband or the transport reported the peer gone",
    ).inc()


# a dead peer as the collective transports report it: Gloo names the
# closed TCP pair ("Gloo AllGather failed ... Connection reset by peer" /
# "Connection closed by peer"), the coordination service the stopped
# heartbeat.  A bare "connection reset" with no collective transport
# named is a client socket and none of this plane's business.
_PEER_TRANSPORT_MARKER = "gloo"
_PEER_SOCKET_MARKERS = (
    "connection reset",
    "connection closed",
    "broken pipe",
    "socket closed",
    "read error",
    "write error",
)
_PEER_HEARTBEAT_MARKER = "heartbeat timeout"


def _peer_lost(exc: BaseException) -> bool:
    msg = str(exc).lower()
    if _PEER_HEARTBEAT_MARKER in msg:
        return True
    return _PEER_TRANSPORT_MARKER in msg and any(
        m in msg for m in _PEER_SOCKET_MARKERS
    )


def _raise_if_peer_lost(exc: BaseException, op: str, axis: str,
                        elapsed: float) -> None:
    """A collective that FAILED because its peer is gone — the transport
    saw the socket close (``Gloo ... Connection reset by peer``) or the
    coordination service the heartbeat stop — is the same event as one
    that hangs until the deadline, reported sooner: write this rank's
    crash record and raise :class:`PeerAbortError`, so survivors leave
    through the recovery plane whichever way the loss surfaced.  Any
    other failure returns and is re-raised unchanged by the caller."""
    if _world() <= 1 or not _peer_lost(exc):
        return
    _count_peer_abort()
    detail = str(exc)[:500]
    write_crash_record(
        "collective.dispatch", FAULT_PEER_ABORT,
        f"peer lost during {op}: {detail}", op=op, elapsed_s=elapsed,
    )
    raise PeerAbortError(
        f"collective '{op}' over axis '{axis}' failed after "
        f"{elapsed:.1f}s because a peer is gone (rank {_rank()} of "
        f"{_world()}): {detail}.  Recovery: relaunch under "
        "utils/supervisor (resume=auto restores the last durable "
        "checkpoint — docs/distributed.md 'Recovery runbook').",
        record={"fault_class": FAULT_PEER_ABORT,
                "site": "collective.dispatch", "error": detail},
    ) from exc
