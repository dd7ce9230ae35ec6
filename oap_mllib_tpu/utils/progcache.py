"""Process-wide compiled-program registry: the compile-amortization core.

The reference pays a JNI + oneDAL kernel dispatch per phase; this port
pays XLA *compiles* instead — seconds of latency the first time any
program shape is seen.  Three things keep that cost amortized across the
many differently-sized fits of a long-lived service (the ROADMAP north
star), and this module is their shared registry:

1. **Program cache** — generalizes the ad-hoc ``functools.lru_cache``
   pattern that grew around the shard_map closures
   (``kmeans_ops._lloyd_model_sharded_fn``, ``pca_ops
   ._model_sharded_cov_fn``; the block-ALS runners rebuilt theirs every
   call): :func:`get_or_build` caches built callables process-wide,
   keyed by (algo, statics, mesh fingerprint), with LRU eviction and
   hit/miss/evict counters.
2. **Launch accounting** — the jitted entry points :func:`note` every
   launch under the same key space, so a fit summary can report how many
   programs it compiled vs reused, and :func:`launch` attributes the
   wall of first-seen launches to ``<phase>/compile`` and cache-hit
   launches to ``<phase>/execute`` in a :class:`~oap_mllib_tpu.utils
   .timing.Timings` (first-call wall = trace + XLA compile + first
   dispatch; hit wall = dispatch only for async launches).
3. **XLA ground truth** — :func:`xla_compile_count` counts actual
   backend compiles via jax.monitoring's
   ``/jax/core/compile/backend_compile_duration`` event, so benches and
   CI gates assert on what XLA really did, not what the registry thinks.

The persistent half lives in :func:`ensure_persistent_cache`: wiring
``Config.compilation_cache_dir`` through ``jax_compilation_cache_dir``
so a warm *process* skips XLA compilation entirely (DrJAX's
amortization argument, PAPERS.md, applied across process lifetimes).
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional

from oap_mllib_tpu.telemetry import metrics as _tm

_log = logging.getLogger("oap_mllib_tpu")

# -- registry ---------------------------------------------------------------


def _count(what: str, algo: str) -> None:
    """Mirror one registry increment into the process metrics registry
    (telemetry/metrics.py) — the summaries keep reading ``stats()``,
    exporters read ``oap_progcache_*_total{algo=...}``."""
    _tm.counter(
        f"oap_progcache_{what}_total", {"algo": algo},
        help=f"Program-cache {what} by algo key",
    ).inc()


class ProgramCache:
    """Keyed registry of built programs + launch counters.

    Two kinds of entries share one key space ``(algo, key)``:

    - *built* entries hold a value (a compiled/jit-wrapped callable) and
      are LRU-evicted past ``maxsize``;
    - *noted* entries hold no value — they only record that a jitted
      entry point has launched this program shape before (jit owns the
      executable; the registry owns the accounting).
    """

    def __init__(self, maxsize: int = 128, note_maxsize: int = 4096):
        self.maxsize = maxsize
        self.note_maxsize = note_maxsize
        self._lock = threading.RLock()
        self._built: "OrderedDict[tuple, Any]" = OrderedDict()
        self._noted: "OrderedDict[tuple, int]" = OrderedDict()
        self._counts: Dict[str, Dict[str, int]] = {}

    def _algo(self, algo: str) -> Dict[str, int]:
        return self._counts.setdefault(
            algo, {"hits": 0, "misses": 0, "evictions": 0}
        )

    def get_or_build(self, algo: str, key: tuple, build: Callable[[], Any]):
        """Return the cached value for ``(algo, key)``, building (and
        counting a miss) on first use.  The build runs outside the lock —
        building traces/compiles and must not serialize unrelated
        lookups; a racing duplicate build is benign (last one wins)."""
        full = (algo, key)
        with self._lock:
            if full in self._built:
                self._built.move_to_end(full)
                self._algo(algo)["hits"] += 1
                _count("hits", algo)
                return self._built[full]
            self._algo(algo)["misses"] += 1
            _count("misses", algo)
        value = build()
        with self._lock:
            self._built[full] = value
            self._built.move_to_end(full)
            while len(self._built) > self.maxsize:
                (ev_algo, _), _ = self._built.popitem(last=False)
                self._algo(ev_algo)["evictions"] += 1
                _count("evictions", ev_algo)
        return value

    def note(self, algo: str, key: tuple) -> bool:
        """Record one launch of a jit-managed program; True = first seen
        (the launch that pays trace + XLA compile)."""
        full = (algo, key)
        with self._lock:
            if full in self._noted:
                self._noted.move_to_end(full)
                self._noted[full] += 1
                self._algo(algo)["hits"] += 1
                _count("hits", algo)
                return False
            self._noted[full] = 1
            self._algo(algo)["misses"] += 1
            _count("misses", algo)
            while len(self._noted) > self.note_maxsize:
                (ev_algo, _), _ = self._noted.popitem(last=False)
                self._algo(ev_algo)["evictions"] += 1
                _count("evictions", ev_algo)
            return True

    def stats(self) -> Dict[str, Any]:
        """Aggregate + per-algo counters.  ``hit_rate`` is per-launch:
        of everything that went through the registry, the fraction that
        reused an existing program."""
        with self._lock:
            by_algo = {a: dict(c) for a, c in self._counts.items()}
        hits = sum(c["hits"] for c in by_algo.values())
        misses = sum(c["misses"] for c in by_algo.values())
        total = hits + misses
        return {
            "hits": hits,
            "misses": misses,
            "evictions": sum(c["evictions"] for c in by_algo.values()),
            "entries": len(self._built) + len(self._noted),
            "hit_rate": (hits / total) if total else None,
            "by_algo": by_algo,
        }

    def clear(self) -> None:
        """Drop every entry AND counter (tests; a cleared registry makes
        the next launch of everything a miss, but jit's own executable
        cache is untouched — only the accounting resets)."""
        with self._lock:
            self._built.clear()
            self._noted.clear()
            self._counts.clear()


_CACHE = ProgramCache()

# the registry is reachable from the serving dispatcher thread as well
# as fit flows; entry mutation is guarded inside ProgramCache._lock,
# and the module-level clear (a cross-thread registry reset) takes this
# tracked seam so the "locks" sanitizer can witness it
from oap_mllib_tpu.utils import locktrace as _locktrace  # noqa: E402

_CLEAR_LOCK = _locktrace.TrackedLock("progcache.clear")


def get_or_build(algo: str, key: tuple, build: Callable[[], Any]):
    return _CACHE.get_or_build(algo, key, build)


def note(algo: str, key: tuple) -> bool:
    return _CACHE.note(algo, key)


def stats() -> Dict[str, Any]:
    """The registry's counters, and ``programs_seen``: how many first
    launches the ledger has booked (the mark :func:`delta` reads from)."""
    out = _CACHE.stats()
    with _XLA_EVENTS_LOCK:
        out["programs_seen"] = len(_READY_LOG)
    return out


def clear() -> None:
    with _CLEAR_LOCK:
        _CACHE.clear()


def delta(before: Dict[str, Any]) -> Dict[str, Any]:
    """Per-fit registry activity: ``stats() - before`` for the scalar
    counters (models snapshot ``stats()`` at fit entry and attach the
    delta to the training summary), and under ``programs`` — only where
    there were any — what XLA made ready meanwhile: ``{name: {seconds,
    compiled, loaded}}``."""
    now = stats()
    out = {
        k: now[k] - before.get(k, 0) for k in ("hits", "misses", "evictions")
    }
    total = out["hits"] + out["misses"]
    out["hit_rate"] = (out["hits"] / total) if total else None
    # the programs made ready meanwhile, by name (the program ledger)
    programs = _programs_since(before.get("programs_seen", now["programs_seen"]))
    if programs:
        out["programs"] = programs
    return out


@contextlib.contextmanager
def launch(algo: str, key: tuple, timings=None, phase: Optional[str] = None,
           record_execute: bool = True):
    """Count one program launch and attribute its wall time.

    A first-seen key books the wall under ``<phase>/compile`` (for a jit
    entry the first call is where trace + XLA compile happen,
    synchronously, before the async dispatch); a hit books under
    ``<phase>/execute``.  ``record_execute=False`` is the per-chunk
    streamed-loop mode: misses still book compile, but the thousands of
    async per-chunk dispatch walls would be noise (the real device time
    is already recorded as the prefetch pipeline's ``compute`` split),
    so hits only count."""
    # the jitted-fit launch chokepoint doubles as the ``fit.execute``
    # fault-injection site (utils/faults.py): an armed device-OOM fault
    # raises here, BEFORE the launch is noted, exactly where a real XLA
    # RESOURCE_EXHAUSTED would surface — so the resilience ladder's
    # halved-chunk rung is testable without real hardware pressure
    from oap_mllib_tpu.utils.faults import maybe_fault

    maybe_fault("fit.execute")
    miss = _CACHE.note(algo, key)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        if timings is not None and phase is not None:
            if miss:
                timings.add(phase + "/compile", time.perf_counter() - t0)
            elif record_execute:
                timings.add(phase + "/execute", time.perf_counter() - t0)


# -- key helpers ------------------------------------------------------------


def array_key(*arrays) -> tuple:
    """Hashable signature of array arguments: (shape, dtype, sharding).

    Sharding rides along because jit specializes on it — the same shapes
    on a different mesh layout are a different executable."""
    out = []
    for a in arrays:
        try:  # tracers (an entry called inside an outer jit) may not
            shard = str(getattr(a, "sharding", ""))  # carry a sharding
        except Exception:
            shard = ""
        out.append((
            tuple(getattr(a, "shape", ())),
            str(getattr(a, "dtype", type(a).__name__)),
            shard,
        ))
    return tuple(out)


def mesh_fingerprint(mesh) -> tuple:
    """Stable hashable identity of a mesh: axis layout + device ids +
    platform.  Two fits on meshes with this fingerprint can share one
    compiled shard_map program."""
    devs = [d for d in mesh.devices.flat]
    return (
        tuple(mesh.axis_names),
        tuple(mesh.devices.shape),
        tuple(d.id for d in devs),
        devs[0].platform if devs else "none",
    )


def backend_fingerprint() -> tuple:
    """Identity of the default-device world, for single-program entry
    points that jit without an explicit mesh (GSPMD decides placement
    from the argument shardings, which array_key captures)."""
    import jax

    return (jax.default_backend(), len(jax.devices()), jax.process_count())


def key_digest(key) -> str:
    """Short stable hex digest of a hashable-repr key tuple, for layers
    that file registry-style keys on DISK (the tuning cache names its
    JSON entries with this; a raw repr would produce filesystem-hostile
    names).  repr-based, so only use with keys built from primitives —
    exactly what the registry key conventions already require."""
    import hashlib

    return hashlib.sha1(repr(key).encode()).hexdigest()[:16]


# -- XLA compile ground truth, and the program ledger -------------------------

_XLA_EVENTS = {"count": 0, "secs": 0.0}
# the compile-event listener fires on whatever thread XLA compiles on,
# and the counters are read from fit flows AND the serving dispatcher
# thread (the request tracer's compile attribution) — same witnessed
# seam as _CLEAR_LOCK
_XLA_EVENTS_LOCK = _locktrace.TrackedLock("progcache.xla_events")
_xla_listener_installed = False
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_LEDGER_FIELD = {_TRACE_EVENT: "trace_s", _LOWER_EVENT: "lower_s",
                 _BACKEND_COMPILE_EVENT: "backend_s"}  # what the ledger keeps
READY_SECONDS = "oap_program_ready_seconds_total"
PROGRAMS_COMPILED = "oap_programs_compiled_total"

# every first launch in arrival order, under _XLA_EVENTS_LOCK: (the name
# jax gives the program — the jitted function's, ``"?"`` for an event that
# names none —, trace_s, lower_s, backend_s, load_s, loaded from the
# persistent cache).  The ledger and a fit's view are groupings of it; one
# record an executable made ready, so it grows as jit's own caches do
_READY_LOG: list = []
# jax emits a program's events in order on the thread that makes it
# ready: trace, lowering, then — inside the backend event — the
# persistent cache's hit and retrieval time, which carry no name
_pending = threading.local()


def _program_name(fun_name) -> str:
    """``write_piece`` of ``jit(write_piece)`` (lowering and backend
    events wrap the name the trace event gives bare)."""
    name = str(fun_name) if fun_name else "?"
    if name.endswith(")") and "(" in name:
        name = name[name.index("(") + 1:-1] or "?"
    return name


def _on_trace_start(event, value, **kwargs):
    # jitted functions traced INSIDE another's trace (every jnp call of
    # a program's body) report durations that the outer one contains:
    # only the outermost is booked
    if event == _TRACE_EVENT:
        _pending.tracing = getattr(_pending, "tracing", 0) + 1


def _on_cache_hit(event, **kwargs):
    if event == _CACHE_HIT_EVENT:
        _pending.loaded = True


def _on_duration(event, duration_secs, **kwargs):
    secs = float(duration_secs)
    if event == _CACHE_RETRIEVAL_EVENT:
        _pending.load_s = secs
        return
    field = _LEDGER_FIELD.get(event)
    if field is None:
        return
    tracing = getattr(_pending, "tracing", 0)
    if event == _TRACE_EVENT:
        tracing = _pending.tracing = max(tracing - 1, 0)
        if tracing:
            return
    name = _program_name(kwargs.get("fun_name"))
    # trace and lowering wait here, by name, for the backend event that
    # makes them a first launch's
    ready = getattr(_pending, "ready", None)
    if ready is None:
        ready = _pending.ready = {}
    if event == _BACKEND_COMPILE_EVENT:
        before = ready.pop(name, {})
        ready.clear()
        _book_first_launch(
            name, before.get("trace_s", 0.0), before.get("lower_s", 0.0), secs
        )
    else:
        mine = ready.setdefault(name, {})
        mine[field] = mine.get(field, 0.0) + secs
    if not tracing:
        # lowering or a compile inside a trace (an eager op on a
        # constant) is inside that trace's seconds already
        _ready_total().inc(secs)


def _book_first_launch(name: str, trace_s: float, lower_s: float,
                       secs: float) -> None:
    """One backend event: an executable compiled, or loaded from the
    persistent cache where the hit event came first on this thread."""
    loaded = bool(getattr(_pending, "loaded", False))
    load_s = getattr(_pending, "load_s", 0.0) if loaded else 0.0
    _pending.loaded, _pending.load_s = False, 0.0
    with _XLA_EVENTS_LOCK:
        _XLA_EVENTS["count"] += 1
        _XLA_EVENTS["secs"] += secs
        _READY_LOG.append((name, trace_s, lower_s, secs, load_s, loaded))
    _tm.counter(
        "oap_xla_compiles_total",
        help="Real XLA backend compiles (jax monitoring event)",
    ).inc()
    _tm.counter(
        "oap_xla_compile_seconds_total",
        help="Wall spent in XLA backend compilation",
    ).inc(secs)
    _tm.histogram(
        "oap_xla_compile_seconds",
        help="Per-program XLA backend compile wall",
    ).observe(secs)
    if not loaded:
        _compiled_total().inc()


def _ready_total():
    return _tm.counter(
        READY_SECONDS,
        help="Trace + lowering + load-or-compile: what first launches paid",
    )


def _compiled_total():
    return _tm.counter(
        PROGRAMS_COMPILED,
        help="Backend compiles the persistent cache did not serve",
    )


def _never_raise(listener):
    """jax calls listeners from inside its compile path: a fault in the
    accounting must not become a fault of the program."""
    def guarded(*args, **kwargs):
        try:
            listener(*args, **kwargs)
        except Exception:  # noqa: BLE001 - the boundary that must keep running
            _log.debug("progcache listener failed", exc_info=True)
    return guarded


def _install_xla_listener() -> None:
    global _xla_listener_installed
    if _xla_listener_installed:
        return
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_never_raise(_on_duration))
    monitoring.register_event_listener(_never_raise(_on_cache_hit))
    monitoring.register_scalar_listener(_never_raise(_on_trace_start))
    # the two totals exist (at 0) from the first snapshot on: a reader
    # tells "nothing compiled" from "a program that has no ledger"
    _ready_total()
    _compiled_total()
    _xla_listener_installed = True


def xla_compile_count() -> int:
    """Monotone count of real XLA backend compiles in this process (the
    ``/jax/core/compile/backend_compile_duration`` event; a program
    loaded from the persistent cache fires it too).  Snapshot
    before/after a region and subtract — that difference is the ground
    truth the compile-sweep bench and the CI gate assert on (the
    registry's miss count is what *we* think; this is what XLA did)."""
    _install_xla_listener()
    with _XLA_EVENTS_LOCK:
        return _XLA_EVENTS["count"]


def xla_compile_secs() -> float:
    """Cumulative seconds spent in XLA backend compilation (same event
    stream as :func:`xla_compile_count`)."""
    _install_xla_listener()
    with _XLA_EVENTS_LOCK:
        return _XLA_EVENTS["secs"]


def program_ledger() -> Dict[str, Dict[str, Any]]:
    """What making each program of this process ready cost, by name:
    ``launches_first_seen`` (first launches: backend events under the
    name, one an executable), of which ``compiled`` were compiled and
    the rest loaded from the persistent cache (``from_persistent_cache``:
    all of them were), ``trace_s`` (the outermost trace), ``lower_s``,
    ``backend_s`` (compile, or the load: ``load_s`` of it).  The sum of
    the three is in ``oap_program_ready_seconds_total``, the compiled
    count in ``oap_programs_compiled_total``."""
    _install_xla_listener()
    with _XLA_EVENTS_LOCK:
        log = list(_READY_LOG)
    out: Dict[str, Dict[str, Any]] = {}
    for name, trace_s, lower_s, backend_s, load_s, loaded in log:
        per = out.setdefault(name, {
            "launches_first_seen": 0, "compiled": 0, "trace_s": 0.0,
            "lower_s": 0.0, "backend_s": 0.0, "load_s": 0.0,
        })
        per["launches_first_seen"] += 1
        per["compiled"] += int(not loaded)
        per["trace_s"] += trace_s
        per["lower_s"] += lower_s
        per["backend_s"] += backend_s
        per["load_s"] += load_s
    for per in out.values():
        per["from_persistent_cache"] = per["compiled"] == 0
    return out


def _programs_since(mark: int) -> Dict[str, Dict[str, Any]]:
    """First launches ``mark`` onward, by name: seconds (trace +
    lowering + load-or-compile), how many were compiled and loaded."""
    with _XLA_EVENTS_LOCK:
        log = _READY_LOG[mark:]
    out: Dict[str, Dict[str, Any]] = {}
    for name, trace_s, lower_s, backend_s, _, loaded in log:
        per = out.setdefault(name, {"seconds": 0.0, "compiled": 0, "loaded": 0})
        per["seconds"] += trace_s + lower_s + backend_s
        per["loaded" if loaded else "compiled"] += 1
    return out


# install at import so compiles that happen before the first explicit
# snapshot (e.g. a warm-up fit) are still counted into the baseline
_install_xla_listener()


# -- persistent (cross-process) compilation cache ---------------------------

_persist_applied: Optional[str] = None

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def ensure_persistent_cache(cache_dir: str) -> None:
    """Wire ``Config.compilation_cache_dir`` into jax's persistent
    compilation cache (idempotent; re-applies only when the dir
    changes).  With a dir set, XLA executables serialize to disk keyed
    by (HLO, compile options, backend version) — a warm process skips
    backend compilation entirely, which is the cross-process half of
    compile amortization (shape bucketing is the within-process half).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, the process already has
    its cache and jax's own handling of that variable stands: this
    function then sets no directory, and a ``Config`` value naming a
    different one is an error, not an override (the path is part of the
    cache key, so a second directory would never hit the first).

    The min-time threshold is zeroed so the small per-chunk streamed
    programs persist too — jax's default only persists programs that
    took >1s to compile, which would exclude most of this framework's
    kernels on a warm CPU tier."""
    global _persist_applied
    if not cache_dir or _persist_applied == cache_dir:
        return
    import os

    import jax

    env_dir = os.environ.get(CACHE_ENV, "")
    if env_dir and os.path.abspath(cache_dir) != os.path.abspath(env_dir):
        raise ValueError(
            f"Config.compilation_cache_dir={cache_dir!r} differs from "
            f"{CACHE_ENV}={env_dir!r}: the environment owns the compile "
            "cache of this process; unset one of the two"
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not env_dir:
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc,
        )

        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # jax pins its cache object to the first dir it initialized
        # with; drop it so the (possibly changed) dir takes effect — it
        # re-creates lazily on the next compile
        _cc.reset_cache()
    _persist_applied = cache_dir


def use_checkout_cache(fixed_dir: str) -> str:
    """Launch scripts (``chip_smoke.py``, ``bench.py``): the compile
    cache lives where ``JAX_COMPILATION_CACHE_DIR`` says and, only where
    that is unset, at ``fixed_dir`` — one fixed path inside the checkout
    (the path is part of the cache key: a directory that moves never
    hits).  Returns the directory in effect."""
    import os

    from oap_mllib_tpu.config import set_config

    cache_dir = os.environ.get(CACHE_ENV, "") or os.path.abspath(fixed_dir)
    set_config(compilation_cache_dir=cache_dir)
    ensure_persistent_cache(cache_dir)
    return cache_dir
