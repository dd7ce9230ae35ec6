"""Span-tree tracing: the storage layer under every fit's phase timings.

PRs 1-3 each grew a flat stats object (``Timings`` record list,
``PrefetchStats``, progcache counters, ``ResilienceStats``) with no
shared model.  This module is the shared model's skeleton: a fit is a
tree of named :class:`Span` nodes — the root is the fit itself
(``kmeans.fit``, ``pca.fit``, ``als.fit``), its children are the phases
the estimators already time (``table_convert``, ``init_centers``,
``lloyd_loop``, ...), and *their* children are the per-pass splits the
streamed pipeline records (``stage``/``transfer``/``compute``) and the
program-cache launch attribution (``compile``/``execute``).

``utils/timing.Timings`` is now a **view** over this tree — its
``as_dict``/``subphases``/``overlap_efficiency``/``compile_split``
accessors return exactly what the flat record list returned, so every
existing caller and test keeps working — and the tree itself is what the
exporters (telemetry/export.py) serialize.

Clocks are monotonic only (``time.perf_counter``): span durations and
orderings are deterministic accounting, never wall-clock timestamps.

A thread-local *active span* stack lets deeper layers attach to whatever
phase is running without threading a handle through every signature —
the collective facade (parallel/collective.py) books its per-op bytes
and dispatch wall onto ``current_span()``, and ``data/`` and ``ops/``
open sub-spans of the running phase through :func:`child`
(``table_convert/upload``, ``init_centers/rounds``).  When a
``jax.profiler`` trace is active (utils/profiling.py), entering a span
also emits a ``jax.profiler.TraceAnnotation`` named by the span's path
below the fit root, so the same names line up in TensorBoard/XProf;
with no trace running the annotation is skipped behind one module-level
bool — the telemetry-off cheap-guard contract.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from oap_mllib_tpu.telemetry import flightrec
from oap_mllib_tpu.utils import profiling

_SEP = "/"


class Span:
    """One named node in a fit's span tree.

    ``duration_s`` accumulates across repeated entries of the same path
    (streamed passes re-enter their phase once per pass — the flat
    ``Timings.as_dict`` summed duplicate phases, the tree accumulates on
    the node, same totals).  ``count`` is the number of explicit
    recordings; implicitly-created path containers keep ``count == 0``
    and are excluded from the flat views, matching the old record list
    (which only ever held explicitly-added phases).  ``path`` is the
    ``a/b`` path below the tree's root (empty on a root): the key of the
    flat views and the name of the span's trace annotation.
    """

    __slots__ = ("name", "path", "duration_s", "count", "attrs", "children")

    def __init__(self, name: str, path: str = ""):
        self.name = name
        self.path = path
        self.duration_s = 0.0
        self.count = 0
        self.attrs: Dict[str, Any] = {}
        self.children: List["Span"] = []

    def child(self, name: str) -> "Span":
        """Find-or-create the child span ``name`` (first match wins, so
        repeated phases accumulate onto one node in first-seen order)."""
        for c in self.children:
            if c.name == name:
                return c
        c = Span(name, self.path + _SEP + name if self.path else name)
        self.children.append(c)
        return c

    def node(self, path: str) -> "Span":
        """Find-or-create the descendant at ``a/b/c``-style ``path``."""
        n = self
        for part in path.split(_SEP):
            n = n.child(part)
        return n

    def record(self, seconds: float) -> None:
        self.duration_s += seconds
        self.count += 1

    def note_collective(self, op: str, nbytes: int, dispatch_s: float,
                        ops: int = 1) -> None:
        """Accumulate collective dispatches (one, or ``ops`` that ran
        inside one program) onto this span's attributes
        (parallel/collective.py calls this on ``current_span()``)."""
        per = self.attrs.setdefault("collectives", {}).setdefault(
            op, {"ops": 0, "bytes": 0, "dispatch_s": 0.0}
        )
        per["ops"] += int(ops)
        per["bytes"] += int(nbytes)
        per["dispatch_s"] += float(dispatch_s)

    # -- flat views (the Timings compatibility surface) ----------------------

    def flat(self) -> Dict[str, float]:
        """``{path: seconds}`` over explicitly-recorded descendants, in
        first-recorded order — exactly the old ``Timings.as_dict``."""
        out: Dict[str, float] = {}
        stack = [("", c) for c in reversed(self.children)]
        while stack:
            prefix, n = stack.pop()
            path = prefix + n.name
            if n.count > 0:
                out[path] = out.get(path, 0.0) + n.duration_s
            stack.extend(
                (path + _SEP, c) for c in reversed(n.children)
            )
        return out

    def as_dict(self) -> Dict[str, Any]:
        """JSON-ready tree (exporters; ``summary["telemetry"]["spans"]``)."""
        out: Dict[str, Any] = {
            "name": self.name,
            "duration_s": self.duration_s,
            "count": self.count,
        }
        if self.attrs:
            out["attrs"] = self.attrs
        if self.children:
            out["children"] = [c.as_dict() for c in self.children]
        return out

    def walk(self, prefix: str = ""):
        """Yield ``(path, span)`` depth-first, self included."""
        path = prefix + self.name
        yield path, self
        for c in self.children:
            yield from c.walk(path + _SEP)

    def __repr__(self) -> str:
        return (
            f"Span({self.name!r}, {self.duration_s:.3f}s, "
            f"children={len(self.children)})"
        )


# -- thread-local active-span stack ------------------------------------------

_tls = threading.local()


def current_span() -> Optional[Span]:
    """The innermost span currently entered on THIS thread, or None.
    Deeper layers (collectives) attach measurements here without a
    handle threaded through the call chain."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


class _Entry:
    """One timed entry of a span (what :func:`enter` and :func:`child`
    hand to ``with``).  A class with slots, not a generator: a fit opens
    a leaf around every ``device_put`` and every wait of its upload, and
    a generator-based manager costs more than the clock pair it wraps."""

    __slots__ = ("span", "annotate", "_stack", "_ann", "_rec", "_t0")

    def __init__(self, span: Span, annotate: bool = True):
        self.span = span
        self.annotate = annotate

    def __enter__(self) -> Span:
        span = self.span
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(span)
        self._stack = stack
        ann = None
        if self.annotate and profiling.trace_active():
            import jax

            ann = jax.profiler.TraceAnnotation(span.path or span.name)
            ann.__enter__()
        self._ann = ann
        # both guards are read once an entry: the close event belongs to
        # the recorder that saw the open
        rec = self._rec = flightrec.enabled()
        if rec:
            flightrec.record("span_open", span.name)
        self._t0 = time.perf_counter()
        return span

    def __exit__(self, *exc) -> bool:
        dt = time.perf_counter() - self._t0
        span = self.span
        span.record(dt)
        if self._rec:
            flightrec.record("span_close", span.name, f"{dt:.6f}s")
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._stack.pop()
        return False


def enter(span: Span, annotate: bool = True) -> _Entry:
    """Time one entry of ``span``: push it as the thread's active span,
    record the monotonic wall on exit, and — only when a jax.profiler
    trace is running (one bool check) — emit a TraceAnnotation so the
    span shows up on the XProf timeline under its path below the root
    (``table_convert``, ``table_convert/upload``: a top-level phase
    keeps its bare name, a sub-span says whose it is).  With the
    flight recorder armed (telemetry/flightrec.py — one config check
    when off), span open/close land in the event ring so post-mortems
    and merged timelines see which phases were in flight."""
    return _Entry(span, annotate)


def child(name: str):
    """Time ``name`` as a sub-span of the thread's active span: how
    ``data/`` and ``ops/`` split the phase that called them
    (``table_convert/upload``, ``init_centers/rounds``) without a
    ``timings`` handle in their signatures and without reading a clock.
    Outside any fit nothing is timed or pushed; the span yielded then is
    a detached one, so a call site sets ``attrs`` unconditionally."""
    stack = getattr(_tls, "stack", None)
    if not stack:
        return contextlib.nullcontext(Span(name))
    return _Entry(stack[-1].child(name))


# -- the seams where the host meets the device --------------------------------
# Leaf names, each opened with ``child`` where the host thread itself
# blocks or prepares (docs/observability.md, "Spans"):
#   put    inside a ``jax.device_put`` call (``attrs["bytes"]`` handed over)
#   land   blocked until uploaded bytes had landed / an in-place write ended
#   cast   blocked on the host threads that cast a row block
#   launch inside a call that hands the device a program: short, unless
#          the runtime holds the call until the device has room for it
#   fetch  blocked on device RESULTS (``attrs["bytes"]`` brought back)
# A phase's wall minus its ``fetch`` and ``land`` descendants is its host
# gap: seconds in which nothing the host waited on was running.
PUT, LAND, CAST, LAUNCH, FETCH = "put", "land", "cast", "launch", "fetch"


def _host_nbytes(out) -> int:
    """Bytes of the host arrays in ``out`` (an array, or a tuple / list
    of them); a device array that was only waited for brought nothing."""
    if isinstance(out, (tuple, list)):
        return sum(_host_nbytes(o) for o in out)
    return out.nbytes if isinstance(out, (np.ndarray, np.generic)) else 0


def launch(program, *args, **kwargs):
    """``program(*args, **kwargs)`` — a call that returns device arrays
    still to be computed — as one entry of the ``launch`` leaf of the
    thread's active span: the host seconds INSIDE the call.  The runtime
    may hold it until the device has finished what was queued before
    (seen where the device's memory is nearly full), so a long ``launch``
    is the host blocked on the device, not the device waiting for it."""
    with child(LAUNCH):
        return program(*args, **kwargs)


def fetch(get, *args):
    """``get(*args)`` — a read that blocks the host on device results
    (``np.asarray`` of a device array, ``jax.block_until_ready``) — as
    one entry of the ``fetch`` leaf of the thread's active span, with
    what came back to the host added to the leaf's ``attrs["bytes"]``."""
    with child(FETCH) as span:
        out = get(*args)
        span.attrs["bytes"] = span.attrs.get("bytes", 0) + _host_nbytes(out)
    return out
