"""Telemetry exporters: JSONL event sink, Prometheus dump, fit reports.

Three consumers, three formats, one source of truth (the span tree +
the metrics registry):

- **JSON-lines sink** — armed by ``Config.telemetry_log`` (env
  ``OAP_MLLIB_TPU_TELEMETRY_LOG``).  Every fit finalization appends one
  record per closed span (type ``"span"``: path, duration, count,
  attrs) followed by a full registry snapshot (type ``"metrics"``), and
  the ONE registered exit hook (:func:`shutdown`, below) appends a
  final flight-recorder drain + snapshot when the process ends.
  Records are rank-tagged and multi-process worlds write per-rank files
  (``<path>.rank<r>`` when the world is larger than one process), so a
  world's files concatenate into one mergeable stream.  Records carry a
  monotonic per-process ``seq`` instead of wall-clock timestamps — the
  deterministic-accounting contract (telemetry/metrics.py).
- **Prometheus text exposition** — :func:`render_prometheus`
  (re-exported from telemetry/metrics.py) for scrapes and CI diffs.
- **Human report** — :func:`report` renders one fit's span tree with
  its per-phase walls, streamed overlap, compile split, progcache and
  resilience counters; with no summary it renders process-wide
  highlights instead (bench.py prints it).

Telemetry-off is one falsy-string check per fit (`Config.telemetry_log`
empty -> no file is ever opened).

**The atexit ordering contract (ISSUE 14):** interpreter-exit work used
to race — the sink's final snapshot, the fleet metrics server teardown,
and the flight-recorder drain each hung off their own implicit
lifecycle, so which ran first depended on registration order across
modules.  :func:`shutdown` is now the ONE registered exit hook (oaplint
``atexit-outside-shutdown`` keeps it unique): it drains the flight
recorder into the sink, appends the final metrics snapshot, and stops
the fleet endpoint — in that order, so the last scrape surface outlives
the last record it could be asked about and no recorder tail is lost.
"""

from __future__ import annotations

import atexit
import itertools
import json
import threading
from typing import Any, Dict, List, Optional

from oap_mllib_tpu.config import get_config
from oap_mllib_tpu.telemetry import metrics as _metrics
from oap_mllib_tpu.telemetry.spans import Span
from oap_mllib_tpu.utils import locktrace

_seq = itertools.count()
# tracked (utils/locktrace.py): the sink lock serializes writers from
# fit threads and the exit hook — a seam the "locks" sanitizer watches
_lock = locktrace.TrackedLock("telemetry.sink", threading.Lock())
_shutdown_registered = False


def _rank() -> int:
    return int(get_config().process_id)


def sink_path() -> Optional[str]:
    """The armed JSONL path for THIS process, or None when telemetry
    logging is off.  Multi-process worlds get a per-rank suffix so
    concurrent writers never interleave inside one file."""
    cfg = get_config()
    path = cfg.telemetry_log
    if not path:
        return None
    if cfg.num_processes > 1:
        return f"{path}.rank{cfg.process_id}"
    return path


def _write_lines(path: str, records: List[Dict[str, Any]]) -> None:
    # the lock EXISTS to serialize appends into one sink file — the
    # file write is the critical section, not an accident of it
    # oaplint: disable=blocking-while-locked -- the sink lock's one job IS serializing this append
    with _lock, open(path, "a") as f:
        for rec in records:
            f.write(json.dumps(rec, sort_keys=True, default=str) + "\n")


def register_shutdown() -> None:
    """Register :func:`shutdown` as the process's ONE exit hook
    (idempotent).  Called by the first sink emit and by the fleet
    endpoint arm — whichever exit-sensitive subsystem wakes first."""
    global _shutdown_registered
    if _shutdown_registered:
        return
    _shutdown_registered = True
    atexit.register(shutdown)


def shutdown() -> None:
    """The ordered interpreter-exit sequence (the atexit contract):

    1. drain the flight recorder + append the final metrics snapshot to
       the JSONL sink (one batch, so the tail and the snapshot land
       together and post-mortem tooling sees a complete stream);
    2. stop the fleet metrics endpoint LAST — a scraper can read the
       final state up to the moment the process stops answering.

    Each step is isolated: a failed sink write must not strand the
    server, and a failed teardown must not mask the exit."""
    try:
        _emit_final_snapshot()
    finally:
        from oap_mllib_tpu.telemetry import fleet as _fleet

        _fleet.stop_server()


def _emit_final_snapshot() -> None:
    path = sink_path()
    if path is None:
        return
    from oap_mllib_tpu.telemetry import flightrec

    records: List[Dict[str, Any]] = []
    events = flightrec.drain_new()
    if events:
        records.append({
            "type": "flightrec",
            "final": True,
            "rank": _rank(),
            "seq": next(_seq),
            "events": events,
        })
    records.append({
        "type": "metrics",
        "final": True,
        "rank": _rank(),
        "seq": next(_seq),
        "metrics": _metrics.snapshot(),
    })
    try:
        _write_lines(path, records)
    except OSError:
        pass  # a torn-down filesystem at exit must not mask the real exit


def emit_requests(records: List[Dict[str, Any]]) -> int:
    """Append finalized request-ledger records (serving/reqtrace.py) to
    the sink as ``type: "request"`` lines — rank- and seq-tagged like
    every other record, so dev/oaptrace.py merges them into the same
    per-rank stream.  Returns the number written (0 when the sink is
    off; an OSError is swallowed — the sink is a diagnosis channel,
    never a liveness one)."""
    path = sink_path()
    if path is None or not records:
        return 0
    register_shutdown()
    rank = _rank()
    out = [
        dict(rec, type="request", rank=rank, seq=next(_seq))
        for rec in records
    ]
    try:
        _write_lines(path, out)
    except OSError:
        return 0
    return len(out)


def emit_fit(root: Span) -> None:
    """Append one record per span in ``root``'s tree (depth-first) plus
    a registry snapshot — the per-fit JSONL batch.  No-op when the sink
    is off (one config-string check)."""
    path = sink_path()
    if path is None:
        return
    register_shutdown()
    rank = _rank()
    records: List[Dict[str, Any]] = []
    for span_path, sp in root.walk():
        rec: Dict[str, Any] = {
            "type": "span",
            "fit": root.name,
            "path": span_path,
            "name": sp.name,
            "duration_s": sp.duration_s,
            "count": sp.count,
            "rank": rank,
            "seq": next(_seq),
        }
        if sp.attrs:
            rec["attrs"] = sp.attrs
        records.append(rec)
    # flight-recorder drain (telemetry/flightrec.py): the events recorded
    # since the last fit's drain ride the sink as one batch, so
    # dev/oaptrace.py can rebuild a real per-rank timeline (span
    # open/close walls + collective fingerprints) and align ranks
    from oap_mllib_tpu.telemetry import flightrec

    events = flightrec.drain_new()
    if events:
        records.append({
            "type": "flightrec",
            "fit": root.name,
            "rank": rank,
            "seq": next(_seq),
            "events": events,
        })
    records.append({
        "type": "metrics",
        "fit": root.name,
        "rank": rank,
        "seq": next(_seq),
        "metrics": _metrics.snapshot(),
    })
    _write_lines(path, records)


# -- fit-summary attachment ---------------------------------------------------


def _summary_get(summary, key: str):
    if summary is None:
        return None
    if isinstance(summary, dict):
        return summary.get(key)
    return getattr(summary, key, None)


def finalize_fit(summary) -> None:
    """Close out one fit's telemetry: fill the root span's wall (sum of
    its top-level phases when the fit body was not itself timed), attach
    ``summary["telemetry"]`` = ``{fit, rank, spans, metrics}`` (dict
    summaries get the key, object summaries the attribute — the
    ``resilience.merge_stats`` convention), and flush the JSONL batch
    when the sink is armed.  Estimators call this once per fit at their
    outermost accelerated return."""
    timings = _summary_get(summary, "timings")
    if timings is None or summary is None:
        return
    # sanitizer fit-boundary hook (utils/sanitizers.py): attach the armed
    # set + the fit's collective fingerprint, and cross-check the
    # fingerprint across ranks — the backstop that converts a TAIL
    # divergence (extra collectives after the last common op) into a
    # diagnostic at the fit boundary.  One config-string check when off.
    from oap_mllib_tpu.utils import sanitizers as _san

    _san.finalize_fit_sanitizers(summary)
    root = timings.root
    if root.count == 0:
        root.duration_s = sum(c.duration_s for c in root.children)
    # fleet fit-boundary hook (telemetry/fleet.py): land the fleet block
    # + fleet span attrs, refresh /healthz state, and (metrics_port
    # armed) make sure the live endpoint is up.  One config check each
    # when the control plane is disarmed.
    from oap_mllib_tpu.telemetry import fleet as _fleet

    _fleet.finalize_fit(summary, root)
    # balance fit-boundary hook (parallel/balance.py, ISSUE 15): land
    # the ``balance`` block (plan origin/weights/extents + the re-plan
    # decision trail + any supervisor hint) and a ``balance`` child
    # span, then reset the controller's per-fit state.  One None-check
    # when no plan is active.
    from oap_mllib_tpu.parallel import balance as _balance

    _balance.finalize_fit(summary, root)
    _metrics.counter(
        "oap_fit_total", {"fit": root.name},
        help="Completed fits by root span name",
    ).inc()
    _metrics.histogram(
        "oap_fit_seconds", {"fit": root.name},
        help="Fit wall per root span",
    ).observe(root.duration_s)
    payload = {
        "fit": root.name,
        "rank": _rank(),
        "spans": root.as_dict(),
        "metrics": _metrics.snapshot(),
    }
    if isinstance(summary, dict):
        summary["telemetry"] = payload
    else:
        summary.telemetry = payload
    emit_fit(root)


# -- human-readable report ----------------------------------------------------


def _fmt_s(v: float) -> str:
    return f"{v * 1e3:.1f} ms" if v < 1.0 else f"{v:.3f} s"


def _span_lines(sp: Span, depth: int, out: List[str]) -> None:
    pad = "  " * depth
    extra = ""
    if sp.count > 1:
        extra += f"  x{sp.count}"
    coll = sp.attrs.get("collectives")
    if coll:
        ops = sum(c["ops"] for c in coll.values())
        byt = sum(c["bytes"] for c in coll.values())
        extra += f"  [collectives: {ops} ops, {byt} B]"
    out.append(f"{pad}{sp.name:<24s} {_fmt_s(sp.duration_s):>10s}{extra}")
    for c in sp.children:
        _span_lines(c, depth + 1, out)


def report(summary=None) -> str:
    """Render a per-fit telemetry report (span tree + the counters that
    matter), or — with no summary — a process-wide metrics digest."""
    if summary is None:
        return _process_report()
    timings = _summary_get(summary, "timings")
    lines: List[str] = []
    if timings is not None:
        root = timings.root
        if root.count == 0:
            root.duration_s = sum(c.duration_s for c in root.children)
        lines.append(f"== telemetry: {root.name} ==")
        _span_lines(root, 0, lines)
        for phase in [c.name for c in root.children]:
            eff = timings.overlap_efficiency(phase)
            if eff is not None:
                lines.append(
                    f"  {phase}: overlap efficiency "
                    f"{eff:.1%} (staging hidden behind compute)"
                )
            split = timings.compile_split(phase)
            if split is not None:
                lines.append(
                    f"  {phase}: compile {_fmt_s(split['compile'])}, "
                    f"execute {_fmt_s(split['execute'])}"
                )
    pc = _summary_get(summary, "progcache")
    if pc:
        rate = pc.get("hit_rate")
        lines.append(
            f"  progcache: {pc.get('hits', 0)} hits / "
            f"{pc.get('misses', 0)} misses"
            + (f" ({rate:.0%} hit rate)" if rate is not None else "")
        )
        if pc.get("misses"):
            # the program ledger's view of this fit (utils/progcache.py):
            # what those misses made ready, the costliest first
            programs = pc.get("programs", {})
            for name in sorted(programs, key=lambda n: -programs[n]["seconds"]):
                per = programs[name]
                lines.append(
                    f"    {name}: {_fmt_s(per['seconds'])} "
                    f"({per['compiled']} compiled, {per['loaded']} loaded)"
                )
    rs = _summary_get(summary, "resilience")
    if rs and (rs.get("faults") or rs.get("retries")):
        lines.append(
            f"  resilience: {rs.get('faults', 0)} faults, "
            f"{rs.get('retries', 0)} retries, "
            f"{rs.get('degradations', 0)} degradations "
            f"({rs.get('backoff_s', 0.0):.2f}s backoff)"
        )
    return "\n".join(lines)


def _series_total(snap: Dict[str, Any], name: str) -> float:
    series = snap.get(name, {})
    total = 0.0
    for v in series.values():
        total += v["sum"] if isinstance(v, dict) else v
    return total


def _process_report() -> str:
    snap = _metrics.snapshot()
    lines = ["== telemetry: process metrics =="]
    rows = [
        ("fits completed", _series_total(snap, "oap_fit_total"), "d"),
        ("XLA compiles", _series_total(snap, "oap_xla_compiles_total"), "d"),
        ("XLA compile wall",
         _series_total(snap, "oap_xla_compile_seconds_total"), "s"),
        ("progcache hits", _series_total(snap, "oap_progcache_hits_total"), "d"),
        ("progcache misses",
         _series_total(snap, "oap_progcache_misses_total"), "d"),
        ("collective ops", _series_total(snap, "oap_collective_ops_total"), "d"),
        ("collective bytes",
         _series_total(snap, "oap_collective_bytes_total"), "d"),
        ("streamed chunks", _series_total(snap, "oap_prefetch_chunks_total"), "d"),
        ("streamed rows", _series_total(snap, "oap_stream_rows_total"), "d"),
        ("bytes staged", _series_total(snap, "oap_stream_bytes_staged_total"), "d"),
        ("resilience faults",
         _series_total(snap, "oap_resilience_faults_total"), "d"),
        ("serve requests", _series_total(snap, "oap_serve_requests_total"), "d"),
        ("serve batches", _series_total(snap, "oap_serve_batches_total"), "d"),
    ]
    for label, v, kind in rows:
        val = _fmt_s(v) if kind == "s" else str(int(v))
        lines.append(f"  {label:<20s} {val}")
    # the serving summary block (registry/batcher/sweep totals + p50/p99
    # latency from the factor-4 log-bucket histogram) when the plane
    # answered anything this process lifetime
    if _series_total(snap, "oap_serve_requests_total"):
        from oap_mllib_tpu.serving.registry import serving_summary

        sv = serving_summary()
        lines.append(
            f"  serving: {sv['requests']} requests / {sv['batches']} "
            f"batches, {sv['pad_rows']} pad rows, p50 "
            f"{_fmt_s(sv.get('latency_p50_s', 0.0))}, p99 "
            f"{_fmt_s(sv.get('latency_p99_s', 0.0))}"
        )
    return "\n".join(lines)
