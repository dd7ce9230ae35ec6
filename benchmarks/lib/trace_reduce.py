"""From a profiler trace (``.xplane.pb``) to intervals, and from intervals to
the numbers the per-layer metrics ask for.

The arithmetic works on plain lists of ``(start_s, end_s, name)`` so that it
can be checked on a hand-made event list (``tests/test_trace_reduce.py``);
``load`` is the only part that touches the profiler's file format.

What a metric file can ask of a ``Trace``:

- ``busy_s(dev, lo, hi)``: seconds in [lo, hi] in which an operation ran on
  that device (the union of the operations' intervals);
- ``busy_inside(dev, names)``: the same inside the host annotations of those
  names, and the annotations' own length;
- ``exposed_s(dev, is_wanted)``: seconds in which operations whose name the
  predicate accepts ran while no other operation did (collective time that
  nothing hides);
- ``top_ops(n)`` and ``idle_gaps(dev, phases, n)`` for the breakdown.
"""

import glob
import os

DEVICE_PLANE_PREFIX = "/device:"
DEVICE_OPS_LINE = "XLA Ops"
HOST_PLANE_PREFIX = "/host:"


def merge(intervals):
    """Sorted, disjoint union of (start, end) pairs."""
    out = []
    for lo, hi in sorted((a, b) for a, b in intervals if b > a):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1][1] = hi
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def clip(merged, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in merged if b > lo and a < hi]


def total(merged):
    return sum(b - a for a, b in merged)


def subtract(merged, holes):
    """The parts of ``merged`` that no interval of ``holes`` covers (both
    sorted and disjoint)."""
    out = []
    for a, b in merged:
        cur = a
        for lo, hi in holes:
            if hi <= cur or lo >= b:
                continue
            if lo > cur:
                out.append((cur, lo))
            cur = max(cur, hi)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


class Trace:
    """``device_ops``: {device name: [(start_s, end_s, op name)]};
    ``host_spans``: [(start_s, end_s, annotation name)]; ``window``:
    (start_s, end_s) of the traced window on the same clock."""

    def __init__(self, device_ops, host_spans, window=None):
        self.device_ops = {k: list(v) for k, v in device_ops.items()}
        self.host_spans = list(host_spans)
        if window is None:
            marks = [t for ops in self.device_ops.values() for a, b, _ in ops for t in (a, b)]
            marks += [t for a, b, _ in self.host_spans for t in (a, b)]
            window = (min(marks), max(marks)) if marks else (0.0, 0.0)
        self.window = window
        self._merged = {
            dev: merge((a, b) for a, b, _ in ops)
            for dev, ops in self.device_ops.items()
        }

    @property
    def devices(self):
        return sorted(self.device_ops)

    @property
    def window_s(self):
        return self.window[1] - self.window[0]

    def busy_s(self, dev, lo=None, hi=None):
        lo = self.window[0] if lo is None else lo
        hi = self.window[1] if hi is None else hi
        return total(clip(self._merged[dev], lo, hi))

    def busiest(self):
        return max(self.devices, key=self.busy_s)

    def mean_busy_s(self):
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def spans_named(self, names):
        names = set(names)
        return merge(
            (a, b) for a, b, n in self.host_spans if n in names
        )

    def busy_inside(self, dev, names):
        """(device-busy seconds inside the host annotations of those names,
        the annotations' own seconds).  Annotations that overlap count
        once."""
        spans = clip(self.spans_named(names), *self.window)
        busy = sum(total(clip(self._merged[dev], a, b)) for a, b in spans)
        return busy, total(spans)

    def busy_between(self, dev, first, last):
        """Device-busy seconds from the start of each ``first`` annotation to
        the end of the next ``last`` one (a phase whose device work may end
        under the phase that follows it), and those stretches' length."""
        starts = sorted(a for a, _, n in self.host_spans if n == first)
        ends = sorted(b for _, b, n in self.host_spans if n == last)
        stretches = []
        for a in starts:
            later = [b for b in ends if b >= a]
            if later:
                stretches.append((a, later[0]))
        stretches = clip(merge(stretches), *self.window)
        busy = sum(total(clip(self._merged[dev], a, b)) for a, b in stretches)
        return busy, total(stretches)

    def exposed_s(self, dev, is_wanted):
        wanted = merge((a, b) for a, b, n in self.device_ops[dev] if is_wanted(n))
        others = merge((a, b) for a, b, n in self.device_ops[dev] if not is_wanted(n))
        return total(clip(subtract(wanted, others), *self.window))

    def top_ops(self, n=10):
        """[(name, seconds)] of the operations that took most device time,
        summed over devices and divided by their number.  Operations that
        enclose others (a loop and its body) both count their own span."""
        acc = {}
        for ops in self.device_ops.values():
            for a, b, name in ops:
                acc[name] = acc.get(name, 0.0) + (b - a)
        nd = max(len(self.device_ops), 1)
        ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, secs / nd] for name, secs in ranked]

    def idle_gaps(self, dev, phases, n=10, outside="outside_fit"):
        """[(phase, seconds)]: the device's idle time inside the window,
        summed by the host annotation (one of ``phases``) open where the gap
        begins, the largest first."""
        lo, hi = self.window
        idle = subtract([(lo, hi)], self._merged[dev])
        phases = set(phases)
        spans = sorted((a, b, nm) for a, b, nm in self.host_spans if nm in phases)
        acc = {}
        for a, b in idle:
            cur = a
            for sa, sb, nm in spans:
                if sb <= cur or sa >= b:
                    continue
                if sa > cur:
                    acc[outside] = acc.get(outside, 0.0) + (sa - cur)
                    cur = sa
                upto = min(sb, b)
                if upto > cur:
                    acc[nm] = acc.get(nm, 0.0) + (upto - cur)
                    cur = upto
            if cur < b:
                acc[outside] = acc.get(outside, 0.0) + (b - cur)
        ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, secs] for name, secs in ranked]


def find_xplane(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(trace_dir, span_names, window_marker=None):
    """Read the newest ``.xplane.pb`` under ``trace_dir``.

    Device operations are the events of the ``XLA Ops`` line of each
    ``/device:`` plane; host spans are the events of any ``/host:`` line whose
    name is in ``span_names``.  ``window_marker`` names the host annotation
    that the harness opened around the measured window.  Returns None where
    the trace has no device plane (a CPU run): there is then nothing to read,
    and no metric is made from it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    want = set(span_names) | ({window_marker} if window_marker else set())
    device_ops, host_spans, window = {}, [], None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name != DEVICE_OPS_LINE:
                    continue
                ops = device_ops.setdefault(plane.name, [])
                for ev in line.events:
                    a = ev.start_ns * 1e-9
                    ops.append((a, a + ev.duration_ns * 1e-9, ev.name))
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in want:
                        a = ev.start_ns * 1e-9
                        b = a + ev.duration_ns * 1e-9
                        if ev.name == window_marker:
                            window = (a, b)
                        else:
                            host_spans.append((a, b, ev.name))
    if not any(device_ops.values()):
        return None
    return Trace(device_ops, host_spans, window)


def describe(trace_dir, limit=12):
    """A by-hand look at a trace: planes, lines, event counts, first names."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    out = []
    for plane in data.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for ev in evs:
                names[ev.name] = names.get(ev.name, 0.0) + ev.duration_ns * 1e-9
            top = sorted(names.items(), key=lambda kv: -kv[1])[:limit]
            first = evs[0].start_ns * 1e-9 if evs else None
            out.append(
                f"  line {line.name!r}: {len(evs)} events, first start {first}, "
                f"top {[(n[:60], round(s, 4)) for n, s in top]}"
            )
    return "\n".join(out)
