"""XLA-level profiling: the deep-trace layer the reference never had.

The reference's only observability is wall-time prints (survey §5);
utils/timing.py replicates that.  This module adds the TPU-native layer
beneath it: ``jax.profiler`` traces capture per-op device timelines,
HBM usage, and ICI collective timing, viewable in TensorBoard/XProf.

Usage::

    from oap_mllib_tpu.utils.profiling import trace
    with trace("/tmp/oap_trace"):
        KMeans(k=8).fit(x)

or set ``Config.profile_dir`` (env ``OAP_MLLIB_TPU_PROFILE_DIR``) and
every estimator fit is traced.  While a trace is live the span tree
(telemetry/spans.py) emits a ``jax.profiler.TraceAnnotation`` per phase,
so the fit's named spans line up on the XProf timeline;
:func:`trace_active` is the one-bool guard that keeps that free when no
trace is running.
"""

from __future__ import annotations

import contextlib
import logging

from oap_mllib_tpu.config import get_config

log = logging.getLogger("oap_mllib_tpu")

# live jax.profiler.trace nesting depth — the cheap guard the span layer
# checks before paying for a TraceAnnotation
_active = 0


def trace_active() -> bool:
    return _active > 0


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a jax.profiler trace for the enclosed block."""
    global _active
    import jax

    log.info("profiler trace -> %s", log_dir)
    with jax.profiler.trace(log_dir):
        _active += 1
        try:
            yield
        finally:
            _active -= 1


@contextlib.contextmanager
def maybe_trace():
    """Trace if ``Config.profile_dir`` is set; no-op otherwise.  The knob
    is env-coerced like every other config field (OAP_MLLIB_TPU_
    PROFILE_DIR), so ``Config.set``/scoped overrides work too — it used
    to read the raw env var only."""
    log_dir = get_config().profile_dir
    if not log_dir:
        yield
        return
    with trace(log_dir):
        yield
