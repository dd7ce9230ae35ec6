"""Persistent per-(backend, shape-bucket) kernel-geometry autotuner.

The double-buffered tile walks (ops/pallas/_dbuf) and the segmented ring
epilogue (ops/pallas/ring_reduce) expose geometry knobs — tile rows,
VMEM rotation depth, solve batch, ring segment count — whose best values
depend on the backend and the problem's shape regime, not on the exact
operand sizes.  This module owns the resolution of those knobs
(ROADMAP item 4; the communication-avoiding formulation of
arXiv:2601.17136 leaves exactly these free parameters):

- :func:`shape_bucket` quantizes kernel-relevant dims to the next power
  of two, so one tuned entry covers a whole shape regime and a SECOND
  fit anywhere on the same backend/bucket launches pre-tuned with zero
  sweeps — the row count ``n`` deliberately never enters a bucket.
- :func:`resolve` maps ``(kernel, bucket, tier)`` to a geometry dict,
  consulting (in order) the ``Config.tuning`` mode, the in-process
  cache, and the persistent JSON cache under ``Config.tuning_cache_dir``
  (entries named by ``progcache.key_digest`` over the full key, which
  includes ``progcache.backend_fingerprint()`` — a cache directory
  shared across heterogeneous backends never cross-pollinates).
- A cache miss in mode ``"on"`` runs :func:`_sweep`: a deterministic
  measured best-of-N over the per-kernel candidate grid, on operands
  from a fixed-seed generator.  Wall-clock noise cannot corrupt shared
  state across processes because the sweep's winner is what's
  persisted and every LATER process resolves from the cache — the
  determinism contract is cache-mediated, not timing-mediated.
- Multi-process worlds must resolve rank-uniformly (a rank-local sweep
  choosing different geometry per rank would diverge collective
  programs — the R16 hazard).  Plain :func:`resolve` therefore refuses
  to sweep when ``jax.process_count() > 1`` (decision
  ``"default-multiproc"``); :func:`resolve_world` is the multi-process
  entry: rank 0 resolves (sweeping if so configured) and the winning
  geometry rides the sanctioned host-collective seam
  (ops/stream_ops._allgather_host) to every rank.

Every resolution is recorded: ``oap_tuning_{hits,misses,sweeps}_total``
counters, a ``tuning`` node on the active span (sweep wall), and the
:func:`mark`/:func:`delta` window that models attach to fit summaries
as ``summary["tuning"]``.
"""

from __future__ import annotations

import json
import logging
import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from oap_mllib_tpu.ops.pallas import _dbuf
from oap_mllib_tpu.utils import locktrace, progcache

log = logging.getLogger("oap_mllib_tpu")

MODES = ("auto", "on", "off")  # plus "pin:<json>"

# knob vocabulary per kernel — pins outside this raise, like any typo
KNOBS = {
    "kmeans": ("tile_rows", "depth"),
    "pca": ("tile_rows", "depth"),
    "als_solve": ("batch", "depth"),
    "als_gram": ("tile_rows", "depth"),
    "ring": ("segments",),
}

# kernels with ONE Pallas form, the double-buffered walk: their ``depth``
# is a rotation depth (_dbuf.DEPTHS) and nothing else.  The others still
# switch to their grid form below depth 2 (ROADMAP D3).
WALK_ONLY = ("kmeans",)

# the hand-picked constants every kernel shipped with — mode "off", and
# the no-cache fallback of mode "auto"
DEFAULTS = {
    "kmeans": {"tile_rows": 512, "depth": 2},
    "pca": {"tile_rows": 512, "depth": 2},
    "als_solve": {"batch": 256, "depth": 2},
    "als_gram": {"tile_rows": 512, "depth": 2},
    "ring": {"segments": 1},
}

# sweep grids: small on purpose — geometry response surfaces are flat
# away from the VMEM/occupancy cliffs, so a coarse grid finds the
# plateau and the bucket quantization amortizes the sweep forever
CANDIDATES = {
    "kmeans": [
        {"tile_rows": t, "depth": dp}
        for t in (256, 512, 1024) for dp in (2, 3)
    ],
    "pca": [
        {"tile_rows": t, "depth": dp}
        for t in (256, 512, 1024) for dp in (2, 3)
    ],
    "als_solve": [
        {"batch": b, "depth": dp} for b in (128, 256, 512) for dp in (2, 3)
    ],
    "als_gram": [
        {"tile_rows": t, "depth": dp}
        for t in (256, 512, 1024) for dp in (2, 3)
    ],
    "ring": [{"segments": s} for s in (1, 2, 4)],
}

_BEST_OF = 3  # min-of-N per candidate (min rejects scheduler noise)

_LOCK = locktrace.TrackedLock("autotune.cache")
_MEM: Dict[tuple, Dict[str, int]] = {}
_DECISIONS: List[Dict[str, Any]] = []  # append-only; mark()/delta() window


# -- mode / pins -------------------------------------------------------------


def parse_mode(spec: str) -> Tuple[str, Optional[Dict[str, Dict[str, int]]]]:
    """Validate ``Config.tuning`` into ``(mode, pins)``.

    ``pins`` is the per-kernel geometry dict of ``pin:<json>`` (None for
    the plain modes).  Unknown modes, malformed JSON, unknown kernels or
    knob names, and non-integer values all raise ValueError — a typo
    silently tuning nothing is the failure mode this guards."""
    spec = str(spec)
    if spec in MODES:
        return spec, None
    if spec.startswith("pin:"):
        try:
            pins = json.loads(spec[4:])
        except json.JSONDecodeError as e:
            raise ValueError(f"Config.tuning pin payload is not JSON: {e}")
        if not isinstance(pins, dict):
            raise ValueError(
                "Config.tuning pin payload must be a JSON object of "
                f"{{kernel: {{knob: int}}}}, got {type(pins).__name__}"
            )
        for kern, geo in pins.items():
            if kern not in KNOBS:
                raise ValueError(
                    f"Config.tuning pins unknown kernel {kern!r} "
                    f"(known: {sorted(KNOBS)})"
                )
            if not isinstance(geo, dict):
                raise ValueError(
                    f"Config.tuning pin for {kern!r} must be an object, "
                    f"got {type(geo).__name__}"
                )
            for knob, val in geo.items():
                if knob not in KNOBS[kern]:
                    raise ValueError(
                        f"Config.tuning pins unknown knob {knob!r} for "
                        f"kernel {kern!r} (known: {KNOBS[kern]})"
                    )
                if not isinstance(val, int) or isinstance(val, bool):
                    raise ValueError(
                        f"Config.tuning pin {kern}.{knob} must be an "
                        f"integer, got {val!r}"
                    )
                if (kern in WALK_ONLY and knob == "depth"
                        and val not in _dbuf.DEPTHS):
                    raise ValueError(
                        f"Config.tuning pin {kern}.depth must be one of "
                        f"{_dbuf.DEPTHS} (the walk's rotation depth; "
                        f"{kern} has no other kernel form), got {val!r}"
                    )
        return "pin", pins
    raise ValueError(
        f"Config.tuning must be one of {MODES} or 'pin:<json>', "
        f"got {spec!r}"
    )


def _mode() -> Tuple[str, Optional[Dict[str, Dict[str, int]]]]:
    from oap_mllib_tpu.config import get_config

    return parse_mode(get_config().tuning)


# -- shape buckets -----------------------------------------------------------


def _pow2(v: int) -> int:
    v = max(1, int(v))
    return 1 << (v - 1).bit_length()


def shape_bucket(*dims: int) -> Tuple[int, ...]:
    """Quantize kernel-relevant dims (k, d, r, world, cols — NEVER n) to
    the next power of two: the bucket identity under which tuned
    geometry is cached and reused."""
    return tuple(_pow2(d) for d in dims)


def cache_key(kernel: str, bucket: Tuple[int, ...], tier: str) -> tuple:
    return (
        progcache.backend_fingerprint(), kernel, tuple(int(b) for b in bucket),
        str(tier),
    )


# -- persistent cache --------------------------------------------------------


def _disk_path(cache_dir: str, key: tuple) -> str:
    return os.path.join(cache_dir, f"tune-{progcache.key_digest(key)}.json")


def _valid_geometry(kernel: str, geo: Any) -> bool:
    return (
        isinstance(geo, dict)
        and set(geo) == set(KNOBS[kernel])
        and all(
            isinstance(v, int) and not isinstance(v, bool)
            for v in geo.values()
        )
        and (kernel not in WALK_ONLY or geo["depth"] in _dbuf.DEPTHS)
    )


def _disk_load(cache_dir: str, kernel: str, key: tuple):
    """Load one persisted entry; a corrupt or mismatched file logs a
    warning and reads as a miss (fresh sweep in mode "on") — the cache
    must never be able to crash a fit."""
    path = _disk_path(cache_dir, key)
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            entry = json.load(f)
        geo = entry["geometry"]
        if entry.get("key") != repr(key) or not _valid_geometry(kernel, geo):
            raise ValueError("stale or malformed entry")
        return {k: int(v) for k, v in geo.items()}
    except Exception as e:  # corrupt file, bad JSON, wrong schema, IO
        log.warning(
            "tuning cache entry %s unreadable (%s); ignoring it and "
            "re-resolving fresh", path, e,
        )
        return None


def _disk_store(cache_dir: str, kernel: str, key: tuple,
                geometry: Dict[str, int]) -> None:
    """Best-effort atomic persist (tmp + rename); an unwritable cache
    dir degrades to in-process memory with a warning."""
    try:
        os.makedirs(cache_dir, exist_ok=True)
        path = _disk_path(cache_dir, key)
        tmp = path + f".tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(
                {"key": repr(key), "kernel": kernel, "geometry": geometry},
                f, indent=1, sort_keys=True,
            )
        os.replace(tmp, path)
    except OSError as e:
        log.warning("tuning cache dir %s unwritable (%s); tuned geometry "
                    "kept in-process only", cache_dir, e)


def clear() -> None:
    """Drop the in-process tuning cache and decision log (tests)."""
    with _LOCK:
        _MEM.clear()
        del _DECISIONS[:]


# -- telemetry ---------------------------------------------------------------


def _count(event: str, kernel: str) -> None:
    from oap_mllib_tpu.telemetry import metrics as _tm

    helps = {
        "hits": "tuning-cache geometry hits (memory or disk) by kernel",
        "misses": "tuning-cache misses by kernel (resolved to default, "
                  "pin, or a fresh sweep per Config.tuning)",
        "sweeps": "autotune candidate sweeps executed by kernel",
    }
    _tm.counter(
        f"oap_tuning_{event}_total", {"kernel": kernel}, help=helps[event]
    ).inc()


def _record(kernel: str, bucket, tier: str, decision: str,
            geometry: Dict[str, int]) -> Dict[str, int]:
    with _LOCK:
        _DECISIONS.append({
            "kernel": kernel,
            "bucket": list(bucket),
            "tier": tier,
            "decision": decision,
            "geometry": dict(geometry),
        })
    if decision in ("hit",):
        _count("hits", kernel)
    elif decision in ("default", "default-multiproc", "sweep"):
        _count("misses", kernel)
    sp = _span()
    if sp is not None:
        node = sp.node("tuning")
        node.attrs.setdefault("decisions", []).append(
            f"{kernel}:{decision}"
        )
    return geometry


def _span():
    from oap_mllib_tpu.telemetry.spans import current_span

    return current_span()


def mark() -> int:
    """Snapshot the decision log at fit entry (pairs with :func:`delta`,
    the ``progcache.stats()``/``delta`` pattern)."""
    with _LOCK:
        return len(_DECISIONS)


def delta(since: int) -> Dict[str, Any]:
    """Per-fit tuning activity since :func:`mark`: the decision list
    plus rollup counts — what models attach as ``summary["tuning"]``."""
    from oap_mllib_tpu.config import get_config

    with _LOCK:
        window = [dict(d) for d in _DECISIONS[since:]]
    return {
        "mode": get_config().tuning,
        "decisions": window,
        "sweeps": sum(1 for d in window if d["decision"] == "sweep"),
        "hits": sum(1 for d in window if d["decision"] == "hit"),
        "misses": sum(
            1 for d in window
            if d["decision"] in ("default", "default-multiproc", "sweep")
        ),
    }


# -- resolution --------------------------------------------------------------


def resolve(kernel: str, bucket, tier: str = "f32",
            interpret: bool = False) -> Dict[str, int]:
    """Resolve tuned geometry for one kernel launch site.

    Decision ladder (each recorded in the fit summary / metrics):
    ``off`` → hand-picked defaults, cache ignored; ``pin`` → defaults
    overlaid with the pinned knobs, verbatim; cache ``hit`` (memory,
    then ``Config.tuning_cache_dir``) → the tuned winner, zero sweeps;
    miss in ``auto`` → ``default`` (never sweeps — zero overhead);
    miss in ``on`` → ``sweep`` once, persist, then it's a hit
    everywhere; miss in ``on`` under a multi-process world →
    ``default-multiproc`` (rank-local sweeps are refused — see
    :func:`resolve_world`)."""
    if kernel not in KNOBS:
        raise ValueError(f"unknown tunable kernel {kernel!r}")
    bucket = tuple(int(b) for b in bucket)
    mode, pins = _mode()
    if mode == "off":
        return _record(kernel, bucket, tier, "off", dict(DEFAULTS[kernel]))
    if mode == "pin" and kernel in (pins or {}):
        geo = dict(DEFAULTS[kernel])
        geo.update(pins[kernel])
        return _record(kernel, bucket, tier, "pin", geo)

    key = cache_key(kernel, bucket, tier)
    with _LOCK:
        cached = _MEM.get(key)
    if cached is not None:
        return _record(kernel, bucket, tier, "hit", dict(cached))

    from oap_mllib_tpu.config import get_config

    cache_dir = get_config().tuning_cache_dir
    if cache_dir:
        loaded = _disk_load(cache_dir, kernel, key)
        if loaded is not None:
            with _LOCK:
                _MEM[key] = dict(loaded)
            return _record(kernel, bucket, tier, "hit", loaded)

    if mode != "on":
        return _record(
            kernel, bucket, tier, "default", dict(DEFAULTS[kernel])
        )
    import jax

    if jax.process_count() > 1:
        # rank-local sweeps could pick per-rank geometry and diverge
        # collective programs (R16); resolve_world is the sanctioned way
        return _record(
            kernel, bucket, tier, "default-multiproc",
            dict(DEFAULTS[kernel]),
        )
    geometry = _sweep(kernel, bucket, tier, interpret)
    with _LOCK:
        _MEM[key] = dict(geometry)
    if cache_dir:
        _disk_store(cache_dir, kernel, key, geometry)
    return _record(kernel, bucket, tier, "sweep", geometry)


def resolve_world(kernel: str, bucket, tier: str = "f32",
                  interpret: bool = False) -> Dict[str, int]:
    """Rank-uniform resolution for multi-process worlds: rank 0 resolves
    (sweeping on a miss if ``tuning="on"``) and broadcasts the winning
    geometry over the sanctioned host-collective seam, so every rank
    traces the identical program geometry (R16).  Single-process this is
    exactly :func:`resolve`."""
    import jax

    if jax.process_count() < 2:
        return resolve(kernel, bucket, tier, interpret)
    knobs = KNOBS[kernel]
    if jax.process_index() == 0:
        mode, pins = _mode()
        if mode == "on":
            # rank 0 may sweep: temporarily lift the multi-process
            # refusal by resolving through the single-process ladder
            geo = _resolve_rank0(kernel, bucket, tier, interpret)
        else:
            geo = resolve(kernel, bucket, tier, interpret)
        frame = np.asarray([float(geo[k]) for k in knobs], np.float32)
    else:
        frame = np.zeros((len(knobs),), np.float32)
    from oap_mllib_tpu.ops import stream_ops

    (gathered,) = stream_ops._allgather_host([frame])
    geo = {k: int(gathered[0, i]) for i, k in enumerate(knobs)}
    if jax.process_index() != 0:
        _record(kernel, tuple(int(b) for b in bucket), tier, "hit", geo)
    return geo


def _resolve_rank0(kernel, bucket, tier, interpret) -> Dict[str, int]:
    """Rank 0's leg of resolve_world in mode "on": same ladder as
    :func:`resolve` but sweeping despite the multi-process world — the
    result is broadcast, so uniformity is preserved by construction."""
    bucket = tuple(int(b) for b in bucket)
    key = cache_key(kernel, bucket, tier)
    with _LOCK:
        cached = _MEM.get(key)
    if cached is not None:
        return _record(kernel, bucket, tier, "hit", dict(cached))
    from oap_mllib_tpu.config import get_config

    cache_dir = get_config().tuning_cache_dir
    if cache_dir:
        loaded = _disk_load(cache_dir, kernel, key)
        if loaded is not None:
            with _LOCK:
                _MEM[key] = dict(loaded)
            return _record(kernel, bucket, tier, "hit", loaded)
    geometry = _sweep(kernel, bucket, tier, interpret)
    with _LOCK:
        _MEM[key] = dict(geometry)
    if cache_dir:
        _disk_store(cache_dir, kernel, key, geometry)
    return _record(kernel, bucket, tier, "sweep", geometry)


# -- the sweep ---------------------------------------------------------------


def _bench_operands(kernel: str, bucket, rng) -> tuple:
    """Fixed-seed operands sized for the bucket, capped so a sweep stays
    cheap (rows 2048, dims 256 — beyond the caps the geometry response
    is governed by the same tile arithmetic)."""
    if kernel == "kmeans":
        k, d = (min(int(bucket[0]), 256), min(int(bucket[1]), 256))
        x = rng.standard_normal((2048, d)).astype(np.float32)
        w = np.ones((2048,), np.float32)
        c = rng.standard_normal((max(k, 2), d)).astype(np.float32)
        return (x, w, c)
    if kernel == "pca":
        d = min(int(bucket[0]), 256)
        x = rng.standard_normal((2048, d)).astype(np.float32)
        mask = np.ones((2048,), np.float32)
        return (x, mask)
    if kernel == "als_solve":
        r = min(int(bucket[0]), 32)
        n = 1024
        a = rng.standard_normal((n, r, r)).astype(np.float32)
        a = a @ a.transpose(0, 2, 1) + 4.0 * np.eye(r, dtype=np.float32)
        b = rng.standard_normal((n, r)).astype(np.float32)
        n_reg = np.full((n,), 3.0, np.float32)
        return (a, b, n_reg)
    if kernel == "als_gram":
        r = min(int(bucket[0]), 32)
        return (rng.standard_normal((2048, r)).astype(np.float32),)
    raise ValueError(f"no sweep bench for kernel {kernel!r}")


def _measure(kernel: str, operands, geometry: Dict[str, int], tier: str,
             interpret: bool) -> float:
    """One candidate's cost: min wall of ``_BEST_OF`` timed launches
    after a warm-up call that absorbs trace + compile."""
    import jax

    from oap_mllib_tpu.utils.timing import tick

    def launch():
        if kernel == "kmeans":
            from oap_mllib_tpu.ops.pallas.kmeans_kernel import (
                lloyd_accumulate_walk,
            )

            x, w, c = operands
            return lloyd_accumulate_walk(
                x, w, c, mode=tier, interpret=interpret,
                tile_rows=geometry["tile_rows"], depth=geometry["depth"],
            )
        if kernel == "pca":
            from oap_mllib_tpu.ops.pallas.pca_kernel import (
                pca_moments_pallas,
            )

            x, mask = operands
            return pca_moments_pallas(
                x, mask, mode=tier, interpret=interpret,
                tile_rows=geometry["tile_rows"], depth=geometry["depth"],
            )
        if kernel == "als_solve":
            from oap_mllib_tpu.ops.pallas.als_kernel import (
                solve_normal_eq_pallas,
            )

            a, b, n_reg = operands
            return solve_normal_eq_pallas(
                a, b, n_reg, 0.1, interpret=interpret,
                batch=geometry["batch"], depth=geometry["depth"],
            )
        if kernel == "als_gram":
            from oap_mllib_tpu.ops.pallas.als_kernel import (
                factor_gram_pallas,
            )

            (factors,) = operands
            return factor_gram_pallas(
                factors, mode=tier, interpret=interpret,
                tile_rows=geometry["tile_rows"], depth=geometry["depth"],
            )
        raise ValueError(kernel)

    jax.block_until_ready(launch())  # warm-up: trace + compile
    best = float("inf")
    for _ in range(_BEST_OF):
        elapsed = tick()
        jax.block_until_ready(launch())
        best = min(best, elapsed())
    return best


def _sweep(kernel: str, bucket, tier: str,
           interpret: bool) -> Dict[str, int]:
    """Measured best-of-N over the candidate grid.  Deterministic
    operands (fixed seed per (kernel, bucket)); ties break toward the
    earlier candidate, so the grid order is part of the contract.  The
    whole sweep's wall books under the active span's ``tuning`` node.

    ``ring`` has no single-device bench (its cost is the inter-device
    schedule, which a local loopback cannot rank honestly) — it resolves
    to its default geometry here, counted as a sweep so the caching
    contract stays uniform."""
    from oap_mllib_tpu.utils.timing import tick

    _count("sweeps", kernel)
    elapsed = tick()
    if kernel == "ring":
        best = dict(DEFAULTS["ring"])
        results = []
    else:
        # process-stable seed (builtin hash is salted per interpreter)
        seed = int(
            progcache.key_digest((kernel,) + tuple(bucket))[:8], 16
        )
        rng = np.random.default_rng(seed)
        operands = _bench_operands(kernel, bucket, rng)
        best, best_t, results = None, float("inf"), []
        for cand in CANDIDATES[kernel]:
            t = _measure(kernel, operands, cand, tier, interpret)
            results.append((cand, t))
            if t < best_t:
                best, best_t = dict(cand), t
    wall = elapsed()
    sp = _span()
    if sp is not None:
        node = sp.node("tuning")
        node.record(wall)
        node.attrs.setdefault("sweeps", []).append({
            "kernel": kernel,
            "bucket": list(bucket),
            "candidates": len(results),
            "winner": dict(best),
        })
    log.info(
        "autotune sweep %s bucket=%s tier=%s -> %s (%d candidates, %.3fs)",
        kernel, list(bucket), tier, best, len(results), wall,
    )
    return best
