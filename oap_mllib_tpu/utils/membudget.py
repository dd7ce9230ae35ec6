"""Memory-budget-governed route planning: every accelerated fit picks
its scale route (in-memory / chunked / streamed / streamed-block) as an
EXPLICIT, auditable, budget-driven decision.

Before ISSUE 12 the route was an accident of input type and scattered
heuristics: an ndarray always ran the fully-resident in-memory path
(however large), a ChunkSource always streamed (however small), and the
ALS streamed entry silently MATERIALIZED its source back to in-memory
layouts on exactly the long-tail degree distributions most likely to
need streaming.  The map-reduce
primitive decomposition (DrJAX, arXiv:2403.07128) and the simplified-
MapReduce K-Means architecture (arXiv:1610.05601) both argue the
streamed pass is a first-class representation, not a fallback: route
selection should be planned against an explicit memory budget, degrade
gracefully and LOUDLY, and never silently.

This module is that planner:

- **Budgets** (``Config.memory_budget_hbm`` / ``memory_budget_host``,
  default auto-detected; ``utils/membudget.parse_budget`` grammar) bound
  the per-device accelerator working set and the staged host footprint.
- **Estimates**: per candidate route, the planner prices the table /
  factor / accumulator / prefetch-buffer footprints from the fit's
  shapes (calibrated by the bytes-staged accounting telemetry already
  collects — see :func:`record_plan`), and records EVERY candidate's
  estimate and rejection reason, not just the winner.
- **Policy** (``Config.scale_policy``): ``auto`` picks the fastest
  feasible route and degrades loudly when the budget forces a slower
  one; ``strict`` raises :class:`BudgetError` instead of deviating from
  the fit's natural route; ``pin:<route>`` forces a route outright.
- **Exposure**: the decision, candidates, budgets, and (on streamed
  routes) the estimate-vs-actual staged-bytes cross-check land in
  ``summary.route``, a ``route`` span node, and ``oap_route_*`` metrics.

The SPILL primitive lives here too: :func:`spill_source` /
:func:`spill_array` are the resilience ladder's host-OOM rung — stage
the fit's source to an atomic disk spill (data/io.SpillWriter) and swap
the attempt onto the disk-backed streamed route.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
from typing import Dict, List, Optional, Sequence, Tuple

from oap_mllib_tpu.config import get_config
from oap_mllib_tpu.telemetry import metrics as _tm

log = logging.getLogger("oap_mllib_tpu")

ROUTE_IN_MEMORY = "in-memory"
ROUTE_CHUNKED = "chunked"
ROUTE_STREAMED = "streamed"
ROUTE_STREAMED_BLOCK = "streamed-block"
# ALS's resident layouts, solved a block of destinations at a time
ROUTE_DST_BLOCKED = "dst-blocked"
ROUTES = (ROUTE_IN_MEMORY, ROUTE_CHUNKED, ROUTE_STREAMED,
          ROUTE_STREAMED_BLOCK, ROUTE_DST_BLOCKED)

# planner fudge on analytic estimates: XLA temporaries, fusion buffers,
# and allocator slack that no shape formula sees.  Streamed estimates
# additionally carry the measured calibration factor (see record_plan).
_OVERHEAD = 1.25

# flat allowance for compiled programs + runtime structures per fit
_PROGRAM_BYTES = 64 << 20


class BudgetError(RuntimeError):
    """``scale_policy="strict"`` and the memory budget forced (or the
    pinned route demanded) a scale downgrade.  ``estimates`` carries
    every candidate's priced footprint so the operator sees exactly what
    was infeasible and why."""

    def __init__(self, algo: str, msg: str,
                 estimates: Optional[List["RouteEstimate"]] = None):
        self.algo = algo
        self.estimates = list(estimates or [])
        detail = "; ".join(
            f"{e.route}: hbm~{_fmt_bytes(e.hbm_bytes)} "
            f"host~{_fmt_bytes(e.host_bytes)}"
            + (f" ({e.reject})" if e.reject else "")
            for e in self.estimates
        )
        super().__init__(
            f"{algo}: {msg}" + (f" — candidates: {detail}" if detail else "")
        )


def _world() -> int:
    try:
        import jax

        return jax.process_count()
    except Exception:  # noqa: BLE001 — planning must work pre-backend
        return 1


def _fmt_bytes(n: int) -> str:
    if n <= 0:
        return "?"
    for unit in ("B", "K", "M", "G", "T"):
        if n < 1024 or unit == "T":
            return f"{n:.4g}{unit}" if unit != "B" else f"{n}B"
        n /= 1024.0
    return f"{n:.4g}T"


_UNITS = {"k": 1 << 10, "m": 1 << 20, "g": 1 << 30, "t": 1 << 40}


def parse_budget(spec: str) -> Optional[int]:
    """Parse a budget knob: ``""`` -> None (auto-detect), ``"0"`` /
    ``"unlimited"`` -> 0 (unbounded), else bytes with an optional
    K/M/G/T suffix (``"4G"``, ``"512M"``, ``"1073741824"``).  A typo
    raises — a budget that silently parses to nothing defeats the
    planner (the fault_spec/kmeans_kernel contract)."""
    s = spec.strip().lower()
    if not s:
        return None
    if s in ("unlimited", "none", "inf"):
        return 0
    mult = 1
    if s[-1] in _UNITS:
        mult = _UNITS[s[-1]]
        s = s[:-1]
    try:
        v = float(s)
    except ValueError:
        raise ValueError(
            f"memory budget must be bytes with an optional K/M/G/T "
            f"suffix, '0'/'unlimited', or empty (auto-detect); got "
            f"{spec!r}"
        ) from None
    if v < 0:
        raise ValueError(f"memory budget must be >= 0, got {spec!r}")
    return int(v * mult)


def detect_hbm_bytes() -> int:
    """Per-device accelerator memory, from the backend's own accounting
    (``memory_stats()['bytes_limit']``).  0 = the backend reports none
    (CPU) — the HBM constraint is then unbounded unless pinned."""
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats()
        if stats:
            return int(stats.get("bytes_limit", 0))
    except Exception:  # noqa: BLE001 — detection must never fail a fit
        pass
    return 0


def detect_host_bytes() -> int:
    """Physical host RAM (sysconf); 0 when undetectable = unbounded."""
    try:
        import os

        return int(os.sysconf("SC_PHYS_PAGES")) * int(
            os.sysconf("SC_PAGE_SIZE")
        )
    except (ValueError, OSError, AttributeError):
        return 0


@dataclasses.dataclass(frozen=True)
class Budgets:
    """Resolved budgets for one plan: 0 = unbounded.  ``*_source`` names
    where each number came from (``config`` vs ``detected``) so
    summary.route is self-explaining."""

    hbm: int
    host: int
    hbm_source: str
    host_source: str

    @classmethod
    def resolve(cls) -> "Budgets":
        cfg = get_config()
        hbm = parse_budget(cfg.memory_budget_hbm)
        host = parse_budget(cfg.memory_budget_host)
        return cls(
            hbm=detect_hbm_bytes() if hbm is None else hbm,
            host=detect_host_bytes() if host is None else host,
            hbm_source="detected" if hbm is None else "config",
            host_source="detected" if host is None else "config",
        )

    def as_dict(self) -> dict:
        return {
            "hbm": self.hbm, "host": self.host,
            "hbm_source": self.hbm_source,
            "host_source": self.host_source,
        }


def scale_policy_cfg() -> Tuple[str, Optional[str]]:
    """Validated ``Config.scale_policy`` -> (mode, pinned_route).  A
    typo raises at fit entry, not after a multi-minute pass (the
    kmeans_kernel contract)."""
    policy = get_config().scale_policy.strip()
    if policy in ("auto", "strict"):
        return policy, None
    if policy.startswith("pin:"):
        route = policy[4:]
        if route in ROUTES:
            return "pin", route
        raise ValueError(
            f"scale_policy pin route must be one of {', '.join(ROUTES)}; "
            f"got {policy!r}"
        )
    raise ValueError(
        f"scale_policy must be auto|strict|pin:<route>, got {policy!r}"
    )


@dataclasses.dataclass
class RouteEstimate:
    """One candidate route's priced footprint.  ``hbm_bytes`` /
    ``host_bytes`` <= 0 mean unknown (an un-sized generator source) —
    unknown fits any budget (the planner cannot reject what it cannot
    price; the estimate is still recorded as unknown)."""

    route: str
    hbm_bytes: int
    host_bytes: int
    reject: str = ""

    def fits(self, budgets: Budgets) -> bool:
        if budgets.hbm > 0 and self.hbm_bytes > budgets.hbm:
            return False
        if budgets.host > 0 and self.host_bytes > budgets.host:
            return False
        return True

    def why_rejected(self, budgets: Budgets) -> str:
        parts = []
        if budgets.hbm > 0 and self.hbm_bytes > budgets.hbm:
            parts.append(
                f"hbm estimate {_fmt_bytes(self.hbm_bytes)} > budget "
                f"{_fmt_bytes(budgets.hbm)}"
            )
        if budgets.host > 0 and self.host_bytes > budgets.host:
            parts.append(
                f"host estimate {_fmt_bytes(self.host_bytes)} > budget "
                f"{_fmt_bytes(budgets.host)}"
            )
        return "; ".join(parts)

    def as_dict(self) -> dict:
        out = {
            "route": self.route,
            "hbm_bytes": self.hbm_bytes,
            "host_bytes": self.host_bytes,
        }
        if self.reject:
            out["reject"] = self.reject
        return out


class RoutePlan:
    """The planner's decision for one fit: the chosen route, the natural
    (infinite-budget) route, every candidate's estimate, the budgets and
    policy that produced it, and the bookkeeping :func:`record_plan`
    turns into summary.route / span / metrics."""

    def __init__(self, algo: str, route: str, natural: str,
                 estimates: List[RouteEstimate], budgets: Budgets,
                 policy: str, *, chunk_rows: int = 0,
                 over_budget: bool = False, forced: bool = False):
        self.algo = algo
        self.route = route
        self.natural = natural
        self.estimates = estimates
        self.budgets = budgets
        self.policy = policy
        self.chunk_rows = chunk_rows  # suggested streamed chunk width
        self.over_budget = over_budget  # no candidate fit; loudest case
        self.forced = forced  # pin: override
        self.dst_block = 0  # destinations a block (ALS dst-blocked route)
        self.downgrades: List[str] = []
        # what the planner priced one staged row at (chunk width x dtype
        # + the mask/weight columns that ride along) — record_plan
        # cross-checks it against the observed bytes/row from the
        # pipeline's staging telemetry and folds the ratio into the
        # calibration EMA
        self.est_row_bytes = 0.0
        # staging-telemetry family totals at plan time: record_plan
        # subtracts them to isolate THIS fit's staged bytes/rows
        self.stream_marker = _tm.family_total("oap_stream_bytes_staged_total")
        self.rows_marker = _tm.family_total("oap_stream_rows_total")

    @property
    def degraded_scale(self) -> bool:
        """True when the budget (not the caller) moved the fit off its
        natural route — the case that must never be silent."""
        return self.route != self.natural and not self.forced

    def estimate_for(self, route: str) -> Optional[RouteEstimate]:
        for e in self.estimates:
            if e.route == route:
                return e
        return None

    def downgrade(self, route: str, why: str) -> None:
        """A post-plan scale downgrade the estimator was forced into
        (e.g. the ALS grouped guard rejecting a long-tail source ->
        in-memory COO).  Never silent: strict raises, auto warns and
        records."""
        mode, _ = scale_policy_cfg()
        if (mode == "strict" and _world() == 1
                and _scale_rank(route) < _scale_rank(self.route)):
            raise BudgetError(
                self.algo,
                f"scale_policy=strict forbids downgrading the planned "
                f"{self.route!r} route to {route!r} ({why})",
                self.estimates,
            )
        log.warning(
            "%s: route downgraded %s -> %s (%s)", self.algo, self.route,
            route, why,
        )
        self.downgrades.append(f"{self.route}->{route}: {why}")
        self.route = route

    def as_dict(self) -> dict:
        out = {
            "route": self.route,
            "natural": self.natural,
            "policy": self.policy,
            "budgets": self.budgets.as_dict(),
            "estimates": [e.as_dict() for e in self.estimates],
        }
        if self.chunk_rows:
            out["chunk_rows"] = self.chunk_rows
        if self.route == ROUTE_DST_BLOCKED:
            out["dst_block"] = self.dst_block
        if self.over_budget:
            out["over_budget"] = True
        if self.forced:
            out["forced"] = True
        if self.degraded_scale:
            out["degraded_scale"] = True
        if self.downgrades:
            out["downgrades"] = list(self.downgrades)
        return out


def _scale_rank(route: str) -> int:
    """Higher = handles more data per resident byte.  A move to a LOWER
    rank is a scale downgrade (the thing strict mode forbids)."""
    return {
        ROUTE_IN_MEMORY: 0, ROUTE_CHUNKED: 1, ROUTE_DST_BLOCKED: 1,
        ROUTE_STREAMED: 2, ROUTE_STREAMED_BLOCK: 3,
    }[route]


def choose(algo: str, estimates: List[RouteEstimate],
           natural: Optional[str] = None) -> RoutePlan:
    """Pick a route from ``estimates`` (ordered fastest-first) under the
    configured budgets and scale policy.

    - ``pin:<route>``: that route, budgets advisory (must be a
      candidate; a pin naming an inapplicable route raises ValueError).
    - ``strict``: the natural route or :class:`BudgetError`.
    - ``auto``: the first candidate that fits both budgets; when none
      fits, the LAST (most scale-capable) candidate runs anyway with
      ``over_budget`` recorded and a loud warning — degrading scale
      further than streaming is impossible, and refusing to fit is
      strict mode's job.
    """
    if not estimates:
        raise ValueError(f"{algo}: no candidate routes to plan over")
    budgets = Budgets.resolve()
    mode, pinned = scale_policy_cfg()
    natural = natural or estimates[0].route
    for e in estimates:
        if not e.fits(budgets):
            e.reject = e.why_rejected(budgets)
    if _world() > 1:
        # multi-process worlds: estimates derive from RANK-LOCAL shard
        # shapes, so a borderline budget could pick different routes on
        # different ranks — a divergent collective schedule (hang).  The
        # planner stays ADVISORY there: the natural route runs, the
        # estimates and any budget breach are still recorded loudly in
        # summary.route, and strict/pin govern single-process fits only
        # (the static-world contract, docs/distributed.md).
        plan = RoutePlan(
            algo, natural, natural, estimates, budgets,
            f"{get_config().scale_policy}(advisory:multi-process)",
        )
        nat = plan.estimate_for(natural)
        if nat is not None and nat.reject:
            plan.over_budget = True
            log.warning(
                "%s: natural route %r exceeds the budget (%s) — "
                "multi-process worlds keep the natural route (planner "
                "advisory)", algo, natural, nat.reject,
            )
        return plan

    if mode == "pin":
        est = next((e for e in estimates if e.route == pinned), None)
        if est is None:
            raise ValueError(
                f"{algo}: scale_policy=pin:{pinned} does not apply to "
                f"this fit (candidates: "
                f"{', '.join(e.route for e in estimates)})"
            )
        plan = RoutePlan(algo, pinned, natural, estimates, budgets,
                         f"pin:{pinned}", forced=True)
        return plan

    chosen = next((e for e in estimates if not e.reject), None)
    if mode == "strict":
        nat = next(e for e in estimates if e.route == natural)
        if nat.reject:
            raise BudgetError(
                algo,
                f"scale_policy=strict and the natural {natural!r} route "
                f"exceeds the budget ({nat.reject})",
                estimates,
            )
        if chosen is None or chosen.route != natural:
            raise BudgetError(
                algo,
                f"scale_policy=strict forbids degrading scale off the "
                f"natural {natural!r} route",
                estimates,
            )
        return RoutePlan(algo, natural, natural, estimates, budgets,
                         "strict")

    over = chosen is None
    if over:
        chosen = estimates[-1]
        log.warning(
            "%s: NO candidate route fits the memory budget "
            "(hbm=%s host=%s) — running the most scale-capable route "
            "%r over budget; consider raising the budget or "
            "scale_policy=strict",
            algo, _fmt_bytes(budgets.hbm), _fmt_bytes(budgets.host),
            chosen.route,
        )
    plan = RoutePlan(algo, chosen.route, natural, estimates, budgets,
                     "auto", over_budget=over)
    if plan.degraded_scale:
        nat = plan.estimate_for(natural)
        log.warning(
            "%s: memory budget moved the fit off its natural %r route "
            "onto %r (%s)", algo, natural, chosen.route,
            nat.reject if nat is not None else "unpriceable",
        )
    return plan


# -- per-algorithm candidate pricing ------------------------------------------


def _dtype_bytes() -> int:
    return 8 if get_config().enable_x64 else 4


def _padded_rows(n: int, shards: int = 1) -> int:
    """Padded rows ONE device holds of an n-row table cut into
    ``shards`` row shards (data/table.py pads to 256 rows a shard)."""
    from oap_mllib_tpu.data.bucketing import bucket_rows

    return bucket_rows(max(int(n), 1), 256 * shards) // shards


def _depth() -> int:
    from oap_mllib_tpu.data.prefetch import resolve_depth

    try:
        return resolve_depth()
    except ValueError:
        return 1


def suggest_chunk_rows(d: int, extra_width: int, budgets: Budgets,
                       default_rows: int) -> int:
    """Streamed chunk width: the default unless the HBM budget demands
    narrower — depth staged (rows, d) chunks plus the (rows,
    extra_width) working block must fit HALF the budget (the other half
    is accumulators/programs/slack), floored at the resilience ladder's
    OOM_CHUNK_FLOOR_ROWS."""
    from oap_mllib_tpu.utils.resilience import OOM_CHUNK_FLOOR_ROWS

    if budgets.hbm <= 0:
        return default_rows
    per_row = (d + extra_width + 1) * _dtype_bytes() * _depth()
    fit_rows = max(int(budgets.hbm // (2 * max(per_row, 1))),
                   OOM_CHUNK_FLOOR_ROWS)
    return max(min(default_rows, fit_rows), 1)


def _calibrated(algo: str, estimate: int) -> int:
    return int(estimate * calibration_factor(algo))


def plan_kmeans(n: Optional[int], d: int, k: int, *,
                source_backing: Optional[str] = None,
                chunk_rows: int = 0,
                row_chunks_hint: int = 1,
                shards: int = 1) -> RoutePlan:
    """Route plan for one K-Means fit.  ``source_backing`` None = array
    input (candidates: in-memory / chunked / streamed); a ChunkSource
    input passes its ``backing`` (natural route: streamed).  ``n`` None
    = un-sized source (footprints unknown; streams unconditionally).

    ``shards`` is the mesh's data-axis size: the HBM budget is ONE
    device's, so the resident routes are priced on what one device
    holds — its row shard and the Lloyd program that runs over it
    (models/kmeans._run_lloyd_data_sharded: a walk or chunked scan over
    the shard, never a whole-table sheet) — with ``row_chunks_hint`` the
    occupancy rule on a shard's rows.  The host holds the whole array
    whatever the mesh.  One shard prices as before."""
    b = _dtype_bytes()
    budgets = Budgets.resolve()
    from oap_mllib_tpu.data.stream import DEFAULT_CHUNK_ROWS
    from oap_mllib_tpu.ops.kmeans_ops import SCORE_BUDGET_ELEMS

    centroids = 3 * k * d * b + _PROGRAM_BYTES
    # array inputs are free to pick their chunk width from the budget;
    # a ChunkSource keeps the width it was built with (the compiled
    # per-chunk programs are keyed on it) and is priced at that width
    rows = chunk_rows or suggest_chunk_rows(
        d, k, budgets, DEFAULT_CHUNK_ROWS
    )
    streamed_hbm = _calibrated(
        "kmeans",
        int((_depth() * rows * (d + k + 1) * b + centroids) * _OVERHEAD),
    )
    if source_backing is None:
        np_ = _padded_rows(n, shards)
        table = np_ * (d + 1) * b
        host = n * d * b
        in_mem = RouteEstimate(
            ROUTE_IN_MEMORY,
            int((table + np_ * k * b + centroids) * _OVERHEAD), host)
        chunked = RouteEstimate(
            ROUTE_CHUNKED,
            int((table + SCORE_BUDGET_ELEMS * b + centroids)
                * _OVERHEAD), host)
        streamed = RouteEstimate(ROUTE_STREAMED, streamed_hbm, host)
        # the natural route is what the resident-table Lloyd actually
        # runs: an unchunked score buffer when auto_row_chunks needs no
        # scan ("in-memory"), else the scan-chunked program ("chunked")
        # — a shape auto_row_chunks already chunks never offers the
        # unbounded in-memory candidate
        if row_chunks_hint <= 1:
            ests = [in_mem, chunked, streamed]
            natural = ROUTE_IN_MEMORY
        else:
            ests = [chunked, streamed]
            natural = ROUTE_CHUNKED
        plan = choose("KMeans", ests, natural)
    else:
        host = (
            n * d * b
            if (n and source_backing == "memory")
            else rows * d * b * 2
        )
        ests = [RouteEstimate(ROUTE_STREAMED, streamed_hbm, host)]
        plan = choose("KMeans", ests, ROUTE_STREAMED)
    plan.chunk_rows = rows
    plan.est_row_bytes = (d + 1) * b  # data row + the mask/weight lane
    return plan


def plan_pca(n: Optional[int], d: int, *,
             source_backing: Optional[str] = None,
             chunk_rows: int = 0) -> RoutePlan:
    """Route plan for one PCA fit (candidates: in-memory covariance vs
    the two-pass streamed moments)."""
    b = _dtype_bytes()
    budgets = Budgets.resolve()
    from oap_mllib_tpu.data.stream import DEFAULT_CHUNK_ROWS

    gram = 2 * d * d * b + _PROGRAM_BYTES
    rows = chunk_rows or suggest_chunk_rows(
        d, 0, budgets, DEFAULT_CHUNK_ROWS
    )
    streamed_hbm = _calibrated(
        "pca", int((_depth() * rows * (d + 1) * b + 2 * gram) * _OVERHEAD)
    )
    if source_backing is None:
        np_ = _padded_rows(n)
        host = n * d * b
        ests = [
            RouteEstimate(
                ROUTE_IN_MEMORY,
                int((np_ * (d + 1) * b + gram) * _OVERHEAD), host),
            RouteEstimate(ROUTE_STREAMED, streamed_hbm, host),
        ]
        plan = choose("PCA", ests, ROUTE_IN_MEMORY)
    else:
        host = (
            n * d * b
            if (n and source_backing == "memory")
            else rows * d * b * 2
        )
        ests = [RouteEstimate(ROUTE_STREAMED, streamed_hbm, host)]
        plan = choose("PCA", ests, ROUTE_STREAMED)
    plan.chunk_rows = rows
    plan.est_row_bytes = (d + 1) * b
    return plan


# grouped-edge layouts: ~12 bytes/edge (idx + value + validity) per
# update direction, times the adaptive-group padding allowance the
# blowup guard enforces (ops/als_ops.GROUPED_MAX_BLOWUP)
_ALS_EDGE_BYTES = 12
_ALS_BLOWUP = 2.0


def als_sheet_bytes(groups: int, rank: int) -> int:
    """The sheet of group moments one grouped half-update holds
    (``ops/als_ops.normal_eq_partials_grouped``), ``(r+1)(r+2)`` floats a
    group of the side's bucket, in the two forms XLA:TPU keeps of it:
    groups-minor while the walk fills it (the width rounded up to 8
    sublanes) and the row-major copy the segment-sum reads (the width
    padded to whole 128-lane rows) — 544 + 1024 B a group at rank 10,
    both live at the copy (compiled for a v5e:
    tests/test_tpu_compile.py).  It doubles with every halving of the
    group width."""
    width = (rank + 1) * (rank + 2)
    return groups * (-(-width // 8) * 8 + -(-width // 128) * 128) * 4


def als_grouped_bytes(layouts: Sequence[Tuple[int, int]], rank: int) -> int:
    """Device bytes of the resident grouped route's data-shaped part:
    every side's ``(G, P)`` layout at ``_ALS_EDGE_BYTES`` a slot — of the
    128 lanes a row of fewer slots takes in the program (compiled for a
    v5e, a ``(4194304, 64)`` array arrives compact, groups minor, and
    the program keeps a row-major copy of it of 2 GB, not 1; priced as
    if every array of a narrow side were held so) — and the sheet of the
    side with the most groups (one half-update runs at a time)."""
    return als_layout_bytes(layouts) + als_sheet_bytes(
        max((g for g, _ in layouts), default=0), rank
    )


def als_layout_bytes(layouts: Sequence[Tuple[int, int]]) -> int:
    """Device bytes of the ``(G, P)`` grouped layouts alone, a row of
    fewer than 128 slots taking 128 lanes (:func:`als_grouped_bytes`)."""
    return sum(g * max(p, 128) for g, p in layouts) * _ALS_EDGE_BYTES


def als_dst_block_bytes(dst_block: int, rank: int) -> int:
    """What one block of the destination-blocked route holds
    (``ops/als_ops.dst_blocked_half``): its ``(D, R, R)`` moment tiles
    (``R``: the rank and two columns, in whole lanes), the ``(D, r, r)``
    systems and their batch-last copy the solve reads."""
    from oap_mllib_tpu.ops.pallas.als_dst import r_pad

    return dst_block * (r_pad(rank) ** 2 + 2 * rank * rank) * 4


def _als_dst_bytes(layouts, n_users: int, n_items: int, rank: int,
                   dst_block: int) -> int:
    """HBM of the destination-blocked route at ``dst_block``: the
    layouts, three generations of factors, the lane-dense source rows
    it reads, one chunk of gathered rows (the XLA twin's; the Pallas
    kernel fetches its rows into VMEM) and one block."""
    from oap_mllib_tpu.ops.als_ops import DST_CHUNK_BYTES
    from oap_mllib_tpu.ops.pallas.als_dst import r_pad

    factors, _ = _als_fixed_bytes(n_users, n_items, rank)
    return int((
        als_layout_bytes(layouts) + 3 * factors
        + max(n_users, n_items) * r_pad(rank) * 4 + DST_CHUNK_BYTES
        + als_dst_block_bytes(dst_block, rank) + _PROGRAM_BYTES
    ) * _OVERHEAD)


# the destination-blocked route's widest block: 1.07 GB of tiles at
# rank 100; wider blocks only shorten a loop of a few dozen steps
_DST_BLOCK_MAX = 16384
_DST_BLOCK_MIN = 128  # one solve step


def _als_fixed_bytes(n_users: int, n_items: int, rank: int):
    """(factors, moments): one generation of both factor tables and the
    destinations' moments — what an ALS fit holds on the device whatever
    its edges' layout (the resident routes keep three generations)."""
    b = 4  # ALS is f32 like the reference
    return (
        (n_users + n_items) * rank * b,
        (n_users + n_items) * rank * (rank + 1) * b,
    )


def als_grouped_room(n_users: int, n_items: int, rank: int) -> Optional[int]:
    """Bytes the in-memory estimate of :func:`plan_als` leaves for
    :func:`als_grouped_bytes` under the HBM budget; None = unbounded."""
    hbm = Budgets.resolve().hbm
    if hbm <= 0:
        return None
    factors, moments = _als_fixed_bytes(n_users, n_items, rank)
    return int(hbm / _OVERHEAD) - 3 * factors - moments - _PROGRAM_BYTES


def plan_als(nnz: int, n_users: int, n_items: int, rank: int, *,
             world: int = 1,
             source_backing: Optional[str] = None,
             grouped: Optional[Sequence[Tuple[int, int]]] = None) -> RoutePlan:
    """Route plan for one ALS fit.  Candidates: the fully-resident
    grouped/COO layouts (in-memory), host-resident edges with chunked
    uploads (streamed), and the mesh-composed streamed block layout
    (streamed-block, world > 1 — per-rank layouts shrink world-fold).
    Source inputs keep host O(nnz) on every route (the triples ingest
    to host arrays, like the reference's executor partitions) — the
    streamed property is DEVICE memory.  ``grouped``: the ``(G, P)`` of
    BOTH grouped sides where the fit has counted them already (the
    single-device fit, after its counting pass), priced with their sheet
    (:func:`als_grouped_bytes`); elsewhere the edges are priced at
    ``_ALS_BLOWUP`` times the ratings a side.

    With ``grouped`` the destination-blocked route (``dst-blocked``,
    ``ops/als_ops.als_run_dst_blocked``) is a candidate between the two:
    the same resident layouts, no sheet and no whole-side equations, a
    block of destinations at a time.  It is taken where the resident
    grouped route does not fit — at rank 100 on the KDD-Cup'11 cell its
    sheet alone is 107 GB a side — and its block (``plan.dst_block``
    destinations) is the widest power of two up to ``_DST_BLOCK_MAX``
    that the budget leaves room for."""
    factors, moments = _als_fixed_bytes(n_users, n_items, rank)
    if grouped is None:
        edges = host_layouts = int(2 * nnz * _ALS_EDGE_BYTES * _ALS_BLOWUP)
    else:
        edges = als_grouped_bytes(grouped, rank)
        host_layouts = sum(g * p for g, p in grouped) * _ALS_EDGE_BYTES
    host_edges = host_layouts + 3 * nnz * 8  # grouped layouts + the id triples
    upload = 64 << 20  # bounded per-step group-chunk upload
    in_mem = RouteEstimate(
        ROUTE_IN_MEMORY,
        int((edges + 3 * factors + moments + _PROGRAM_BYTES) * _OVERHEAD),
        host_edges,
    )
    streamed = RouteEstimate(
        ROUTE_STREAMED,
        _calibrated("als", int(
            (3 * factors + moments + upload + _PROGRAM_BYTES) * _OVERHEAD
        )),
        host_edges,
    )
    if world > 1:
        block = RouteEstimate(
            ROUTE_STREAMED_BLOCK,
            _calibrated("als", int(
                (3 * factors // world + moments // world + upload
                 + _PROGRAM_BYTES) * _OVERHEAD
            )),
            host_edges // world + 3 * nnz * 8,
        )
        # multi-device worlds have no single-device candidates: the
        # block layout IS the natural route (and the only one offered —
        # restricting the device set is the num_user_blocks knob's job)
        plan = choose("ALS", [block], ROUTE_STREAMED_BLOCK)
    else:
        natural = (
            ROUTE_STREAMED if source_backing is not None
            else ROUTE_IN_MEMORY
        )
        ests = (
            [streamed, in_mem] if source_backing is not None
            else [in_mem, streamed]
        )
        dst_block = 0
        if grouped is not None:
            hbm = Budgets.resolve().hbm
            dst_block = _DST_BLOCK_MAX
            while dst_block > _DST_BLOCK_MIN and hbm > 0 and _als_dst_bytes(
                    grouped, n_users, n_items, rank, dst_block) > hbm:
                dst_block //= 2
            ests.insert(ests.index(streamed), RouteEstimate(
                ROUTE_DST_BLOCKED,
                _als_dst_bytes(grouped, n_users, n_items, rank, dst_block),
                host_edges,
            ))
        plan = choose("ALS", ests, natural)
        plan.dst_block = dst_block
    # triples stage as width-3 f64 chunks on the streamed ingest path
    plan.est_row_bytes = 3 * 8
    return plan


# -- spill: the resilience ladder's host-OOM rung -----------------------------


def spill_source(holder: Dict[str, object], algo: str) -> bool:
    """Stage ``holder["source"]`` (and the lockstep ``holder["weights"]``
    source, if any) to atomic disk spills and swap the holder onto the
    disk-backed replacements — the ladder re-runs its attempt reading
    from disk through the same prefetch pipeline.  Returns False (and
    warns) on any failure: the ladder falls through, the original
    source is untouched (SpillWriter never replaces a file it did not
    finish)."""
    try:
        src = holder["source"]
        spilled = src.spill_to_disk()
        w = holder.get("weights")
        if w is not None:
            holder["weights"] = w.spill_to_disk()
        holder["source"] = spilled
        holder["spilled"] = True
        _tm.counter(
            "oap_route_spills_total", {"algo": algo},
            help="Host-OOM spill rungs taken (table staged to disk)",
        ).inc()
        log.warning(
            "%s: spilled %s rows to %s", algo, spilled.n_rows,
            getattr(spilled, "backing", "disk"),
        )
        return True
    except Exception as e:  # noqa: BLE001 — the rung falls through
        log.warning("%s: spill to disk failed: %s", algo, e)
        return False


def spill_array(holder: Dict[str, object], x, weights, chunk_rows: int,
                algo: str) -> bool:
    """The in-memory route's spill hook: wrap the resident array (and
    optional per-row weights) as chunk sources, spill them, and leave
    the disk-backed sources in ``holder`` — the attempt closure re-reads
    the holder and re-enters the STREAMED route from disk."""
    from oap_mllib_tpu.data.stream import ChunkSource

    try:
        import numpy as np

        holder["source"] = ChunkSource.from_array(x, chunk_rows=chunk_rows)
        if weights is not None:
            holder["weights"] = ChunkSource.from_array(
                np.asarray(weights).reshape(-1, 1), chunk_rows=chunk_rows
            )
        return spill_source(holder, algo)
    except Exception as e:  # noqa: BLE001 — the rung falls through
        log.warning("%s: spill to disk failed: %s", algo, e)
        return False


# -- calibration: estimates learn from the bytes-staged telemetry ------------

_cal_lock = threading.Lock()
_cal: Dict[str, float] = {}
_CAL_ALPHA = 0.3  # EMA weight of the newest observation
_CAL_CLAMP = (0.25, 4.0)  # a wild ratio is a bug, not a calibration


def calibration_factor(algo: str) -> float:
    with _cal_lock:
        return _cal.get(algo, 1.0)


def reset_calibration() -> None:
    with _cal_lock:
        _cal.clear()


def _note_calibration(algo: str, estimated: float, actual: float) -> float:
    """Fold one fit's estimated-vs-observed staged bytes/row ratio into
    the per-algo EMA the next plan's streamed estimates are scaled by."""
    if estimated <= 0 or actual <= 0:
        return calibration_factor(algo)
    ratio = min(max(actual / estimated, _CAL_CLAMP[0]), _CAL_CLAMP[1])
    with _cal_lock:
        prev = _cal.get(algo, 1.0)
        _cal[algo] = prev + _CAL_ALPHA * (ratio - prev)
        return _cal[algo]


# -- exposure: summary.route + route span + oap_route_* metrics ---------------


def record_plan(summary, plan: Optional[RoutePlan], *,
                spilled: bool = False) -> None:
    """Attach the plan to the fit summary (``summary["route"]`` /
    ``summary.route`` — the merge_stats convention), annotate the span
    tree's ``route`` node, book the ``oap_route_*`` metrics, and fold
    the streamed estimate-vs-actual staged bytes into the calibration
    EMA.  Call BEFORE telemetry.finalize_fit so the exporters see it."""
    if summary is None or plan is None:
        return
    d = plan.as_dict()
    if spilled:
        d["spilled"] = True
    # estimate-vs-actual cross-check: the bytes/row the pipeline
    # actually staged this fit (the accounting telemetry already
    # collects per pass) against the bytes/row the planner priced —
    # the ratio calibrates the next plan's streamed estimates
    actual_b = _tm.family_total("oap_stream_bytes_staged_total") \
        - plan.stream_marker
    actual_r = _tm.family_total("oap_stream_rows_total") - plan.rows_marker
    if actual_b > 0:
        d["actual_bytes_staged"] = int(actual_b)
    if actual_b > 0 and actual_r > 0 and plan.est_row_bytes > 0:
        observed = actual_b / actual_r
        d["staged_bytes_per_row"] = round(observed, 2)
        d["estimated_bytes_per_row"] = round(plan.est_row_bytes, 2)
        d["calibration"] = round(
            _note_calibration(
                plan.algo.lower(), plan.est_row_bytes, observed
            ), 4,
        )
    labels = {"algo": plan.algo, "route": plan.route}
    _tm.counter(
        "oap_route_decisions_total", labels,
        help="Route-planner decisions by algorithm and chosen route",
    ).inc()
    chosen = plan.estimate_for(plan.route)
    if chosen is not None:
        _tm.gauge(
            "oap_route_estimated_hbm_bytes", labels,
            help="Planner HBM estimate of the chosen route",
        ).set(float(max(chosen.hbm_bytes, 0)))
        _tm.gauge(
            "oap_route_estimated_host_bytes", labels,
            help="Planner host-RAM estimate of the chosen route",
        ).set(float(max(chosen.host_bytes, 0)))
    if plan.over_budget:
        _tm.counter(
            "oap_route_over_budget_total", {"algo": plan.algo},
            help="Fits where no candidate route fit the budget",
        ).inc()
    if plan.degraded_scale or plan.downgrades:
        _tm.counter(
            "oap_route_downgrades_total", labels,
            help="Fits moved off their natural route (budget or guard)",
        ).inc()
    if isinstance(summary, dict):
        summary["route"] = d
        timings = summary.get("timings")
    else:
        summary.route = d
        timings = getattr(summary, "timings", None)
    if timings is not None and getattr(timings, "root", None) is not None:
        timings.root.node("route").attrs.update(d)
