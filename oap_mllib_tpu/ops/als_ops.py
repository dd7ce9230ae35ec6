"""Implicit ALS compute kernels: jitted alternating least squares.

Replaces the reference's oneDAL 4-step distributed implicit ALS
(native/ALSDALImpl.cpp): there, each half-iteration runs step1Local
(partial cross-products), gathers serialized partials to the root
(:53-97), the root's step2Master forms the global cross-product (:261-281)
and broadcasts it back, step3Local/step4Local exchange partial models
all-to-all and solve per-block factors (:283-316) — plus a native ratings
shuffle and a transposed item-major CSR copy per rank (ALSShuffle.cpp,
ALSDALImpl.cpp:192-214).

TPU-first redesign — the whole half-iteration is three MXU/VPU passes over
a COO ratings tensor, no transposed copy and no master rank:

1. Gram: ``G = Y^T Y`` — one (r, n)x(n, r) matmul, psum over the mesh.
   (This is steps 1+2: the "cross-product" IS the Gram matrix.)
2. Per-edge contributions: for each rating (u, i, c): gather ``y_i``,
   form ``alpha*c * y_i y_i^T`` (nnz, r, r) and ``(1+alpha*c) y_i``
   (nnz, r), then ``segment_sum`` by user — XLA scatter-adds, the
   all-to-all-free equivalent of steps 3+4's partial-model exchange.
3. Solve: batched (r, r) Cholesky/LU solve over all users at once.

The item update reuses the SAME COO arrays with the index roles swapped —
the reference's per-rank transposed table (ALSDALImpl.cpp:209-213) has no
equivalent here because segment_sum doesn't care about sort order.

Padded COO entries carry ``valid = 0`` so they vanish from both A and b
(survey §2.6 fixed-shape design note).  dtype float32, matching the
reference kernel (ALSDALImpl.cpp:35 ``CpuAlgorithmFPType = float``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from oap_mllib_tpu.utils import precision as psn
from oap_mllib_tpu.utils import progcache


def _edge_chunks(nnz: int, r: int, budget_elems: int = 1 << 24) -> int:
    """Chunk count for the (chunk, r, r) per-edge outer-product buffer.

    Power-of-two divisors of nnz so the live intermediate stays under
    ``budget_elems`` (peak memory O(chunk * r^2 + n_dst * r^2) instead of
    O(nnz * r^2) — at MovieLens-25M scale the unchunked buffer would blow
    HBM).  Callers pad nnz to a power-of-two-friendly multiple.
    """
    chunks = 1
    while (nnz // chunks) * r * r > budget_elems and nnz % (chunks * 2) == 0:
        chunks *= 2
    return chunks


def normal_eq_partials(
    dst_idx: jax.Array,  # (nnz,) int32 — side being solved (e.g. users)
    src_idx: jax.Array,  # (nnz,) int32 — fixed side (e.g. items)
    conf: jax.Array,  # (nnz,) f32 ratings/confidences
    valid: jax.Array,  # (nnz,) f32 1/0 mask
    src_factors: jax.Array,  # (n_src, r)
    n_dst: int,
    alpha: float,
    implicit: bool,
    policy: str = "f32",
):
    """Per-edge normal-equation partials grouped by dst id — Spark parity.

    Implicit (reference ALS.scala:1781-1795): with c1 = alpha * |r|,
    A += c1 * y y^T for EVERY rating (|r| keeps A PSD for non-positive
    ratings), b += (1 + c1) * y only when r > 0 (preference 0 otherwise),
    and the regularization count n_reg counts only r > 0 ratings.
    Explicit: A += y y^T, b += r * y, n_reg counts all ratings.  The
    returned n_reg feeds both ALS-WR lambda scaling (Spark scales reg by
    the per-row rating count: solve(ne, numExplicits * regParam)) and the
    empty-row factor masking.

    Returns (a_part (n_dst, r, r), b (n_dst, r), n_reg (n_dst,)).  Shared
    by the global-program path (this file) and the block-parallel path
    (als_block.py, which psums these across the mesh) so the two can never
    diverge in the weighting math.  Edge-chunked via lax.scan so the
    (chunk, r, r) outer-product intermediate never scales with nnz.

    ``policy`` (utils/precision.py) governs the per-edge factor outer
    products: bf16 casts the gathered factor rows and accumulates f32
    (b/n segment-sums and the solves stay f32); the f32 default keeps
    the pre-policy HIGHEST einsum bit-for-bit.
    """
    nnz = dst_idx.shape[0]
    r = src_factors.shape[1]
    chunks = _edge_chunks(nnz, r)

    def partial_chunk(dst_c, src_c, conf_c, valid_c):
        ys = src_factors[src_c]  # (cs, r) gather
        if implicit:
            a_w = alpha * jnp.abs(conf_c) * valid_c
            pos = (conf_c > 0).astype(conf_c.dtype) * valid_c
            b_w = (1.0 + alpha * jnp.abs(conf_c)) * pos
            n_w = pos
        else:
            a_w = valid_c
            b_w = conf_c * valid_c
            n_w = valid_c
        outer = psn.peinsum(
            "er,es->ers", ys * a_w[:, None], ys, policy
        )  # (cs, r, r) — f32 accumulation under every policy
        a_c = jax.ops.segment_sum(outer, dst_c, num_segments=n_dst)
        b_c = jax.ops.segment_sum(ys * b_w[:, None], dst_c, num_segments=n_dst)
        n_c = jax.ops.segment_sum(n_w, dst_c, num_segments=n_dst)
        return a_c, b_c, n_c

    if chunks == 1:
        return partial_chunk(dst_idx, src_idx, conf, valid)

    cs = nnz // chunks
    def step(carry, chunk):
        a0, b0, n0 = carry
        a_c, b_c, n_c = partial_chunk(*chunk)
        return (a0 + a_c, b0 + b_c, n0 + n_c), None

    zero = (
        jnp.zeros((n_dst, r, r), src_factors.dtype),
        jnp.zeros((n_dst, r), src_factors.dtype),
        jnp.zeros((n_dst,), src_factors.dtype),
    )
    chunked = tuple(
        a.reshape(chunks, cs) for a in (dst_idx, src_idx, conf, valid)
    )
    (a_part, b, n_reg), _ = lax.scan(step, zero, chunked)
    return a_part, b, n_reg


def _chol_solve_unrolled(a: jax.Array, b: jax.Array) -> jax.Array:
    """Batched SPD solve for TINY static ranks, fully unrolled.

    XLA's general batched ``cholesky`` + ``solve_triangular`` is
    latency-bound at ALS sizes — measured 46.8 ms for a (6040, 10, 10)
    factorization on v5e (round 3), thousands of times the arithmetic
    cost.  With ``r`` small and static, the r elimination steps unroll
    into ~3r fused batch-wide vector ops (each O(B·r) / O(B·r²)):
    column-by-column Cholesky via rank-1 Schur downdates, then unrolled
    forward/back substitution.  Measured 0.9 ms for the same batch —
    ~50x.  Singular/non-SPD inputs produce NaN (sqrt of a negative or
    0-division) exactly like the library path, which the caller's
    nan_to_num + degree mask absorb.
    """
    r = b.shape[-1]
    idx = jnp.arange(r)
    # batch-LAST layout: (r, r, B) puts the big batch dim on the 128-lane
    # axis — batch-first (B, r, r) would pad both r-sized minor dims to
    # the (8, 128) vreg tile, a >10x memory/compute blowup at r=10.
    # NO scatters anywhere (scatter breaks XLA fusion, leaving ~3r
    # sequential kernel launches — that alone measured 22 ms): L lives as
    # a Python list of (r, B) columns, substitution as (B,) rows.
    at = jnp.transpose(a, (1, 2, 0))  # (r, r, B)
    cols = []
    for j in range(r):
        d = jnp.sqrt(at[j, j])  # (B,)
        col = (at[:, j] / d[None, :]) * (idx >= j)[:, None]  # (r, B)
        cols.append(col)
        at = at - col[:, None, :] * col[None, :, :]  # Schur downdate
    rhs = [b.T[j] for j in range(r)]  # (B,) rows
    z = [None] * r
    for j in range(r):  # forward: L z = b
        z[j] = rhs[j] / cols[j][j]
        for i in range(j + 1, r):
            rhs[i] = rhs[i] - cols[j][i] * z[j]
    w = [None] * r
    for j in reversed(range(r)):  # back: L^T w = z; L^T[j, k] = cols[j][k]
        acc = z[j]
        for k in range(j + 1, r):
            acc = acc - cols[j][k] * w[k]
        w[j] = acc / cols[j][j]
    return jnp.stack(w, axis=1)  # (B, r)


# The largest rank the unrolled batch-wide solves take: the XLA one
# (:func:`_chol_solve_unrolled`) and the fused Pallas one
# (ops/pallas/als_kernel.py); larger ranks take the library routines.
UNROLLED_RANK_MAX = 32


def masked_solve(a: jax.Array, b: jax.Array, deg: jax.Array) -> jax.Array:
    """Batched SPD solve via Cholesky; rows with no (reg-counted) ratings
    get zero factors (fallback-path semantics).  Small static ranks (the
    ALS regime — Spark's default is 10) take the unrolled batch-wide
    factorization (:func:`_chol_solve_unrolled`, ~50x the library path's
    latency-bound lowering); larger ranks use the library routines.  A
    singular/non-SPD A (possible at reg=0) yields NaN either way, which
    nan_to_num + the degree mask absorb."""
    if b.shape[-1] <= UNROLLED_RANK_MAX:
        factors = _chol_solve_unrolled(a, b)
    else:
        import jax.scipy.linalg as jsl

        chol = jnp.linalg.cholesky(a)
        z = jsl.solve_triangular(chol, b[:, :, None], lower=True)
        factors = jsl.solve_triangular(
            chol.transpose(0, 2, 1), z, lower=False
        )[:, :, 0]
    return jnp.where(deg[:, None] > 0, jnp.nan_to_num(factors), 0.0)


# ---------------------------------------------------------------------------
# Grouped-edge path: scatter-free normal equations (single-device hot path)
# ---------------------------------------------------------------------------
# The COO path above pays one scatter of (nnz, r, r) outer products per
# half-iteration — measured 83 ms/iter at MovieLens-1M scale on v5e, ~12x
# the cost of streaming the same bytes.  The TPU-first layout instead sorts
# edges by destination ONCE (indices are static across iterations) and pads
# each destination's edge list to a multiple of P, so every P-edge group
# belongs to exactly one destination.  The whole normal-equation build then
# becomes ONE batched MXU matmul per group,
#
#     [Ys | 1]^T @ [a_w*Ys | b_w | n_w]   ->  (r+1, r+2)
#
# whose blocks are A (r x r), b (col r), and the reg count (at [r, r+1]),
# plus a group->destination segment-sum of tiny (r+1, r+2) tiles — no
# scatter anywhere in the half-iteration partials, which the TPU executes
# far slower than batched matmuls.  This is the reference's blocked-CSR idea
# (ALSDALImpl.scala:184-230 builds per-rank CSR precisely so oneDAL can
# batch row solves) rebuilt for the MXU.  The width P is the layout's one
# free choice: every slot, pad or not, is gathered and multiplied, so a
# side is as fast as it has few slots, and every halving of P doubles the
# groups.  The single-device fit picks it from the degrees it has counted
# (group_sizes_for); callers without counts take the mean's
# (auto_group_size).


# Guard shared by the single-device and block-parallel dispatchers: the
# grouped layout is taken only while its padded edge total stays within
# this factor of the true edge count (extreme long-tail degree splits fall
# back to the COO programs).  One definition so the two paths cannot
# silently route the same dataset to different kernels.
def unpack_flat_moments(m_flat: jax.Array, r: int):
    """(a, b, n_reg) from a flat (n_dst, (r+1)(r+2)) moment carry — the
    layout every streamed accumulate produces (als_stream,
    als_block_stream)."""
    n_dst = m_flat.shape[0]
    m = m_flat.reshape(n_dst, r + 1, r + 2)
    return m[:, :r, :r], m[:, :r, r], m[:, r, r + 1]


def regularized_solve(a, b, n_reg, reg, eye, gram=None,
                      kernel: str = "xla",
                      geometry=None) -> jax.Array:
    """THE half-update solve every ALS path consumes moments through
    (single-device grouped/COO, streamed, block-parallel, streamed
    block): ALS-WR lambda scaling (reg x per-row rating count — Spark
    parity, reference ALS.scala:1794-1795), optional implicit-feedback
    Gram term, masked Cholesky.  One definition so the paths cannot
    diverge in the regularization convention.

    ``kernel`` selects the consumer: "xla" (default) keeps the
    batch-wide unrolled solve below; "pallas" routes through the fused
    assembly+solve kernel (ops/pallas/als_kernel.solve_traced — same
    elimination sequence, one HBM read of the moments, resolved by
    :func:`resolve_solve_kernel`); "pallas_interpret" is the CPU
    interpret-mode leg tier-1 exercises the full runners through.
    ``geometry``: tuned ``(batch, depth)`` for the pallas consumer
    (ops/pallas/autotune, resolved eagerly by the runner wrappers and
    threaded here as jit statics; None keeps the hand-picked
    constants)."""
    if kernel.startswith("pallas"):
        from oap_mllib_tpu.ops.pallas.als_kernel import solve_traced

        batch, depth = geometry if geometry else (None, None)
        return solve_traced(
            a, b, n_reg, reg, gram, interpret=kernel == "pallas_interpret",
            batch=batch, depth=depth,
        )
    a = a + reg * n_reg[:, None, None] * eye[None]
    if gram is not None:
        a = gram[None] + a
    return masked_solve(a, b, n_reg)


def _factor_gram(factors, kernel: str = "xla", geometry=None):
    """The implicit-feedback Gram ``F^T F`` feeding regularized_solve —
    psn.pdot on the XLA route, the streamed Pallas factor-Gram kernel on
    the pallas routes.  Pinned mode="highest" either way: Grams condition
    the solve and never run reduced (utils/precision.py contract).
    ``geometry``: tuned ``(tile_rows, depth)`` statics, like
    :func:`regularized_solve`."""
    if kernel.startswith("pallas"):
        from oap_mllib_tpu.ops.pallas.als_kernel import factor_gram_traced

        tile_rows, depth = geometry if geometry else (None, None)
        return factor_gram_traced(
            factors, "highest", interpret=kernel == "pallas_interpret",
            tile_rows=tile_rows, depth=depth,
        )
    return psn.pdot(factors.T, factors)


def resolve_solve_kernel(r: int, dtype=None, cfg=None) -> str:
    """Resolve Config.als_solve_kernel to the concrete consumer for this
    fit — the single decision point every ALS runner (single-device,
    block-parallel, streamed) resolves through, so two paths cannot
    route the same fit to different solve kernels.  "auto" takes the
    fused Pallas kernel on TPU with f32 factors in the unrolled-rank
    regime (r <= :data:`UNROLLED_RANK_MAX`); anything else — CPU tier-1
    included — keeps the XLA path.  A typo'd value raises on EVERY accelerated fit."""
    import numpy as np

    from oap_mllib_tpu.config import get_config

    cfg = cfg or get_config()
    choice = cfg.als_solve_kernel
    if choice not in ("auto", "xla", "pallas"):
        raise ValueError(
            f"als_solve_kernel must be auto|xla|pallas, got {choice!r}"
        )
    from oap_mllib_tpu.ops.pallas.als_kernel import pallas_solve_preferred

    want = choice == "pallas" or (
        choice == "auto" and pallas_solve_preferred(r)
    )
    if (
        want
        and jax.default_backend() == "tpu"
        and r <= UNROLLED_RANK_MAX
        and (dtype is None or np.dtype(dtype) == np.float32)
    ):
        return "pallas"
    return "xla"


def resolve_gather_kernel(n_src: int, r: int, dtype=None,
                          backend: str = "") -> str:
    """Which program gathers the source factors' rows of the grouped
    moments (:func:`gather_factor_rows`): "pallas", the VMEM-resident
    walk of ops/pallas/als_gather.py, on a TPU with float32 factors whose
    packed table fits its bound (``als_gather.fits``: 1,048,576 sources
    at rank 10); "xla", XLA's gather, elsewhere — the CPU of tier-1
    included.  One rule over what a fit can observe, no switch: the two
    give the same bits.  ``backend`` ("" = ``jax.default_backend()``) is
    the test seam."""
    import numpy as np

    from oap_mllib_tpu.ops.pallas import als_gather

    if (
        (backend or jax.default_backend()) == "tpu"
        and (dtype is None or np.dtype(dtype) == np.float32)
        and als_gather.fits(n_src, r)
    ):
        return "pallas"
    return "xla"


def gather_factor_rows(src_factors: jax.Array, src_b: jax.Array,
                       kernel: str = "", table=None) -> jax.Array:
    """``src_factors.T[:, src_b]``: the ``(r, Gb, P)`` factor rows of a
    block's slots, by XLA's gather or, on ``kernel`` "pallas", the walk
    over the packed ``table`` (``als_gather.pack_table(src_factors)``,
    packed here when not handed in) — the same bits either way.
    ``kernel``: "" resolves :func:`resolve_gather_kernel`;
    "pallas_interpret" runs the walk in interpret mode (the CPU tests)."""
    n_src, r = src_factors.shape
    kernel = kernel or resolve_gather_kernel(n_src, r, src_factors.dtype)
    if not kernel.startswith("pallas"):
        return src_factors.T[:, src_b]
    from oap_mllib_tpu.ops.pallas import als_gather

    if table is None:
        table = als_gather.pack_table(src_factors)
    return als_gather.gather_walk(
        table, src_b, r, interpret=kernel == "pallas_interpret"
    )


GROUPED_MAX_BLOWUP = 6.0


def grouped_padded_edges(dst, n_dst: int, group_size: int = 0) -> int:
    """Padded edge count the grouped layout WOULD produce for one side —
    the blowup-guard input, from per-destination counts alone (no sort of
    payloads, no (G, P) materialization).  Destinations with zero edges
    pad to zero, so counting only the present ones gives the exact total
    build_grouped_edges would realize.  Prefers the native counting pass
    (O(nnz + n_dst), native/src/grouped_prep.cpp) over np.unique's sort."""
    import numpy as np

    from oap_mllib_tpu.data.io import _force_py

    p = group_size or auto_group_size(len(dst), n_dst)
    if not _force_py():
        from oap_mllib_tpu import native

        total = native.als_grouped_total(np.asarray(dst, np.int64), n_dst, p)
        if total is not None:
            return total
    _, counts = np.unique(np.asarray(dst, np.int64), return_counts=True)
    return int((-(counts // -p) * p).sum())


def auto_group_size(nnz: int, n_dst: int) -> int:
    """Group size for callers that have no per-destination counts (the
    block routes' per-block layouts, ops/als_block.py; ``group_size=0``
    of :func:`build_grouped_edges` / :func:`grouped_padded_edges`): the
    next power of two ABOVE the mean degree, capped at 256, which keeps
    total padded edges <= nnz + n_dst*P < 3*nnz.  It never looks at the
    degrees: on a heavy tail most destinations sit far under the mean and
    carry one group that is mostly pad.  The single-device fit, which has
    counted, asks :func:`group_sizes_for` and reports this value beside
    its choice (``group_size_by_mean`` of the ``group_edges`` span)."""
    import numpy as np

    mean_deg = max(1.0, nnz / max(1, n_dst))
    return int(max(8, min(256, 2 ** int(np.ceil(np.log2(mean_deg))))))


_BUILD_THREADS_MAX = 12


def build_threads() -> int:
    """Host threads the grouped build spreads over: the cores this
    process may run on, at most ``_BUILD_THREADS_MAX`` (the build is
    bound by the host's memory well before that many)."""
    import os

    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:  # not Linux
        cores = os.cpu_count() or 1
    return max(1, min(_BUILD_THREADS_MAX, cores))


def count_edges(dst, n_dst: int, threads: int = 0):
    """``(ranges, n_dst)`` int32: how often each destination occurs in
    each of ``ranges`` equal ranges of the edges — the one counting pass
    the blowup guard, the route plan and :func:`build_grouped_edges` all
    read (``counts=``).  ``threads`` ranges on the native path (0 = the
    cores at hand), counted on as many host threads from int32 ids as
    they are; one range by ``np.bincount`` elsewhere.  An id outside
    [0, n_dst) raises on either path."""
    import numpy as np

    from oap_mllib_tpu.data.io import _force_py

    dst = np.ascontiguousarray(dst, dtype=np.int32)
    if not _force_py() and n_dst > 0 and len(dst):
        from oap_mllib_tpu import native

        counts = native.als_count_ranges(
            dst, n_dst, threads or build_threads()
        )
        if counts is not None:
            return counts
    if len(dst) and (dst.min() < 0 or dst.max() >= n_dst):
        raise ValueError("destination id out of range for grouped layout")
    return np.bincount(dst, minlength=n_dst).astype(np.int32)[None, :]


def padded_edges(counts, group_size: int) -> int:
    """Slots the grouped layout takes for these :func:`count_edges`
    counts: every destination's edges rounded up to whole groups."""
    import numpy as np

    per_dst = counts.sum(axis=0, dtype=np.int64)
    return int((-(per_dst // -group_size) * group_size).sum())


# the widths a grouped side may take, widest first (ties go to the wider)
_GROUP_SIZES = (256, 128, 64, 32, 16, 8)

# What the width of a grouped side moves in one half-update, in ns on a
# v5e.  Measured once, at the KDD-Cup'11 cell's shape (500,495 x 624,961,
# 126.4M ratings, rank 10), by cycling P = 256 / 128 / 64 / 32 over both
# sides' built layouts in one process: the three terms and a constant a
# side reproduce all eight half-updates (0.89 ... 1.26 s) within 0.15%
# (PERF.md section 6, PR 39).
# - a slot, pad or not: the factor-row gather, ten floats at about four
#   cycles an index whatever the bytes — XLA's gather;
_SLOT_NS = 4.6
#   the walk over the VMEM-resident packed table (ops/pallas/als_gather,
#   resolve_gather_kernel "pallas"): 2.18 ns a slot beyond its table
#   fill at the cell's two tables, a block of 2^20 slots in groups of
#   128, and 2.25 by a half-update's difference from XLA's at P = 128
#   (dev/als_gather_ab.py lever D; PERF.md section 6);
_WALK_SLOT_NS = 2.2
# - a slot of a group's row as the moment products and layout copies
#   hold it, padded to the 128 lanes: below P = 128 a group costs 128;
_LANE_SLOT_NS = 0.53
# - a group of the BUCKET, live or pad: the sheet of group moments is
#   zeroed, copied and segment-summed whole (the live groups alone fit a
#   term of their own at -6 ns: none).
_BUCKET_GROUP_NS = 16.0


def group_sizes_for(counts, r: int, room=None, gather: str = "xla",
                    least: int = 0):
    """The group width ``P`` of each grouped side, from the degrees the
    fit has counted: ``counts`` holds one :func:`count_edges` a side.
    Every width of ``_GROUP_SIZES`` is priced from the groups it would
    make of these degrees (one pass over ``n_dst`` integers),

        groups * (P * slot + max(P, 128) * _LANE_SLOT_NS)
        + group_bucket(groups) * _BUCKET_GROUP_NS,

    where ``slot`` is what a slot's gather costs on the route that runs
    (``gather``, :func:`resolve_gather_kernel`): ``_SLOT_NS`` for XLA's,
    ``_WALK_SLOT_NS`` for the walk; and the cheapest set of widths wins,
    the wider on a tie: a side
    whose destinations all have about 250 edges keeps 256, a heavy tail
    gets what its tail wants.  ``room`` (bytes,
    ``membudget.als_grouped_room``; None = unbounded) is what the
    resident route can give the sides' bucketed layouts and the sheet of
    group moments of the side with more groups
    (``membudget.als_grouped_bytes``): a set of widths that needs more
    is no candidate — the sheet doubles with every halving of ``P``, and
    a row of fewer than 128 slots still takes 128 lanes — unless none
    fits, and then the streamed route, which holds neither, runs the
    cheapest.  ``least``: the narrowest width a side may take (the
    destination-blocked route multiplies a group as one tile of whole
    lanes, :data:`DST_LEAST_GROUP`)."""
    import itertools

    import numpy as np

    from oap_mllib_tpu.utils.membudget import als_grouped_bytes

    slot_ns = _WALK_SLOT_NS if gather.startswith("pallas") else _SLOT_NS
    priced = []  # a side: (ns, P, bucketed G) of every width
    for side in counts:
        per_dst = side.sum(axis=0, dtype=np.int64)
        options = []
        for p in (p for p in _GROUP_SIZES if p >= least):
            groups = int((-(per_dst // -p)).sum())
            bucket = group_bucket(groups)
            ns = groups * (
                p * slot_ns + max(p, 128) * _LANE_SLOT_NS
            ) + bucket * _BUCKET_GROUP_NS
            options.append((ns, p, bucket))
        priced.append(options)
    choices = list(itertools.product(*priced))  # widest first
    fitting = [
        c for c in choices
        if room is None
        or als_grouped_bytes([(g, p) for _, p, g in c], r) <= room
    ]
    # min keeps the first of equals: the wider
    chosen = min(fitting or choices, key=lambda c: sum(ns for ns, _, _ in c))
    return [p for _, p, _ in chosen]


def group_bucket(groups: int) -> int:
    """The group count a grouped side is padded to: ``groups`` on the
    row-bucket series of data/bucketing.py (x2 steps by default,
    ``Config.shape_bucketing``).  ``als.run_grouped`` is keyed on
    ``(G, P)`` and ``G`` follows the data, so without it every new table
    — a refit with a few more ratings — compiles anew.  Pad groups carry
    ``valid = 0`` and the last destination's id; the program walks only
    as far as the live groups reach (``normal_eq_partials_grouped``)."""
    from oap_mllib_tpu.data.bucketing import bucket_rows

    return bucket_rows(groups, _GROUP_BUCKET_MULTIPLE)


# anchor of the group-count buckets: a power of two, so that a bucketed
# side splits into its power-of-two block count with no remainder (a
# remainder is padded INSIDE the program: a second copy of the layout)
_GROUP_BUCKET_MULTIPLE = 256


def build_grouped_edges(
    dst: "np.ndarray",
    src: "np.ndarray",
    conf: "np.ndarray",
    n_dst: int,
    group_size: int = 0,
    *,
    counts=None,
    groups: int = 0,
    threads: int = 0,
):
    """Host-side one-time prep: sort edges by ``dst`` and pad each dst's
    edge list to a multiple of ``group_size`` — the single-device fit's
    comes from the counted degrees (:func:`group_sizes_for`); 0 = from
    the mean degree, for callers without counts (:func:`auto_group_size`).

    Returns (src_g (H, P) int32, conf_g (H, P) f32, valid_g (H, P) f32,
    group_dst (G,) int32).  Padding entries carry src=0, valid=0 so they
    vanish from every weighted sum.  ~1.2x edge blowup at P=64 on
    MovieLens-like degree distributions.  ``groups`` > the groups the
    edges fill pads ``G`` up to it (:func:`group_bucket`): pad groups
    carry the last destination's id, so ``group_dst`` stays sorted, and
    are all zeros — on the DEVICE: the other three arrays hold the
    first ``H`` groups, the live ones alone where they fill an upload
    piece (data/table.held_rows; ``H == G`` for small layouts and where
    ``groups`` pads nothing), and ``data/table.upload_arrays(...,
    rows=)`` with ``G`` makes the rest as zeros there, never sent.

    Prefers the native stable counting sort (O(nnz + n_dst),
    native/src/grouped_prep.cpp — the reference's host-side CSR prep
    analog, ALSDALImpl.cpp:184-230) over the NumPy argsort path: int32
    ids as Spark holds them (other dtypes are cast once), counted and
    placed over ``threads`` host threads (0 = the cores at hand), each
    on a range of the edges — the same layout bit for bit whatever their
    number.  ``counts``: :func:`count_edges` of the same ``dst``, where
    the caller has counted already.
    """
    import numpy as np

    from oap_mllib_tpu.data.io import _force_py
    from oap_mllib_tpu.data.table import held_rows

    P = group_size or auto_group_size(len(dst), n_dst)
    native_ok = False
    if not _force_py() and n_dst > 0 and len(dst):
        from oap_mllib_tpu import native

        native_ok = native.available()
    if native_ok:
        dst = np.ascontiguousarray(dst, dtype=np.int32)
        src = np.ascontiguousarray(src, dtype=np.int32)
        conf = np.ascontiguousarray(conf, dtype=np.float32)
        if counts is None:
            counts = count_edges(dst, n_dst, threads)
        per_dst = counts.sum(axis=0, dtype=np.int64)
        start = np.zeros(n_dst + 1, np.int64)
        np.cumsum(-(per_dst // -P) * P, out=start[1:])
        total = int(start[-1])
        G = max(total // P, groups)
        H = held_rows(G, total // P, P * 4)
        # zeros, not empty: the rows past the live groups go up as pad
        src_g = np.zeros((H, P), np.int32)
        conf_g = np.zeros((H, P), np.float32)
        valid_g = np.zeros((H, P), np.float32)
        group_dst = np.full((G,), max(n_dst - 1, 0), np.int32)
        native.als_place_ranges(
            dst, src, conf, counts, start, P, src_g.reshape(-1),
            conf_g.reshape(-1), valid_g.reshape(-1), group_dst,
        )
        return src_g, conf_g, valid_g, group_dst
    dst = np.asarray(dst, np.int64)
    order = np.argsort(dst, kind="stable")
    d = dst[order]
    counts = np.bincount(d, minlength=n_dst)
    padded = ((counts + P - 1) // P) * P
    starts = np.concatenate([[0], np.cumsum(padded)])[:-1]
    first = np.concatenate([[0], np.cumsum(counts)])[:-1]
    slot = starts[d] + (np.arange(len(d)) - first[d])
    total = int(padded.sum())
    G = max(total // P, groups)
    H = held_rows(G, total // P, P * 4)
    src_g = np.zeros(H * P, np.int32)
    conf_g = np.zeros(H * P, np.float32)
    valid_g = np.zeros(H * P, np.float32)
    src_g[slot] = np.asarray(src, np.int32)[order]
    conf_g[slot] = np.asarray(conf, np.float32)[order]
    valid_g[slot] = 1.0
    group_dst = np.full(G, max(n_dst - 1, 0), np.int32)
    group_dst[: total // P] = np.repeat(
        np.arange(n_dst, dtype=np.int32), padded // P
    )
    return (
        src_g.reshape(H, P),
        conf_g.reshape(H, P),
        valid_g.reshape(H, P),
        group_dst,
    )


# live-element budget for one grouped-partials block: the (r+..., Gc, P)
# intermediates of a block stay near 256 MB f32 so ML-25M-scale sides
# (40M+ padded edges) fit one chip — unchunked, XLA materialized a
# (padded_nnz, r) gather fusion whose (8,128) lane padding alone was
# 21 GB (measured OOM at 25M nnz, round 3)
_GROUPED_BUDGET_ELEMS = 1 << 26


def _grouped_block_count(G: int, P: int, r: int) -> int:
    """Smallest power-of-two block count keeping a block under budget.

    The per-block cost model charges XLA's (8, 128) lane padding — a
    (…, Gb, P) buffer with P < 128 still occupies 128 lanes — and the ~3
    concurrently-live (r+2)-deep intermediates (ys / lhs / rhs), so the
    bound holds for small-P long-tail sides too, not just the aligned
    P=128/256 layouts it was measured on.  Stops subdividing at one
    group per block (a budget below a single padded row cannot hang)."""
    lanes = max(P, 128)
    n = 1
    while n < G and (-(-G // n)) * lanes * (r + 2) * 3 > _GROUPED_BUDGET_ELEMS:
        n *= 2
    return n


def grouped_block_moments(
    src_b: jax.Array,  # (Gb, P) int32
    conf_b: jax.Array,
    valid_b: jax.Array,
    src_factors: jax.Array,  # (n_src, r)
    alpha,
    implicit: bool,
    policy: str = "f32",
    gather: str = "",
    table=None,
) -> jax.Array:
    """(Gb, r+1, r+2) normal-equation moment matrices for one group
    block — the MXU inner kernel shared by the in-memory grouped partials
    (:func:`normal_eq_partials_grouped`) and the host-chunked streamed
    accumulate (ops/als_stream.py), so the two paths cannot diverge in
    the weighting math.  Layout note: the transposed gather keeps the big
    static group width P on the 128-lane axis (see the grouped-path
    module notes).  ``gather`` / ``table``: the gather's program and its
    packed table (:func:`gather_factor_rows`)."""
    # (r, Gb, P) transposed gather
    ys = gather_factor_rows(src_factors, src_b, gather, table)
    a_w, b_w, n_w = slot_weights(conf_b, valid_b, alpha, implicit)
    lhs = jnp.concatenate(
        [ys, jnp.ones_like(conf_b)[None]], axis=0
    )  # (r+1, Gb, P)
    rhs = jnp.concatenate(
        [ys * a_w[None], b_w[None], n_w[None]], axis=0
    )  # (r+2, Gb, P)
    return psn.peinsum(
        "agp,bgp->gab", lhs, rhs, policy
    )  # (Gb, r+1, r+2)  <- batched MXU, P-lane contraction; bf16 policy
    # casts the factor-carrying lhs/rhs tiles and accumulates f32 — the
    # per-destination moment tiles (and the solves they feed) stay f32


def slot_weights(conf, valid, alpha, implicit: bool):
    """``(a_w, b_w, n_w)`` of a grouped layout's slots: what a rating adds
    to its destination's ``A`` (times ``y y^T``), ``b`` (times ``y``) and
    regularization count — Spark's weighting of :func:`normal_eq_partials`
    (implicit: ``alpha |r|``, ``(1 + alpha |r|)`` and 1 for ``r > 0``
    only; explicit: 1, ``r``, 1); pad slots (``valid`` 0) add nothing."""
    if implicit:
        a_w = alpha * jnp.abs(conf) * valid
        pos = (conf > 0).astype(conf.dtype) * valid
        b_w = (1.0 + alpha * jnp.abs(conf)) * pos
        n_w = pos
    else:
        a_w = valid
        b_w = conf * valid
        n_w = valid
    return a_w, b_w, n_w


def normal_eq_partials_grouped(
    src_g: jax.Array,  # (G, P) int32
    conf_g: jax.Array,  # (G, P) f32
    valid_g: jax.Array,  # (G, P) f32
    group_dst: jax.Array,  # (G,) int32, sorted
    src_factors: jax.Array,  # (n_src, r)
    n_dst: int,
    alpha: float,
    implicit: bool,
    policy: str = "f32",
    live_groups=None,
    gather: str = "",
):
    """Scatter-free normal-equation partials: same math and Spark-parity
    weighting as :func:`normal_eq_partials`, grouped-edge layout.

    ``gather``: the program that gathers the source factors' rows ("" =
    :func:`resolve_gather_kernel` on their shape); the walk's packed
    table is made once here, outside the loop over group blocks.

    ``live_groups`` (a traced int32 scalar, :func:`live_group_count`):
    the groups that hold an edge, where ``G`` was padded up to its
    bucket (:func:`group_bucket`).  The walk over group blocks then
    stops behind the block that holds the last of them, so a side pays
    for the groups it has, not for its bucket; the pad groups it still
    meets in that block add exact zeros.

    Layout note: every (…, G, P) intermediate keeps the big static group
    width P on the minor (128-lane) axis — gathering ``(G, P, r)`` with
    the rank (~10) minor pads each buffer ~12.8x to the vreg tile and
    measured 11x slower on v5e (30.9 vs 2.8 ms for the ML-1M user-side
    partials, round 3).  Hence the gather runs against the TRANSPOSED
    factor table and the batched matmul contracts the lane axis.

    Sides whose (r, G, P) intermediates exceed ``_GROUPED_BUDGET_ELEMS``
    are processed as a loop over group blocks that keeps every group's
    flat ((r+1)*(r+2),) moments (flat so they pad to lane tiles once,
    not per (r+1, r+2) matrix) and folds them by destination in ONE
    segment-sum at the end.

    Returns (a_part (n_dst, r, r), b (n_dst, r), n_reg (n_dst,)).
    """
    n_src, r = src_factors.shape
    G, P = src_g.shape
    gather = gather or resolve_gather_kernel(n_src, r, src_factors.dtype)
    table = None
    if gather.startswith("pallas"):
        from oap_mllib_tpu.ops.pallas import als_gather

        table = als_gather.pack_table(src_factors)

    def block_moments(src_b, conf_b, valid_b):
        return grouped_block_moments(
            src_b, conf_b, valid_b, src_factors, alpha, implicit, policy,
            gather, table,
        )

    blocks = _grouped_block_count(G, P, r)
    if blocks == 1:
        M = jax.ops.segment_sum(
            block_moments(src_g, conf_g, valid_g),
            group_dst, num_segments=n_dst, indices_are_sorted=True,
        )
        return M[:, :r, :r], M[:, :r, r], M[:, r, r + 1]

    gb = -(-G // blocks)
    pad = blocks * gb - G
    # dummy groups: valid=0 rows contribute exact zeros to dst n_dst-1
    src_p = jnp.pad(src_g, ((0, pad), (0, 0)))
    conf_p = jnp.pad(conf_g, ((0, pad), (0, 0)))
    valid_p = jnp.pad(valid_g, ((0, pad), (0, 0)))
    gd_p = jnp.pad(group_dst, (0, pad), constant_values=n_dst - 1)
    width = (r + 1) * (r + 2)

    # Every block's group moments are kept, and ONE segment-sum folds all
    # of them by destination at the end: XLA:TPU's scatter copies its
    # whole operand a call, so a (n_dst, width) carry scattered into once
    # a block cost 1.7 ms a block of 4096 groups at 625k destinations —
    # 0.45 s a half-update, thirty times the scatter itself (PERF.md
    # section 6, PR 38).  The sheet of all groups' moments is G x width
    # floats (lane-padded: 1 GB at the cell's 2^20 groups).
    def moments_of(blk):
        src_b, conf_b, valid_b = blk
        return block_moments(src_b, conf_b, valid_b).reshape(gb, width)

    blocked = (
        src_p.reshape(blocks, gb, P),
        conf_p.reshape(blocks, gb, P),
        valid_p.reshape(blocks, gb, P),
    )
    if live_groups is None:
        _, sheet = lax.scan(lambda c, blk: (c, moments_of(blk)), 0, blocked)
    else:
        sheet = lax.fori_loop(
            0, -(live_groups // -gb),
            lambda k, out: lax.dynamic_update_index_in_dim(
                out,
                moments_of(tuple(
                    lax.dynamic_index_in_dim(a, k, keepdims=False)
                    for a in blocked
                )),
                k, 0,
            ),
            jnp.zeros((blocks, gb, width), src_factors.dtype),
        )
    M_flat = jax.ops.segment_sum(
        sheet.reshape(blocks * gb, width), gd_p, num_segments=n_dst,
        indices_are_sorted=True,
    )
    M = M_flat.reshape(n_dst, r + 1, r + 2)
    return M[:, :r, :r], M[:, :r, r], M[:, r, r + 1]


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_users", "n_items", "max_iter", "implicit", "policy",
        "solve_kernel", "solve_geo", "gram_geo", "gather_kernel",
    ),
)
def _als_run_grouped_jit(
    u_src_g, u_conf_g, u_valid_g, u_group_dst,  # item ids grouped by user
    i_src_g, i_conf_g, i_valid_g, i_group_dst,  # user ids grouped by item
    x0: jax.Array,
    y0: jax.Array,
    n_users: int,
    n_items: int,
    max_iter: int,
    reg: float,
    alpha: float,
    implicit: bool,
    policy: str = "f32",
    solve_kernel: str = "xla",
    solve_geo=None,
    gram_geo=None,
    gather_kernel: str = "xla",
) -> Tuple[jax.Array, jax.Array]:
    r = x0.shape[1]
    eye = jnp.eye(r, dtype=x0.dtype)
    # read once a program, before the loop: where each side's bucket of
    # groups stops holding edges
    u_live, i_live = live_group_count(u_valid_g), live_group_count(i_valid_g)

    def half(src_g, conf_g, valid_g, group_dst, factors, n_dst, live):
        a, b, n_reg = normal_eq_partials_grouped(
            src_g, conf_g, valid_g, group_dst, factors, n_dst, alpha,
            implicit, policy, live, gather_kernel,
        )
        gram = (
            _factor_gram(factors, solve_kernel, gram_geo)
            if implicit else None
        )
        return regularized_solve(
            a, b, n_reg, reg, eye, gram, solve_kernel, solve_geo
        ).astype(factors.dtype)

    def body(carry, _):
        x, y = carry
        x = half(u_src_g, u_conf_g, u_valid_g, u_group_dst, y, n_users,
                 u_live)
        y = half(i_src_g, i_conf_g, i_valid_g, i_group_dst, x, n_items,
                 i_live)
        return (x, y), None

    (x, y), _ = lax.scan(body, (x0, y0), None, length=max_iter)
    return x, y


def live_group_count(valid_g: jax.Array) -> jax.Array:
    """Groups of a grouped side that hold an edge, read on the device: a
    group's slots fill from its first, and :func:`build_grouped_edges`
    makes no group without an edge, so the live groups are those whose
    first slot is valid — and they come first (pad groups close the
    bucket)."""
    return jnp.sum(valid_g[:, 0] > 0, dtype=jnp.int32)


def als_run_grouped(
    u_src_g, u_conf_g, u_valid_g, u_group_dst,
    i_src_g, i_conf_g, i_valid_g, i_group_dst,
    x0: jax.Array,
    y0: jax.Array,
    n_users: int,
    n_items: int,
    max_iter: int,
    reg: float,
    alpha: float,
    implicit: bool,
    timings=None,
    phase: str = "als_iterations",
    policy: str = "f32",
    solve_kernel: str = "",
    gather_kernel: str = "",
) -> Tuple[jax.Array, jax.Array]:
    """Full ALS loop on the grouped-edge layout (both feedback modes).

    Scatter-free partials + Cholesky solves, where the COO path pays a
    segment-sum scatter per half-iteration.  The launch registers with
    the program-cache registry (utils/progcache); ``timings`` receives
    the ``<phase>/compile`` / ``<phase>/execute`` wall split.  ``policy``
    is the compute-precision policy (utils/precision.py) for the moment
    matmuls — the Gram and every solve stay f32 under all policies.
    ``solve_kernel``: "" resolves Config.als_solve_kernel
    (:func:`resolve_solve_kernel`); ``gather_kernel``: "" resolves
    :func:`resolve_gather_kernel` once for both sides, on the larger
    table; explicit values are the test seams."""
    solve_kernel = solve_kernel or resolve_solve_kernel(
        x0.shape[1], x0.dtype
    )
    gather_kernel = gather_kernel or resolve_gather_kernel(
        max(n_users, n_items), x0.shape[1], x0.dtype
    )
    solve_geo, gram_geo = _tuned_geometry(
        x0.shape[1], solve_kernel, implicit
    )
    # reg/alpha are traced scalars, not statics — they do not key a new
    # program and so stay out of the cache key
    key = (
        progcache.backend_fingerprint(),
        progcache.array_key(u_src_g, i_src_g, x0, y0),
        n_users, n_items, max_iter, implicit, policy, solve_kernel,
        solve_geo, gram_geo, gather_kernel,
    )
    with progcache.launch("als.run_grouped", key, timings, phase):
        return _als_run_grouped_jit(
            u_src_g, u_conf_g, u_valid_g, u_group_dst,
            i_src_g, i_conf_g, i_valid_g, i_group_dst,
            x0, y0, n_users, n_items, max_iter, reg, alpha, implicit,
            policy, solve_kernel, solve_geo, gram_geo, gather_kernel,
        )


# ---------------------------------------------------------------------------
# Destination-blocked path: ranks whose sheet of group moments does not fit
# ---------------------------------------------------------------------------
# The grouped path keeps every group's (r+1)(r+2) moments and folds them by
# destination at the end, and solves the whole side at once: at rank 100 on
# the KDD-Cup'11 cell that sheet is 107 GB a side and the (n_dst, r, r)
# equations 25 GB.  This path walks a side's destinations ``D`` at a time
# (``membudget.plan_als`` sizes ``D`` from the memory the layouts leave).
# The groups are sorted by destination, so a block's groups are one run of
# the layout: its chunks of ``Gc`` groups read their source rows whole and
# lane-dense (ops/pallas/als_dst.lane_rows: at rank 100 a row pads 1.28x,
# where the grouped path's transposed gather exists because rank 10 pads
# 12.8x) — the moments kernel copies them from HBM into VMEM itself, the
# XLA twin gathers them —, their moments go straight into the block's
# per-destination tiles, and the block is solved and written before the
# next one starts.  A destination
# belongs to exactly one block; within a block its groups may span two
# chunks, and the second chunk adds to the tile the first left.

# bytes of source rows a chunk of groups names, all of them gathered on
# the XLA twin (the kernel holds a step's alone): sizes the chunk
DST_CHUNK_BYTES = 128 << 20
# the narrowest group the path takes: a group's rows are one tile's lanes,
# and a narrower group costs a whole tile all the same
DST_LEAST_GROUP = 128


def dst_chunk_groups(P: int, r: int) -> int:
    """Groups of a chunk: about ``DST_CHUNK_BYTES`` of source rows, in
    whole grid steps of the moments kernel."""
    from oap_mllib_tpu.ops.pallas import als_dst

    step = als_dst.STEP_GROUPS
    want = DST_CHUNK_BYTES // (P * als_dst.r_pad(r) * 4)
    return max(step, want // step * step)


def dst_blocks(n_dst: int, dst_block: int) -> Tuple[int, int]:
    """``(D, blocks)`` of one side: a block of at most ``dst_block``
    destinations, whole solve steps of them, as many as cover ``n_dst``."""
    from oap_mllib_tpu.ops.pallas.als_dst import SOLVE_LANES

    d = min(dst_block, -(-max(n_dst, 1) // SOLVE_LANES) * SOLVE_LANES)
    return d, -(-max(n_dst, 1) // d)


def dst_walked_chunks(group_dst, live: int, n_dst: int, dst_block: int,
                      chunk: int) -> int:
    """Chunks of ``min(chunk, G)`` groups one half-update's moments walk
    on the destination-blocked route, each block's groups in whole
    chunks; reckoned on the host from the side's built ``group_dst``
    ``(G,)`` and its ``live`` groups (:func:`dst_block_starts`, as the
    program reads them)."""
    import numpy as np

    D, blocks = dst_blocks(n_dst, dst_block)
    gc = min(chunk, group_dst.shape[0])
    bounds = np.arange(blocks + 1, dtype=np.int64) * D
    starts = np.minimum(np.searchsorted(group_dst, bounds, side="left"), live)
    return int(np.sum(-(-np.diff(starts) // gc)))


def resolve_dst_kernels(backend: str = "") -> Tuple[str, str]:
    """``(moments, solve)`` programs of the destination-blocked path:
    the Pallas kernels of ops/pallas/als_dst.py on a TPU, their XLA
    twins elsewhere (the CPU of tier-1); ``backend`` ("" =
    ``jax.default_backend()``) is the test seam."""
    if (backend or jax.default_backend()) == "tpu":
        return "pallas", "pallas"
    return "xla", "xla"


def dst_block_starts(group_dst: jax.Array, live, blocks: int,
                     D: int) -> jax.Array:
    """``(blocks + 1,)`` int32: the first group of each block of ``D``
    destinations, and behind the last the live groups' end (the pad
    groups of a bucket, which name the last destination, belong to no
    block)."""
    bounds = jnp.arange(blocks + 1, dtype=jnp.int32) * D
    starts = jnp.searchsorted(group_dst, bounds, side="left")
    return jnp.minimum(starts.astype(jnp.int32), live)


def dst_weights(conf: jax.Array, valid: jax.Array, alpha,
                implicit: bool) -> jax.Array:
    """``(Gc, 8, P)``: a chunk's :func:`slot_weights` as rows ``a_w``,
    ``b_w``, ``n_w`` of the moments kernel's weight tile."""
    w = jnp.stack(slot_weights(conf, valid, alpha, implicit), axis=1)
    return jnp.pad(w.astype(jnp.float32), ((0, 0), (0, 5), (0, 0)))


def dst_chunk_moments(rows, src, w, ldst, acc, r: int, mode: str,
                      kernel: str) -> jax.Array:
    """``acc`` with one chunk's tiles added: the ``als_dst_moments``
    kernel on "pallas" ("pallas_interpret": the same in interpret mode),
    which fetches the chunk's source rows from ``rows`` itself, or its
    XLA twin, the rows gathered whole, a batched product and a
    scatter-add."""
    from oap_mllib_tpu.ops.pallas import als_dst

    if kernel.startswith("pallas"):
        return als_dst.dst_moments(
            rows, src, w, ldst, acc, r, mode,
            interpret=kernel == "pallas_interpret",
        )
    tiles = jax.vmap(lambda y, wt: als_dst.tile_of(y, wt, r, mode))(rows[src], w)
    return acc.at[ldst].add(tiles, indices_are_sorted=True)


def dst_block_solve(acc, gram, reg, r: int, kernel: str) -> jax.Array:
    """``(D, r)`` factors of a block from its ``(D, R, R)`` tiles, the
    Gram (None: explicit feedback) and ``reg`` x each row's count: the
    ``als_block_cholesky`` kernel on "pallas" / "pallas_interpret", the
    library Cholesky of :func:`regularized_solve` on "xla"."""
    a, b, n = acc[:, :r, :r], acc[:, :r, r], acc[:, r, r + 1]
    eye = jnp.eye(r, dtype=jnp.float32)
    if not kernel.startswith("pallas"):
        return regularized_solve(a, b, n, reg, eye, gram)
    from oap_mllib_tpu.ops.pallas import als_dst

    a = a + reg * n[:, None, None] * eye[None]
    if gram is not None:
        a = a + gram[None]
    return als_dst.block_cholesky(
        jnp.transpose(a, (1, 2, 0)), b.T, n[None, :],
        interpret=kernel == "pallas_interpret",
    ).T


def dst_blocked_half(src_g, conf_g, valid_g, group_dst, starts, factors,
                     n_dst: int, reg, alpha, implicit: bool, *,
                     dst_block: int, chunk: int, mode: str = "highest",
                     moments: str = "xla", solve: str = "xla") -> jax.Array:
    """One half-update on the destination-blocked path: ``(n_dst, r)``
    factors from the other side's ``factors``, ``starts`` from
    :func:`dst_block_starts` at ``dst_block`` destinations a block."""
    from oap_mllib_tpu.ops.pallas import als_dst

    r = factors.shape[1]
    G = src_g.shape[0]
    R = als_dst.r_pad(r)
    D, blocks = dst_blocks(n_dst, dst_block)
    gc = min(chunk, G)
    rows = als_dst.lane_rows(factors)
    gram = _factor_gram(factors) if implicit else None

    def block(k, out):
        d0 = k * D
        lo, hi = starts[k], starts[k + 1]

        def add_chunk(c, acc):
            g0 = lo + c * gc
            s = jnp.minimum(g0, G - gc)  # the last chunk of the layout ends at G
            src, conf, valid = (
                lax.dynamic_slice_in_dim(a, s, gc) for a in (src_g, conf_g, valid_g)
            )
            gi = s + jnp.arange(gc, dtype=jnp.int32)
            live = ((gi >= g0) & (gi < hi)).astype(jnp.float32)
            # groups outside [g0, hi) add zeros: those before join the
            # chunk's first destination, those after the block's last
            first = group_dst[g0] - d0
            ldst = jnp.clip(
                lax.dynamic_slice_in_dim(group_dst, s, gc) - d0, first, D - 1
            )
            w = dst_weights(conf, valid * live[:, None], alpha, implicit)
            return dst_chunk_moments(rows, src, w, ldst, acc, r, mode, moments)

        acc = lax.fori_loop(
            0, (hi - lo + gc - 1) // gc, add_chunk,
            jnp.zeros((D, R, R), jnp.float32),
        )
        x = dst_block_solve(acc, gram, reg, r, solve)
        return lax.dynamic_update_slice_in_dim(out, x.astype(out.dtype), d0, 0)

    out = lax.fori_loop(
        0, blocks, block, jnp.zeros((blocks * D, r), factors.dtype)
    )
    return out[:n_dst]


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_users", "n_items", "max_iter", "implicit", "policy", "dst_block",
        "chunks", "moments", "solve",
    ),
)
def _als_run_dst_blocked_jit(
    u_src_g, u_conf_g, u_valid_g, u_group_dst,
    i_src_g, i_conf_g, i_valid_g, i_group_dst,
    x0, y0, n_users: int, n_items: int, max_iter: int, reg, alpha,
    implicit: bool, policy: str = "f32", dst_block: int = 16384,
    chunks: Tuple[int, int] = (2048, 2048), moments: str = "xla",
    solve: str = "xla",
):
    from oap_mllib_tpu.ops.pallas._tiers import check_mode

    mode = check_mode(policy)
    sides = []
    for (src_g, conf_g, valid_g, group_dst), n_dst, gc in zip(
        ((u_src_g, u_conf_g, u_valid_g, u_group_dst),
         (i_src_g, i_conf_g, i_valid_g, i_group_dst)),
        (n_users, n_items), chunks,
    ):
        D, blocks = dst_blocks(n_dst, dst_block)
        # read once a program: where each block's groups start
        starts = dst_block_starts(
            group_dst, live_group_count(valid_g), blocks, D
        )
        sides.append(functools.partial(
            dst_blocked_half, src_g, conf_g, valid_g, group_dst, starts,
            n_dst=n_dst, reg=reg, alpha=alpha, implicit=implicit,
            dst_block=dst_block, chunk=gc, mode=mode, moments=moments,
            solve=solve,
        ))

    def body(carry, _):
        x, y = carry
        x = sides[0](y)
        y = sides[1](x)
        return (x, y), None

    (x, y), _ = lax.scan(body, (x0, y0), None, length=max_iter)
    return x, y


def als_run_dst_blocked(
    u_src_g, u_conf_g, u_valid_g, u_group_dst,
    i_src_g, i_conf_g, i_valid_g, i_group_dst,
    x0: jax.Array, y0: jax.Array, n_users: int, n_items: int,
    max_iter: int, reg: float, alpha: float, implicit: bool, *,
    dst_block: int, timings=None, phase: str = "als_iterations",
    policy: str = "f32", moments: str = "", solve: str = "",
) -> Tuple[jax.Array, jax.Array]:
    """Full ALS loop on the destination-blocked path, over the same
    device layouts as :func:`als_run_grouped`; ``dst_block``
    destinations a block (``membudget.plan_als``).  ``moments`` /
    ``solve``: "" resolves :func:`resolve_dst_kernels`; explicit values
    are the test seams.  Registry-tracked like the grouped loop."""
    auto = resolve_dst_kernels()
    moments, solve = moments or auto[0], solve or auto[1]
    r = x0.shape[1]
    chunks = tuple(
        dst_chunk_groups(g.shape[1], r) for g in (u_src_g, i_src_g)
    )
    key = (
        progcache.backend_fingerprint(),
        progcache.array_key(u_src_g, i_src_g, x0, y0),
        n_users, n_items, max_iter, implicit, policy, dst_block, chunks,
        moments, solve,
    )
    with progcache.launch("als.run_dst_blocked", key, timings, phase):
        return _als_run_dst_blocked_jit(
            u_src_g, u_conf_g, u_valid_g, u_group_dst,
            i_src_g, i_conf_g, i_valid_g, i_group_dst,
            x0, y0, n_users, n_items, max_iter, reg, alpha, implicit,
            policy, dst_block, chunks, moments, solve,
        )


def _tuned_geometry(r: int, solve_kernel: str, implicit: bool):
    """Tuned ALS kernel geometry for the pallas consumers (ops/pallas/
    autotune): ``(solve_geo, gram_geo)`` as hashable static tuples —
    ``(batch, depth)`` and ``(tile_rows, depth)`` — or ``(None, None)``
    on the XLA route.  Resolved EAGERLY by the runner wrappers (never
    inside a traced body) so the cache/sweep machinery runs exactly once
    per program build."""
    if not solve_kernel.startswith("pallas"):
        return None, None
    from oap_mllib_tpu.ops.pallas import autotune

    g = autotune.resolve("als_solve", autotune.shape_bucket(r))
    solve_geo = (g["batch"], g["depth"])
    gram_geo = None
    if implicit:
        gg = autotune.resolve("als_gram", autotune.shape_bucket(r))
        gram_geo = (gg["tile_rows"], gg["depth"])
    return solve_geo, gram_geo


def _half_update(
    dst_idx: jax.Array,
    src_idx: jax.Array,
    conf: jax.Array,
    valid: jax.Array,
    src_factors: jax.Array,
    n_dst: int,
    reg: float,
    alpha: float,
    policy: str = "f32",
    solve_kernel: str = "xla",
    solve_geo=None,
    gram_geo=None,
) -> jax.Array:
    """Solve one side's factors given the other side's. Returns (n_dst, r)."""
    r = src_factors.shape[1]
    # (r, r) <- MXU, psum over mesh — stays full f32 under every policy
    # (the Gram conditions the solve; its cost is O(n*r^2), not the hot path)
    gram = _factor_gram(src_factors, solve_kernel, gram_geo)
    a_part, b, n_reg = normal_eq_partials(
        dst_idx, src_idx, conf, valid, src_factors, n_dst, alpha, True,
        policy,
    )
    eye = jnp.eye(r, dtype=src_factors.dtype)
    return regularized_solve(
        a_part, b, n_reg, reg, eye, gram, solve_kernel, solve_geo
    ).astype(src_factors.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_users", "n_items", "max_iter", "policy", "solve_kernel",
        "solve_geo", "gram_geo",
    ),
)
def _als_implicit_run_jit(
    u_idx: jax.Array,
    i_idx: jax.Array,
    conf: jax.Array,
    valid: jax.Array,
    x0: jax.Array,  # (n_users, r)
    y0: jax.Array,  # (n_items, r)
    n_users: int,
    n_items: int,
    max_iter: int,
    reg: float,
    alpha: float,
    policy: str = "f32",
    solve_kernel: str = "xla",
    solve_geo=None,
    gram_geo=None,
) -> Tuple[jax.Array, jax.Array]:

    def body(carry, _):
        x, y = carry
        x = _half_update(
            u_idx, i_idx, conf, valid, y, n_users, reg, alpha, policy,
            solve_kernel, solve_geo, gram_geo,
        )
        y = _half_update(
            i_idx, u_idx, conf, valid, x, n_items, reg, alpha, policy,
            solve_kernel, solve_geo, gram_geo,
        )
        return (x, y), None

    (x, y), _ = lax.scan(body, (x0, y0), None, length=max_iter)
    return x, y


def als_implicit_run(
    u_idx, i_idx, conf, valid, x0, y0,
    n_users: int, n_items: int, max_iter: int, reg: float, alpha: float,
    timings=None, phase: str = "als_iterations", policy: str = "f32",
    solve_kernel: str = "",
) -> Tuple[jax.Array, jax.Array]:
    """Full training loop: alternating user/item updates under lax.scan
    (the reference's trainModel loop, ALSDALImpl.cpp:318-438).
    Registry-tracked (utils/progcache), like :func:`als_run_grouped`."""
    solve_kernel = solve_kernel or resolve_solve_kernel(
        x0.shape[1], x0.dtype
    )
    solve_geo, gram_geo = _tuned_geometry(x0.shape[1], solve_kernel, True)
    key = (
        progcache.backend_fingerprint(),
        progcache.array_key(u_idx, x0, y0),
        n_users, n_items, max_iter, policy, solve_kernel, solve_geo,
        gram_geo,
    )
    with progcache.launch("als.implicit_coo", key, timings, phase):
        return _als_implicit_run_jit(
            u_idx, i_idx, conf, valid, x0, y0,
            n_users, n_items, max_iter, reg, alpha, policy, solve_kernel,
            solve_geo, gram_geo,
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_users", "n_items", "max_iter", "policy", "solve_kernel",
        "solve_geo",
    ),
)
def _als_explicit_run_jit(
    u_idx: jax.Array,
    i_idx: jax.Array,
    rating: jax.Array,
    valid: jax.Array,
    x0: jax.Array,
    y0: jax.Array,
    n_users: int,
    n_items: int,
    max_iter: int,
    reg: float,
    policy: str = "f32",
    solve_kernel: str = "xla",
    solve_geo=None,
) -> Tuple[jax.Array, jax.Array]:

    def half(dst_idx, src_idx, src_factors, n_dst):
        r = src_factors.shape[1]
        a_part, b, n_reg = normal_eq_partials(
            dst_idx, src_idx, rating, valid, src_factors, n_dst, 0.0,
            False, policy,
        )
        eye = jnp.eye(r, dtype=src_factors.dtype)
        return regularized_solve(
            a_part, b, n_reg, reg, eye, None, solve_kernel, solve_geo
        ).astype(src_factors.dtype)

    def body(carry, _):
        x, y = carry
        x = half(u_idx, i_idx, y, n_users)
        y = half(i_idx, u_idx, x, n_items)
        return (x, y), None

    (x, y), _ = lax.scan(body, (x0, y0), None, length=max_iter)
    return x, y


def als_explicit_run(
    u_idx, i_idx, rating, valid, x0, y0,
    n_users: int, n_items: int, max_iter: int, reg: float,
    timings=None, phase: str = "als_iterations", policy: str = "f32",
    solve_kernel: str = "",
) -> Tuple[jax.Array, jax.Array]:
    """Explicit-feedback ALS (beyond the reference's accelerated surface —
    it falls back to Spark for explicit; we accelerate both).
    Registry-tracked (utils/progcache), like :func:`als_run_grouped`."""
    solve_kernel = solve_kernel or resolve_solve_kernel(
        x0.shape[1], x0.dtype
    )
    solve_geo, _ = _tuned_geometry(x0.shape[1], solve_kernel, False)
    key = (
        progcache.backend_fingerprint(),
        progcache.array_key(u_idx, x0, y0),
        n_users, n_items, max_iter, policy, solve_kernel, solve_geo,
    )
    with progcache.launch("als.explicit_coo", key, timings, phase):
        return _als_explicit_run_jit(
            u_idx, i_idx, rating, valid, x0, y0,
            n_users, n_items, max_iter, reg, policy, solve_kernel,
            solve_geo,
        )


@jax.jit
def predict_pairs(x: jax.Array, y: jax.Array, users: jax.Array, items: jax.Array) -> jax.Array:
    return jnp.sum(x[users] * y[items], axis=1)
