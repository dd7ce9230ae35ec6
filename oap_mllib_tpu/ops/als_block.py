"""Block-parallel implicit ALS: the distributed 2-D layout under shard_map.

This is the scalable counterpart of ops/als_ops.py (which jits one global
program and lets GSPMD place the segment-sums).  Here the distribution is
explicit, mirroring — and simplifying — the reference's 4-step oneDAL
scheme (native/ALSDALImpl.cpp):

- Edges (ratings) are sharded by USER BLOCK over the ``data`` mesh axis —
  the layout produced by the ratings shuffle (parallel/shuffle.py, the
  cShuffleData analog).  User ids are LOCAL to the block; item ids global.
- User factors X are sharded by the same blocks: the user update is fully
  local — each rank solves only its users (reference step3/step4Local,
  ALSDALImpl.cpp:283-316), zero communication.
Two item-factor layouts (config ``als_item_layout``):

- **replicated** (small n_items): Y lives on every device.  The item
  update computes per-rank partial normal equations (A_i, b_i) for ALL
  items from local edges, then one ``psum`` over the mesh — collapsing
  the reference's gather -> step2Master -> broadcast -> all2all chain
  (ALSDALImpl.cpp:336-431, 4 collective rounds per half-iteration) into a
  single ICI allreduce.  Cost per iteration: psum traffic
  ~2 * n_items * r * (r + 1) floats (allreduce = reduce-scatter +
  all-gather), transient per-device partials O(n_items * r^2).
- **sharded** (the full 2-D user x item grid, the reference's per-rank
  transposed item blocks — ALSDALImpl.cpp:192-214 builds an item-major
  CSR per rank, computeStep4Local:301-316 solves only that rank's item
  partition): edges are shuffled a SECOND time by item block, Y is
  block-sharded like X, and each half-iteration all_gathers the other
  side's factors instead of psumming full item partials.  Cost per
  iteration: all_gather traffic ~(n_users + n_items) * r floats —
  ~(r + 1)x less than replicated — and both the per-rank item partials
  and resident Y shrink world-fold.  Prep pays a second shuffle +
  grouped build.

- The Gram matrices (r x r) cost one psum each in the sharded layout
  (both sides block-sharded); replicated needs it only for X^T X.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from oap_mllib_tpu.config import get_config
# shared normal-equation math — the block path only inserts psums between
# partials and solve, so the two paths cannot diverge in the weighting
from oap_mllib_tpu.ops.als_ops import (
    GROUPED_MAX_BLOWUP,
    _factor_gram,
    normal_eq_partials,
    normal_eq_partials_grouped,
    regularized_solve,
    resolve_solve_kernel,
)
from oap_mllib_tpu.parallel import collective
from oap_mllib_tpu.utils import progcache
from oap_mllib_tpu.utils.jax_compat import shard_map


# Auto-crossover for als_item_layout="auto": the replicated layout
# allreduces ~2 * n_items * r * (r+1) * 4 bytes per iteration AND holds a
# transient (n_items, r, r) partial per device; the sharded layout
# replaces both with two factor all_gathers at the price of a second
# shuffle + grouped build at fit time.  Shard once the per-iteration
# replicated psum payload (n_items * r * (r+1) * 4 bytes) crosses this
# bound — below it the psum is cheap and the replicated path's simpler
# prep wins (ML-25M at r=10 is ~26 MB/iter: replicated).
ITEM_SHARD_AUTO_BYTES = 1 << 27  # 128 MB


def als_item_layout_cfg() -> str:
    """Validated Config.als_item_layout.  Called on EVERY accelerated
    dispatch — single-device included, where the knob has no layout
    effect — so a typo raises everywhere, matching the als_kernel
    contract (it must not surface only once deployed to a mesh)."""
    layout = get_config().als_item_layout
    if layout not in ("auto", "replicated", "sharded"):
        raise ValueError(
            f"als_item_layout must be auto|replicated|sharded, got {layout!r}"
        )
    return layout


def item_layout_sharded(
    n_items: int, r: int, world: int, n_users: int = 0
) -> bool:
    """Resolve config.als_item_layout to a concrete layout decision.

    "auto" shards when BOTH hold: the replicated psum payload
    (n_items·r·(r+1)·4 bytes/iter) crosses ITEM_SHARD_AUTO_BYTES, AND
    the sharded layout's traffic is actually lower — its per-iteration
    all_gathers move ~(n_users+n_items)·r vs the psum's
    ~2·n_items·r·(r+1), so a USER-dominated workload
    (n_users > n_items·(2r+1)) would trade a big psum for a bigger X
    all_gather and stays replicated."""
    layout = als_item_layout_cfg()
    if layout != "auto":
        return layout == "sharded"
    return (
        world > 1
        and n_items * r * (r + 1) * 4 > ITEM_SHARD_AUTO_BYTES
        and n_users <= n_items * (2 * r + 1)
    )


def _block_body(user_partials, item_partials, reg, implicit, axis, eye,
                solve_kernel="xla"):
    """One alternating iteration of the block layout, shared by the COO and
    grouped-edge programs: user update fully local, item update partials +
    ONE psum (replacing the reference's gather/step2Master/bcast/all2all
    chain, ALSDALImpl.cpp:336-431).  ``user_partials(y)`` /
    ``item_partials(x_blk)`` return (A, b, n_reg) from whichever edge
    layout the caller closed over.  ``solve_kernel`` picks the
    regularized_solve consumer (als_ops.resolve_solve_kernel)."""

    def body(carry, _):
        x_blk, y = carry
        a_u, b_u, n_u = user_partials(y)
        gram_y = (
            _factor_gram(y, solve_kernel)
            if implicit else None
        )
        x_blk = regularized_solve(
            a_u, b_u, n_u, reg, eye, gram_y, solve_kernel
        ).astype(y.dtype)
        a_i, b_i, n_i = item_partials(x_blk)
        a_i = collective.psum(a_i, axis)
        b_i = collective.psum(b_i, axis)
        n_i = collective.psum(n_i, axis)
        gram_x = (
            collective.psum(
                _factor_gram(x_blk, solve_kernel),
                axis,
            )
            if implicit else None
        )
        y = regularized_solve(
            a_i, b_i, n_i, reg, eye, gram_x, solve_kernel
        ).astype(x_blk.dtype)
        return (x_blk, y), None

    return body


def _block_body_2d(user_partials, item_partials, reg, implicit, axis, eye,
                   solve_kernel="xla"):
    """One alternating iteration of the fully-sharded 2-D layout: BOTH
    factor matrices block-sharded.  Each half-iteration all_gathers the
    other side's factors (tiled, so the gathered array IS the padded
    global layout — see the prepare_* identity-mapping note), builds
    partials only for this rank's destinations, and solves locally — the
    reference's computeStep4Local (ALSDALImpl.cpp:301-316) with the
    4-collective exchange chain replaced by one all_gather.  The implicit
    Gram needs a psum on both sides now (each side holds only its block;
    padded rows are zero so the psum of block Grams is the exact Gram).

    ``user_partials(y_full)`` -> (A, b, n) for this rank's upb users;
    ``item_partials(x_full)`` -> (A, b, n) for this rank's ipb items."""

    def body(carry, _):
        x_blk, y_blk = carry
        y_full = collective.all_gather(y_blk, axis, tiled=True)
        a_u, b_u, n_u = user_partials(y_full)
        gram_y = (
            collective.psum(
                _factor_gram(y_blk, solve_kernel),
                axis,
            )
            if implicit else None
        )
        x_blk = regularized_solve(
            a_u, b_u, n_u, reg, eye, gram_y, solve_kernel
        ).astype(y_blk.dtype)
        x_full = collective.all_gather(x_blk, axis, tiled=True)
        a_i, b_i, n_i = item_partials(x_full)
        gram_x = (
            collective.psum(
                _factor_gram(x_blk, solve_kernel),
                axis,
            )
            if implicit else None
        )
        y_blk = regularized_solve(
            a_i, b_i, n_i, reg, eye, gram_x, solve_kernel
        ).astype(y_blk.dtype)
        return (x_blk, y_blk), None

    return body


def als_block_run(
    u_local: jax.Array,  # (world * epr,) int32, LOCAL user ids, block-sharded
    i_global: jax.Array,  # (world * epr,) int32 global item ids
    conf: jax.Array,
    valid: jax.Array,
    x0: jax.Array,  # (world * upb, r) user factors, block-sharded rows
    y0: jax.Array,  # (n_items, r) item factors, replicated
    max_iter: int,
    reg: float,
    alpha: float,
    mesh: Mesh,
    *,
    implicit: bool,
    policy: str = "f32",
) -> Tuple[jax.Array, jax.Array]:
    """Run block-parallel ALS (implicit or explicit) over the mesh.

    Returns (X, Y).  Shapes: every rank holds ``epr`` edges and ``upb``
    user rows (padded — the shuffle guarantees equal shapes; invalid edges
    carry valid=0).  The explicit mode drops the Gram term and uses rating
    b-weights; both modes apply ALS-WR lambda scaling (Spark parity,
    reference ALS.scala:1794-1795) via the shared normal_eq_partials.
    ``policy`` is the compute-precision policy (utils/precision.py) for
    the per-edge factor matmuls; Grams and solves stay f32.
    """
    cfg = get_config()
    axis = cfg.data_axis
    world = mesh.shape[axis]
    upb = x0.shape[0] // world  # users per block (padded)
    n_items, r = y0.shape
    solve_kernel = resolve_solve_kernel(r, y0.dtype, cfg)

    # the jitted shard_map program is registry-cached (utils/progcache):
    # rebuilding the closure per fit — the pattern every runner in this
    # module had — re-jitted and recompiled on each call even for
    # identical layouts.  reg/alpha ARE key components here (unlike the
    # single-device entries' traced scalars): they bake into the traced
    # program as closure constants.
    def build():
        eye = jnp.eye(r, dtype=y0.dtype)

        def rank_program(u_loc, i_glob, cf, vl, x_blk, y):
            # x_blk: (upb, r) this rank's users; y: (n_items, r) replicated
            body = _block_body(
                lambda y_: normal_eq_partials(
                    u_loc, i_glob, cf, vl, y_, upb, alpha, implicit,
                    policy,
                ),
                lambda x_: normal_eq_partials(
                    i_glob, u_loc, cf, vl, x_, n_items, alpha, implicit,
                    policy,
                ),
                reg, implicit, axis, eye, solve_kernel,
            )
            (x_blk, y), _ = lax.scan(body, (x_blk, y), None, length=max_iter)
            return x_blk, y

        shard = P(axis)
        rep = P()
        return jax.jit(
            shard_map(
                rank_program,
                mesh=mesh,
                in_specs=(shard, shard, shard, shard, P(axis, None), rep),
                out_specs=(P(axis, None), rep),
                check_vma=False,
            )
        )

    key = (
        progcache.mesh_fingerprint(mesh), axis, upb, n_items, r,
        max_iter, reg, alpha, implicit, str(y0.dtype), policy,
        solve_kernel,
    )
    fn = progcache.get_or_build("als_block.coo", key, build)
    launch_key = key + (progcache.array_key(u_local, x0),)
    with progcache.launch("als_block.coo.run", launch_key):
        return fn(u_local, i_global, conf, valid, x0, y0)


# ---------------------------------------------------------------------------
# Grouped-edge block path: the scatter-free layout (als_ops grouped-path
# notes) applied per rank.  Each rank's local edges are sorted/padded by
# destination ONCE on the host — by local user for the user update, by
# global item for the item update (the reference's per-rank CSR + transposed
# CSR pair, ALSDALImpl.cpp:192-214, as two grouped layouts) — then every
# iteration's normal-equation build is batched MXU matmuls with zero
# scatters.  Ranks pad their group counts to the global maxima so the
# shard_map program keeps equal shapes.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GroupedBlocks:
    """Device-resident grouped-edge layouts, block-sharded over the mesh."""

    u_src: jax.Array  # (world * Gu, Pu) item ids grouped by local user
    u_conf: jax.Array
    u_valid: jax.Array
    u_dst: jax.Array  # (world * Gu,) local user id per group (sorted/rank)
    i_src: jax.Array  # (world * Hi, Pi) user ids grouped by global item
    i_conf: jax.Array
    i_valid: jax.Array
    i_dst: jax.Array  # (world * Hi,) global item id per group (sorted/rank)


def _global_sum(arr) -> np.ndarray:
    """Elementwise int64 sum of a host array across processes (identity in
    single-process worlds) — the one definition every cross-process
    reduction in this module goes through."""
    arr = np.asarray(arr, np.int64)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        g = np.asarray(multihost_utils.process_allgather(arr))
        return g.reshape((-1,) + arr.shape).sum(axis=0)
    return arr


def _global_max(arr) -> np.ndarray:
    """Elementwise int64 max across processes (identity single-process)."""
    arr = np.asarray(arr, np.int64)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        g = np.asarray(multihost_utils.process_allgather(arr))
        return g.reshape((-1,) + arr.shape).max(axis=0)
    return arr


def _group_sizes(nnz_global: int, world: int, users_per_block: int,
                 n_items: int):
    """(p_u, p_i) — ONE derivation shared by the pre-shuffle guard and the
    layout build, so they can never size different layouts."""
    from oap_mllib_tpu.ops.als_ops import auto_group_size

    p_u = auto_group_size(max(1, nnz_global), world * users_per_block)
    p_i = auto_group_size(max(1, nnz_global // world), n_items)
    return p_u, p_i


def block_grouped_guard(
    users: np.ndarray,
    items: np.ndarray,
    n_users: int,
    n_items: int,
    world: int,
    max_blowup: float = GROUPED_MAX_BLOWUP,
):
    """Grouped-vs-COO decision for the block path, BEFORE the shuffle and
    from host degree counts alone — a COO decision must pay neither the
    grouped build nor the device->host pull of the shuffled blocks.

    Returns ``(use_grouped, (p_u, p_i, nnz_global))``; the sizes tuple is
    threaded into :func:`prepare_grouped_inputs` so the build uses exactly
    the layout the guard priced.

    Accounting matches what the build REALIZES: every rank is padded to
    the global max group counts, so the estimate is ``world * (max_b
    padded_u_b + max_b padded_i_b)`` over per-block padded totals — a
    sum over blocks would undercount skewed splits by up to ``world``x.
    The per-block totals are computable pre-shuffle because the shuffle
    routes every edge to block ``min(u // kpb, world - 1)``.
    Multi-process worlds sum per-block totals across processes (degrees
    split across processes pad per process — an overestimate, so
    borderline datasets conservatively take COO).
    """
    nnz_global = int(_global_sum([len(users)])[0])
    kpb = max(1, -(-n_users // world))
    p_u, p_i = _group_sizes(nnz_global, world, kpb, n_items)
    u = np.asarray(users, np.int64)
    it = np.asarray(items, np.int64)
    # user side: a user's edges land in ONE block — shared ceil-padding
    # accounting with the 2-D guard (one formula, both guards)
    pu_b = _side_padded_per_block(u, kpb, world, p_u)
    # item side (replicated layout): each item's edges SPLIT across user
    # blocks, so the per-(block, item) pair counts pad independently
    pi_b = np.zeros((world,), np.int64)
    block = np.minimum(u // kpb, world - 1)
    ki, ci = np.unique(block * n_items + it, return_counts=True)
    np.add.at(pi_b, ki // n_items, (-(ci // -p_i)) * p_i)
    pu_b = _global_sum(pu_b)
    pi_b = _global_sum(pi_b)
    total = world * (int(pu_b.max()) + int(pi_b.max()))
    return total <= max_blowup * max(nnz_global, 1), (p_u, p_i, nnz_global)


def _host_blocks(arr: jax.Array, world: int) -> dict:
    """Per-rank host views of a block-sharded device array ({rank: rows}).
    Multi-process worlds see only their addressable blocks."""
    per = arr.shape[0] // world
    if arr.is_fully_addressable:
        h = np.asarray(arr)
        return {b: h[b * per : (b + 1) * per] for b in range(world)}
    out = {}
    for sh in arr.addressable_shards:
        start = sh.index[0].start or 0
        out[start // per] = np.asarray(sh.data)  # model-axis dupes collapse
    return out


def _pad_groups(grouped, g_max: int, n_dst: int):
    """Pad a rank's grouped arrays to ``g_max`` groups.  Padding groups
    carry valid=0 and dst = n_dst - 1 (keeps group_dst sorted, so the
    segment-sum's indices_are_sorted contract holds)."""
    src_g, conf_g, valid_g, gdst = grouped
    pad = g_max - src_g.shape[0]
    if pad > 0:
        p = src_g.shape[1]
        src_g = np.concatenate([src_g, np.zeros((pad, p), np.int32)])
        conf_g = np.concatenate([conf_g, np.zeros((pad, p), np.float32)])
        valid_g = np.concatenate([valid_g, np.zeros((pad, p), np.float32)])
        gdst = np.concatenate(
            [gdst, np.full((pad,), n_dst - 1, np.int32)]
        )
    return src_g, conf_g, valid_g, gdst


def _build_grouped_side(dst_b, src_b, conf_b, valid_b, n_dst: int, p: int):
    """Per-rank grouped layouts for ONE side: {block: grouped tuple}.
    Shared by the 1-D and 2-D preps so the build semantics cannot
    diverge between the replicated and sharded item layouts."""
    from oap_mllib_tpu.ops.als_ops import build_grouped_edges

    out = {}
    for b in dst_b:
        sel = valid_b[b] > 0
        out[b] = build_grouped_edges(
            dst_b[b][sel].astype(np.int64),
            src_b[b][sel].astype(np.int64),
            conf_b[b][sel].astype(np.float32),
            n_dst, p,
        )
    return out


def _pad_stack_place(by_user, by_item, u_ndst: int, i_ndst: int, mesh: Mesh):
    """Shared tail of both grouped preps: pad every rank to the GLOBAL
    max group counts (one allgather covers both sides), stack rank-major,
    and place block-sharded on the mesh."""
    cfg = get_config()
    axis = cfg.data_axis
    gu_local = max(g[0].shape[0] for g in by_user.values())
    hi_local = max(g[0].shape[0] for g in by_item.values())
    gu, hi = (int(v) for v in _global_max([gu_local, hi_local]))

    blocks = sorted(by_user)
    u_pad = {b: _pad_groups(by_user[b], gu, u_ndst) for b in blocks}
    i_pad = {b: _pad_groups(by_item[b], hi, i_ndst) for b in blocks}
    u_stack = [
        np.concatenate([u_pad[b][j] for b in blocks]) for j in range(4)
    ]
    i_stack = [
        np.concatenate([i_pad[b][j] for b in blocks]) for j in range(4)
    ]

    def place(local):
        sharding = NamedSharding(mesh, P(axis, *([None] * (local.ndim - 1))))
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(sharding, local)
        return jax.device_put(local, sharding)

    u_dev = [place(m) for m in u_stack]
    i_dev = [place(m) for m in i_stack]
    return GroupedBlocks(
        u_src=u_dev[0], u_conf=u_dev[1], u_valid=u_dev[2], u_dst=u_dev[3],
        i_src=i_dev[0], i_conf=i_dev[1], i_valid=i_dev[2], i_dst=i_dev[3],
    )


def prepare_grouped_inputs(
    u_local: jax.Array,
    i_global: jax.Array,
    conf: jax.Array,
    valid: jax.Array,
    mesh: Mesh,
    upb: int,
    n_items: int,
    *,
    sizes=None,
):
    """Build per-rank grouped-edge layouts from the shuffled block arrays.

    Returns a :class:`GroupedBlocks`.  The grouped-vs-COO decision is NOT
    made here — :func:`block_grouped_guard` is the single decision point
    (it runs pre-shuffle so a COO decision pays nothing); ``sizes`` is its
    ``(p_u, p_i, nnz_global)`` tuple, threaded through so the build uses
    exactly the layout the guard priced (and skips a redundant allgather
    round).  Host cost is one sort of each rank's local edges — indices
    are static across iterations, so this runs once per fit (same
    contract as the single-device grouped prep).
    """
    cfg = get_config()
    world = mesh.shape[cfg.data_axis]
    ub = _host_blocks(u_local, world)
    ib = _host_blocks(i_global, world)
    cb = _host_blocks(conf, world)
    vb = _host_blocks(valid, world)

    if sizes is not None:
        p_u, p_i, _ = sizes
    else:
        nnz_local = sum(int((vb[b] > 0).sum()) for b in vb)
        nnz_global = int(_global_sum([nnz_local])[0])
        # group sizes from GLOBAL stats so every process compiles
        # identical static shapes
        p_u, p_i = _group_sizes(nnz_global, world, upb, n_items)

    by_user = _build_grouped_side(ub, ib, cb, vb, upb, p_u)
    by_item = _build_grouped_side(ib, ub, cb, vb, n_items, p_i)
    return _pad_stack_place(by_user, by_item, upb, n_items, mesh)


def als_block_run_grouped(
    gb: GroupedBlocks,
    x0: jax.Array,  # (world * upb, r) block-sharded user factors
    y0: jax.Array,  # (n_items, r) replicated item factors
    max_iter: int,
    reg: float,
    alpha: float,
    mesh: Mesh,
    *,
    implicit: bool,
    policy: str = "f32",
) -> Tuple[jax.Array, jax.Array]:
    """Block-parallel ALS on the grouped-edge layout (both feedback modes).

    Identical math and collective structure to :func:`als_block_run` (one
    psum per item update) with the scatter-free partials — the multi-device
    form of the single-device grouped layout."""
    cfg = get_config()
    axis = cfg.data_axis
    world = mesh.shape[axis]
    upb = x0.shape[0] // world
    n_items, r = y0.shape
    solve_kernel = resolve_solve_kernel(r, y0.dtype, cfg)

    def build():
        eye = jnp.eye(r, dtype=y0.dtype)

        def rank_program(su, cu, vu, gu, si, ci, vi, gi, x_blk, y):
            body = _block_body(
                lambda y_: normal_eq_partials_grouped(
                    su, cu, vu, gu, y_, upb, alpha, implicit, policy
                ),
                lambda x_: normal_eq_partials_grouped(
                    si, ci, vi, gi, x_, n_items, alpha, implicit, policy
                ),
                reg, implicit, axis, eye, solve_kernel,
            )
            (x_blk, y), _ = lax.scan(body, (x_blk, y), None, length=max_iter)
            return x_blk, y

        sh2 = P(axis, None)
        sh1 = P(axis)
        rep = P()
        return jax.jit(
            shard_map(
                rank_program,
                mesh=mesh,
                in_specs=(sh2, sh2, sh2, sh1, sh2, sh2, sh2, sh1, sh2, rep),
                out_specs=(sh2, rep),
                check_vma=False,
            )
        )

    key = (
        progcache.mesh_fingerprint(mesh), axis, upb, n_items, r,
        max_iter, reg, alpha, implicit, str(y0.dtype), policy,
        solve_kernel,
    )
    fn = progcache.get_or_build("als_block.grouped", key, build)
    launch_key = key + (progcache.array_key(gb.u_src, gb.i_src, x0),)
    with progcache.launch("als_block.grouped.run", launch_key):
        return fn(
            gb.u_src, gb.u_conf, gb.u_valid, gb.u_dst,
            gb.i_src, gb.i_conf, gb.i_valid, gb.i_dst,
            x0, y0,
        )


def als_block_run_2d(
    u_local: jax.Array,  # user-sharded copy: (world * epr,) LOCAL user ids
    i_row: jax.Array,  # global item ids == padded-Y rows (identity mapping)
    conf_u: jax.Array,
    valid_u: jax.Array,
    i_local: jax.Array,  # item-sharded copy: (world * epr2,) LOCAL item ids
    u_row: jax.Array,  # global user ids == padded-X rows
    conf_i: jax.Array,
    valid_i: jax.Array,
    x0: jax.Array,  # (world * upb, r) block-sharded user factors
    y0: jax.Array,  # (world * ipb, r) block-sharded item factors
    max_iter: int,
    reg: float,
    alpha: float,
    mesh: Mesh,
    *,
    implicit: bool,
    policy: str = "f32",
) -> Tuple[jax.Array, jax.Array]:
    """COO 2-D ALS: both factor sides block-sharded (see _block_body_2d).

    Takes TWO shuffled edge copies — by user block (u_local local,
    i_row global) and by item block (i_local local, u_row global); the
    global ids index the all_gathered padded factor layouts directly
    (prepare_block_inputs identity-mapping note)."""
    cfg = get_config()
    axis = cfg.data_axis
    world = mesh.shape[axis]
    upb = x0.shape[0] // world
    ipb = y0.shape[0] // world
    r = y0.shape[1]
    solve_kernel = resolve_solve_kernel(r, y0.dtype, cfg)

    def build():
        eye = jnp.eye(r, dtype=y0.dtype)

        def rank_program(ul, ir, cu, vu, il, ur, ci, vi, x_blk, y_blk):
            body = _block_body_2d(
                lambda y_full: normal_eq_partials(
                    ul, ir, cu, vu, y_full, upb, alpha, implicit, policy
                ),
                lambda x_full: normal_eq_partials(
                    il, ur, ci, vi, x_full, ipb, alpha, implicit, policy
                ),
                reg, implicit, axis, eye, solve_kernel,
            )
            (x_blk, y_blk), _ = lax.scan(
                body, (x_blk, y_blk), None, length=max_iter
            )
            return x_blk, y_blk

        sh1 = P(axis)
        sh2 = P(axis, None)
        return jax.jit(
            shard_map(
                rank_program,
                mesh=mesh,
                in_specs=(sh1,) * 8 + (sh2, sh2),
                out_specs=(sh2, sh2),
                check_vma=False,
            )
        )

    key = (
        progcache.mesh_fingerprint(mesh), axis, upb, ipb, r,
        max_iter, reg, alpha, implicit, str(y0.dtype), policy,
        solve_kernel,
    )
    fn = progcache.get_or_build("als_block.coo_2d", key, build)
    launch_key = key + (progcache.array_key(u_local, i_local, x0),)
    with progcache.launch("als_block.coo_2d.run", launch_key):
        return fn(
            u_local, i_row, conf_u, valid_u, i_local, u_row, conf_i,
            valid_i, x0, y0,
        )


def als_block_run_grouped_2d(
    gb: GroupedBlocks,
    x0: jax.Array,  # (world * upb, r) block-sharded user factors
    y0: jax.Array,  # (world * ipb, r) block-sharded item factors
    max_iter: int,
    reg: float,
    alpha: float,
    mesh: Mesh,
    *,
    implicit: bool,
    policy: str = "f32",
) -> Tuple[jax.Array, jax.Array]:
    """Grouped-edge 2-D ALS: scatter-free partials on both block-sharded
    sides.  ``gb`` comes from :func:`prepare_grouped_inputs_2d` — its
    u_* arrays group the user-sharded edge copy by LOCAL user (src =
    padded-Y rows) and its i_* arrays group the item-sharded copy by
    LOCAL item (src = padded-X rows)."""
    cfg = get_config()
    axis = cfg.data_axis
    world = mesh.shape[axis]
    upb = x0.shape[0] // world
    ipb = y0.shape[0] // world
    r = y0.shape[1]
    solve_kernel = resolve_solve_kernel(r, y0.dtype, cfg)

    def build():
        eye = jnp.eye(r, dtype=y0.dtype)

        def rank_program(su, cu, vu, gu, si, ci, vi, gi, x_blk, y_blk):
            body = _block_body_2d(
                lambda y_full: normal_eq_partials_grouped(
                    su, cu, vu, gu, y_full, upb, alpha, implicit, policy
                ),
                lambda x_full: normal_eq_partials_grouped(
                    si, ci, vi, gi, x_full, ipb, alpha, implicit, policy
                ),
                reg, implicit, axis, eye, solve_kernel,
            )
            (x_blk, y_blk), _ = lax.scan(
                body, (x_blk, y_blk), None, length=max_iter
            )
            return x_blk, y_blk

        sh2 = P(axis, None)
        sh1 = P(axis)
        return jax.jit(
            shard_map(
                rank_program,
                mesh=mesh,
                in_specs=(sh2, sh2, sh2, sh1, sh2, sh2, sh2, sh1, sh2, sh2),
                out_specs=(sh2, sh2),
                check_vma=False,
            )
        )

    key = (
        progcache.mesh_fingerprint(mesh), axis, upb, ipb, r,
        max_iter, reg, alpha, implicit, str(y0.dtype), policy,
        solve_kernel,
    )
    fn = progcache.get_or_build("als_block.grouped_2d", key, build)
    launch_key = key + (progcache.array_key(gb.u_src, gb.i_src, x0),)
    with progcache.launch("als_block.grouped_2d.run", launch_key):
        return fn(
            gb.u_src, gb.u_conf, gb.u_valid, gb.u_dst,
            gb.i_src, gb.i_conf, gb.i_valid, gb.i_dst,
            x0, y0,
        )


def _side_padded_per_block(ids: np.ndarray, kpb: int, world: int, p: int):
    """(world,) padded edge totals one grouped side would realize, from
    host degree counts alone — every id's edges land in ONE block (ids
    are partitioned contiguously by ``kpb``), so the block's total is the
    sum of per-id ceil-paddings."""
    k, c = np.unique(np.asarray(ids, np.int64), return_counts=True)
    out = np.zeros((world,), np.int64)
    np.add.at(out, np.minimum(k // kpb, world - 1), (-(c // -p)) * p)
    return out


def block_grouped_guard_2d(
    users: np.ndarray,
    items: np.ndarray,
    n_users: int,
    n_items: int,
    world: int,
    max_blowup: float = GROUPED_MAX_BLOWUP,
):
    """Grouped-vs-COO decision for the 2-D sharded-item path.

    Symmetric pricing: both sides are block-partitioned by id, so each
    side's realized total is ``world * max_b (per-block padded sum)``
    (rank group counts pad to the global max, exactly like the user side
    of :func:`block_grouped_guard`).  Returns
    ``(use_grouped, (p_u, p_i, nnz_global))`` for
    :func:`prepare_grouped_inputs_2d`."""
    nnz_global = int(_global_sum([len(users)])[0])
    kpb_u = max(1, -(-n_users // world))
    kpb_i = max(1, -(-n_items // world))
    p_u, p_i = _group_sizes_2d(nnz_global, world, kpb_u, kpb_i)
    pu_b = _global_sum(_side_padded_per_block(users, kpb_u, world, p_u))
    pi_b = _global_sum(_side_padded_per_block(items, kpb_i, world, p_i))
    total = world * (int(pu_b.max()) + int(pi_b.max()))
    return total <= max_blowup * max(nnz_global, 1), (p_u, p_i, nnz_global)


def _group_sizes_2d(nnz_global: int, world: int, upb: int, ipb: int):
    """Group sizes for the 2-D layout.  Unlike the replicated layout
    (whose item side spreads each item's edges over all ranks), both
    sides here keep every destination's edges on one rank, so both size
    from the GLOBAL mean degree."""
    from oap_mllib_tpu.ops.als_ops import auto_group_size

    p_u = auto_group_size(max(1, nnz_global), world * upb)
    p_i = auto_group_size(max(1, nnz_global), world * ipb)
    return p_u, p_i


def prepare_grouped_inputs_2d(
    u_local: jax.Array,
    i_row: jax.Array,
    conf_u: jax.Array,
    valid_u: jax.Array,
    i_local: jax.Array,
    u_row: jax.Array,
    conf_i: jax.Array,
    valid_i: jax.Array,
    mesh: Mesh,
    upb: int,
    ipb: int,
    *,
    sizes=None,
):
    """Grouped-edge layouts for the 2-D path, one per shuffled copy:
    by-LOCAL-user from the user-sharded copy (src = padded-Y rows) and
    by-LOCAL-item from the item-sharded copy (src = padded-X rows) — the
    reference's per-rank CSR + transposed-CSR pair (ALSDALImpl.cpp
    :192-214) where, unlike :func:`prepare_grouped_inputs`, the item side
    also covers only this rank's item partition.  Returns a
    :class:`GroupedBlocks` for :func:`als_block_run_grouped_2d`."""
    cfg = get_config()
    world = mesh.shape[cfg.data_axis]
    ub = _host_blocks(u_local, world)
    irb = _host_blocks(i_row, world)
    cub = _host_blocks(conf_u, world)
    vub = _host_blocks(valid_u, world)
    ib = _host_blocks(i_local, world)
    urb = _host_blocks(u_row, world)
    cib = _host_blocks(conf_i, world)
    vib = _host_blocks(valid_i, world)

    if sizes is not None:
        p_u, p_i, _ = sizes
    else:
        nnz_local = sum(int((vub[b] > 0).sum()) for b in vub)
        nnz_global = int(_global_sum([nnz_local])[0])
        p_u, p_i = _group_sizes_2d(nnz_global, world, upb, ipb)

    by_user = _build_grouped_side(ub, irb, cub, vub, upb, p_u)
    by_item = _build_grouped_side(ib, urb, cib, vib, ipb, p_i)
    return _pad_stack_place(by_user, by_item, upb, ipb, mesh)


def prepare_block_inputs(
    users: np.ndarray,
    items: np.ndarray,
    ratings: np.ndarray,
    mesh: Mesh,
    n_users: int,
    offsets: "np.ndarray | None" = None,
):
    """Shuffle ratings into the block layout and build device inputs.

    Returns (u_local, i_global, conf, valid, offsets, upb) where the edge
    arrays are block-sharded over the mesh and user ids are local to each
    rank's block (padded user rows run to ``upb`` per rank).

    Identity-mapping note (load-bearing for the 2-D layout): with the
    default uniform layout, blocks are contiguous id ranges of width
    kpb = ceil(n/world) and ``upb == kpb`` whenever world > 1, so a
    GLOBAL id g living in block b sits at padded row
    ``b * upb + (g - b * kpb) == g`` of the block-stacked factor array.
    The 2-D runners exploit this: the OTHER side's global ids in each
    edge copy index the all_gathered padded factors directly, no remap
    tensor needed.  ``offsets`` (the capability-weighted uneven layout,
    parallel/balance.plan_block_offsets) BREAKS that identity, so the
    caller must only pass it on the replicated-item layout — the
    models/als dispatch enforces this; the rebasing and every consumer
    of (offsets, upb) here is boundary-generic.
    """
    from oap_mllib_tpu.parallel.shuffle import exchange_ratings

    cfg = get_config()
    axis = cfg.data_axis
    world = mesh.shape[axis]
    u, i, r, valid, offsets = exchange_ratings(
        users, items, ratings, mesh, n_users, offsets=offsets
    )
    upb = int(np.max(np.diff(offsets))) if world > 1 else n_users
    upb = max(upb, 1)
    # rebase global user ids to block-local ids on device: id - offset[rank]
    per_rank = u.shape[0] // world
    rank_of_row = jnp.repeat(jnp.arange(world, dtype=jnp.int32), per_rank)
    off = jnp.asarray(offsets[:-1], jnp.int32)[rank_of_row]
    u_local = jnp.where(valid > 0, u - off, upb - 1).astype(jnp.int32)
    # clamp invalid edges to a real row; valid=0 zeroes their contribution
    u_local = jnp.clip(u_local, 0, upb - 1)
    return u_local, i, r, valid, offsets, upb
