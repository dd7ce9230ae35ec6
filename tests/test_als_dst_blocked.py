"""ALS's destination-blocked route (``als_ops.als_run_dst_blocked``), the
route a rank whose sheet of group moments does not fit takes: moments
accumulated a block of destinations at a time straight into each
destination's tile, and a batched Cholesky solve of the block.  Against
the package's NumPy ALS (``fallback/als_np.py``) and the grouped route, on
the CPU at small sizes; its two kernels (``ops/pallas/als_dst.py``) under
the interpreter.  What Mosaic makes of them is compiled in
``test_tpu_compile.py``."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oap_mllib_tpu import ALS
from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.fallback import als_np
from oap_mllib_tpu.ops import als_ops
from oap_mllib_tpu.ops.pallas import als_dst
from oap_mllib_tpu.utils import membudget

N_USERS, N_ITEMS, NNZ = 600, 800, 60000
UNRATED_ITEMS = (5, 611)  # nobody rated them
ZERO_ONLY_ITEMS = (7, 612)  # rated, every score 0
UNRATED_USERS = (597, 598, 599)
ZERO_ONLY_USER = 5  # rates, every score 0
PIN = "pin:" + membudget.ROUTE_DST_BLOCKED


def _table(seed=0):
    """A long-tailed 600 x 800 table of 60,000 ratings, scores 0 ... 40 in
    steps of 10 with repeated pairs, and the destinations the route must
    give zeros: items nobody rated, items and a user rated only 0."""
    rng = np.random.default_rng(seed)
    users = rng.integers(0, min(UNRATED_USERS), NNZ).astype(np.int32)
    items = np.minimum(
        (rng.pareto(1.0, NNZ) * 20).astype(np.int64), N_ITEMS - 1
    ).astype(np.int32)
    ratings = (rng.integers(0, 5, NNZ) * 10).astype(np.float32)
    users[50:100], items[50:100] = users[100:150], items[100:150]
    for i in UNRATED_ITEMS:
        items[items == i] = i + 1
    items[0:3], items[3:6] = ZERO_ONLY_ITEMS
    for i in ZERO_ONLY_ITEMS:
        ratings[items == i] = 0.0
    ratings[users == ZERO_ONLY_USER] = 0.0
    return users, items, ratings


def _fit(x, rank, max_iter=2, policy=PIN, seed=3, **cfg):
    set_config(scale_policy=policy, **cfg)
    users, items, ratings = x
    init = (als_np.init_factors(N_USERS, rank, seed),
            als_np.init_factors(N_ITEMS, rank, seed + 1))
    model = ALS(rank=rank, max_iter=max_iter, implicit_prefs=True, alpha=40.0,
                num_user_blocks=1).fit(users, items, ratings, n_users=N_USERS,
                                       n_items=N_ITEMS, init=init)
    return model, init


def _rel(got, want):
    return float(np.linalg.norm(np.asarray(got, np.float64) - want)
                 / np.linalg.norm(want))


@pytest.fixture(scope="module")
def table():
    return _table()


class TestAgainstTheReferences:
    @pytest.mark.parametrize("rank", [40, 100])
    def test_the_route_holds_numpy_als(self, table, rank):
        model, init = _fit(table, rank)
        assert model.summary["als_kernel"] == "dst_blocked"
        assert model.summary["route"]["route"] == membudget.ROUTE_DST_BLOCKED
        want = als_np.als_np(*table, N_USERS, N_ITEMS, rank, 2, 0.1, 40.0,
                             True, init=init)
        # float32 moments and solves against float64 NumPy over two
        # iterations at alpha 40: the grouped route reads 1e-4 ... 3e-4
        # here, the bound leaves three times that
        for got, w in zip((model.user_factors_, model.item_factors_), want):
            assert _rel(got, w) < 1e-3

    @pytest.mark.parametrize("rank", [10, 40])
    def test_the_route_gives_the_grouped_routes_factors(self, table, rank):
        blocked, _ = _fit(table, rank, max_iter=3)
        grouped, _ = _fit(table, rank, max_iter=3, policy="auto")
        assert grouped.summary["als_kernel"] == "grouped"
        # the same arithmetic summed in another order: float32 rounding,
        # carried over three iterations (4e-5 here), the bound the grouped
        # route's widths are held to against each other
        for got, want in ((blocked.user_factors_, grouped.user_factors_),
                          (blocked.item_factors_, grouped.item_factors_)):
            assert _rel(got, want) < 2e-4

    @pytest.mark.parametrize("side", ["user", "item"])
    def test_unrated_and_zero_only_destinations_get_zeros(self, table, side):
        model, _ = _fit(table, 40, max_iter=1)
        if side == "item":
            f = model.item_factors_
            for i in UNRATED_ITEMS + ZERO_ONLY_ITEMS:
                assert not f[i].any(), i
            assert np.isfinite(f).all()
        else:
            f = model.user_factors_
            assert not f[ZERO_ONLY_USER].any()
            for u in UNRATED_USERS:
                assert not f[u].any(), u
            assert np.isfinite(f).all()


class TestBlocksAndChunks:
    """A destination's groups may span chunks within its block, and a block
    boundary may fall anywhere among the destinations: the heaviest item
    is walked in many chunks, the sides in many blocks."""

    @pytest.mark.parametrize("dst_block, chunk_bytes", [
        (128, 8 * 64 * 128 * 4),  # chunks of 8 groups, blocks of 128
        (256, 16 * 64 * 128 * 4),
        (16384, 8 * 64 * 128 * 4),  # one block a side
    ])
    def test_any_blocking_gives_the_same_factors(
            self, table, monkeypatch, dst_block, chunk_bytes):
        users, items, ratings = table
        r = 12
        layouts = []
        for dst, src, n in ((users, items, N_USERS), (items, users, N_ITEMS)):
            g = als_ops.group_bucket(als_ops.grouped_padded_edges(dst, n, 64) // 64)
            layouts += [jnp.asarray(a) for a in als_ops.build_grouped_edges(
                dst, src, ratings, n, 64, groups=g)]
        x0 = jnp.asarray(als_np.init_factors(N_USERS, r, 1))
        y0 = jnp.asarray(als_np.init_factors(N_ITEMS, r, 2))
        heavy = np.bincount(items).max()
        monkeypatch.setattr(als_ops, "DST_CHUNK_BYTES", chunk_bytes)
        gc = als_ops.dst_chunk_groups(64, r)
        assert heavy > 2 * gc * 64  # the heaviest item spans chunks
        got = als_ops.als_run_dst_blocked(
            *layouts, x0, y0, N_USERS, N_ITEMS, 2, 0.1, 40.0, True,
            dst_block=dst_block)
        want = als_ops.als_run_grouped(
            *layouts, x0, y0, N_USERS, N_ITEMS, 2, 0.1, 40.0, True)
        for g_, w in zip(got, want):  # as in TestAgainstTheReferences
            assert _rel(g_, np.asarray(w, np.float64)) < 2e-4
        D, blocks = als_ops.dst_blocks(N_ITEMS, dst_block)
        assert blocks == -(-N_ITEMS // D) and D % 128 == 0


def _parents_form(rows, src, w, ldst, acc, r):
    """The moments as the route added them before the kernel fetched its
    own rows: every slot's row gathered whole (``rows[src]``), then each
    group's tile added to its destination's, in the groups' order."""
    tiles = jax.vmap(lambda y, wt: als_dst.tile_of(y, wt, r, "highest"))(
        rows[src], w)
    out = jnp.asarray(acc)
    for g, d in enumerate(np.asarray(ldst)):
        out = out.at[d].set(out[d] + tiles[g])
    return np.asarray(out)


def _chunk(rng, gc, p, n_src, live):
    """``(src, conf, valid)`` of ``gc`` groups whose slots fill from the
    first, ``live[g]`` of them; the pad slots name source 0, as the
    grouped build leaves them."""
    valid = (np.arange(p)[None, :] < np.asarray(live)[:, None]).astype(np.float32)
    src = np.where(valid > 0, rng.integers(0, n_src, (gc, p)), 0).astype(np.int32)
    conf = (rng.integers(0, 5, (gc, p)) * 10).astype(np.float32) * valid
    return src, conf, valid


class TestTheKernels:
    """``als_dst_moments`` and ``als_block_cholesky`` under the
    interpreter against ``jnp.einsum`` and ``numpy.linalg.cholesky``, and
    the moments kernel, which fetches its own source rows, against the
    gathered rows it used to be handed."""

    @pytest.mark.parametrize("r, p", [(10, 128), (40, 64), (100, 128)])
    def test_the_moments_kernel_is_the_einsum(self, r, p):
        rng = np.random.default_rng(r + p)
        gc, d, n_src = 24, 9, 50
        f = jnp.asarray(rng.standard_normal((n_src, r)).astype(np.float32))
        src = rng.integers(0, n_src, (gc, p)).astype(np.int32)
        conf = (rng.integers(0, 5, (gc, p)) * 10).astype(np.float32)
        valid = (rng.random((gc, p)) < 0.8).astype(np.float32)
        # sorted destinations, a run across the chunk's steps of 8,
        # destinations 0 and 8 none; and a partial tile already there
        ldst = np.sort(rng.integers(1, d - 1, gc)).astype(np.int32)
        ldst[4:13] = ldst[4]
        acc0 = np.zeros((d, als_dst.r_pad(r), als_dst.r_pad(r)), np.float32)
        acc0[ldst[0], :r, :r] = 1.5
        w = als_ops.dst_weights(jnp.asarray(conf), jnp.asarray(valid), 40.0, True)
        rows = als_dst.lane_rows(f)
        got = als_dst.dst_moments(rows, jnp.asarray(src), w, jnp.asarray(ldst),
                                  jnp.asarray(acc0), r, interpret=True)
        y = np.asarray(f)[src]
        a_w = 40.0 * np.abs(conf) * valid
        pos = (conf > 0) * valid
        want = acc0.copy()
        for g in range(gc):
            lhs = np.concatenate([y[g], np.ones((p, 1))], axis=1)
            rhs = np.concatenate([y[g] * a_w[g][:, None],
                                  ((1 + 40.0 * np.abs(conf[g])) * pos[g])[:, None],
                                  pos[g][:, None]], axis=1)
            want[ldst[g], :r + 1, :r + 2] += np.asarray(jnp.einsum(
                "pa,pb->ab", lhs, rhs, precision="highest"))
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=1e-2)
        xla = als_ops.dst_chunk_moments(rows, jnp.asarray(src), w,
                                        jnp.asarray(ldst), jnp.asarray(acc0),
                                        r, "highest", "xla")
        np.testing.assert_allclose(np.asarray(got), np.asarray(xla),
                                   rtol=1e-5, atol=1e-3)

    @pytest.mark.parametrize("case", [
        "pad_at_group_tails", "empty_groups_at_the_end", "ids_at_both_ends",
        "one_step",
    ])
    def test_the_fetch_gives_the_gathered_rows_tiles(self, case):
        """Bit for bit the parent's form: the rows the kernel copies are
        the rows ``rows[src]`` gathered, pad slots (source 0, weight 0)
        included."""
        r, p, d, n_src = 100, 128, 9, 50
        gc = 8 if case == "one_step" else 24
        rng = np.random.default_rng(len(case))
        live = rng.integers(1, p + 1, gc)
        if case == "pad_at_group_tails":
            live[::3] = p  # full groups beside ones that end early
            live[1], live[2] = 1, p - 1
        elif case == "empty_groups_at_the_end":
            # the groups past the chunk's owner: no rows, no weights
            live[-11:] = 0
        src, conf, valid = _chunk(rng, gc, p, n_src, live)
        if case == "ids_at_both_ends":
            src[0, :live[0]] = 0
            src[5, :live[5]] = n_src - 1
            src[7, 0], src[7, live[7] - 1] = n_src - 1, 0
        f = jnp.asarray(rng.standard_normal((n_src, r)).astype(np.float32))
        rows = als_dst.lane_rows(f)
        ldst = np.sort(rng.integers(0, d, gc)).astype(np.int32)
        acc0 = np.zeros((d, 128, 128), np.float32)
        acc0[ldst[0], :r, :r] = 1.5
        w = als_ops.dst_weights(jnp.asarray(conf), jnp.asarray(valid), 40.0, True)
        got = als_dst.dst_moments(rows, jnp.asarray(src), w, jnp.asarray(ldst),
                                  jnp.asarray(acc0), r, interpret=True)
        want = _parents_form(rows, jnp.asarray(src), w, ldst, acc0, r)
        assert np.array_equal(np.asarray(got), want)

    def test_a_destination_across_steps_and_chunks(self):
        """One destination's groups run over two grid steps of the first
        chunk and on into the second: its tile is carried across both."""
        r, p, gc, d, n_src = 100, 128, 16, 5, 70
        rng = np.random.default_rng(46)
        f = jnp.asarray(rng.standard_normal((n_src, r)).astype(np.float32))
        rows = als_dst.lane_rows(f)
        # destination 2 holds groups 5 ... 20 of 32: steps 0 and 1 of the
        # first chunk, step 0 of the second
        ldst = np.array([0] * 3 + [1] * 2 + [2] * 16 + [3] * 6 + [4] * 5,
                        np.int32)
        live = rng.integers(1, p + 1, 2 * gc)
        src, conf, valid = _chunk(rng, 2 * gc, p, n_src, live)
        w = als_ops.dst_weights(jnp.asarray(conf), jnp.asarray(valid), 40.0, True)
        acc = want = np.zeros((d, 128, 128), np.float32)
        for c in range(2):
            part = slice(c * gc, (c + 1) * gc)
            acc = als_dst.dst_moments(
                rows, jnp.asarray(src[part]), w[part], jnp.asarray(ldst[part]),
                jnp.asarray(acc), r, interpret=True)
            want = _parents_form(rows, jnp.asarray(src[part]), w[part],
                                 ldst[part], want, r)
        assert np.array_equal(np.asarray(acc), want)
        # its count: the preferred ratings of its sixteen groups
        assert np.asarray(acc)[2, r, r + 1] == ((conf > 0) * valid)[5:21].sum()

    @pytest.mark.parametrize("r, b", [(10, 128), (40, 200), (100, 130)])
    def test_the_block_cholesky_solves_what_numpy_factors(self, r, b):
        rng = np.random.default_rng(r * b)
        m = rng.standard_normal((b, r, 2 * r))
        a = (m @ m.transpose(0, 2, 1) + 0.5 * np.eye(r)).astype(np.float32)
        rhs = rng.standard_normal((b, r)).astype(np.float32)
        n = np.ones(b, np.float32)
        n[[3, b - 1]] = 0.0  # no preferred rating: zeros
        got = np.asarray(als_dst.block_cholesky(
            jnp.asarray(a.transpose(1, 2, 0)), jnp.asarray(rhs.T),
            jnp.asarray(n[None, :]), interpret=True)).T
        low = np.linalg.cholesky(a.astype(np.float64))
        z = np.linalg.solve(low, rhs[:, :, None].astype(np.float64))
        want = np.linalg.solve(low.transpose(0, 2, 1), z)[:, :, 0]
        want[n == 0] = 0.0
        assert not got[[3, b - 1]].any()
        # float32 elimination against float64; these systems' condition
        # numbers run to about 1e4 at rank 100
        assert _rel(got, want) < 1e-4 * r


class TestThePlan:
    """``membudget.plan_als`` on the KDD-Cup'11 cell's shape under a v5e's
    memory: the destination-blocked route at rank 100, the grouped one at
    rank 10."""

    USERS, ITEMS, RATINGS = 500495, 624961, 126_400_138
    HBM = 15.75 * 2**30
    # the cell's grouped layouts at P = 128: the bucket of each side
    LAYOUTS = [(2097152, 128), (2097152, 128)]

    @pytest.mark.parametrize("rank, route", [
        (10, membudget.ROUTE_IN_MEMORY),
        (100, membudget.ROUTE_DST_BLOCKED),
    ])
    def test_the_route_by_rank(self, rank, route):
        set_config(memory_budget_hbm=str(int(self.HBM)))
        plan = membudget.plan_als(self.RATINGS, self.USERS, self.ITEMS, rank,
                                  grouped=self.LAYOUTS)
        assert plan.route == route
        if route == membudget.ROUTE_DST_BLOCKED:
            # the grouped route's sheet alone is more than the chip
            assert membudget.als_sheet_bytes(2097152, rank) > self.HBM
            assert plan.estimates[0].reject and plan.dst_block == 16384
            est = plan.estimate_for(route)
            assert not est.reject and est.hbm_bytes <= self.HBM
            assert plan.as_dict()["dst_block"] == 16384

    @pytest.mark.parametrize("hbm_gb, block", [(15.75, 16384), (12.0, 8192),
                                               (11.0, 4096)])
    def test_the_block_shrinks_with_the_memory(self, hbm_gb, block):
        set_config(memory_budget_hbm=str(int(hbm_gb * 2**30)))
        plan = membudget.plan_als(self.RATINGS, self.USERS, self.ITEMS, 100,
                                  grouped=self.LAYOUTS)
        assert plan.route == membudget.ROUTE_DST_BLOCKED
        assert plan.dst_block == block

    def test_the_cpu_runs_the_xla_twins(self):
        assert als_ops.resolve_dst_kernels() == ("xla", "xla")
        assert als_ops.resolve_dst_kernels("tpu") == ("pallas", "pallas")


@pytest.mark.parametrize("moments", ["xla", "pallas_interpret"])
def test_the_fit_books_the_route_on_its_span(table, monkeypatch, moments):
    if moments != "xla":
        monkeypatch.setattr(als_ops, "resolve_dst_kernels",
                            lambda backend="": (moments, "xla"))
    model, _ = _fit(table, 40, max_iter=1)
    attrs = model.summary["timings"].root.node("als_iterations").attrs
    D, blocks_u = als_ops.dst_blocks(N_USERS, 16384)
    build = model.summary["timings"].root.node("table_convert/group_edges").attrs
    assert build["group_size"] == [128, 128]
    # one chunk a side: every bucket holds fewer groups than a chunk
    walked = (build["groups_user"] + build["groups_item"]) * 128
    assert attrs == {
        "iterations": 1, "solve_kernel": "xla", "rank": 40, "implicit": True,
        "route": membudget.ROUTE_DST_BLOCKED, "dst_block": 16384,
        "blocks_user": blocks_u, "blocks_item": 1, "r_pad": 128,
        # the XLA twin gathers every slot of its chunks; the kernel copies
        # them too, and its last grid step's rows once more a chunk
        "gather": "rows" if moments == "xla" else "dma",
        "rows_fetched": walked if moments == "xla"
        else walked + 2 * als_dst.STEP_GROUPS * 128,
        "slots_walked": walked, "moments": moments, "solve": "xla",
    }
    assert build["sheet_bytes"] == 0
    assert model.summary["timings"].root.attrs["kernel"] == "dst_blocked"
    if moments != "xla":
        twin, _ = _fit(table, 40, max_iter=1)
        for got, want in ((model.user_factors_, twin.user_factors_),
                          (model.item_factors_, twin.item_factors_)):
            assert _rel(got, np.asarray(want, np.float64)) < 1e-6


def test_the_walk_is_reckoned_in_whole_chunks_a_block():
    """``dst_walked_chunks``: 300 destinations in blocks of 128, eleven
    live groups of a 16-group bucket."""
    group_dst = np.array([0, 0, 5, 127, 128, 130, 200, 255, 256, 299, 299]
                         + [299] * 5, np.int32)
    # blocks hold groups [0, 4), [4, 8), [8, 11): chunks of 4 take one
    # each, chunks of 3 two, two and one
    assert als_ops.dst_walked_chunks(group_dst, 11, 300, 128, 4) == 3
    assert als_ops.dst_walked_chunks(group_dst, 11, 300, 128, 3) == 5
    # one block of 384 destinations, its 11 groups in one chunk of the
    # bucket's 16 (a chunk is never longer than the layout)
    assert als_ops.dst_walked_chunks(group_dst, 11, 300, 16384, 2048) == 1
