"""The Lloyd walk ends at the last tile that holds a row.

``kmeans_kernel.live_tiles`` reads the walk's bound from the padded weight
column — one past the last tile with a non-zero weight — and the DMA
kernel (``_dbuf.tile_walk(..., live=)``) and its XLA twin both stop there.
A tile past the bound holds weight 0 in every row, so it would add exact
zeros: what is held here is that the bounded walk returns the BITS of a
walk over every tile, wherever the weights end; that it never reads past
the bound; that a fit reports the device's own bound; that every shard of
a mesh takes its own; that a second size of a bucket compiles nothing;
and that the walks which pass no bound (PCA, ALS) trace to the program
they traced to before.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import oap_mllib_tpu.models.kmeans as kmeans_mod
from oap_mllib_tpu.config import get_config
from oap_mllib_tpu.models.kmeans import KMeans
from oap_mllib_tpu.ops import kmeans_ops
from oap_mllib_tpu.ops.pallas import _dbuf, als_kernel, autotune, pca_kernel
from oap_mllib_tpu.ops.pallas import kmeans_kernel as kk
from oap_mllib_tpu.parallel.mesh import data_sharding, get_mesh
from oap_mllib_tpu.utils import progcache

TILE = 128
TILES = 8
N, D, K = TILE * TILES, 16, 5
DEFAULT_TILE = autotune.DEFAULTS["kmeans"]["tile_rows"]


def _weights(rng, case):
    """(weight column of N rows, the bound it should read)."""
    w = (rng.random(N) + 0.5).astype(np.float32)
    if case == "ends_on_a_tile":
        w[3 * TILE:] = 0.0
        return w, 3
    if case == "one_row_past_a_tile":
        w[3 * TILE + 1:] = 0.0
        return w, 4
    if case == "first_tile_only":
        w[10:] = 0.0
        return w, 1
    if case == "nowhere":
        return np.zeros_like(w), 0
    if case == "last_tile":
        return w, TILES
    if case == "zeros_in_the_middle_and_the_tail":
        w[2 * TILE: 3 * TILE] = 0.0  # a whole tile of zero sample weights
        w[rng.choice(2 * TILE, 50, replace=False)] = 0.0  # and single rows
        w[5 * TILE + 7:] = 0.0
        return w, 6
    raise AssertionError(case)


CASES = [
    "ends_on_a_tile", "one_row_past_a_tile", "first_tile_only", "nowhere",
    "last_tile", "zeros_in_the_middle_and_the_tail",
]


def _walk(route, need_cost):
    """``f(x_p, w_p, c_p, live)`` of one route, jitted: the DMA kernel
    under the interpreter at rotation depth 2 or 3 (one or two warm-up
    starts to guard), or the XLA twin."""
    if route == "twin":
        return jax.jit(lambda x, w, c, live: kk._xla_walk(
            x, w, c, "highest", need_cost, TILE, live))
    depth = {"dma_depth2": 2, "dma_depth3": 3}[route]
    return jax.jit(lambda x, w, c, live: kk._pallas_accumulate_dbuf(
        x, w, c, "highest", True, need_cost, TILE, depth, live))


@pytest.mark.parametrize("need_cost", [True, False], ids=["cost", "loop"])
@pytest.mark.parametrize("route", ["dma_depth2", "dma_depth3", "twin"])
@pytest.mark.parametrize("case", CASES)
def test_bounded_walk_is_the_whole_walk_bit_for_bit(rng, case, route,
                                                    need_cost):
    w, expected = _weights(rng, case)
    x = rng.normal(size=(N, D)).astype(np.float32)
    c = rng.normal(size=(K, D)).astype(np.float32)
    x_p, w_p, c_p = jax.jit(
        functools.partial(kk._pad_operands_traced, block_rows=TILE)
    )(x, w, c)
    live = kk.live_tiles(w_p, TILE)
    assert live.dtype == jnp.int32 and int(live) == expected
    walk = _walk(route, need_cost)
    whole = walk(x_p, w_p, c_p, jnp.int32(TILES))
    # rows past the bound are never read: poison them
    poisoned = x_p.at[expected * TILE:].set(jnp.nan)
    bounded = walk(poisoned, w_p, c_p, live)
    for a, b in zip(bounded, whole):
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(a).all()
        assert a.tobytes() == b.tobytes()
    if expected == 0:
        assert not any(np.asarray(a).any() for a in bounded)


@pytest.mark.parametrize("case", CASES)
def test_single_shot_walk_derives_the_bound_from_the_weights(rng, case):
    """``lloyd_accumulate_walk`` (autotuner, gates): interpreter and twin
    agree bit for bit, and with the XLA accumulate to rounding, wherever
    the weights end."""
    w, _ = _weights(rng, case)
    x = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(K, D)).astype(np.float32))
    w = jnp.asarray(w)
    twin = kk.lloyd_accumulate_walk(x, w, c, tile_rows=TILE)
    dma = kk.lloyd_accumulate_walk(x, w, c, tile_rows=TILE, interpret=True)
    ref = kmeans_ops._accumulate(x, w, c)
    for a, b, r in zip(dma, twin, ref):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(r), rtol=1e-5, atol=1e-4
        )


# -- through the public entry ------------------------------------------------


def _blobs(rng, n, k=K, d=D, spread=0.3):
    """(float32 blobs far apart against float32's step, their labels)."""
    proto = (rng.standard_normal((k, d)) * 4.0).astype(np.float32)
    labels = rng.integers(k, size=n)
    x = proto[labels] + spread * rng.standard_normal((n, d))
    return x.astype(np.float32), labels


def _separating_seed(labels, k=K):
    """A seed whose ``init_random`` draws one row of every blob: no row
    then sits near a tie, and every route assigns every row alike."""
    for seed in range(1000):
        idx = np.random.default_rng(seed).choice(len(labels), k, replace=False)
        if len(set(labels[idx])) == k:
            return seed
    raise AssertionError("no separating seed")


def _route_as_on_the_tpu(monkeypatch, n_devices):
    """``KMeans.fit`` on the first ``n_devices`` virtual devices with the
    route the chip would take: the walk (run here by its XLA twin)."""
    mesh = get_mesh(n_devices=n_devices)
    monkeypatch.setattr(kmeans_mod, "get_mesh", lambda: mesh)
    route = kmeans_ops.lloyd_route
    monkeypatch.setattr(
        kmeans_ops, "lloyd_route",
        lambda *a, **kw: route(*a, **{**kw, "backend": "tpu"}),
    )
    return mesh


@pytest.mark.parametrize("n_valid,bucket", [
    (3000, 4096), (2048, 2048), (2049, 4096), (100, 256),
])
def test_fit_reports_the_tiles_the_device_walked(rng, monkeypatch, n_valid,
                                                 bucket):
    """``lloyd_loop.attrs``: ``walk_tiles`` the padded table holds,
    ``walk_tiles_live`` = ceil(n_valid / tile_rows), the device's count."""
    _route_as_on_the_tpu(monkeypatch, 1)
    x, _ = _blobs(rng, n_valid)
    model = KMeans(k=K, max_iter=3, tol=0.0, seed=5).fit(x)
    assert model.summary.kernel == "pallas"
    loop = model.summary.timings.root.node("lloyd_loop")
    padded = int(model.summary.timings.root.node(
        "table_convert/upload").attrs["padded_rows"])
    assert padded == bucket
    assert loop.attrs["walk_tiles"] == max(padded // DEFAULT_TILE, 1)
    assert loop.attrs["walk_tiles_live"] == -(-n_valid // DEFAULT_TILE)
    assert float(np.sum(model.summary.cluster_sizes)) == n_valid


def test_zero_sample_weights_in_the_tail_shorten_the_walk(rng, monkeypatch):
    """The bound follows the WEIGHTS, not the table's row count."""
    _route_as_on_the_tpu(monkeypatch, 1)
    x, _ = _blobs(rng, 4096)
    sw = np.ones((4096,), np.float32)
    sw[1500:] = 0.0
    model = KMeans(k=K, max_iter=3, tol=0.0, seed=5).fit(x, sample_weight=sw)
    loop = model.summary.timings.root.node("lloyd_loop")
    assert loop.attrs["walk_tiles"] == 4096 // DEFAULT_TILE
    assert loop.attrs["walk_tiles_live"] == -(-1500 // DEFAULT_TILE)
    assert float(np.sum(model.summary.cluster_sizes)) == 1500


def test_the_xla_route_reports_no_walk(rng, monkeypatch):
    mesh = get_mesh(n_devices=1)
    monkeypatch.setattr(kmeans_mod, "get_mesh", lambda: mesh)
    x, _ = _blobs(rng, 1000)
    model = KMeans(k=K, max_iter=2, seed=5).fit(x)
    assert model.summary.kernel == "xla"
    loop = model.summary.timings.root.node("lloyd_loop")
    assert "walk_tiles" not in loop.attrs
    assert "walk_tiles_live" not in loop.attrs


class TestOnTheMesh:
    """Single-process tables keep their pad at the END of the global row
    order: the last shards hold it, and each shard walks to its own
    bound — a shard of pad walks no tile."""

    ROWS, VALID, SHARDS = 4096, 2300, 4  # shards of 1024 rows = 4 tiles of 256

    def _operands(self, rng):
        x, labels = _blobs(rng, self.ROWS)
        w = np.zeros((self.ROWS,), np.float32)
        w[:self.VALID] = 1.0
        c0 = x[[int(np.flatnonzero(labels[:self.VALID] == j)[0])
                for j in range(K)]]
        return x, w, c0

    def test_each_shard_takes_its_own_bound(self, rng):
        x, w, c0 = self._operands(rng)
        mesh = get_mesh(n_devices=self.SHARDS)
        dax = get_config().data_axis
        fn = kmeans_ops._build_lloyd(
            mesh, dax, self.SHARDS, 4, "highest", "f32", True, 256, 2,
            False, 1,
        )
        out = fn(
            jax.device_put(x, data_sharding(mesh, 2)),
            jax.device_put(w, data_sharding(mesh, 1)),
            jnp.asarray(c0), jnp.asarray(0.0, jnp.float32),
        )
        # rows 0..2299 valid: 4, 4, ceil(252 / 256) = 1 and 0 tiles
        assert out[4].dtype == jnp.int32
        assert np.asarray(out[4]).tolist() == [4, 4, 1, 0]
        assert float(np.sum(np.asarray(out[3]))) == self.VALID

    @pytest.mark.parametrize("interpret", [False, True],
                             ids=["twin", "dma_interpreted"])
    def test_fit_equals_the_one_device_fit(self, rng, interpret):
        from oap_mllib_tpu.telemetry import spans

        x, w, c0 = self._operands(rng)
        tol = jnp.asarray(0.0, jnp.float32)
        kw = dict(accumulate="pallas", tile_rows=256, interpret=interpret)
        one = [np.asarray(o) for o in kmeans_ops.lloyd_run(
            jnp.asarray(x), jnp.asarray(w), jnp.asarray(c0), 6, tol, **kw
        )]
        mesh = get_mesh(n_devices=self.SHARDS)
        loop = spans.Span("lloyd_loop")
        with spans.enter(loop, annotate=False):
            c, it, cost, counts = (np.asarray(o) for o in kmeans_ops.lloyd_run(
                jax.device_put(x, data_sharding(mesh, 2)),
                jax.device_put(w, data_sharding(mesh, 1)),
                jnp.asarray(c0), 6, tol, mesh=mesh,
                data_axis=get_config().data_axis, **kw,
            ))
        # as tests/test_kmeans_data_sharded.py holds device counts: the
        # shards' float32 sums are added in another order
        np.testing.assert_allclose(c, one[0], atol=1e-5)
        np.testing.assert_allclose(cost, one[2], rtol=1e-5)
        np.testing.assert_array_equal(counts, one[3])
        assert int(it) == int(one[1])
        assert loop.attrs["walk_tiles"] == 4
        assert loop.attrs["walk_tiles_live"] == 4  # the fullest shard's
        assert loop.attrs["shards"] == self.SHARDS

    def test_public_fit_on_the_mesh(self, rng, monkeypatch):
        x, labels = _blobs(rng, self.VALID)
        seed = _separating_seed(labels)
        _route_as_on_the_tpu(monkeypatch, 1)
        one = KMeans(k=K, max_iter=6, tol=0.0, seed=seed,
                     init_mode="random").fit(x)
        _route_as_on_the_tpu(monkeypatch, self.SHARDS)
        model = KMeans(k=K, max_iter=6, tol=0.0, seed=seed,
                       init_mode="random").fit(x)
        assert model.summary.kernel == one.summary.kernel == "pallas"
        np.testing.assert_allclose(
            model.cluster_centers_, one.cluster_centers_, atol=1e-5
        )
        np.testing.assert_array_equal(
            model.summary.cluster_sizes, one.summary.cluster_sizes
        )
        loop = model.summary.timings.root.node("lloyd_loop")
        # 4096 padded rows on four shards of 1024 = 2 default tiles each
        assert loop.attrs["walk_tiles"] == 1024 // DEFAULT_TILE
        assert loop.attrs["walk_tiles_live"] == 1024 // DEFAULT_TILE
        assert one.summary.timings.root.node(
            "lloyd_loop").attrs["walk_tiles_live"] == -(-self.VALID // DEFAULT_TILE)


# -- one program a bucket ----------------------------------------------------


def test_two_sizes_of_a_bucket_share_every_program(rng, monkeypatch):
    """The bound is data, not shape: a second size of one x2 bucket
    compiles nothing and builds nothing, on the walk's route too."""
    _route_as_on_the_tpu(monkeypatch, 1)
    x, _ = _blobs(rng, 3900)
    first = KMeans(k=K, max_iter=4, tol=0.0, seed=2).fit(x[:2500])
    compiles = progcache.xla_compile_count()
    built = dict(progcache.stats()["by_algo"]["kmeans.lloyd"])
    second = KMeans(k=K, max_iter=4, tol=0.0, seed=2).fit(x)
    assert progcache.xla_compile_count() == compiles
    assert second.summary.progcache["misses"] == 0
    after = progcache.stats()["by_algo"]["kmeans.lloyd"]
    assert after["misses"] == built["misses"]
    assert after["hits"] == built["hits"] + 1
    lives = [
        m.summary.timings.root.node("lloyd_loop").attrs["walk_tiles_live"]
        for m in (first, second)
    ]
    assert lives == [-(-2500 // DEFAULT_TILE), -(-3900 // DEFAULT_TILE)]


def test_the_lloyd_programs_registry_key_is_as_it_was(rng, monkeypatch):
    """``("kmeans.lloyd", ...)``: world, shards, iterations, tier and the
    route's statics — nothing of the bound, nothing of the row count."""
    seen = []
    build = progcache.get_or_build

    def spy(algo, key, make):
        seen.append((algo, key))
        return build(algo, key, make)

    monkeypatch.setattr(progcache, "get_or_build", spy)
    x = jnp.asarray(rng.normal(size=(600, D)).astype(np.float32))
    kmeans_ops.lloyd_run(
        x, jnp.ones((600,), jnp.float32), x[:K], 3,
        jnp.asarray(0.0, jnp.float32), accumulate="pallas", tile_rows=256,
    )
    assert seen == [(
        "kmeans.lloyd",
        (progcache.backend_fingerprint(), 1, 3, "highest", "f32", True,
         256, 2, False, 1),
    )]


# -- the walks that pass no bound --------------------------------------------


def _tile_walk_before(inputs, bufs, sems, tile, num_tiles, depth, body,
                      axes=None):
    """``_dbuf.tile_walk`` as it stood before it took a bound (static
    trip count, unguarded warm-up), kept here as the oracle of the
    programs PCA and ALS must still trace to."""
    if axes is None:
        axes = (0,) * len(inputs)

    def _dma(ref, buf, sem, ax, slot, t):
        if ax is None:
            src = ref.at[t]
        elif ax == 0:
            src = ref.at[pl.ds(t * tile, tile)]
        else:
            src = ref.at[:, pl.ds(t * tile, tile)]
        return pltpu.make_async_copy(src, buf.at[slot], sem.at[slot])

    def _start(t):
        slot = lax.rem(t, depth)
        for ref, buf, sem, ax in zip(inputs, bufs, sems, axes):
            _dma(ref, buf, sem, ax, slot, t).start()

    def _wait(t):
        slot = lax.rem(t, depth)
        for ref, buf, sem, ax in zip(inputs, bufs, sems, axes):
            _dma(ref, buf, sem, ax, slot, t).wait()

    for t in range(min(depth - 1, num_tiles)):
        _start(jnp.int32(t))

    def _step(t, carry):
        nxt = t + depth - 1

        @pl.when(nxt < num_tiles)
        def _prefetch():
            _start(nxt)

        _wait(t)
        slot = lax.rem(t, depth)
        body(t, [buf[slot] for buf in bufs])
        return carry

    lax.fori_loop(0, num_tiles, _step, jnp.int32(0))


def _pca_walk(x, m, mean):
    return pca_kernel._pallas_moments_dbuf(
        x, m, mean, "highest", True, True, 128, 3)


def _als_solve_walk(m_t, gram, reg):
    return als_kernel._pallas_solve_dbuf(
        m_t, gram, reg, 8, True, True, 128, 2)


def _als_gram_walk(f_p):
    return als_kernel._pallas_factor_gram_dbuf(f_p, "highest", True, 128, 3)


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


@pytest.mark.parametrize("walk,shapes", [
    (_pca_walk, (_f32(512, 128), _f32(512, 1), _f32(1, 128))),
    (_als_solve_walk, (_f32(80, 512), _f32(8, 8), _f32(1, 1))),
    (_als_gram_walk, (_f32(512, 128),)),
], ids=["pca_moments", "als_solve", "als_factor_gram"])
def test_walks_without_a_bound_trace_as_before(monkeypatch, walk, shapes):
    now = str(jax.make_jaxpr(walk)(*shapes))
    monkeypatch.setattr(_dbuf, "tile_walk", _tile_walk_before)
    before = str(jax.make_jaxpr(walk)(*shapes))
    assert "dma_start" in now and "while" not in now
    assert now == before
