"""The k-means|| candidate reduction on the device (ISSUE 28).

``kmeans_ops._reduce_candidates`` is the host's ``_weighted_kmeans_pp``
step for step on a fixed-shape slot buffer, one jitted program.  The
host loop stays as the oracle: another random stream, the same
distribution, so the comparison is of seeding COSTS over seeds, with a
control (sampling by weight alone, ignoring D^2) that the tolerance must
refuse.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oap_mllib_tpu.ops import kmeans_ops
from oap_mllib_tpu.utils import progcache

SEEDS = range(8)
K = 24


def _reduce(slots, weights, valid, seed, k, mesh=None):
    return kmeans_ops.reduce_candidates(
        slots, weights, valid, jax.random.PRNGKey(seed), k, mesh
    )


def _rows_of(centers, slots):
    """Index in ``slots`` of each returned centre (exact rows: the
    reduction copies, it never averages)."""
    hit = (centers[:, None, :] == slots[None, :, :]).all(-1)
    assert hit.any(axis=1).all()
    return hit.argmax(axis=1)


@pytest.fixture(scope="module")
def blobs():
    """K tight blobs of very unequal size behind a few empty slots:
    D^2 sampling finds the small blobs, sampling by weight does not."""
    rng = np.random.default_rng(7)
    sizes = np.r_[np.full(4, 60), np.full(K - 4, 3)]
    protos = rng.normal(size=(K, 5)) * 20
    pts = np.concatenate([
        p + 0.05 * rng.normal(size=(n, 5)) for p, n in zip(protos, sizes)
    ]).astype(np.float32)
    weights = rng.uniform(0.5, 2.0, len(pts)).astype(np.float32)
    # every seventh slot is empty: zeros with a weight that must not count
    m = len(pts) + len(pts) // 6
    valid = np.ones(m, bool)
    valid[::7] = False
    slots = np.zeros((m, 5), np.float32)
    slots[valid] = pts[: valid.sum()]
    w = np.full(m, 5.0, np.float32)
    w[valid] = weights[: valid.sum()]
    return slots, w, valid


def _seeding_cost(centers, slots, w, valid):
    d2 = ((slots[:, None, :] - centers[None]) ** 2).sum(-1).min(1)
    return float((d2 * w)[valid].sum())


@pytest.fixture(scope="module")
def oracle_range(blobs):
    slots, w, valid = blobs
    costs = [
        _seeding_cost(
            kmeans_ops._weighted_kmeans_pp(
                slots[valid].astype(np.float64), w[valid].astype(np.float64),
                K, np.random.default_rng(seed),
            ),
            slots, w, valid,
        )
        for seed in range(16)
    ]
    return min(costs), max(costs)


class TestAgainstTheHostLoop:
    @pytest.mark.parametrize("seed", SEEDS)
    def test_seeding_cost_lies_in_the_oracles_range(
        self, blobs, oracle_range, seed
    ):
        slots, w, valid = blobs
        lo, hi = oracle_range
        cost = _seeding_cost(_reduce(slots, w, valid, seed, K), slots, w, valid)
        # 16 host seeds do not bound a 17th: half the range's bottom and
        # twice its top, where the control reads past ten times the top
        assert lo / 2 <= cost <= 2 * hi

    @pytest.mark.parametrize("seed", SEEDS)
    def test_sampling_by_weight_alone_fails_that_range(
        self, blobs, oracle_range, seed
    ):
        """The control: k draws by weight, D^2 ignored, land in the large
        blobs and leave small ones uncovered."""
        slots, w, valid = blobs
        p = np.where(valid, w, 0.0).astype(np.float64)
        idx = np.random.default_rng(seed).choice(len(p), K, p=p / p.sum())
        cost = _seeding_cost(slots[idx], slots, w, valid)
        assert cost > 10 * oracle_range[1]

    def test_first_two_draws_follow_the_exact_probabilities(self):
        """P(i) = w_i / W, then P(j | i) = w_j d2_ij / sum: 20,000 keys
        against the exact joint table of a six-candidate set."""
        pts = np.array([[0.], [1.], [3.], [7.], [7.5], [20.], [9.]], np.float32)
        w = np.array([1, 2, 0.5, 3, 1, 0.2, 4.0], np.float32)
        valid = np.array([1, 1, 1, 1, 1, 1, 0], np.float32)  # 9.0 is empty
        n = 20000
        pair = jax.jit(jax.vmap(lambda key: kmeans_ops._reduce_candidates(
            jnp.asarray(pts), jnp.asarray(w), jnp.asarray(valid), key, k=2
        )))(jax.random.split(jax.random.PRNGKey(0), n))
        idx = (np.asarray(pair)[:, :, 0, None] == pts[None, None, :, 0]).argmax(-1)
        mass = w * valid
        joint = np.zeros((7, 7))
        for i in range(7):
            p = (pts[:, 0] - pts[i, 0]) ** 2 * mass
            joint[i] = mass[i] / mass.sum() * p / p.sum()
        seen = np.zeros((7, 7))
        np.add.at(seen, (idx[:, 0], idx[:, 1]), 1)
        possible = joint > 0
        assert seen[~possible].sum() == 0
        want = n * joint[possible]
        chi2 = ((seen[possible] - want) ** 2 / want).sum()
        # 29 degrees of freedom: 60 is the 0.9994 quantile (read: 34.4)
        assert possible.sum() == 30 and chi2 < 60

    @pytest.mark.parametrize("seed", SEEDS)
    def test_k_distinct_rows_of_distinct_candidates(self, blobs, seed):
        slots, w, valid = blobs
        centers = _reduce(slots, w, valid, seed, K)
        assert centers.shape == (K, 5) and centers.dtype == np.float32
        rows = _rows_of(centers, slots)
        assert len(set(rows.tolist())) == K
        assert valid[rows].all()


class TestSlotsThatMustNotBeDrawn:
    @pytest.fixture(scope="class")
    def marked(self):
        """12 candidates with mass among 6 empty slots and 6 of weight 0,
        each kind recognisable by its first column."""
        rng = np.random.default_rng(3)
        slots = rng.normal(size=(24, 4)).astype(np.float32)
        kind = np.arange(24) % 4  # 0, 1: mass; 2: empty; 3: weight 0
        slots[:, 0] = 100.0 * kind
        valid = kind != 2
        w = np.where(kind == 3, 0.0, 1.0 + np.arange(24)).astype(np.float32)
        return slots, w, valid, kind

    @pytest.mark.parametrize("seed", SEEDS)
    def test_empty_and_weightless_slots_stay_behind(self, marked, seed):
        slots, w, valid, kind = marked
        # as many centres as candidates with mass: none is left at the end
        centers = _reduce(slots, w, valid, seed, 12)
        rows = _rows_of(centers, slots)
        assert (kind[rows] < 2).all()
        assert len(set(rows.tolist())) == 12

    @pytest.mark.parametrize("seed", SEEDS)
    def test_no_weight_at_all_means_every_valid_slot_weighs_one(
        self, marked, seed
    ):
        slots, _, valid, kind = marked
        none = _reduce(slots, np.zeros(24, np.float32), valid, seed, 10)
        ones = _reduce(slots, np.ones(24, np.float32), valid, seed, 10)
        np.testing.assert_array_equal(none, ones)
        assert (kind[_rows_of(none, slots)] != 2).all()

    def test_first_centre_is_uniform_over_valid_slots_without_weight(
        self, marked
    ):
        slots, _, valid, _ = marked
        firsts = [
            int(_rows_of(
                _reduce(slots, np.zeros(24, np.float32), valid, seed, 1),
                slots,
            )[0])
            for seed in range(200)
        ]
        counts = np.bincount(firsts, minlength=24)
        assert (counts[~valid] == 0).all()
        # 18 valid slots, 200 draws: 11 expected each; none starved
        assert (counts[valid] > 0).all() and counts.max() < 30

    @pytest.mark.parametrize("seed", SEEDS)
    def test_more_centres_than_mass_draws_uniformly_among_valid(
        self, marked, seed
    ):
        """Every candidate with mass is a centre after 12 steps: the rest
        are uniform draws among the VALID slots, as on the host."""
        slots, w, valid, kind = marked
        rows = _rows_of(_reduce(slots, w, valid, seed, 20), slots)
        assert set(np.flatnonzero(kind < 2)) <= set(rows.tolist())
        assert valid[rows].all()

    @pytest.mark.parametrize("seed", SEEDS)
    def test_equal_candidates_give_k_copies(self, seed):
        slots = np.full((9, 3), 2.5, np.float32)
        centers = _reduce(slots, np.ones(9, np.float32), np.ones(9), seed, 5)
        assert np.isfinite(centers).all()
        np.testing.assert_array_equal(centers, np.full((5, 3), 2.5))


class TestDeterminism:
    @pytest.fixture
    def mesh(self):
        from jax.sharding import Mesh

        assert len(jax.devices()) == 8
        return Mesh(np.asarray(jax.devices()).reshape(8, 1), ("data", "model"))

    @pytest.mark.parametrize("seed", SEEDS)
    def test_same_key_same_centres_on_one_and_eight_devices(
        self, blobs, mesh, seed
    ):
        slots, w, valid = blobs
        one = _reduce(slots, w, valid, seed, K)
        np.testing.assert_array_equal(one, _reduce(slots, w, valid, seed, K))
        np.testing.assert_array_equal(
            one, _reduce(slots, w, valid, seed, K, mesh)
        )
        other = _reduce(slots, w, valid, seed + 100, K)
        assert not np.array_equal(one, other)

    @pytest.mark.parametrize("seed", [0, 5])
    def test_init_kmeans_parallel_repeats(self, seed):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(600, 6)).astype(np.float32))
        ones = jnp.ones((600,), jnp.float32)
        a = kmeans_ops.init_kmeans_parallel(x, ones, 600, 5, seed)
        b = kmeans_ops.init_kmeans_parallel(x, ones, 600, 5, seed)
        np.testing.assert_array_equal(a, b)
        assert _rows_of(a, np.asarray(x)).shape == (5,)


class TestThroughTheFit:
    @pytest.mark.parametrize("streamed", [False, True])
    def test_second_fit_of_a_shape_builds_no_program(self, streamed):
        from oap_mllib_tpu import KMeans
        from oap_mllib_tpu.data.stream import ChunkSource

        rng = np.random.default_rng(2)
        x = np.concatenate([
            rng.normal(size=(256, 6)) + 8 * i for i in range(4)
        ]).astype(np.float32)

        def fit():
            data = ChunkSource.from_array(x, chunk_rows=256) if streamed else x
            return KMeans(k=4, max_iter=3, seed=0).fit(data)

        first = fit()
        assert first.summary.accelerated
        assert (
            progcache.stats()["by_algo"]["kmeans.reduce_candidates"]["misses"]
            >= 1
        )
        compiles = progcache.xla_compile_count()
        second = fit()
        assert second.summary.progcache["misses"] == 0
        assert progcache.xla_compile_count() == compiles
        np.testing.assert_array_equal(
            first.cluster_centers_, second.cluster_centers_
        )

    def test_rounds_span_says_no_pick_was_dropped(self):
        """4k slots a round for about 2k picks: the fit's span counts what
        fell past the capacity beside what filled it, and that is 0."""
        from oap_mllib_tpu import KMeans

        rng = np.random.default_rng(3)
        x = rng.normal(size=(1024, 6)).astype(np.float32)
        model = KMeans(k=8, max_iter=2, seed=1).fit(x)
        attrs = model.summary.timings.root.node("init_centers/rounds").attrs
        assert attrs["picks_dropped"] == 0
        assert 0 < attrs["slots_filled"] <= attrs["rounds"] * 4 * 8

    def test_rounds_span_counts_picks_past_the_capacity(self):
        """k = 1 leaves a round 4 slots for 2 expected picks: over a few
        seeds some round samples 5 rows or more (1 round in 19 would, by
        Poisson(2)), keeps the first 4 and the span says how many went."""
        from oap_mllib_tpu.telemetry import spans

        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(512, 4)).astype(np.float32))
        ones = jnp.ones((512,), jnp.float32)
        for seed in range(200):
            root = spans.Span("fit")
            with spans.enter(root, annotate=False):
                centre = kmeans_ops.init_kmeans_parallel(x, ones, 512, 1, seed)
            attrs = root.node("rounds").attrs
            assert centre.shape == (1, 4)
            assert attrs["slots_filled"] <= attrs["rounds"] * 4
            assert attrs["slot_chunks"] <= attrs["slot_chunks_cap"]
            if attrs["picks_dropped"]:
                break
        else:
            pytest.fail("no round of 200 fits sampled past its 4 slots")
        # a round that dropped picks filled every one of its slots
        assert attrs["picks_dropped"] > 0 and attrs["slots_filled"] >= 4
