"""The k-means|| round folds only the slot chunks it filled (ISSUE 32).

``kmeans_ops._pll_round`` bounds its fold by the round's own pick count.
The oracle below is the same round with the fold written plainly over
ALL ``cap // chunk`` chunks, as the program folded them before: a chunk
with no valid slot reads ``inf`` on every row and moves none, so the two
must agree bit for bit wherever the picks end.

Which rows a round picks is steered through the weights: with a huge
``l`` every row of positive cost has probability 1, so the picks are
exactly the rows whose weight is 1, in row order.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oap_mllib_tpu.ops import kmeans_ops
from oap_mllib_tpu.parallel.mesh import data_sharding, get_mesh

N, D, CAP, CHUNK = 512, 6, 64, 16
BASE = 1 + CAP  # a second round's ids: behind candidate 0 and round one
EVERY_ROW = 1e12  # an l that clips every positive probability to 1


@functools.partial(jax.jit, static_argnames=("cap", "chunk"))
def _round_folding_every_chunk(x, w, dmin, amin, base_id, key, l, cap, chunk):
    cost = dmin * w
    phi = jnp.sum(cost)
    prob = jnp.minimum(l * cost / jnp.maximum(phi, 1e-30), 1.0)
    picked = jax.random.uniform(key, dmin.shape, dtype=dmin.dtype) < prob
    slot_of = jnp.where(picked, jnp.cumsum(picked.astype(jnp.int32)) - 1, cap)
    slots = jnp.zeros((cap, x.shape[1]), x.dtype).at[slot_of].add(
        x * picked[:, None].astype(x.dtype), mode="drop"
    )
    slot_valid = jnp.zeros((cap,), x.dtype).at[slot_of].add(
        picked.astype(x.dtype), mode="drop"
    )
    for i in range(cap // chunk):
        at = slice(i * chunk, (i + 1) * chunk)
        d2 = kmeans_ops.pairwise_sq_dists(x, slots[at])
        d2 = jnp.where(slot_valid[None, at] > 0, d2, jnp.inf)
        cm = jnp.min(d2, axis=1)
        ca = (kmeans_ops.argmin_rows(d2, cm).astype(jnp.int32)
              + base_id + chunk * i)
        better = cm < dmin
        dmin, amin = jnp.where(better, cm, dmin), jnp.where(better, ca, amin)
    return slots, slot_valid, dmin, amin, phi


@pytest.fixture(scope="module")
def table():
    """Rows around a few prototypes, and the running state against a
    first candidate (row 0), as ``init_kmeans_parallel`` starts a round."""
    rng = np.random.default_rng(11)
    proto = rng.normal(size=(9, D)) * 3.0
    x = (proto[rng.integers(9, size=N)] + 0.4 * rng.normal(size=(N, D)))
    x = jnp.asarray(x.astype(np.float32))
    dmin = kmeans_ops.pairwise_sq_dists(x, x[:1])[:, 0]
    # row 0 is candidate 0 (cost 0, never picked): weight it 0 throughout
    return x, dmin, jnp.zeros((N,), jnp.int32)


def _weights(picks):
    """Weight 1 on ``picks`` rows spread over the table, 0 elsewhere."""
    w = np.zeros(N, np.float32)
    w[1 + (N - 1) * np.arange(picks) // max(picks, 1)] = 1.0
    assert int(w.sum()) == picks and w[0] == 0
    return jnp.asarray(w)


def _args(table, w, l, seed=5):
    x, dmin, amin = table
    return (x, w, dmin, amin, jnp.asarray(BASE, jnp.int32),
            jax.random.PRNGKey(seed), jnp.asarray(l, jnp.float32))


def _assert_same_bits(got, want):
    for name, g, o in zip(("slots", "slot_valid", "dmin", "amin", "phi"),
                          got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(o), name)


def _fold_loops(hlo_text):
    """The compiled round's ``while`` instructions that are the slot fold
    (the random bits' own loop, under ``_uniform``, has a fixed count)."""
    return [
        line for line in hlo_text.splitlines()
        if " while(" in line and "pll_round/while" in line
        and "_uniform" not in line
    ]


# picks, l, and how many of the CAP // CHUNK = 4 chunks hold a valid slot
CASES = {
    "well_under_one_chunk": (3, EVERY_ROW, 1),
    "on_a_chunk_edge": (2 * CHUNK, EVERY_ROW, 2),
    "one_past_an_edge": (2 * CHUNK + 1, EVERY_ROW, 3),
    "one_short_of_the_capacity": (CAP - 1, EVERY_ROW, 4),
    "over_the_capacity": (CAP + 37, EVERY_ROW, 4),
    "sampled_at_half_the_capacity": (N - 1, CAP / 2, None),
}


class TestAgainstTheFoldOverEveryChunk:
    @pytest.mark.parametrize("case", CASES)
    def test_same_bits_as_the_whole_fold(self, table, case):
        picks, l, live = CASES[case]
        args = _args(table, _weights(picks), l)
        got = kmeans_ops._pll_round(*args, cap=CAP, chunk=CHUNK)
        want = _round_folding_every_chunk(*args, cap=CAP, chunk=CHUNK)
        _assert_same_bits(got, want)
        filled = int((np.asarray(got[1]) > 0).sum())
        if live is None:  # sampled: about CAP / 2 picks, never none
            assert 0 < filled < CAP
        else:
            assert filled == min(picks, CAP)  # overflow is dropped
            assert kmeans_ops._live_chunks(filled, CHUNK) == live
        # the fold did move rows: the case is no fold of nothing
        assert (np.asarray(got[3]) >= BASE).any()
        assert np.asarray(got[3]).max() < BASE + filled

    def test_no_picks_is_zero_trips_and_the_state_as_given(self, table):
        x, dmin, amin = table
        args = _args(table, jnp.zeros((N,), jnp.float32), 2.0 * CAP)
        got = kmeans_ops._pll_round(*args, cap=CAP, chunk=CHUNK)
        _assert_same_bits(
            got, _round_folding_every_chunk(*args, cap=CAP, chunk=CHUNK)
        )
        slots, slot_valid, new_dmin, new_amin, phi = got
        assert float(phi) == 0.0
        assert not np.asarray(slots).any() and not np.asarray(slot_valid).any()
        np.testing.assert_array_equal(np.asarray(new_dmin), np.asarray(dmin))
        np.testing.assert_array_equal(np.asarray(new_amin), np.asarray(amin))
        assert kmeans_ops._live_chunks(0, CHUNK) == 0

    @pytest.mark.parametrize("chunk", [8, 32, CAP])
    def test_the_chunk_size_moves_neither_candidates_nor_owners(
        self, table, chunk
    ):
        args = _args(table, _weights(2 * CHUNK + 5), EVERY_ROW)
        ref = kmeans_ops._pll_round(*args, cap=CAP, chunk=CHUNK)
        got = kmeans_ops._pll_round(*args, cap=CAP, chunk=chunk)
        for i in (0, 1, 3):  # slots, slot_valid, amin
            np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(ref[i]))
        # the distances come from |x|^2 + |s|^2 - 2 x.s with |x|^2 near
        # 60: a product of another width may round its last bits apart
        np.testing.assert_allclose(
            np.asarray(got[2]), np.asarray(ref[2]), rtol=0, atol=1e-4
        )

    def test_the_loop_bound_is_read_on_the_device(self, table):
        """One program for every pick count: the trip count is a value,
        not a shape, so a second pick count compiles nothing."""
        few = _args(table, _weights(3), EVERY_ROW)
        many = _args(table, _weights(CAP), EVERY_ROW)
        text = kmeans_ops._pll_round.lower(
            *few, cap=CAP, chunk=CHUNK
        ).compile().as_text()
        assert _fold_loops(text) and not any(
            "known_trip_count" in line for line in _fold_loops(text)
        )
        kmeans_ops._pll_round(*few, cap=CAP, chunk=CHUNK)
        size = kmeans_ops._pll_round._cache_size()
        kmeans_ops._pll_round(*many, cap=CAP, chunk=CHUNK)
        assert kmeans_ops._pll_round._cache_size() == size


class TestOnTheRowShardedMesh:
    @pytest.mark.parametrize("case", ["one_past_an_edge", "over_the_capacity"])
    def test_eight_row_shards_fold_what_one_device_folds(self, table, case):
        picks, l, live = CASES[case]
        args = _args(table, _weights(picks), l)
        want = kmeans_ops._pll_round(*args, cap=CAP, chunk=CHUNK)
        mesh = get_mesh(n_devices=8)
        x, w, dmin, amin, *rest = args
        got = kmeans_ops._pll_round(
            jax.device_put(x, data_sharding(mesh, 2)),
            *(jax.device_put(a, data_sharding(mesh, 1))
              for a in (w, dmin, amin)),
            *rest, cap=CAP, chunk=CHUNK,
        )
        assert len(got[2].sharding.device_set) == 8  # the state stays sharded
        for i in (0, 1, 2, 3):  # slots, slot_valid, dmin, amin
            np.testing.assert_array_equal(
                np.asarray(got[i]), np.asarray(want[i])
            )
        # eight partial sums of the cost: another order, the same value
        np.testing.assert_allclose(float(got[4]), float(want[4]), rtol=1e-6)
        filled = int((np.asarray(got[1]) > 0).sum())
        assert kmeans_ops._live_chunks(filled, CHUNK) == live
