"""The k-means|| round folds only the slot chunks it filled (ISSUE 32)
and gathers the rows it picked (ISSUE 36).

``kmeans_ops._pll_round`` bounds its fold by the round's own pick count
and finds each slot's row by a search of the picked-prefix.  The oracle
below is the same round written plainly: the picked rows scatter-added
into their slots by walking every row of the table, and the fold over
ALL ``cap // chunk`` chunks, as the program did both before: a slot
receives one row plus zeros, and a chunk with no valid slot reads
``inf`` on every row and moves none, so the two must agree bit for bit
wherever the picks lie and end.

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
    pos = jnp.cumsum(picked.astype(jnp.int32)) - 1
    slot_of = jnp.where(picked, pos, cap)
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
    return slots, slot_valid, dmin, amin, phi, pos[-1] + 1


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
    names = ("slots", "slot_valid", "dmin", "amin", "phi", "picks")
    assert len(got) == len(want) == len(names)
    for name, g, o in zip(names, got, want):
        assert g.shape == o.shape and g.dtype == o.dtype, name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(o), name)


def _fold_loops(hlo_text):
    """The compiled round's ``while`` instructions that are the slot fold
    (the random bits' own loop, under ``_uniform``, and the search of the
    prefix, under ``searchsorted``, have fixed counts)."""
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
        slots, slot_valid, new_dmin, new_amin, phi, picks = got
        assert float(phi) == 0.0 and int(picks) == 0
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
        for i in (0, 1, 3, 5):  # slots, slot_valid, amin, picks
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
        for i in (0, 1, 2, 3, 5):  # slots, slot_valid, dmin, amin, picks
            np.testing.assert_array_equal(
                np.asarray(got[i]), np.asarray(want[i])
            )
        # eight partial sums of the cost: another order, the same value
        np.testing.assert_allclose(float(got[4]), float(want[4]), rtol=1e-6)
        filled = int((np.asarray(got[1]) > 0).sum())
        assert kmeans_ops._live_chunks(filled, CHUNK) == live


def _gather_case(table, name):
    """``(x, w, dmin, l, rows)``: a table, the weights and ``l`` that
    steer the picks, and the rows a round must place, in slot order."""
    x, dmin, _ = table
    w = np.zeros(N, np.float32)
    l = EVERY_ROW
    if name == "picks_under_the_capacity":
        rows = np.arange(5, N, 23)
    elif name == "no_picks":
        # every row weighs 1, so phi > 0, and l is too small to pick one
        return x, jnp.ones((N,), jnp.float32), dmin, 1e-30, np.arange(0)
    elif name == "picks_over_the_capacity":
        rows = np.arange(1, N, 5)  # 103 picks into 64 slots
    elif name == "last_quarter_is_pad":
        # a padded table: zero rows of weight 0 behind the valid ones,
        # over which the prefix does not rise
        valid = 3 * N // 4
        x = x.at[valid:].set(0.0)
        dmin = dmin.at[valid:].set(0.0)
        rows = np.arange(1, valid, 13)
    elif name == "first_and_last_row":
        # the state against row 7, so that row 0 has a cost to be picked by
        dmin = kmeans_ops.pairwise_sq_dists(x, x[7:8])[:, 0]
        rows = np.asarray([0, N // 2, N - 1])
    w[rows] = 1.0
    return x, jnp.asarray(w), dmin, l, rows


GATHER_CASES = ["picks_under_the_capacity", "no_picks",
                "picks_over_the_capacity", "last_quarter_is_pad",
                "first_and_last_row"]


class TestTheGatherAgainstTheScatter:
    """ISSUE 36: the round finds each slot's row in the prefix and gathers
    it; the oracle scatter-adds every row of the table.  The same bits,
    on one device and on eight row shards."""

    @pytest.mark.parametrize(
        "shards", [1, 8], ids=["one_device", "eight_row_shards"]
    )
    @pytest.mark.parametrize("case", GATHER_CASES)
    def test_same_slots_state_and_count_as_the_scatter(
        self, table, case, shards
    ):
        x, w, dmin, l, rows = _gather_case(table, case)
        amin = jnp.zeros((N,), jnp.int32)
        rest = (jnp.asarray(BASE, jnp.int32), jax.random.PRNGKey(5),
                jnp.asarray(l, jnp.float32))
        want = _round_folding_every_chunk(
            x, w, dmin, amin, *rest, cap=CAP, chunk=CHUNK
        )
        if shards > 1:
            mesh = get_mesh(n_devices=shards)
            x = jax.device_put(x, data_sharding(mesh, 2))
            w, dmin, amin = (jax.device_put(a, data_sharding(mesh, 1))
                             for a in (w, dmin, amin))
        got = kmeans_ops._pll_round(
            x, w, dmin, amin, *rest, cap=CAP, chunk=CHUNK
        )
        if shards > 1:
            assert len(got[2].sharding.device_set) == shards
            assert got[0].sharding.is_fully_replicated
            # eight partial sums of the cost: another order, the same value
            np.testing.assert_allclose(
                float(got[4]), float(want[4]), rtol=1e-6
            )
            got = got[:4] + (want[4],) + got[5:]
        _assert_same_bits(got, want)

        slots, slot_valid, _, new_amin, phi, picks = map(np.asarray, got)
        kept = rows[:CAP]
        assert float(phi) > 0 and int(picks) == len(rows)
        assert int(picks) - int((slot_valid > 0).sum()) == len(rows) - len(kept)
        # picks fill the slots from 0 in row order; the rest is zeros
        np.testing.assert_array_equal(slots[: len(kept)], np.asarray(x)[kept])
        assert not slots[len(kept):].any()
        np.testing.assert_array_equal(slot_valid, np.arange(CAP) < len(kept))
        if len(kept) == 0:  # zero fold trips: the state as given
            np.testing.assert_array_equal(new_amin, 0)
            np.testing.assert_array_equal(got[2], dmin)
        else:  # a picked row is its own nearest candidate
            np.testing.assert_array_equal(
                new_amin[kept], BASE + np.arange(len(kept))
            )
