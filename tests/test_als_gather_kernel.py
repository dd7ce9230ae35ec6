"""The factor-row gather of the grouped ALS moments as a Pallas walk over
a packed, VMEM-resident table (``ops/pallas/als_gather.py``): the same
bits as XLA's ``src_factors.T[:, src_b]``, the rule that chooses between
the two, and a fit through either — the walk under the interpreter, on
the CPU.  What Mosaic makes of it is compiled in ``test_tpu_compile.py``
and run on the chip in ``tests_tpu/test_als_tpu.py``."""

import numpy as np
import pytest

import jax.numpy as jnp

from oap_mllib_tpu import ALS
from oap_mllib_tpu.ops import als_ops
from oap_mllib_tpu.ops.pallas import als_gather

N_SRC = (1, 7, 8, 63, 64, 1000, 4099)
RANKS = (3, 10, 16, 32)
WIDTHS = (64, 128, 256)
GROUPS = 19  # a step walks 8 or 16 groups here: never a whole number of steps


def _factors(n_src, r, seed):
    """Factors with the values a copy could get wrong: signed zeros,
    infinities, a NaN with a payload, subnormals."""
    rng = np.random.default_rng(seed)
    f = rng.standard_normal((n_src, r)).astype(np.float32)
    special = np.array([-0.0, np.inf, -np.inf, 1e-45, -3e-39], np.float32)
    f.reshape(-1)[: len(special)] = special[: f.size]
    bits = f.view(np.uint32)
    bits.reshape(-1)[-1] = 0x7FC0_1234  # NaN, payload kept by a copy
    return f


def _slots(n_src, groups, p, seed):
    """Indices over the whole table, the last slots of each group pad
    slots (source 0, as the grouped layouts write them), and the last
    source named once."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_src, (groups, p)).astype(np.int32)
    src[:, -3:] = 0
    src[groups // 2, 0] = n_src - 1
    return src


@pytest.mark.parametrize(
    "n_src, r, p",
    [(n, r, WIDTHS[(i + j) % 3])
     for i, n in enumerate(N_SRC) for j, r in enumerate(RANKS)],
)
def test_the_walk_copies_the_bits_of_xlas_gather(monkeypatch, n_src, r, p):
    # steps of 1024 slots, so that GROUPS takes several and a padded last
    monkeypatch.setattr(als_gather, "_STEP_SLOTS", 1024)
    groups, step = GROUPS, als_gather._step_groups(GROUPS, p)
    assert groups % step and groups > step
    f = jnp.asarray(_factors(n_src, r, n_src * 100 + r))
    src = jnp.asarray(_slots(n_src, groups, p, p + r))
    want = f.T[:, src]
    table = als_gather.pack_table(f)
    assert table.shape == (als_gather.table_rows(n_src, r), 128)
    assert table.nbytes == als_gather.table_bytes(n_src, r)
    got = als_ops.gather_factor_rows(f, src, "pallas_interpret", table)
    assert got.shape == want.shape == (r, groups, p)
    assert got.dtype == want.dtype == jnp.float32
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


def test_the_block_moments_are_the_same_through_either_gather():
    # the walk packs its own table here
    n_src, r, p = 1000, 10, 128
    f = jnp.asarray(np.random.default_rng(1).standard_normal((n_src, r)),
                    jnp.float32)
    src = _slots(n_src, 24, p, 2)
    rng = np.random.default_rng(3)
    conf = (rng.integers(0, 5, src.shape) * 25).astype(np.float32)
    valid = np.ones(src.shape, np.float32)
    valid[:, -3:] = 0  # the pad slots
    args = [jnp.asarray(a) for a in (src, conf, valid)]
    xla = als_ops.grouped_block_moments(*args, f, 40.0, True, "f32", "xla")
    walk = als_ops.grouped_block_moments(
        *args, f, 40.0, True, "f32", "pallas_interpret")
    assert np.asarray(walk).tobytes() == np.asarray(xla).tobytes()


class TestTheRule:
    """``als_ops.resolve_gather_kernel``: the walk where the backend is a
    TPU and the packed table fits its VMEM bound; XLA's gather elsewhere."""

    def test_the_cpu_keeps_xlas_gather(self):
        assert als_ops.resolve_gather_kernel(624961, 10) == "xla"
        assert als_ops.resolve_gather_kernel(624961, 10, backend="cpu") == "xla"

    def test_the_cells_tables_fit(self):
        for n_src in (500495, 624961):
            assert als_ops.resolve_gather_kernel(
                n_src, 10, np.float32, backend="tpu") == "pallas"
        # eight sources a 512-byte row at rank 10
        assert als_gather.table_bytes(624961, 10) == 78128 * 512
        assert als_gather.table_bytes(500495, 10) == 62568 * 512

    def test_the_bound(self):
        at = 1 << 20  # 64 MiB packed at rank 10: the whole table's users fit
        assert als_gather.table_bytes(at, 10) == als_gather.TABLE_BOUND_BYTES
        assert als_ops.resolve_gather_kernel(at, 10, backend="tpu") == "pallas"
        assert als_ops.resolve_gather_kernel(1_000_990, 10, backend="tpu") == "pallas"
        assert als_ops.resolve_gather_kernel(at + 8, 10, backend="tpu") == "xla"
        # rank 32 takes four sources a row where rank 10 takes eight
        assert als_ops.resolve_gather_kernel(at // 2, 32, backend="tpu") == "pallas"
        assert als_ops.resolve_gather_kernel(at // 2 + 4, 32, backend="tpu") == "xla"

    def test_what_the_walk_does_not_take(self):
        # a source wider than a row, or factors that are not float32
        assert als_ops.resolve_gather_kernel(100, 129, backend="tpu") == "xla"
        assert als_ops.resolve_gather_kernel(
            100, 10, np.float64, backend="tpu") == "xla"

    @pytest.mark.parametrize("r", [1, 3, 8, 9, 10, 16, 17, 32, 64, 128])
    def test_a_row_holds_whole_sources(self, r):
        width = als_gather.row_width(r)
        assert width >= max(r, 8) and 128 % width == 0 and width & (width - 1) == 0

    def test_the_width_rule_prices_the_route_that_runs(self, monkeypatch):
        tail = np.minimum(
            10 + np.random.default_rng(0).lognormal(3.0, 1.5, 4000), 1e5
        ).astype(np.int32)[None, :]
        walk = als_ops.group_sizes_for([tail], 10, gather="pallas")
        monkeypatch.setattr(als_ops, "_SLOT_NS", als_ops._WALK_SLOT_NS)
        assert als_ops.group_sizes_for([tail], 10) == walk


def _layouts(seed, n_users=300, n_items=500, nnz=20000, p=16):
    rng = np.random.default_rng(seed)
    users = np.minimum((rng.pareto(1.2, nnz) * 3).astype(np.int32), n_users - 1)
    items = rng.integers(0, n_items, nnz).astype(np.int32)
    ratings = (rng.integers(0, 5, nnz) * 25).astype(np.float32)
    return users, items, ratings, [
        tuple(jnp.asarray(a) for a in als_ops.build_grouped_edges(
            dst, src, ratings, n_dst, p))
        for dst, src, n_dst in ((users, items, n_users), (items, users, n_items))
    ]


def test_a_grouped_fit_is_the_same_through_either_gather():
    n_users, n_items, r = 300, 500, 4
    *_, (by_user, by_item) = _layouts(4)
    rng = np.random.default_rng(5)
    x0 = jnp.asarray(rng.standard_normal((n_users, r)) * 0.1, jnp.float32)
    y0 = jnp.asarray(rng.standard_normal((n_items, r)) * 0.1, jnp.float32)
    runs = [
        als_ops.als_run_grouped(
            *by_user, *by_item, x0, y0, n_users, n_items, 3, 0.1, 40.0, True,
            solve_kernel="xla", gather_kernel=gather,
        )
        for gather in ("xla", "pallas_interpret")
    ]
    for a, b in zip(*runs):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_the_fit_books_the_gather_it_ran(monkeypatch):
    users, items, ratings, _ = _layouts(6)

    def fit():
        return ALS(rank=4, max_iter=2, implicit_prefs=True, alpha=40.0, seed=3,
                   num_user_blocks=1).fit(users, items, ratings,
                                          n_users=300, n_items=500)

    plain = fit()
    attrs = plain.summary["timings"].root.node("als_iterations").attrs
    assert attrs["gather_kernel"] == "xla" and attrs["gather_table_bytes"] == [0, 0]
    monkeypatch.setattr(als_ops, "resolve_gather_kernel",
                        lambda *a, **k: "pallas_interpret")
    walked = fit()
    attrs = walked.summary["timings"].root.node("als_iterations").attrs
    assert attrs["gather_kernel"] == "pallas_interpret"
    # the user side gathers from the items' table, the item side from the users'
    assert attrs["gather_table_bytes"] == [
        als_gather.table_bytes(500, 4), als_gather.table_bytes(300, 4)]
    assert walked.user_factors_.tobytes() == plain.user_factors_.tobytes()
    assert walked.item_factors_.tobytes() == plain.item_factors_.tobytes()
