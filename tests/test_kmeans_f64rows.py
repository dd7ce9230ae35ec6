"""K-Means on the table a Spark executor hands over (ISSUE 33): float64
rows on no row bucket, cast and padded under the upload.  The benchmark's
configuration ``kmeans_d256_k1000_f64rows`` at its ``rehearse`` size, its
adapter and its plain reference, on the CPU."""

import importlib.util
import os

import numpy as np
import pytest

from oap_mllib_tpu import KMeans
from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.data import table as table_mod
from oap_mllib_tpu.models import kmeans as kmeans_mod
from oap_mllib_tpu.parallel.mesh import get_mesh

CELL = "kmeans_d256_k1000_f64rows.fit_loop"


@pytest.fixture(scope="module")
def bench():
    """(``benchmarks/run.py`` as a module, the cell, its configuration at
    the rehearse size, its adapter, its reference)."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "run.py",
    )
    spec = importlib.util.spec_from_file_location("oap_bench_run_f64rows", path)
    harness = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(harness)
    _, cell, cfg, _ = harness.load_cell(CELL, rehearse=True)
    adapter = harness._module("estimators", cfg["estimator"])
    ref = harness._module("reference", adapter.REFERENCE)
    return harness, cell, cfg, adapter, ref


@pytest.fixture
def small_pieces(monkeypatch):
    """Blocks of 1,000 rows of the rehearse table, three staging buffers a
    shard, a shard of more than a block cast under the upload: the route
    of the cell's 3.2 GB at 128 KB a block."""

    def arm(cfg, n_devices):
        mesh = get_mesh(n_devices=n_devices)
        monkeypatch.setattr(kmeans_mod, "get_mesh", lambda: mesh)
        block = 1000 * cfg["d"] * 4
        monkeypatch.setattr(table_mod, "_UPLOAD_PIECE_BYTES", block)
        monkeypatch.setattr(table_mod, "_CAST_BLOCK_BYTES", block)
        monkeypatch.setattr(table_mod, "_CAST_RING_SLOTS", 3)
        set_config(matmul_precision=cfg["matmul_precision"])

    return arm


def _under(numbers, limits):
    assert set(numbers) == set(limits)
    return {n: v for n, v in numbers.items() if not v <= limits[n]}


class TestCellAtRehearseSize:
    def test_the_configuration_is_the_listed_share(self, bench):
        harness, cell, _, adapter, _ = bench
        cfg = harness.load_cell(CELL)[2]
        assert cfg["rows_per_chip"] == 100_000_000 // 32 == 3_125_000
        assert cfg["reduced"] == [] and cell["chips"] == 1
        assert cfg["input_dtype"] == "float64" and cfg["dtype"] == "float32"
        sibling = harness._load_json(
            harness.HERE, "configs", "kmeans_d256_k1000.json"
        )
        for key in ("d", "k", "matmul_precision", "init_mode", "init_steps",
                    "max_iter", "tol", "data", "program_config", "phases",
                    "expect_kernel", "limits"):
            assert cfg[key] == sibling[key], key
        # off its bucket, and the bucket is 2^22 rows
        assert cfg["rows_per_chip"] % 256 == 8
        assert table_mod._padded_row_target(cfg["rows_per_chip"], 256) == 1 << 22

    @pytest.mark.parametrize("seed", [3, 2_147_483_659])
    @pytest.mark.parametrize("n_devices", [1, 4])
    def test_fit_is_correct_by_the_plain_reference(
        self, bench, small_pieces, seed, n_devices
    ):
        _, cell, cfg, adapter, ref = bench
        x = adapter.make_data(cfg, cfg["rows_per_chip"] * cell["chips"], seed)
        assert x.dtype == np.float64 and x.flags.c_contiguous
        assert x.shape == (8000, cfg["d"])
        assert (x != x.astype(np.float32)).mean() > 0.99  # every value rounds
        small_pieces(cfg, n_devices)
        result, info = adapter.fit(cfg, x, seed)
        staging = info["staging"]
        assert staging["copied_bytes"] == 0
        assert staging["cast_bytes"] == 8000 * cfg["d"] * 4
        assert staging["valid_rows"] == 8000
        assert staging["padded_rows"] == 8192 and staging["pieces"] > 1
        assert info["accelerated"] and not any(
            info["resilience"].get(k) for k in ("degradations", "retries", "faults")
        )
        assert int(result["sizes"].sum()) == 8000  # no pad row, ever
        assert not _under(ref.judge(x, cfg, [result], seed), cfg["limits"])
        # required work counts the valid rows alone
        assert adapter.fit_work(cfg, 8000, info)["bytes"] == (
            4.0 * 8000 * cfg["d"] * (cfg["init_steps"] + 1 + info["num_iter"] + 1)
        )

    @pytest.mark.parametrize("n_devices", [1, 4])
    def test_rounding_before_or_under_the_upload_is_one_fit(
        self, bench, small_pieces, n_devices
    ):
        """float64 rows cast under the upload, the same rows rounded by the
        caller first, and (on a bucket) the rounded rows going up as they
        are: the same bytes on the device, so the same centres, bit for
        bit."""
        _, _, cfg, adapter, _ = bench
        small_pieces(cfg, n_devices)

        def centres(x):
            return KMeans(
                k=cfg["k"], max_iter=5, seed=11, init_mode=cfg["init_mode"]
            ).fit(x).cluster_centers_.tobytes()

        x = adapter.make_data(cfg, 8192, 7)
        assert centres(x[:8000]) == centres(x[:8000].astype(np.float32))
        model = KMeans(k=cfg["k"], max_iter=5, seed=11).fit(x.astype(np.float32))
        up = model.summary.timings.root.node("table_convert/upload")
        assert up.attrs["cast_bytes"] == 0  # on its bucket: as it is
        assert centres(x) == model.cluster_centers_.tobytes()


class TestControlsThatMustFail:
    """The comparison is tight enough to tell what the configuration
    guarantees from what breaks it."""

    def test_reference_at_highest_passes_and_bf16_stored_rows_fail(self, bench):
        _, _, cfg, adapter, ref = bench
        x = adapter.make_data(cfg, 8000, 5)
        sound = ref.fit_plain(x, cfg, 6, "highest")
        assert not _under(ref.judge(x, cfg, [sound], 5), cfg["limits"])
        stored = ref.fit_plain(x, cfg, 6, "bfloat16")
        assert _under(ref.judge(x, cfg, [stored], 5), cfg["limits"])

    @pytest.mark.parametrize("pad_rows_counted", [1, -1])
    def test_a_pad_row_counted_or_a_row_dropped_fails(self, bench, pad_rows_counted):
        _, _, cfg, adapter, ref = bench
        x = adapter.make_data(cfg, 8000, 5)
        result = ref.fit_plain(x, cfg, 6, "highest")
        result["sizes"] = result["sizes"].copy()
        result["sizes"][0] += pad_rows_counted
        over = _under(ref.judge(x, cfg, [result], 5), cfg["limits"])
        assert over["count_gap"] == pytest.approx(1 / 8000)


class TestPlainReference:
    def test_rounds_once_in_blocks_and_walks_exactly_the_rows(self, bench, monkeypatch):
        *_, ref = bench
        rng = np.random.default_rng(0)
        x = rng.normal(size=(1003, 5))
        monkeypatch.setattr(ref, "ROUND_BLOCK_ROWS", 100)
        assert ref.rounded(x).tobytes() == x.astype(np.float32).tobytes()
        x32 = x.astype(np.float32)
        assert ref.rounded(x32) is x32
        # the cell: 100 blocks of 31,250; the rehearsal: one of 8,000
        assert ref.block_rows(3_125_000) == 31_250
        assert ref.block_rows(8000) == 8000
        assert ref.block_rows(1003) == 1003  # under a block: whole
        x3 = ref.upload(x32)
        assert x3.shape == (1, 1003, 5)
        assert ref.upload(np.zeros((40_001 * 3, 2), np.float32)).shape == (
            13, 9_231, 2
        )
        assert np.asarray(x3).reshape(1003, 5).tobytes() == x32.tobytes()
