"""The two readers that came with the PCA cell (``covariance_s``,
``upload_gb_per_s``) on a hand-made ``Context``, that every per-layer
metric ``BENCHMARK.json`` names has its reader file, and the adapter that
holds the cell's upload to the configuration (``estimators/pca_staged.py``).

    python -m pytest benchmarks/tests -q        (CPU)
"""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402

PCA_CELL = "pca_d512_k10.fit_loop"


def _ctx(phases_of_fits, cfg=None, rows=0):
    fits = [
        {"index": i, "wall_s": 1.0, "result": {}, "info": {"phases": p}, "error": None}
        for i, p in enumerate(phases_of_fits)
    ]
    # a fit that raised has no summary and is not averaged over
    fits.append({"index": len(fits), "wall_s": 9.0, "result": None, "info": {},
                 "error": "RuntimeError: boom"})
    return harness.Context(run={"fits": fits}, cfg=cfg or {}, rows=rows)


def _read(metric, ctx):
    return harness._module("metrics", metric).read(ctx)


def test_covariance_s_is_the_phase_mean():
    ctx = _ctx([{"covariance": 0.09, "eigh": 0.05}, {"covariance": 0.11, "eigh": 0.04}])
    assert _read("covariance_s", ctx) == pytest.approx(0.10)
    assert _read("covariance_s", _ctx([{"lloyd_loop": 1.0}])) is None


@pytest.mark.parametrize("workload,rows,nbytes", [
    # the bytes the program's upload span counts in each cell (PERF.md, section 5)
    ("kmeans_d256_k1000.fit_loop", 2097152, 2_155_872_256),
    ("kmeans_d256_k1000_host4.fit_loop", 8388608, 8_623_489_024),
    (PCA_CELL, 4194304, 8_589_934_592 + 16_777_216),
])
def test_upload_gb_per_s_reckons_table_and_mask(workload, rows, nbytes):
    _, cell, cfg, _ = harness.load_cell(workload)
    assert rows == cfg["rows_per_chip"] * cell["chips"]
    ctx = _ctx(
        [{"table_convert": 0.9, "table_convert/upload": 0.5},
         {"table_convert": 1.1, "table_convert/upload": 1.5}],
        cfg=cfg, rows=rows,
    )
    assert _read("upload_gb_per_s", ctx) == pytest.approx(nbytes / 1.0 / 1e9)


def test_upload_gb_per_s_finds_nothing_without_the_span():
    cfg = {"d": 512, "dtype": "float32"}
    assert _read("upload_gb_per_s", _ctx([{"table_convert": 2.0}], cfg, 1024)) is None
    assert _read("upload_gb_per_s", _ctx([], cfg, 1024)) is None


def test_every_per_layer_name_has_a_reader():
    bench = harness._load_json(harness.ROOT, "BENCHMARK.json")
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics", m["name"] + ".py")), m
        assert callable(harness._module("metrics", m["name"]).read)


def test_benchmark_json_holds_the_pca_cell():
    bench, cell, cfg, traffic = harness.load_cell(PCA_CELL)
    assert PCA_CELL in {w["name"] for w in bench["workloads"]}  # not pending
    assert cell["chips"] == 1 and cell["traffic"] == "fit_loop"
    assert cfg["rows_per_chip"] == 4194304 and cfg["d"] == 512 and cfg["k"] == 10
    assert cfg["estimator"] == "pca_staged"
    assert cfg["expect_upload"]["piece_bytes_max"] == 1 << 30
    reported = {m["name"] for m in harness.metrics_of(bench, cell, "per_layer")}
    assert {"pca_device_roofline", "eigh_s", "covariance_s", "upload_gb_per_s",
            "upload_s", "host_copy_s", "device_idle_pct", "fit_mfu_pct"} <= reported
    assert not {"lloyd_iter_ms", "lloyd_roofline", "init_centers_s"} & reported
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in ("pca_device_roofline", "eigh_s", "covariance_s"):
        assert entries[name]["workloads"] == [PCA_CELL]
    assert "workloads" not in entries["upload_gb_per_s"]
    assert entries["upload_gb_per_s"]["better"] == "higher"


def test_the_staged_configuration_is_the_plain_one_but_for_the_upload():
    plain = harness._load_json(BENCH, "configs", "pca_d512_k10.json")
    staged = harness._load_json(BENCH, "configs", "pca_d512_k10_staged.json")
    added = {"guarantees", "expect_upload"}
    assert set(staged) - set(plain) == added
    assert {k: v for k, v in staged.items() if k not in added | {"estimator"}} == {
        k: v for k, v in plain.items() if k != "estimator"
    }
    adapter, base = (harness._module("estimators", n) for n in ("pca_staged", "pca"))
    for name in ("REFERENCE", "make_data", "program_settings", "phase_work", "fit_work"):
        assert getattr(adapter, name) is getattr(base, name)


@pytest.mark.parametrize("attrs,breach", [
    # the cell on the chip since PR 31: 32 pieces of 256 MiB
    ({"bytes": 8_606_711_808, "shards": 1, "pieces": 32}, False),
    ({"bytes": 8_606_711_808, "shards": 1, "pieces": 9}, False),
    ({"bytes": 8_606_711_808, "shards": 1, "pieces": 8}, True),  # the mask counts
    # a program from before PR 31: one device_put, no count of pieces
    ({"bytes": 8_606_711_808, "shards": 1}, True),
    ({"bytes": 8_606_711_808}, True),
    # four shards of 2.15 GB in three pieces each; whole
    ({"bytes": 8_623_489_024, "shards": 4, "pieces": 3}, False),
    ({"bytes": 8_623_489_024, "shards": 4, "pieces": 1}, True),
    # a table under one piece goes whole; no span, nothing to hold
    ({"bytes": 1 << 30, "shards": 1, "pieces": 1}, False),
    ({}, False),
])
def test_upload_breach(attrs, breach):
    adapter = harness._module("estimators", "pca_staged")
    cfg = {"name": "c", "expect_upload": {"piece_bytes_max": 1 << 30}}
    assert bool(adapter.upload_breach(cfg, attrs)) is breach
    assert adapter.upload_breach({"name": "c"}, attrs) is None  # nothing expected
