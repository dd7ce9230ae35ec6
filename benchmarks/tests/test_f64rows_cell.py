"""The cell ``kmeans_d256_k1000_f64rows.fit_loop`` (PR 33): its rehearsal
through ``run.py``, its two readers on made-up spans, that ``BENCHMARK.json``
names every reader and file of it, and the controls that must come out not
correct.

    python -m pytest benchmarks/tests -q        (CPU)
"""

import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402

CELL = "kmeans_d256_k1000_f64rows.fit_loop"
STAGED = {"bytes": 3_216_777_216, "shards": 1, "pieces": 48, "valid_rows": 3_125_000,
          "padded_rows": 4_194_304, "cast_bytes": 3_200_000_000, "cast_threads": 12,
          "copied_bytes": 0}


def _ctx(stagings, cfg=None, rows=0):
    fits = [
        {"index": i, "wall_s": 1.0, "result": {}, "error": None,
         "info": {"phases": {"table_convert/upload": 0.4}, **({"staging": s} if s is not None else {})}}
        for i, s in enumerate(stagings)
    ]
    fits.append({"index": len(fits), "wall_s": 9.0, "result": None, "info": {},
                 "error": "RuntimeError: boom"})
    return harness.Context(run={"fits": fits}, cfg=cfg or {}, rows=rows)


def _read(metric, ctx):
    return harness._module("metrics", metric).read(ctx)


def test_stage_cast_wait_s_is_the_mean_of_the_spans_attribute():
    ctx = _ctx([dict(STAGED, cast_wait_s=0.2), dict(STAGED, cast_wait_s=0.3)])
    assert _read("stage_cast_wait_s", ctx) == pytest.approx(0.25)
    # the caller's array as it is: nothing cast, nothing waited for
    assert _read("stage_cast_wait_s", _ctx([dict(STAGED, cast_wait_s=0)])) == 0.0


def test_pad_rows_pct_is_the_share_of_padding():
    assert _read("pad_rows_pct", _ctx([STAGED, STAGED])) == pytest.approx(
        100.0 * (4_194_304 - 3_125_000) / 4_194_304
    )
    assert round(_read("pad_rows_pct", _ctx([STAGED])), 2) == 25.49
    on_bucket = dict(STAGED, valid_rows=2_097_152, padded_rows=2_097_152)
    assert _read("pad_rows_pct", _ctx([on_bucket])) == 0.0


@pytest.mark.parametrize("stagings", [
    [], [None], [{}],
    # a program from before PR 33: an upload span with three attributes
    [{"bytes": 4_311_744_512, "shards": 1, "pieces": 16, "copied_bytes": 4_294_967_296}],
])
@pytest.mark.parametrize("metric", ["stage_cast_wait_s", "pad_rows_pct"])
def test_readers_find_nothing_where_the_program_says_nothing(metric, stagings):
    assert _read(metric, _ctx(stagings)) is None


def test_upload_gb_per_s_reckons_what_this_route_sends():
    _, cell, cfg, _ = harness.load_cell(CELL)
    rows = cfg["rows_per_chip"] * cell["chips"]
    assert rows * (cfg["d"] + 1) * 4 == 3_212_500_000
    assert _read("upload_gb_per_s", _ctx([STAGED, STAGED], cfg, rows)) == (
        pytest.approx(3_212_500_000 / 0.4 / 1e9)
    )


def test_benchmark_json_names_the_cell_and_every_file_of_it():
    bench, cell, cfg, traffic = harness.load_cell(CELL)
    assert CELL in {w["name"] for w in bench["workloads"]}
    assert cell == {"name": CELL, "config": "kmeans_d256_k1000_f64rows",
                    "traffic": "fit_loop", "chips": 1, "why": cell["why"]}
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    assert entry["reduced"] == [] == cfg["reduced"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["file"] == "benchmarks/configs/kmeans_d256_k1000_f64rows.json"
    assert cfg["rows_per_chip"] == 3_125_000 and cfg["input_dtype"] == "float64"
    for kind, name in (("estimators", cfg["estimator"]),
                       ("drivers", traffic["driver"]),
                       ("reference", "kmeans_f64rows_ref")):
        assert os.path.isfile(os.path.join(BENCH, kind, name + ".py")), name
    adapter = harness._module("estimators", cfg["estimator"])
    assert adapter.REFERENCE == "kmeans_f64rows_ref"
    reported = [m["name"] for m in harness.metrics_of(bench, cell, "per_layer")]
    assert reported[-2:] == ["stage_cast_wait_s", "pad_rows_pct"]
    assert {"host_copy_s", "upload_s", "upload_gb_per_s", "table_convert_s",
            "estimator_other_s", "window_compiles", "device_idle_pct",
            "peak_hbm_gb", "fit_mfu_pct"} <= set(reported)
    assert not {"lloyd_iter_ms", "lloyd_roofline", "init_centers_s"} & set(reported)
    for name in reported:
        assert callable(harness._module("metrics", name).read)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, source in (("stage_cast_wait_s", "program_span"),
                         ("pad_rows_pct", "program_counter")):
        assert entries[name] == {
            "name": name, "unit": entries[name]["unit"], "better": "lower",
            "source": source, "layer": "staging", "moves": "fit_s",
            "workloads": [CELL],
        }
    assert [m["name"] for m in harness.metrics_of(bench, cell, "end_to_end")] == [
        "fit_s", "setup_s"
    ]


def test_the_configuration_is_the_siblings_but_for_the_table_handed_over():
    sibling = harness._load_json(BENCH, "configs", "kmeans_d256_k1000.json")
    cfg = harness._load_json(BENCH, "configs", "kmeans_d256_k1000_f64rows.json")
    assert set(cfg) - set(sibling) == {"guarantees", "input_dtype", "input_layout"}
    differs = {k for k in sibling if cfg[k] != sibling[k]}
    assert differs == {"name", "estimator", "source", "deployment", "rows_per_chip",
                       "reduced", "assumed", "rehearse"}
    assert cfg["rehearse"]["rows_per_chip"] == 8000  # off its bucket too
    assert cfg["rehearse"]["limits"] == sibling["rehearse"]["limits"]
    adapter, base = (harness._module("estimators", n) for n in ("kmeans_f64rows", "kmeans"))
    for name in ("program_settings", "phase_work", "fit_work"):
        assert getattr(adapter, name) is getattr(base, name)


def test_make_data_is_float64_on_no_bucket_and_follows_the_seed():
    _, cell, cfg, _ = harness.load_cell(CELL, rehearse=True)
    adapter = harness._module("estimators", cfg["estimator"])
    x = adapter.make_data(cfg, 8000, 2_147_500_123)
    assert x.shape == (8000, 32) and x.dtype == np.float64 and x.flags.c_contiguous
    assert x.tobytes() == adapter.make_data(cfg, 8000, 2_147_500_123).tobytes()
    assert x.tobytes() != adapter.make_data(cfg, 8000, 2_147_500_124).tobytes()
    assert (x.astype(np.float32) != x).mean() > 0.99


@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal_runs_the_cell_and_names_what_it_would_report(trace, capsys):
    line, code = harness.drive(CELL, 2_147_500_123, 0.5, trace, rehearse=True,
                               log=open(os.devnull, "w"))
    assert code == 1 and line["rehearsal"] and line["metrics"] == {}
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["checks"]["count_gap"] == {"value": 0.0, "limit": 0.0}
    want = ["fit_s", "setup_s"] if not trace else None
    if want:
        assert line["would_report"] == want
    else:
        assert line["would_report"][-2:] == ["stage_cast_wait_s", "pad_rows_pct"]
    json.dumps(line)


class TestControls:
    @pytest.fixture(scope="class")
    def made(self):
        _, cell, cfg, _ = harness.load_cell(CELL, rehearse=True)
        adapter = harness._module("estimators", cfg["estimator"])
        ref = harness._module("reference", adapter.REFERENCE)
        return cfg, ref, adapter.make_data(cfg, 8000, 5)

    @staticmethod
    def _over(numbers, limits):
        return {n for n, v in numbers.items() if not v <= limits[n]}

    def test_the_reference_in_the_programs_place_is_correct(self, made):
        cfg, ref, x = made
        sound = ref.fit_plain(x, cfg, 6, "highest")
        assert not self._over(ref.judge(x, cfg, [sound], 5), cfg["limits"])

    def test_bf16_stored_rows_are_not(self, made):
        cfg, ref, x = made
        stored = ref.fit_plain(x, cfg, 6, "bfloat16")
        assert self._over(ref.judge(x, cfg, [stored], 5), cfg["limits"])

    def test_one_pad_row_counted_is_not(self, made):
        cfg, ref, x = made
        result = ref.fit_plain(x, cfg, 6, "highest")
        result["sizes"] = result["sizes"] + np.eye(1, cfg["k"], dtype=np.int64)[0]
        # at 8,000 rows one row is also over size_gap (6e-5 of 3e-5); at the
        # cell's 3,125,000 it is 1.6e-7, and count_gap alone says so
        assert "count_gap" in self._over(ref.judge(x, cfg, [result], 5), cfg["limits"])

    def test_a_lloyd_loop_that_never_ran_is_not(self, made):
        cfg, ref, x = made
        for fault, override in cfg["control_faults"].items():
            result = ref.fit_plain(x, dict(cfg, **override), 6, "highest")
            assert self._over(ref.judge(x, cfg, [result], 5), cfg["limits"]), fault
