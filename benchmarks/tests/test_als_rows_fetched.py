"""The reader ``als_rows_fetched_pct`` of ``als_implicit_r100_kddcup11.
fit_loop``: that ``BENCHMARK.json`` lists it for that cell alone, at the
end of ``per_layer``, and what it reads from made-up spans.

    python -m pytest benchmarks/tests -q        (CPU)
"""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402

CELL = "als_implicit_r100_kddcup11.fit_loop"
METRIC = "als_rows_fetched_pct"
SPAN = {"iterations": 5, "route": "dst-blocked", "dst_block": 16384,
        "blocks_user": 31, "blocks_item": 39, "r_pad": 128, "gather": "dma",
        "rows_fetched": 1_264_001_380, "slots_walked": 1_660_944_384,
        "moments": "pallas", "solve": "pallas"}


def _fit(span):
    info = {"phases": {"als_iterations": 30.0}, "iterations": 5}
    if span is not None:
        info["iterations_span"] = dict(span)
    return {"index": 0, "wall_s": 40.0, "result": {}, "error": None, "info": info}


def _read(fits):
    fits = list(fits) + [{"index": 9, "wall_s": 9.0, "result": None, "info": {},
                          "error": "RuntimeError: boom"}]
    ctx = harness.Context(run={"fits": fits, "elapsed_s": 40.0})
    return harness._module("metrics", METRIC).read(ctx)


def test_benchmark_json_lists_the_metric_for_the_rank_100_cell():
    bench, cell, _, _ = harness.load_cell(CELL, False)
    entry = bench["per_layer"][-1]
    assert entry == {"name": METRIC, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "kernels",
                     "moves": "fit_s", "workloads": [CELL]}
    assert METRIC in [m["name"] for m in harness.metrics_of(bench, cell, "per_layer")]
    other, _, _, _ = harness.load_cell("als_implicit_r10_kddcup11.fit_loop", False)
    rank10 = {c["name"]: c for c in other["workloads"]}["als_implicit_r10_kddcup11.fit_loop"]
    assert METRIC not in [m["name"] for m in harness.metrics_of(bench, rank10, "per_layer")]


def test_it_reads_the_rows_fetched_over_the_slots_walked():
    half = dict(SPAN, rows_fetched=100, slots_walked=400)
    assert _read([_fit(SPAN)]) == pytest.approx(
        100.0 * 1_264_001_380 / 1_660_944_384)
    # over the window's fits, not a mean of their shares
    assert _read([_fit(SPAN), _fit(half)]) == pytest.approx(
        100.0 * (1_264_001_380 + 100) / (1_660_944_384 + 400))


@pytest.mark.parametrize("span", [
    None,  # a fit without the span's attributes
    {k: v for k, v in SPAN.items() if k not in ("rows_fetched", "slots_walked")},
    dict(SPAN, slots_walked=0),
])
def test_it_reads_nothing_where_the_program_counts_nothing(span):
    assert _read([_fit(span)]) is None
    assert _read([]) is None
