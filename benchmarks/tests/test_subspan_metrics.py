"""The four readers of the program's sub-spans (``host_copy_s``, ``upload_s``,
``init_rounds_s``, ``init_host_s``) on a hand-made ``Context``: each returns
the mean of its span over the fits that recorded it, and nothing where no fit
did (a program without the span, as the parent of the PR that added them).

    python -m pytest benchmarks/tests -q        (CPU)
"""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402

READS = {
    "host_copy_s": "table_convert/host_copy",
    "upload_s": "table_convert/upload",
    "init_rounds_s": "init_centers/rounds",
    "init_host_s": "init_centers/kmeanspp_host",
}


def _ctx(phases_of_fits):
    fits = [
        {"index": i, "wall_s": 5.0, "result": {}, "info": {"phases": p}, "error": None}
        for i, p in enumerate(phases_of_fits)
    ]
    # a fit that raised has no summary and is not averaged over
    fits.append({"index": len(fits), "wall_s": 9.0, "result": None, "info": {},
                 "error": "RuntimeError: boom"})
    return harness.Context(run={"fits": fits})


@pytest.mark.parametrize("metric", sorted(READS))
def test_reader_returns_the_sub_span_mean(metric):
    path = READS[metric]
    ctx = _ctx([
        {"table_convert": 2.0, "init_centers": 1.5, path: 0.25},
        {"table_convert": 2.2, "init_centers": 1.7, path: 0.75},
    ])
    assert harness._module("metrics", metric).read(ctx) == pytest.approx(0.5)


@pytest.mark.parametrize("metric", sorted(READS))
def test_reader_finds_nothing_without_the_span(metric):
    ctx = _ctx([{"table_convert": 2.0, "init_centers": 1.5, "lloyd_loop": 1.5}])
    assert harness._module("metrics", metric).read(ctx) is None


def test_benchmark_json_names_the_readers():
    bench = harness._load_json(harness.ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    kmeans = ["kmeans_d256_k1000.fit_loop"]
    for metric in READS:
        assert entries[metric]["source"] == "program_span"
        assert entries[metric]["moves"] == "fit_s"
    assert "workloads" not in entries["host_copy_s"]
    assert "workloads" not in entries["upload_s"]
    assert entries["init_rounds_s"]["workloads"] == kmeans
    assert entries["init_host_s"]["workloads"] == kmeans
