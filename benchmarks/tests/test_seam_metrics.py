"""The five readers of the program's host-device seams and of its program
ledger (PR 35: ``upload_put_s``, ``upload_land_s``, ``host_gap_s``,
``program_ready_s``, ``programs_compiled``) on a hand-made ``Context``: what
each computes, nothing where the program says nothing (a program from before
PR 35), and that ``BENCHMARK.json`` names their five entries and keeps
every older one.

    python -m pytest benchmarks/tests -q        (CPU)
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402

NEW = {
    "upload_put_s": ("s", "program_span", "staging", "fit_s"),
    "upload_land_s": ("s", "program_span", "staging", "fit_s"),
    "host_gap_s": ("s", "program_span", "dispatch", "fit_s"),
    "program_ready_s": ("s", "program_counter", "compile", "setup_s"),
    "programs_compiled": ("count", "program_counter", "compile", "setup_s"),
}
KMEANS_PHASES = ["table_convert", "init_centers", "lloyd_loop"]
# one K-Means fit as the adapters hand it on: Timings.as_dict(), whole
FIT = {
    "table_convert": 0.40, "table_convert/host_copy": 0.01,
    "table_convert/upload": 0.38, "table_convert/upload/put": 0.02,
    "table_convert/upload/land": 0.30, "table_convert/upload/cast": 0.05,
    "init_centers": 0.50, "init_centers/rounds": 0.45,
    "init_centers/rounds/fetch": 0.40, "init_centers/kmeanspp_host": 0.04,
    "init_centers/kmeanspp_host/fetch": 0.035,
    "lloyd_loop": 1.20, "lloyd_loop/execute": 0.001, "lloyd_loop/fetch": 1.19,
}


def _ctx(phases_of_fits, phases=KMEANS_PHASES):
    fits = [
        {"index": i, "wall_s": 5.0, "result": {}, "info": {"phases": p}, "error": None}
        for i, p in enumerate(phases_of_fits)
    ]
    # a fit that raised has no summary and is not averaged over
    fits.append({"index": len(fits), "wall_s": 9.0, "result": None, "info": {},
                 "error": "RuntimeError: boom"})
    return harness.Context(run={"fits": fits}, cfg={"phases": phases})


def _read(metric, ctx):
    return harness._module("metrics", metric).read(ctx)


@pytest.mark.parametrize("metric,leaf", [("upload_put_s", "put"), ("upload_land_s", "land")])
def test_upload_leaves_are_the_means_of_their_spans(metric, leaf):
    path = "table_convert/upload/" + leaf
    ctx = _ctx([FIT, dict(FIT, **{path: FIT[path] + 0.1})])
    assert _read(metric, ctx) == pytest.approx(FIT[path] + 0.05)


def test_host_gap_is_the_phases_walls_less_what_the_host_waited_for():
    # init_centers 0.50 - (0.40 + 0.035) + lloyd_loop 1.20 - 1.19; the
    # upload's land is table_convert's and stays out
    assert _read("host_gap_s", _ctx([FIT])) == pytest.approx(0.065 + 0.01)
    slower = dict(FIT, init_centers=0.60)
    assert _read("host_gap_s", _ctx([FIT, slower])) == pytest.approx(0.075 + 0.05)


def test_host_gap_counts_a_land_below_a_phase_and_any_depth():
    pca = {
        "table_convert": 0.8, "table_convert/upload/land": 0.7,
        "covariance": 0.09, "covariance/fetch": 0.08, "covariance/stage/land": 0.004,
        "eigh": 0.02, "eigh/fetch": 0.015,
    }
    ctx = _ctx([pca], ["table_convert", "covariance", "eigh"])
    assert _read("host_gap_s", ctx) == pytest.approx(0.006 + 0.005)


PARENT_FIT = {k: v for k, v in FIT.items() if k.count("/") < 2 and "fetch" not in k}


@pytest.mark.parametrize("metric", ["upload_put_s", "upload_land_s", "host_gap_s"])
@pytest.mark.parametrize("fits", [[], [{}], [PARENT_FIT]])
def test_span_readers_find_nothing_in_a_program_without_the_leaves(metric, fits):
    assert "table_convert/upload" in PARENT_FIT and "lloyd_loop" in PARENT_FIT
    assert _read(metric, _ctx(fits)) is None


@pytest.mark.parametrize("metric,series", [
    ("program_ready_s", "oap_program_ready_seconds_total"),
    ("programs_compiled", "oap_programs_compiled_total"),
])
def test_counter_readers_sum_the_programs_series(metric, series, monkeypatch):
    sys.path.insert(0, harness.ROOT)
    from oap_mllib_tpu import telemetry

    monkeypatch.setattr(
        telemetry, "snapshot",
        lambda: {series: {"": 3.5}, "oap_xla_compiles_total": {"": 40.0}},
    )
    assert _read(metric, harness.Context()) == 3.5
    monkeypatch.setattr(telemetry, "snapshot", lambda: {series: {"": 0.0}})
    assert _read(metric, harness.Context()) == 0.0
    # a program from before PR 35 has no such series
    monkeypatch.setattr(
        telemetry, "snapshot", lambda: {"oap_xla_compiles_total": {"": 40.0}}
    )
    assert _read(metric, harness.Context()) is None


def test_the_program_feeds_both_counters_from_its_first_import():
    sys.path.insert(0, harness.ROOT)
    from oap_mllib_tpu.utils import progcache  # noqa: F401 - installs the listener

    assert _read("program_ready_s", harness.Context()) >= 0.0
    assert _read("programs_compiled", harness.Context()) >= 0.0


def test_benchmark_json_names_the_five_entries_with_their_fields():
    bench = harness._load_json(harness.ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert len(by_name) == len(bench["per_layer"])
    for name, (unit, source, layer, moves) in NEW.items():
        assert by_name[name] == {"name": name, "unit": unit, "better": "lower",
                                 "source": source, "layer": layer, "moves": moves}
        assert callable(harness._module("metrics", name).read)
    # no `workloads` key: every cell reports all five, wherever they stand
    for cell in bench["workloads"]:
        reported = {m["name"] for m in harness.metrics_of(bench, cell, "per_layer")}
        assert set(NEW) <= reported, cell["name"]


def _kept(older, newer):
    """Nothing of ``older`` was edited, removed or reordered in ``newer``: a
    list may have gained items anywhere (a later PR's entries, a cell appended
    to a metric's ``workloads``), a dict keeps its keys, a value is equal."""
    if isinstance(older, list) and isinstance(newer, list):
        rest = iter(newer)
        return all(any(_kept(item, other) for other in rest) for item in older)
    if isinstance(older, dict) and isinstance(newer, dict):
        return older.keys() == newer.keys() and all(
            _kept(v, newer[k]) for k, v in older.items()
        )
    return older == newer


def test_kept_tells_an_addition_from_a_change():
    old = {"run_seconds": 30, "per_layer": [{"name": "a"}, {"name": "b", "workloads": ["x"]}]}
    assert _kept(old, old)
    assert _kept(old, {"run_seconds": 30, "per_layer": [
        {"name": "new"}, {"name": "a"}, {"name": "b", "workloads": ["x", "y"]}, {"name": "c"}]})
    for worse in (
        {"run_seconds": 10, "per_layer": old["per_layer"]},
        {"run_seconds": 30, "per_layer": old["per_layer"][::-1]},
        {"run_seconds": 30, "per_layer": old["per_layer"][:1]},
        {"run_seconds": 30, "per_layer": [{"name": "a", "unit": "s"}, old["per_layer"][1]]},
        {"run_seconds": 30, "per_layer": [{"name": "a"}, {"name": "b", "workloads": ["y"]}]},
    ):
        assert not _kept(old, worse), worse


def test_every_older_entry_is_as_it_was():
    """Against the parent commit's file, where git has one to show: whatever
    this PR and later ones add, and wherever, nothing that was there changed."""
    try:
        old = subprocess.run(
            ["git", "show", "HEAD:BENCHMARK.json"], cwd=harness.ROOT,
            capture_output=True, check=True, timeout=30,
        ).stdout.decode()
    except (OSError, subprocess.SubprocessError):
        pytest.skip("no git history here to compare with")
    assert _kept(json.loads(old), harness._load_json(harness.ROOT, "BENCHMARK.json"))
