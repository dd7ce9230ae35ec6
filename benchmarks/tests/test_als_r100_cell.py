"""The cell ``als_implicit_r100_kddcup11.fit_loop``: that ``BENCHMARK.json``
names it and every file of it, that its configuration is the rank-10 one at
rank 100, its three readers on made-up traces and spans, its rehearsal
through ``run.py``, the adapter's refusal of a program that cannot run it,
and the controls that must come out not correct.

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

CELL = "als_implicit_r100_kddcup11.fit_loop"
RANK10 = "als_implicit_r10_kddcup11"
NEW = ["als_moments_roofline", "als_solve_roofline", "als_dst_blocks"]
SPAN = {"iterations": 5, "route": "dst-blocked", "dst_block": 16384,
        "blocks_user": 31, "blocks_item": 39, "r_pad": 128, "gather": "rows",
        "moments": "pallas", "solve": "pallas"}
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _fit(span=SPAN, iterations=5):
    info = {"phases": {"als_iterations": 30.0}, "iterations": iterations}
    if span is not None:
        info["iterations_span"] = dict(span)
    return {"index": 0, "wall_s": 40.0, "result": {}, "error": None, "info": info}


def _ctx(fits, **kw):
    fits = list(fits) + [{"index": 9, "wall_s": 9.0, "result": None, "info": {},
                          "error": "RuntimeError: boom"}]
    return harness.Context(run={"fits": fits, "elapsed_s": 40.0}, **kw)


def _read(metric, ctx):
    return harness._module("metrics", metric).read(ctx)


def _cell(rehearse=False):
    bench, cell, cfg, traffic = harness.load_cell(CELL, rehearse)
    adapter = harness._module("estimators", cfg["estimator"])
    return bench, cell, cfg, traffic, adapter


def test_benchmark_json_names_the_cell_and_every_file_of_it():
    bench, cell, cfg, traffic, adapter = _cell()
    assert cell == {"name": CELL, "config": "als_implicit_r100_kddcup11",
                    "traffic": "fit_loop", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    assert entry["reduced"] == ["users", "ratings"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["file"] == "benchmarks/configs/als_implicit_r100_kddcup11.json"
    for kind, name in (("estimators", cfg["estimator"]), ("drivers", traffic["driver"]),
                       ("reference", adapter.REFERENCE)):
        assert os.path.isfile(os.path.join(BENCH, kind, name + ".py")), name
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert entries[name]["workloads"] == [CELL] and entries[name]["moves"] == "fit_s"
        assert callable(harness._module("metrics", name).read)
    assert [m["name"] for m in bench["per_layer"][-3:]] == NEW
    reported = [m["name"] for m in harness.metrics_of(bench, cell, "per_layer")]
    # the readers without a list read the new cell through the adapter
    assert set(NEW) | {"peak_hbm_gb", "fit_mfu_pct", "device_idle_pct",
                       "table_convert_s"} <= set(reported)
    assert "als_update_roofline" not in reported
    assert [m["name"] for m in harness.metrics_of(bench, cell, "end_to_end")] == [
        "fit_s", "setup_s"]


def test_the_configuration_is_the_rank_10_one_at_rank_100():
    _, _, cfg, _, _ = _cell()
    with open(os.path.join(BENCH, "configs", RANK10 + ".json")) as f:
        base = json.load(f)
    changed = {k for k in set(cfg) | set(base) if cfg.get(k) != base.get(k)}
    assert changed == {"name", "estimator", "source", "deployment", "rank",
                       "assumed", "expect_kernel", "limits", "limits_why",
                       "rehearse"}
    assert cfg["rank"] == 100 and cfg["expect_kernel"] == "dst_blocked"
    assert {k: v for k, v in cfg["assumed"].items() if k != "rank"} == base["assumed"]
    assert "rank=100" in cfg["deployment"] and "rank=10," in base["deployment"]
    assert cfg["deployment"].split("rank=100, maxIter=5)")[1].split(
        ", on the KDD")[1] == base["deployment"].split(", on the KDD")[1]
    assert set(cfg["limits"]) == set(base["limits"])
    rehearse = dict(cfg["rehearse"])
    assert rehearse.pop("program_config") == dict(
        base["program_config"], scale_policy="pin:dst-blocked")
    rehearse.pop("program_config_why")
    rehearse.pop("limits_why")
    assert set(rehearse) == set(base["rehearse"])


def test_the_adapter_prices_the_two_kernels_required_work():
    _, _, cfg, _, adapter = _cell()
    rows, r, n = cfg["rows_per_chip"], 100, cfg["users"] + cfg["items"]
    m = adapter.moments_work(cfg, rows, {"iterations": 5})
    assert m == {"flops": pytest.approx(10 * rows * (r * (r + 1) + 2 * r)),
                 "bytes": pytest.approx(10 * rows * (12 + 4 * r))}
    s = adapter.solve_work(cfg, rows, {"iterations": 5})
    assert s == {"flops": pytest.approx(5 * n * (r ** 3 / 3 + 2 * r * r)),
                 "bytes": pytest.approx(5 * n * 4 * (r * r + 2 * r))}
    # the bytes bind both kernels at rank 100
    assert m["bytes"] / 819e9 > m["flops"] / 197e12
    assert s["bytes"] / 819e9 > s["flops"] / 197e12
    assert adapter.make_data is harness._module("estimators", "als_implicit").make_data


@pytest.mark.parametrize("metric, kernel, work", [
    ("als_moments_roofline", "als_dst_moments", "moments_work"),
    ("als_solve_roofline", "als_block_cholesky", "solve_work"),
])
def test_a_roofline_is_required_work_over_its_kernels_time(metric, kernel, work):
    from lib import trace_reduce

    _, cell, cfg, _, adapter = _cell()
    rows = cfg["rows_per_chip"]
    trace = trace_reduce.Trace(
        {"/device:TPU:0": [(1.0, 2.0, f"%{kernel}.3 = f32[] custom-call()"),
                           (1.5, 2.5, f"{kernel}.4"), (3.0, 3.5, "gather.1"),
                           (9.0, 10.0, f"{kernel}.3")]},
        [(0.5, 4.5, "als_iterations")], window=(0.0, 5.0),
    )
    ctx = _ctx([_fit(), _fit()], cfg=cfg, cell=cell, rows=rows, adapter=adapter,
               trace=trace, peaks=PEAKS)
    least = 2 * ctx.least_time_s(getattr(adapter, work)(cfg, rows, {"iterations": 5}))[0]
    # the kernel's events inside the window, overlaps once: 1.5 s
    assert _read(metric, ctx) == pytest.approx(100.0 * least / 1.5)


@pytest.mark.parametrize("metric", NEW)
@pytest.mark.parametrize("case", ["no_trace", "no_kernel", "no_span", "no_fits"])
def test_readers_find_nothing_where_the_program_says_nothing(metric, case):
    from lib import trace_reduce

    _, cell, cfg, _, adapter = _cell()
    trace = trace_reduce.Trace({"/device:TPU:0": [(1.0, 2.0, "fusion.1")]},
                               [(0.5, 4.5, "als_iterations")])
    fits, peaks = [_fit(span=None)], PEAKS
    if case == "no_trace":
        trace, peaks = None, None
    elif case == "no_span":  # a program without the route: the rank-10 adapter's info
        fits = [_fit(span={"iterations": 5, "rank": 100})]
    elif case == "no_fits":
        fits = []
    ctx = _ctx(fits, cfg=cfg, cell=cell, rows=cfg["rows_per_chip"], adapter=adapter,
               trace=trace, peaks=peaks)
    assert _read(metric, ctx) is None


def test_als_dst_blocks_reads_the_span():
    _, cell, cfg, _, adapter = _cell()
    other = dict(SPAN, blocks_user=62, blocks_item=78)
    ctx = _ctx([_fit(), _fit(span=other)], cfg=cfg, cell=cell,
               rows=cfg["rows_per_chip"], adapter=adapter, trace=None, peaks=None)
    assert _read("als_dst_blocks", ctx) == pytest.approx((70 + 140) / 2)


class TestTheAdapterRefuses:
    """A program that cannot run the configuration ends the run at its
    first fit, with no result line: one that raises, one that runs
    another kernel."""

    @pytest.fixture(scope="class")
    def made(self):
        _, _, cfg, _, adapter = _cell(rehearse=True)
        small = dict(cfg, users=60, items=80, rows_per_chip=3000, max_iter=1)
        return small, adapter, adapter.make_data(small, 3000, 9)

    def test_another_kernel(self, made):
        from oap_mllib_tpu.config import set_config

        cfg, adapter, x = made
        set_config(**dict(adapter.program_settings(cfg), device="auto"))
        with pytest.raises(SystemExit) as e:
            adapter.fit(dict(cfg, expect_kernel="grouped"), x, 3)
        assert e.value.code == adapter.EXIT_CANNOT_RUN == 5
        result, info = adapter.fit(cfg, x, 3)
        assert info["kernel"] == "dst_blocked"
        assert info["iterations_span"]["route"] == "dst-blocked"
        assert result["item_factors"].shape == (80, 100)

    def test_a_fit_that_raises(self, made):
        from oap_mllib_tpu.config import set_config

        cfg, adapter, x = made
        set_config(**dict(adapter.program_settings(cfg), device="auto"))
        with pytest.raises(SystemExit) as e:
            adapter.fit(dict(cfg, items=10), x, 3)  # item ids out of range
        assert e.value.code == adapter.EXIT_CANNOT_RUN


@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal_runs_the_cell_and_names_what_it_would_report(trace):
    line, code = harness.drive(CELL, 2_147_500_123, 0.5, trace, rehearse=True,
                               log=open(os.devnull, "w"))
    assert code == 1 and line["rehearsal"] and line["metrics"] == {}
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    if trace:
        assert set(NEW) <= set(line["would_report"])
    else:
        assert line["would_report"] == ["fit_s", "setup_s"]
    json.dumps(line)


class TestControls:
    @pytest.fixture(scope="class")
    def made(self):
        _, _, cfg, _, adapter = _cell(rehearse=True)
        ref = harness._module("reference", adapter.REFERENCE)
        return cfg, ref, adapter.make_data(cfg, cfg["rows_per_chip"], 5)

    @staticmethod
    def _over(numbers, limits):
        return {n for n, v in numbers.items() if not v <= limits[n]}

    def test_the_reference_imports_nothing_of_the_program(self, made):
        _, ref, _ = made
        source = open(ref.__file__).read()
        assert "import oap_mllib_tpu" not in source and "from oap_mllib_tpu" not in source

    def test_the_reference_in_the_programs_place_is_correct(self, made):
        cfg, ref, x = made
        sound = ref.fit_plain(x, cfg, 6, "highest")
        numbers = ref.judge(x, cfg, [sound], 5)
        assert not self._over(numbers, cfg["limits"]), numbers

    def test_bf16_stored_edges_and_factors_are_not(self, made):
        cfg, ref, x = made
        stored = ref.fit_plain(x, cfg, 6, "bfloat16")
        assert self._over(ref.judge(x, cfg, [stored], 5), cfg["limits"])

    def test_each_planted_fault_is_not(self, made):
        cfg, ref, x = made
        assert set(cfg["control_faults"]) == {"no_iteration", "zero_scores_preferred"}
        for fault, override in cfg["control_faults"].items():
            result = ref.fit_plain(x, dict(cfg, **override), 6, "highest")
            assert self._over(ref.judge(x, cfg, [result], 5), cfg["limits"]), fault

    def test_the_sample_holds_the_most_rated_items(self, made):
        cfg, ref, x = made
        side = ref._sample(x, cfg, 5)
        kept = np.concatenate([np.asarray(d).reshape(-1) for _, _, d in side.classes])
        kept = set(kept[kept < cfg["items"]].tolist())
        top = np.argsort(-np.bincount(x[1], minlength=cfg["items"]), kind="stable")
        assert set(top[:ref.TOP_ITEMS].tolist()) <= kept
        assert len(kept) == min(ref.SAMPLE_ITEMS, int((np.bincount(x[1]) > 0).sum()))

    def test_a_malformed_answer_is_not(self, made):
        cfg, ref, x = made
        result = ref.fit_plain(x, cfg, 6, "highest")
        result["item_factors"] = result["item_factors"][:-1]
        assert ref.judge(x, cfg, [result], 5)["shape_gap"] == 1.0
