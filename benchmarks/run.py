#!/usr/bin/env python3
"""The benchmark's entry: one cell, one run, one line of JSON.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name from ``BENCHMARK.json``: its
configuration (``configs/<config>.json``), its traffic mix
(``traffic/<traffic>.json``), the driver the mix names
(``drivers/<driver>.py``), the estimator adapter the configuration names
(``estimators/<estimator>.py``), the plain reference the adapter names
(``reference/<name>.py``) and one reader per per-layer metric
(``metrics/<metric>.py``).  See ``README.md``.

``--rehearse 1`` drives the same control flow at the configuration's tiny
``rehearse`` sizes on whatever backend is there.  It prints no metric at all
(a number from a CPU never stands under a device metric's name), says
``"rehearsal": true`` and exits 1.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WINDOW_MARK = "bench_window"


def _load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _module(kind, name):
    """``benchmarks/<kind>/<name>.py`` as a module."""
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    return importlib.import_module(f"{kind}.{name}")


def load_cell(workload, rehearse=False):
    """The cell's entries and files.  A cell that BENCHMARK.json does not
    hold is looked up in ``pending_cells.json`` (cells with their files in
    place, not admitted yet), whose entries then count as the benchmark's."""
    bench = _load_json(ROOT, "BENCHMARK.json")
    if workload not in {w["name"] for w in bench["workloads"]}:
        pending = _load_json(HERE, "pending_cells.json")
        for section in ("configs", "workloads", "per_layer"):
            bench[section] = bench[section] + pending[section]
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json "
                         "or benchmarks/pending_cells.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = _load_json(ROOT, entry["file"])
    if rehearse:
        cfg = dict(cfg, **cfg["rehearse"])
    traffic = _load_json(HERE, "traffic", cell["traffic"] + ".json")
    return bench, cell, cfg, traffic


def metrics_of(bench, cell, section):
    return [
        m for m in bench[section]
        if "workloads" not in m or cell["name"] in m["workloads"]
    ]


class Context:
    """What a per-layer metric's reader may look at."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    @property
    def good_fits(self):
        return [f for f in self.run["fits"] if f["result"] is not None]

    def phase_mean_s(self, phase):
        """Mean over the window's fits of one phase wall of
        ``summary.timings``; None where no fit recorded the phase."""
        walls = [
            f["info"]["phases"][phase] for f in self.good_fits
            if phase in f["info"].get("phases", {})
        ]
        return sum(walls) / len(walls) if walls else None

    def least_time_s(self, work):
        """(least seconds the chips could take for that work, the bound
        that binds)."""
        chips = self.cell["chips"]
        t_flops = work["flops"] / (self.peaks["flops_per_s"] * chips)
        t_bytes = work["bytes"] / (self.peaks["hbm_bytes_per_s"] * chips)
        return max(t_flops, t_bytes), ("compute" if t_flops >= t_bytes else "memory")


def fit_failed(fit, cfg, on_chip):
    """A fit that raised, degraded, retried, fell back or (on the chip) ran
    another kernel than the configuration promises counts as failed."""
    if fit["result"] is None:
        return True
    info = fit["info"]
    res = info.get("resilience", {})
    bad = (
        not info.get("accelerated")
        or any(res.get(k) for k in ("degradations", "retries", "faults"))
    )
    if on_chip and cfg.get("expect_kernel"):
        bad = bad or info.get("kernel") != cfg["expect_kernel"]
    return bool(bad)


def judge(adapter, cfg, x, run, seed):
    """{name: {"value", "limit"}} of every number compared.  A fit that
    raised is a malformed answer; a number without a limit cannot pass."""
    ref = _module("reference", adapter.REFERENCE)
    results = [f["result"] for f in run["fits"] if f["result"] is not None]
    numbers = ref.judge(x, cfg, results, seed) if results else {}
    if len(results) != len(run["fits"]) or not results:
        numbers["shape_gap"] = 1.0
    limits = cfg["limits"]
    return {
        name: {"value": float(v), "limit": limits.get(name)}
        for name, v in numbers.items()
    }


def drive(workload, seed, seconds, trace, rehearse=False, log=sys.stderr,
          describe_to=None):
    """One run of one cell; returns (result line as a dict, exit code)."""
    bench, cell, cfg, traffic = load_cell(workload, rehearse)

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    on_chip = device["platform"] == "tpu"
    if not rehearse:
        if not on_chip:
            print(f"run.py: no TPU: JAX found {device}; nothing was run", file=log)
            return None, 2
        if device["count"] != cell["chips"]:
            print(f"run.py: {workload} needs {cell['chips']} chip(s), JAX found "
                  f"{device['count']}", file=log)
            return None, 2
    peaks = _load_json(HERE, "peaks.json").get(device["kind"])
    if peaks is None and not rehearse:
        print(f"run.py: device_kind {device['kind']!r} is not in peaks.json", file=log)
        return None, 2

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from oap_mllib_tpu.config import set_config
    from oap_mllib_tpu.utils import profiling, progcache

    adapter = _module("estimators", cfg["estimator"])
    driver = _module("drivers", traffic["driver"])
    settings = adapter.program_settings(cfg)
    if rehearse and not on_chip:
        settings["device"] = "auto"
    set_config(seed=int(seed) % driver.SEED_MODULUS, **settings)
    cache_dir = progcache.use_checkout_cache(os.path.join(ROOT, ".jax_cache"))

    rows = cfg["rows_per_chip"] * cell["chips"]
    x = adapter.make_data(cfg, rows, seed)
    t_data = time.perf_counter()
    # one whole fit loads every program of the cell's shapes (max_iter is a
    # static argument of the Lloyd program: a shorter fit is another program)
    warm = driver.run(adapter, cfg, traffic, x, seed, 0.0)
    del warm["fits"][0]["result"]
    gc.collect()
    compiles_setup = progcache.xla_compile_count()
    compile_secs_setup = progcache.xla_compile_secs()
    setup_s = time.perf_counter() - T_START
    print(f"run.py: {workload} on {device}; cache {cache_dir}; data "
          f"{t_data - T_START:.2f}s, warm-up fit "
          f"{time.perf_counter() - t_data:.2f}s, {compiles_setup} programs in "
          f"{compile_secs_setup:.2f}s", file=log)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        if trace:
            with profiling.trace(trace_dir):
                with jax.profiler.TraceAnnotation(WINDOW_MARK):
                    run = driver.run(adapter, cfg, traffic, x, seed, seconds)
        else:
            run = driver.run(adapter, cfg, traffic, x, seed, seconds)
        window_compiles = progcache.xla_compile_count() - compiles_setup
        peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices()
        )
        device["memory_peak_bytes"] = int(peak)
        tr = None
        if trace:
            from lib import trace_reduce

            tr = trace_reduce.load(trace_dir, cfg["phases"], WINDOW_MARK)
            if describe_to:
                with open(describe_to, "w") as f:
                    f.write(trace_reduce.describe(trace_dir))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    gc.collect()

    fits = run["fits"]
    failed = sum(fit_failed(f, cfg, on_chip) for f in fits)
    for f in fits[:8]:
        print(f"run.py: fit {f['index']}: {f['wall_s']:.3f}s "
              f"{ {k: round(v, 3) for k, v in f['info'].get('phases', {}).items()} } "
              f"kernel={f['info'].get('kernel')} {f['error'] or ''}", file=log)

    ctx = Context(
        cell=cell, cfg=cfg, rows=rows, adapter=adapter, run=run, trace=tr,
        peaks=peaks, device=device, window_compiles=window_compiles,
    )
    metrics = {}
    if not rehearse:
        if trace:
            for m in metrics_of(bench, cell, "per_layer"):
                value = _module("metrics", m["name"]).read(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        else:
            e2e = {"fit_s": run["elapsed_s"] / max(len(fits), 1), "setup_s": setup_s}
            for m in metrics_of(bench, cell, "end_to_end"):
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    t_judge = time.perf_counter()
    checks = judge(adapter, cfg, x, run, seed)
    checks["failed_fits"] = {"value": float(failed), "limit": 0.0}
    print(f"run.py: reference and comparison {time.perf_counter() - t_judge:.2f}s",
          file=log)

    line = {
        "correct": all(
            c["limit"] is not None and c["value"] <= c["limit"]
            for c in checks.values()
        ),
        "attempted": len(fits),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if tr is not None and not rehearse:
        dev = tr.busiest()
        device["busy_s"] = tr.mean_busy_s()
        device["window_s"] = tr.window_s
        line["breakdown"] = {
            "device_ops": tr.top_ops(10),
            "idle_gaps": tr.idle_gaps(dev, cfg["phases"], 10),
        }
    if rehearse:
        line["rehearsal"] = True
        line["would_report"] = [
            m["name"] for m in metrics_of(
                bench, cell, "per_layer" if trace else "end_to_end")
        ]
    line["checks"] = checks
    for name, c in checks.items():
        verdict = "ok" if c["limit"] is not None and c["value"] <= c["limit"] else "OVER"
        print(f"check {name} {c['value']:.6g} limit {c['limit']} {verdict}", file=log)
    return line, (1 if rehearse else 0)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    ap.add_argument("--describe-trace", default=None, metavar="FILE",
                    help="with --trace 1: write a by-hand look at the trace "
                         "(planes, lines, top event names) to FILE")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "oap_mllib_tpu")):
        print("run.py: the oap_mllib_tpu package is not beside benchmarks/",
              file=sys.stderr)
        return 3
    seconds = args.seconds
    if seconds is None:
        seconds = _load_json(ROOT, "BENCHMARK.json")["run_seconds"]
    line, code = drive(args.workload, args.seed, seconds, bool(args.trace),
                       bool(args.rehearse), describe_to=args.describe_trace)
    if line is None:
        return code
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
