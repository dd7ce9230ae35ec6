#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, one cell a call.

    python3 benchmarks/control.py --workload <cell> --seeds 12 --control-seeds 3 \
        [--first-seed N] [--rehearse 1] [--out chiprun_out/<file>.jsonl]

In ONE process (set-up is long): for each seed the table is made, the program
fits it once through the timed entry as the configuration states, and the
plain reference judges the answer: the LOWER readings.  Then, on the first
``--control-seeds`` seeds, the controls: the program with its own
lower-precision paths switched on (``matmul_precision`` one and two steps
down), the plain reference put in the program's place at ``highest`` (has to
pass), ``high``, ``default`` and ``bfloat16``, and the configuration's
``control_faults`` planted in that reference: the UPPER readings.  One JSON line per reading; the benchmark's
own runs never call this file.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as harness  # noqa: E402

LOWER = ("high", "default")  # the program's own paths: three passes; one
REFERENCE_AT = ("highest", "high", "default", "bfloat16")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_147_500_000)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    bench, cell, cfg, traffic = harness.load_cell(args.workload, bool(args.rehearse))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"control.py: no TPU ({dev.platform})", file=sys.stderr)
        return 2
    sys.path.insert(0, harness.ROOT)
    from oap_mllib_tpu.config import set_config
    from oap_mllib_tpu.utils import progcache

    adapter = harness._module("estimators", cfg["estimator"])
    driver = harness._module("drivers", traffic["driver"])
    ref = harness._module("reference", adapter.REFERENCE)
    settings = adapter.program_settings(cfg)
    if dev.platform != "tpu":
        settings["device"] = "auto"
    set_config(**settings)
    progcache.use_checkout_cache(os.path.join(harness.ROOT, ".jax_cache"))
    rows = cfg["rows_per_chip"] * cell["chips"]
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        rec.update(workload=args.workload, platform=dev.platform, rows=rows)
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def judged(results, seed):
        t = time.perf_counter()
        numbers = ref.judge(x, cfg, results, seed)
        return numbers, time.perf_counter() - t

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        fit_seed = seed % driver.SEED_MODULUS
        x = adapter.make_data(cfg, rows, seed)
        t = time.perf_counter()
        result, info = adapter.fit(cfg, x, fit_seed)
        wall = time.perf_counter() - t
        numbers, jt = judged([result], seed)
        emit({"who": "program", "precision": cfg["matmul_precision"], "seed": seed,
              "numbers": numbers, "fit_wall_s": wall, "judge_s": jt,
              "kernel": info.get("kernel"), "phases": info.get("phases")})
        if i >= args.control_seeds:
            continue
        for prec in LOWER:
            set_config(matmul_precision=prec)
            try:
                result, info = adapter.fit(cfg, x, fit_seed)
                numbers, jt = judged([result], seed)
                emit({"who": "program", "precision": prec, "seed": seed,
                      "numbers": numbers, "kernel": info.get("kernel")})
            except Exception as e:  # a control that crashes has failed
                emit({"who": "program", "precision": prec, "seed": seed,
                      "error": f"{type(e).__name__}: {e}"[:300]})
            finally:
                set_config(matmul_precision=cfg["matmul_precision"])
        for prec in REFERENCE_AT:
            result = ref.fit_plain(x, cfg, seed + 1, prec)
            numbers, jt = judged([result], seed)
            emit({"who": "reference", "precision": prec, "seed": seed,
                  "numbers": numbers})
        # faults planted in the reference put in the program's place: the
        # configuration's ``control_faults`` are overrides of its own keys
        for fault, override in cfg.get("control_faults", {}).items():
            result = ref.fit_plain(x, dict(cfg, **override), seed + 1, "highest")
            numbers, jt = judged([result], seed)
            emit({"who": "reference", "precision": "highest", "fault": fault,
                  "seed": seed, "numbers": numbers})
    return 0


if __name__ == "__main__":
    sys.exit(main())
