"""The control: the plain reference, put in the program's place and computed
in a lower precision, has to come out NOT correct; at ``highest`` it has to
come out correct (or the comparison would fail any second implementation).

On the chip the readings come from ``benchmarks/control.py`` at the cell's own
size (PERF.md, section 2, gives them and the limits set from them).  This is
the same control at the configuration's tiny ``rehearse`` sizes, where a test
run can hold it.  ``bfloat16`` rounds explicitly, so the CPU shows it too;
``high`` and ``default`` only ask the backend for fewer passes, which the CPU
ignores, so they are read on the chip alone.

    python -m pytest benchmarks/tests -q
"""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402

CELLS = ("kmeans_d256_k1000.fit_loop", "pca_d512_k10.fit_loop")
SEEDS = (3, 2_147_483_659, 2_147_491_578)


def _judged(workload, seed, precision):
    _, cell, cfg, _ = harness.load_cell(workload, rehearse=True)
    adapter = harness._module("estimators", cfg["estimator"])
    ref = harness._module("reference", adapter.REFERENCE)
    x = adapter.make_data(cfg, cfg["rows_per_chip"] * cell["chips"], seed)
    answer = ref.fit_plain(x, cfg, seed + 1, precision)
    numbers = ref.judge(x, cfg, [answer], seed)
    limits = cfg["limits"]
    return {n: (v, limits[n]) for n, v in numbers.items()}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("workload", CELLS)
def test_reference_at_highest_is_correct(workload, seed):
    got = _judged(workload, seed, "highest")
    assert all(v <= lim for v, lim in got.values()), got


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("workload", CELLS)
def test_control_in_bfloat16_is_not_correct(workload, seed):
    got = _judged(workload, seed, "bfloat16")
    assert any(v > lim for v, lim in got.values()), got
