"""``correct`` has to come out false when the timed path is broken.

Each test skips the harness's look for a chip (``rehearse=True``: the
configuration's tiny sizes, any backend) and drives the rest of a run -- data
from the seed, warm-up, window, reference, comparison against the limits --
with one fault planted in the PROGRAM, underneath the entry the window calls:

- a step that returns its state unchanged (the Lloyd loop does not iterate);
- half of the batch left out, the answer taken over the rest;
- an answer altered where it is produced.

The exchange between chips does not exist in a one-chip cell.  A sound run of
the same seeds has to come out correct, or the faults would prove nothing.

    python -m pytest benchmarks/tests -q        (CPU, about a minute)
"""

import io
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import run as harness  # noqa: E402

KMEANS_CELL = "kmeans_d256_k1000.fit_loop"
PCA_CELL = "pca_d512_k10.fit_loop"
SEEDS = (11, 2_147_483_659)


def drive(workload, seed):
    line, code = harness.drive(workload, seed, 0.2, False, rehearse=True,
                               log=io.StringIO())
    assert code == 1 and line["rehearsal"] and line["metrics"] == {}
    return line


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("workload", (KMEANS_CELL, PCA_CELL))
def test_sound_run_is_correct(workload, seed):
    line = drive(workload, seed)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1


def _over(line):
    return sorted(n for n, c in line["checks"].items() if c["value"] > c["limit"])


@pytest.mark.parametrize("seed", SEEDS)
def test_kmeans_state_unchanged(monkeypatch, seed):
    from oap_mllib_tpu.models.kmeans import KMeans

    real = KMeans._run_lloyd

    def no_step(self, *a, **kw):
        keep, self.max_iter = self.max_iter, 0
        try:
            return real(self, *a, **kw)
        finally:
            self.max_iter = keep

    monkeypatch.setattr(KMeans, "_run_lloyd", no_step)
    line = drive(KMEANS_CELL, seed)
    assert not line["correct"]
    assert "step_gap" in _over(line), line["checks"]


@pytest.mark.parametrize("seed", SEEDS)
def test_kmeans_half_the_batch_left_out(monkeypatch, seed):
    from oap_mllib_tpu.models.kmeans import KMeans

    real = KMeans._fit_tpu
    monkeypatch.setattr(
        KMeans, "_fit_tpu",
        lambda self, x, w, degraded=False: real(self, x[: len(x) // 2], w, degraded),
    )
    line = drive(KMEANS_CELL, seed)
    assert not line["correct"]
    assert "count_gap" in _over(line), line["checks"]


@pytest.mark.parametrize("seed", SEEDS)
def test_kmeans_answer_altered(monkeypatch, seed):
    from oap_mllib_tpu.models.kmeans import KMeans

    real = KMeans._fit_tpu_inner

    def altered(self, *a, **kw):
        model = real(self, *a, **kw)
        model.cluster_centers_ = np.array(model.cluster_centers_)
        model.cluster_centers_[0] += 0.25  # one centre of k, moved a little
        return model

    monkeypatch.setattr(KMeans, "_fit_tpu_inner", altered)
    line = drive(KMEANS_CELL, seed)
    assert not line["correct"]
    assert {"cost_gap", "size_gap"} & set(_over(line)), line["checks"]


@pytest.mark.parametrize("seed", SEEDS)
def test_pca_half_the_batch_left_out(monkeypatch, seed):
    from oap_mllib_tpu.models.pca import PCA

    real = PCA._fit_tpu
    monkeypatch.setattr(PCA, "_fit_tpu", lambda self, x: real(self, x[: len(x) // 2]))
    line = drive(PCA_CELL, seed)
    assert not line["correct"]
    assert {"ratio_gap", "residual_gap"} & set(_over(line)), line["checks"]


@pytest.mark.parametrize("seed", SEEDS)
def test_pca_answer_altered(monkeypatch, seed):
    from oap_mllib_tpu.models.pca import PCA

    real = PCA._fit_tpu_inner

    def altered(self, *a, **kw):
        model = real(self, *a, **kw)
        model.components_ = np.array(model.components_)
        model.components_[0, 0] += 1e-3  # one entry of d x k
        return model

    monkeypatch.setattr(PCA, "_fit_tpu_inner", altered)
    line = drive(PCA_CELL, seed)
    assert not line["correct"]
    assert "residual_gap" in _over(line), line["checks"]
