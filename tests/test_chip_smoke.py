"""The launch scripts never pass a CPU off as a chip.

``chip_smoke.py`` is the bring-up proof the driver runs on the v5e; here,
with no accelerator, it must refuse before any phase, and its rehearsal
must run every phase and still end ``"ok": false``.  ``bench.py`` must
raise on a device it has no published peak for.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def _run(args, cwd=_REPO, script=_SMOKE, devices=None, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    if devices:
        env["XLA_FLAGS"] += (
            f" --xla_force_host_platform_device_count={devices}"
        )
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


class TestChipSmokeWithoutAChip:
    def test_no_tpu_fails_before_any_phase_and_prints_no_result(self):
        p = _run([])
        assert p.returncode not in (0, 1), (p.returncode, p.stderr[-500:])
        assert "no TPU" in p.stderr
        assert p.stdout.strip() == ""  # no phase ran, no JSON result

    def test_wrong_device_count_is_refused(self):
        p = _run(["--chips", "4", "--rehearse"])  # one CPU device only
        assert p.returncode not in (0, 1) and p.stdout.strip() == ""
        assert "--chips 4 needs exactly 4" in p.stderr

    def test_alone_in_a_directory_it_fails_and_prints_no_result(
            self, tmp_path):
        lonely = shutil.copy(_SMOKE, str(tmp_path / "chip_smoke.py"))
        p = _run([], cwd=str(tmp_path), script=lonely)
        assert p.returncode != 0 and p.stdout.strip() == ""

    @pytest.mark.parametrize("chips", [1, 4])
    def test_rehearsal_runs_every_phase_and_ends_not_ok(self, chips):
        """Tiny sizes, CPU allowed: the same phases and checks pass,
        chip-only checks are named as not checked, and the last line
        says ok:false with the platform really seen — exit 1."""
        args = ["--rehearse"] + (["--chips", "4"] if chips == 4 else [])
        p = _run(args, devices=4 if chips == 4 else None)
        assert p.returncode == 1, p.stdout[-3000:] + p.stderr[-3000:]
        assert "FAILED" not in p.stdout
        last = json.loads(p.stdout.strip().splitlines()[-1])
        assert last["ok"] is False and last["rehearsal"] is True
        assert last["device"] == {
            "platform": "cpu", "kind": "cpu", "count": chips,
        }
        phases = [ln for ln in p.stdout.splitlines() if ln.startswith("== ")]
        want = (
            ["native", "kmeans", "block als", "model_parallel=2", "pca"]
            if chips == 4 else
            ["native", "kmeans fit", "pca fit", "als fit", "serving"]
        )
        for w in want:
            assert any(w in ph for ph in phases), (w, phases)
        assert "not checked on cpu" in p.stdout  # HBM, Pallas dispatch


class TestBenchNeedsTheChip:
    def test_unknown_device_kind_raises_instead_of_defaulting(self):
        sys.path.insert(0, _REPO)
        try:
            import bench
        finally:
            sys.path.remove(_REPO)
        with pytest.raises(RuntimeError, match="no bf16 peak on record"):
            bench._peak_flops()  # the suite's devices are CPUs

    def test_no_cpu_proxy_metric_and_no_chip_child_remain(self):
        src = open(os.path.join(_REPO, "bench.py")).read()
        assert "cpuproxy" not in src
        assert "tests_tpu/\"" not in src and "_tests_tpu_status" not in src
        assert "mkdtemp" not in src  # the XLA cache is at a fixed path
