"""Test harness: single-host multi-rank pseudo-cluster.

The reference tests its "distributed" code as a 1-rank collective world on
local[*] (Utils.scala:119-121) plus a 2-executor pseudo-YARN cluster in CI
(survey §4).  Here the analog is stronger: an 8-device virtual CPU mesh via
``--xla_force_host_platform_device_count=8``, so every sharded program in
the suite actually executes 8-way SPMD with real XLA collectives.
"""

import os

# The suite is the 8-rank pseudo-cluster whatever the session's default
# backend: pin the CPU platform and its device count before jax loads.
# The XLA flag is exported as well so the subprocesses the suite spawns
# inherit an 8-device world unless they ask for another size.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_config():
    """Fresh global config per test."""
    import oap_mllib_tpu.config as cfgmod

    with cfgmod._lock:
        cfgmod._config = None
    yield
    with cfgmod._lock:
        cfgmod._config = None


@pytest.fixture
def rng():
    return np.random.default_rng(42)
