"""Real-hardware suite: compiled (non-interpret) kernels on an actual TPU.

Unlike ``tests/`` (which pins JAX to the 8-device virtual CPU pseudo-cluster),
this suite uses whatever backend the session has and is a command of its
own — ``python -m pytest tests_tpu/ -q`` from the one process that holds
the chip; tier-1 collects ``tests/`` only.  Every test is skipped unless
that backend is a TPU, decided when the test starts, never at collection.
"""

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _needs_tpu():
    import jax

    if jax.default_backend() != "tpu":
        pytest.skip("requires a real TPU backend")


@pytest.fixture
def rng():
    return np.random.default_rng(42)
