"""Float64 parity lane: the reference's K-Means/PCA kernels run in double
(KMeansDALImpl.cpp:32) and its parity suite asserts 1e-5 (IntelPCASuite).
With enable_x64 the TPU-native kernels hit the same bar (here: far past it,
since both sides are f64).  jax's x64 flag is process-global, so this lane
runs in a subprocess."""

import os
import subprocess
import sys
import textwrap

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

_SCRIPT = textwrap.dedent("""
    import sys
    sys.path.insert(0, %r)
    import os
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    jax.config.update("jax_enable_x64", True)
    import numpy as np
    from oap_mllib_tpu.config import set_config
    set_config(enable_x64=True)

    rng = np.random.default_rng(11)

    # PCA: components must match the f64 NumPy oracle to 1e-9
    basis = rng.normal(size=(10, 10)) * np.linspace(3, 0.1, 10)
    x = rng.normal(size=(400, 10)) @ basis
    from oap_mllib_tpu import PCA
    m = PCA(k=4).fit(x)
    xc = x - x.mean(0)
    cov = xc.T @ xc / (len(x) - 1)
    vals, vecs = np.linalg.eigh(cov)
    vecs = vecs[:, ::-1]; vals = vals[::-1]
    np.testing.assert_allclose(
        np.abs(m.components_), np.abs(vecs[:, :4]), atol=1e-9)
    np.testing.assert_allclose(
        m.explained_variance_, vals[:4] / vals.sum(), atol=1e-12)

    # K-Means: fixed init, converged centers match f64 oracle to 1e-9
    from oap_mllib_tpu.ops.kmeans_ops import lloyd_run
    import jax.numpy as jnp
    blobs = rng.normal(size=(4, 6)) * 5
    data = blobs[rng.integers(4, size=500)] + rng.normal(size=(500, 6)) * 0.05
    init = data[rng.choice(500, 4, replace=False)]
    c, it, cost, _ = lloyd_run(
        jnp.asarray(data), jnp.ones(500), jnp.asarray(init), 60,
        jnp.asarray(1e-12))
    cc = init.copy()
    for _ in range(60):
        d2 = ((data[:, None] - cc[None]) ** 2).sum(-1)
        a = d2.argmin(1)
        new = np.stack([data[a == j].mean(0) if (a == j).any() else cc[j]
                        for j in range(4)])
        done = ((new - cc) ** 2).sum(1).max() <= 1e-24
        cc = new
        if done:
            break
    np.testing.assert_allclose(np.asarray(c), cc, atol=1e-9)
    assert np.asarray(c).dtype == np.float64
    print("X64_PARITY_OK")
""" % REPO)


def test_f64_parity_subprocess():
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)  # breaks the TPU plugin; subprocess uses CPU anyway
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        timeout=300, env=env,
    )
    assert "X64_PARITY_OK" in out.stdout, out.stdout + out.stderr
