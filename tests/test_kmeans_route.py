"""``kmeans_ops.lloyd_route``: the one place that decides which Lloyd
program a ``KMeans.fit`` runs, and the one validator of ``kmeans_kernel``
and ``ring_reduction``.

The route is a function of what can be observed, so the TPU's answers are
asked for here by naming the backend: nothing runs on a device.  What the
routes compute is held elsewhere (tests/test_pallas.py,
tests/test_kmeans_data_sharded.py); that a fit reports the route it ran is
``TestCounters`` there and tests_tpu/ on the chip.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from oap_mllib_tpu.config import get_config, set_config
from oap_mllib_tpu.ops import kmeans_ops
from oap_mllib_tpu.ops.pallas import autotune
from oap_mllib_tpu.parallel.mesh import get_mesh
from oap_mllib_tpu.telemetry import spans
from oap_mllib_tpu.utils import progcache

ROWS, D, K = 1 << 20, 256, 1000  # 32 chunks of 32768 rows by the occupancy rule
DEFAULT_GEOMETRY = autotune.DEFAULTS["kmeans"]

# (id, Config overrides, (devices, model_parallel) or None = streamed,
#  lloyd_route keywords, expected LloydRoute fields or the error's match)
CASES = [
    ("one_chip_walks", {}, (1, 1), {},
     dict(kernel="pallas", shards=1, row_chunks=1,
          geometry=DEFAULT_GEOMETRY)),
    ("off_the_tpu_xla", {}, (1, 1), dict(backend="cpu"),
     dict(kernel="xla", shards=1, row_chunks=32)),
    ("degraded_forbids_the_kernel_and_doubles_the_chunks", {}, (1, 1),
     dict(degraded=2), dict(kernel="xla", row_chunks=128)),
    ("checkpoint_armed_forbids_the_kernel", {}, (1, 1),
     dict(checkpoint=True), dict(kernel="xla", row_chunks=32)),
    ("every_shard_walks", {}, (4, 1), {},
     dict(kernel="pallas", shards=4, row_chunks=1)),
    ("shards_chunk_their_own_rows", {}, (4, 1), dict(backend="cpu"),
     dict(kernel="xla", shards=4, row_chunks=8)),
    ("blocks_past_vmem_xla", {}, (1, 1), dict(k=8192, d=512),
     dict(kernel="xla")),  # 8192 x 512 = 2^22 padded elements
    ("pallas_forced_past_the_bound", dict(kmeans_kernel="pallas"), (1, 1),
     dict(k=8192, d=512), dict(kernel="pallas")),
    ("two_processes_xla", {}, (1, 1), dict(processes=2), dict(kernel="xla")),
    ("float64_xla", {}, (1, 1), dict(dtype=np.float64), dict(kernel="xla")),
    ("model_axis_feature_shards", {}, (8, 2), {},
     dict(kernel="model_sharded", shards=4, geometry={"segments": 1})),
    ("xla_forced_on_a_model_axis_is_data_parallel",
     dict(kmeans_kernel="xla"), (8, 2), {}, dict(kernel="xla", shards=4)),
    ("streamed_rows_xla", {}, None, {}, dict(kernel="xla", shards=1)),
    ("typo_kernel_raises_on_the_streamed_route",
     dict(kmeans_kernel="palas"), None, {}, "kmeans_kernel"),
    ("typo_ring_raises_on_the_streamed_route",
     dict(ring_reduction="yes"), None, {}, "ring_reduction"),
    ("typo_kernel_raises_on_a_model_axis",
     dict(kmeans_kernel="palas"), (8, 2), {}, "kmeans_kernel"),
    ("pinned_depth_1_raises",
     dict(tuning='pin:{"kmeans": {"depth": 1}}'), (1, 1), {},
     "rotation depth"),
    ("pinned_tile_rows_chunk_the_xla_scan",
     dict(tuning='pin:{"kmeans": {"tile_rows": 1024}}'), (1, 1),
     dict(backend="cpu"),
     dict(kernel="xla", row_chunks=1024,
          geometry={"tile_rows": 1024, "depth": 2})),
]


@pytest.mark.parametrize(
    "config,devices,kw,expected",
    [pytest.param(*case[1:], id=case[0]) for case in CASES],
)
def test_lloyd_route(config, devices, kw, expected):
    set_config(**config)
    mesh = None
    if devices is not None:
        mesh = get_mesh(n_devices=devices[0], model_parallel=devices[1])
    args = dict(
        rows=None if mesh is None else ROWS, d=D, k=K, dtype=np.float32,
        precision="highest", backend="tpu", processes=1,
    )
    args.update(kw)

    def ask():
        return kmeans_ops.lloyd_route(get_config(), mesh, **args)

    if isinstance(expected, str):
        with pytest.raises(ValueError, match=expected):
            ask()
        return
    route = ask()
    assert route.kernel in kmeans_ops.LLOYD_ROUTES
    for field, value in expected.items():
        assert getattr(route, field) == value, (field, route)


def test_one_device_and_one_shard_mesh_share_the_program(rng):
    """No mesh, and a mesh of one device, are one program: the second
    launch builds nothing and emits no collective."""
    x = jnp.asarray(rng.normal(size=(512, 6)).astype(np.float32))
    w = jnp.ones((512,), jnp.float32)
    c0 = x[:3]
    tol = jnp.asarray(0.0, jnp.float32)

    def built():
        return progcache.stats()["by_algo"].get(
            "kmeans.lloyd", {"hits": 0, "misses": 0}
        )

    # an iteration count no other test of this file's process uses
    plain = kmeans_ops.lloyd_run(x, w, c0, 13, tol)
    before = dict(built())
    meshed = kmeans_ops.lloyd_run(
        x, w, c0, 13, tol, mesh=get_mesh(n_devices=1)
    )
    after = built()
    assert after["misses"] == before["misses"]
    assert after["hits"] == before["hits"] + 1
    for a, b in zip(plain, meshed):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("accumulate,precision,expected", [
    ("pallas", "highest", {"cross": 6, "sums": 3}),
    ("pallas", "high", {"cross": 1, "sums": 2}),
    ("pallas", "default", {"cross": 1, "sums": 1}),
    ("xla", "highest", None),
])
def test_the_walk_notes_its_mxu_passes_on_the_phase(
        rng, accumulate, precision, expected):
    """``lloyd_loop.attrs["mxu_passes"]``: the bf16 passes the tile walk
    issues a tile, by tier (six for the HIGHEST cross term, three for the
    exact-split sums); absent where no walk ran."""
    x = jnp.asarray(rng.normal(size=(512, 6)).astype(np.float32))
    w = jnp.ones((512,), jnp.float32)
    loop = spans.Span("lloyd_loop")
    with spans.enter(loop, annotate=False):
        kmeans_ops.lloyd_run(
            x, w, x[:3], 2, jnp.asarray(0.0, jnp.float32),
            precision=precision, accumulate=accumulate, tile_rows=256,
        )
    assert loop.attrs.get("mxu_passes") == expected
