"""The leaf spans at the seams where the host meets the device (ISSUE 35):
``put``, ``land``, ``cast`` and ``launch`` under ``table_convert/upload`` on
every staging route, ``fetch`` wherever a phase blocks on device results and
``launch`` where the k-means‖ rounds hand the device a program — in the
one span tree, in ``summary.timings``, on the profiler's clock only while a
trace runs — and model outputs that no span touches."""

import contextlib
import hashlib

import jax
import numpy as np
import pytest

from oap_mllib_tpu import KMeans, PCA
from oap_mllib_tpu.data import table as table_mod
from oap_mllib_tpu.models import kmeans as kmeans_mod
from oap_mllib_tpu.models import pca as pca_mod
from oap_mllib_tpu.parallel.mesh import get_mesh
from oap_mllib_tpu.telemetry import spans
from oap_mllib_tpu.utils import profiling

D = 8
ROWS = 2048  # on the 256 * 2^j bucket of one device and of four
PIECE_ROWS = 300  # several pieces a shard and an uneven last one

# route -> (devices, dtype of the caller's array, are the pieces shrunk)
ROUTES = {
    "whole": (1, np.float32, False),
    "in_place": (1, np.float32, True),
    "mesh_in_place": (4, np.float32, True),
    "blocks_one_device": (1, np.float64, True),
    "blocks_mesh": (4, np.float64, True),
}
UPLOAD = "table_convert/upload"
FETCHES = {
    "kmeans": ("init_centers/rounds/fetch", "init_centers/kmeanspp_host/fetch",
               "lloyd_loop/fetch"),
    "pca": ("covariance/fetch", "eigh/fetch"),
}
PHASES = {
    "kmeans": ("init_centers", "lloyd_loop"),
    "pca": ("covariance", "eigh"),
}


def _data(dtype, rows=ROWS):
    rng = np.random.default_rng(7)
    centres = rng.normal(size=(4, D)) * 6
    x = centres[rng.integers(4, size=rows)] + rng.normal(size=(rows, D))
    return np.ascontiguousarray(x.astype(dtype))


def _fit(estimator, x):
    """(the fit's flat outputs, its Timings)."""
    if estimator == "kmeans":
        m = KMeans(k=4, max_iter=5, seed=3).fit(x)
        s = m.summary
        out = (m.cluster_centers_, s.cluster_sizes,
               np.float64(s.training_cost), np.int64(s.num_iter))
        return out, s.timings
    m = PCA(k=3).fit(x)
    return (m.components_, m.explained_variance_), m.summary["timings"]


@pytest.fixture
def on_route(monkeypatch):
    """Arm a staging route: the mesh the estimators ask for and, where
    the route wants pieces, the module's own limits shrunk to
    ``PIECE_ROWS`` rows.  Returns the caller's array."""

    def arm(route):
        n_devices, dtype, shrunk = ROUTES[route]
        mesh = get_mesh(n_devices=n_devices)
        monkeypatch.setattr(kmeans_mod, "get_mesh", lambda: mesh)
        monkeypatch.setattr(pca_mod, "get_mesh", lambda: mesh)
        if shrunk:
            piece = PIECE_ROWS * D * 4
            in_flight = 2 if n_devices == 1 else 1
            monkeypatch.setattr(
                table_mod, "_UPLOAD_PIECE_BYTES", piece * in_flight
            )
            monkeypatch.setattr(
                table_mod, "_ONE_DEVICE_PIECES_IN_FLIGHT", in_flight
            )
            monkeypatch.setattr(table_mod, "_CAST_BLOCK_BYTES", piece)
        # float64 rows off their bucket: the cast route has a pad to make
        return _data(dtype, ROWS - 40 if dtype is np.float64 else ROWS)

    return arm


@pytest.fixture
def put_calls(monkeypatch):
    """Every ``jax.device_put`` the staging module makes, with the path
    of the span that was active when it was made."""
    calls = []
    put = table_mod.jax.device_put

    def spy(v, where):
        calls.append((spans.current_span().path, v.nbytes))
        return put(v, where)

    monkeypatch.setattr(table_mod.jax, "device_put", spy)
    return calls


@pytest.mark.parametrize("route", sorted(ROUTES))
@pytest.mark.parametrize("estimator", ["kmeans", "pca"])
class TestUploadLeaves:
    def test_the_upload_splits_into_its_leaves(
        self, estimator, route, on_route, put_calls
    ):
        x = on_route(route)
        _, timings = _fit(estimator, x)
        flat = timings.as_dict()
        up = timings.root.node(UPLOAD)
        leaves = {c.name: c for c in up.children}
        casts = route.startswith("blocks")
        assert set(leaves) == (
            {"put", "land"} | ({"cast"} if casts else set())
            | ({"launch"} if route != "whole" else set())
        )
        for name, leaf in leaves.items():
            assert flat[f"{UPLOAD}/{name}"] == leaf.duration_s
            assert leaf.count >= 1 and not leaf.children
        # one entry a device_put call, each inside the put leaf, and the
        # bytes they were handed are the bytes the upload says it sent
        made = [nbytes for path, nbytes in put_calls]
        assert {path for path, _ in put_calls} == {f"{UPLOAD}/put"}
        assert leaves["put"].count == len(made)
        assert leaves["put"].attrs["bytes"] == sum(made) == up.attrs["bytes"]
        assert up.attrs["pieces"] > 1 or route == "whole"
        # one entry a program the upload starts: a jnp.zeros a device and
        # an in-place write a piece (the mask goes up in one put a device
        # and is written nowhere, so there are as many launches as puts)
        if route != "whole":
            assert leaves["launch"].count == len(made)
        # the children are parts of the parent, on one clock
        assert sum(c.duration_s for c in up.children) <= up.duration_s
        assert up.duration_s <= flat["table_convert"]
        # cast_wait_s is the cast leaf's seconds, in its older view
        assert up.attrs["cast_wait_s"] == (
            leaves["cast"].duration_s if casts else 0
        )
        if casts:
            assert up.attrs["cast_bytes"] == x.shape[0] * D * 4
            assert leaves["cast"].duration_s > 0

    def test_outputs_are_what_the_fit_gives_without_the_leaves(
        self, estimator, route, on_route, monkeypatch
    ):
        """The parent's program: the same calls with no span around them."""
        x = on_route(route)
        with_leaves, _ = _fit(estimator, x)
        monkeypatch.setattr(
            spans, "child",
            lambda name: contextlib.nullcontext(spans.Span(name)),
        )
        without, timings = _fit(estimator, x)
        # nothing was split: only the launch accounting sits below a phase
        assert {p.rsplit("/", 1)[1] for p in timings.as_dict() if "/" in p} <= {
            "compile", "execute", "tuning"
        }
        assert _digest(with_leaves) == _digest(without)


def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(str((a.shape, a.dtype)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("estimator", ["kmeans", "pca"])
def test_every_phase_has_its_fetch_leaf_and_a_host_gap(
    estimator, n_devices, monkeypatch
):
    mesh = get_mesh(n_devices=n_devices)
    monkeypatch.setattr(kmeans_mod, "get_mesh", lambda: mesh)
    monkeypatch.setattr(pca_mod, "get_mesh", lambda: mesh)
    _, timings = _fit(estimator, _data(np.float32))
    flat = timings.as_dict()
    for path in FETCHES[estimator]:
        leaf = timings.root.node(path)
        assert path in flat and leaf.count >= 1 and not leaf.children
        assert leaf.attrs["bytes"] >= 0
    # what came back: the (k, d) centres of the reduction; centres,
    # sizes, cost and the iteration count of the Lloyd loop; the spectrum
    if estimator == "kmeans":
        assert timings.root.node(FETCHES[estimator][1]).attrs["bytes"] == 4 * D * 4
        assert timings.root.node("lloyd_loop/fetch").attrs["bytes"] >= (
            4 * D * 4 + 4 * 4 + 4 + 4
        )
        # the seed row, and phi and the pick count of each round in one
        # read; the wait for the candidate weights brings nothing back
        rounds = timings.root.node("init_centers/rounds")
        fetch = timings.root.node("init_centers/rounds/fetch")
        assert fetch.count == 1 + rounds.attrs["rounds"] + 1
        assert fetch.attrs["bytes"] == D * 4 + rounds.attrs["rounds"] * (4 + 4)
        # the programs the rounds start: the distances to the seed row, a
        # round each, the candidate buffer and its weights
        launch = timings.root.node("init_centers/rounds/launch")
        assert launch.count == 1 + rounds.attrs["rounds"] + 2
        assert not launch.children and launch.path in flat
    else:
        assert timings.root.node("eigh/fetch").attrs["bytes"] == (D + D * D) * 4
        assert timings.root.node("covariance/fetch").attrs["bytes"] == 0
    # a phase's wall minus what the host waited for below it
    for phase in PHASES[estimator]:
        waited = sum(
            s for p, s in flat.items()
            if p.startswith(phase + "/") and p.rsplit("/", 1)[1] in ("fetch", "land")
        )
        assert 0 < waited <= flat[phase]


class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: notes its name."""

    made = []

    def __init__(self, name):
        self.made.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("tracing", [True, False])
@pytest.mark.parametrize("estimator", ["kmeans", "pca"])
def test_leaves_are_on_the_profilers_clock_only_under_a_trace(
    estimator, tracing, on_route, monkeypatch
):
    x = on_route("blocks_one_device")
    monkeypatch.setattr(profiling, "trace_active", lambda: tracing)
    monkeypatch.setattr(_Annotation, "made", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    _, timings = _fit(estimator, x)
    if not tracing:
        assert _Annotation.made == []
        return
    # one annotation an entry, named by the span's path below the root
    want = {
        path.split("/", 1)[1]: span.count
        for path, span in timings.root.walk()
        if "/" in path and span.count and span.name not in ("compile", "execute")
    }
    got = {name: _Annotation.made.count(name) for name in set(_Annotation.made)}
    assert got == want
    for leaf in (f"{UPLOAD}/put", f"{UPLOAD}/land", f"{UPLOAD}/cast",
                 f"{UPLOAD}/launch", *FETCHES[estimator]):
        assert got[leaf] >= 1
    assert ("init_centers/rounds/launch" in got) == (estimator == "kmeans")


def test_fetch_outside_a_fit_times_nothing_and_returns_the_value():
    assert spans.current_span() is None
    out = spans.fetch(np.asarray, jax.numpy.arange(3))
    assert out.tolist() == [0, 1, 2]


def test_launch_is_one_entry_a_call_and_hands_on_what_the_program_returns():
    root = spans.Span("fit")
    with spans.enter(root, annotate=False):
        out = spans.launch(jax.numpy.zeros, (2, 3), jax.numpy.float32)
        out = spans.launch(jax.numpy.add, out, 1.0)
    assert np.asarray(out).tolist() == [[1.0] * 3] * 2
    leaf = root.node("launch")
    assert leaf.count == 2 and leaf.attrs == {} and not leaf.children
    assert spans.launch(max, 1, 2) == 2  # outside a fit: times nothing


def test_fetch_counts_host_arrays_only():
    root = spans.Span("fit")
    with spans.enter(root, annotate=False):
        spans.fetch(jax.device_get, (jax.numpy.zeros((2, 3)), 5, np.float32(1)))
        spans.fetch(jax.block_until_ready, jax.numpy.zeros((4,)))
    leaf = root.node("fetch")
    assert leaf.count == 2 and leaf.attrs["bytes"] == 2 * 3 * 4 + 4
    assert leaf.path == "fetch" and root.flat() == {"fetch": leaf.duration_s}
