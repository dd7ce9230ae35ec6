"""The staging rule of ``DenseTable``'s ndarray constructors (ISSUE 26):
at most ONE host pass over the table before the upload, and none when
dtype, layout and row bucket already match — the caller's array itself is
what ``_upload`` receives then."""

import math
import tracemalloc

import jax
import numpy as np
import pytest

from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.data import table as table_mod
from oap_mllib_tpu.data.table import DenseTable
from oap_mllib_tpu.parallel.mesh import get_mesh
from oap_mllib_tpu.utils.timing import Timings, phase_timer

D = 6
# (dtype of x, dtype asked of the table)
DTYPES = {
    "match": (np.float32, np.float32),
    "f64_to_f32": (np.float64, np.float32),
    "int": (np.int32, np.float32),
}
LAYOUTS = ("c", "fortran", "strided", "readonly")
CONSTRUCTORS = ("from_numpy", "from_process_local")


def _bucket(mesh):
    """The smallest row bucket of the suite's mesh."""
    return mesh.shape[mesh.axis_names[0]] * table_mod._ROW_MULTIPLE


def _make(rng, n, src_dtype, layout, d=D):
    base = (rng.normal(size=(2 * n, 2 * d)) * 100).astype(src_dtype)
    if layout == "strided":
        x = base[::2, ::2]
        assert not x.flags.c_contiguous and not x.flags.f_contiguous
        return x
    x = np.ascontiguousarray(base[:n, :d])
    if layout == "fortran":
        x = np.asfortranarray(x)
        assert not x.flags.c_contiguous
    elif layout == "readonly":
        x.flags.writeable = False
    return x


def _aligned(rng, shape, offset):
    """A C-contiguous float32 array whose first byte sits ``offset`` past
    a 64-byte boundary."""
    nbytes = int(np.prod(shape)) * 4
    buf = np.empty(nbytes + 128, np.uint8)
    start = (-buf.ctypes.data) % 64 + offset
    x = buf[start:start + nbytes].view(np.float32).reshape(shape)
    x[:] = rng.normal(size=shape)
    assert x.flags.c_contiguous and x.ctypes.data % 64 == offset
    return x


@pytest.fixture
def staged(monkeypatch):
    """Build a table inside a ``table_convert`` phase; returns
    ``build(constructor, x, dtype) -> (table, the host array _upload
    received, the host_copy span)``."""
    uploads = []
    upload = table_mod._upload

    def spy(put, padded, mask, mesh, n_valid):
        uploads.append(padded)
        return upload(put, padded, mask, mesh, n_valid)

    monkeypatch.setattr(table_mod, "_upload", spy)

    def build(constructor, x, dtype):
        timings = Timings("test.fit")
        with phase_timer(timings, "table_convert"):
            table = getattr(DenseTable, constructor)(x, get_mesh(), dtype)
        assert len(uploads) == 1
        return (
            table, uploads.pop(),
            timings.root.node("table_convert/host_copy"),
        )

    return build


@pytest.mark.parametrize("constructor", CONSTRUCTORS)
@pytest.mark.parametrize("rows", ["on_bucket", "off_bucket"])
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("dtypes", sorted(DTYPES))
def test_staging_makes_at_most_one_pass(
    rng, staged, dtypes, layout, rows, constructor
):
    src_dtype, dtype = DTYPES[dtypes]
    target = _bucket(get_mesh())
    n = target if rows == "on_bucket" else target - 548
    x = _make(rng, n, src_dtype, layout)
    before = x.copy()

    table, sent, span = staged(constructor, x, dtype)

    zero_pass = (
        dtypes == "match" and layout in ("c", "readonly")
        and rows == "on_bucket"
    )
    if zero_pass:
        assert sent is x
        assert span.attrs["copied_bytes"] == 0
    else:
        # one new array of the bucket's shape, cast, un-strided, padded
        assert not np.shares_memory(sent, x)
        assert sent.shape == (target, D) and sent.dtype == dtype
        assert sent.flags.c_contiguous
        np.testing.assert_array_equal(sent[:n], before.astype(dtype))
        assert not sent[n:].any()
        assert span.attrs["copied_bytes"] == sent.nbytes
    assert span.duration_s > 0 and span.count == 1
    # the same bytes reach the device either way, and the caller's array
    # is as it was
    assert table.n_rows == n and table.n_padded == target
    assert table.data.dtype == dtype
    np.testing.assert_array_equal(table.to_numpy(), before.astype(dtype))
    mask = np.asarray(table.mask)
    assert mask[:n].all() and not mask[n:].any()
    assert x.tobytes() == before.tobytes()


@pytest.mark.parametrize("dtypes", ["f64_to_f32", "int", "match"])
def test_one_pass_allocates_one_table(rng, dtypes):
    """A cast AND a pad are one allocation of the padded table, not a
    cast copy followed by a padded copy."""
    src_dtype, dtype = DTYPES[dtypes]
    x = (rng.normal(size=(1500, 64)) * 100).astype(src_dtype)[:, ::2]
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        padded, n, copied = table_mod._stage_rows(x, 2048, dtype)
        peak = tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()
    assert n == 1500 and copied == padded.nbytes == 2048 * 32 * 4
    assert padded.nbytes <= peak < 1.25 * padded.nbytes


def test_no_dtype_asked_keeps_the_callers(rng, staged):
    x = rng.normal(size=(_bucket(get_mesh()), D))  # float64
    table, sent, span = staged("from_numpy", x, None)
    assert sent is x and span.attrs["copied_bytes"] == 0
    assert table.n_rows == x.shape[0]


@pytest.mark.parametrize("constructor", CONSTRUCTORS)
def test_not_two_dimensional_raises(constructor):
    with pytest.raises(ValueError, match="2-D"):
        getattr(DenseTable, constructor)(
            np.zeros(8, np.float32), get_mesh(), np.float32
        )


@pytest.mark.parametrize("offset", [0, 16], ids=["aligned64", "unaligned"])
def test_mutating_x_after_the_constructor_returns(rng, staged, offset):
    """Off the CPU the table is a device buffer of its own.  On the CPU
    backend (jax 0.9.0) ``device_put`` copies a host buffer unless it is
    64-byte aligned, and SHARES an aligned one — which the old
    unconditional ``astype`` copy made irrelevant.  The table is a
    snapshot wherever jax copied; the class docstring says what holds
    where it did not."""
    x = _aligned(rng, (_bucket(get_mesh()), D), offset)
    want = x.copy()
    table, sent, _ = staged("from_numpy", x, np.float32)
    assert sent is x  # the zero-pass route: nothing of ours in between
    shared = any(
        np.shares_memory(np.asarray(s.data), x)
        for s in table.data.addressable_shards
    )
    x += 1.0
    if offset:
        assert not shared
    if not shared:
        np.testing.assert_array_equal(table.to_numpy(), want)
    else:
        assert jax.default_backend() == "cpu"


@pytest.fixture
def blobs(rng):
    """On the bucket, float32, C-contiguous: the zero-pass route."""
    n = _bucket(get_mesh())
    centres = rng.normal(size=(4, D)) * 10
    x = centres[rng.integers(4, size=n)] + rng.normal(size=(n, D))
    return x.astype(np.float32)


def _fit(estimator, x):
    from oap_mllib_tpu import KMeans, PCA

    if estimator == "kmeans":
        model = KMeans(k=4, max_iter=3, seed=0).fit(x)
        assert model.summary.accelerated
        return model.cluster_centers_, model.summary.timings
    model = PCA(k=2).fit(x)
    return model.components_, model.summary["timings"]


@pytest.mark.parametrize("estimator", ["kmeans", "pca"])
def test_fit_leaves_the_callers_array_as_it_was(blobs, estimator):
    """The caller's array is what is uploaded, so nothing in a fit may
    write into it: it goes in read-only and comes out bit-identical."""
    before = blobs.tobytes()
    blobs.flags.writeable = False
    _, timings = _fit(estimator, blobs)
    copy = timings.root.node("table_convert/host_copy")
    assert copy.attrs["copied_bytes"] == 0 and copy.duration_s > 0
    assert blobs.tobytes() == before


@pytest.mark.parametrize("estimator", ["kmeans", "pca"])
def test_fit_result_does_not_depend_on_the_layout(blobs, estimator):
    """Zero passes (C order) and one pass (Fortran order) put the same
    bytes on the device: bit-equal models."""
    got_c, _ = _fit(estimator, blobs)
    got_f, timings = _fit(estimator, np.asfortranarray(blobs))
    copy = timings.root.node("table_convert/host_copy")
    assert copy.attrs["copied_bytes"] == blobs.nbytes
    assert np.asarray(got_c).tobytes() == np.asarray(got_f).tobytes()


class TestUploadInPieces:
    """A row shard of more than one piece goes up in pieces of bounded
    size, views of the caller's array (``data/table._put_rows``), each
    written IN PLACE into its device's one shard-sized buffer by the one
    writer (``_write`` / ``_write_piece``).  On a mesh of several
    devices: one piece a device in flight.  On ONE device: a few pieces
    in flight together.  Either way the same array as the one
    ``device_put``."""

    def _table(self, monkeypatch, x, piece_bytes, n_devices=4, in_flight=1):
        """(the table of ``x`` with pieces of ``piece_bytes`` — on one
        device ``in_flight`` of them together — and what the upload did:
        ``("put", the host array sent)`` and ``("wait", arrays waited
        for)`` in order)."""
        from oap_mllib_tpu.parallel.mesh import get_mesh

        self._limits(monkeypatch, piece_bytes, in_flight)
        events = []
        wait = table_mod.jax.block_until_ready
        put = table_mod.jax.device_put
        monkeypatch.setattr(
            table_mod.jax, "block_until_ready",
            lambda v: (
                events.append(("wait", len(jax.tree_util.tree_leaves(v)))),
                wait(v),
            )[1],
        )
        monkeypatch.setattr(
            table_mod.jax, "device_put",
            lambda v, where: (events.append(("put", v)), put(v, where))[1],
        )
        table = DenseTable.from_numpy(x, get_mesh(n_devices=n_devices))
        return table, events

    @staticmethod
    def _limits(monkeypatch, piece_bytes, in_flight=1):
        """Pieces of ``piece_bytes``, on one device ``in_flight`` of them
        together, in the module's own terms: the bytes a device has in
        flight and the pieces they go as on one device."""
        monkeypatch.setattr(
            table_mod, "_UPLOAD_PIECE_BYTES", piece_bytes * in_flight
        )
        monkeypatch.setattr(
            table_mod, "_ONE_DEVICE_PIECES_IN_FLIGHT", in_flight
        )

    @staticmethod
    def _piece_bytes(x, shard_rows, pieces):
        """A piece size that cuts ``shard_rows`` rows into ``pieces``
        pieces (a fraction leaves a shorter last piece; None: a row)."""
        row = x.nbytes // x.shape[0]
        return row * (1 if pieces is None else math.ceil(shard_rows / pieces))

    # pieces a shard: whole, halves, three with a ragged tail, one row each
    @pytest.mark.parametrize("pieces", [1, 2, 2.5, None])
    def test_same_array_whatever_the_piece(self, blobs, monkeypatch, pieces):
        from oap_mllib_tpu.parallel.mesh import data_sharding, get_mesh

        shard_rows = blobs.shape[0] // 4
        piece = self._piece_bytes(blobs, shard_rows, pieces)
        table, events = self._table(monkeypatch, blobs, piece)
        mesh = get_mesh(n_devices=4)
        assert table.data.sharding == data_sharding(mesh, 2)
        assert np.asarray(table.data).tobytes() == blobs.tobytes()
        assert np.asarray(table.mask).tolist() == [1.0] * blobs.shape[0]
        assert [s.data.shape for s in table.data.addressable_shards] == (
            [(shard_rows, blobs.shape[1])] * 4
        )
        # one piece a device in flight, the table's then the mask's (an
        # item a row), a device in turn: from the fourth piece on, each
        # waits for the oldest before the next goes; then the writer's
        # own wait for every device's table and mask
        waves = {1: 1, 2: 2, 2.5: 3, None: shard_rows}[pieces]
        mask_waves = -(-shard_rows // (piece // blobs.itemsize))
        waits = [n for what, n in events if what == "wait"]
        assert waits == [1] * (4 * (waves + mask_waves) - 3) + [8]

    # on ONE device: whole, halves, a ragged tail twice over, one row each
    @pytest.mark.parametrize("in_flight", [1, 3])
    @pytest.mark.parametrize("pieces", [1, 2, 2.5, 3.7, None])
    def test_one_device_table_is_what_device_put_makes(
        self, blobs, monkeypatch, pieces, in_flight
    ):
        from oap_mllib_tpu.parallel.mesh import data_sharding, get_mesh

        want = jax.device_put(blobs, data_sharding(get_mesh(n_devices=1), 2))
        piece = self._piece_bytes(blobs, blobs.shape[0], pieces)
        table, _ = self._table(
            monkeypatch, blobs, piece, n_devices=1, in_flight=in_flight
        )
        assert table.data.sharding == want.sharding
        assert table.data.dtype == want.dtype and table.data.shape == want.shape
        assert np.asarray(table.data).tobytes() == np.asarray(want).tobytes()
        assert np.asarray(table.mask).tolist() == [1.0] * blobs.shape[0]

    @pytest.mark.parametrize("pieces,in_flight", [
        (1, 1), (2, 2), (2, 1), (2.5, 1), (2.5, 2), (7, 3),
    ])
    def test_one_device_goes_up_as_it_did(
        self, blobs, monkeypatch, pieces, in_flight
    ):
        """No more than one piece: one ``device_put`` of the caller's
        array itself.  Over it: ``ceil(bytes / piece)`` puts, each a view
        of the caller's array and none larger than the piece, never more
        in flight than allowed — a piece counts until the wait for the
        table it was written into returns."""
        piece = self._piece_bytes(blobs, blobs.shape[0], pieces)
        table, events = self._table(
            monkeypatch, blobs, piece, n_devices=1, in_flight=in_flight
        )
        n = -(-blobs.nbytes // piece)
        assert n == math.ceil(pieces)
        sent = [v for what, v in events if what == "put" and v.ndim == 2]
        assert len(sent) == n
        if n == 1:
            assert sent[0] is blobs
        for part in sent:
            assert np.shares_memory(part, blobs) and part.flags.c_contiguous
            assert part.nbytes <= piece
        assert sum(part.shape[0] for part in sent) == blobs.shape[0]
        flying, most = 0, 0
        for what, v in events:
            if what == "put" and v.ndim == 2:
                flying += 1
                most = max(most, flying)
            elif what == "wait" and flying:
                flying -= 1
        assert most == min(n, in_flight)
        assert np.asarray(table.data).tobytes() == blobs.tobytes()

    def test_one_device_sends_four_pieces_together(self, blobs, monkeypatch):
        """The module's own numbers: 1 GiB a device in flight, as four
        pieces on one device."""
        assert table_mod._UPLOAD_PIECE_BYTES == 1 << 30
        assert table_mod._ONE_DEVICE_PIECES_IN_FLIGHT == 4
        monkeypatch.setattr(table_mod, "_UPLOAD_PIECE_BYTES", blobs.nbytes // 4)
        timings = Timings("test.fit")
        with phase_timer(timings, "table_convert"):
            table = DenseTable.from_numpy(blobs, get_mesh(n_devices=1))
        up = timings.root.node("table_convert/upload")
        assert up.attrs["pieces"] == 16 and up.attrs["shards"] == 1
        assert np.asarray(table.data).tobytes() == blobs.tobytes()

    @pytest.mark.parametrize("in_flight", [1, 2])
    def test_no_piece_outlives_the_upload(self, blobs, monkeypatch, in_flight):
        """After the upload the device holds the table and the mask: the
        pieces are gone, and the buffer each write was handed went INTO
        the next (donated), so nothing table-sized is left beside it."""
        import gc

        gc.collect()
        before = {id(a) for a in jax.live_arrays()}
        table, _ = self._table(
            monkeypatch, blobs, self._piece_bytes(blobs, blobs.shape[0], 5),
            n_devices=1, in_flight=in_flight,
        )
        gc.collect()
        new = [a for a in jax.live_arrays() if id(a) not in before]
        assert sorted(a.shape for a in new) == sorted(
            [table.data.shape, table.mask.shape]
        )
        assert {id(a) for a in new} == {id(table.data), id(table.mask)}

    @pytest.mark.parametrize("n_devices,pieces,in_flight", [
        (1, 1, 1), (1, 3, 1), (1, 3, 2), (1, 3, 3), (4, 1, 1), (4, 3, 1),
    ])
    def test_upload_span_says_what_went_up(
        self, blobs, monkeypatch, n_devices, pieces, in_flight
    ):
        from oap_mllib_tpu.parallel.mesh import get_mesh

        self._limits(
            monkeypatch,
            self._piece_bytes(blobs, blobs.shape[0] // n_devices, pieces),
            in_flight,
        )
        timings = Timings("test.fit")
        with phase_timer(timings, "table_convert"):
            table = DenseTable.from_numpy(blobs, get_mesh(n_devices=n_devices))
        up = timings.root.node("table_convert/upload")
        assert up.attrs == {
            "bytes": blobs.nbytes + blobs.shape[0] * blobs.itemsize,
            "shards": n_devices,
            "pieces": pieces,
            # the caller's array itself: nothing to cast, nothing padded
            "valid_rows": blobs.shape[0], "padded_rows": blobs.shape[0],
            "cast_bytes": 0, "cast_wait_s": 0, "cast_threads": 0,
        }
        assert up.duration_s > 0 and table.n_rows == blobs.shape[0]

    def test_a_model_axis_holds_replicas_of_the_pieces(self, blobs, monkeypatch):
        from oap_mllib_tpu.parallel.mesh import get_mesh

        monkeypatch.setattr(table_mod, "_UPLOAD_PIECE_BYTES", blobs.nbytes // 4)
        set_config(model_parallel=2)
        try:
            mesh = get_mesh(n_devices=4)
            table = DenseTable.from_numpy(blobs, mesh)
        finally:
            set_config(model_parallel=1)
        assert np.asarray(table.data).tobytes() == blobs.tobytes()
        half = blobs.shape[0] // 2
        assert sorted(s.index[0].start or 0 for s in table.data.addressable_shards) == (
            [0, 0, half, half]
        )

    def test_a_fit_through_the_pieces_copies_nothing(self, blobs, monkeypatch):
        from oap_mllib_tpu import KMeans
        from oap_mllib_tpu.utils import progcache

        whole = KMeans(k=4, max_iter=3, seed=0).fit(blobs)
        monkeypatch.setattr(
            table_mod, "_UPLOAD_PIECE_BYTES", blobs.nbytes // 24
        )
        pieced = KMeans(k=4, max_iter=3, seed=0).fit(blobs)
        root = pieced.summary.timings.root
        assert root.node("table_convert/host_copy").attrs["copied_bytes"] == 0
        assert root.node("table_convert/upload").attrs["shards"] == 8
        assert root.node("table_convert/upload").attrs["pieces"] == 4
        assert (
            pieced.cluster_centers_.tobytes() == whole.cluster_centers_.tobytes()
        )
        # every device's pieces are written in place by the one writer,
        # found again by the next table
        writes = lambda: dict(progcache.stats()["by_algo"]["table.write_piece"])
        before = writes()
        KMeans(k=4, max_iter=3, seed=0).fit(blobs)
        assert writes()["hits"] == before["hits"] + 1
        assert writes()["misses"] == before["misses"]

    @pytest.mark.parametrize("estimator", ["kmeans", "pca"])
    def test_a_one_device_fit_through_three_pieces(
        self, blobs, monkeypatch, estimator
    ):
        """On one device, three pieces, two in flight: nothing copied on
        the host, and the model of the one-``device_put`` fit, bit for
        bit."""
        from oap_mllib_tpu.models import kmeans as kmeans_mod
        from oap_mllib_tpu.models import pca as pca_mod
        from oap_mllib_tpu.parallel.mesh import get_mesh
        from oap_mllib_tpu.utils import progcache

        mesh = get_mesh(n_devices=1)
        monkeypatch.setattr(kmeans_mod, "get_mesh", lambda: mesh)
        monkeypatch.setattr(pca_mod, "get_mesh", lambda: mesh)
        whole, timings = _fit(estimator, blobs)
        assert timings.root.node("table_convert/upload").attrs["pieces"] == 1
        self._limits(
            monkeypatch, self._piece_bytes(blobs, blobs.shape[0], 3), 2
        )
        pieced, timings = _fit(estimator, blobs)
        up = timings.root.node("table_convert/upload")
        assert up.attrs["pieces"] == 3 and up.attrs["shards"] == 1
        copy = timings.root.node("table_convert/host_copy")
        assert copy.attrs["copied_bytes"] == 0
        assert np.asarray(pieced).tobytes() == np.asarray(whole).tobytes()
        # the writer is one program a backend, found again by the next table
        writes = lambda: dict(progcache.stats()["by_algo"]["table.write_piece"])
        before = writes()
        _fit(estimator, blobs)
        assert writes()["hits"] == before["hits"] + 1
        assert writes()["misses"] == before["misses"]


# -- the table cast, un-strided and padded UNDER the upload (ISSUE 33) -------

W = 16  # a width at which the mask is a sixteenth of the table
# how the caller's array differs from what can go up as it is:
# (its dtype, its layout, whether its rows sit on their bucket)
KINDS = {
    "f64_off_bucket": (np.float64, "c", False),
    "f64_on_bucket": (np.float64, "c", True),
    "f32_off_bucket": (np.float32, "c", False),
    "fortran": (np.float32, "fortran", False),
    "row_strided": (np.float32, "strided", False),
}


def _callers_array(rng, kind, bucket):
    src_dtype, layout, on_bucket = KINDS[kind]
    return _make(rng, bucket if on_bucket else bucket - 137, src_dtype, layout, W)


class TestCastUnderTheUpload:
    """A caller's array that cannot go up as it is, and whose padded shard
    is more than a device may have in flight, is never made whole on the
    host: ``_RowBlocks`` casts it a row block at a time by host threads
    while earlier blocks are in flight, sends the valid rows alone and
    writes them into shards that start as zeros made on their devices.
    The table is ``np.pad(x.astype(dtype))`` bit for bit."""

    def _build(self, monkeypatch, x, n_devices, piece_rows, in_flight=3):
        """(table, upload span, host_copy span, nbytes of every 2-D array
        the staging module allocated with ``np.empty``) with pieces of
        ``piece_rows`` rows — views of an array that goes up as it is,
        cast blocks of one that cannot — and ``in_flight`` of them a
        device: a shard of more than that is over what may be in flight."""
        monkeypatch.setattr(
            table_mod, "_UPLOAD_PIECE_BYTES",
            piece_rows * W * 4 * (in_flight if n_devices == 1 else 1),
        )
        monkeypatch.setattr(table_mod, "_ONE_DEVICE_PIECES_IN_FLIGHT", in_flight)
        monkeypatch.setattr(table_mod, "_CAST_BLOCK_BYTES", piece_rows * W * 4)
        monkeypatch.setattr(table_mod, "_CAST_RING_SLOTS", in_flight)
        made = []
        empty = np.empty
        monkeypatch.setattr(
            table_mod.np, "empty",
            lambda *a, **k: (made.append(empty(*a, **k)), made[-1])[1],
        )
        timings = Timings("test.fit")
        with phase_timer(timings, "table_convert"):
            table = DenseTable.from_numpy(
                x, get_mesh(n_devices=n_devices), np.float32
            )
        monkeypatch.setattr(table_mod.np, "empty", empty)
        return (
            table, timings.root.node("table_convert/upload"),
            timings.root.node("table_convert/host_copy"),
            [a.nbytes for a in made if a.ndim == 2],
        )

    @pytest.mark.parametrize("n_devices", [1, 4])
    @pytest.mark.parametrize("kind", sorted(KINDS))
    def test_table_is_the_plain_statement(self, rng, monkeypatch, kind, n_devices):
        bucket = n_devices * table_mod._ROW_MULTIPLE * 4
        x = _callers_array(rng, kind, bucket)
        n, before = x.shape[0], x.copy()
        # several pieces a shard and an uneven last one
        piece_rows = 100
        table, up, copy, made = self._build(monkeypatch, x, n_devices, piece_rows)

        assert table.n_rows == n and table.n_padded == bucket
        assert table.data.dtype == np.float32
        whole = np.asarray(table.data)
        want = np.zeros((bucket, W), np.float32)
        want[:n] = before.astype(np.float32)
        assert whole.tobytes() == want.tobytes()  # the pad rows are zero
        assert table.to_numpy().tobytes() == before.astype(np.float32).tobytes()
        mask = np.asarray(table.mask)
        assert mask.sum() == n and mask[:n].all()
        assert x.tobytes() == before.tobytes()
        # the host never held a table: no pass of its own, and for staging
        # a ring of three buffers a shard, a block each
        assert copy.attrs["copied_bytes"] == 0
        assert set(made) == {piece_rows * W * 4} and len(made) <= 3 * n_devices
        assert sum(made) < x.astype(np.float32).nbytes // 2
        shard_rows = bucket // n_devices
        assert up.attrs == {
            "bytes": n * W * 4 + bucket * 4,  # the valid rows, and the mask
            "shards": n_devices,
            "pieces": -(-min(n, shard_rows) // piece_rows),
            "valid_rows": n, "padded_rows": bucket,
            "cast_bytes": n * W * 4,
            "cast_wait_s": up.attrs["cast_wait_s"],
            "cast_threads": table_mod._cast_threads(),
        }
        assert 0 < up.attrs["cast_wait_s"] <= up.duration_s

    @pytest.mark.parametrize("n_devices", [1, 4])
    def test_as_is_input_reports_as_before(self, rng, monkeypatch, n_devices):
        """dtype, layout and bucket match: the caller's array itself, in
        views, and nothing cast."""
        bucket = n_devices * table_mod._ROW_MULTIPLE * 2
        x = (rng.normal(size=(bucket, W)) * 100).astype(np.float32)
        table, up, copy, made = self._build(monkeypatch, x, n_devices, 100)
        assert np.asarray(table.data).tobytes() == x.tobytes()
        assert copy.attrs["copied_bytes"] == 0 and not made
        assert up.attrs == {
            "bytes": x.nbytes + bucket * 4, "shards": n_devices,
            "pieces": -(-bucket // n_devices // 100),
            "valid_rows": bucket, "padded_rows": bucket,
            "cast_bytes": 0, "cast_wait_s": 0, "cast_threads": 0,
        }

    @pytest.mark.parametrize("n_devices", [1, 4])
    def test_a_shard_that_may_all_be_in_flight_is_staged_whole(
        self, rng, monkeypatch, n_devices
    ):
        """No device program's shape follows the valid rows there, so
        sizes that share a bucket share every program: ONE pass of its
        own into the padded table, as before."""
        bucket = n_devices * table_mod._ROW_MULTIPLE * 2
        x = _callers_array(rng, "f64_off_bucket", bucket)
        table, up, copy, made = self._build(
            monkeypatch, x, n_devices, bucket // n_devices
        )
        assert made == [bucket * W * 4] == [copy.attrs["copied_bytes"]]
        assert up.attrs["bytes"] == bucket * W * 4 + bucket * 4
        assert up.attrs["pieces"] == 1 and up.attrs["cast_bytes"] == 0
        assert up.attrs["valid_rows"] == x.shape[0]
        assert table.to_numpy().tobytes() == x.astype(np.float32).tobytes()

    def test_pad_past_whole_shards_is_made_on_the_device(self, rng, monkeypatch):
        """Valid rows that end inside the second of four shards: the third
        and fourth are zeros that never crossed the link."""
        bucket = 4 * table_mod._ROW_MULTIPLE * 4
        x = rng.normal(size=(bucket // 2 + 300, W))  # on the 4096 bucket
        assert table_mod._padded_row_target(x.shape[0], 1024) == bucket
        puts = []
        put = table_mod.jax.device_put
        monkeypatch.setattr(
            table_mod.jax, "device_put",
            lambda v, where: (puts.append(np.shape(v)), put(v, where))[1],
        )
        table, up, _, _ = self._build(monkeypatch, x, 4, 400)
        monkeypatch.setattr(table_mod.jax, "device_put", put)
        assert sum(s[0] for s in puts if len(s) == 2) == x.shape[0]
        assert np.asarray(table.data)[: x.shape[0]].tobytes() == (
            x.astype(np.float32).tobytes()
        )
        assert not np.asarray(table.data)[x.shape[0]:].any()
        assert np.asarray(table.mask).sum() == x.shape[0]

    def test_a_model_axis_casts_a_shard_once(self, rng, monkeypatch):
        bucket = 2 * table_mod._ROW_MULTIPLE * 2
        x = _callers_array(rng, "f64_off_bucket", bucket)
        monkeypatch.setattr(table_mod, "_UPLOAD_PIECE_BYTES", 100 * W * 4)
        monkeypatch.setattr(table_mod, "_CAST_BLOCK_BYTES", 100 * W * 4)
        set_config(model_parallel=2)
        try:
            timings = Timings("test.fit")
            with phase_timer(timings, "table_convert"):
                table = DenseTable.from_numpy(
                    x, get_mesh(n_devices=4), np.float32
                )
        finally:
            set_config(model_parallel=1)
        up = timings.root.node("table_convert/upload")
        assert up.attrs["cast_bytes"] == x.shape[0] * W * 4
        assert up.attrs["shards"] == 2
        assert table.to_numpy().tobytes() == x.astype(np.float32).tobytes()
        assert len(table.data.addressable_shards) == 4

    @pytest.mark.parametrize("n_devices", [1, 4])
    def test_nothing_but_table_and_mask_outlives_the_upload(
        self, rng, monkeypatch, n_devices
    ):
        import gc

        x = _callers_array(
            rng, "f64_off_bucket", n_devices * table_mod._ROW_MULTIPLE * 2
        )
        gc.collect()
        before = {id(a) for a in jax.live_arrays()}
        table, _, _, _ = self._build(monkeypatch, x, n_devices, 100)
        gc.collect()
        new = [a for a in jax.live_arrays() if id(a) not in before]
        assert {id(a) for a in new} == {id(table.data), id(table.mask)}


# -- every piece source through the one writer (``data/table._write``) -------

PIECE_ROWS = 100  # pieces and cast blocks of 100 rows of W float32
# source -> (devices, the caller's dtype): float32 goes up as it is in
# views, float64 off its bucket is cast in blocks; None: several arrays
# of which the host holds the first rows (``upload_arrays``)
SOURCES = {
    "as_is_one_device": (1, np.float32),
    "as_is_mesh": (8, np.float32),
    "cast_one_device": (1, np.float64),
    "cast_mesh": (8, np.float64),
    "held_rows": (1, None),
}


@pytest.mark.parametrize("source", sorted(SOURCES))
def test_every_source_goes_through_the_one_writer(rng, monkeypatch, source):
    """Views of an as-is array, cast blocks and arrays with held rows,
    on one device and on the 8-device mesh: the device arrays are
    ``np.pad(x.astype(dtype))`` byte for byte, and the upload span's
    ``pieces`` and ``bytes`` are what the geometry of 100-row pieces
    gives (a shard's pieces; the valid rows and the mask; for held rows
    every piece of every array, the last overlapping the one before)."""
    n_devices, src_dtype = SOURCES[source]
    piece = PIECE_ROWS * W * 4
    monkeypatch.setattr(
        table_mod, "_UPLOAD_PIECE_BYTES",
        piece * (table_mod._ONE_DEVICE_PIECES_IN_FLIGHT if n_devices == 1 else 1),
    )
    monkeypatch.setattr(table_mod, "_CAST_BLOCK_BYTES", piece)
    timings = Timings("test.fit")
    if src_dtype is None:
        rows, held = 1000, 250  # three pieces, the last from row 150
        hosts = [(rng.normal(size=(held, W)) * 100).astype(np.float32),
                 rng.integers(0, 9, (held, W)).astype(np.int32)]
        with phase_timer(timings, "table_convert"):
            out = table_mod.upload_arrays(
                hosts, jax.sharding.SingleDeviceSharding(jax.local_devices()[0]),
                rows=[rows, rows],
            )
        got, want = out, [np.pad(h, ((0, rows - held), (0, 0))) for h in hosts]
        pieces = 2 * 3
        sent = 2 * 3 * piece
    else:
        bucket = n_devices * table_mod._ROW_MULTIPLE * 2
        n = bucket if src_dtype == np.float32 else bucket - 137
        x = (rng.normal(size=(n, W)) * 100).astype(src_dtype)
        with phase_timer(timings, "table_convert"):
            table = DenseTable.from_numpy(
                x, get_mesh(n_devices=n_devices), np.float32
            )
        got = [table.data, table.mask]
        want = [np.pad(x.astype(np.float32), ((0, bucket - n), (0, 0))),
                (np.arange(bucket) < n).astype(np.float32)]
        pieces = -(-min(n, bucket // n_devices) // PIECE_ROWS)
        sent = n * W * 4 + bucket * 4
    for dev, host in zip(got, want):
        assert dev.dtype == host.dtype and dev.shape == host.shape
        assert np.asarray(dev).tobytes() == host.tobytes()
    up = timings.root.node("table_convert/upload")
    assert up.attrs["pieces"] == pieces and up.attrs["bytes"] == sent
    assert timings.root.node("table_convert/upload/put").attrs["bytes"] == sent
