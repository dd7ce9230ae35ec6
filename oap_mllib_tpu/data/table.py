"""Device-resident table abstractions.

Replaces the reference's native table layer:

- ``DenseTable`` ~ oneDAL ``HomogenNumericTable`` / ``RowMergedNumericTable``
  (built in OneDAL.scala:92-166 via per-partition memcpy + executor-local
  merge).  Here: one padded, row-sharded `jax.Array` plus valid-row count; a
  per-row validity mask replaces variable per-rank row counts.
- ``CSRTable`` ~ the one-based CSR table the reference builds for ALS
  (ALSDALImpl.scala:184-230, OneDAL.cpp:109-145).  Here: zero-based COO/CSR
  segment arrays padded to static shapes, the XLA-friendly sparse layout
  (gather/segment_sum instead of sparse BLAS).

Memory lifetime is JAX's (GC'd device buffers) — no explicit
``releaseNumericTables`` registry needed (reference OneDAL.scala:81-90);
``delete()`` is provided for eager HBM release on large tables.
"""

from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from oap_mllib_tpu.data.bucketing import bucket_rows
from oap_mllib_tpu.parallel.mesh import data_sharding
from oap_mllib_tpu.telemetry import spans
from oap_mllib_tpu.utils import progcache
from oap_mllib_tpu.utils.jax_compat import shard_map

# rows are padded per shard to this multiple (cheap: padding is masked)
_ROW_MULTIPLE = 256


def _padded_row_target(n: int, multiple: int) -> int:
    """Padded row count for an n-row table: the shape-bucketed target
    (geometric x2 buckets anchored at the shard multiple, so one
    compiled program serves every size in a bucket — data/bucketing.py)
    or the exact multiple when bucketing is off.  Bucketed counts are
    multiple * 2^j, i.e. highly divisible — which is exactly what
    auto_row_chunks / _accumulate_chunked want to see."""
    return bucket_rows(n, multiple)


def _stage_rows(x, multiple: int, dtype, shards: int = 0):
    """The host side of both ndarray constructors: what to upload, its
    valid-row count, and the bytes a pass of its own copied to make it.
    Decided from the input alone.  ``x`` itself (no allocation, no pass
    over the table) when its dtype matches, it is C-contiguous and its
    rows sit on their bucket.  Otherwise ONE pass into a fresh array of
    the bucket's shape — the assignment casts and un-strides, and only
    the tail rows are zeroed — unless, with ``shards`` the row shards of
    a one-process mesh, a padded shard is more than a device may have in
    flight: such a table is never made whole on the host, and goes up
    as ``_RowBlocks``, cast and padded under the upload (0 bytes)."""
    x = np.asarray(x)
    if x.ndim != 2:
        raise ValueError(f"expected 2-D data, got shape {x.shape}")
    dtype = np.dtype(dtype if dtype is not None else x.dtype)
    n = x.shape[0]
    # pad so every data-axis shard has equal rows AND, with bucketing on
    # (the default), so the padded count lands on a geometric bucket —
    # every fit whose rows share a bucket reuses one compiled program,
    # and the bucketed count's power-of-two chunk factors feed the
    # chunked Lloyd cleanly
    target = _padded_row_target(n, multiple)
    if x.dtype == dtype and x.flags.c_contiguous and n == target:
        return x, n, 0
    if shards and target // shards * x.shape[1] * dtype.itemsize > _UPLOAD_PIECE_BYTES:
        return _RowBlocks(x, target, dtype), n, 0
    padded = np.empty((target, x.shape[1]), dtype)
    padded[:n] = x
    padded[n:] = 0
    return padded, n, padded.nbytes


# Host-to-device bytes ONE device has in flight at a time.  Measured on a
# v5e host.  Four chips (PERF.md section 6, PR 27): the four 2.15 GB row
# shards of an 8.6 GB table put all at once — by one ``device_put`` under
# the row sharding or by four — land at 1.8-2.1 GB/s, their time spent
# mapping DMA buffers; the same shards in pieces of 1.07 GB, one a device
# in flight, at 25 GB/s, and in pieces of 268 MB at 24.  One chip
# (PERF.md section 6, PR 31): 8.6 GB in one ``device_put`` at 0.8-1.3
# GB/s; one 1 GiB piece at a time at 9.8 (2.1 GB whole: 10.4).
_UPLOAD_PIECE_BYTES = 1 << 30
# The pieces that GiB goes as where ONE device takes the table: in
# flight together, so that the host prepares one transfer under
# another's DMA — 2 x 512, 4 x 256 or 8 x 128 MiB land at 11.0-11.3
# GB/s (PR 31).  Devices that are several are those transfers already:
# four chips with two or eight pieces each in flight read 21.7 GB/s
# against 24.5 with one.
_ONE_DEVICE_PIECES_IN_FLIGHT = 4


def _put(host, to, send=None):
    """``jax.device_put(host, to)`` (or ``send`` in its place) as one
    entry of the upload's ``put`` leaf: the host seconds INSIDE the
    call, which returns before the bytes land, with what it was handed
    added to the leaf's ``attrs["bytes"]``."""
    with spans.child(spans.PUT) as span:
        out = (send or jax.device_put)(host, to)
        span.attrs["bytes"] = span.attrs.get("bytes", 0) + host.nbytes
    return out


def _land(arrays):
    """``jax.block_until_ready(arrays)`` as one entry of the upload's
    ``land`` leaf: the host seconds BLOCKED until bytes put earlier had
    landed, or an in-place write of them had finished."""
    with spans.child(spans.LAND):
        return jax.block_until_ready(arrays)


def _join_pieces(sharding):
    """The program that makes every device's row shard of the pieces it
    holds (one ``concatenate`` a device, no traffic), kept in the program
    registry: a fresh jit(shard_map) closure a table would recompile."""
    return progcache.get_or_build(
        "table.join_pieces",
        (progcache.mesh_fingerprint(sharding.mesh), tuple(sharding.spec)),
        lambda: jax.jit(
            shard_map(
                lambda pieces: jnp.concatenate(pieces, axis=0),
                mesh=sharding.mesh,
                in_specs=(sharding.spec,),
                out_specs=sharding.spec,
            )
        ),
    )


def _write_piece():
    """The program that writes a piece into a table at a row offset IN
    PLACE: the table-sized buffer is donated, so the output is the
    input's memory and the device holds the table and the pieces in
    flight, never the table twice.  Kept in the program registry like
    every jitted program of the package."""

    def write_piece(rows, piece, lo):
        return jax.lax.dynamic_update_slice_in_dim(rows, piece, lo, axis=0)

    return progcache.get_or_build(
        "table.write_piece", (progcache.backend_fingerprint(),),
        lambda: jax.jit(write_piece, donate_argnums=0),
    )


def _put_in_place(host: np.ndarray, sharding):
    """``_put_rows`` on ONE device, where joined pieces would hold the
    table twice: ``host`` goes up in row-block views of at most
    ``_UPLOAD_PIECE_BYTES`` in flight, ``_ONE_DEVICE_PIECES_IN_FLIGHT``
    of them together, each written into ONE table-sized buffer
    (``_write_piece``) and dropped before the next is sent."""
    step = max(
        1,
        _UPLOAD_PIECE_BYTES // _ONE_DEVICE_PIECES_IN_FLIGHT
        * host.shape[0] // max(host.nbytes, 1),
    )
    flying = collections.deque()  # (piece, its row offset), oldest first
    write = _write_piece()
    table = None

    def write_oldest(table):
        piece, lo = flying.popleft()
        if table is None:
            table = spans.launch(
                jnp.zeros, host.shape, piece.dtype, device=sharding
            )
        return spans.launch(write, table, piece, np.int32(lo))

    for lo in range(0, host.shape[0], step):
        flying.append((_put(host[lo:lo + step], sharding), lo))
        if len(flying) == _ONE_DEVICE_PIECES_IN_FLIGHT:
            table = write_oldest(table)
            # the oldest piece has landed, is written and is gone: room
            # for the next one, which goes while the others are in flight
            _land(table)
    while flying:
        table = write_oldest(table)
    return table, -(-host.shape[0] // step)


def _piece_rows(row_bytes: int) -> int:
    """Rows of one ``upload_arrays`` piece, for rows of ``row_bytes``."""
    return max(
        1, _UPLOAD_PIECE_BYTES // _ONE_DEVICE_PIECES_IN_FLIGHT // max(row_bytes, 1)
    )


def held_rows(rows: int, live: int, row_bytes: int) -> int:
    """How many rows of an array of ``rows`` rows of ``row_bytes``, of
    which only the first ``live`` hold anything and the rest are zeros,
    the host holds for ``upload_arrays``: the live rows alone where they
    fill at least one piece — the zeros behind them are made on the
    device and never cross the link —, else one piece's rows, or all
    ``rows`` where the whole array is under a piece (it goes up whole).
    Every piece of an array is then one piece long whatever ``live``
    is: a piece of another length would be another write program."""
    return min(rows, max(live, _piece_rows(row_bytes)))


def upload_arrays(hosts, sharding, rows=None):
    """The ``upload`` sub-span for host arrays that are no row table of
    their own — ALS's grouped edge layouts, eight arrays of one fit —
    onto ONE device: together they are several GB, and handed to
    ``jnp.asarray`` one after another they are all in flight at once,
    which is the host link's slow path (``_UPLOAD_PIECE_BYTES``).  Here
    they go up under ``_put_in_place``'s bounds, shared by all of them:
    views of ``_UPLOAD_PIECE_BYTES`` / ``_ONE_DEVICE_PIECES_IN_FLIGHT``
    along the first axis, that many in flight, each written in place
    into its array's ONE buffer (``_write_piece``, donated), which
    starts as zeros made on the device; an array of one piece that is
    the whole of it is that piece.

    ``rows``: the first-axis length of each device array (None: the
    host array's).  A host array may hold fewer, its first rows
    (``held_rows``): the rest of the device array is those zeros, and
    is never sent.  Every piece is a whole piece: the last ends at the
    held rows and overlaps the one before it, re-sending rows it wrote
    with the same bytes, so the pieces of arrays of one shape and dtype
    are one write program whatever rows the host holds.

    Returns the device arrays, landed.  ``attrs["bytes"]`` is what was
    sent (overlaps included), ``attrs["device_bytes"]`` what the device
    arrays hold, ``attrs["pieces"]`` in how many pieces, ``attrs
    ["arrays"]`` of how many arrays; the ``put`` / ``land`` / ``launch``
    leaves say what the host thread did meanwhile (``_upload``)."""
    piece_bytes = _UPLOAD_PIECE_BYTES // _ONE_DEVICE_PIECES_IN_FLIGHT
    rows = [h.shape[0] for h in hosts] if rows is None else list(rows)
    flying = collections.deque()  # (piece, its array, its offset or None)
    write = _write_piece()
    out = [None] * len(hosts)

    def write_oldest():
        piece, k, lo = flying.popleft()
        if lo is None:
            out[k] = piece
        else:
            if out[k] is None:
                out[k] = spans.launch(
                    jnp.zeros, (rows[k], *hosts[k].shape[1:]), piece.dtype,
                    device=sharding,
                )
            out[k] = spans.launch(write, out[k], piece, np.int32(lo))
        return out[k]

    with spans.child("upload") as span:
        pieces = sent = device_bytes = 0
        for k, host in enumerate(hosts):
            n = host.shape[0]
            row_bytes = host.dtype.itemsize * int(np.prod(host.shape[1:]))
            device_bytes += rows[k] * row_bytes
            whole = n == rows[k] and host.nbytes <= piece_bytes
            step = n if whole else min(n, _piece_rows(row_bytes))
            for lo in range(0, max(n, 1), max(step, 1)):
                lo = max(0, min(lo, n - step))
                piece = host[lo:lo + step]
                flying.append(
                    (_put(piece, sharding), k, None if whole else lo)
                )
                sent += piece.nbytes
                pieces += 1
                if len(flying) == _ONE_DEVICE_PIECES_IN_FLIGHT:
                    _land(write_oldest())
        while flying:
            write_oldest()
        _land(out)
        span.attrs["bytes"] = sent
        span.attrs["device_bytes"] = device_bytes
        span.attrs["pieces"] = pieces
        span.attrs["arrays"] = len(hosts)
    return out


# What the cast route (``_RowBlocks``) sends at a time, and how.  Its
# staging buffers are fresh memory, touched for the first time by the
# cast that fills them, and a v5e host without transparent hugepages
# faults 256 MiB in at about 0.1 s — three times what the cast itself
# takes.  So the ring is small.  3.2 GB of float32 out of float64, by
# block size x buffers a shard (PERF.md section 6, PR 33): 256 MiB x 4
# 0.63-0.70 s, 128 x 4 0.41-0.42, 64 x 4 0.38, **64 x 2 0.344-0.349**,
# 32 x 4 0.42, 16 x 8 0.66-0.70; the same four of 256 MiB kept from the
# table before, 0.335: the link's own rate.  ``device_put`` returns in a
# millisecond, so two buffers a shard keep the link busy: one in flight
# while the other is cast.  The cast is bound by the host's memory, not
# its cores, and NumPy's cast loop releases the GIL: at 64 MiB x 4,
# twelve threads read 0.379-0.381 s, eight 0.387-0.395 (256 MiB x 4:
# 0.63, 0.67, and 0.78 for four).
_CAST_BLOCK_BYTES = 64 << 20
_CAST_RING_SLOTS = 2
_CAST_THREADS_MAX = 12


def _cast_threads() -> int:
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:  # not Linux
        cores = os.cpu_count() or 1
    return max(1, min(_CAST_THREADS_MAX, cores))


class _RowBlocks:
    """The caller's array where it cannot go up as it is (dtype differs,
    not C-contiguous, or rows off their bucket) and its padded shard is
    more than a device may have in flight: never made whole on the host.
    ``put`` walks it in row blocks of ``_CAST_BLOCK_BYTES``, each cast /
    un-strided into a staging buffer by host threads (NumPy's own cast
    of the block, once: the table is bit for bit
    ``np.pad(x.astype(dtype), ...)``) and handed to ``device_put``, which
    returns at once, while earlier blocks are still in flight.  Every
    device's row shard starts as zeros made on that device and each block
    is written into it in place (``_write_piece``, donated): only the
    valid rows cross the link, the pad is what was never written, and a
    device holds its shard and the blocks in flight, never a shard twice.
    The staging buffers are a ring of ``_CAST_RING_SLOTS`` a shard, which
    is also what is in flight: far under ``_UPLOAD_PIECE_BYTES`` a
    device.

    ``shape`` is the padded table's, ``nbytes`` what crosses the link;
    after ``put``: ``cast_bytes`` (what the block casts wrote).  The
    seconds the sender stood waiting for block casts, sending nothing
    (the blocks in flight go on landing meanwhile), are the upload's
    ``cast`` leaf."""

    def __init__(self, x: np.ndarray, padded_rows: int, dtype):
        self.x = x
        self.dtype = np.dtype(dtype)
        self.shape = (padded_rows, x.shape[1])
        self.row_bytes = x.shape[1] * self.dtype.itemsize
        self.nbytes = x.shape[0] * self.row_bytes
        self.cast_bytes = 0
        self.cast_threads = _cast_threads()

    def _cast(self, pool, buf, lo):
        """``buf[:] = x[lo:lo + len(buf)]``, the rows shared out among the
        pool's threads."""
        cuts = np.linspace(0, buf.shape[0], self.cast_threads + 1).astype(int)
        with spans.child(spans.CAST):
            casts = [
                pool.submit(np.copyto, buf[a:b], self.x[lo + a:lo + b], "unsafe")
                for a, b in zip(cuts[:-1], cuts[1:]) if b > a
            ]
            for cast in casts:
                cast.result()
        self.cast_bytes += buf.nbytes

    def put(self, sharding):
        n, (rows, d) = self.x.shape[0], self.shape
        index = sharding.addressable_devices_indices_map(self.shape)
        # the devices of a model axis hold replicas of a row shard: one
        # cast for them all
        shards = collections.defaultdict(list)
        for dev, idx in index.items():
            shards[idx[0].indices(rows)[0]].append(dev)
        shard_rows = rows // len(shards)
        step = max(1, _CAST_BLOCK_BYTES // self.row_bytes)
        # (index in its shard, the shard's first row, rows) of every
        # block, a block of every shard in turn so that the devices'
        # transfers overlap
        blocks = sorted(
            (at, start, min(step, start + shard_rows - lo, n - lo))
            for start in shards
            for at, lo in enumerate(range(start, min(start + shard_rows, n), step))
        )
        slots = _CAST_RING_SLOTS * len(shards)
        ring = [
            np.empty((step, d), self.dtype) for _ in range(min(slots, len(blocks)))
        ]
        write = _write_piece()
        table = {
            dev: spans.launch(
                jnp.zeros, (shard_rows, d), self.dtype, device=dev
            )
            for dev in index
        }
        flying = collections.deque()  # (device, piece, row in its shard)

        def write_oldest():
            dev, piece, at = flying.popleft()
            table[dev] = spans.launch(write, table[dev], piece, np.int32(at))
            return table[dev]

        with concurrent.futures.ThreadPoolExecutor(self.cast_threads) as pool:
            for i, (at, start, height) in enumerate(blocks):
                if i >= slots:
                    # the block that had this staging buffer has landed
                    # and is written: the buffer is free to be cast into
                    _land(
                        [write_oldest() for _ in shards[blocks[i - slots][1]]]
                    )
                buf = ring[i % slots][:height]
                self._cast(pool, buf, start + at * step)
                for dev in shards[start]:
                    flying.append((dev, _put(buf, dev), at * step))
        while flying:
            write_oldest()
        return (
            jax.make_array_from_single_device_arrays(
                self.shape, sharding, [table[dev] for dev in index]
            ),
            max(1, -(-min(n, shard_rows) // step)),
        )


def _put_rows(host: np.ndarray, sharding):
    """(``jax.device_put(host, sharding)`` of a table every row of which
    this process holds, the pieces a row shard went up in).  In a world
    of several processes, and on one device for a table of at most
    ``_UPLOAD_PIECE_BYTES``, just that.  Else no device has more than
    ``_UPLOAD_PIECE_BYTES`` in flight, so that what is in flight does
    not grow with the table, and the pieces are views of ``host``
    (nothing is copied on the host).  One device: written in place into
    one buffer (``_put_in_place``), table + 1 GiB live.  Several: every
    device's row slice in pieces, one piece a device in flight; a shard
    of several pieces is joined on its device, where it is held twice
    until the pieces are dropped.  ``_RowBlocks`` (an array that needs a
    cast, an un-striding or a pad under those same bounds) puts itself."""
    if isinstance(host, _RowBlocks):
        return host.put(sharding)
    index = sharding.addressable_devices_indices_map(host.shape)
    if jax.process_count() > 1:
        return _put(host, sharding), 1
    if len(index) == 1:
        if host.nbytes <= _UPLOAD_PIECE_BYTES:
            return _put(host, sharding), 1
        return _put_in_place(host, sharding)
    slices = [(dev, host[idx]) for dev, idx in index.items()]
    shard_rows = slices[0][1].shape[0]
    step = max(1, _UPLOAD_PIECE_BYTES * host.shape[0] // max(host.nbytes, 1))
    pieces = []  # one global array a wave: that piece of every shard
    for lo in range(0, shard_rows, step):
        parts = [_put(rows[lo:lo + step], dev) for dev, rows in slices]
        _land(parts)
        shape = (host.shape[0] // shard_rows * parts[0].shape[0], *host.shape[1:])
        pieces.append(
            jax.make_array_from_single_device_arrays(shape, sharding, parts)
        )
    table = (
        pieces[0] if len(pieces) == 1
        else spans.launch(_join_pieces(sharding), pieces)
    )
    return table, len(pieces)


def _upload(put, padded, mask: np.ndarray, mesh, n_valid: int):
    """The ``upload`` sub-span of both constructors: ``put(host array,
    sharding)`` for the table and its mask, then the wait for both —
    ``device_put`` returns before the bytes land, and without the wait
    the rest of the upload is booked to whichever phase first blocks on
    the table.  ``attrs["bytes"]`` is what this process sent,
    ``attrs["shards"]`` the row shards the table was cut into (one a
    device of the data axis) and ``attrs["pieces"]`` the pieces each of
    them went up in; ``attrs["valid_rows"]`` of ``attrs["padded_rows"]``
    are the caller's.  Where the table was cast block by block under the
    transfers (``padded`` a ``_RowBlocks``): ``attrs["cast_bytes"]`` the
    casts wrote, by ``attrs["cast_threads"]`` threads, the sender
    waiting ``attrs["cast_wait_s"]`` for them; all 0 elsewhere.

    What the host thread did with the span's seconds is in its leaves
    (telemetry/spans.py): ``put`` inside the ``device_put`` calls,
    ``land`` blocked until bytes had landed or an in-place write had
    finished, ``cast`` blocked on a block's cast threads —
    ``attrs["cast_wait_s"]`` is that leaf's seconds, one clock reading
    in two views —, ``launch`` inside the calls that start ``jnp.zeros``,
    an in-place write or the join (a write returns after 2-6 ms while
    pieces are in flight, 0.3 ms otherwise).  What is left is the span's
    self time: Python."""
    with spans.child("upload") as span:
        data, pieces = put(padded, data_sharding(mesh, 2))
        mask_dev, _ = put(mask, data_sharding(mesh, 1))
        _land((data, mask_dev))
        span.attrs["bytes"] = padded.nbytes + mask.nbytes
        span.attrs["shards"] = mesh.shape[mesh.axis_names[0]]
        span.attrs["pieces"] = pieces
        span.attrs["valid_rows"] = n_valid
        span.attrs["padded_rows"] = padded.shape[0]
        for name in ("cast_bytes", "cast_threads"):
            span.attrs[name] = getattr(padded, name, 0)
        span.attrs["cast_wait_s"] = sum(
            c.duration_s for c in span.children if c.name == spans.CAST
        )
    return data, mask_dev


@dataclasses.dataclass
class DenseTable:
    """A row-sharded dense matrix with padded rows.

    ``data`` is (n_padded, d) sharded P(data, None) over the mesh;
    ``mask`` is (n_padded,) float (1.0 valid / 0.0 pad), sharded the same
    way so masked reductions stay local + psum.

    The constructors split the phase that calls them (``table_convert``)
    into two sub-spans (telemetry/spans.child): ``host_copy`` — whatever
    is made on the host BEFORE the upload begins: the mask, and, where
    there is one, the one pass that casts, un-strides and pads
    (``_stage_rows``) or densifies, with what that pass wrote in
    ``attrs["copied_bytes"]`` — and ``upload``, which ends when the
    bytes have LANDED (``block_until_ready``), not when ``device_put``
    returns, and carries what was sent in ``attrs["bytes"]``, the row
    shards in ``attrs["shards"]``, the pieces a shard went up in in
    ``attrs["pieces"]``, the caller's rows in ``attrs["valid_rows"]`` of
    the table's ``attrs["padded_rows"]``, and what was cast under it in
    ``attrs["cast_bytes"]``, ``["cast_wait_s"]`` and ``["cast_threads"]``
    (0 where nothing was).  The wait costs no wall where the caller's
    next statement depends on the table anyway (every in-memory fit's
    does).

    What a caller pays for its array (one process), decided from the
    array alone:

    - the table's dtype, C-contiguous, rows on their bucket: uploaded AS
      IS, with no host copy (``copied_bytes`` 0, ``cast_bytes`` 0).  A
      shard of more than 1 GiB goes up in row-block views of the array,
      1 GiB a device in flight (``_put_rows``): what is in flight does
      not grow with the table.  On one device the pieces are written in
      place into the table's one buffer — it holds table + 1 GiB, never
      the table twice; on a mesh a shard's pieces are joined on its
      device.  The array must not be mutated until the constructor
      returns; the program never writes into it;
    - another dtype (Spark's float64 rows), another layout, or rows off
      their bucket, and a padded shard of more than 1 GiB: cast,
      un-strided and padded UNDER the upload (``_RowBlocks``), 64 MiB
      blocks by host threads while earlier blocks are in flight.  No
      table-sized array is ever made on the host (``copied_bytes`` 0,
      ``cast_bytes`` = the valid rows in the table's dtype), only the
      valid rows cross the link, the pad rows are zeros made on the
      device, and each value is rounded once, by NumPy's own cast: the
      table is ``np.pad(x.astype(dtype), ...)`` bit for bit;
    - the same, and a padded shard of at most 1 GiB: ONE pass of its own
      into a padded host array (``copied_bytes`` = its size), put whole.
      No device program's shape follows the valid rows there, so fits
      whose sizes share a bucket share every compiled program; the
      blocks route compiles one small in-place write for the block its
      valid rows end in.

    Off the CPU ``data`` is a device buffer of its own from then on.  On
    the CPU backend ``device_put`` SHARES a host buffer that is 64-byte
    aligned instead of copying it (jax 0.9.0), so there an array
    uploaded as is and WHOLE must stay unchanged while the table lives —
    inside ``fit`` it does: the table dies with the fit.
    """

    data: jax.Array
    mask: jax.Array
    n_rows: int  # valid rows
    # multi-host bookkeeping (None for single-process tables): this
    # process's valid-row count, and every process's valid-row counts —
    # recorded so per-row vectors (sample weights) can be aligned to the
    # per-process padding layout and valid-row indices mapped into it
    local_valid: Optional[int] = None
    per_process_valid: Optional[np.ndarray] = None

    @property
    def n_padded(self) -> int:
        return self.data.shape[0]

    @property
    def n_features(self) -> int:
        return self.data.shape[1]

    @classmethod
    def from_numpy(cls, x: np.ndarray, mesh, dtype=None) -> "DenseTable":
        """The table of a host array (or SciPy matrix) ``x`` on ``mesh``,
        as ``dtype`` (``x``'s own when None).  At most one host pass
        over the rows: none when ``x`` can go up as it is, and none of
        its own when a large ``x`` is cast under the upload (class
        docstring).  Do not mutate ``x`` until this returns."""
        from oap_mllib_tpu.data import sparse as _sparse

        n_data = mesh.shape[mesh.axis_names[0]]
        with spans.child("host_copy") as span:
            if _sparse.is_sparse(x):
                # SciPy input: densify per row block straight into the
                # padded table (data/sparse.densify_into) — peak host
                # extra is the padded table + one block, never CSR + a
                # second full dense copy
                if x.ndim != 2:
                    raise ValueError(
                        f"expected 2-D data, got shape {x.shape}"
                    )
                n_valid = int(x.shape[0])
                target = _padded_row_target(n_valid, n_data * _ROW_MULTIPLE)
                out_dtype = np.dtype(
                    dtype if dtype is not None
                    else (x.dtype if x.dtype.kind == "f" else np.float64)
                )
                padded = np.zeros((target, int(x.shape[1])), out_dtype)
                _sparse.densify_into(padded, x, n_valid)
                copied = padded.nbytes
            else:
                padded, n_valid, copied = _stage_rows(
                    x, n_data * _ROW_MULTIPLE, dtype,
                    shards=n_data if jax.process_count() == 1 else 0,
                )
            span.attrs["copied_bytes"] = copied
            mask = np.zeros((padded.shape[0],), dtype=padded.dtype)
            mask[:n_valid] = 1.0
        data, mask = _upload(_put_rows, padded, mask, mesh, n_valid)
        return cls(data=data, mask=mask, n_rows=n_valid)

    @classmethod
    def from_process_local(cls, x_local: np.ndarray, mesh, dtype=None) -> "DenseTable":
        """Multi-host ingestion: each process contributes its LOCAL row shard
        and the result is one global row-sharded table spanning all hosts.

        This is the multi-host analog of the reference's per-executor table
        build (OneDAL.scala:92-166, where each executor converts only its
        partitions) — here `jax.make_array_from_process_local_data` stitches
        the per-host shards into a global array without any host ever
        holding the full table.  Every process must call this collectively
        with equally-shaped shards (pad the last host's shard with zero-
        weight rows).  In a single-process world it's identical to
        ``from_numpy``.
        """
        if jax.process_count() == 1:
            return cls.from_numpy(x_local, mesh, dtype)
        n_data = mesh.shape[mesh.axis_names[0]]
        local_devices = max(1, n_data // jax.process_count())
        with spans.child("host_copy"):
            # bucket per-process shards too: the allgathered max below
            # then lands on a bucket, so multi-host tables amortize
            # exactly like single-host ones (every process re-pads to
            # the common max)
            padded, n_valid_local, copied = _stage_rows(
                x_local, local_devices * _ROW_MULTIPLE, dtype
            )
        # Per-process shards pad independently, so valid-row counts landing
        # in different padding buckets (e.g. 100 vs 1100 rows) would yield
        # UNEQUAL local shapes — breaking both the global-shape inference of
        # make_array_from_process_local_data and the n_padded // nproc
        # layout math in valid_to_padded/align_weights.  Allgather the
        # actual padded sizes (alongside the exact valid counts — summing
        # the f32 mask on device loses integers past 2^24) and re-pad every
        # shard to the common max.  The allgather waits on the peers and
        # belongs to neither sub-span.
        from jax.experimental import multihost_utils

        gathered = np.asarray(
            multihost_utils.process_allgather(
                np.asarray([n_valid_local, padded.shape[0]], np.int64)
            )
        ).reshape(-1, 2)
        counts = gathered[:, 0]
        target = int(gathered[:, 1].max())
        with spans.child("host_copy") as span:
            if padded.shape[0] < target:
                padded = np.concatenate(
                    [padded,
                     np.zeros((target - padded.shape[0], padded.shape[1]),
                              padded.dtype)]
                )
                copied += padded.nbytes
            span.attrs["copied_bytes"] = copied
            mask_local = np.zeros((padded.shape[0],), dtype=padded.dtype)
            mask_local[:n_valid_local] = 1.0
        data, mask = _upload(
            lambda host, sharding: (
                _put(
                    host, sharding,
                    lambda h, s: jax.make_array_from_process_local_data(s, h),
                ), 1
            ),
            padded, mask_local, mesh, n_valid_local,
        )
        return cls(
            data=data,
            mask=mask,
            n_rows=int(counts.sum()),
            local_valid=n_valid_local,
            per_process_valid=counts,
        )

    def valid_to_padded(self, idx):
        """Map valid-row indices [0, n_rows) to padded-layout row indices.

        Single-process tables store valid rows contiguously (identity).
        Multi-host tables pad per process, so zero rows sit mid-array —
        sampling initial centers by global valid index must skip them
        (otherwise an all-zero padding row can become a centroid).
        """
        idx = np.asarray(idx)
        if self.per_process_valid is None:
            return idx
        local_padded = self.n_padded // len(self.per_process_valid)
        prefix = np.concatenate([[0], np.cumsum(self.per_process_valid)])
        proc = np.searchsorted(prefix, idx, side="right") - 1
        return proc * local_padded + (idx - prefix[proc])

    def align_weights(self, w: np.ndarray, mesh) -> jax.Array:
        """Per-row weights aligned to this table's padding layout.

        Single-process tables: ``w`` covers all ``n_rows`` valid rows and is
        padded with zeros to ``n_padded``.  Multi-host tables (built by
        ``from_process_local``): ``w`` is this process's LOCAL weights — the
        per-process zero padding sits in the middle of the global array, so
        weights must be stitched collectively with the same layout as the
        mask (they cannot be placed from a global vector).
        """
        w = np.asarray(w, dtype=np.dtype(self.mask.dtype))
        if self.local_valid is None:
            if w.shape[0] != self.n_rows:
                raise ValueError(
                    f"sample_weight has {w.shape[0]} rows, data has {self.n_rows}"
                )
            padded = np.zeros((self.n_padded,), dtype=w.dtype)
            padded[: self.n_rows] = w
            return jax.device_put(padded, data_sharding(mesh, 1))
        if w.shape[0] != self.local_valid:
            raise ValueError(
                f"sample_weight has {w.shape[0]} rows, this process's local "
                f"shard has {self.local_valid}"
            )
        local_padded = self.n_padded // jax.process_count()
        padded = np.zeros((local_padded,), dtype=w.dtype)
        padded[: self.local_valid] = w
        return jax.make_array_from_process_local_data(
            data_sharding(mesh, 1), padded
        )

    def to_numpy(self) -> np.ndarray:
        """Gather valid rows back to host (reverse data plane,
        ~ numericTableToVectors, OneDAL.scala:37-52)."""
        return np.asarray(self.data)[: self.n_rows]

    def delete(self) -> None:
        """Eagerly drop device buffers (~ cFreeDataMemory, OneDAL.cpp:83-89)."""
        self.data.delete()
        self.mask.delete()


@dataclasses.dataclass
class CSRTable:
    """A sparse ratings block in padded COO form with CSR row offsets.

    Arrays are host-or-device; all zero-based (the reference's one-based CSR
    is a oneDAL requirement, OneDAL.cpp:123-126 — not carried over).

    - ``rows``/``cols``: (nnz_padded,) int32 indices; padding entries point
      at row ``n_rows`` (one past the end) so segment ops drop them.
    - ``values``: (nnz_padded,) float32.
    - ``row_offsets``: (n_rows + 1,) int32 CSR offsets over the *valid* nnz.
    - ``nnz``: valid entry count.
    """

    rows: jax.Array
    cols: jax.Array
    values: jax.Array
    row_offsets: jax.Array
    n_rows: int
    n_cols: int
    nnz: int

    @classmethod
    def from_coo(
        cls,
        rows: np.ndarray,
        cols: np.ndarray,
        values: np.ndarray,
        n_rows: int,
        n_cols: int,
        nnz_padded: Optional[int] = None,
    ) -> "CSRTable":
        """Build from COO triples; sorts by (row, col) like the reference's
        post-shuffle sort (ALSShuffle.cpp:111)."""
        rows = np.asarray(rows, dtype=np.int32)
        cols = np.asarray(cols, dtype=np.int32)
        values = np.asarray(values, dtype=np.float32)
        if len(rows) and (rows.max() >= n_rows or rows.min() < 0):
            raise ValueError(f"row index out of range [0, {n_rows})")
        if len(cols) and (cols.max() >= n_cols or cols.min() < 0):
            raise ValueError(f"col index out of range [0, {n_cols})")
        order = np.lexsort((cols, rows))
        rows, cols, values = rows[order], cols[order], values[order]
        nnz = len(values)
        counts = np.bincount(rows, minlength=n_rows)
        row_offsets = np.zeros((n_rows + 1,), dtype=np.int32)
        np.cumsum(counts, out=row_offsets[1:])
        if nnz_padded is not None and nnz_padded > nnz:
            pad = nnz_padded - nnz
            rows = np.concatenate([rows, np.full((pad,), n_rows, np.int32)])
            cols = np.concatenate([cols, np.zeros((pad,), np.int32)])
            values = np.concatenate([values, np.zeros((pad,), np.float32)])
        return cls(
            rows=jnp.asarray(rows),
            cols=jnp.asarray(cols),
            values=jnp.asarray(values),
            row_offsets=jnp.asarray(row_offsets),
            n_rows=n_rows,
            n_cols=n_cols,
            nnz=nnz,
        )

    def to_dense(self) -> np.ndarray:
        out = np.zeros((self.n_rows, self.n_cols), dtype=np.float32)
        r = np.asarray(self.rows)[: self.nnz]
        c = np.asarray(self.cols)[: self.nnz]
        v = np.asarray(self.values)[: self.nnz]
        out[r, c] = v
        return out
