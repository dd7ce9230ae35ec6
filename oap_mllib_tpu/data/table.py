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
import itertools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from oap_mllib_tpu.data.bucketing import bucket_rows
from oap_mllib_tpu.parallel.mesh import data_sharding
from oap_mllib_tpu.telemetry import spans
from oap_mllib_tpu.utils import progcache

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
# in flight, at 25 GB/s, and in pieces of 268 MB at 24 (written in place,
# 256 MiB pieces read 0.405 s against 0.352 for 1 GiB).  One chip
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


def _geometry(devices: int, cast: bool, row_bytes: int):
    """(rows a piece, pieces a row shard has in flight) of an upload of
    rows of ``row_bytes`` onto ``devices`` devices: the one rule.  Rows
    cast under the upload go in ``_CAST_BLOCK_BYTES`` blocks, a ring of
    ``_CAST_RING_SLOTS`` a shard; rows as they are in
    ``_UPLOAD_PIECE_BYTES`` a device, on one device as
    ``_ONE_DEVICE_PIECES_IN_FLIGHT`` pieces in flight together."""
    if cast:
        piece, in_flight = _CAST_BLOCK_BYTES, _CAST_RING_SLOTS
    elif devices == 1:
        in_flight = _ONE_DEVICE_PIECES_IN_FLIGHT
        piece = _UPLOAD_PIECE_BYTES // in_flight
    else:
        piece, in_flight = _UPLOAD_PIECE_BYTES, 1
    return max(1, piece // max(row_bytes, 1)), in_flight


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


def _zeros(device):
    """The program that makes ``jnp.zeros(shape, dtype)`` ON ``device``
    (a device or a one-device sharding), kept in the program registry.
    ``jnp.zeros(device=)`` fills on the default device and copies: on a
    mesh the first device held the others' zeros too (9 GiB on the
    four-chip cell's first chip: PERF.md section 6)."""
    if not isinstance(device, jax.sharding.Sharding):
        device = jax.sharding.SingleDeviceSharding(device)
    return progcache.get_or_build(
        "table.zeros", (progcache.backend_fingerprint(), device),
        lambda: jax.jit(jnp.zeros, static_argnums=(0, 1), out_shardings=device),
    )


def _write(buffers, pieces, in_flight: int):
    """THE upload writer: every table and layout goes up through it.

    ``buffers``: (shape, dtype, device) of each device buffer to fill;
    ``pieces``: an ordered stream of (host array, the buffers it goes
    into — a shard's replicas on a model axis take one piece —, its row
    offset there).  Each piece is put at once; at most ``in_flight`` are
    outstanding, and when that many are, the oldest is written into its
    buffers IN PLACE (``_write_piece``, donated) and landed before the
    next is put.  A buffer starts as zeros made on its device, so rows
    no piece covers never cross the link, unless it is exactly one
    piece: that piece IS the buffer, no write.

    Returns (the buffers, landed; the pieces each took; bytes sent)."""
    write = _write_piece()
    out = [None] * len(buffers)
    taken = [0] * len(buffers)
    sent = 0
    flying = collections.deque()  # (device pieces, their buffers, offset)

    def zeros(k):
        shape, dtype, device = buffers[k]
        return spans.launch(_zeros(device), shape, np.dtype(dtype))

    def write_oldest():
        parts, into, lo = flying.popleft()
        for part, k in zip(parts, into):
            if part.shape == buffers[k][0]:
                out[k] = part
            else:
                out[k] = spans.launch(write, out[k], part, np.int32(lo))
        return [out[k] for k in into]

    for host, into, lo in pieces:
        for k in into:
            if out[k] is None and host.shape != buffers[k][0]:
                # made under the first piece's transfer; made at the first
                # write it cost the cast route 7 ms (PERF.md section 6)
                out[k] = zeros(k)
        flying.append(([_put(host, buffers[k][2]) for k in into], into, lo))
        sent += host.nbytes
        for k in into:
            taken[k] += 1
        if len(flying) == in_flight:
            # the oldest piece has landed, is written and is gone: room
            # for the next one, which goes while the others are in flight
            _land(write_oldest())
    while flying:
        write_oldest()
    # a buffer no piece reached holds pad rows alone
    out = [zeros(k) if b is None else b for k, b in enumerate(out)]
    return _land(out), taken, sent


def _row_walk(n: int, starts, shard_rows: int, step: int):
    """(a shard's first row, a piece's first row in its shard, the
    piece's rows) of every piece of ``step`` rows that holds some of the
    first ``n`` rows of shards of ``shard_rows`` rows starting at
    ``starts``: a piece of every shard in turn, so that the devices'
    transfers overlap."""
    for at in range(0, shard_rows, step):
        for start in starts:
            rows = min(step, shard_rows - at, n - start - at)
            if rows > 0:
                yield start, at, rows


def _views(host: np.ndarray, shards, shard_rows: int, step: int):
    """The pieces of an array that goes up as it is: row views of it,
    nothing copied on the host (the array itself where one piece is all
    of it)."""
    for start, at, rows in _row_walk(host.shape[0], shards, shard_rows, step):
        lo = start + at
        view = host if rows == host.shape[0] else host[lo:lo + rows]
        yield view, shards[start], at


def held_rows(rows: int, live: int, row_bytes: int) -> int:
    """How many rows of an array of ``rows`` rows of ``row_bytes``, of
    which only the first ``live`` hold anything and the rest are zeros,
    the host holds for ``upload_arrays``: the live rows alone where they
    fill at least one piece — the zeros behind them are made on the
    device and never cross the link —, else one piece's rows, or all
    ``rows`` where the whole array is under a piece (it goes up whole).
    Every piece of an array is then one piece long whatever ``live``
    is: a piece of another length would be another write program."""
    return min(rows, max(live, _geometry(1, False, row_bytes)[0]))


def upload_arrays(hosts, sharding, rows=None):
    """The ``upload`` sub-span for host arrays that are no row table of
    their own — ALS's grouped edge layouts, eight arrays of one fit —
    onto ONE device: together they are several GB, and handed to
    ``jnp.asarray`` one after another they are all in flight at once,
    which is the host link's slow path (``_UPLOAD_PIECE_BYTES``).  Here
    they go through ``_write`` under one device's ``_geometry``, shared
    by all of them, each into its array's ONE buffer.

    ``rows``: the first-axis length of each device array (None: the
    host array's).  A host array may hold fewer, its first rows
    (``held_rows``): the rest of the device array is zeros made there,
    and is never sent.  Every piece is a whole piece: the last ends at
    the held rows and overlaps the one before it, re-sending rows it
    wrote with the same bytes, so the pieces of arrays of one shape and
    dtype are one write program whatever rows the host holds.

    Returns the device arrays, landed.  ``attrs["bytes"]`` is what was
    sent (overlaps included), ``attrs["device_bytes"]`` what the device
    arrays hold, ``attrs["pieces"]`` in how many pieces, ``attrs
    ["arrays"]`` of how many arrays; the ``put`` / ``land`` / ``launch``
    leaves say what the host thread did meanwhile (``_upload``)."""
    rows = [h.shape[0] for h in hosts] if rows is None else list(rows)

    def pieces():
        for k, host in enumerate(hosts):
            n = host.shape[0]
            step = max(1, min(n, _geometry(1, False, host[:1].nbytes)[0]))
            for lo in range(0, max(n, 1), step):
                lo = max(0, min(lo, n - step))
                yield host[lo:lo + step], (k,), lo

    with spans.child("upload") as span:
        out, taken, sent = _write(
            [((r, *h.shape[1:]), h.dtype, sharding) for h, r in zip(hosts, rows)],
            pieces(), _geometry(1, False, 0)[1],
        )
        span.attrs["bytes"] = sent
        span.attrs["device_bytes"] = sum(a.nbytes for a in out)
        span.attrs["pieces"] = sum(taken)
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
    ``pieces`` walks its valid rows in blocks of ``_CAST_BLOCK_BYTES``,
    each cast / un-strided into a staging buffer by host threads
    (NumPy's own cast of the block, once: the table is bit for bit
    ``np.pad(x.astype(dtype), ...)``) and handed to ``_write``, which
    writes it into shards that start as zeros on their devices: only the
    valid rows cross the link, and the pad is what was never written.
    The staging buffers are a ring as long as what ``_write`` keeps in
    flight, so a buffer is cast into again only once the block that held
    it has landed.

    ``shape`` is the padded table's, ``nbytes`` what crosses the link;
    after the upload: ``cast_bytes`` (what the block casts wrote).  The
    seconds the sender stood waiting for block casts, sending nothing
    (the blocks in flight go on landing meanwhile), are the upload's
    ``cast`` leaf."""

    def __init__(self, x: np.ndarray, padded_rows: int, dtype):
        self.x = x
        self.dtype = np.dtype(dtype)
        self.shape = (padded_rows, x.shape[1])
        self.nbytes = x.shape[0] * x.shape[1] * self.dtype.itemsize
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

    def pieces(self, shards, shard_rows: int, step: int, slots: int):
        """``_views`` of the cast table: its valid rows in blocks of
        ``step`` rows, each cast into the next of ``slots`` staging
        buffers — ``_write``'s ``in_flight``, which has landed the block
        that last held a buffer before it asks for the block after."""
        blocks = list(_row_walk(self.x.shape[0], shards, shard_rows, step))
        ring = [
            np.empty((step, self.shape[1]), self.dtype)
            for _ in range(min(slots, len(blocks)))
        ]
        with concurrent.futures.ThreadPoolExecutor(self.cast_threads) as pool:
            for i, (start, at, rows) in enumerate(blocks):
                buf = ring[i % slots][:rows]
                self._cast(pool, buf, start + at)
                yield buf, shards[start], at


def _put_whole(padded, mask, mesh, send=None):
    """(table, mask, 1): one ``device_put`` each (or ``send`` in its
    place), landed — the upload of a world of several processes."""
    return (*_land((
        _put(padded, data_sharding(mesh, 2), send),
        _put(mask, data_sharding(mesh, 1), send),
    )), 1)


def _put_rows(padded, mask, mesh):
    """(table, mask, the pieces a row shard of the table went up in) of a
    table every row of which this process holds.  In a world of several
    processes ``_put_whole``.  Else both go through ``_write``, the
    table's row shards first, a piece of every shard in turn, then the
    mask's, at the table's ``_geometry``: what is in flight does not
    grow with the table, and every device's shard is a buffer of its
    own, written in place — a device holds its shard and the pieces in
    flight, never a shard twice.  The pieces are views of ``padded``
    (nothing is copied on the host), or, for a ``_RowBlocks``, its cast
    blocks."""
    if jax.process_count() > 1:
        return _put_whole(padded, mask, mesh)
    table_sharding, mask_sharding = data_sharding(mesh, 2), data_sharding(mesh, 1)
    index = table_sharding.addressable_devices_indices_map(padded.shape)
    devices = len(index)
    # {a shard's first row: its buffers} — the devices of a model axis
    # hold replicas of one shard, which take one piece
    starts = [idx[0].indices(padded.shape[0])[0] for idx in index.values()]
    shards = {s: [k for k, t in enumerate(starts) if t == s] for s in starts}
    marks = {s: [k + devices for k in ks] for s, ks in shards.items()}
    shard_rows = padded.shape[0] // len(shards)
    buffers = [
        ((shard_rows, *a.shape[1:]), a.dtype, dev)
        for a in (padded, mask) for dev in index
    ]
    cast = isinstance(padded, _RowBlocks)
    step, in_flight = _geometry(
        devices, cast, padded.shape[1] * padded.dtype.itemsize
    )
    in_flight *= len(shards)
    table = (
        padded.pieces(shards, shard_rows, step, in_flight) if cast
        else _views(padded, shards, shard_rows, step)
    )
    mask_step = _geometry(devices, False, mask.itemsize)[0]
    out, taken, _ = _write(
        buffers,
        itertools.chain(table, _views(mask, marks, shard_rows, mask_step)),
        in_flight,
    )
    return (
        jax.make_array_from_single_device_arrays(
            padded.shape, table_sharding, out[:devices]
        ),
        jax.make_array_from_single_device_arrays(
            mask.shape, mask_sharding, out[devices:]
        ),
        max(1, *taken[:devices]),
    )


def _upload(put, padded, mask: np.ndarray, mesh, n_valid: int):
    """The ``upload`` sub-span of both constructors: ``put(table, mask,
    mesh)`` sends the table and its mask and waits for both —
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
    in two views —, ``launch`` inside the calls that start ``jnp.zeros``
    or an in-place write (a write returns after 2-6 ms while pieces are
    in flight, 0.3 ms otherwise).  What is left is the span's self time:
    Python."""
    with spans.child("upload") as span:
        data, mask_dev, pieces = put(padded, mask, mesh)
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
      IS, with no host copy (``copied_bytes`` 0, ``cast_bytes`` 0), in
      row-block views of the array, 1 GiB a device in flight
      (``_put_rows``): what is in flight does not grow with the table.
      A shard of more than one piece (256 MiB on one device, 1 GiB a
      device on a mesh) is written in place into its device's one
      buffer — a device holds its shard + 1 GiB, never the shard twice.
      The array must not be mutated until the constructor returns; the
      program never writes into it;
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
      into a padded host array (``copied_bytes`` = its size), which
      goes up as the first case's does.
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
            lambda padded, mask, mesh: _put_whole(
                padded, mask, mesh,
                lambda h, s: jax.make_array_from_process_local_data(s, h),
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
