"""Double-buffered async chunk prefetch: overlap host staging with device
compute on every streamed path.

Every streamed route (K-Means/PCA passes in ops/stream_ops.py, ALS edge
uploads in ops/als_stream.py and ops/als_block_stream.py) used to be
strictly serial per chunk: pull from the source, pad/convert on host,
``device_put``, dispatch the step, repeat.  The device sat idle through
each chunk's staging and the host sat idle through each chunk's compute.
This module is the shared communication-hiding
stage (cf. arxiv 2112.01075's transfer/compute overlap): a bounded
background thread runs the host half of the pipeline up to
``Config.prefetch_depth`` chunks ahead of the consumer, so chunk N+1's
staging and transfer issue while chunk N's step is still executing.

Contracts:

- **Order and math are untouched.**  Chunks reach the consumer in source
  order whatever the depth; depth only moves WHEN staging happens, so
  results are bit-identical across depths (and depth=1 runs the exact
  pre-pipeline serial loop, no thread at all).
- **Bounded memory.**  The producer owns a semaphore slot per staged
  chunk, acquired BEFORE pulling from the source and released when the
  consumer retires the chunk — the pipeline never holds more than
  ``depth`` staged chunks (queued + consumer-held) nor runs the source
  more than ``depth`` pulls ahead.
- **Fail-fast multi-process semantics.**  A staging failure (source
  error, conversion error, device_put OOM) is captured in the producer
  and re-raised from the consumer's next ``__next__`` — which sits inside
  the caller's ``_PassGuard`` block, so the error rides the next
  collective reduction and every rank fails together instead of peers
  hanging in process_allgather (ops/stream_ops._PassGuard).
- **Buffer retirement.**  With ``retire=True`` the jax arrays of the
  previously consumed chunk are ``delete()``d when the consumer advances
  (the runtime frees them once in-flight steps finish) — the streamed
  paths' donation analog: the consumed chunk's HBM returns to the pool
  immediately instead of at garbage collection, keeping peak device
  memory at O(depth x chunk) even under allocator pressure.
- **Clean shutdown.**  ``close()`` (or the context-manager exit) cancels
  the producer and drains it; abandoning the iterator mid-pass (an early
  break, an exception in the consumer) cannot leave a thread blocked on
  the queue.
"""

from __future__ import annotations

import contextlib
import logging
import queue
import threading
from typing import Any, Callable, Iterable, Iterator, Optional

from oap_mllib_tpu.config import get_config
from oap_mllib_tpu.telemetry import flightrec
from oap_mllib_tpu.telemetry import metrics as _tm
from oap_mllib_tpu.utils import sanitizers
from oap_mllib_tpu.utils.faults import maybe_fault
from oap_mllib_tpu.utils.timing import tick

log = logging.getLogger("oap_mllib_tpu")


def resolve_depth(depth: Optional[int] = None) -> int:
    """The effective prefetch depth: the argument if given, else
    ``Config.prefetch_depth`` (env ``OAP_MLLIB_TPU_PREFETCH_DEPTH``)."""
    d = get_config().prefetch_depth if depth is None else depth
    d = int(d)
    if d < 1:
        raise ValueError(f"prefetch depth must be >= 1, got {d}")
    return d


class PrefetchStats:
    """Per-pipeline accounting for the stage/transfer/compute split.

    - ``stage_s``: host time inside the stage callable (pad/convert +
      transfer dispatch).
    - ``transfer_s``: the portion of ``stage_s`` spent issuing device
      transfers (stage callables wrap their ``device_put`` in
      :meth:`transfer`); dispatch time, not DMA completion — the async
      runtime overlaps the DMA itself.
    - ``wait_s``: time the CONSUMER spent blocked waiting for a staged
      chunk.  Serial (depth=1) this equals ``stage_s``; with overlap it
      shrinks toward zero — the visible win.
    - ``chunks``: chunks that reached the consumer.
    - ``bytes_staged`` / ``rows``: payload staged through the pipeline —
      total array bytes of every staged item and the (padded) row count
      of its leading 2-D array — the per-pass throughput denominators
      the telemetry registry exports.
    - ``leaked_threads``: producer threads that failed to join within
      the shutdown timeout (daemon threads, so the process still exits,
      but a nonzero count means a stage callable is wedged — logged
      with the pending site and asserted zero in tests).

    :meth:`finalize` writes the split into a ``Timings`` registry as
    ``<prefix>/stage`` (host-only), ``<prefix>/transfer``,
    ``<prefix>/compute`` (= pass wall - wait) and ``<prefix>/stream_wall``
    so ``Timings.overlap_efficiency`` / bench.py can report how much
    staging was hidden behind compute — and mirrors the whole split into
    the process metrics registry (telemetry/metrics.py,
    ``oap_prefetch_*`` / ``oap_stream_*`` labelled by phase).
    """

    __slots__ = ("stage_s", "transfer_s", "wait_s", "chunks",
                 "bytes_staged", "rows", "leaked_threads")

    def __init__(self) -> None:
        self.stage_s = 0.0
        self.transfer_s = 0.0
        self.wait_s = 0.0
        self.chunks = 0
        self.bytes_staged = 0
        self.rows = 0
        self.leaked_threads = 0

    @contextlib.contextmanager
    def transfer(self):
        elapsed = tick()
        try:
            yield
        finally:
            self.transfer_s += elapsed()

    def note_staged(self, item: Any) -> None:
        """Account one staged item's payload (producer side): sum the
        array bytes it carries and the row count of its leading 2-D
        array (padded rows — what the device actually processes)."""
        b, r = _payload_size(item)
        self.bytes_staged += b
        self.rows += r

    def finalize(self, timings, prefix: str, wall: float) -> None:
        """Record this pipeline's split under ``prefix`` (accumulates
        across passes — Timings.as_dict sums duplicate phases) and
        mirror it into the process metrics registry."""
        lab = {"phase": prefix}
        _tm.counter("oap_prefetch_stage_seconds_total", lab,
                    help="Host staging wall (pad/convert, transfer excluded)"
                    ).inc(max(self.stage_s - self.transfer_s, 0.0))
        _tm.counter("oap_prefetch_transfer_seconds_total", lab,
                    help="Device-transfer dispatch wall inside staging"
                    ).inc(self.transfer_s)
        _tm.counter("oap_prefetch_wait_seconds_total", lab,
                    help="Consumer wall blocked waiting for a staged chunk"
                    ).inc(self.wait_s)
        _tm.counter("oap_prefetch_compute_seconds_total", lab,
                    help="Pass wall not spent waiting on staging"
                    ).inc(max(wall - self.wait_s, 0.0))
        _tm.counter("oap_prefetch_chunks_total", lab,
                    help="Chunks that reached the consumer").inc(self.chunks)
        _tm.counter("oap_stream_bytes_staged_total", lab,
                    help="Array bytes staged through the pipeline"
                    ).inc(self.bytes_staged)
        _tm.counter("oap_stream_rows_total", lab,
                    help="Padded rows staged through the pipeline"
                    ).inc(self.rows)
        if timings is None:
            return
        timings.add(prefix + "/stage", max(self.stage_s - self.transfer_s, 0.0))
        timings.add(prefix + "/transfer", self.transfer_s)
        timings.add(prefix + "/compute", max(wall - self.wait_s, 0.0))
        timings.add(prefix + "/stream_wall", wall)


def _payload_size(item: Any) -> tuple:
    """(total array bytes, leading-2-D-array rows) of a staged item —
    tuples/lists walked recursively, scalars ignored.  Rows count the
    FIRST matrix found (the data chunk; masks/weights ride along but do
    not double-count rows)."""
    nbytes = 0
    rows = 0
    stack = [item]
    while stack:
        v = stack.pop()
        if isinstance(v, (tuple, list)):
            stack.extend(reversed(v))
            continue
        b = getattr(v, "nbytes", None)
        shape = getattr(v, "shape", None)
        if b is None or shape is None:
            continue
        nbytes += int(b)
        if rows == 0 and len(shape) >= 2:
            rows = int(shape[0])
    return nbytes, rows


def _delete_jax_arrays(item: Any) -> None:
    """Best-effort ``delete()`` of every jax array inside a staged item
    (tuples/lists walked recursively; host np arrays untouched).  The
    runtime defers the actual free until in-flight steps consuming the
    buffer complete, so retiring immediately after the consumer advances
    is safe."""
    if isinstance(item, (tuple, list)):
        for v in item:
            _delete_jax_arrays(v)
        return
    delete = getattr(item, "delete", None)
    if delete is not None and hasattr(item, "is_deleted"):
        try:
            if not item.is_deleted():
                delete()
        except Exception:
            pass  # freeing is an optimization; never fail a pass over it


class _Serial:
    """depth=1: the exact pre-pipeline loop — stage inline on demand, no
    thread.  Kept as its own tiny class so the serial path shares zero
    concurrency machinery (the bit-identical baseline the parity tests
    pin)."""

    def __init__(self, items: Iterator, stage, stats: PrefetchStats, retire):
        self._items = items
        self._stage = stage
        self._stats = stats
        self._retire = retire
        self._prev = None

    def __iter__(self):
        return self

    def __next__(self):
        if self._retire and self._prev is not None:
            _delete_jax_arrays(self._prev)
            self._prev = None
        elapsed = tick()
        item = next(self._items)  # StopIteration propagates
        out = item if self._stage is None else self._stage(item)
        dt = elapsed()
        # serial staging blocks the consumer: it is both stage and wait
        self._stats.stage_s += dt
        self._stats.wait_s += dt
        self._stats.chunks += 1
        if self._retire:
            self._prev = out
        return out

    def close(self):
        if self._retire and self._prev is not None:
            _delete_jax_arrays(self._prev)
            self._prev = None


class _Sentinel:
    __slots__ = ("err",)

    def __init__(self, err: Optional[BaseException]):
        self.err = err


# producer join budget at shutdown, seconds (module-level so tests can
# shrink it when deliberately wedging a stage callable)
JOIN_TIMEOUT_S = 5.0


class _ClosableSource:
    """Iterator wrapper the consumer can exhaust remotely: after
    :meth:`close` the next pull raises StopIteration, so a wedged
    producer that eventually wakes cannot keep reading a retired
    source (ISSUE 14 satellite — the close() contract)."""

    __slots__ = ("_it", "_closed")

    def __init__(self, it: Iterator):
        self._it = it
        self._closed = False

    def close(self) -> None:
        self._closed = True

    def __iter__(self):
        return self

    def __next__(self):
        if self._closed:
            raise StopIteration
        return next(self._it)


class _PoisonQueue:
    """The retired pipeline's queue stand-in: anything a late (wedged,
    now woken) producer stages after close() is retired on the spot and
    counted — it can never reach a consumer or pin device memory."""

    __slots__ = ("_retire",)

    def __init__(self, retire: bool):
        self._retire = retire

    def put(self, item) -> None:
        if self._retire and not isinstance(item, _Sentinel):
            _delete_jax_arrays(item)
        _tm.counter(
            "oap_prefetch_poisoned_puts_total",
            help="Staged items discarded because the pipeline was "
                 "already retired when the producer woke",
        ).inc()

    def get(self, *a, **kw):  # pragma: no cover - consumers are gone
        raise queue.Empty

    def get_nowait(self):
        raise queue.Empty


class _Threaded:
    """depth>=2: bounded background staging (module docstring)."""

    def __init__(self, items: Iterator, stage, depth: int,
                 stats: PrefetchStats, retire):
        self._items = _ClosableSource(items)
        self._stage = stage
        self._stats = stats
        self._retire = retire
        self._depth = depth
        self._slots = threading.Semaphore(depth)
        self._q: queue.Queue = queue.Queue()
        self._cancel = threading.Event()
        self._prev = None
        self._done = False
        self._thread = threading.Thread(
            target=self._produce, name="oap-mllib-tpu-prefetch", daemon=True
        )
        self._thread.start()

    # -- producer (background thread) ---------------------------------------

    def _acquire_slot(self) -> bool:
        while not self._slots.acquire(timeout=0.05):
            if self._cancel.is_set():
                return False
        if self._cancel.is_set():
            return False
        return True

    def _produce(self) -> None:
        try:
            while True:
                # slot BEFORE the source pull: bounds how far the source
                # itself runs ahead, not just the staged queue
                if not self._acquire_slot():
                    return
                try:
                    item = next(self._items)
                except StopIteration:
                    self._q.put(_Sentinel(None))
                    return
                elapsed = tick()
                out = item if self._stage is None else self._stage(item)
                self._stats.stage_s += elapsed()
                self._q.put(out)
        except BaseException as e:  # noqa: BLE001 — must cross the thread
            self._q.put(_Sentinel(e))

    # -- consumer ------------------------------------------------------------

    def _join_producer(self, where: str) -> None:
        """Join the producer; a thread still alive past the timeout is a
        wedged stage callable (hung device_put / IO).  It used to be
        ignored silently — now it is counted (``PrefetchStats
        .leaked_threads``, asserted zero in tests), logged with the
        pending site, AND quarantined: the source is marked exhausted
        and the staging queue is swapped for a poison queue, so if the
        wedged thread ever wakes it cannot stage into a retired
        pipeline — its output is retired on arrival and its next source
        pull ends it (the ISSUE 14 wedged-producer contract)."""
        self._thread.join(timeout=JOIN_TIMEOUT_S)
        if self._thread.is_alive():
            self._stats.leaked_threads += 1
            _tm.counter(
                "oap_prefetch_leaked_threads_total",
                help="Producer threads that failed to join at shutdown",
            ).inc()
            log.warning(
                "prefetch producer thread failed to join within %.1fs at "
                "%s; leaking daemon thread %r (source poisoned: a late "
                "wake cannot write into the retired pipeline)",
                JOIN_TIMEOUT_S, where, self._thread.name,
            )
            self._items.close()  # next pull raises StopIteration
            self._q = _PoisonQueue(self._retire)

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        if self._prev is not None:
            if self._retire:
                _delete_jax_arrays(self._prev)
            self._prev = None
            self._slots.release()
        elapsed = tick()
        out = self._q.get()
        self._stats.wait_s += elapsed()
        if isinstance(out, _Sentinel):
            self._done = True
            self._join_producer("__next__ (end-of-stream drain)")
            if out.err is not None:
                raise out.err
            raise StopIteration
        self._stats.chunks += 1
        self._prev = out
        return out

    def close(self):
        self._cancel.set()
        self._items.close()  # a producer mid-pull ends at the source too
        # drain so a producer blocked on put/semaphore wakes and exits
        try:
            while True:
                item = self._q.get_nowait()
                if self._retire and not isinstance(item, _Sentinel):
                    _delete_jax_arrays(item)
                self._slots.release()
        except queue.Empty:
            pass
        if self._prev is not None:
            if self._retire:
                _delete_jax_arrays(self._prev)
            self._prev = None
        self._join_producer("close() (cancel drain)")
        self._done = True


class Prefetcher:
    """Iterate ``stage(item)`` over ``items`` with up to ``depth`` chunks
    staged ahead by a background thread (depth=1: inline serial loop).

    Use as a context manager — exit closes the pipeline so an early break
    or consumer exception never strands the producer::

        with Prefetcher(chunks, stage, stats=stats, retire=True) as pf:
            for staged in pf:
                ...dispatch the step...
    """

    def __init__(
        self,
        items: Iterable,
        stage: Optional[Callable[[Any], Any]] = None,
        depth: Optional[int] = None,
        stats: Optional[PrefetchStats] = None,
        retire: bool = False,
    ):
        self.stats = PrefetchStats() if stats is None else stats
        self.depth = resolve_depth(depth)
        it = iter(items)
        # every stage call is a fault-injection site ("prefetch.stage",
        # utils/faults.py) — stageless pipelines included, so staging
        # faults are drillable on identity passes like reservoir
        # sampling; unarmed, maybe_fault is a dict miss
        inner = stage
        stats_ref = self.stats

        def staged(item):
            maybe_fault("prefetch.stage")
            out = item if inner is None else inner(item)
            stats_ref.note_staged(out)
            return out

        if self.depth == 1:
            self._impl = _Serial(it, staged, self.stats, retire)
        else:
            self._impl = _Threaded(it, staged, self.depth, self.stats, retire)

    def __iter__(self):
        it = iter(self._impl)
        # sanitizer plane (utils/sanitizers.py, Config.sanitizers):
        # "transfer" runs each CONSUMER body under a disallow transfer
        # guard; "retrace" asserts zero new XLA compiles after the
        # first chunk.  Off (the default) returns the raw iterator —
        # two cached string checks per pass, nothing per chunk.
        guard = sanitizers.enabled("transfer")
        watch = (
            sanitizers.RetraceWatch("prefetch")
            if sanitizers.enabled("retrace") else None
        )
        # flight recorder (telemetry/flightrec.py): one "chunk" event per
        # consumed chunk when armed, so a post-mortem tail shows how far
        # into a pass each rank got.  Off = one config check per pass.
        if flightrec.enabled():
            it = self._recorded(it)
        if not guard and watch is None:
            return it
        return self._sanitized(it, guard, watch)

    @staticmethod
    def _recorded(it):
        for i, item in enumerate(it):
            flightrec.record("chunk", "prefetch", f"#{i}")
            yield item

    @staticmethod
    def _sanitized(it, guard: bool, watch):
        """Yield chunks with the armed sanitizers active in the consumer
        body: the transfer guard covers exactly the code between yields
        (the per-chunk step dispatch), and the retrace watch checks the
        XLA compile count at every chunk boundary past the first."""
        index = 0
        for item in it:
            if guard:
                with sanitizers.transfer_scope():
                    yield item
            else:
                yield item
            if watch is not None:
                watch.chunk_done(index)
            index += 1

    def __enter__(self) -> "Prefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self._impl.close()

    def close(self) -> None:
        self._impl.close()
