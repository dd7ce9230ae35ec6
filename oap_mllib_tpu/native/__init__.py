"""Native runtime bindings (ctypes).

The C++ layer (src/) replaces the reference's native components that are
not device compute: the table store (~ OneDAL.cpp), file parsers (~ the
Spark readers / Service.java helpers), bootstrap network probing
(~ OneCCL.cpp's interface/port scanning), and the ALS shuffle prep
(~ ALSShuffle.cpp).  Loading mirrors the reference's LibLoader
(LibLoader.java: extract + System.load at first use): the .so is built
on demand with `make` the first time it's needed and cached under
native/build/.  Every entry point has a pure-NumPy fallback, so the
framework works without a toolchain (the capability-fallback contract).

Use ``available()`` to check, or call the wrappers — they fall back
silently.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

log = logging.getLogger("oap_mllib_tpu")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO_PATH = os.path.join(_HERE, "build", "liboapmllibtpu.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build(force: bool = False) -> bool:
    """Run make; ``force`` rebuilds unconditionally (-B) for the stale-.so
    retry.  The Makefile links to a temp file and renames it over the
    target, so concurrent ranks sharing this checkout always dlopen a
    complete .so (old or new), never a missing or half-written one."""
    try:
        cmd = ["make", "-C", _HERE, "-j4"]
        if force:
            cmd.insert(1, "-B")
        # oaplint: disable=blocking-while-locked -- one-shot dlopen init: the lock IS the once guard
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        return os.path.exists(_SO_PATH)
    except (subprocess.SubprocessError, OSError) as e:
        log.info("native build failed (using NumPy fallbacks): %s", e)
        return False


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    """Declare every entry point's signature.  Raises AttributeError when
    the .so predates a symbol — the caller rebuilds and retries once
    (stale build caches must degrade to the NumPy fallbacks, never crash
    the whole native layer)."""
    i64, i32, f64p = ctypes.c_int64, ctypes.c_int32, ctypes.POINTER(ctypes.c_double)
    lib.oap_table_create.restype = i64
    lib.oap_table_create.argtypes = [i64, i64]
    lib.oap_table_append.restype = i64
    lib.oap_table_append.argtypes = [i64, f64p, i64]
    lib.oap_table_merge.restype = i64
    lib.oap_table_merge.argtypes = [i64, i64]
    lib.oap_table_rows.restype = i64
    lib.oap_table_rows.argtypes = [i64]
    lib.oap_table_cols.restype = i64
    lib.oap_table_cols.argtypes = [i64]
    lib.oap_table_copy_out.restype = i64
    lib.oap_table_copy_out.argtypes = [i64, f64p, i64]
    lib.oap_table_data.restype = f64p
    lib.oap_table_data.argtypes = [i64]
    lib.oap_table_free.restype = i64
    lib.oap_table_free.argtypes = [i64]
    lib.oap_table_count.restype = i64
    lib.oap_table_count.argtypes = []
    lib.oap_parse_libsvm.restype = i64
    lib.oap_parse_libsvm.argtypes = [ctypes.c_char_p, i64, ctypes.POINTER(i64)]
    lib.oap_parse_csv.restype = i64
    lib.oap_parse_csv.argtypes = [ctypes.c_char_p, ctypes.c_char]
    lib.oap_parse_ratings.restype = i64
    lib.oap_parse_ratings.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    lib.oap_local_ip.restype = ctypes.c_int
    lib.oap_local_ip.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.oap_free_port.restype = ctypes.c_int
    lib.oap_free_port.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int]
    lib.oap_shuffle_block_ids.restype = None
    lib.oap_shuffle_block_ids.argtypes = [
        ctypes.POINTER(i64), i64, i64, i64, ctypes.POINTER(i32)]
    lib.oap_shuffle_block_counts.restype = None
    lib.oap_shuffle_block_counts.argtypes = [
        ctypes.POINTER(i32), i64, i64, ctypes.POINTER(i64)]
    lib.oap_shuffle_sort_perm.restype = None
    lib.oap_shuffle_sort_perm.argtypes = [
        ctypes.POINTER(i32), ctypes.POINTER(i64), ctypes.POINTER(i64),
        i64, ctypes.POINTER(i64)]
    lib.oap_distinct_count.restype = i64
    lib.oap_distinct_count.argtypes = [ctypes.POINTER(i64), i64]
    f32p = ctypes.POINTER(ctypes.c_float)
    lib.oap_als_grouped_total.restype = i64
    lib.oap_als_grouped_total.argtypes = [ctypes.POINTER(i64), i64, i64, i64]
    lib.oap_als_group_edges.restype = i64
    lib.oap_als_group_edges.argtypes = [
        ctypes.POINTER(i64), ctypes.POINTER(i64), f32p, i64, i64, i64,
        i64, ctypes.POINTER(i32), f32p, f32p, ctypes.POINTER(i32)]
    i32p, i64p = ctypes.POINTER(i32), ctypes.POINTER(i64)
    lib.oap_als_count_range_i32.restype = i64
    lib.oap_als_count_range_i32.argtypes = [i32p, i64, i64, i64, i32p]
    lib.oap_als_place_range_i32.restype = i64
    lib.oap_als_place_range_i32.argtypes = [
        i32p, i32p, f32p, i64, i64, i64p, i32p, f32p]
    lib.oap_als_fill_range.restype = i64
    lib.oap_als_fill_range.argtypes = [
        i64p, i64p, i64, i64, i64, i32p, f32p, f32p, i32p]
    return lib


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        # oaplint: disable=blocking-while-locked -- one-shot dlopen init: the lock IS the once guard
        if not os.path.exists(_SO_PATH) and not _build():
            return None
        load_path = _SO_PATH
        for attempt in (0, 1):
            try:
                lib = ctypes.CDLL(load_path)
            except OSError as e:
                log.info("native load failed (using NumPy fallbacks): %s", e)
                return None
            finally:
                if load_path != _SO_PATH:
                    # the dlopen mapping outlives the unlink (Linux); never
                    # leave the retry's temp copy behind
                    try:
                        os.remove(load_path)
                    except OSError:
                        pass
            try:
                _lib = _bind(lib)
                return _lib
            except AttributeError as e:
                # stale .so from before a symbol existed: force-rebuild
                # (make -B; never remove-then-rebuild — peers sharing this
                # checkout must not see a missing .so) and retry through a
                # unique temp copy — dlopen caches the stale handle for
                # the original path within this process
                if attempt == 0:
                    # oaplint: disable=blocking-while-locked -- stale-.so rebuild in one-shot init
                    if _build(force=True):
                        import shutil
                        import tempfile

                        fd, load_path = tempfile.mkstemp(suffix=".so")
                        os.close(fd)
                        shutil.copy(_SO_PATH, load_path)
                        continue
                log.info(
                    "native library is stale and rebuild failed "
                    "(using NumPy fallbacks): %s", e,
                )
                return None
        return None


def available() -> bool:
    return _load() is not None


def table_view(handle: int) -> np.ndarray:
    """Zero-copy numpy view of a live native table (no copy; the caller
    must keep the table alive and not free it while the view exists).
    This is the handoff point to the device runtime: jnp.asarray /
    jax.device_put consume the view directly."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    rows = lib.oap_table_rows(handle)
    cols = lib.oap_table_cols(handle)
    ptr = lib.oap_table_data(handle)
    if rows < 0 or cols < 0 or not ptr:
        raise RuntimeError("invalid native table handle")
    return np.ctypeslib.as_array(ptr, shape=(rows, cols))


def _table_to_numpy(lib, handle: int) -> np.ndarray:
    rows = lib.oap_table_rows(handle)
    cols = lib.oap_table_cols(handle)
    if rows < 0 or cols < 0:
        raise RuntimeError("invalid native table handle")
    out = np.empty((rows, cols), dtype=np.float64)
    got = lib.oap_table_copy_out(
        handle, out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), rows
    )
    if got != rows:
        raise RuntimeError("native table copy_out failed")
    return out


# -- parsers ----------------------------------------------------------------

def parse_libsvm(path: str, n_features: int = 0) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Native libsvm parse; returns (labels, X) or None if unavailable."""
    lib = _load()
    if lib is None:
        return None
    lh = ctypes.c_int64(-1)
    h = lib.oap_parse_libsvm(path.encode(), n_features, ctypes.byref(lh))
    if h < 0:
        raise ValueError(f"native libsvm parse failed: {path}")
    try:
        x = _table_to_numpy(lib, h)
        labels = _table_to_numpy(lib, lh.value)[:, 0]
    finally:
        lib.oap_table_free(h)
        if lh.value >= 0:
            lib.oap_table_free(lh.value)
    return labels, x


def parse_csv(path: str, delimiter: str = ",") -> Optional[np.ndarray]:
    lib = _load()
    if lib is None:
        return None
    h = lib.oap_parse_csv(path.encode(), delimiter.encode()[:1])
    if h < 0:
        raise ValueError(f"native csv parse failed: {path}")
    try:
        return _table_to_numpy(lib, h)
    finally:
        lib.oap_table_free(h)


def parse_ratings(
    path: str, sep: str = "::"
) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    lib = _load()
    if lib is None:
        return None
    h = lib.oap_parse_ratings(path.encode(), sep.encode())
    if h < 0:
        raise ValueError(f"native ratings parse failed: {path}")
    try:
        t = _table_to_numpy(lib, h)
    finally:
        lib.oap_table_free(h)
    return (
        t[:, 0].astype(np.int64),
        t[:, 1].astype(np.int64),
        t[:, 2].astype(np.float32),
    )


# -- bootstrap probing ------------------------------------------------------

def local_ip() -> Optional[str]:
    """First non-loopback IPv4 (~ Utils.sparkFirstExecutorIP analog's
    native side). None if native lib unavailable or no interface."""
    lib = _load()
    if lib is None:
        return None
    buf = ctypes.create_string_buffer(64)
    if lib.oap_local_ip(buf, 64) != 0:
        return None
    return buf.value.decode()


def free_port(ip: str = "", start: int = 3000, max_tries: int = 1000) -> Optional[int]:
    """Scan for a bindable TCP port (~ OneCCL.cpp:207-247)."""
    lib = _load()
    if lib is None:
        return None
    port = lib.oap_free_port(ip.encode(), start, max_tries)
    return port if port > 0 else None


# -- shuffle prep -----------------------------------------------------------

def shuffle_prep(
    users: np.ndarray, items: np.ndarray, ratings: np.ndarray,
    keys_per_block: int, n_blocks: int,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Bucket + sort ratings by (user block, user, item).

    Returns (users, items, ratings, block_counts, perm) with records
    reordered block-grouped, per-block counts for the alltoall size
    exchange, and the permutation applied.  Falls back to NumPy.
    """
    if keys_per_block <= 0:
        raise ValueError(f"keys_per_block must be > 0, got {keys_per_block}")
    if n_blocks <= 0:
        raise ValueError(f"n_blocks must be > 0, got {n_blocks}")
    users = np.ascontiguousarray(users, dtype=np.int64)
    items = np.ascontiguousarray(items, dtype=np.int64)
    ratings = np.asarray(ratings)
    n = len(users)
    lib = _load()
    if lib is None:
        block = np.minimum(users // keys_per_block, n_blocks - 1).astype(np.int32)
        perm = np.lexsort((items, users, block))
        counts = np.bincount(block, minlength=n_blocks).astype(np.int64)
        return users[perm], items[perm], ratings[perm], counts, perm
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    block = np.empty((n,), dtype=np.int32)
    lib.oap_shuffle_block_ids(
        users.ctypes.data_as(i64p), n, keys_per_block, n_blocks,
        block.ctypes.data_as(i32p))
    counts = np.empty((n_blocks,), dtype=np.int64)
    lib.oap_shuffle_block_counts(
        block.ctypes.data_as(i32p), n, n_blocks, counts.ctypes.data_as(i64p))
    perm = np.empty((n,), dtype=np.int64)
    lib.oap_shuffle_sort_perm(
        block.ctypes.data_as(i32p), users.ctypes.data_as(i64p),
        items.ctypes.data_as(i64p), n, perm.ctypes.data_as(i64p))
    return users[perm], items[perm], ratings[perm], counts, perm


def shuffle_prep_offsets(
    users: np.ndarray, items: np.ndarray, ratings: np.ndarray,
    offsets: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Bucket + sort ratings by (user block, user, item) under EXPLICIT
    block boundaries — the uneven-offset variant of :func:`shuffle_prep`
    for capability-weighted block layouts (parallel/balance
    .plan_block_offsets).  ``offsets`` is the ``(n_blocks + 1,)``
    monotone key-boundary array; block b owns users in
    ``[offsets[b], offsets[b+1])``.  Same return contract as
    shuffle_prep.  Pure NumPy (searchsorted replaces the C library's
    uniform-width division; the uneven layout only engages on
    heterogeneous worlds, where the shuffle is not the bottleneck)."""
    offsets = np.asarray(offsets, np.int64)
    n_blocks = len(offsets) - 1
    if n_blocks < 1:
        raise ValueError("offsets must have >= 2 entries")
    if np.any(np.diff(offsets) < 0):
        raise ValueError("offsets must be monotone non-decreasing")
    users = np.ascontiguousarray(users, dtype=np.int64)
    items = np.ascontiguousarray(items, dtype=np.int64)
    ratings = np.asarray(ratings)
    block = np.clip(
        np.searchsorted(offsets, users, side="right") - 1, 0, n_blocks - 1
    ).astype(np.int32)
    perm = np.lexsort((items, users, block))
    counts = np.bincount(block, minlength=n_blocks).astype(np.int64)
    return users[perm], items[perm], ratings[perm], counts, perm


def distinct_count(sorted_keys: np.ndarray) -> int:
    sorted_keys = np.ascontiguousarray(sorted_keys, dtype=np.int64)
    lib = _load()
    if lib is None:
        if len(sorted_keys) == 0:
            return 0
        return int(1 + np.count_nonzero(np.diff(sorted_keys)))
    return int(lib.oap_distinct_count(
        sorted_keys.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(sorted_keys)))


# -- ALS grouped-edge prep --------------------------------------------------

def als_grouped_total(dst: np.ndarray, n_dst: int, p: int) -> Optional[int]:
    """Padded edge total for one grouped side (blowup-guard fast path);
    None if the native lib is unavailable (or its O(n_dst) counts buffer
    cannot be allocated — callers fall back to NumPy)."""
    if n_dst <= 0 or len(dst) == 0:
        return 0  # empty side: no groups, matching the NumPy path
    lib = _load()
    if lib is None:
        return None
    dst = np.ascontiguousarray(dst, dtype=np.int64)
    total = lib.oap_als_grouped_total(
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), len(dst),
        n_dst, p,
    )
    if total == -2:
        return None  # allocation failure: NumPy fallback
    if total < 0:
        raise ValueError("destination id out of range for grouped layout")
    return int(total)


def als_group_edges(
    dst: np.ndarray, src: np.ndarray, conf: np.ndarray, n_dst: int, p: int,
) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]]:
    """Stable counting-sort build of the padded (G, P) grouped-edge layout
    (~ ops/als_ops.build_grouped_edges, O(nnz + n_dst) instead of the
    NumPy argsort path); None if the native lib is unavailable."""
    lib = _load()
    if lib is None or n_dst <= 0 or len(dst) == 0:
        return None  # empty/degenerate sides keep the NumPy path's behavior
    dst = np.ascontiguousarray(dst, dtype=np.int64)
    src = np.ascontiguousarray(src, dtype=np.int64)
    conf = np.ascontiguousarray(conf, dtype=np.float32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    f32p = ctypes.POINTER(ctypes.c_float)
    # one counting pass to size the buffers, one inside the builder — the
    # duplicate O(nnz) count is noise next to the argsort it replaces
    total = als_grouped_total(dst, n_dst, p)
    if total is None:
        return None
    src_g = np.zeros((total,), np.int32)
    conf_g = np.zeros((total,), np.float32)
    valid_g = np.zeros((total,), np.float32)
    group_dst = np.zeros((total // p,), np.int32)
    got = lib.oap_als_group_edges(
        dst.ctypes.data_as(i64p), src.ctypes.data_as(i64p),
        conf.ctypes.data_as(f32p), len(dst), n_dst, p, total,
        src_g.ctypes.data_as(i32p), conf_g.ctypes.data_as(f32p),
        valid_g.ctypes.data_as(f32p), group_dst.ctypes.data_as(i32p),
    )
    if got == -2:
        return None  # allocation failure: NumPy fallback
    if got != total:
        raise RuntimeError("native grouped-edge build failed")
    g = total // p
    return (
        src_g.reshape(g, p),
        conf_g.reshape(g, p),
        valid_g.reshape(g, p),
        group_dst,
    )


# -- the grouped build over host threads, int32 ids --------------------------
# Each entry point of native/src/grouped_prep.cpp works on a range of its
# own and allocates nothing; ctypes releases the GIL, so the ranges run on
# a pool of Python threads.

def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def _over_ranges(call, cuts) -> list:
    """``call(k, lo, hi)`` for each range ``cuts[k]:cuts[k + 1]`` that is
    not empty, on as many threads; their return values."""
    from concurrent.futures import ThreadPoolExecutor

    todo = [(k, int(a), int(b)) for k, (a, b) in
            enumerate(zip(cuts[:-1], cuts[1:])) if b > a]
    if len(todo) <= 1:
        return [call(*t) for t in todo]
    with ThreadPoolExecutor(len(todo)) as pool:
        return list(pool.map(lambda t: call(*t), todo))


def _cuts(n: int, parts: int) -> np.ndarray:
    return np.linspace(0, n, max(1, parts) + 1).astype(np.int64)


def als_count_ranges(dst: np.ndarray, n_dst: int,
                     threads: int) -> Optional[np.ndarray]:
    """``(threads, n_dst)`` int32: how often each destination occurs in
    each of ``threads`` equal ranges of the edges (``dst`` int32,
    C-contiguous).  None if the native lib is unavailable; an id outside
    [0, n_dst) raises."""
    lib = _load()
    if lib is None:
        return None
    counts = np.zeros((max(1, threads), n_dst), np.int32)
    dp = _ptr(dst, ctypes.c_int32)
    got = _over_ranges(
        lambda k, lo, hi: lib.oap_als_count_range_i32(
            dp, lo, hi, n_dst, _ptr(counts[k], ctypes.c_int32)),
        _cuts(len(dst), threads),
    )
    if any(g < 0 for g in got):
        raise ValueError("destination id out of range for grouped layout")
    return counts


def als_place_ranges(dst, src, conf, counts: np.ndarray, start: np.ndarray,
                     p: int, src_g, conf_g, valid_g, group_dst) -> None:
    """Fill the flat grouped layout from ``counts`` (``als_count_ranges``
    of the same ``dst``) and ``start`` (n_dst + 1 padded offsets): every
    range of edges places its own from cursors that start behind the
    earlier ranges' edges of each destination, so edges keep their input
    order within a destination whatever the number of ranges; ``valid``,
    ``group_dst`` and the pad slots are written over ranges of
    destinations.  The outputs need not come zeroed up to ``start[-1]``."""
    lib = _load()
    threads, n_dst = counts.shape
    # cursor[k, d]: where range k's first edge of destination d goes
    cursor = np.cumsum(counts, axis=0, dtype=np.int64)
    total = cursor[-1].copy()
    cursor -= counts
    cursor += start[:-1]
    dp, sp = _ptr(dst, ctypes.c_int32), _ptr(src, ctypes.c_int32)
    cp = _ptr(conf, ctypes.c_float)
    sg, cg = _ptr(src_g, ctypes.c_int32), _ptr(conf_g, ctypes.c_float)
    _over_ranges(
        lambda k, lo, hi: lib.oap_als_place_range_i32(
            dp, sp, cp, lo, hi, _ptr(cursor[k], ctypes.c_int64), sg, cg),
        _cuts(len(dst), threads),
    )
    stp, tp = _ptr(start, ctypes.c_int64), _ptr(total, ctypes.c_int64)
    vg, gd = _ptr(valid_g, ctypes.c_float), _ptr(group_dst, ctypes.c_int32)
    # destination ranges of about equal slots, not equal destinations
    d_cuts = np.searchsorted(start, _cuts(int(start[-1]), threads))
    d_cuts[0], d_cuts[-1] = 0, n_dst
    _over_ranges(
        lambda k, lo, hi: lib.oap_als_fill_range(
            stp, tp, lo, hi, p, sg, cg, vg, gd),
        d_cuts,
    )
