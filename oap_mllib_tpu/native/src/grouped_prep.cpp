// ALS grouped-edge layout prep (~ the reference's host-side data prep in
// ALSDALImpl.cpp:184-230, which built per-rank CSR tables before handing
// off to the device kernels).  The NumPy path (ops/als_ops.py
// build_grouped_edges) is argsort-bound — O(nnz log nnz) plus several
// full-size temporaries; this is a stable counting sort by destination,
// O(nnz + n_dst), filling the padded (G, P) blocks in one pass.
//
// Error contract (shared by both entry points): -1 = bad input (P<=0,
// n_dst<=0, or a destination id outside [0, n_dst)); -2 = allocation
// failure (the O(n_dst) counts buffer — callers fall back to the NumPy
// path).  No exception ever crosses the extern "C" boundary.

#include <algorithm>
#include <cstdint>
#include <new>
#include <vector>

namespace {

// counts per destination; returns false on out-of-range ids
bool count_dsts(const int64_t* dst, int64_t nnz, int64_t n_dst,
                std::vector<int64_t>& counts) {
  counts.assign(static_cast<size_t>(n_dst), 0);
  for (int64_t e = 0; e < nnz; ++e) {
    int64_t d = dst[e];
    if (d < 0 || d >= n_dst) return false;
    counts[static_cast<size_t>(d)]++;
  }
  return true;
}

}  // namespace

extern "C" {

// Total padded edge count the grouped layout produces for one side:
// each destination's edge list rounds up to a multiple of P.  Also the
// native fast path for the COO-fallback blowup guard
// (ops/als_ops.py grouped_padded_edges).
int64_t oap_als_grouped_total(const int64_t* dst, int64_t nnz, int64_t n_dst,
                              int64_t P) {
  if (P <= 0 || n_dst <= 0 || nnz < 0) return -1;
  try {
    std::vector<int64_t> counts;
    if (!count_dsts(dst, nnz, n_dst, counts)) return -1;
    int64_t total = 0;
    for (int64_t d = 0; d < n_dst; ++d)
      total += ((counts[static_cast<size_t>(d)] + P - 1) / P) * P;
    return total;
  } catch (const std::bad_alloc&) {
    return -2;
  } catch (...) {
    return -2;
  }
}

// Fill the padded grouped layout.  Outputs are caller-allocated with
// capacity `total` (= oap_als_grouped_total) for src_g/conf_g/valid_g and
// total/P for group_dst, and MUST be pre-zeroed (pad slots keep src=0,
// conf=0, valid=0).  The capacity is validated BEFORE any output write,
// so a stale/mismatched capacity returns -1 without touching the
// buffers.  Edges keep their input order within each destination
// (stable, matching the NumPy path's stable argsort).  Returns total.
int64_t oap_als_group_edges(const int64_t* dst, const int64_t* src,
                            const float* conf, int64_t nnz, int64_t n_dst,
                            int64_t P, int64_t capacity, int32_t* src_g,
                            float* conf_g, float* valid_g,
                            int32_t* group_dst) {
  if (P <= 0 || n_dst <= 0 || nnz < 0) return -1;
  try {
    std::vector<int64_t> counts;
    if (!count_dsts(dst, nnz, n_dst, counts)) return -1;
    // per-destination padded start offsets; validate capacity before
    // writing a single output element
    std::vector<int64_t> start(static_cast<size_t>(n_dst), 0);
    int64_t total = 0;
    for (int64_t d = 0; d < n_dst; ++d) {
      start[static_cast<size_t>(d)] = total;
      total += ((counts[static_cast<size_t>(d)] + P - 1) / P) * P;
    }
    if (total != capacity) return -1;
    int64_t gidx = 0;
    for (int64_t d = 0; d < n_dst; ++d) {
      int64_t padded =
          ((counts[static_cast<size_t>(d)] + P - 1) / P) * P;
      for (int64_t g = 0; g < padded / P; ++g)
        group_dst[gidx++] = static_cast<int32_t>(d);
    }
    // stable scatter: slot = start[d] + (running fill of d)
    std::vector<int64_t>& fill = counts;  // reuse as fill cursors
    std::fill(fill.begin(), fill.end(), 0);
    for (int64_t e = 0; e < nnz; ++e) {
      int64_t d = dst[e];
      int64_t slot =
          start[static_cast<size_t>(d)] + fill[static_cast<size_t>(d)]++;
      src_g[slot] = static_cast<int32_t>(src[e]);
      conf_g[slot] = conf[e];
      valid_g[slot] = 1.0f;
    }
    return total;
  } catch (const std::bad_alloc&) {
    return -2;
  } catch (...) {
    return -2;
  }
}

// -- the build over host threads, on int32 ids ------------------------------
// Spark ML ALS holds ids as Int, and a table of 10^8 ratings built by one
// thread costs tens of seconds.  The three entry points below are called
// by ops/als_ops.build_grouped_edges from several Python threads at once
// (ctypes releases the GIL), each on a range of its own: a counting pass
// over a range of EDGES into that range's own counts, the placement of
// the same range from cursors that start where the earlier ranges'
// edges of each destination end (so edges keep their input order within
// a destination: the layout is the one-thread build's bit for bit), and
// the fill of `valid`, `group_dst` and the pad slots over a range of
// DESTINATIONS.  No entry point allocates.

// counts[d] += 1 for every edge of [lo, hi); -1 on an id outside
// [0, n_dst).  `counts` is the range's own, zeroed by the caller.
int64_t oap_als_count_range_i32(const int32_t* dst, int64_t lo, int64_t hi,
                                int64_t n_dst, int32_t* counts) {
  if (n_dst <= 0 || lo < 0 || hi < lo) return -1;
  for (int64_t e = lo; e < hi; ++e) {
    int32_t d = dst[e];
    if (d < 0 || d >= n_dst) return -1;
    counts[d]++;
  }
  return hi - lo;
}

// src_g / conf_g at cursor[dst[e]]++ for every edge of [lo, hi);
// `cursor` is the range's own (n_dst slots) and is consumed.
int64_t oap_als_place_range_i32(const int32_t* dst, const int32_t* src,
                                const float* conf, int64_t lo, int64_t hi,
                                int64_t* cursor, int32_t* src_g,
                                float* conf_g) {
  for (int64_t e = lo; e < hi; ++e) {
    int64_t slot = cursor[dst[e]]++;
    src_g[slot] = src[e];
    conf_g[slot] = conf[e];
  }
  return hi - lo;
}

// For destinations [d_lo, d_hi): valid = 1 on each one's first counts[d]
// slots from start[d] and 0 on its pad slots, whose src and conf are
// zeroed too (the outputs need not come zeroed), and its groups' entries
// of group_dst.  start[d_hi] is read: `start` has n_dst + 1 entries.
int64_t oap_als_fill_range(const int64_t* start, const int64_t* counts,
                           int64_t d_lo, int64_t d_hi, int64_t P,
                           int32_t* src_g, float* conf_g, float* valid_g,
                           int32_t* group_dst) {
  if (P <= 0 || d_lo < 0 || d_hi < d_lo) return -1;
  for (int64_t d = d_lo; d < d_hi; ++d) {
    int64_t s = start[d], live = s + counts[d], end = start[d + 1];
    for (int64_t k = s; k < live; ++k) valid_g[k] = 1.0f;
    for (int64_t k = live; k < end; ++k) {
      valid_g[k] = 0.0f;
      src_g[k] = 0;
      conf_g[k] = 0.0f;
    }
    for (int64_t g = s / P; g < end / P; ++g)
      group_dst[g] = static_cast<int32_t>(d);
  }
  return d_hi - d_lo;
}

}  // extern "C"
