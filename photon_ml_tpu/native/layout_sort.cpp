// Native core of the Pallas slot-layout build (ops/sparse_pallas.py).
//
// The host-side layout build is the ingest bottleneck once transfers run
// at PCIe rates: numpy spends its time in argsort + run-length + fancy
// scatter passes over tens of millions of entries.  This file implements
// exactly those passes in C++ — a stable LSD radix argsort by the
// (tile, gather-window, lane) key, the per-cell depth positions and
// per-(tile, window) max lane loads in one sequential scan, and the
// final slot scatter — leaving the (tiny) cost model and bin-packing in
// numpy.  The radix sort is stable with the same tie order as
// np.argsort(key, kind="stable"), so the produced layout is
// BIT-IDENTICAL to the Python path (tests assert array equality).
//
// C ABI + ctypes (no pybind11 in this environment); the loader in
// native/__init__.py compiles this lazily with the system g++ and falls
// back to the numpy path on any failure.

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <vector>

#ifdef _OPENMP
#include <omp.h>
#endif

namespace {

// Field extraction shared by both passes.  tile_edge is the (square)
// tile size; WIN is fixed at 128 lanes.
struct Fields {
  int64_t nbc;
  int64_t tile_edge;
  int64_t wins;  // tile_edge / 128

  inline int64_t tile(int64_t r, int64_t c) const {
    return (r / tile_edge) * nbc + (c / tile_edge);
  }
  inline int64_t gwin(int64_t c) const { return (c % tile_edge) >> 7; }
  inline int64_t lane(int64_t r) const { return r & 127; }
  inline int64_t key(int64_t r, int64_t c) const {
    return (tile(r, c) * wins + gwin(c)) * 128 + lane(r);
  }
};

}  // namespace

namespace {

// Actual deliverable team size: observed from a real parallel region with
// dynamic adjustment disabled.  Every later region requests exactly this
// size; a region body ADDITIONALLY verifies its own team and degrades to
// sequential (thread 0 owns everything) on any mismatch — range math from
// a team size the runtime did not deliver would silently drop elements.
inline int observed_team() {
#ifdef _OPENMP
  omp_set_dynamic(0);
  int team = 1;
#pragma omp parallel
  {
#pragma omp single
    team = omp_get_num_threads();
  }
  return team;
#else
  return 1;
#endif
}

inline void my_range(int64_t nnz, int team, int64_t* lo, int64_t* hi) {
#ifdef _OPENMP
  const int actual = omp_get_num_threads();
  const int tid = omp_get_thread_num();
#else
  const int actual = 1, tid = 0;
#endif
  if (actual != team) {  // degraded team: thread 0 does everything
    *lo = (tid == 0) ? 0 : nnz;
    *hi = (tid == 0) ? nnz : nnz;
    return;
  }
  *lo = nnz * tid / team;
  *hi = nnz * (tid + 1) / team;
}

inline int my_row(int team) {
#ifdef _OPENMP
  if (omp_get_num_threads() != team) return 0;
  return omp_get_thread_num();
#else
  (void)team;
  return 0;
#endif
}

}  // namespace

extern "C" {

// Stable argsort of entries by (tile, gwin, lane) key + one sequential
// scan emitting per-entry depth positions and per-(tile, window) max
// lane loads.  order_out/depth_pos_out: nnz int32 (caller-allocated);
// M_out: nt*wins int64, caller-zeroed.  Returns 0, or -1 when nnz
// exceeds int32 indexing.
int64_t pl_sort_orientation(
    const int64_t* rows, const int64_t* cols, int64_t nnz,
    int64_t nbc, int64_t tile_edge, int64_t nt,
    int32_t* order_out, int32_t* depth_pos_out, int64_t* M_out) {
  if (nnz > INT32_MAX) return -1;
  const Fields F{nbc, tile_edge, tile_edge >> 7};
  const int64_t key_span = nt * F.wins * 128;

  std::vector<int64_t> keys(static_cast<size_t>(nnz));
#pragma omp parallel for schedule(static)
  for (int64_t i = 0; i < nnz; ++i) keys[i] = F.key(rows[i], cols[i]);

  // Parallel LSD radix argsort, 16-bit digits — STABLE with numpy's
  // kind="stable" tie order: each thread owns a CONTIGUOUS input range,
  // per-(thread, bucket) counts are prefix-summed bucket-major then
  // thread-major, so equal keys keep their original relative order.
  int bits = 1;
  while ((int64_t(1) << bits) < key_span) ++bits;
  const int DIGIT = 16;
  const int n_buckets = 1 << DIGIT;
  const int n_threads = observed_team();
  std::vector<int32_t> idx_a(static_cast<size_t>(nnz));
  std::vector<int32_t> idx_b(static_cast<size_t>(nnz));
#pragma omp parallel for schedule(static)
  for (int64_t i = 0; i < nnz; ++i) idx_a[i] = static_cast<int32_t>(i);
  std::vector<int64_t> counts(
      static_cast<size_t>(n_threads) * n_buckets);
  int32_t* src = idx_a.data();
  int32_t* dst = idx_b.data();
  for (int shift = 0; shift < bits; shift += DIGIT) {
    std::memset(counts.data(), 0,
                sizeof(int64_t) * counts.size());
    // Histogram + prefix + stable scatter in ONE parallel region: the
    // two per-thread phases see the SAME team by construction (a
    // degraded team degrades both), so range/row math can never mix
    // team sizes.
#pragma omp parallel num_threads(n_threads)
    {
      int64_t lo, hi;
      my_range(nnz, n_threads, &lo, &hi);
      int64_t* my =
          counts.data() + static_cast<size_t>(my_row(n_threads)) * n_buckets;
      for (int64_t i = lo; i < hi; ++i)
        ++my[(keys[src[i]] >> shift) & (n_buckets - 1)];
#ifdef _OPENMP
#pragma omp barrier
#pragma omp single
#endif
      {
        // Exclusive prefix over (bucket, thread) pairs, bucket-major:
        // thread t's entries in bucket b land after every thread's
        // smaller buckets and earlier threads' bucket b — stability.
        int64_t run = 0;
        for (int b = 0; b < n_buckets; ++b) {
          for (int t = 0; t < n_threads; ++t) {
            int64_t& slot =
                counts[static_cast<size_t>(t) * n_buckets + b];
            int64_t c = slot;
            slot = run;
            run += c;
          }
        }
      }
      for (int64_t i = lo; i < hi; ++i) {
        int32_t e = src[i];
        dst[my[(keys[e] >> shift) & (n_buckets - 1)]++] = e;
      }
    }
    std::swap(src, dst);
  }
  std::memcpy(order_out, src, sizeof(int32_t) * nnz);

  // Sequential scan: depth position within each (tile, window, lane)
  // cell and the max lane load per (tile, window).
  int64_t prev_key = -1;
  int32_t run_len = 0;
  for (int64_t i = 0; i < nnz; ++i) {
    const int64_t k = keys[order_out[i]];
    if (k == prev_key) {
      ++run_len;
    } else {
      prev_key = k;
      run_len = 0;
    }
    depth_pos_out[i] = run_len;
    const int64_t tw = k >> 7;  // tile*wins + gwin
    if (run_len + 1 > M_out[tw]) M_out[tw] = run_len + 1;
  }
  return 0;
}

// Scatter kept entries into the slot grids; overflow indices (positions
// into the ORIGINAL entry arrays) go to spill_out.  code_out is int16
// when code_bytes == 2 else int32; base is the per-(tile, window)
// exclusive sublane offset.  Returns the spill count.
int64_t pl_scatter(
    const int64_t* rows, const int64_t* cols, const float* vals,
    const int32_t* order, const int32_t* depth_pos, const int32_t* base,
    int64_t nnz, int64_t nbc, int64_t tile_edge,
    int64_t depth, int64_t a, int64_t win_shift, int64_t code_bytes,
    void* code_out, float* val_out, int64_t* spill_out) {
  const Fields F{nbc, tile_edge, tile_edge >> 7};
  int16_t* code16 = static_cast<int16_t*>(code_out);
  int32_t* code32 = static_cast<int32_t*>(code_out);

  // Parallel over contiguous sorted ranges: slot targets are unique per
  // kept entry (disjoint writes), and per-thread spill segments are laid
  // out in thread order, which IS sorted order — identical spill
  // ordering to the sequential loop (and the numpy path).
  const int n_threads = observed_team();
  std::vector<int64_t> spill_base(n_threads + 1, 0);
  // Count + prefix + write in ONE region: both phases share the same
  // team by construction (see the sort loop).
#pragma omp parallel num_threads(n_threads)
  {
    int64_t lo, hi;
    my_range(nnz, n_threads, &lo, &hi);
    const int row = my_row(n_threads);
    int64_t n = 0;
    for (int64_t i = lo; i < hi; ++i)
      if (depth_pos[i] >= depth) ++n;
    // Atomic: in a degraded team every thread maps to row 0, and an
    // empty-range thread's plain "= 0" store could clobber the total.
#ifdef _OPENMP
#pragma omp atomic
#endif
    spill_base[row + 1] += n;
#ifdef _OPENMP
#pragma omp barrier
#pragma omp single
#endif
    {
      for (int t = 0; t < n_threads; ++t)
        spill_base[t + 1] += spill_base[t];
    }
    int64_t cursor = spill_base[row];
    for (int64_t i = lo; i < hi; ++i) {
      const int32_t e = order[i];
      if (depth_pos[i] >= depth) {
        spill_out[cursor++] = e;
        continue;
      }
      const int64_t r = rows[e], c = cols[e];
      const int64_t t = F.tile(r, c);
      const int64_t g = F.gwin(c);
      const int64_t sub = base[t * F.wins + g] + depth_pos[i];
      const int64_t flat = (t * a + sub) * 128 + F.lane(r);
      const int64_t ohi = (r % tile_edge) >> 7;
      const int64_t code =
          (g << win_shift) | (ohi << 7) | (c & 127);
      if (code_bytes == 2) {
        code16[flat] = static_cast<int16_t>(code);
      } else {
        code32[flat] = static_cast<int32_t>(code);
      }
      if (val_out) val_out[flat] = vals[e];  // null: a unit-value layout
    }
  }
  return spill_base[n_threads];
}

// The packed sublane counts of BOTH orientations of one column labeling,
// uncapped: max over tiles of the sum over windows of the worst lane
// load — what the numpy reference (_predict_a in ops/sparse_pallas.py)
// gets from a sort of one key per entry, here from one counting pass.
// rows must be nondecreasing: a band of tile_edge rows is then one
// contiguous run, and every cell of both orientations that an entry of
// the band can fall in belongs to the band.  Orientation F's cell is
// (column tile, column window, row lane), orientation B's (column tile,
// row window, column lane): nbc * tile_edge counters each.  relabel (old
// col -> new col, n_relabel long; null for the identity) maps into
// [0, nbc*tile_edge).  out2 = {F's depth, B's depth}.  Returns 0; 1 for a
// row smaller than its predecessor or an index outside the grid; 2 where
// nnz passes int32 or one thread's counters would outweigh the entries
// themselves (the caller then takes the sort, which allocates nothing
// per column).
int64_t pl_band_depths(
    const int64_t* rows, const int64_t* cols, int64_t nnz,
    int64_t nbr, int64_t nbc, int64_t tile_edge,
    const int64_t* relabel, int64_t n_relabel, int64_t* out2) {
  out2[0] = out2[1] = 0;
  if (nnz > INT32_MAX) return 2;
  const Fields F{nbc, tile_edge, tile_edge >> 7};
  const int64_t n_cells = nbc * tile_edge;   // per orientation, per band
  const int64_t n_tw = nbc * F.wins;
  // Two int32 counter arrays a thread, against two int64 an entry: the
  // team is as large as keeps all counters within the entries' own bytes.
  const int64_t afford = (2 * nnz) / (n_cells > 0 ? n_cells : 1);
  if (afford < 1) return 2;
  const int team = static_cast<int>(
      std::min<int64_t>(afford, observed_team()));

  int64_t bad = 0;
#pragma omp parallel for schedule(static) reduction(| : bad)
  for (int64_t i = 1; i < nnz; ++i) bad |= (rows[i] < rows[i - 1]);
  if (bad || (nnz && (rows[0] < 0 || rows[nnz - 1] >= nbr * tile_edge)))
    return 1;

  // First entry of each band (rows are sorted: a binary search a band).
  std::vector<int64_t> start(static_cast<size_t>(nbr) + 1);
#pragma omp parallel for schedule(static)
  for (int64_t b = 0; b <= nbr; ++b)
    start[b] = std::lower_bound(rows, rows + nnz, b * tile_edge) - rows;

  // Where entry i of the band that starts at row0 counts: its column
  // tile, its (tile, window) of each orientation and the lane within.
  // False for a column outside the grid.
  struct Cell { int64_t tc, tw_f, tw_b, lane_f, lane_b; };
  auto cell_of = [=](int64_t i, int64_t row0, Cell* at) -> bool {
    int64_t c = cols[i];
    if (relabel) {
      if (c < 0 || c >= n_relabel) return false;
      c = relabel[c];
    }
    if (c < 0 || c >= n_cells) return false;
    const int64_t r = rows[i];
    at->tc = c / tile_edge;
    at->tw_f = at->tc * F.wins + F.gwin(c);
    at->tw_b = at->tc * F.wins + ((r - row0) >> 7);
    at->lane_f = F.lane(r);
    at->lane_b = F.lane(c);
    return true;
  };

  int64_t best_f = 0, best_b = 0;
#pragma omp parallel num_threads(team) reduction(max : best_f, best_b) \
    reduction(| : bad)
  {
    // cell counts, then per (column tile, window) the worst lane so far,
    // then per column tile the sum of those: every increment of a worst
    // lane is an increment of its tile's sum, so the running maximum of
    // the sums is the depth and nothing is reduced at a band's end.
    std::vector<int32_t> cnt_f(n_cells), cnt_b(n_cells);
    std::vector<int32_t> top_f(n_tw), top_b(n_tw);
    std::vector<int32_t> sum_f(nbc), sum_b(nbc);
    Cell at;
#pragma omp for schedule(dynamic)
    for (int64_t b = 0; b < nbr; ++b) {
      const int64_t lo = start[b], hi = start[b + 1];
      const int64_t row0 = b * tile_edge;
      for (int64_t i = lo; i < hi; ++i) {
        if (!cell_of(i, row0, &at)) { bad = 1; continue; }
        const int32_t nf = ++cnt_f[at.tw_f * 128 + at.lane_f];
        if (nf > top_f[at.tw_f]) {
          top_f[at.tw_f] = nf;
          if (++sum_f[at.tc] > best_f) best_f = sum_f[at.tc];
        }
        const int32_t nb = ++cnt_b[at.tw_b * 128 + at.lane_b];
        if (nb > top_b[at.tw_b]) {
          top_b[at.tw_b] = nb;
          if (++sum_b[at.tc] > best_b) best_b = sum_b[at.tc];
        }
      }
      // Clear what the band touched by walking it again, not by memset:
      // a wide matrix stays O(entries).
      for (int64_t i = lo; i < hi; ++i) {
        if (!cell_of(i, row0, &at)) continue;
        cnt_f[at.tw_f * 128 + at.lane_f] = 0;
        cnt_b[at.tw_b * 128 + at.lane_b] = 0;
        top_f[at.tw_f] = top_b[at.tw_b] = 0;
        sum_f[at.tc] = sum_b[at.tc] = 0;
      }
    }
  }
  if (bad) return 1;
  out2[0] = best_f;
  out2[1] = best_b;
  return 0;
}

// Test introspection: the ACTUAL deliverable team size.  The multi-thread
// partition paths only execute when this exceeds 1 (a single-CPU host
// still delivers a >1 team under OMP_NUM_THREADS), and the team-coverage
// test asserts it rather than passing vacuously at team=1.
int64_t pl_observed_team() { return observed_team(); }

}  // extern "C"
