// Native fill of the GAME grouping's blocks (game/data.py,
// _group_entities, inside the game.group.fill span).
//
// After the sort by entity every entity's rows are one contiguous run of
// sorted positions, and every row's entries one contiguous range of the
// row-sorted CSR.  The numpy fill ignores that: for every bucket it builds
// int64 index arrays over all of the bucket's rows and entries and pushes
// them through a chain of fancy-indexed gathers and scatters.  This file
// walks each entity's run once and copies every row field and every entry
// value to its place in its bucket's arrays, which the caller allocates
// (zeroed or sentinel-filled) exactly as the numpy path does.  It copies
// and never computes a value, and duplicates were summed before the fill,
// so every array comes out BIT-IDENTICAL to the numpy path's (tests assert
// array equality).
//
// One parallel loop covers the entities of every bucket: lanes write
// disjoint slices, and one team for the whole fill wakes the threads once
// (a team a bucket woke them twenty times a grouping, which cost up to
// 10 ms a wake-up on a host whose idle cores sleep).
//
// C ABI + ctypes (no pybind11 in this environment); the loader in
// native/__init__.py compiles this lazily with the system g++ and the
// caller falls back to the numpy path on any failure.

#include <sys/mman.h>
#include <unistd.h>

#include <algorithm>
#include <cstdint>

#ifdef _OPENMP
#include <omp.h>
#endif

extern "C" {

// The grouping's arrays over sorted positions, entries and entities.
struct GfRows {
  const int64_t* starts;      // entity -> its first sorted position
  const int64_t* span_sizes;  // entity -> its rows
  const uint8_t* keep;        // position -> kept (trained) or passive
  const int64_t* order;       // position -> global row
  const float* labels;        // global row -> label
  const float* weights;       // global row -> weight
  const int64_t* indptr;      // position -> its first entry
  const float* data;          // entry -> value
  const void* col_rank;       // entry -> its (entity, column) pair's rank
  int64_t rank_i64;           // col_rank is int64 (else int32)
  const uint8_t* col_hit;     // entry -> its pair is active (passive only)
  const int64_t* act_before;  // entity -> rank of its first active pair
  const int64_t* act_counts;  // entity -> its active columns
  const int32_t* act_col;     // active pair -> global column
};

// One bucket's arrays.  X is (E, R, D), or (E, D, R) when minor_r; Xp
// (P, D), or (D, P) when p_minor_r; the passive arrays are null where the
// bucket has no passive rows.
struct GfBucket {
  int64_t E, R, D, minor_r, P, p_minor_r;
  float* lab;
  float* wts;
  int32_t* rindex;
  float* X;
  int32_t* cmap;
  int32_t* rindexp;
  int32_t* slot;
  float* Xp;
};

}  // extern "C"

namespace {

// Asks the kernel for huge pages under a zeroed array that nothing has
// touched yet (numpy's zeros is calloc's: fresh mappings, small pages):
// the fill's first touches then fault a 2 MiB page each, zeroed by the
// faulting thread, where small-page faults were half of a fill's time on
// the CPU host it was tried on.  Only the pages' size changes, never a
// value; a refusal (no transparent huge pages) changes nothing.
void advise_huge(void* p, int64_t bytes) {
#ifdef MADV_HUGEPAGE
  if (bytes < (int64_t{1} << 22)) return;
  const uintptr_t page = static_cast<uintptr_t>(sysconf(_SC_PAGESIZE));
  const uintptr_t at = reinterpret_cast<uintptr_t>(p);
  const uintptr_t lo = (at + page - 1) & ~(page - 1);
  const uintptr_t hi = (at + static_cast<uintptr_t>(bytes)) & ~(page - 1);
  if (hi > lo) madvise(reinterpret_cast<void*>(lo), hi - lo, MADV_HUGEPAGE);
#endif
}

// Entity `g`, lane `lane` of bucket `b`: its active columns, kept rows at
// (lane, k) with k its k-th kept row, entries at local column
// col_rank - act_before[g]; its passive rows at flat row first + their
// index, with only the entries whose pair is active (the rest drop).
// Returns 1 where an index fell outside its array.
template <typename Rank>
int64_t fill_entity(const GfRows& in, const GfBucket& b, int64_t g,
                    int64_t lane, int64_t first, int64_t slot_of_lane) {
  const Rank* rank = static_cast<const Rank*>(in.col_rank);
  const int64_t R = b.R, D = b.D, P = b.P;
  const int64_t first_col = in.act_before[g];
  const int64_t n_cols = in.act_counts[g];
  if (n_cols > D) return 1;
  for (int64_t c = 0; c < n_cols; ++c)
    b.cmap[lane * D + c] = in.act_col[first_col + c];
  int64_t k = 0, f = first;
  const int64_t lo = in.starts[g], hi = lo + in.span_sizes[g];
  for (int64_t p = lo; p < hi; ++p) {
    const int64_t row = in.order[p];
    if (in.keep[p]) {
      if (k >= R || lane >= b.E) return 1;
      const int64_t at = lane * R + k;
      b.lab[at] = in.labels[row];
      b.wts[at] = in.weights[row];
      b.rindex[at] = static_cast<int32_t>(row);
      for (int64_t j = in.indptr[p]; j < in.indptr[p + 1]; ++j) {
        const int64_t c = static_cast<int64_t>(rank[j]) - first_col;
        if (c < 0 || c >= n_cols) return 1;
        b.X[b.minor_r ? (lane * D + c) * R + k : at * D + c] = in.data[j];
      }
      ++k;
    } else {
      if (!b.Xp || f < 0 || f >= P) return 1;
      b.rindexp[f] = static_cast<int32_t>(row);
      b.slot[f] = static_cast<int32_t>(slot_of_lane);
      for (int64_t j = in.indptr[p]; j < in.indptr[p + 1]; ++j) {
        if (!in.col_hit[j]) continue;
        const int64_t c = static_cast<int64_t>(rank[j]) - first_col;
        if (c < 0 || c >= n_cols) return 1;
        b.Xp[b.p_minor_r ? c * P + f : f * D + c] = in.data[j];
      }
      ++f;
    }
  }
  return 0;
}

}  // namespace

extern "C" {

// Every bucket's blocks in one pass: entity g is lane lane_of[g] of
// bucket bucket_of[g]; its passive rows start at flat row first_of[g] and
// carry slot slot_of[g].  The team grows with the entries, one thread a
// 2^20 of them (a few ms of copies a thread), so a small grouping (a
// validation set, a block of a scoring stream) does not wait for idle
// cores to wake for less work than the wake costs.  Returns 0, or 1 where
// an index fell outside its array (the caller's arrays disagree with each
// other: a bug, never a fallback).
int64_t gf_fill(const GfRows* in, const GfBucket* buckets, int64_t n_buckets,
                int64_t n_ent, int64_t n_entries, const int64_t* bucket_of,
                const int64_t* lane_of, const int64_t* first_of,
                const int64_t* slot_of) {
#ifdef _OPENMP
  const int team = static_cast<int>(std::min<int64_t>(
      omp_get_max_threads(), 1 + (n_entries >> 20)));
#endif
  for (int64_t b = 0; b < n_buckets; ++b) {
    const GfBucket& k = buckets[b];
    advise_huge(k.X, k.E * k.R * k.D * int64_t{sizeof(float)});
    if (k.Xp) advise_huge(k.Xp, k.P * k.D * int64_t{sizeof(float)});
  }
  int64_t bad = 0;
#pragma omp parallel for num_threads(team) schedule(dynamic, 16) \
    reduction(| : bad)
  for (int64_t g = 0; g < n_ent; ++g) {
    const GfBucket& b = buckets[bucket_of[g]];
    bad |= in->rank_i64
        ? fill_entity<int64_t>(*in, b, g, lane_of[g], first_of[g], slot_of[g])
        : fill_entity<int32_t>(*in, b, g, lane_of[g], first_of[g], slot_of[g]);
  }
  return bad;
}

}  // extern "C"
