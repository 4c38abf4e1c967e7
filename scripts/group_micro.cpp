// Form B of scripts/group_micro.py, and nothing the program loads: runs of
// posting rows ([n, kk + 4] uint32) merged pairwise, level by level, the
// whole row carried (pc_merge2's shape with ties kept and a payload).
// Built by the micro itself; the form the tree adopted is pt_merge_runs
// in dsi_tpu/native/mergeruns.cpp.

#include <cstdint>
#include <cstring>

namespace {

inline bool before(const uint32_t* a, const uint32_t* b, int kk) {
  for (int j = 0; j < kk; j++) {
    if (a[j] != b[j]) return a[j] < b[j];
  }
  return false;
}

void merge2(const uint32_t* a, long na, const uint32_t* b, long nb, int kk,
            int w, uint32_t* out) {
  const size_t row = sizeof(uint32_t) * (size_t)w;
  long i = 0, j = 0;
  while (i < na && j < nb) {
    // b leaves first only where it sorts before a: ties keep a's row first
    if (before(b + j * w, a + i * w, kk)) {
      memcpy(out, b + j++ * w, row);
    } else {
      memcpy(out, a + i++ * w, row);
    }
    out += w;
  }
  memcpy(out, a + i * w, row * (na - i));
  memcpy(out + (na - i) * w, b + j * w, row * (nb - j));
}

}  // namespace

extern "C" {

// ``rows`` holds ``n_runs`` runs one behind the other, run r at rows
// ``edges[r]:edges[r + 1]`` (``edges`` is overwritten); ``other`` is a
// buffer of the same size.  Returns 0 where the merged table ends up in
// ``rows``, 1 where in ``other``.
int gm_merge_levels(uint32_t* rows, uint32_t* other, int64_t* edges,
                    long n_runs, int kk) {
  const int w = kk + 4;
  int flip = 0;
  while (n_runs > 1) {
    long out_runs = 0;
    for (long r = 0; r + 1 < n_runs; r += 2) {
      merge2(rows + edges[r] * w, edges[r + 1] - edges[r],
             rows + edges[r + 1] * w, edges[r + 2] - edges[r + 1], kk, w,
             other + edges[r] * w);
      edges[out_runs++] = edges[r];
    }
    if (n_runs % 2) {
      memcpy(other + edges[n_runs - 1] * w, rows + edges[n_runs - 1] * w,
             sizeof(uint32_t) * (size_t)w
                 * (edges[n_runs] - edges[n_runs - 1]));
      edges[out_runs++] = edges[n_runs - 1];
    }
    edges[out_runs] = edges[n_runs];
    n_runs = out_runs;
    uint32_t* t = rows;
    rows = other;
    other = t;
    flip ^= 1;
  }
  return flip;
}

}  // extern "C"
