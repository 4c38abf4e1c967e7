// Sorted runs of packed word keys, merged: parallel/merge.PackedCounts.
//
// A run is a table of rows that strictly increase in their key lanes
// ([n, k] uint32, lane 0 primary: big-endian zero-padded spellings, so
// lane order is byte order), with a length, a count and a reduce
// partition a row.  Every table a device step hands to the host is one
// (the step program sorted and grouped it), so the host never has to
// sort: two runs merge with two pointers, the counts of a word both hold
// summed in int64, and a window of runs merges pairwise.  Length and
// partition are functions of the word: the first run's are kept.

#include <cstdint>
#include <cstring>

namespace {

// <0, 0, >0 as row a sorts before, with, after row b.
inline int cmp_rows(const uint32_t* a, const uint32_t* b, int k) {
  for (int j = 0; j < k; j++) {
    if (a[j] != b[j]) return a[j] < b[j] ? -1 : 1;
  }
  return 0;
}

}  // namespace

extern "C" {

// 1 where the rows strictly increase (sorted and distinct), else 0.
int pc_rows_increase(const uint32_t* keys, long n, int k) {
  for (long i = 1; i < n; i++) {
    if (cmp_rows(keys + (i - 1) * k, keys + i * k, k) >= 0) return 0;
  }
  return 1;
}

// Runs a and b of one lane width into ``out`` (room for na + nb rows).
// Returns the rows written: the distinct keys of both, increasing.
long pc_merge2(const uint32_t* ak, const int32_t* al, const int64_t* ac,
               const int32_t* ap, long na,
               const uint32_t* bk, const int32_t* bl, const int64_t* bc,
               const int32_t* bp, long nb, int k,
               uint32_t* ok, int32_t* ol, int64_t* oc, int32_t* op) {
  long i = 0, j = 0, o = 0;
  const size_t row = sizeof(uint32_t) * (size_t)k;
  while (i < na && j < nb) {
    const int c = cmp_rows(ak + i * k, bk + j * k, k);
    if (c <= 0) {
      memcpy(ok + o * k, ak + i * k, row);
      ol[o] = al[i];
      op[o] = ap[i];
      oc[o] = ac[i];
      if (c == 0) oc[o] += bc[j++];
      i++;
    } else {
      memcpy(ok + o * k, bk + j * k, row);
      ol[o] = bl[j];
      op[o] = bp[j];
      oc[o] = bc[j];
      j++;
    }
    o++;
  }
  if (i < na) {
    const long m = na - i;
    memcpy(ok + o * k, ak + i * k, row * m);
    memcpy(ol + o, al + i, sizeof(int32_t) * m);
    memcpy(oc + o, ac + i, sizeof(int64_t) * m);
    memcpy(op + o, ap + i, sizeof(int32_t) * m);
    o += m;
  }
  if (j < nb) {
    const long m = nb - j;
    memcpy(ok + o * k, bk + j * k, row * m);
    memcpy(ol + o, bl + j, sizeof(int32_t) * m);
    memcpy(oc + o, bc + j, sizeof(int64_t) * m);
    memcpy(op + o, bp + j, sizeof(int32_t) * m);
    o += m;
  }
  return o;
}

}  // extern "C"
