// Sorted runs of packed word keys, merged: parallel/merge.PackedCounts,
// and below it parallel/merge.PostingsTable's posting rows.
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
#include <new>
#include <vector>

namespace {

// <0, 0, >0 as row a sorts before, with, after row b.
inline int cmp_rows(const uint32_t* a, const uint32_t* b, int k) {
  for (int j = 0; j < k; j++) {
    if (a[j] != b[j]) return a[j] < b[j] ? -1 : 1;
  }
  return 0;
}

// One run of posting rows in the merge's tournament: its next row and
// where it ends.
struct Run {
  const uint32_t* row;
  const uint32_t* end;
};

// A node of the tournament: the run that lost here, and the first eight
// bytes of the word at its head (lanes 0 and 1 packed; all ones once the
// run is spent), kept beside it so that a comparison reads one place.
struct Seat {
  uint64_t key;
  int64_t run;
};

inline uint64_t head_key(const Run& r, int kk) {
  if (r.row == r.end) return ~(uint64_t)0;
  return ((uint64_t)r.row[0] << 32) | (kk > 1 ? r.row[1] : 0);
}

// Of two runs whose heads agree in their first eight bytes, whether a's
// leaves before b's: the lower word in the lanes beyond, and of one word
// the earlier run's (the merge is stable).  A spent run never.
inline bool tie_leaves_first(const Run* runs, int64_t a, int64_t b, int kk) {
  const Run &ra = runs[a], &rb = runs[b];
  if (ra.row == ra.end || rb.row == rb.end) return ra.row != ra.end;
  for (int j = 2; j < kk; j++) {
    if (ra.row[j] != rb.row[j]) return ra.row[j] < rb.row[j];
  }
  return a < b;
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

// Posting rows, parallel/merge.PostingsTable: [n, kk + 4] uint32, kk key
// lanes (a word) then its length, a term frequency, a document and a
// reduce partition.  A wave's rows leave the device in word order, so the
// table's buffers are runs (rows whose words never descend; a word may
// repeat, once a document), and the index is their stable merge.

// The rows of a [n, kk + 4] table that sort before the row above them
// (where a new run starts): how many, the first ``cap`` written to
// ``cuts``.
long pt_run_cuts(const uint32_t* rows, long n, int kk, int64_t* cuts,
                 long cap) {
  const int w = kk + 4;
  long found = 0;
  for (long i = 1; i < n; i++) {
    if (cmp_rows(rows + i * w, rows + (i - 1) * w, kk) < 0) {
      if (found < cap) cuts[found] = i;
      found++;
    }
  }
  return found;
}

// ``n_runs`` runs of posting rows (``where[r]`` the address of run r's
// ``lens[r]`` rows), merged into the grouped index: a tournament of the
// runs' heads (a loser tree: log2(n_runs) comparisons a row, each
// decided by the first eight bytes of the two words almost always), the
// earlier run first among equal words, so a word's postings stay in the
// order the runs were handed over.  No row is written: what leaves is
// the index's columns, ``tfs`` and ``docs`` a posting (room for every
// row), and ``skeys`` [., kk], ``wlens``, ``parts``, ``starts`` a word
// (room for as many: the words are counted here).  Returns the words, or
// -1 where the tournament could not be allocated.
long pt_merge_runs(const uint64_t* where, const int64_t* lens, long n_runs,
                   int kk, uint32_t* skeys, uint32_t* wlens, uint32_t* parts,
                   int64_t* starts, uint32_t* tfs, uint32_t* docs) {
  const int w = kk + 4;
  const size_t key_bytes = sizeof(uint32_t) * (size_t)kk;
  long leaves = 1;
  while (leaves < n_runs) leaves *= 2;
  std::vector<Run> runs;
  std::vector<Seat> seats, up;
  try {
    runs.assign(leaves, Run{nullptr, nullptr});
    seats.assign(leaves, Seat{~(uint64_t)0, 0});
    up.assign(2 * leaves, Seat{~(uint64_t)0, 0});
  } catch (const std::bad_alloc&) {
    return -1;
  }
  long n_rows = 0;
  for (long r = 0; r < n_runs; r++) {
    const uint32_t* first = reinterpret_cast<const uint32_t*>(where[r]);
    runs[r] = Run{first, first + lens[r] * w};
    n_rows += lens[r];
  }
  // the first tournament, from the leaves up: a seat keeps the loser of
  // its two sides, and the winner goes up
  Seat* const seat = seats.data();
  for (long i = 0; i < leaves; i++) {
    up[leaves + i] = Seat{head_key(runs[i], kk), i};
  }
  for (long node = leaves - 1; node >= 1; node--) {
    const Seat a = up[2 * node], b = up[2 * node + 1];
    const bool a_first = a.key != b.key
        ? a.key < b.key
        : tie_leaves_first(runs.data(), a.run, b.run, kk);
    up[node] = a_first ? a : b;
    seat[node] = a_first ? b : a;
  }
  Seat lead = up[1];
  const uint32_t* word = nullptr;  // the last word written to skeys
  long o = 0, t = 0;
  while (o < n_rows) {
    Run& run = runs[lead.run];
    const uint32_t* row = run.row;
    if (word == nullptr || memcmp(row, word, key_bytes) != 0) {
      memcpy(skeys + t * kk, row, key_bytes);
      word = skeys + t * kk;
      wlens[t] = row[kk];
      parts[t] = row[kk + 3];
      starts[t] = o;
      t++;
    }
    // the run leads for as long as it holds the same word: every other
    // head is a later word, or the same word of a later run
    do {
      tfs[o] = row[kk + 1];
      docs[o] = row[kk + 2];
      o++;
      row += w;
    } while (row != run.end && memcmp(row, word, key_bytes) == 0);
    run.row = row;
    // hundreds of runs are more streams than the hardware follows: ask
    // for the run's rows a few lines ahead of where it is read
    __builtin_prefetch(row + 16 * w);
    lead.key = head_key(run, kk);
    // replay the leaf's path: the run meets the losers it passed, and
    // changes places with one that leaves first.  Which of two words is
    // the lower is a coin's toss to the processor, so the places change
    // by a mask and not by a branch; only a tie branches, being rare
    // where a word comes once a run
    for (long node = (leaves + lead.run) >> 1; node >= 1; node >>= 1) {
      const Seat other = seat[node];
      uint64_t swap;
      if (__builtin_expect(other.key == lead.key, 0)) {
        swap = tie_leaves_first(runs.data(), other.run, lead.run, kk);
      } else {
        swap = other.key < lead.key;
      }
      const uint64_t mask = (uint64_t)0 - swap;
      const uint64_t dkey = (other.key ^ lead.key) & mask;
      const int64_t drun = (other.run ^ lead.run) & (int64_t)mask;
      seat[node] = Seat{other.key ^ dkey, other.run ^ drun};
      lead = Seat{lead.key ^ dkey, lead.run ^ drun};
    }
  }
  return t;
}

}  // extern "C"
