// Native slot index: key -> slot assignment with LRU eviction.
//
// The host-side hot path of the TPU rate limiter: every decision needs a
// key -> slot lookup before it can join a device batch.  The pure-Python
// index (ratelimiter_tpu/engine/slots.py — the semantic reference for this
// file) tops out around 1-2M ops/s; this open-addressing table with an
// intrusive LRU list sustains tens of millions, keeping the host from
// starving the device.
//
// Design:
//  - 128-bit key fingerprints (two independent FNV-1a streams) instead of
//    stored keys: collision odds ~n^2/2^129 (~1e-25 at 10M keys).  Both
//    string keys and int64 ids are supported; a per-limiter `lid` seed is
//    mixed in so tenants are isolated.
//  - Open addressing, linear probing, power-of-two capacity, tombstone-free
//    deletion (backward-shift), load factor <= 0.5.
//  - Intrusive doubly-linked LRU over the entries; eviction returns the
//    victim's slot so the caller can zero its device state before reuse.
//    Recency is BATCH-GRANULAR by design: all hits of a key within one
//    batch-assign call count as one touch (at its first occurrence), so
//    repeat hits skip the 3-cache-line LRU re-link — the dominant host
//    cost on Zipf traffic.  Keys touched in the same batch are equally
//    "recent" for eviction purposes (the same resolution trade Redis
//    makes with its sampled LRU); the Python index documents the same
//    contract for its scalar path, where every call is its own batch.
//  - Pinning: (a) an explicit pin refcount per slot for queued async
//    requests, (b) a generation stamp so entries touched by the current
//    batch call are never evicted by later keys of the same batch.
//
// Exposed as a plain C ABI for ctypes (no pybind11 in the image).

#include <algorithm>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <exception>
#include <thread>
#include <vector>

#if defined(__linux__)
#include <sys/mman.h>
#endif

namespace {

// Exactly 32 bytes, 32-aligned: two entries per cache line with no
// straddle, so the home-bucket probe touches ONE line.  gen is u32 (a
// per-batch counter compared only for equality with the current batch;
// a wrap after 2^32 batches can at worst skip one LRU re-link or
// eviction candidate once — recency noise, not a correctness hazard).
struct alignas(32) Entry {
  uint64_t h1 = 0, h2 = 0;  // 128-bit fingerprint; h1==0 && h2==0 => empty
  int32_t slot = -1;
  int32_t lru_prev = -1, lru_next = -1;
  uint32_t gen = 0;
};

struct Index {
  int64_t num_slots;
  uint64_t mask;              // table size - 1
  std::vector<Entry> table;
  std::vector<int32_t> entry_of_slot;  // slot -> table position (-1 if free)
  std::vector<int32_t> free_slots;
  std::vector<uint32_t> pins;          // slot -> pin refcount
  // Slots removed (admin reset) while their pin refcount was nonzero:
  // freeing them immediately would let a new key take the slot before the
  // pinned dispatch enqueues, receiving its stale write.  They are flagged
  // here and surface on the dirty list at last unpin; reassignment reports
  // them as their own eviction so the caller re-clears device state first.
  std::vector<uint8_t> deferred;       // slot -> removed-while-pinned flag
  std::vector<int32_t> dirty_free;     // unpinned deferred slots (need clear)
  int64_t size = 0;
  int32_t lru_head = -1, lru_tail = -1;  // head = most recent
  uint64_t gen = 0;
  // Scratch for the relay path (assign_batch_uniques): per-slot duplicate
  // counters for the current batch, epoch-tagged so no per-batch reset is
  // needed.  One 16-byte struct per slot (not parallel arrays) so the
  // rank loop costs a single cache-line touch per request, which pass 2
  // prefetches ahead from the already-resolved slot ids.  Allocated
  // lazily on the first uniques call.
  struct BatchScratch {
    uint64_t epoch = 0;   // last batch generation seen
    int32_t cnt = 0;      // occurrences so far this batch
    int32_t uidx = -1;    // dense unique index this batch
  };
  std::vector<BatchScratch> batch;
  std::vector<int32_t> ucnt;           // dense per-unique occurrence counts
  // Within-batch front cache: repeat hits of a key inside one batch call
  // (most of Zipf traffic) resolve from this cache-resident direct-mapped
  // table instead of re-probing the DRAM hash table.  Safe because a hit
  // is only honored when the line was verified under the CURRENT batch
  // generation — and current-generation entries are eviction-protected,
  // so the cached slot cannot have been reassigned mid-batch.  One
  // 32-byte struct per line (not parallel arrays): a hit touches ONE
  // cache line, and the line carries the batch-dense unique index so the
  // fused uniques walk never touches the slot-indexed scratch on hits.
  struct FcLine {
    uint64_t h1 = 0, h2 = 0;
    uint64_t gen = 0;
    int32_t slot = -1;
    int32_t uidx = -1;
  };
  std::vector<FcLine> fc;
};

const uint64_t kFrontCacheSize = 1 << 17;  // 128K lines, 4 MB

static void advise_huge(void* p, size_t bytes) {
  // The probe is one random DRAM access per request; at 10M+ slots the
  // table spans hundreds of MB and 4K-page TLB misses double its cost.
  // Transparent huge pages are advisory — failure is fine.  madvise
  // rejects non-page-aligned starts with EINVAL, and heap pointers are
  // rarely page-aligned, so round the range inward first.
#if defined(__linux__) && defined(MADV_HUGEPAGE)
  const uintptr_t kPage = 4096;
  uintptr_t start = (reinterpret_cast<uintptr_t>(p) + kPage - 1) & ~(kPage - 1);
  uintptr_t end = (reinterpret_cast<uintptr_t>(p) + bytes) & ~(kPage - 1);
  if (end > start && end - start >= (2u << 20))
    madvise(reinterpret_cast<void*>(start), end - start, MADV_HUGEPAGE);
#else
  (void)p;
  (void)bytes;
#endif
}

inline void fnv_mix(uint64_t& h, uint64_t x) {
  h ^= x;
  h *= 0x100000001b3ULL;
}

inline void hash_bytes(const uint8_t* p, int64_t n, uint64_t seed,
                       uint64_t& h1, uint64_t& h2) {
  h1 = 0xcbf29ce484222325ULL ^ seed;
  h2 = 0x84222325cbf29ce4ULL ^ (seed * 0x9e3779b97f4a7c15ULL);
  for (int64_t i = 0; i < n; i++) {
    fnv_mix(h1, p[i]);
    h2 = (h2 ^ (p[i] + 0x9e3779b97f4a7c15ULL + (h2 << 6) + (h2 >> 2)));
  }
  h2 = h2 * 0xff51afd7ed558ccdULL + n;
  if (h1 == 0 && h2 == 0) h2 = 1;  // reserve (0,0) for "empty"
}

inline void hash_int(int64_t key, uint64_t seed, uint64_t& h1, uint64_t& h2) {
  uint64_t x = static_cast<uint64_t>(key) + seed * 0x9e3779b97f4a7c15ULL;
  // splitmix64 twice for two independent streams
  uint64_t z = (x += 0x9e3779b97f4a7c15ULL);
  z = (z ^ (z >> 30)) * 0xbf58476d1ce4e5b9ULL;
  z = (z ^ (z >> 27)) * 0x94d049bb133111ebULL;
  h1 = z ^ (z >> 31);
  z = (x += 0x9e3779b97f4a7c15ULL);
  z = (z ^ (z >> 30)) * 0xbf58476d1ce4e5b9ULL;
  z = (z ^ (z >> 27)) * 0x94d049bb133111ebULL;
  h2 = z ^ (z >> 31);
  if (h1 == 0 && h2 == 0) h2 = 1;
}

// -- LRU helpers -------------------------------------------------------------

inline uint32_t gen32(const Index* ix) {
  return static_cast<uint32_t>(ix->gen);
}

inline void lru_unlink(Index* ix, int32_t pos) {
  Entry& e = ix->table[pos];
  if (e.lru_prev >= 0) ix->table[e.lru_prev].lru_next = e.lru_next;
  else ix->lru_head = e.lru_next;
  if (e.lru_next >= 0) ix->table[e.lru_next].lru_prev = e.lru_prev;
  else ix->lru_tail = e.lru_prev;
  e.lru_prev = e.lru_next = -1;
}

inline void lru_push_front(Index* ix, int32_t pos) {
  Entry& e = ix->table[pos];
  e.lru_prev = -1;
  e.lru_next = ix->lru_head;
  if (ix->lru_head >= 0) ix->table[ix->lru_head].lru_prev = pos;
  ix->lru_head = pos;
  if (ix->lru_tail < 0) ix->lru_tail = pos;
}

inline void lru_touch(Index* ix, int32_t pos) {
  if (ix->lru_head == pos) return;
  lru_unlink(ix, pos);
  lru_push_front(ix, pos);
}

// -- table ops ---------------------------------------------------------------

inline int32_t find(Index* ix, uint64_t h1, uint64_t h2) {
  uint64_t pos = h1 & ix->mask;
  while (true) {
    Entry& e = ix->table[pos];
    if (e.h1 == 0 && e.h2 == 0) return -1;
    if (e.h1 == h1 && e.h2 == h2) return static_cast<int32_t>(pos);
    pos = (pos + 1) & ix->mask;
  }
}

// Backward-shift deletion keeps probe chains intact without tombstones.
inline void erase_at(Index* ix, uint64_t pos) {
  uint64_t hole = pos;
  uint64_t next = (hole + 1) & ix->mask;
  while (true) {
    Entry& e = ix->table[next];
    if (e.h1 == 0 && e.h2 == 0) break;
    uint64_t home = e.h1 & ix->mask;
    // Can e move into the hole? Yes iff hole lies within [home, next).
    bool movable = ((next - home) & ix->mask) >= ((next - hole) & ix->mask);
    if (movable) {
      // Fix LRU links & slot back-pointer to the new position.
      int32_t np = static_cast<int32_t>(next), hp = static_cast<int32_t>(hole);
      if (e.lru_prev >= 0) ix->table[e.lru_prev].lru_next = hp;
      else ix->lru_head = hp;
      if (e.lru_next >= 0) ix->table[e.lru_next].lru_prev = hp;
      else ix->lru_tail = hp;
      ix->entry_of_slot[e.slot] = hp;
      ix->table[hole] = e;
      e = Entry{};
      hole = next;
      (void)np;
    }
    next = (next + 1) & ix->mask;
  }
  ix->table[hole] = Entry{};
}

inline int32_t insert(Index* ix, uint64_t h1, uint64_t h2, int32_t slot) {
  uint64_t pos = h1 & ix->mask;
  while (true) {
    Entry& e = ix->table[pos];
    if (e.h1 == 0 && e.h2 == 0) {
      e.h1 = h1; e.h2 = h2; e.slot = slot;
      e.gen = gen32(ix);
      ix->entry_of_slot[slot] = static_cast<int32_t>(pos);
      lru_push_front(ix, static_cast<int32_t>(pos));
      ix->size++;
      return static_cast<int32_t>(pos);
    }
    pos = (pos + 1) & ix->mask;
  }
}

// Returns evicted slot (>= 0) or -1 if a free slot was available, -2 if
// eviction failed (everything pinned).
inline int64_t take_slot(Index* ix, int32_t* out_slot) {
  if (!ix->free_slots.empty()) {
    *out_slot = ix->free_slots.back();
    ix->free_slots.pop_back();
    return -1;
  }
  // Dirty free slots (removed while pinned, since unpinned) may carry a
  // stale write from the formerly-pinned dispatch: hand them out as their
  // own "eviction" so the caller zeroes the device state before reuse.
  // A dirty slot can have been RE-pinned since it was listed (a queued
  // micro-batch request pinned via the per-call set) — skip those, exactly
  // as the LRU eviction scan below does.  The list is tiny (admin resets
  // racing streams), so the scan is O(few).
  for (size_t i = ix->dirty_free.size(); i-- > 0;) {
    int32_t slot = ix->dirty_free[i];
    if (ix->pins[slot] == 0) {
      ix->dirty_free.erase(ix->dirty_free.begin() + i);
      *out_slot = slot;
      return slot;
    }
  }
  // Evict from LRU tail, skipping pinned and current-generation entries.
  int32_t pos = ix->lru_tail;
  while (pos >= 0) {
    Entry& e = ix->table[pos];
    if (ix->pins[e.slot] == 0 && e.gen != gen32(ix)) {
      int32_t victim_slot = e.slot;
      lru_unlink(ix, pos);
      ix->entry_of_slot[victim_slot] = -1;
      erase_at(ix, static_cast<uint64_t>(pos));
      ix->size--;
      *out_slot = victim_slot;
      return victim_slot;
    }
    pos = e.lru_prev;
  }
  return -2;
}

// Probe-or-insert WITHOUT front-cache handling (callers manage the fc
// line themselves; the fused uniques walk writes it with the unique id).
inline int64_t probe_or_insert(Index* ix, uint64_t h1, uint64_t h2,
                               int32_t* out_slot) {
  int32_t pos = find(ix, h1, h2);
  if (pos >= 0) {
    Entry& e = ix->table[pos];
    // Repeat hit within the same batch generation: the entry is already
    // recency-stamped and eviction-protected; skip the LRU re-link (3
    // random cache lines).  Zipf batches repeat hot keys constantly, so
    // this removes most of the pointer chasing on the host hot path.
    if (e.gen != gen32(ix)) {
      e.gen = gen32(ix);
      lru_touch(ix, pos);
    }
    *out_slot = e.slot;
    return -1;
  }
  int32_t slot;
  int64_t evicted = take_slot(ix, &slot);
  if (evicted == -2) { *out_slot = -1; return -2; }
  insert(ix, h1, h2, slot);
  *out_slot = slot;
  return evicted;
}

inline int64_t assign_hashed(Index* ix, uint64_t h1, uint64_t h2,
                             int32_t* out_slot) {
  const uint64_t fci = h1 & (kFrontCacheSize - 1);
  if (!ix->fc.empty()) {
    Index::FcLine& L = ix->fc[fci];
    if (L.gen == ix->gen && L.h1 == h1 && L.h2 == h2) {
      // Repeat hit within this batch: already gen-stamped + LRU-touched.
      *out_slot = L.slot;
      return -1;
    }
  }
  int64_t evicted = probe_or_insert(ix, h1, h2, out_slot);
  if (evicted != -2 && !ix->fc.empty()) {
    Index::FcLine& L = ix->fc[fci];
    L.h1 = h1; L.h2 = h2; L.gen = ix->gen;
    L.slot = *out_slot; L.uidx = -1;
  }
  return evicted;
}

// One batch-assign loop for every key flavor (the hash functor is the
// only difference).  Chunked hash-then-prefetch-then-probe: the probe is
// DRAM-latency-bound, so home buckets are prefetched a chunk ahead.
const int kChunk = 32;

inline void ensure_fc(Index* ix) {
  if (ix->fc.empty()) {  // batch paths only; scalar calls skip the fc
    ix->fc.assign(kFrontCacheSize, Index::FcLine{});
    advise_huge(ix->fc.data(), ix->fc.size() * sizeof(Index::FcLine));
  }
}

template <typename HashAt>
inline void assign_batch(Index* ix, int64_t n, int32_t* out_slots,
                         int32_t* out_evicted, HashAt&& hash_at) {
  ensure_fc(ix);
  ix->gen++;
  uint64_t h1s[kChunk], h2s[kChunk];
  for (int64_t base = 0; base < n; base += kChunk) {
    int64_t m = n - base < kChunk ? n - base : kChunk;
    for (int64_t j = 0; j < m; j++) {
      hash_at(base + j, h1s[j], h2s[j]);
      __builtin_prefetch(&ix->fc[h1s[j] & (kFrontCacheSize - 1)], 1, 3);
      __builtin_prefetch(&ix->table[h1s[j] & ix->mask], 1, 1);
    }
    for (int64_t j = 0; j < m; j++) {
      int64_t ev = assign_hashed(ix, h1s[j], h2s[j], &out_slots[base + j]);
      out_evicted[base + j] = static_cast<int32_t>(ev);
    }
  }
}

// Unique-compaction variant (the segment-digest path): one uint32 word
// per UNIQUE slot of the batch — (slot << (rank_bits+1)) | (count << 1)
// with count clamped like the rank — plus per-request (unique-index,
// rank) scratch the caller keeps host-side to reconstruct per-request
// decisions from the device's per-unique allowed counts.  On skewed
// traffic this cuts host->device bytes by the duplicate factor.
// Returns the number of uniques (first-appearance order).
// FUSED probe + duplicate-structure walk: one pass over the requests.
// Front-cache hits (the bulk of skewed traffic) touch ONE fc cache line
// and one dense-ucnt cell — the slot-indexed scratch (tens of MB, a DRAM
// touch per request in the old two-pass layout) is consulted only on fc
// misses.  Within a chunk, requests are staged hits-then-misses; a key's
// requests always land in the SAME stage (the fc line is stable across a
// chunk's check loop), so per-segment rank order stays arrival order.
template <typename HashAt>
inline int64_t assign_batch_uniques(Index* ix, int64_t n, int32_t rank_bits,
                                    uint32_t* out_uwords, int32_t* out_uidx,
                                    int32_t* out_rank, int32_t* out_evicted,
                                    HashAt&& hash_at) {
  if (ix->batch.empty()) {
    ix->batch.assign(ix->num_slots, {});
    advise_huge(ix->batch.data(),
                ix->batch.size() * sizeof(Index::BatchScratch));
  }
  if (static_cast<int64_t>(ix->ucnt.size()) < n) ix->ucnt.resize(n);
  ensure_fc(ix);
  ix->gen++;
  const uint64_t epoch = ix->gen;
  const uint32_t rank_max = (1u << rank_bits) - 1;
  Index::BatchScratch* scratch = ix->batch.data();
  Index::FcLine* fc = ix->fc.data();
  int32_t* ucnt = ix->ucnt.data();
  int64_t u = 0;
  uint64_t h1s[kChunk], h2s[kChunk];
  int64_t misses[kChunk];
  for (int64_t base = 0; base < n; base += kChunk) {
    int64_t m = n - base < kChunk ? n - base : kChunk;
    for (int64_t j = 0; j < m; j++) {
      hash_at(base + j, h1s[j], h2s[j]);
      __builtin_prefetch(&fc[h1s[j] & (kFrontCacheSize - 1)], 1, 3);
    }
    // Stage 1: fc hits resolve immediately; misses queue with their
    // table bucket prefetched (the DRAM latency overlaps the rest of
    // the chunk instead of stalling per request).
    int64_t nm = 0;
    for (int64_t j = 0; j < m; j++) {
      const int64_t i = base + j;
      Index::FcLine& L = fc[h1s[j] & (kFrontCacheSize - 1)];
      if (L.gen == epoch && L.h1 == h1s[j] && L.h2 == h2s[j]) {
        out_evicted[i] = -1;
        out_uidx[i] = L.uidx;
        out_rank[i] = ucnt[L.uidx]++;
        continue;
      }
      __builtin_prefetch(&ix->table[h1s[j] & ix->mask], 1, 1);
      misses[nm++] = j;
    }
    // Stage 2: misses probe/insert the main table in arrival order.
    // 2a resolves every miss's table position (home bucket prefetched
    // in stage 1) while issuing prefetches for the strict-LRU relink
    // neighbors and the slot scratch that 2b will touch — the relink
    // is up to 3 random DRAM accesses that a serial loop pays at full
    // latency per request (the 10M-key uniform walk measured
    // ~198 ns/request, VERDICT r3 #3); overlapping them across the
    // chunk is the fix.  Recorded positions stay valid across pure
    // INSERTS (linear-probe insert fills an empty bucket and never
    // relocates existing entries) — only an EVICTION's backward-shift
    // erase can move entries, so 2b keeps using the staged positions
    // until the first eviction of the chunk and re-probes after (the
    // r5 code fell back to fully serial probe_or_insert for the WHOLE
    // chunk on any insert, which made first-touch churn passes lose
    // every prefetch the staged path buys — the scenario-4
    // churn-vs-steady gap).
    int32_t hitpos[kChunk];
    bool has_insert = false;
    const uint32_t g32 = gen32(ix);
    for (int64_t k = 0; k < nm; k++) {
      const int64_t j = misses[k];
      int32_t pos = find(ix, h1s[j], h2s[j]);
      hitpos[k] = pos;
      if (pos < 0) {
        has_insert = true;
        continue;
      }
      const Entry& e = ix->table[pos];
      if (e.gen != g32) {
        if (e.lru_prev >= 0)
          __builtin_prefetch(&ix->table[e.lru_prev], 1, 1);
        if (e.lru_next >= 0)
          __builtin_prefetch(&ix->table[e.lru_next], 1, 1);
      }
      __builtin_prefetch(&scratch[e.slot], 1, 1);
    }
    if (ix->lru_head >= 0)
      __builtin_prefetch(&ix->table[ix->lru_head], 1, 1);
    if (has_insert) {
      // First-touch staging: the inserts of this chunk will pop the
      // free-list tail in order (as long as no eviction interleaves),
      // so prefetch those slots' batch scratch + back-pointer lines
      // now; a wrong guess (eviction path taken instead) is harmless.
      const int64_t fs = static_cast<int64_t>(ix->free_slots.size());
      int64_t taken = 0;
      for (int64_t k = 0; k < nm && taken < fs; k++) {
        if (hitpos[k] >= 0) continue;
        int32_t s = ix->free_slots[fs - 1 - taken++];
        __builtin_prefetch(&scratch[s], 1, 1);
        __builtin_prefetch(&ix->entry_of_slot[s], 1, 1);
      }
    }
    bool positions_valid = true;
    for (int64_t k = 0; k < nm; k++) {
      const int64_t j = misses[k];
      const int64_t i = base + j;
      int32_t slot;
      int64_t ev;
      if (hitpos[k] >= 0 && positions_valid) {
        Entry& e = ix->table[hitpos[k]];
        if (e.gen != g32) {
          e.gen = g32;
          lru_touch(ix, hitpos[k]);
        }
        slot = e.slot;
        ev = -1;
      } else {
        ev = probe_or_insert(ix, h1s[j], h2s[j], &slot);
        // An eviction ran erase_at (backward shift relocates entries):
        // staged positions recorded in 2a may now be stale.
        if (ev >= 0) positions_valid = false;
      }
      out_evicted[i] = static_cast<int32_t>(ev);
      if (ev == -2) {  // assignment failed: deny lane, not a unique
        out_uidx[i] = -1;
        out_rank[i] = 0;
        continue;
      }
      Index::BatchScratch& b = scratch[slot];
      int32_t ui;
      if (b.epoch != epoch) {
        b.epoch = epoch;
        ui = b.uidx = static_cast<int32_t>(u);
        out_uwords[u] = static_cast<uint32_t>(slot) << (rank_bits + 1);
        ucnt[u] = 0;
        u++;
      } else {
        ui = b.uidx;
      }
      Index::FcLine& L = fc[h1s[j] & (kFrontCacheSize - 1)];
      L.h1 = h1s[j]; L.h2 = h2s[j]; L.gen = epoch;
      L.slot = slot; L.uidx = ui;
      out_uidx[i] = ui;
      out_rank[i] = ucnt[ui]++;
    }
  }
  for (int64_t j = 0; j < u; j++) {
    uint32_t cnt = static_cast<uint32_t>(ucnt[j]);
    if (cnt > rank_max) cnt = rank_max;
    out_uwords[j] |= cnt << 1;
  }
  return u;
}

// -- Ranged partition routing (engine/partitioned.py) ------------------------
// The host-partitioned index routes a batch to its partitions and merges
// the partitions' walks back to request order in contiguous request
// ranges [bounds[r], bounds[r+1]) that run side by side, each writing
// only its own slice of the outputs.

// body(r) for every range: range 0 on the calling thread, the others on
// threads of their own, started together and joined before returning.
// A range whose thread cannot be started runs on the caller.
template <typename Body>
void on_ranges(int32_t n_ranges, const Body& body) {
  std::vector<std::thread> threads;
  int32_t started = 1;
  try {
    threads.reserve(n_ranges > 1 ? n_ranges - 1 : 0);
    for (; started < n_ranges; started++) threads.emplace_back(body, started);
  } catch (const std::exception&) {
  }
  for (int32_t r = started; r < n_ranges; r++) body(r);
  if (n_ranges > 0) body(0);
  for (auto& t : threads) t.join();
}

// Partition of each request of [a, b) into part[] and the range's count
// per partition: the splitmix64 finalizer of an int key (hashed, as
// rl_shard_route) or a fingerprint's h1 as it is (as rl_route_hashes),
// modulo n_parts; a power-of-two count masks instead, with the same
// result.
void route_range(const uint64_t* keys, int64_t a, int64_t b,
                 int32_t n_parts, bool hashed, uint8_t* part,
                 int64_t* counts) {
  int64_t cnt[256] = {0};
  const uint64_t np = static_cast<uint64_t>(n_parts);
  const bool pow2 = (np & (np - 1)) == 0;
  for (int64_t i = a; i < b; i++) {
    uint64_t x = keys[i];
    if (hashed) {
      x += 0x9E3779B97F4A7C15ULL;
      x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9ULL;
      x = (x ^ (x >> 27)) * 0x94D049BB133111EBULL;
      x = x ^ (x >> 31);
    }
    const uint32_t p = static_cast<uint32_t>(pow2 ? x & (np - 1) : x % np);
    part[i] = static_cast<uint8_t>(p);
    cnt[p]++;
  }
  for (int32_t p = 0; p < n_parts; p++) counts[p] = cnt[p];
}

// dst[cursors[p]++] = src[i] for each request i of [a, b) in partition
// p = part[i]; src1/dst1 may be null.
void scatter_range(const uint8_t* part, int64_t a, int64_t b,
                   int32_t n_parts, const int64_t* cursors,
                   const uint64_t* src0, uint64_t* dst0,
                   const uint64_t* src1, uint64_t* dst1) {
  int64_t cur[256];
  for (int32_t p = 0; p < n_parts; p++) cur[p] = cursors[p];
  if (src1 != nullptr) {
    for (int64_t i = a; i < b; i++) {
      const int64_t j = cur[part[i]]++;
      dst0[j] = src0[i];
      dst1[j] = src1[i];
    }
  } else {
    for (int64_t i = a; i < b; i++) dst0[cur[part[i]]++] = src0[i];
  }
}

// dst0[i] = src0[p][j] + add0[p] and dst1[i] = src1[p][j] for each
// request i of [a, b), where p = part[i] and j = cursors[p]++ is its
// position in that partition's walk; src1/dst1 may be null.
void merge_range(const uint8_t* part, int64_t a, int64_t b,
                 int32_t n_parts, const int64_t* cursors,
                 const int32_t* const* src0, const int32_t* add0,
                 int32_t* dst0, const int32_t* const* src1,
                 int32_t* dst1) {
  int64_t cur[256];
  for (int32_t p = 0; p < n_parts; p++) cur[p] = cursors[p];
  if (src1 != nullptr) {
    for (int64_t i = a; i < b; i++) {
      const uint8_t p = part[i];
      const int64_t j = cur[p]++;
      dst0[i] = src0[p][j] + add0[p];
      dst1[i] = src1[p][j];
    }
  } else {
    for (int64_t i = a; i < b; i++) {
      const uint8_t p = part[i];
      dst0[i] = src0[p][cur[p]++] + add0[p];
    }
  }
}

}  // namespace

extern "C" {

void* rl_index_new(int64_t num_slots) {
  Index* ix = new Index();
  ix->num_slots = num_slots;
  uint64_t cap = 16;
  while (cap < static_cast<uint64_t>(num_slots) * 2) cap <<= 1;
  ix->mask = cap - 1;
  ix->table.assign(cap, Entry{});
  advise_huge(ix->table.data(), cap * sizeof(Entry));
  ix->entry_of_slot.assign(num_slots, -1);
  ix->pins.assign(num_slots, 0);
  ix->deferred.assign(num_slots, 0);
  ix->free_slots.reserve(num_slots);
  for (int64_t s = num_slots - 1; s >= 0; s--)
    ix->free_slots.push_back(static_cast<int32_t>(s));
  return ix;
}

void rl_index_free(void* h) { delete static_cast<Index*>(h); }

int64_t rl_index_len(void* h) { return static_cast<Index*>(h)->size; }

// Batch assign for int64 keys. out_evicted[i] = slot to clear before reuse
// (-1 none, -2 assignment failed: all pinned).
//
void rl_index_assign_ints(void* h, const int64_t* keys, int64_t n,
                          uint64_t lid_seed, int32_t* out_slots,
                          int32_t* out_evicted) {
  assign_batch(static_cast<Index*>(h), n, out_slots, out_evicted,
               [&](int64_t i, uint64_t& h1, uint64_t& h2) {
                 hash_int(keys[i], lid_seed, h1, h2);
               });
}

// Batch assign for int64 keys with PER-REQUEST seeds (multi-tenant batches:
// seed = limiter id, so the namespace is identical to per-lid scalar calls).
void rl_index_assign_ints_multi(void* h, const int64_t* keys,
                                const uint64_t* seeds, int64_t n,
                                int32_t* out_slots, int32_t* out_evicted) {
  assign_batch(static_cast<Index*>(h), n, out_slots, out_evicted,
               [&](int64_t i, uint64_t& h1, uint64_t& h2) {
                 hash_int(keys[i], seeds[i], h1, h2);
               });
}

// Batch assign for string keys packed as bytes + offsets (offsets[n] entries
// of start positions, key i = data[offsets[i]..offsets[i+1])).
void rl_index_assign_bytes(void* h, const uint8_t* data, const int64_t* offsets,
                           int64_t n, uint64_t lid_seed, int32_t* out_slots,
                           int32_t* out_evicted) {
  assign_batch(static_cast<Index*>(h), n, out_slots, out_evicted,
               [&](int64_t i, uint64_t& h1, uint64_t& h2) {
                 hash_bytes(data + offsets[i], offsets[i + 1] - offsets[i],
                            lid_seed, h1, h2);
               });
}

// Unique-compaction variants (see assign_batch_uniques above).
int64_t rl_index_assign_ints_uniques(void* h, const int64_t* keys, int64_t n,
                                     uint64_t lid_seed, int32_t rank_bits,
                                     uint32_t* out_uwords, int32_t* out_uidx,
                                     int32_t* out_rank, int32_t* out_evicted) {
  return assign_batch_uniques(static_cast<Index*>(h), n, rank_bits,
                              out_uwords, out_uidx, out_rank, out_evicted,
                              [&](int64_t i, uint64_t& h1, uint64_t& h2) {
                                hash_int(keys[i], lid_seed, h1, h2);
                              });
}

int64_t rl_index_assign_ints_multi_uniques(
    void* h, const int64_t* keys, const uint64_t* seeds, int64_t n,
    int32_t rank_bits, uint32_t* out_uwords, int32_t* out_uidx,
    int32_t* out_rank, int32_t* out_evicted) {
  return assign_batch_uniques(static_cast<Index*>(h), n, rank_bits,
                              out_uwords, out_uidx, out_rank, out_evicted,
                              [&](int64_t i, uint64_t& h1, uint64_t& h2) {
                                hash_int(keys[i], seeds[i], h1, h2);
                              });
}

int64_t rl_index_assign_bytes_uniques(
    void* h, const uint8_t* data, const int64_t* offsets, int64_t n,
    uint64_t lid_seed, int32_t rank_bits, uint32_t* out_uwords,
    int32_t* out_uidx, int32_t* out_rank, int32_t* out_evicted) {
  return assign_batch_uniques(
      static_cast<Index*>(h), n, rank_bits, out_uwords, out_uidx, out_rank,
      out_evicted, [&](int64_t i, uint64_t& h1, uint64_t& h2) {
        hash_bytes(data + offsets[i], offsets[i + 1] - offsets[i], lid_seed,
                   h1, h2);
      });
}

// Unique-compaction assign for PRECOMPUTED fingerprints — the native
// string fast path: the CPython-API hasher (str_pack.cpp:
// rl_strlist_hash_fp) emits (h1, h2) straight from the interned UTF-8
// buffers, and this walk consumes them with zero byte copies.  The
// fingerprints are bit-identical to hash_bytes over the same UTF-8, so
// this path interoperates with every bytes/scalar entry point.  The
// (0,0) reservation guard is applied here too so raw callers can't
// alias the empty sentinel.
int64_t rl_index_assign_fps_uniques(
    void* h, const uint64_t* h1s, const uint64_t* h2s, int64_t n,
    int32_t rank_bits, uint32_t* out_uwords, int32_t* out_uidx,
    int32_t* out_rank, int32_t* out_evicted) {
  return assign_batch_uniques(static_cast<Index*>(h), n, rank_bits,
                              out_uwords, out_uidx, out_rank, out_evicted,
                              [&](int64_t i, uint64_t& h1, uint64_t& h2) {
                                h1 = h1s[i];
                                h2 = h2s[i] |
                                     (h1 == 0 && h2s[i] == 0 ? 1 : 0);
                              });
}

// Batch fingerprint hashing for packed byte keys (no table access): the
// fallback producer for the fingerprint paths when the CPython hasher
// is unavailable, and the router's input for sharded string streams.
// Bit-identical to the hash the assign walks compute internally.
void rl_hash_bytes_batch(const uint8_t* data, const int64_t* offsets,
                         int64_t n, uint64_t seed, uint64_t* out_h1,
                         uint64_t* out_h2) {
  for (int64_t i = 0; i < n; i++) {
    hash_bytes(data + offsets[i], offsets[i + 1] - offsets[i], seed,
               out_h1[i], out_h2[i]);
  }
}

// Shard routing from precomputed fingerprints (string streams): shard =
// h1 % n_shards plus the same stable counting sort as rl_shard_route,
// so each shard's requests become one contiguous slice in arrival
// order.  Must agree with parallel/sharded.py:shard_of_key's string
// branch (which computes the same h1 scalar-side).
void rl_route_hashes(const uint64_t* h1s, int64_t n, int32_t n_shards,
                     int32_t* out_shard, int64_t* out_order,
                     int64_t* out_counts) {
  for (int32_t s = 0; s < n_shards; s++) out_counts[s] = 0;
  const uint64_t ns = static_cast<uint64_t>(n_shards);
  for (int64_t i = 0; i < n; i++) {
    int32_t s = static_cast<int32_t>(h1s[i] % ns);
    out_shard[i] = s;
    out_counts[s]++;
  }
  std::vector<int64_t> off(n_shards);
  int64_t acc = 0;
  for (int32_t s = 0; s < n_shards; s++) {
    off[s] = acc;
    acc += out_counts[s];
  }
  for (int64_t i = 0; i < n; i++) out_order[off[out_shard[i]]++] = i;
}

// Fused route + gather (r6): same as rl_shard_route but the second
// pass also emits the keys in shard-sorted order — on the 1-core bench
// host the separate numpy fancy-gather was a whole extra memory pass
// per chunk.
void rl_shard_route2(const int64_t* keys, int64_t n, int32_t n_shards,
                     int32_t* out_shard, int64_t* out_order,
                     int64_t* out_counts, int64_t* out_keys_sorted) {
  for (int32_t s = 0; s < n_shards; s++) out_counts[s] = 0;
  const uint64_t ns = static_cast<uint64_t>(n_shards);
  for (int64_t i = 0; i < n; i++) {
    uint64_t x = static_cast<uint64_t>(keys[i]) + 0x9E3779B97F4A7C15ULL;
    x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9ULL;
    x = (x ^ (x >> 27)) * 0x94D049BB133111EBULL;
    x = x ^ (x >> 31);
    int32_t s = static_cast<int32_t>(x % ns);
    out_shard[i] = s;
    out_counts[s]++;
  }
  std::vector<int64_t> off(n_shards);
  int64_t acc = 0;
  for (int32_t s = 0; s < n_shards; s++) {
    off[s] = acc;
    acc += out_counts[s];
  }
  for (int64_t i = 0; i < n; i++) {
    int64_t p = off[out_shard[i]]++;
    out_order[p] = i;
    out_keys_sorted[p] = keys[i];
  }
}

// Fused fingerprint route + gather (string streams): shard = h1 %
// n_shards, emitting both fingerprint streams shard-sorted alongside
// the stable order.
void rl_route_hashes2(const uint64_t* h1s, const uint64_t* h2s,
                      int64_t n, int32_t n_shards, int32_t* out_shard,
                      int64_t* out_order, int64_t* out_counts,
                      uint64_t* out_h1_sorted, uint64_t* out_h2_sorted) {
  for (int32_t s = 0; s < n_shards; s++) out_counts[s] = 0;
  const uint64_t ns = static_cast<uint64_t>(n_shards);
  for (int64_t i = 0; i < n; i++) {
    int32_t s = static_cast<int32_t>(h1s[i] % ns);
    out_shard[i] = s;
    out_counts[s]++;
  }
  std::vector<int64_t> off(n_shards);
  int64_t acc = 0;
  for (int32_t s = 0; s < n_shards; s++) {
    off[s] = acc;
    acc += out_counts[s];
  }
  for (int64_t i = 0; i < n; i++) {
    int64_t p = off[out_shard[i]]++;
    out_order[p] = i;
    out_h1_sorted[p] = h1s[i];
    out_h2_sorted[p] = h2s[i];
  }
}

// Relay decision reconstruction SCATTERED to caller positions (r6):
// out[pos[i]] = rank[i] < counts[uidx[i]].  The sharded drain used to
// materialize the decisions densely and then numpy-fancy-scatter them
// into the output — two memory passes fused into one here.
void rl_relay_decide_pos(const uint8_t* counts, int32_t counts_width,
                         const int32_t* uidx, const int32_t* rank,
                         const int64_t* pos, int64_t n,
                         uint8_t* out, int64_t* out_allowed) {
  int64_t allowed = 0;
  if (counts_width == 1) {
    for (int64_t i = 0; i < n; i++) {
      uint8_t a = rank[i] < static_cast<int32_t>(counts[uidx[i]]);
      out[pos[i]] = a;
      allowed += a;
    }
  } else {
    const uint16_t* c16 = reinterpret_cast<const uint16_t*>(counts);
    for (int64_t i = 0; i < n; i++) {
      uint8_t a = rank[i] < static_cast<int32_t>(c16[uidx[i]]);
      out[pos[i]] = a;
      allowed += a;
    }
  }
  *out_allowed = allowed;
}

// Scalar lookups (no assignment). Return slot or -1.
int32_t rl_index_get_int(void* h, int64_t key, uint64_t lid_seed) {
  Index* ix = static_cast<Index*>(h);
  uint64_t h1, h2;
  hash_int(key, lid_seed, h1, h2);
  int32_t pos = find(ix, h1, h2);
  if (pos < 0) return -1;
  lru_touch(ix, pos);
  return ix->table[pos].slot;
}

int32_t rl_index_get_bytes(void* h, const uint8_t* data, int64_t len,
                           uint64_t lid_seed) {
  Index* ix = static_cast<Index*>(h);
  uint64_t h1, h2;
  hash_bytes(data, len, lid_seed, h1, h2);
  int32_t pos = find(ix, h1, h2);
  if (pos < 0) return -1;
  lru_touch(ix, pos);
  return ix->table[pos].slot;
}

// Remove a key; returns its slot (caller must clear device state BEFORE the
// slot can be reused) or -1.  A slot with a live pin refcount (a stream's
// assign->dispatch window) is NOT freed here — that would let a new key take
// it before the pinned dispatch enqueues its write.  It is deferred and
// surfaces on the dirty list at last unpin (see take_slot).
static int32_t remove_at(Index* ix, int32_t pos) {
  int32_t slot = ix->table[pos].slot;
  lru_unlink(ix, pos);
  ix->entry_of_slot[slot] = -1;
  erase_at(ix, static_cast<uint64_t>(pos));
  ix->size--;
  if (ix->pins[slot] > 0)
    ix->deferred[slot] = 1;
  else
    ix->free_slots.push_back(slot);
  return slot;
}

int32_t rl_index_remove_bytes(void* h, const uint8_t* data, int64_t len,
                              uint64_t lid_seed) {
  Index* ix = static_cast<Index*>(h);
  uint64_t h1, h2;
  hash_bytes(data, len, lid_seed, h1, h2);
  int32_t pos = find(ix, h1, h2);
  if (pos < 0) return -1;
  return remove_at(ix, pos);
}

int32_t rl_index_remove_int(void* h, int64_t key, uint64_t lid_seed) {
  Index* ix = static_cast<Index*>(h);
  uint64_t h1, h2;
  hash_int(key, lid_seed, h1, h2);
  int32_t pos = find(ix, h1, h2);
  if (pos < 0) return -1;
  return remove_at(ix, pos);
}

// -- enumeration / restore (checkpointing at native speed) -------------------
// The table stores fingerprints, not keys, so enumeration yields
// (h1, h2, slot) triples.  Dump order is LRU order, most-recent first;
// restore rebuilds the exact same recency order, so eviction behavior
// continues unchanged across a snapshot/restore cycle.

int64_t rl_index_dump(void* h, uint64_t* out_h1, uint64_t* out_h2,
                      int32_t* out_slots) {
  Index* ix = static_cast<Index*>(h);
  int64_t i = 0;
  for (int32_t pos = ix->lru_head; pos >= 0; pos = ix->table[pos].lru_next) {
    const Entry& e = ix->table[pos];
    out_h1[i] = e.h1;
    out_h2[i] = e.h2;
    out_slots[i] = e.slot;
    i++;
  }
  return i;
}

// Rebuild from a dump (MRU-first order, as produced by rl_index_dump).
// Returns 0 on success, -1 on invalid input (bad slot, duplicate slot or
// fingerprint, zero fingerprint, n > num_slots).  The index is cleared
// first; on failure it is left cleared.
static void reset_empty(Index* ix) {
  std::fill(ix->table.begin(), ix->table.end(), Entry{});
  std::fill(ix->entry_of_slot.begin(), ix->entry_of_slot.end(), -1);
  std::fill(ix->deferred.begin(), ix->deferred.end(), 0);
  ix->dirty_free.clear();
  ix->size = 0;
  ix->lru_head = ix->lru_tail = -1;
  ix->free_slots.clear();
  // Pin refcounts survive a clear/restore (they belong to in-flight
  // dispatch windows, not to the mapping): a still-pinned slot must not
  // reach the clean free list — defer it so it surfaces on the dirty
  // list (=> cleared before reuse) at last unpin.
  for (int64_t s = ix->num_slots - 1; s >= 0; s--) {
    if (ix->pins[s] > 0)
      ix->deferred[s] = 1;
    else
      ix->free_slots.push_back(static_cast<int32_t>(s));
  }
}

int32_t rl_index_restore(void* h, const uint64_t* h1s, const uint64_t* h2s,
                         const int32_t* slots, int64_t n) {
  Index* ix = static_cast<Index*>(h);
  reset_empty(ix);
  if (n > ix->num_slots) return -1;  // index left empty-but-usable
  ix->free_slots.clear();
  // Insert tail-first so entry 0 ends at the LRU head (most recent).
  for (int64_t i = n - 1; i >= 0; i--) {
    uint64_t h1 = h1s[i], h2 = h2s[i];
    int32_t slot = slots[i];
    if (slot < 0 || slot >= ix->num_slots || (h1 == 0 && h2 == 0) ||
        ix->entry_of_slot[slot] >= 0 || find(ix, h1, h2) >= 0) {
      reset_empty(ix);
      return -1;
    }
    insert(ix, h1, h2, slot);
  }
  for (int64_t s = ix->num_slots - 1; s >= 0; s--) {
    if (ix->entry_of_slot[s] >= 0) {
      // Slot re-mapped by the restore: it must NOT surface on the dirty
      // free list at last unpin (two keys would share it).
      ix->deferred[s] = 0;
      continue;
    }
    if (ix->pins[s] > 0)  // in-flight dispatch window: see reset_empty
      ix->deferred[s] = 1;
    else
      ix->free_slots.push_back(static_cast<int32_t>(s));
  }
  return 0;
}

// Fingerprint-level lookup/assign (flat-to-flat rebalance: fingerprints are
// geometry-independent for LRU-assigned tables, so a dump from a smaller
// index can be imported into a larger one without knowing the keys).
void rl_index_lookup_fps(void* h, const uint64_t* h1s, const uint64_t* h2s,
                         int64_t n, int32_t* out_slots) {
  Index* ix = static_cast<Index*>(h);
  for (int64_t i = 0; i < n; i++) {
    int32_t pos = find(ix, h1s[i], h2s[i]);
    out_slots[i] = pos < 0 ? -1 : ix->table[pos].slot;
  }
}

void rl_index_assign_fps(void* h, const uint64_t* h1s, const uint64_t* h2s,
                         int64_t n, int32_t* out_slots, int32_t* out_evicted) {
  assign_batch(static_cast<Index*>(h), n, out_slots, out_evicted,
               [&](int64_t i, uint64_t& h1, uint64_t& h2) {
                 h1 = h1s[i];
                 h2 = h2s[i] | (h1 == 0 && h2s[i] == 0 ? 1 : 0);
               });
}

// Relay decision reconstruction: allowed[i] = rank[i] < counts[uidx[i]].
// One fused pass instead of numpy's gather + astype + compare temporaries;
// counts element width is 1 or 2 bytes (the device's u8/u16 output).
void rl_relay_decide(const uint8_t* counts, int32_t counts_width,
                     const int32_t* uidx, const int32_t* rank, int64_t n,
                     uint8_t* out_allowed) {
  if (counts_width == 1) {
    for (int64_t i = 0; i < n; i++)
      out_allowed[i] = rank[i] < static_cast<int32_t>(counts[uidx[i]]);
  } else {
    const uint16_t* c16 = reinterpret_cast<const uint16_t*>(counts);
    for (int64_t i = 0; i < n; i++)
      out_allowed[i] = rank[i] < static_cast<int32_t>(c16[uidx[i]]);
  }
}

// Shard routing for the sharded stream paths: one pass hashes every key
// with the splitmix64 finalizer (bit-identical to
// parallel/sharded.py:shard_of_int_keys) and counts per shard; a second
// pass emits the STABLE counting-sort order, so each shard's requests
// become one contiguous slice in arrival order.  Replaces a numpy
// hash (6 vector passes) + O(n log n) argsort on the chunk hot path.
void rl_shard_route(const int64_t* keys, int64_t n, int32_t n_shards,
                    int32_t* out_shard, int64_t* out_order,
                    int64_t* out_counts) {
  for (int32_t s = 0; s < n_shards; s++) out_counts[s] = 0;
  const uint64_t ns = static_cast<uint64_t>(n_shards);
  for (int64_t i = 0; i < n; i++) {
    uint64_t x = static_cast<uint64_t>(keys[i]) + 0x9E3779B97F4A7C15ULL;
    x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9ULL;
    x = (x ^ (x >> 27)) * 0x94D049BB133111EBULL;
    x = x ^ (x >> 31);
    int32_t s = static_cast<int32_t>(x % ns);
    out_shard[i] = s;
    out_counts[s]++;
  }
  std::vector<int64_t> off(n_shards);
  int64_t acc = 0;
  for (int32_t s = 0; s < n_shards; s++) {
    off[s] = acc;
    acc += out_counts[s];
  }
  for (int64_t i = 0; i < n; i++) out_order[off[out_shard[i]]++] = i;
}

// Route a batch to n_parts partitions over n_ranges request ranges
// (at most 256 partitions: the partition lane is uint8).  First each
// range writes its requests' partitions into part[] and counts them;
// prefix sums over (range, partition) then give local[r][p], range r's
// first position in partition p's walk, and offs[0..n_parts], the
// partitions' offsets in the partition-major copies; last each range
// copies its keys (and src1, if given) to dst0 (dst1) at those
// cursors.  Every partition's slice keeps arrival order, as
// rl_shard_route's stable counting sort.
void rl_route_ranges(const uint64_t* keys, int32_t hashed, int32_t n_parts,
                     const int64_t* bounds, int32_t n_ranges,
                     uint8_t* part, int64_t* local, int64_t* offs,
                     uint64_t* dst0, const uint64_t* src1,
                     uint64_t* dst1) {
  on_ranges(n_ranges, [&](int32_t r) {
    route_range(keys, bounds[r], bounds[r + 1], n_parts, hashed != 0, part,
                local + static_cast<int64_t>(r) * n_parts);
  });
  std::vector<int64_t> total(n_parts, 0);
  for (int32_t r = 0; r < n_ranges; r++) {
    for (int32_t p = 0; p < n_parts; p++) {
      int64_t& c = local[static_cast<int64_t>(r) * n_parts + p];
      const int64_t count = c;
      c = total[p];
      total[p] += count;
    }
  }
  offs[0] = 0;
  for (int32_t p = 0; p < n_parts; p++) offs[p + 1] = offs[p] + total[p];
  on_ranges(n_ranges, [&](int32_t r) {
    int64_t cursors[256];
    for (int32_t p = 0; p < n_parts; p++)
      cursors[p] = offs[p] + local[static_cast<int64_t>(r) * n_parts + p];
    scatter_range(part, bounds[r], bounds[r + 1], n_parts, cursors, keys,
                  dst0, src1, dst1);
  });
}

// The walks' int32 outputs back to request order over the same ranges:
// dst0[i] = src0[p][j] + add0[p] and dst1[i] = src1[p][j], where p is
// request i's partition and j its position in that partition's walk
// (src0/src1: one array per partition, null where it has no requests;
// src1/dst1 may be null).
void rl_merge_ranges(const uint8_t* part, int32_t n_parts,
                     const int64_t* bounds, int32_t n_ranges,
                     const int64_t* local, const int32_t* const* src0,
                     const int32_t* add0, int32_t* dst0,
                     const int32_t* const* src1, int32_t* dst1) {
  on_ranges(n_ranges, [&](int32_t r) {
    merge_range(part, bounds[r], bounds[r + 1], n_parts,
                local + static_cast<int64_t>(r) * n_parts, src0, add0, dst0,
                src1, dst1);
  });
}

void rl_index_pin(void* h, int32_t slot) {
  Index* ix = static_cast<Index*>(h);
  if (slot >= 0 && slot < ix->num_slots) ix->pins[slot]++;
}

// Last unpin of a removed-while-pinned slot frees it onto the dirty list
// (take_slot reports dirty slots as their own eviction => cleared on reuse).
static inline void unpin_one(Index* ix, int32_t slot) {
  if (slot < 0 || slot >= ix->num_slots || ix->pins[slot] == 0) return;
  if (--ix->pins[slot] == 0 && ix->deferred[slot]) {
    ix->deferred[slot] = 0;
    ix->dirty_free.push_back(slot);
  }
}

void rl_index_unpin(void* h, int32_t slot) {
  unpin_one(static_cast<Index*>(h), slot);
}

// Batch pin/unpin (refcounted, duplicates fine): streams hold these from
// slot assignment until their device dispatch is enqueued, so concurrent
// scalar traffic can never evict-and-clear a slot that an in-preparation
// batch is about to write (the reverse direction — queued micro-batcher
// slots vs stream assigns — is covered by the per-call pinned set).
void rl_index_pin_batch(void* h, const int32_t* slots, int64_t n) {
  Index* ix = static_cast<Index*>(h);
  for (int64_t i = 0; i < n; i++) {
    int32_t s = slots[i];
    if (s >= 0 && s < ix->num_slots) ix->pins[s]++;
  }
}

void rl_index_unpin_batch(void* h, const int32_t* slots, int64_t n) {
  Index* ix = static_cast<Index*>(h);
  for (int64_t i = 0; i < n; i++) unpin_one(ix, slots[i]);
}

// ---------------------------------------------------------------------------
// Weighted-relay rank-major layout (storage/tpu.py:_stream_weighted).
//
// The device's weighted scan step wants segments sorted by occurrence
// count DESCENDING so each rank step's active set is a prefix, with the
// per-request permits laid out rank-major compacted (all rank-0 permits,
// then rank-1, ...).  The probe walk already produced per-unique counts
// (in the uwords' count field) and per-request (uidx, rank) — this pass
// turns them into the device layout in O(u + n), replacing a numpy
// argsort + bincount/cumsum + fancy-index scatter that cost ~1.4 s on a
// 16M-request chunk (VERDICT r3 #2).
//
// Inputs: uwords[u] with the segment count in bits 1..rank_bits (true,
// unclamped — the caller verified r_max <= r_cap < r_b), per-request
// uidx/rank, permits as int64 (values already bounded to the engine's
// <=255 weighted cap), and r_b = pow2 >= r_max.
// Outputs (all caller-allocated): uw_sorted (first u entries written;
// caller pre-fills the padding), spos[u] (unique -> sorted position),
// roff[r_b] (rank-major block offsets), perms_rank (caller-zeroed;
// exactly n positions scattered).  Returns 0, or -1 if a count exceeds
// r_b (caller's r_cap check violated — layout would be out of bounds).
int32_t rl_weighted_layout(const uint32_t* uwords, int64_t u,
                           int32_t rank_bits, const int32_t* uidx,
                           const int32_t* rank, int64_t n,
                           const int64_t* perms, int64_t r_b,
                           uint32_t* uw_sorted, int32_t* spos,
                           int64_t* roff, uint8_t* perms_rank) {
  if (r_b <= 0 || r_b > 4096) return -1;
  const uint32_t cmask = (1u << rank_bits) - 1u;
  std::vector<int64_t> hist(r_b + 1, 0);
  for (int64_t i = 0; i < u; i++) {
    uint32_t c = (uwords[i] >> 1) & cmask;
    if (static_cast<int64_t>(c) > r_b) return -1;
    hist[c]++;
  }
  // start[v] = #segments with count > v — the descending-stable bucket
  // start, and also k_r (active segments at rank step v).
  std::vector<int64_t> start(r_b + 1, 0);
  int64_t acc = 0;
  for (int64_t v = r_b; v >= 0; v--) {
    start[v] = acc;
    acc += hist[v];
  }
  // roff[r] = sum_{q<r} k_r[q] — BEFORE start is consumed by placement.
  int64_t racc = 0;
  for (int64_t r = 0; r < r_b; r++) {
    roff[r] = racc;
    racc += start[r];
  }
  for (int64_t i = 0; i < u; i++) {
    uint32_t c = (uwords[i] >> 1) & cmask;
    int64_t p = start[c]++;
    uw_sorted[p] = uwords[i];
    spos[i] = static_cast<int32_t>(p);
  }
  for (int64_t i = 0; i < n; i++) {
    int64_t p = roff[rank[i]] + spos[uidx[i]];
    perms_rank[p] = static_cast<uint8_t>(perms[i]);
  }
  return 0;
}

// Sort a uniques batch by SLOT and remap uidx accordingly — in place.
// Slot-sorted digests let the device scatter run as a tile sweep
// (ops/pallas/block_scatter presorted path) instead of XLA's
// ~45-90 ns/index generic scatter, and the gather ride ascending
// addresses.  Slots are unique within a batch, so a unique's position
// is its slot's rank among the batch's slots: one bit per slot in a
// bitmap, a popcount prefix per 64-slot word, then every word goes
// straight to its place.  The scratch is kept per thread (the walk
// pool's threads and the caller sort), so a call faults in no fresh
// pages.  O(u + max slot / 64) + O(n) remap.
int32_t rl_sort_uniques(uint32_t* uwords, int64_t u, int32_t rank_bits,
                        int32_t* uidx, int64_t n) {
  if (u <= 1) return 0;
  static thread_local std::vector<uint64_t> bits;
  static thread_local std::vector<int32_t> prefix, inv;
  static thread_local std::vector<uint32_t> placed;
  const int shift = rank_bits + 1;
  uint32_t max_slot = 0;
  for (int64_t i = 0; i < u; i++) {
    uint32_t s = uwords[i] >> shift;
    if (s > max_slot) max_slot = s;
  }
  const size_t words = (max_slot >> 6) + 1;
  if (bits.size() < words) {
    bits.resize(words);
    prefix.resize(words);
  }
  std::memset(bits.data(), 0, words * sizeof(uint64_t));
  if (static_cast<int64_t>(inv.size()) < u) {
    inv.resize(u);
    placed.resize(u);
  }
  for (int64_t i = 0; i < u; i++) {
    uint32_t s = uwords[i] >> shift;
    bits[s >> 6] |= uint64_t{1} << (s & 63);
  }
  int32_t acc = 0;
  for (size_t w = 0; w < words; w++) {
    prefix[w] = acc;
    acc += __builtin_popcountll(bits[w]);
  }
  for (int64_t i = 0; i < u; i++) {
    uint32_t s = uwords[i] >> shift;
    int32_t p = prefix[s >> 6] +
                __builtin_popcountll(bits[s >> 6] &
                                     ((uint64_t{1} << (s & 63)) - 1));
    inv[i] = p;
    placed[p] = uwords[i];
  }
  std::memcpy(uwords, placed.data(), u * sizeof(uint32_t));
  for (int64_t i = 0; i < n; i++) {
    int32_t ui = uidx[i];
    if (ui >= 0) uidx[i] = inv[ui];
  }
  return 0;
}

// Per-request words-mode reconstruction (ops/relay.py:rebuild_words in
// one pass): word = (slot | clamped rank | last-of-segment), written
// straight into the caller's padded dispatch buffer — the numpy version
// materialized ~6 full-stream temporaries plus a pad copy, ~1s of the
// 10M-key uniform pass's host time.  For an over-clamp segment the
// flagged lane is the one at rank clamp-1, matching the numpy fallback
// bit for bit.
void rl_rebuild_words(const uint32_t* uwords, const int32_t* uidx,
                      const int32_t* rank, int64_t n, int32_t rank_bits,
                      uint32_t* out) {
  const uint32_t rmask = (1u << rank_bits) - 1u;
  for (int64_t i = 0; i < n; i++) {
    uint32_t w = uwords[uidx[i]];
    uint32_t cnt = (w >> 1) & rmask;
    uint32_t r = static_cast<uint32_t>(rank[i]);
    uint32_t rcl = r > rmask ? rmask : r;
    out[i] = (w & ~((rmask << 1) | 1u)) | (rcl << 1)
             | ((r + 1 == cnt) ? 1u : 0u);
  }
}

// Decision reconstruction for the layout above: request i's decision is
// bit (roff[rank[i]] + spos[uidx[i]]) of the fetched bitmask (MSB-first
// within each byte, matching numpy packbits).  One pass replaces
// unpackbits + a fancy-index gather.
void rl_weighted_decide(const uint8_t* bits, const int64_t* roff,
                        const int32_t* spos, const int32_t* uidx,
                        const int32_t* rank, int64_t n, uint8_t* out) {
  for (int64_t i = 0; i < n; i++) {
    int64_t p = roff[rank[i]] + spos[uidx[i]];
    out[i] = (bits[p >> 3] >> (7 - (p & 7))) & 1;
  }
}

}  // extern "C"
