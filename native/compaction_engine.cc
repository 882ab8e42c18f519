// Native compaction shell: SST block decode -> merge+GC -> block encode.
//
// The production CPU side of the compaction job (ref: CompactionJob::Run,
// src/yb/rocksdb/db/compaction_job.cc:442, incl. hot loop #3 block building
// at :958-1024). Round 2 measured ~88% of the full disk-to-disk job spent in
// the Python shell (block codec, value gather, file plumbing); this engine
// moves the entire byte path native while Python keeps the metadata
// authority (index/bloom/props assembly, VersionSet wiring).
//
// Used two ways:
//   - device="native": ce_job_merge runs the shared heap-merge + GC filter
//     (merge_gc_core.h) — the full reference architecture end to end.
//   - TPU path: the device kernel computes the merge+GC decisions
//     (ops/run_merge.py packed decision buffer) and Python injects them via
//     ce_job_set_survivors; the engine only materializes output bytes.
//
// Block format: storage/block_format.py layout, byte-identical.
// Build: g++ -O3 -shared -fPIC -o libcompaction_engine.so compaction_engine.cc -lz -lpthread

#include <zlib.h>

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

#include "merge_gc_core.h"

namespace {

constexpr uint32_t kBlockMagic = 0x53425459;  // "YTBS"
constexpr int kHeaderLen = 24;                // 6 x u32

inline uint32_t rd_u32(const uint8_t* p) {
  uint32_t v;
  memcpy(&v, p, 4);
  return v;  // x86: little-endian, matching struct.pack("<I")
}
inline void wr_u32(uint8_t* p, uint32_t v) { memcpy(p, &v, 4); }

struct BlockHandle {
  int64_t off;
  int32_t size;
  int32_t count;
};

struct InputFile {
  const uint8_t* data;
  int64_t size;
  std::vector<BlockHandle> handles;
};

struct OutBlockMeta {
  int64_t off;
  int32_t size;
  int32_t count;
  std::vector<uint8_t> last_key;
};

struct OutputMeta {
  std::vector<OutBlockMeta> blocks;
  std::vector<uint64_t> bloom_hashes;  // one per output row
  std::vector<uint8_t> first_key, last_key;
  int64_t data_size = 0;
};

// FNV-1a over the first len bytes — must match storage/bloom.py fnv64_masked.
inline uint64_t fnv1a(const uint8_t* p, int32_t len) {
  uint64_t h = 0xCBF29CE484222325ULL;
  for (int32_t i = 0; i < len; ++i) h = (h ^ p[i]) * 0x100000001B3ULL;
  return h;
}

template <class F>
void pfor(int64_t n, int n_threads, F&& body) {
  if (n_threads <= 1 || n <= 1) {
    for (int64_t i = 0; i < n; ++i) body(i);
    return;
  }
  std::atomic<int64_t> next{0};
  auto worker = [&] {
    for (;;) {
      int64_t i = next.fetch_add(1);
      if (i >= n) return;
      body(i);
    }
  };
  std::vector<std::thread> ts;
  int t = n_threads < n ? n_threads : (int)n;
  ts.reserve(t - 1);
  for (int i = 1; i < t; ++i) ts.emplace_back(worker);
  worker();
  for (auto& th : ts) th.join();
}

// ---- native run cache ---------------------------------------------------
// Packed-run retention across compactions: a flush or compaction output is
// exported ONCE as decoded SoA columns and retained in host RAM, so the
// next compaction over it skips file read + block decode entirely (the
// reference pays TableReader iteration per input every job even on block
// cache hits, ref: db/compaction_job.cc:442 + table/merger.cc:51; this
// cache is the host-side counterpart of the HBM key-column cache in
// storage/device_cache.py). Entries are immutable after export; shared_ptr
// keeps a run alive while a job reads it even if Python drops it mid-job.
struct CachedRun {
  int64_t n = 0;
  int32_t stride = 0;
  std::vector<uint8_t> keys;
  std::vector<int32_t> key_len, dkl;
  std::vector<uint64_t> ht;
  std::vector<uint32_t> wid;
  std::vector<uint8_t> flags;
  std::vector<int64_t> ttl_ms;
  std::vector<uint8_t> vals;
  std::vector<int64_t> val_offs;  // n+1
  int64_t bytes() const {
    return (int64_t)keys.size() + 4 * 2 * n + 8 * n + 4 * n + n + 8 * n +
           (int64_t)vals.size() + 8 * (n + 1);
  }
};

std::mutex g_rc_mu;
std::unordered_map<int64_t, std::shared_ptr<CachedRun>> g_rc;
int64_t g_rc_next_id = 1;
int64_t g_rc_bytes = 0;

struct Job {
  std::vector<InputFile> inputs;
  std::vector<std::shared_ptr<CachedRun>> cached;  // zero-decode inputs
  int n_threads = 4;
  std::string error;

  // decoded SoA (normalized to max stride)
  int64_t n = 0;
  int32_t stride = 0;
  std::vector<uint8_t> keys;
  std::vector<int32_t> key_len, dkl;
  std::vector<uint64_t> ht;
  std::vector<uint32_t> wid;
  std::vector<uint8_t> flags;
  std::vector<int64_t> ttl_ms;
  std::vector<const uint8_t*> val_ptr;
  std::vector<uint32_t> val_len;
  std::vector<std::unique_ptr<std::vector<uint8_t>>> decomp;  // owned bodies
  std::vector<int64_t> run_offsets;

  // merge results
  std::vector<int64_t> order;
  std::vector<uint8_t> keep, mk;
  std::vector<int64_t> surv;      // kept input rows, merged order
  std::vector<uint8_t> surv_mk;   // rewrite-as-tombstone per survivor

  OutputMeta out;                  // meta of the last written output file
};

bool decode_block(Job* j, const uint8_t* p, int32_t size, int64_t row0,
                  int32_t expect_n, const uint8_t** vbase_out) {
  if (size < kHeaderLen + 4) return false;
  uint32_t magic = rd_u32(p), n = rd_u32(p + 4), bstride = rd_u32(p + 8);
  // arrays were sized from the base-file handle counts; a data file paired
  // with a stale base would otherwise write out of bounds
  if ((int32_t)n != expect_n) return false;
  uint32_t bflags = rd_u32(p + 12), body_len = rd_u32(p + 16),
           raw_len = rd_u32(p + 20);
  if (magic != kBlockMagic) return false;
  if ((int64_t)kHeaderLen + body_len + 4 > size) return false;
  const uint8_t* stored = p + kHeaderLen;
  uint32_t crc = rd_u32(stored + body_len);
  uint32_t want = crc32(0, p + 4, kHeaderLen - 4);
  want = crc32(want, stored, body_len);
  if (crc != want) return false;
  const uint8_t* body = stored;
  if (bflags & 1) {  // zlib
    auto buf = std::make_unique<std::vector<uint8_t>>(raw_len);
    uLongf dlen = raw_len;
    if (uncompress(buf->data(), &dlen, stored, body_len) != Z_OK ||
        dlen != raw_len)
      return false;
    body = buf->data();
    static std::mutex mu;
    std::lock_guard<std::mutex> lock(mu);
    j->decomp.push_back(std::move(buf));
  }
  // body layout: keys | key_len u16 | dkl u16 | ht_hi u32 | ht_lo u32 |
  //              wid u32 | flags u8 | ttl i64 | val_off u32[n+1] | val bytes
  const uint8_t* q = body;
  const uint8_t* kq = q;                 q += (int64_t)n * bstride;
  const uint8_t* klq = q;                q += 2 * (int64_t)n;
  const uint8_t* dklq = q;               q += 2 * (int64_t)n;
  const uint8_t* hthq = q;               q += 4 * (int64_t)n;
  const uint8_t* htlq = q;               q += 4 * (int64_t)n;
  const uint8_t* widq = q;               q += 4 * (int64_t)n;
  const uint8_t* flq = q;                q += (int64_t)n;
  const uint8_t* ttlq = q;               q += 8 * (int64_t)n;
  const uint8_t* voq = q;                q += 4 * ((int64_t)n + 1);
  const uint8_t* vb = q;
  if (q - body > raw_len) return false;
  for (uint32_t i = 0; i < n; ++i) {
    int64_t r = row0 + i;
    memcpy(&j->keys[r * j->stride], kq + (int64_t)i * bstride, bstride);
    uint16_t kl, dk;
    memcpy(&kl, klq + 2 * i, 2);
    memcpy(&dk, dklq + 2 * i, 2);
    j->key_len[r] = kl;
    j->dkl[r] = dk;
    uint32_t hi, lo, w;
    memcpy(&hi, hthq + 4 * i, 4);
    memcpy(&lo, htlq + 4 * i, 4);
    memcpy(&w, widq + 4 * i, 4);
    j->ht[r] = ((uint64_t)hi << 32) | lo;
    j->wid[r] = w;
    j->flags[r] = flq[i];
    int64_t t;
    memcpy(&t, ttlq + 8 * i, 8);
    j->ttl_ms[r] = t;
    uint32_t v0, v1;
    memcpy(&v0, voq + 4 * i, 4);
    memcpy(&v1, voq + 4 * (i + 1), 4);
    j->val_ptr[r] = vb + v0;
    j->val_len[r] = v1 - v0;
  }
  *vbase_out = vb;
  return true;
}

}  // namespace

extern "C" {

void* ce_job_new(int n_threads) {
  Job* j = new Job();
  j->n_threads = n_threads > 0 ? n_threads : 1;
  return j;
}

void ce_job_free(void* jp) { delete (Job*)jp; }

const char* ce_job_error(void* jp) { return ((Job*)jp)->error.c_str(); }

// data must stay valid (Python holds the bytes) until ce_job_free.
void ce_job_add_input(void* jp, const uint8_t* data, int64_t size,
                      const int64_t* offs, const int32_t* sizes,
                      const int32_t* counts, int32_t n_blocks) {
  Job* j = (Job*)jp;
  InputFile f{data, size, {}};
  f.handles.reserve(n_blocks);
  for (int32_t b = 0; b < n_blocks; ++b)
    f.handles.push_back({offs[b], sizes[b], counts[b]});
  j->inputs.push_back(std::move(f));
}

// Ingest path: fill the job's SoA straight from one packed run (the flush
// job / bulk load, ref: db/flush_job.cc WriteLevel0Table + memtable.cc).
// keys_blob/key_offs hold the raw key-prefix bytes; ht/wid the
// DocHybridTime columns; vals_blob/val_offs the value payloads. flags,
// ttl and doc_key_len are derived NATIVELY from the value control fields
// (docdb/value.py: optional 'k'+4B merge flags, 't'+8B TTL, then the
// payload tag) and the DocKey structure parser below, so Python's
// per-entry work drops to blob concatenation.
void ce_job_add_raw(void* jp, const uint8_t* keys_blob,
                    const int64_t* key_offs, int64_t n, const uint64_t* ht,
                    const uint32_t* wid, const uint8_t* vals_blob,
                    const int64_t* val_offs) {
  Job* j = (Job*)jp;
  int32_t stride = 4;
  for (int64_t i = 0; i < n; ++i) {
    int32_t kl = (int32_t)(key_offs[i + 1] - key_offs[i]);
    if (kl > stride) stride = kl;
  }
  stride = (stride + 3) & ~3;
  j->n = n;
  j->stride = stride;
  j->keys.assign((size_t)n * stride, 0);
  j->key_len.resize(n);
  j->dkl.resize(n);
  j->ht.assign(ht, ht + n);
  j->wid.assign(wid, wid + n);
  j->flags.resize(n);
  j->ttl_ms.resize(n);
  j->val_ptr.resize(n);
  j->val_len.resize(n);
  j->run_offsets = {0, n};
  pfor(n, j->n_threads, [&](int64_t i) {
    const uint8_t* k = keys_blob + key_offs[i];
    int32_t kl = (int32_t)(key_offs[i + 1] - key_offs[i]);
    memcpy(&j->keys[i * stride], k, kl);
    j->key_len[i] = kl;
    int32_t d = ybtpu::doc_key_len(k, kl);
    j->dkl[i] = d;
    const uint8_t* v = vals_blob + val_offs[i];
    int64_t vl = val_offs[i + 1] - val_offs[i];
    j->val_ptr[i] = v;
    j->val_len[i] = (uint32_t)vl;
    // control fields + payload tag -> slab flags (ops/slabs.py pack_kvs)
    uint8_t fl = 0;
    int64_t ttl = 0;
    int64_t pos = 0;
    if (pos + 5 <= vl && v[pos] == 'k') pos += 5;        // kMergeFlags
    if (pos + 9 <= vl && v[pos] == 't') {                // kTTL (ms, >q BE)
      int64_t t = 0;
      for (int b = 1; b <= 8; ++b) t = (t << 8) | v[pos + b];
      ttl = t;
      fl |= 4;
      pos += 9;
    }
    if (pos < vl) {
      uint8_t tag = v[pos];
      if (tag == 'X') fl |= 1;          // kTombstone
      else if (tag == '{') fl |= 2;     // kObject
    }
    if (kl > d && ybtpu::subkey_depth(k, kl, d) > 1) fl |= 8;  // FLAG_DEEP
    j->flags[i] = fl;
    j->ttl_ms[i] = ttl;
  });
}

// Accept the run as already internal-key-ordered, or sort it (stable) by
// (key asc, ht desc, wid desc). Flush inputs arrive sorted from the
// memtable; bulk loads may not. Returns survivor count (= n: no GC here).
int64_t ce_job_sort_all(void* jp) {
  Job* j = (Job*)jp;
  int64_t n = j->n;
  ybtpu::Ctx c{j->keys.data(), j->key_len.data(), j->stride, j->ht.data(),
               j->wid.data()};
  bool sorted = true;
  for (int64_t i = 1; i < n; ++i) {
    if (ybtpu::cmp_entries(c, i - 1, i) > 0) { sorted = false; break; }
  }
  j->surv.resize(n);
  for (int64_t i = 0; i < n; ++i) j->surv[i] = i;
  if (!sorted) {
    std::stable_sort(j->surv.begin(), j->surv.end(),
                     [&](int64_t a, int64_t b) {
                       return ybtpu::cmp_entries(c, a, b) < 0;
                     });
  }
  j->surv_mk.assign(n, 0);
  return n;
}

int32_t ce_job_stride(void* jp) { return ((Job*)jp)->stride; }

// The job's columns in survivor order, as the columns of a KVSlab
// (ops/slabs.py): what ce_job_add_raw derived is handed out, so that no
// second parser walks the run to describe it. key_words is [n, stride / 4]:
// the key bytes as big-endian words, zero-padded (the stride is the slab's
// width: a multiple of 4, at least 4). perm[i] is the input row of survivor
// i, for the caller's gather of the values. Valid after ce_job_sort_all (a
// flush keeps every row and rewrites none). Returns 1 where the survivors
// are not the input order (perm is not the identity), 0 where they are.
int32_t ce_job_export_columns(void* jp, uint32_t* key_words,
                              int32_t* key_len, int32_t* dkl,
                              uint32_t* ht_hi, uint32_t* ht_lo,
                              uint32_t* wid, uint32_t* flags,
                              int64_t* ttl_ms, int64_t* perm) {
  Job* j = (Job*)jp;
  int64_t n = (int64_t)j->surv.size();
  int32_t w = j->stride / 4;
  std::atomic<bool> reordered{false};
  // chunks, not rows: pfor hands out one index at a time
  constexpr int64_t kChunk = 8192;
  pfor((n + kChunk - 1) / kChunk, j->n_threads, [&](int64_t c) {
    int64_t end = (c + 1) * kChunk < n ? (c + 1) * kChunk : n;
    bool moved = false;
    for (int64_t i = c * kChunk; i < end; ++i) {
      int64_t r = j->surv[i];
      moved |= r != i;
      const uint8_t* k = &j->keys[r * j->stride];
      for (int32_t x = 0; x < w; ++x)
        key_words[i * w + x] = __builtin_bswap32(rd_u32(k + 4 * x));
      key_len[i] = j->key_len[r];
      dkl[i] = j->dkl[r];
      ht_hi[i] = (uint32_t)(j->ht[r] >> 32);
      ht_lo[i] = (uint32_t)j->ht[r];
      wid[i] = j->wid[r];
      flags[i] = j->flags[r];
      ttl_ms[i] = j->ttl_ms[r];
      perm[i] = r;
    }
    if (moved) reordered.store(true);
  });
  return reordered.load() || n != j->n ? 1 : 0;
}

// Whole-file props the base file needs (valid after add_raw or prepare):
// max_expire_us (0 unless EVERY entry has a TTL) and has_deep.
void ce_job_props(void* jp, uint64_t* max_expire_us, int32_t* has_deep) {
  Job* j = (Job*)jp;
  uint64_t mx = 0;
  bool all_ttl = j->n > 0, deep = false;
  for (int64_t i = 0; i < j->n; ++i) {
    if (j->flags[i] & 8) deep = true;
    if (!(j->flags[i] & 4)) { all_ttl = false; continue; }
    uint64_t e = (j->ht[i] >> 12) + (uint64_t)j->ttl_ms[i] * 1000;
    if (e > mx) mx = e;
  }
  *max_expire_us = all_ttl ? mx : 0;
  *has_deep = deep ? 1 : 0;
}

// Decode every block of every input (parallel). Returns total rows, -1 on
// corruption.
int64_t ce_job_prepare(void* jp) {
  Job* j = (Job*)jp;
  // pass 1: strides + counts + per-block target row offsets
  int64_t n = 0;
  int32_t stride = 4;
  struct Task { int fi; int bi; int64_t row0; };
  std::vector<Task> tasks;
  j->run_offsets.push_back(0);
  for (size_t fi = 0; fi < j->inputs.size(); ++fi) {
    InputFile& f = j->inputs[fi];
    for (size_t bi = 0; bi < f.handles.size(); ++bi) {
      BlockHandle& h = f.handles[bi];
      if (h.off + kHeaderLen > f.size) { j->error = "handle oob"; return -1; }
      uint32_t bstride = rd_u32(f.data + h.off + 8);
      if ((int32_t)bstride > stride) stride = bstride;
      tasks.push_back({(int)fi, (int)bi, n});
      n += h.count;
    }
    j->run_offsets.push_back(n);
  }
  j->n = n;
  j->stride = stride;
  j->keys.assign((size_t)n * stride, 0);
  j->key_len.resize(n);
  j->dkl.resize(n);
  j->ht.resize(n);
  j->wid.resize(n);
  j->flags.resize(n);
  j->ttl_ms.resize(n);
  j->val_ptr.resize(n);
  j->val_len.resize(n);
  std::atomic<bool> ok{true};
  pfor((int64_t)tasks.size(), j->n_threads, [&](int64_t t) {
    const Task& task = tasks[t];
    InputFile& f = j->inputs[task.fi];
    const BlockHandle& h = f.handles[task.bi];
    const uint8_t* vb;
    if (!decode_block(j, f.data + h.off, h.size, task.row0, h.count, &vb))
      ok.store(false);
  });
  if (!ok.load()) { j->error = "block decode/crc failure"; return -1; }
  return n;
}

// Merge + GC natively (the reference architecture). Returns survivor count.
int64_t ce_job_merge(void* jp, uint64_t cutoff_ht, int32_t is_major,
                     int32_t retain_deletes) {
  Job* j = (Job*)jp;
  int64_t n = j->n;
  j->order.resize(n);
  j->keep.resize(n);
  j->mk.resize(n);
  ybtpu::Ctx c{j->keys.data(), j->key_len.data(), j->stride, j->ht.data(),
               j->wid.data()};
  // run count from run_offsets, not inputs: cached-run and add_raw jobs
  // have no InputFile entries
  ybtpu::merge_and_filter(c, (int32_t)j->run_offsets.size() - 1,
                          j->run_offsets.data(), j->dkl.data(),
                          j->flags.data(), j->ttl_ms.data(), cutoff_ht,
                          is_major, retain_deletes, j->keep.data(),
                          j->mk.data(), j->order.data());
  j->surv.clear();
  j->surv_mk.clear();
  for (int64_t i = 0; i < n; ++i) {
    if (j->keep[i]) {
      j->surv.push_back(j->order[i]);
      j->surv_mk.push_back(j->mk[i]);
    }
  }
  return (int64_t)j->surv.size();
}

// TPU path: decisions computed on device, injected here.
void ce_job_set_survivors(void* jp, const int64_t* surv, const uint8_t* mk,
                          int64_t n_out) {
  Job* j = (Job*)jp;
  j->surv.assign(surv, surv + n_out);
  j->surv_mk.assign(mk, mk + n_out);
}

// Streaming TPU path: stage C of the pipelined compaction appends each
// chunk's survivors as its decision download lands, so write_output on the
// already-appended span overlaps the later chunks' device compute and D2H.
// Chunks arrive in global merged order (route-partitioned), so appending
// preserves the survivor order set_survivors would have produced.
void ce_job_append_survivors(void* jp, const int64_t* surv,
                             const uint8_t* mk, int64_t n_out) {
  Job* j = (Job*)jp;
  j->surv.insert(j->surv.end(), surv, surv + n_out);
  j->surv_mk.insert(j->surv_mk.end(), mk, mk + n_out);
}

int64_t ce_job_rows(void* jp) { return ((Job*)jp)->n; }
int64_t ce_job_n_survivors(void* jp) { return (int64_t)((Job*)jp)->surv.size(); }

// Write one output data file from survivor range [start, end). Returns the
// file byte size, or -1 on error. Block encode is parallel; writes are
// sequential appends.
int64_t ce_job_write_output(void* jp, int64_t start, int64_t end,
                            const char* path, int32_t block_entries,
                            int32_t compress, const uint8_t* tomb_value,
                            int32_t tomb_len) {
  Job* j = (Job*)jp;
  int64_t n_rows = end - start;
  int64_t n_blocks = block_entries > 0
                         ? (n_rows + block_entries - 1) / block_entries
                         : 0;
  OutputMeta& out = j->out;
  out.blocks.assign(n_blocks, {});
  out.bloom_hashes.resize(n_rows);

  // Encode the rows of block b into dst (the exact on-disk body bytes,
  // raw_len of them), filling bloom hashes as a side effect.
  auto encode_body = [&](int64_t b, uint8_t* dst) {
    int64_t s0 = start + b * block_entries;
    int64_t s1 = s0 + block_entries < end ? s0 + block_entries : end;
    uint32_t bn = (uint32_t)(s1 - s0);
    uint8_t* q = dst;
    uint8_t* kq = q;    q += (int64_t)bn * j->stride;
    uint8_t* klq = q;   q += 2 * (int64_t)bn;
    uint8_t* dklq = q;  q += 2 * (int64_t)bn;
    uint8_t* hthq = q;  q += 4 * (int64_t)bn;
    uint8_t* htlq = q;  q += 4 * (int64_t)bn;
    uint8_t* widq = q;  q += 4 * (int64_t)bn;
    uint8_t* flq = q;   q += (int64_t)bn;
    uint8_t* ttlq = q;  q += 8 * (int64_t)bn;
    uint8_t* voq = q;   q += 4 * ((int64_t)bn + 1);
    uint8_t* vb = q;
    uint32_t voff = 0;
    for (uint32_t i = 0; i < bn; ++i) {
      int64_t si = s0 + i;             // survivor slot
      int64_t r = j->surv[si];         // input row
      bool as_tomb = j->surv_mk[si] != 0;  // surv_mk is survivor-absolute,
                                           // like surv (NOT file-relative)
      memcpy(kq + (int64_t)i * j->stride, &j->keys[r * j->stride], j->stride);
      uint16_t kl = (uint16_t)j->key_len[r], dk = (uint16_t)j->dkl[r];
      memcpy(klq + 2 * i, &kl, 2);
      memcpy(dklq + 2 * i, &dk, 2);
      uint32_t hi = (uint32_t)(j->ht[r] >> 32), lo = (uint32_t)j->ht[r];
      memcpy(hthq + 4 * i, &hi, 4);
      memcpy(htlq + 4 * i, &lo, 4);
      memcpy(widq + 4 * i, &j->wid[r], 4);
      uint8_t fl = j->flags[r];
      int64_t ttl = j->ttl_ms[r];
      if (as_tomb) { fl |= 1; }
      flq[i] = fl;
      memcpy(ttlq + 8 * i, &ttl, 8);
      memcpy(voq + 4 * i, &voff, 4);
      if (as_tomb) {
        memcpy(vb + voff, tomb_value, tomb_len);
        voff += tomb_len;
      } else {
        memcpy(vb + voff, j->val_ptr[r], j->val_len[r]);
        voff += j->val_len[r];
      }
      out.bloom_hashes[si - start] = fnv1a(&j->keys[r * j->stride], dk);
    }
    memcpy(voq + 4 * (int64_t)bn, &voff, 4);
    // block meta (crc/offset filled by the caller)
    OutBlockMeta& bm = out.blocks[b];
    bm.count = bn;
    int64_t last = j->surv[s1 - 1];
    bm.last_key.assign(&j->keys[last * j->stride],
                       &j->keys[last * j->stride] + j->key_len[last]);
  };

  auto block_raw_len = [&](int64_t b) {
    int64_t s0 = start + b * block_entries;
    int64_t s1 = s0 + block_entries < end ? s0 + block_entries : end;
    int64_t bn = s1 - s0;
    int64_t vtotal = 0;
    for (int64_t i = s0; i < s1; ++i)
      vtotal += j->surv_mk[i] ? tomb_len : j->val_len[j->surv[i]];
    // per row: stride key bytes + 2+2 lens + 4+4 ht + 4 wid + 1 flags +
    // 8 ttl + 4 val_off = stride+29; plus the (n+1)th val_off word
    return bn * j->stride + 29 * bn + 4 + vtotal;
  };

  int64_t off = 0;
  if (!compress) {
    // Hot path: block sizes are deterministic, so encode every block IN
    // PLACE into one arena (single allocation, zero re-copy) and issue
    // one write. The old per-block vector design page-faulted a fresh
    // mmap per ~450KB block and made ~1000 small fwrites — ~2s of the
    // 4M-row job on the 1-core bench machine.
    std::vector<int64_t> offs(n_blocks + 1, 0);
    pfor(n_blocks, j->n_threads, [&](int64_t b) {
      offs[b + 1] = kHeaderLen + block_raw_len(b) + 4;
    });
    for (int64_t b = 0; b < n_blocks; ++b) offs[b + 1] += offs[b];
    std::vector<uint8_t> arena(offs[n_blocks]);
    pfor(n_blocks, j->n_threads, [&](int64_t b) {
      uint8_t* blk = arena.data() + offs[b];
      int64_t raw_len = (offs[b + 1] - offs[b]) - kHeaderLen - 4;
      int64_t s0 = start + b * block_entries;
      int64_t s1 = s0 + block_entries < end ? s0 + block_entries : end;
      wr_u32(blk + 0, kBlockMagic);
      wr_u32(blk + 4, (uint32_t)(s1 - s0));
      wr_u32(blk + 8, (uint32_t)j->stride);
      wr_u32(blk + 12, 0);           // uncompressed
      wr_u32(blk + 16, (uint32_t)raw_len);
      wr_u32(blk + 20, (uint32_t)raw_len);
      encode_body(b, blk + kHeaderLen);
      uint32_t crc = crc32(0, blk + 4, kHeaderLen - 4);
      crc = crc32(crc, blk + kHeaderLen, raw_len);
      wr_u32(blk + kHeaderLen + raw_len, crc);
      out.blocks[b].off = offs[b];
      out.blocks[b].size = (int32_t)(offs[b + 1] - offs[b]);
    });
    FILE* fp = fopen(path, "wb");
    if (!fp) { j->error = "cannot open output"; return -1; }
    if (fwrite(arena.data(), 1, arena.size(), fp) != arena.size()) {
      fclose(fp);
      j->error = "short write";
      return -1;
    }
    fclose(fp);
    off = (int64_t)arena.size();
  } else {
    // Compressed path: sizes unknown upfront; per-block buffers.
    std::vector<std::vector<uint8_t>> bufs(n_blocks);
    pfor(n_blocks, j->n_threads, [&](int64_t b) {
      int64_t raw_len = block_raw_len(b);
      std::vector<uint8_t> body(raw_len);
      encode_body(b, body.data());
      std::vector<uint8_t>& blk = bufs[b];
      std::vector<uint8_t> comp;
      const uint8_t* stored = body.data();
      int64_t stored_len = raw_len;
      uint32_t bflags = 0;
      uLongf clen = compressBound(raw_len);
      comp.resize(clen);
      if (compress2(comp.data(), &clen, body.data(), raw_len, 1) == Z_OK &&
          (int64_t)clen < raw_len) {
        stored = comp.data();
        stored_len = clen;
        bflags = 1;
      }
      blk.resize(kHeaderLen + stored_len + 4);
      wr_u32(&blk[0], kBlockMagic);
      wr_u32(&blk[4], out.blocks[b].count);
      wr_u32(&blk[8], (uint32_t)j->stride);
      wr_u32(&blk[12], bflags);
      wr_u32(&blk[16], (uint32_t)stored_len);
      wr_u32(&blk[20], (uint32_t)raw_len);
      memcpy(&blk[kHeaderLen], stored, stored_len);
      uint32_t crc = crc32(0, &blk[4], kHeaderLen - 4);
      crc = crc32(crc, stored, stored_len);
      wr_u32(&blk[kHeaderLen + stored_len], crc);
    });
    FILE* fp = fopen(path, "wb");
    if (!fp) { j->error = "cannot open output"; return -1; }
    for (int64_t b = 0; b < n_blocks; ++b) {
      out.blocks[b].off = off;
      out.blocks[b].size = (int32_t)bufs[b].size();
      if (fwrite(bufs[b].data(), 1, bufs[b].size(), fp) != bufs[b].size()) {
        fclose(fp);
        j->error = "short write";
        return -1;
      }
      off += bufs[b].size();
    }
    fclose(fp);
  }
  out.data_size = off;
  if (n_rows > 0) {
    int64_t f = j->surv[start], l = j->surv[end - 1];
    out.first_key.assign(&j->keys[f * j->stride],
                         &j->keys[f * j->stride] + j->key_len[f]);
    out.last_key.assign(&j->keys[l * j->stride],
                        &j->keys[l * j->stride] + j->key_len[l]);
  } else {
    out.first_key.clear();
    out.last_key.clear();
  }
  return off;
}

// Bloom bit scatter (storage/bloom.py BloomFilterBuilder.add_hashes): the
// numpy path is an unbuffered ufunc.at — ~100ns per scattered OR; this is
// the same double-hash schedule at memcpy-class speed.
void ce_bloom_build(const uint64_t* h, int64_t n, uint8_t* bits,
                    uint64_t m_bits, int32_t k) {
  for (int64_t i = 0; i < n; ++i) {
    uint64_t h1 = h[i] & 0xFFFFFFFFull;
    uint64_t h2 = (h[i] >> 32) | 1ull;
    for (int32_t j = 0; j < k; ++j) {
      uint64_t pos = (h1 + (uint64_t)j * h2) % m_bits;
      bits[pos >> 3] |= (uint8_t)(1u << (pos & 7));
    }
  }
}

// Variable-length row gather (ops/slabs.py ValueArray.gather): row i of the
// output is lens[i] bytes at src + starts[i] — or at rep + starts[i] where
// from_rep[i] is set (the TTL-expiry -> tombstone rewrite; from_rep may be
// null) — copied to out + out_off[i]. The numpy form builds an int64 index
// per output BYTE; this is one memcpy per row. The caller has range-checked
// every (start, len) against its buffer.
void ce_gather_rows(const uint8_t* src, const uint8_t* rep,
                    const uint8_t* from_rep, const int64_t* starts,
                    const int64_t* lens, const int64_t* out_off, int64_t n,
                    uint8_t* out) {
  for (int64_t i = 0; i < n; ++i) {
    const uint8_t* base = (from_rep && from_rep[i]) ? rep : src;
    memcpy(out + out_off[i], base + starts[i], (size_t)lens[i]);
  }
}

// --- native run cache ----------------------------------------------------
// Export survivors [start, end) of a finished job as a cached packed run —
// byte-equivalent to decoding the output file just written for that range
// (same tombstone rewrite: flags |= kTombstone and the value replaced).
// Valid after merge/set_survivors (compaction) or sort_all (flush).
// Returns the new run id, or -1.
int64_t ce_runcache_export(void* jp, int64_t start, int64_t end,
                           const uint8_t* tomb_value, int32_t tomb_len) {
  Job* j = (Job*)jp;
  int64_t n = end - start;
  if (n < 0 || start < 0 || end > (int64_t)j->surv.size()) return -1;
  auto run = std::make_shared<CachedRun>();
  run->n = n;
  run->stride = j->stride;
  run->keys.assign((size_t)n * j->stride, 0);
  run->key_len.resize(n);
  run->dkl.resize(n);
  run->ht.resize(n);
  run->wid.resize(n);
  run->flags.resize(n);
  run->ttl_ms.resize(n);
  run->val_offs.resize(n + 1);
  int64_t vtotal = 0;
  for (int64_t i = 0; i < n; ++i) {
    run->val_offs[i] = vtotal;
    vtotal += j->surv_mk[start + i] ? tomb_len
                                    : j->val_len[j->surv[start + i]];
  }
  run->val_offs[n] = vtotal;
  run->vals.resize(vtotal);
  pfor(n, j->n_threads, [&](int64_t i) {
    int64_t r = j->surv[start + i];
    memcpy(&run->keys[i * run->stride], &j->keys[r * j->stride], j->stride);
    run->key_len[i] = j->key_len[r];
    run->dkl[i] = j->dkl[r];
    run->ht[i] = j->ht[r];
    run->wid[i] = j->wid[r];
    run->ttl_ms[i] = j->ttl_ms[r];
    if (j->surv_mk[start + i]) {
      run->flags[i] = j->flags[r] | 1;  // rewritten as tombstone
      memcpy(&run->vals[run->val_offs[i]], tomb_value, tomb_len);
    } else {
      run->flags[i] = j->flags[r];
      memcpy(&run->vals[run->val_offs[i]], j->val_ptr[r], j->val_len[r]);
    }
  });
  std::lock_guard<std::mutex> lock(g_rc_mu);
  int64_t id = g_rc_next_id++;
  g_rc_bytes += run->bytes();
  g_rc.emplace(id, std::move(run));
  return id;
}

int64_t ce_runcache_entry_bytes(int64_t id) {
  std::lock_guard<std::mutex> lock(g_rc_mu);
  auto it = g_rc.find(id);
  return it == g_rc.end() ? -1 : it->second->bytes();
}

void ce_runcache_drop(int64_t id) {
  std::lock_guard<std::mutex> lock(g_rc_mu);
  auto it = g_rc.find(id);
  if (it != g_rc.end()) {
    g_rc_bytes -= it->second->bytes();
    g_rc.erase(it);  // in-flight jobs keep their shared_ptr
  }
}

int64_t ce_runcache_bytes() {
  std::lock_guard<std::mutex> lock(g_rc_mu);
  return g_rc_bytes;
}

// Append a cached run as a job input. All-cached jobs then use
// ce_job_prepare_cached instead of add_input + prepare; run ORDER must
// match the device staging order (run-major survivor indexes).
int32_t ce_job_add_cached(void* jp, int64_t id) {
  Job* j = (Job*)jp;
  std::shared_ptr<CachedRun> run;
  {
    std::lock_guard<std::mutex> lock(g_rc_mu);
    auto it = g_rc.find(id);
    if (it == g_rc.end()) return -1;
    run = it->second;
  }
  j->cached.push_back(std::move(run));
  return 0;
}

// Fill the SoA from cached runs only — the zero-decode steady-state input
// path (no file read, no block decode, no CRC pass; value bytes are
// POINTED AT in the cached blobs, never copied). Returns total rows, -1 on
// misuse (mixed with file inputs, or nothing added).
int64_t ce_job_prepare_cached(void* jp) {
  Job* j = (Job*)jp;
  if (!j->inputs.empty() || j->cached.empty()) {
    j->error = "prepare_cached: requires cached inputs only";
    return -1;
  }
  int64_t n = 0;
  int32_t stride = 4;
  j->run_offsets.assign(1, 0);
  for (auto& run : j->cached) {
    n += run->n;
    if (run->stride > stride) stride = run->stride;
    j->run_offsets.push_back(n);
  }
  j->n = n;
  j->stride = stride;
  j->keys.assign((size_t)n * stride, 0);
  j->key_len.resize(n);
  j->dkl.resize(n);
  j->ht.resize(n);
  j->wid.resize(n);
  j->flags.resize(n);
  j->ttl_ms.resize(n);
  j->val_ptr.resize(n);
  j->val_len.resize(n);
  for (size_t ri = 0; ri < j->cached.size(); ++ri) {
    CachedRun& run = *j->cached[ri];
    int64_t base = j->run_offsets[ri];
    pfor(run.n, j->n_threads, [&](int64_t i) {
      int64_t r = base + i;
      memcpy(&j->keys[r * stride], &run.keys[i * run.stride], run.stride);
      j->key_len[r] = run.key_len[i];
      j->dkl[r] = run.dkl[i];
      j->ht[r] = run.ht[i];
      j->wid[r] = run.wid[i];
      j->flags[r] = run.flags[i];
      j->ttl_ms[r] = run.ttl_ms[i];
      j->val_ptr[r] = run.vals.data() + run.val_offs[i];
      j->val_len[r] = (uint32_t)(run.val_offs[i + 1] - run.val_offs[i]);
    });
  }
  return n;
}

// --- accessors for the last written output ------------------------------
int32_t ce_out_n_blocks(void* jp) {
  return (int32_t)((Job*)jp)->out.blocks.size();
}
void ce_out_block_meta(void* jp, int64_t* offs, int32_t* sizes,
                       int32_t* counts, int32_t* last_key_lens) {
  Job* j = (Job*)jp;
  for (size_t b = 0; b < j->out.blocks.size(); ++b) {
    offs[b] = j->out.blocks[b].off;
    sizes[b] = j->out.blocks[b].size;
    counts[b] = j->out.blocks[b].count;
    last_key_lens[b] = (int32_t)j->out.blocks[b].last_key.size();
  }
}
void ce_out_last_keys(void* jp, uint8_t* buf) {
  Job* j = (Job*)jp;
  for (auto& bm : j->out.blocks) {
    memcpy(buf, bm.last_key.data(), bm.last_key.size());
    buf += bm.last_key.size();
  }
}
void ce_out_bloom_hashes(void* jp, uint64_t* buf) {
  Job* j = (Job*)jp;
  memcpy(buf, j->out.bloom_hashes.data(),
         j->out.bloom_hashes.size() * sizeof(uint64_t));
}
int32_t ce_out_first_key(void* jp, uint8_t* buf, int32_t cap) {
  Job* j = (Job*)jp;
  int32_t n = (int32_t)j->out.first_key.size();
  memcpy(buf, j->out.first_key.data(), n < cap ? n : cap);
  return n;  // caller re-calls with a bigger buffer if n > cap
}
int32_t ce_out_last_key(void* jp, uint8_t* buf, int32_t cap) {
  Job* j = (Job*)jp;
  int32_t n = (int32_t)j->out.last_key.size();
  memcpy(buf, j->out.last_key.data(), n < cap ? n : cap);
  return n;
}

}  // extern "C"
