// KvEmbedding: dynamic-shape hashtable embedding store (C++ core).
//
// Reference parity (SURVEY.md §2.6): TFPlus KvVariable
// (tfplus/kv_variable/kernels/kv_variable.h:89, hashmap.h, kernels/
// training_ops.cc) — a concurrent find-or-insert embedding table with
// frequency/timestamp tracking, feature eviction, full/delta
// import-export for incremental model delivery, and sparse optimizers
// applied directly on the table.
//
// TPU design: XLA needs static shapes, so the dynamic table lives
// host-side in C++; training gathers fixed-size key windows
// (jax pure_callback) and optimizers apply host-side on the sparse rows
// touched. Striped shards (own mutex + open hash map each) give
// concurrent lookup/update from the input pipeline's threads.
//
// Build: g++ -O3 -shared -fPIC -std=c++17 (no external deps).

#include <algorithm>
#include <atomic>
#include <chrono>
#include <cmath>
#include <condition_variable>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#if defined(__x86_64__)
#include <immintrin.h>  // must precede the anonymous namespace: a
// system header included inside `namespace {` would re-declare libc
// symbols with internal linkage on toolchains whose include guards
// don't already short-circuit it
#endif
#include <cstring>
#include <functional>
#include <mutex>
#include <random>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

namespace {

double now_sec() {
  return std::chrono::duration<double>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

// phase counters for batched_update, filled only under KV_PROF=1 and
// read/reset through kv_prof_report() (atomic: shard workers add
// concurrently)
std::atomic<uint64_t> prof_group_ns{0}, prof_dedup_ns{0},
    prof_resolve_ns{0}, prof_apply_ns{0};

uint64_t ns_since(std::chrono::steady_clock::time_point t0) {
  return static_cast<uint64_t>(
      std::chrono::duration_cast<std::chrono::nanoseconds>(
          std::chrono::steady_clock::now() - t0)
          .count());
}

// ---- row kernels (runtime-dispatched ISA clones) --------------------
// The batched-update profile (KV_PROF) put 68% of wall in the apply
// loop, and the working set of a repeated batch fits in LLC — i.e. the
// loop is vector-ALU bound (sqrtps/divps on 4 lanes), not DRAM bound.
// The build deliberately ships baseline ISA (-O3, no -march: a cached
// .so can cross heterogeneous hosts, where AVX2 code SIGILLs with no
// diagnostic); target_clones sidesteps that safely — gcc emits an
// AVX2+FMA clone AND a baseline clone and picks per-host at load time
// via the glibc IFUNC resolver. Measured: adam row 2.34 -> ~1.1 ms per
// 8k x 64 batch on an AVX2 host, identical results on any other host.

// x86-only clone lists are a hard compile error on other arches (gcc
// rejects unknown ISA names), and this .cc is built by g++ on the
// importing host — keep non-x86 builds working with plain functions
#if defined(__x86_64__)
#define DLROVER_ISA_CLONES \
  __attribute__((target_clones("avx2,fma", "default")))
#else
#define DLROVER_ISA_CLONES
#endif

DLROVER_ISA_CLONES void axpy_row(float* __restrict__ w,
                                 const float* __restrict__ v,
                                 float alpha, int64_t dim) {
  for (int64_t d = 0; d < dim; ++d) w[d] += alpha * v[d];
}

DLROVER_ISA_CLONES void adagrad_row(float* __restrict__ w,
                                    float* __restrict__ acc,
                                    const float* __restrict__ g,
                                    float lr, float eps, int64_t dim) {
  for (int64_t d = 0; d < dim; ++d) {
    acc[d] += g[d] * g[d];
    w[d] -= lr * g[d] / (std::sqrt(acc[d]) + eps);
  }
}

void adam_row_generic(float* __restrict__ w, float* __restrict__ m,
                      float* __restrict__ v,
                      const float* __restrict__ gr, float lr, float b1,
                      float b2, float eps, float mscale, float vscale,
                      int64_t dim) {
  for (int64_t d = 0; d < dim; ++d) {
    m[d] = b1 * m[d] + (1 - b1) * gr[d];
    v[d] = b2 * v[d] + (1 - b2) * gr[d] * gr[d];
    const float mh = m[d] * mscale;
    const float vh = v[d] * vscale;
    w[d] -= lr * mh / (std::sqrt(vh) + eps);
  }
}

// The adam update is vector-ALU bound on the sqrt+div chain (the
// KV_PROF profile is flat across L1/L2/LLC working sets), and
// target_clones alone doesn't change the chain — vsqrtps+vdivps have
// the same ~14-cycle throughput at any width on this core family. The
// win is replacing them with rsqrt/rcp estimates + one Newton-Raphson
// step each (~24-bit, ~3e-7 relative — indistinguishable at adam's
// noise floor): all cheap fma/mul ops. Guarded by __builtin_cpu_
// supports at dispatch time, so the baseline-ISA build stays portable.
#if defined(__x86_64__)
__attribute__((target("avx2,fma"))) void adam_row_avx2(
    float* __restrict__ w, float* __restrict__ m,
    float* __restrict__ v, const float* __restrict__ gr, float lr,
    float b1, float b2, float eps, float mscale, float vscale,
    int64_t dim) {
  const __m256 b1v = _mm256_set1_ps(b1);
  const __m256 ib1 = _mm256_set1_ps(1.0f - b1);
  const __m256 b2v = _mm256_set1_ps(b2);
  const __m256 ib2 = _mm256_set1_ps(1.0f - b2);
  const __m256 msv = _mm256_set1_ps(mscale);
  const __m256 vsv = _mm256_set1_ps(vscale);
  const __m256 epv = _mm256_set1_ps(eps);
  const __m256 lrv = _mm256_set1_ps(lr);
  const __m256 c15 = _mm256_set1_ps(1.5f);
  const __m256 c05 = _mm256_set1_ps(0.5f);
  const __m256 c20 = _mm256_set1_ps(2.0f);
  // floor vh at FLT_MIN: rsqrt(0) = inf would turn s = vh*r into NaN
  // (exact path has sqrt(0)+eps = eps; with the floor, s ~ 1e-19 and
  // the denominator is eps again). Ceiling at FLT_MAX for the same
  // reason from the other side: vh = inf (g*g overflow) gives
  // rsqrt = 0 and the NR step computes inf*0 = NaN, silently
  // poisoning w forever — where the exact path's 1/(sqrt(inf)+eps)
  // is a finite no-op update. Clamped, the update is ~0 as well.
  const __m256 tiny = _mm256_set1_ps(1.17549435e-38f);
  const __m256 huge = _mm256_set1_ps(3.40282347e38f);
  int64_t d = 0;
  for (; d + 8 <= dim; d += 8) {
    const __m256 g = _mm256_loadu_ps(gr + d);
    const __m256 mm = _mm256_fmadd_ps(
        b1v, _mm256_loadu_ps(m + d), _mm256_mul_ps(ib1, g));
    _mm256_storeu_ps(m + d, mm);
    const __m256 vv = _mm256_fmadd_ps(
        b2v, _mm256_loadu_ps(v + d),
        _mm256_mul_ps(ib2, _mm256_mul_ps(g, g)));
    _mm256_storeu_ps(v + d, vv);
    const __m256 mh = _mm256_mul_ps(mm, msv);
    const __m256 vh = _mm256_min_ps(
        _mm256_max_ps(_mm256_mul_ps(vv, vsv), tiny), huge);
    // s = sqrt(vh) via rsqrt + one NR step: r1 = r*(1.5 - 0.5*vh*r^2)
    __m256 r = _mm256_rsqrt_ps(vh);
    r = _mm256_mul_ps(
        r, _mm256_fnmadd_ps(
               _mm256_mul_ps(c05, vh), _mm256_mul_ps(r, r), c15));
    const __m256 s = _mm256_mul_ps(vh, r);
    const __m256 den = _mm256_add_ps(s, epv);
    // u = 1/den via rcp + one NR step: u1 = u*(2 - den*u)
    __m256 u = _mm256_rcp_ps(den);
    u = _mm256_mul_ps(u, _mm256_fnmadd_ps(den, u, c20));
    const __m256 upd = _mm256_mul_ps(lrv, _mm256_mul_ps(mh, u));
    _mm256_storeu_ps(w + d, _mm256_sub_ps(_mm256_loadu_ps(w + d), upd));
  }
  if (d < dim) {
    adam_row_generic(w + d, m + d, v + d, gr + d, lr, b1, b2, eps,
                     mscale, vscale, dim - d);
  }
}
#endif  // __x86_64__

using AdamRowFn = void (*)(float*, float*, float*, const float*, float,
                           float, float, float, float, float, int64_t);

AdamRowFn resolve_adam_row() {
#if defined(__x86_64__)
  if (__builtin_cpu_supports("avx2") && __builtin_cpu_supports("fma")) {
    return adam_row_avx2;
  }
#endif
  return adam_row_generic;
}

const AdamRowFn adam_row = resolve_adam_row();

// Reusable open-addressing dedup table (linear probing, generation-
// stamped so clearing between calls is one counter bump). Replaces a
// fresh std::unordered_map per shard per batched_update call, whose
// construction+rehash was ~14% of the update's wall clock.
// thread_local: shard groups fan out across WorkPool threads.
struct DedupTable {
  std::vector<int64_t> keys;
  std::vector<int64_t> vals;
  // 64-bit generation: a 32-bit counter can wrap within a weeks-long
  // PS run (one bump per shard per update), after which a stale slot
  // would alias a live one and return an out-of-range batch index
  std::vector<uint64_t> gens;
  uint64_t gen = 0;
  size_t mask = 0;

  void begin(size_t n) {
    size_t cap = 16;
    while (cap < n * 2) cap <<= 1;
    if (cap > keys.size()) {
      keys.assign(cap, 0);
      vals.assign(cap, 0);
      gens.assign(cap, 0);
      gen = 0;
    }
    mask = keys.size() - 1;
    ++gen;
  }

  // returns the slot's value; `fresh` reports whether it was inserted
  int64_t find_or_insert(int64_t key, int64_t val, bool* fresh) {
    size_t h = static_cast<size_t>(key) * 0x9E3779B97F4A7C15ull;
    size_t i = h & mask;
    for (;;) {
      if (gens[i] != gen) {
        gens[i] = gen;
        keys[i] = key;
        vals[i] = val;
        *fresh = true;
        return val;
      }
      if (keys[i] == key) {
        *fresh = false;
        return vals[i];
      }
      i = (i + 1) & mask;
    }
  }
};

struct Slot {
  std::vector<float> data;  // [value(dim) | m(dim) | v(dim)] lazily sized
  uint32_t freq = 0;
  double last_access = 0.0;
  uint64_t version = 0;  // table version at last write
};

constexpr int kNumShards = 64;

// Lazy persistent worker pool for the batched optimizer updates:
// spawning+joining std::threads per call taxed the exact hot path the
// batching exists to speed up (~100 us/call). Workers are detached and
// park on a condition variable between jobs; the caller participates
// in every job, so zero workers (1-core hosts) degrades to serial.
// DLROVER_KV_THREADS overrides the worker count (tests use it to
// exercise the pool on single-core machines).
class WorkPool {
 public:
  static WorkPool& get() {
    static WorkPool* p = new WorkPool();  // leaked: workers detached
    return *p;
  }

  template <typename F>
  void parallel_for(size_t total, F&& fn) {
    if (workers_ == 0 || total <= 1) {
      for (size_t i = 0; i < total; ++i) fn(i);
      return;
    }
    Job job;
    std::function<void(size_t)> wrapped =
        [&fn](size_t i) { fn(i); };
    job.fn = &wrapped;
    job.total = total;
    {
      std::lock_guard<std::mutex> lk(mu_);
      cur_ = &job;
      ++epoch_;
    }
    cv_.notify_all();
    size_t i;
    while ((i = job.next.fetch_add(1)) < total) wrapped(i);
    std::unique_lock<std::mutex> lk(mu_);
    cur_ = nullptr;  // late wakers see no job and keep parking
    done_cv_.wait(lk, [&] { return job.active.load() == 0; });
  }

 private:
  struct Job {
    std::function<void(size_t)>* fn = nullptr;
    size_t total = 0;
    std::atomic<size_t> next{0};
    std::atomic<int> active{0};
  };

  WorkPool() {
    long n = -1;
    if (const char* e = std::getenv("DLROVER_KV_THREADS")) {
      n = std::strtol(e, nullptr, 10);
    }
    if (n < 0) {
      const unsigned hw = std::thread::hardware_concurrency();
      n = hw > 1 ? static_cast<long>(std::min(hw - 1, 7u)) : 0;
    }
    workers_ = static_cast<size_t>(n);
    for (size_t t = 0; t < workers_; ++t) {
      std::thread([this] { worker(); }).detach();
    }
  }

  void worker() {
    uint64_t seen = 0;
    for (;;) {
      Job* j;
      {
        std::unique_lock<std::mutex> lk(mu_);
        cv_.wait(lk, [&] {
          return epoch_ != seen && cur_ != nullptr;
        });
        seen = epoch_;
        j = cur_;
        // counted under mu_: the caller's done-wait (also under
        // mu_) can never observe active==0 while we hold the job
        j->active.fetch_add(1);
      }
      size_t i;
      while ((i = j->next.fetch_add(1)) < j->total) (*j->fn)(i);
      {
        std::lock_guard<std::mutex> lk(mu_);
        j->active.fetch_sub(1);
      }
      done_cv_.notify_all();
    }
  }

  size_t workers_ = 0;
  std::mutex mu_;
  std::condition_variable cv_;
  std::condition_variable done_cv_;
  Job* cur_ = nullptr;
  uint64_t epoch_ = 0;
};

struct Shard {
  std::unordered_map<int64_t, Slot> map;
  mutable std::mutex mu;
};

// Disk-tier index entry: where a spilled row lives in the spill file
// plus the stats needed for eviction/export without touching the disk.
// Reference parity: tfplus hybrid_embedding TableManager/StorageTable
// (table_manager.h:45, storage_table.h:199) — tiered DRAM/SSD rows with
// promotion on access.
struct DiskRow {
  int64_t offset = 0;      // byte offset of the data payload
  int32_t state_mult = 1;  // how many dim-sized segments are stored
  uint32_t freq = 0;
  double last_access = 0.0;
  uint64_t version = 0;
};

class KvTable {
 public:
  KvTable(int64_t dim, int init_mode, uint64_t seed, float init_scale)
      : dim_(dim),
        init_mode_(init_mode),
        init_scale_(init_scale),
        seed_(seed),
        version_(1) {}

  int64_t dim() const { return dim_; }

  int64_t size() const {
    int64_t n = 0;
    for (const auto& s : shards_) {
      std::lock_guard<std::mutex> g(s.mu);
      n += static_cast<int64_t>(s.map.size());
    }
    return n;
  }

  // Gather rows for keys; missing keys: insert (insert_missing=1) with
  // the configured initializer, or return zeros without inserting (=0)
  // — the GatherOrInsert / GatherOrZeros pair of the reference.
  // Rows spilled to the disk tier are transparently promoted back.
  void lookup(const int64_t* keys, int64_t n, float* out,
              int insert_missing) {
    const double t = now_sec();
    for (int64_t i = 0; i < n; ++i) {
      const int64_t k = keys[i];
      Shard& sh = shard(k);
      std::lock_guard<std::mutex> g(sh.mu);
      auto it = sh.map.find(k);
      if (it == sh.map.end() && promote_from_disk(k, sh)) {
        it = sh.map.find(k);
      }
      if (it == sh.map.end()) {
        if (!insert_missing) {
          std::memset(out + i * dim_, 0, sizeof(float) * dim_);
          continue;
        }
        it = sh.map.emplace(k, Slot{}).first;
        init_value(k, it->second);
      }
      Slot& slot = it->second;
      slot.freq++;
      slot.last_access = t;
      std::memcpy(out + i * dim_, slot.data.data(),
                  sizeof(float) * dim_);
    }
  }

  void scatter_add(const int64_t* keys, int64_t n, const float* vals,
                   float alpha) {
    const uint64_t ver = ++version_;
    batched_update(keys, n, vals, 1, [&](const float* v, Slot& slot) {
      axpy_row(slot.data.data(), v, alpha, dim_);
      slot.version = ver;
    });
  }

  // SGD on the touched rows.
  void apply_sgd(const int64_t* keys, int64_t n, const float* grads,
                 float lr) {
    scatter_add(keys, n, grads, -lr);
  }

  // Adagrad: accumulator in data[dim..2*dim).
  void apply_adagrad(const int64_t* keys, int64_t n, const float* grads,
                     float lr, float eps) {
    const uint64_t ver = ++version_;
    batched_update(keys, n, grads, 2, [&](const float* g2, Slot& slot) {
      float* w = slot.data.data();
      adagrad_row(w, w + dim_, g2, lr, eps, dim_);
      slot.version = ver;
    });
  }

  // Adam with optional sparse-group-lasso regularization — the
  // reference's GroupAdam (tfplus python/training/group_adam.py:272,
  // kernels/training_ops.cc): after the adam step, apply l2 shrinkage
  // and a group-l1 soft threshold over the whole row (feature group),
  // which drives unused embedding rows to exact zero.
  void apply_adam(const int64_t* keys, int64_t n, const float* grads,
                  float lr, float b1, float b2, float eps, int64_t step,
                  float l1, float l2) {
    const uint64_t ver = ++version_;
    const float bc1 = 1.0f - std::pow(b1, static_cast<float>(step));
    const float bc2 = 1.0f - std::pow(b2, static_cast<float>(step));
    // pre-fold the bias corrections into per-term scales: one divide
    // per row instead of two per element
    const float mscale = 1.0f / bc1;
    const float vscale = 1.0f / bc2;
    batched_update(keys, n, grads, 3, [&](const float* gr, Slot& slot) {
      // w/m/v are disjoint dim_-sized segments of slot.data and gr
      // lives in the dedup accumulator, never aliasing them; the row
      // kernel is an ISA-dispatched clone (see adam_row)
      float* w = slot.data.data();
      float* m = w + dim_;
      float* v = w + 2 * dim_;
      adam_row(w, m, v, gr, lr, b1, b2, eps, mscale, vscale, dim_);
      if (l2 > 0.f) {
        const float shrink = 1.0f / (1.0f + lr * l2);
        for (int64_t d = 0; d < dim_; ++d) w[d] *= shrink;
      }
      if (l1 > 0.f) {
        // group soft-threshold on the row norm
        float norm = 0.f;
        for (int64_t d = 0; d < dim_; ++d) norm += w[d] * w[d];
        norm = std::sqrt(norm);
        const float thresh = lr * l1;
        if (norm <= thresh) {
          std::memset(w, 0, sizeof(float) * dim_);
        } else {
          const float scale = (norm - thresh) / norm;
          for (int64_t d = 0; d < dim_; ++d) w[d] *= scale;
        }
      }
      slot.version = ver;
    });
  }

  // Remove rows with freq < min_freq OR idle longer than max_idle_sec.
  int64_t delete_keys(const int64_t* keys, int64_t n) {
    // targeted removal (shard-move handoff: rows re-owned by another
    // host are deleted here so stale copies never re-enter exports)
    int64_t removed = 0;
    for (int64_t i = 0; i < n; ++i) {
      Shard& sh = shard(keys[i]);
      std::lock_guard<std::mutex> g(sh.mu);
      removed += static_cast<int64_t>(sh.map.erase(keys[i]));
    }
    {
      std::lock_guard<std::mutex> g(disk_mu_);
      for (int64_t i = 0; i < n; ++i) {
        auto it = disk_index_.find(keys[i]);
        if (it != disk_index_.end()) {
          dead_bytes_ += sizeof(float) * it->second.state_mult * dim_;
          disk_index_.erase(it);
          ++removed;
        }
      }
    }
    ++version_;
    return removed;
  }

  int64_t evict(uint32_t min_freq, double max_idle_sec) {
    const double t = now_sec();
    int64_t removed = 0;
    for (auto& sh : shards_) {
      std::lock_guard<std::mutex> g(sh.mu);
      for (auto it = sh.map.begin(); it != sh.map.end();) {
        const Slot& s = it->second;
        const bool idle =
            max_idle_sec > 0 && (t - s.last_access) > max_idle_sec;
        const bool cold = min_freq > 0 && s.freq < min_freq;
        if (idle || cold) {
          it = sh.map.erase(it);
          ++removed;
        } else {
          ++it;
        }
      }
    }
    {
      // disk-tier rows age out by the same criteria
      std::lock_guard<std::mutex> g(disk_mu_);
      for (auto it = disk_index_.begin();
           it != disk_index_.end();) {
        const DiskRow& r = it->second;
        const bool idle =
            max_idle_sec > 0 && (t - r.last_access) > max_idle_sec;
        const bool cold = min_freq > 0 && r.freq < min_freq;
        if (idle || cold) {
          dead_bytes_ += sizeof(float) * r.state_mult * dim_;
          it = disk_index_.erase(it);
          ++removed;
        } else {
          ++it;
        }
      }
    }
    return removed;
  }

  // Export rows with version > since_version (0 = full export).
  // Two-phase: count then fill, caller allocates.
  int64_t export_count(uint64_t since_version) const {
    int64_t n = 0;
    for (const auto& sh : shards_) {
      std::lock_guard<std::mutex> g(sh.mu);
      for (const auto& kv : sh.map)
        if (kv.second.version > since_version) ++n;
    }
    {
      // spilled rows are still part of the table's state
      std::lock_guard<std::mutex> g(disk_mu_);
      for (const auto& kv : disk_index_)
        if (kv.second.version > since_version) ++n;
    }
    return n;
  }

  int64_t export_rows(uint64_t since_version, int64_t* keys_out,
                      float* vals_out, int64_t max_n) const {
    int64_t n = 0;
    for (const auto& sh : shards_) {
      std::lock_guard<std::mutex> g(sh.mu);
      for (const auto& kv : sh.map) {
        if (kv.second.version <= since_version) continue;
        if (n >= max_n) return n;
        keys_out[n] = kv.first;
        std::memcpy(vals_out + n * dim_, kv.second.data.data(),
                    sizeof(float) * dim_);
        ++n;
      }
    }
    {
      std::lock_guard<std::mutex> g(disk_mu_);
      for (const auto& kv : disk_index_) {
        if (!spill_file_) break;
        if (kv.second.version <= since_version) continue;
        if (n >= max_n) return n;
        std::fseek(spill_file_, kv.second.offset, SEEK_SET);
        if (std::fread(vals_out + n * dim_, sizeof(float), dim_,
                       spill_file_) !=
            static_cast<size_t>(dim_)) {
          continue;
        }
        keys_out[n] = kv.first;
        ++n;
      }
    }
    return n;
  }

  void import_rows(const int64_t* keys, const float* vals, int64_t n) {
    const uint64_t ver = ++version_;
    const double t = now_sec();
    for (int64_t i = 0; i < n; ++i) {
      const float* src = vals + i * dim_;
      with_slot(keys[i], 1, [&](Slot& slot) {
        std::memcpy(slot.data.data(), src, sizeof(float) * dim_);
        slot.version = ver;
        slot.last_access = t;
        // a freshly imported row must survive frequency eviction until
        // it is actually looked up again
        if (slot.freq == 0) slot.freq = 1;
      });
    }
  }

  // Widest per-row state actually allocated (1=value only, 2=+adagrad
  // acc, 3=+adam m,v) — lets checkpoints carry exactly the state that
  // exists instead of always padding to 3*dim.
  int max_state_mult() const {
    size_t mx = 1;
    for (const auto& sh : shards_) {
      std::lock_guard<std::mutex> g(sh.mu);
      for (const auto& kv : sh.map) {
        const size_t m = kv.second.data.size() / dim_;
        if (m > mx) mx = m;
      }
    }
    return static_cast<int>(mx);
  }

  // Full-state export/import: the whole row state [value|m|v]
  // (state_mult*dim, zero-padded when a row keeps less) plus freq — so
  // a restored checkpoint resumes with intact optimizer moments and
  // eviction statistics (reference ImportV2/ExportV2 carry slot state:
  // tfplus kv_variable.h FullOrDeltaImport/Export).
  int64_t export_full(uint64_t since_version, int64_t* keys_out,
                      float* state_out, uint32_t* freq_out,
                      int64_t max_n, int state_mult) const {
    const int64_t w = state_mult * dim_;
    int64_t n = 0;
    for (const auto& sh : shards_) {
      std::lock_guard<std::mutex> g(sh.mu);
      for (const auto& kv : sh.map) {
        if (kv.second.version <= since_version) continue;
        if (n >= max_n) return n;
        keys_out[n] = kv.first;
        float* dst = state_out + n * w;
        const auto& src = kv.second.data;
        const size_t have =
            std::min(src.size(), static_cast<size_t>(w));
        std::memcpy(dst, src.data(), sizeof(float) * have);
        if (have < static_cast<size_t>(w))
          std::memset(dst + have, 0, sizeof(float) * (w - have));
        freq_out[n] = kv.second.freq;
        ++n;
      }
    }
    {
      std::lock_guard<std::mutex> g(disk_mu_);
      std::vector<float> buf;
      for (const auto& kv : disk_index_) {
        if (!spill_file_) break;
        if (kv.second.version <= since_version) continue;
        if (n >= max_n) return n;
        const size_t have = std::min(
            static_cast<size_t>(kv.second.state_mult) * dim_,
            static_cast<size_t>(w));
        buf.resize(have);
        std::fseek(spill_file_, kv.second.offset, SEEK_SET);
        if (std::fread(buf.data(), sizeof(float), have,
                       spill_file_) != have) {
          continue;
        }
        float* dst = state_out + n * w;
        std::memcpy(dst, buf.data(), sizeof(float) * have);
        if (have < static_cast<size_t>(w))
          std::memset(dst + have, 0, sizeof(float) * (w - have));
        keys_out[n] = kv.first;
        freq_out[n] = kv.second.freq;
        ++n;
      }
    }
    return n;
  }

  void import_full(const int64_t* keys, const float* state,
                   const uint32_t* freq, int64_t n, int state_mult) {
    const uint64_t ver = ++version_;
    const double t = now_sec();
    const int64_t w = state_mult * dim_;
    for (int64_t i = 0; i < n; ++i) {
      const float* src = state + i * w;
      with_slot(keys[i], state_mult, [&](Slot& slot) {
        std::memcpy(slot.data.data(), src, sizeof(float) * w);
        slot.version = ver;
        slot.last_access = t;
        slot.freq = freq[i] > 0 ? freq[i] : 1;
      });
    }
  }

  uint64_t version() const { return version_.load(); }

  // ---- hybrid DRAM/disk tier -------------------------------------------

  bool set_spill_path(const char* path) {
    std::lock_guard<std::mutex> g(disk_mu_);
    if (spill_file_) {
      std::fclose(spill_file_);
      spill_file_ = nullptr;
    }
    spill_path_ = path ? path : "";
    disk_index_.clear();  // entries point into the old file either way
    file_bytes_ = 0;
    dead_bytes_ = 0;
    if (spill_path_.empty()) return true;
    spill_file_ = std::fopen(spill_path_.c_str(), "w+b");
    return spill_file_ != nullptr;
  }

  // Move cold rows (freq < min_freq OR idle > max_idle_sec) to disk.
  // Returns rows spilled; no-op without a spill path.
  int64_t spill(uint32_t min_freq, double max_idle_sec) {
    const double t = now_sec();
    int64_t moved = 0;
    for (auto& sh : shards_) {
      std::lock_guard<std::mutex> g(sh.mu);
      for (auto it = sh.map.begin(); it != sh.map.end();) {
        const Slot& s = it->second;
        const bool idle =
            max_idle_sec > 0 && (t - s.last_access) > max_idle_sec;
        const bool cold = min_freq > 0 && s.freq < min_freq;
        if (!(idle || cold)) {
          ++it;
          continue;
        }
        {
          std::lock_guard<std::mutex> dg(disk_mu_);
          if (!spill_file_) return moved;
          std::fseek(spill_file_, 0, SEEK_END);
          DiskRow row;
          row.offset = std::ftell(spill_file_);
          row.state_mult =
              static_cast<int32_t>(s.data.size() / dim_);
          if (row.state_mult < 1) row.state_mult = 1;
          row.freq = s.freq;
          row.last_access = s.last_access;
          row.version = s.version;
          const size_t nfloats =
              static_cast<size_t>(row.state_mult) * dim_;
          if (std::fwrite(s.data.data(), sizeof(float), nfloats,
                          spill_file_) != nfloats) {
            return moved;  // disk full: keep the row in DRAM
          }
          auto old = disk_index_.find(it->first);
          if (old != disk_index_.end()) {
            dead_bytes_ += sizeof(float) * old->second.state_mult *
                           dim_;
          }
          disk_index_[it->first] = row;
          file_bytes_ += sizeof(float) * nfloats;
        }
        it = sh.map.erase(it);
        ++moved;
      }
    }
    return moved;
  }

  int64_t disk_size() const {
    std::lock_guard<std::mutex> g(disk_mu_);
    return static_cast<int64_t>(disk_index_.size());
  }

  // Rewrite the spill file keeping only live rows (call when
  // promotions have made much of it dead). Returns live rows.
  int64_t compact() {
    std::lock_guard<std::mutex> g(disk_mu_);
    if (!spill_file_ || spill_path_.empty()) return 0;
    const std::string tmp = spill_path_ + ".compact";
    FILE* nf = std::fopen(tmp.c_str(), "w+b");
    if (!nf) return -1;
    // stage all mutations; the live index/file change only after the
    // rename succeeds, so any failure leaves the old tier intact
    std::vector<float> buf;
    std::unordered_map<int64_t, int64_t> new_offsets;
    std::vector<int64_t> unreadable;
    for (const auto& kv : disk_index_) {
      const DiskRow& row = kv.second;
      const size_t nfloats =
          static_cast<size_t>(row.state_mult) * dim_;
      buf.resize(nfloats);
      std::fseek(spill_file_, row.offset, SEEK_SET);
      if (std::fread(buf.data(), sizeof(float), nfloats,
                     spill_file_) != nfloats) {
        // unreadable in the old file: unrecoverable — drop on commit
        unreadable.push_back(kv.first);
        continue;
      }
      std::fseek(nf, 0, SEEK_END);
      const int64_t off = std::ftell(nf);
      if (std::fwrite(buf.data(), sizeof(float), nfloats, nf) !=
          nfloats) {
        std::fclose(nf);  // disk full mid-compact: abort
        std::remove(tmp.c_str());
        return -1;
      }
      new_offsets[kv.first] = off;
    }
    if (std::fflush(nf) != 0 ||
        std::rename(tmp.c_str(), spill_path_.c_str()) != 0) {
      std::fclose(nf);
      std::remove(tmp.c_str());
      return -1;
    }
    std::fclose(spill_file_);
    spill_file_ = nf;
    for (int64_t key : unreadable) disk_index_.erase(key);
    for (const auto& kv : new_offsets)
      disk_index_[kv.first].offset = kv.second;
    dead_bytes_ = 0;
    file_bytes_ = 0;
    for (const auto& kv : disk_index_) {
      file_bytes_ +=
          sizeof(float) * kv.second.state_mult * dim_;
    }
    return static_cast<int64_t>(disk_index_.size());
  }

 private:
  // caller holds the shard lock for `key`; takes the disk lock inside
  // (lock order everywhere: shard → disk)
  bool promote_from_disk(int64_t key, Shard& sh) {
    std::lock_guard<std::mutex> g(disk_mu_);
    if (!spill_file_) return false;
    auto it = disk_index_.find(key);
    if (it == disk_index_.end()) return false;
    const DiskRow& row = it->second;
    const size_t nfloats =
        static_cast<size_t>(row.state_mult) * dim_;
    Slot slot;
    slot.data.resize(nfloats);
    std::fseek(spill_file_, row.offset, SEEK_SET);
    if (std::fread(slot.data.data(), sizeof(float), nfloats,
                   spill_file_) != nfloats) {
      return false;
    }
    slot.freq = row.freq;
    slot.last_access = row.last_access;
    slot.version = row.version;
    sh.map.emplace(key, std::move(slot));
    dead_bytes_ += sizeof(float) * nfloats;
    disk_index_.erase(it);
    return true;
  }
  size_t shard_index(int64_t key) const {
    // splitmix64 scramble → shard index
    uint64_t x = static_cast<uint64_t>(key) + 0x9e3779b97f4a7c15ULL;
    x = (x ^ (x >> 30)) * 0xbf58476d1ce4e5b9ULL;
    x = (x ^ (x >> 27)) * 0x94d049bb133111ebULL;
    return (x ^ (x >> 31)) % kNumShards;
  }

  Shard& shard(int64_t key) {
    return shards_[shard_index(key)];
  }

  void init_value(int64_t key, Slot& slot) {
    slot.data.assign(dim_, 0.0f);
    slot.last_access = now_sec();
    // bump the table version so gather-or-insert rows are visible to
    // delta export (version > since), not just optimizer-touched ones
    slot.version = ++version_;
    if (init_mode_ == 1) {
      // deterministic per-key pseudo-normal init
      std::mt19937_64 rng(seed_ ^ static_cast<uint64_t>(key));
      std::normal_distribution<float> dist(0.f, init_scale_);
      for (int64_t d = 0; d < dim_; ++d) slot.data[d] = dist(rng);
    }
  }

  // Batched write path: group rows by shard, DEDUP-ACCUMULATE the
  // gradients of duplicate keys (single vectorized float add per
  // dup), then take each shard lock ONCE and apply the optimizer a
  // single pass per UNIQUE key; disjoint shard groups fan out across
  // threads. This replaces both the per-row lock+hash round-trip
  // (the sparse update ran ~10x slower than the raw lookup) and the
  // caller's python-side np.unique + np.add.at (which dominated at
  // ~5 ms per 8k batch). row_fn(acc_grad_row, slot) sees the SUMMED
  // gradient exactly as the dedup'd path did before.
  // Lock order shard -> disk is preserved: each worker thread holds
  // only ITS shard's lock when promote_from_disk takes disk_mu_.
  template <typename F>
  void batched_update(const int64_t* keys, int64_t n,
                      const float* grads, int state_mult, F&& row_fn) {
    // KV_PROF=1: accumulate per-phase ns into process-wide counters,
    // dumped by kv_prof_report() — a measurement aid, off by default
    static const bool kProf = std::getenv("KV_PROF") != nullptr;
    using TimePoint = std::chrono::steady_clock::time_point;
    // clock reads only when profiling: ~20 ns each, and the off path
    // is the exact hot path this function exists to keep fast
    auto tick = [&]() -> TimePoint {
      return kProf ? std::chrono::steady_clock::now() : TimePoint{};
    };
    auto t_start = tick();
    std::vector<std::vector<int64_t>> by_shard(kNumShards);
    for (int64_t i = 0; i < n; ++i)
      by_shard[shard_index(keys[i])].push_back(i);
    if (kProf) prof_group_ns += ns_since(t_start);
    const size_t need = static_cast<size_t>(dim_) * state_mult;
    const int64_t dim = dim_;
    auto run_shard = [&](size_t s) {
      const auto& rows = by_shard[s];
      if (rows.empty()) return;
      auto t_shard = tick();
      // dedup + accumulate OUTSIDE the lock: writers in other threads
      // own other shards, readers only need the lock for the apply.
      // Common case (callers already dedup'd / few collisions): no
      // copy at all — each unique points at its grads row; the first
      // duplicate triggers a copy into `acc` (reserved upfront, so
      // row pointers stay stable) and sums there. The dedup index is
      // a reused thread_local flat table (DedupTable): constructing a
      // std::unordered_map per shard per call was ~14% of the
      // update's wall clock (KV_PROF profile).
      static thread_local DedupTable uidx;
      uidx.begin(rows.size());
      std::vector<int64_t> ukeys;
      std::vector<const float*> gsrc;
      std::vector<int64_t> accpos;  // offset into acc, -1 = none
      std::vector<float> acc;
      ukeys.reserve(rows.size());
      gsrc.reserve(rows.size());
      accpos.reserve(rows.size());
      acc.reserve(rows.size() * dim);  // no realloc: pointers stable
      for (int64_t i : rows) {
        const int64_t key = keys[i];
        const float* g = grads + i * dim;
        bool fresh = false;
        const int64_t u = uidx.find_or_insert(
            key, static_cast<int64_t>(ukeys.size()), &fresh);
        if (fresh) {
          ukeys.push_back(key);
          gsrc.push_back(g);
          accpos.push_back(-1);
        } else {
          if (accpos[u] < 0) {
            // first dup for this key: materialize the accumulator
            accpos[u] = static_cast<int64_t>(acc.size());
            acc.insert(acc.end(), gsrc[u], gsrc[u] + dim);
            gsrc[u] = acc.data() + accpos[u];
          }
          float* a = acc.data() + accpos[u];
          for (int64_t d = 0; d < dim; ++d) a[d] += g[d];
        }
      }
      if (kProf) prof_dedup_ns += ns_since(t_shard);
      auto t_resolve = tick();
      Shard& sh = shards_[s];
      std::lock_guard<std::mutex> g(sh.mu);
      // resolve all slots first, then apply with the NEXT rows
      // prefetched: slot payloads live at random heap addresses, so
      // the apply loop is memory-latency bound without this (the
      // update's cost scales with slot bytes, not flops)
      std::vector<Slot*> slots(ukeys.size());
      for (size_t u = 0; u < ukeys.size(); ++u) {
        const int64_t key = ukeys[u];
        auto it = sh.map.find(key);
        if (it == sh.map.end() && promote_from_disk(key, sh)) {
          it = sh.map.find(key);
        }
        if (it == sh.map.end()) {
          it = sh.map.emplace(key, Slot{}).first;
          init_value(key, it->second);
        }
        if (it->second.data.size() < need) {
          it->second.data.resize(need, 0.f);
        }
        slots[u] = &it->second;
      }
      // apply in ascending PAYLOAD-ADDRESS order: slot payloads are
      // heap-scattered, and the apply loop is DRAM-latency bound, so
      // visiting them in address order converts random-page walks
      // into mostly-monotonic ones (TLB hits + the hardware stream
      // prefetcher engage). Order within a shard is free to permute:
      // keys are unique after dedup, so updates commute.
      if (kProf) prof_resolve_ns += ns_since(t_resolve);
      auto t_apply = tick();
      std::vector<uint32_t> order(slots.size());
      for (uint32_t u = 0; u < order.size(); ++u) order[u] = u;
      std::sort(order.begin(), order.end(),
                [&](uint32_t a, uint32_t b) {
                  return slots[a]->data.data() <
                         slots[b]->data.data();
                });
      constexpr size_t kAhead = 8;
      for (size_t i = 0; i < order.size(); ++i) {
        if (i + kAhead < order.size()) {
          const float* p = slots[order[i + kAhead]]->data.data();
          for (size_t b = 0; b < need * sizeof(float);
               b += 64) {
            __builtin_prefetch(
                reinterpret_cast<const char*>(p) + b, 1);
          }
        }
        const uint32_t u = order[i];
        row_fn(gsrc[u], *slots[u]);
      }
      if (kProf) prof_apply_ns += ns_since(t_apply);
    };
    // parallelism only pays off on big batches; below the threshold
    // the pool handoff overhead beats the win
    if (n < 4096) {
      for (size_t s = 0; s < kNumShards; ++s) run_shard(s);
      return;
    }
    WorkPool::get().parallel_for(
        kNumShards, [&](size_t s) { run_shard(s); });
  }

  // find-or-create + run f(slot), all under the shard lock so a
  // concurrent evict() cannot invalidate the slot mid-update; checks
  // the disk tier before re-initializing
  template <typename F>
  void with_slot(int64_t key, int state_mult, F&& f) {
    Shard& sh = shard(key);
    std::lock_guard<std::mutex> g(sh.mu);
    auto it = sh.map.find(key);
    if (it == sh.map.end() && promote_from_disk(key, sh)) {
      it = sh.map.find(key);
    }
    if (it == sh.map.end()) {
      it = sh.map.emplace(key, Slot{}).first;
      init_value(key, it->second);
    }
    const size_t need = static_cast<size_t>(dim_) * state_mult;
    if (it->second.data.size() < need) it->second.data.resize(need, 0.f);
    f(it->second);
  }

  const int64_t dim_;
  const int init_mode_;
  const float init_scale_;
  const uint64_t seed_;
  std::atomic<uint64_t> version_;
  Shard shards_[kNumShards];

  // disk tier (guarded by disk_mu_)
  mutable std::mutex disk_mu_;
  std::string spill_path_;
  FILE* spill_file_ = nullptr;
  std::unordered_map<int64_t, DiskRow> disk_index_;
  int64_t file_bytes_ = 0;
  int64_t dead_bytes_ = 0;

 public:
  ~KvTable() {
    std::lock_guard<std::mutex> g(disk_mu_);
    if (spill_file_) std::fclose(spill_file_);
  }
};

}  // namespace

extern "C" {

void* kv_create(int64_t dim, int init_mode, uint64_t seed,
                float init_scale) {
  return new KvTable(dim, init_mode, seed, init_scale);
}

void kv_free(void* t) { delete static_cast<KvTable*>(t); }

int64_t kv_size(void* t) { return static_cast<KvTable*>(t)->size(); }

int64_t kv_dim(void* t) { return static_cast<KvTable*>(t)->dim(); }

uint64_t kv_version(void* t) {
  return static_cast<KvTable*>(t)->version();
}

void kv_lookup(void* t, const int64_t* keys, int64_t n, float* out,
               int insert_missing) {
  static_cast<KvTable*>(t)->lookup(keys, n, out, insert_missing);
}

void kv_scatter_add(void* t, const int64_t* keys, int64_t n,
                    const float* vals, float alpha) {
  static_cast<KvTable*>(t)->scatter_add(keys, n, vals, alpha);
}

void kv_apply_sgd(void* t, const int64_t* keys, int64_t n,
                  const float* grads, float lr) {
  static_cast<KvTable*>(t)->apply_sgd(keys, n, grads, lr);
}

void kv_apply_adagrad(void* t, const int64_t* keys, int64_t n,
                      const float* grads, float lr, float eps) {
  static_cast<KvTable*>(t)->apply_adagrad(keys, n, grads, lr, eps);
}

void kv_apply_adam(void* t, const int64_t* keys, int64_t n,
                   const float* grads, float lr, float b1, float b2,
                   float eps, int64_t step, float l1, float l2) {
  static_cast<KvTable*>(t)->apply_adam(keys, n, grads, lr, b1, b2, eps,
                                       step, l1, l2);
}

// batched_update phase totals since the last call (ns): [group, dedup,
// resolve, apply]. Populated only when KV_PROF=1; reading resets.
void kv_prof_report(uint64_t* out4) {
  out4[0] = prof_group_ns.exchange(0);
  out4[1] = prof_dedup_ns.exchange(0);
  out4[2] = prof_resolve_ns.exchange(0);
  out4[3] = prof_apply_ns.exchange(0);
}

int64_t kv_evict(void* t, uint32_t min_freq, double max_idle_sec) {
  return static_cast<KvTable*>(t)->evict(min_freq, max_idle_sec);
}

int64_t kv_delete_keys(void* t, const int64_t* keys, int64_t n) {
  return static_cast<KvTable*>(t)->delete_keys(keys, n);
}

int64_t kv_export_count(void* t, uint64_t since_version) {
  return static_cast<KvTable*>(t)->export_count(since_version);
}

int64_t kv_export_rows(void* t, uint64_t since_version,
                       int64_t* keys_out, float* vals_out,
                       int64_t max_n) {
  return static_cast<KvTable*>(t)->export_rows(since_version, keys_out,
                                               vals_out, max_n);
}

void kv_import_rows(void* t, const int64_t* keys, const float* vals,
                    int64_t n) {
  static_cast<KvTable*>(t)->import_rows(keys, vals, n);
}

int kv_max_state_mult(void* t) {
  return static_cast<KvTable*>(t)->max_state_mult();
}

int64_t kv_export_full(void* t, uint64_t since_version,
                       int64_t* keys_out, float* state_out,
                       uint32_t* freq_out, int64_t max_n,
                       int state_mult) {
  return static_cast<KvTable*>(t)->export_full(
      since_version, keys_out, state_out, freq_out, max_n, state_mult);
}

void kv_import_full(void* t, const int64_t* keys, const float* state,
                    const uint32_t* freq, int64_t n, int state_mult) {
  static_cast<KvTable*>(t)->import_full(keys, state, freq, n,
                                        state_mult);
}

int kv_set_spill_path(void* t, const char* path) {
  return static_cast<KvTable*>(t)->set_spill_path(path) ? 1 : 0;
}

int64_t kv_spill(void* t, uint32_t min_freq, double max_idle_sec) {
  return static_cast<KvTable*>(t)->spill(min_freq, max_idle_sec);
}

int64_t kv_disk_size(void* t) {
  return static_cast<KvTable*>(t)->disk_size();
}

int64_t kv_compact(void* t) {
  return static_cast<KvTable*>(t)->compact();
}

}  // extern "C"
