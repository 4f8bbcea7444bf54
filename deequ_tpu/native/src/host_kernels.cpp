// Native host kernels for the string-typed hot loops of the scan frontend.
//
// The reference's native tier is its set of Catalyst ImperativeAggregate /
// UDAF kernels doing per-row buffer updates inside Spark executors
// (reference `analyzers/catalyst/StatefulHyperloglogPlus.scala:89-115`,
// `StatefulDataType.scala:26-83`). Here the device tier is XLA; this C++
// tier covers the host-side per-value string work the device cannot do:
// xxHash64 batch hashing (HLL ingest), type classification (DataType
// analyzer) and UTF-8 length counting (Min/MaxLength), all operating on
// Arrow-layout buffers (concatenated UTF-8 bytes + offsets) in one pass.
//
// Build: python -m deequ_tpu.native.build  (g++ -O3 -shared -fPIC)

#include <cstdint>
#include <cstdlib>
#include <cstring>

extern "C" {

// ---------------------------------------------------------------------------
// xxHash64 (public algorithm; must match deequ_tpu/ops/hashing.py and
// Spark's XxHash64Function bit-for-bit)
// ---------------------------------------------------------------------------

static const uint64_t P1 = 11400714785074694791ULL;
static const uint64_t P2 = 14029467366897019727ULL;
static const uint64_t P3 = 1609587929392839161ULL;
static const uint64_t P4 = 9650029242287828579ULL;
static const uint64_t P5 = 2870177450012600261ULL;

static inline uint64_t rotl64(uint64_t x, int r) {
  return (x << r) | (x >> (64 - r));
}

static inline uint64_t read64(const uint8_t* p) {
  uint64_t v;
  std::memcpy(&v, p, 8);
  return v;  // little-endian hosts only (x86-64 / aarch64)
}

static inline uint32_t read32(const uint8_t* p) {
  uint32_t v;
  std::memcpy(&v, p, 4);
  return v;
}

static uint64_t xxh64(const uint8_t* data, int64_t len, uint64_t seed) {
  const uint8_t* p = data;
  const uint8_t* end = data + len;
  uint64_t h;
  if (len >= 32) {
    uint64_t v1 = seed + P1 + P2;
    uint64_t v2 = seed + P2;
    uint64_t v3 = seed;
    uint64_t v4 = seed - P1;
    const uint8_t* limit = end - 32;
    do {
      v1 = rotl64(v1 + read64(p) * P2, 31) * P1; p += 8;
      v2 = rotl64(v2 + read64(p) * P2, 31) * P1; p += 8;
      v3 = rotl64(v3 + read64(p) * P2, 31) * P1; p += 8;
      v4 = rotl64(v4 + read64(p) * P2, 31) * P1; p += 8;
    } while (p <= limit);
    h = rotl64(v1, 1) + rotl64(v2, 7) + rotl64(v3, 12) + rotl64(v4, 18);
    h = (h ^ (rotl64(v1 * P2, 31) * P1)) * P1 + P4;
    h = (h ^ (rotl64(v2 * P2, 31) * P1)) * P1 + P4;
    h = (h ^ (rotl64(v3 * P2, 31) * P1)) * P1 + P4;
    h = (h ^ (rotl64(v4 * P2, 31) * P1)) * P1 + P4;
  } else {
    h = seed + P5;
  }
  h += (uint64_t)len;
  while (p + 8 <= end) {
    h = rotl64(h ^ (rotl64(read64(p) * P2, 31) * P1), 27) * P1 + P4;
    p += 8;
  }
  if (p + 4 <= end) {
    h = rotl64(h ^ ((uint64_t)read32(p) * P1), 23) * P2 + P3;
    p += 4;
  }
  while (p < end) {
    h = rotl64(h ^ ((uint64_t)(*p) * P5), 11) * P1;
    ++p;
  }
  h ^= h >> 33;
  h *= P2;
  h ^= h >> 29;
  h *= P3;
  h ^= h >> 32;
  return h;
}

// hash n strings given arrow large-string layout; null/invalid -> seed
void xxhash64_batch(const uint8_t* data, const int64_t* offsets,
                    const uint8_t* valid, int64_t n, uint64_t seed,
                    uint64_t* out) {
  for (int64_t i = 0; i < n; ++i) {
    if (valid != nullptr && !valid[i]) {
      out[i] = seed;
      continue;
    }
    out[i] = xxh64(data + offsets[i], offsets[i + 1] - offsets[i], seed);
  }
}

// ---------------------------------------------------------------------------
// HLL ingest: hash value -> (register index, leading-zero count), packed as
// uint16 = (idx << 6) | pw. One pass per column, so the device feed is 2
// bytes/row instead of 8 (mirrors the per-row math of the reference
// `StatefulHyperloglogPlus.update`, `StatefulHyperloglogPlus.scala:93-114`:
// idx = top P bits, pw = clz((hash << P) | 1 << (P-1)) + 1, P = 9).
// Nulls pack as 0 (idx 0, pw 0), which never wins a register max.
// ---------------------------------------------------------------------------

static const int HLL_P = 9;

static inline uint16_t hll_pack_hash(uint64_t h) {
  uint32_t idx = (uint32_t)(h >> (64 - HLL_P));
  uint64_t w = (h << HLL_P) | (1ULL << (HLL_P - 1));
  // w always has a bit set (the padding bit), so clzll is defined
  uint32_t pw = (uint32_t)__builtin_clzll(w) + 1;
  return (uint16_t)((idx << 6) | pw);
}

static inline uint64_t xxh64_fixed8(uint64_t value, uint64_t seed) {
  // xxh64 specialized to an 8-byte input (Spark hashes fixed-width values
  // as one little-endian long)
  uint64_t h = seed + P5 + 8;
  uint64_t k = rotl64(value * P2, 31) * P1;
  h ^= k;
  h = rotl64(h, 27) * P1 + P4;
  h ^= h >> 33;
  h *= P2;
  h ^= h >> 29;
  h *= P3;
  h ^= h >> 32;
  return h;
}

// doubles: IEEE754 bits with -0.0 normalized to 0.0 (Spark semantics)
void hll_pack_f64(const double* vals, const uint8_t* valid, int64_t n,
                  uint64_t seed, uint16_t* out) {
  for (int64_t i = 0; i < n; ++i) {
    if (valid != nullptr && !valid[i]) {
      out[i] = 0;
      continue;
    }
    double d = vals[i] == 0.0 ? 0.0 : vals[i];  // collapses -0.0
    uint64_t bits;
    std::memcpy(&bits, &d, 8);
    out[i] = hll_pack_hash(xxh64_fixed8(bits, seed));
  }
}

void hll_pack_i64(const int64_t* vals, const uint8_t* valid, int64_t n,
                  uint64_t seed, uint16_t* out) {
  for (int64_t i = 0; i < n; ++i) {
    if (valid != nullptr && !valid[i]) {
      out[i] = 0;
      continue;
    }
    out[i] = hll_pack_hash(xxh64_fixed8((uint64_t)vals[i], seed));
  }
}

// strings in arrow large-string layout
void hll_pack_strings(const uint8_t* data, const int64_t* offsets,
                      const uint8_t* valid, int64_t n, uint64_t seed,
                      uint16_t* out) {
  for (int64_t i = 0; i < n; ++i) {
    if (valid != nullptr && !valid[i]) {
      out[i] = 0;
      continue;
    }
    out[i] = hll_pack_hash(
        xxh64(data + offsets[i], offsets[i + 1] - offsets[i], seed));
  }
}

// ---------------------------------------------------------------------------
// type classification (reference regexes,
// `analyzers/catalyst/StatefulDataType.scala:36-38`):
//   FRACTIONAL: ^(-|\+)? ?\d*\.\d*$
//   INTEGRAL:   ^(-|\+)? ?\d*$
//   BOOLEAN:    ^(true|false)$
// decision order: null -> fractional -> integral -> boolean -> string
// codes: 0=null/unknown 1=fractional 2=integral 3=boolean 4=string
// ---------------------------------------------------------------------------

static inline bool match_numericish(const uint8_t* s, int64_t len, bool* fractional) {
  int64_t i = 0;
  if (i < len && (s[i] == '-' || s[i] == '+')) ++i;
  if (i < len && s[i] == ' ') ++i;  // the reference regex admits one space
  int64_t digits_before = 0;
  while (i < len && s[i] >= '0' && s[i] <= '9') { ++i; ++digits_before; }
  if (i == len) {           // integral (digits may be empty, as in the regex)
    *fractional = false;
    return true;
  }
  if (s[i] != '.') return false;
  ++i;
  while (i < len && s[i] >= '0' && s[i] <= '9') ++i;
  if (i != len) return false;
  *fractional = true;       // digits on either side of '.' may be empty
  return true;
}

static inline bool match_boolean(const uint8_t* s, int64_t len) {
  return (len == 4 && std::memcmp(s, "true", 4) == 0) ||
         (len == 5 && std::memcmp(s, "false", 5) == 0);
}

void classify_types_batch(const uint8_t* data, const int64_t* offsets,
                          const uint8_t* valid, int64_t n, int32_t* out) {
  for (int64_t i = 0; i < n; ++i) {
    if (valid != nullptr && !valid[i]) {
      out[i] = 0;
      continue;
    }
    const uint8_t* s = data + offsets[i];
    int64_t len = offsets[i + 1] - offsets[i];
    bool fractional = false;
    if (match_numericish(s, len, &fractional)) {
      out[i] = fractional ? 1 : 2;
    } else if (match_boolean(s, len)) {
      out[i] = 3;
    } else {
      out[i] = 4;
    }
  }
}

// ---------------------------------------------------------------------------
// UTF-8 codepoint lengths (matches python len(str)); null -> 0
// ---------------------------------------------------------------------------

void string_lengths_batch(const uint8_t* data, const int64_t* offsets,
                          const uint8_t* valid, int64_t n, int32_t* out) {
  for (int64_t i = 0; i < n; ++i) {
    if (valid != nullptr && !valid[i]) {
      out[i] = 0;
      continue;
    }
    const uint8_t* s = data + offsets[i];
    int64_t len = offsets[i + 1] - offsets[i];
    int32_t count = 0;
    for (int64_t j = 0; j < len; ++j) {
      if ((s[j] & 0xC0) != 0x80) ++count;  // count non-continuation bytes
    }
    out[i] = count;
  }
}

// ---------------------------------------------------------------------------
// Block-partial reduction kernels (the ingest tier).
//
// When the accelerator feed link cannot sustain raw column streaming (the
// engine probes this), per-batch partial states are computed here — one
// C-speed pass over the block — and the device folds the tiny states with
// the same semigroup `merge` algebra it uses across shards (SURVEY.md §2.9:
// partial aggregation near the data + algebraic merge IS the reference's
// execution model; Spark's partial-agg runs executor-side for the same
// reason). Two-pass moments match the batch formulas of the device update
// (`analyzers/simple.py` StandardDeviation/Correlation.update).
// ---------------------------------------------------------------------------

// NaN semantics (uniform with the device update and the numpy fallback in
// HostBatchContext.block_stats — Spark's NaN-largest total order): NaN never
// wins the min (min is NaN only when NO non-NaN value exists, which is also
// the MinState identity); ANY nonnull NaN wins the max; sum/m2 propagate NaN.
//
// The loops are branchless with LANES independent accumulators so -O3
// -march=native auto-vectorizes them (blend + fma); masked-out slots blend
// to the identity BEFORE any arithmetic, so garbage bytes in Arrow null
// slots (possibly NaN/inf) never poison a lane. Lane-wise summation
// reassociates the additions; the resulting sums are at least as accurate
// as the sequential order and well inside the engine's 1e-9 cross-path
// tolerance.
#define BLOCK_STATS_LANES 8
#define BLOCK_STATS_IMPL(NAME, T)                                            \
  void NAME(const T* v, const uint8_t* m, int64_t n, double* out) {          \
    /* out: [count, sum, min, max, m2] */                                    \
    double inf = __builtin_inf(), qnan = __builtin_nan("");                  \
    double sum_l[BLOCK_STATS_LANES], mn_l[BLOCK_STATS_LANES],                \
        mx_l[BLOCK_STATS_LANES];                                             \
    int64_t cnt_l[BLOCK_STATS_LANES], nan_l[BLOCK_STATS_LANES];              \
    for (int j = 0; j < BLOCK_STATS_LANES; ++j) {                            \
      sum_l[j] = 0.0; mn_l[j] = inf; mx_l[j] = -inf;                         \
      cnt_l[j] = 0; nan_l[j] = 0;                                            \
    }                                                                        \
    int64_t main_n = n - (n % BLOCK_STATS_LANES);                            \
    for (int64_t i = 0; i < main_n; i += BLOCK_STATS_LANES) {                \
      for (int j = 0; j < BLOCK_STATS_LANES; ++j) {                          \
        int64_t live = (m == nullptr) || m[i + j];          \
        double x = (double)v[i + j];                                         \
        int64_t isnan_ = x != x;                                             \
        sum_l[j] += live ? x : 0.0;                                          \
        cnt_l[j] += live;                                                    \
        nan_l[j] += live & isnan_;                                           \
        double xo = (live && !isnan_) ? x : inf;                             \
        mn_l[j] = xo < mn_l[j] ? xo : mn_l[j];                               \
        double xh = (live && !isnan_) ? x : -inf;                            \
        mx_l[j] = xh > mx_l[j] ? xh : mx_l[j];                               \
      }                                                                      \
    }                                                                        \
    for (int64_t i = main_n; i < n; ++i) {                                   \
      int64_t live = (m == nullptr) || m[i];                                 \
      double x = (double)v[i];                                               \
      int64_t isnan_ = x != x;                                               \
      sum_l[0] += live ? x : 0.0;                                            \
      cnt_l[0] += live;                                                      \
      nan_l[0] += live & isnan_;                                             \
      double xo = (live && !isnan_) ? x : inf;                               \
      mn_l[0] = xo < mn_l[0] ? xo : mn_l[0];                                 \
      double xh = (live && !isnan_) ? x : -inf;                              \
      mx_l[0] = xh > mx_l[0] ? xh : mx_l[0];                                 \
    }                                                                        \
    double sum = 0.0, mn = inf, mx = -inf;                                   \
    int64_t count = 0, nans = 0;                                             \
    for (int j = 0; j < BLOCK_STATS_LANES; ++j) {                            \
      sum += sum_l[j];                                                       \
      count += cnt_l[j];                                                     \
      nans += nan_l[j];                                                      \
      mn = mn_l[j] < mn ? mn_l[j] : mn;                                      \
      mx = mx_l[j] > mx ? mx_l[j] : mx;                                      \
    }                                                                        \
    double m2 = 0.0;                                                         \
    if (count > 0) {                                                         \
      double mean = sum / (double)count;                                     \
      double m2_l[BLOCK_STATS_LANES];                                        \
      for (int j = 0; j < BLOCK_STATS_LANES; ++j) m2_l[j] = 0.0;             \
      for (int64_t i = 0; i < main_n; i += BLOCK_STATS_LANES) {              \
        for (int j = 0; j < BLOCK_STATS_LANES; ++j) {                        \
          int64_t live = (m == nullptr) || m[i + j];        \
          double d = live ? (double)v[i + j] - mean : 0.0;                   \
          m2_l[j] += d * d;                                                  \
        }                                                                    \
      }                                                                      \
      for (int64_t i = main_n; i < n; ++i) {                                 \
        int64_t live = (m == nullptr) || m[i];                               \
        double d = live ? (double)v[i] - mean : 0.0;                         \
        m2_l[0] += d * d;                                                    \
      }                                                                      \
      for (int j = 0; j < BLOCK_STATS_LANES; ++j) m2 += m2_l[j];             \
    }                                                                        \
    int64_t nonnan = count - nans;                                           \
    out[0] = (double)count;                                                  \
    out[1] = sum;                                                            \
    out[2] = nonnan > 0 ? mn : qnan;                                         \
    out[3] = nans > 0 ? qnan : (nonnan > 0 ? mx : qnan);                     \
    out[4] = m2;                                                             \
    out[5] = (double)nonnan;                                                 \
    out[6] = nonnan > 0 ? mx : qnan; /* NaN-excluded max (KLL g_max) */      \
  }

BLOCK_STATS_IMPL(block_stats_f64, double)
BLOCK_STATS_IMPL(block_stats_f32, float)
BLOCK_STATS_IMPL(block_stats_i64, int64_t)
BLOCK_STATS_IMPL(block_stats_i32, int32_t)

// Pearson co-moments for Correlation: out = [n, xsum, ysum, ck, xmk, ymk]
// (branchless multi-lane like BLOCK_STATS_IMPL)
void block_comoments_f64(const double* x, const double* y, const uint8_t* m,
                         int64_t n, double* out) {
  double xs_l[BLOCK_STATS_LANES] = {0}, ys_l[BLOCK_STATS_LANES] = {0};
  int64_t cnt_l[BLOCK_STATS_LANES] = {0};
  int64_t main_n = n - (n % BLOCK_STATS_LANES);
  for (int64_t i = 0; i < main_n; i += BLOCK_STATS_LANES) {
    for (int j = 0; j < BLOCK_STATS_LANES; ++j) {
      int64_t live = (m == nullptr) || m[i + j];
      xs_l[j] += live ? x[i + j] : 0.0;
      ys_l[j] += live ? y[i + j] : 0.0;
      cnt_l[j] += live;
    }
  }
  for (int64_t i = main_n; i < n; ++i) {
    int64_t live = (m == nullptr) || m[i];
    xs_l[0] += live ? x[i] : 0.0;
    ys_l[0] += live ? y[i] : 0.0;
    cnt_l[0] += live;
  }
  double xs = 0.0, ys = 0.0;
  int64_t count = 0;
  for (int j = 0; j < BLOCK_STATS_LANES; ++j) {
    xs += xs_l[j]; ys += ys_l[j]; count += cnt_l[j];
  }
  double ck = 0.0, xmk = 0.0, ymk = 0.0;
  if (count > 0) {
    double xa = xs / (double)count, ya = ys / (double)count;
    double ck_l[BLOCK_STATS_LANES] = {0}, xmk_l[BLOCK_STATS_LANES] = {0},
        ymk_l[BLOCK_STATS_LANES] = {0};
    for (int64_t i = 0; i < main_n; i += BLOCK_STATS_LANES) {
      for (int j = 0; j < BLOCK_STATS_LANES; ++j) {
        int64_t live = (m == nullptr) || m[i + j];
        double dx = live ? x[i + j] - xa : 0.0;
        double dy = live ? y[i + j] - ya : 0.0;
        ck_l[j] += dx * dy;
        xmk_l[j] += dx * dx;
        ymk_l[j] += dy * dy;
      }
    }
    for (int64_t i = main_n; i < n; ++i) {
      int64_t live = (m == nullptr) || m[i];
      double dx = live ? x[i] - xa : 0.0;
      double dy = live ? y[i] - ya : 0.0;
      ck_l[0] += dx * dy;
      xmk_l[0] += dx * dx;
      ymk_l[0] += dy * dy;
    }
    for (int j = 0; j < BLOCK_STATS_LANES; ++j) {
      ck += ck_l[j]; xmk += xmk_l[j]; ymk += ymk_l[j];
    }
  }
  out[0] = (double)count;
  out[1] = xs;
  out[2] = ys;
  out[3] = ck;
  out[4] = xmk;
  out[5] = ymk;
}

// HLL register update in place: regs[512] must be zero- or prior-initialized.
// Hashes are computed 8 rows at a time into a local block first (independent
// chains -> instruction-level parallelism); the register max-scatter stays
// scalar (data-dependent indices). Masked-out garbage hashes harmlessly and
// is discarded at scatter time.
#define BLOCK_HLL_IMPL(NAME, T, TOBITS)                                      \
  void NAME(const T* v, const uint8_t* m, int64_t n, uint64_t seed,          \
            uint8_t* regs) {                                                 \
    uint64_t h[8];                                                           \
    int64_t main_n = n - (n % 8);                                            \
    for (int64_t i = 0; i < main_n; i += 8) {                                \
      for (int j = 0; j < 8; ++j) h[j] = xxh64_fixed8(TOBITS(v[i + j]), seed); \
      for (int j = 0; j < 8; ++j) {                                          \
        if (m != nullptr && !m[i + j]) continue;                             \
        uint32_t idx = (uint32_t)(h[j] >> (64 - HLL_P));                     \
        uint64_t w = (h[j] << HLL_P) | (1ULL << (HLL_P - 1));                \
        uint8_t pw = (uint8_t)(__builtin_clzll(w) + 1);                      \
        if (pw > regs[idx]) regs[idx] = pw;                                  \
      }                                                                      \
    }                                                                        \
    for (int64_t i = main_n; i < n; ++i) {                                   \
      if (m != nullptr && !m[i]) continue;                                   \
      uint64_t hh = xxh64_fixed8(TOBITS(v[i]), seed);                        \
      uint32_t idx = (uint32_t)(hh >> (64 - HLL_P));                         \
      uint64_t w = (hh << HLL_P) | (1ULL << (HLL_P - 1));                    \
      uint8_t pw = (uint8_t)(__builtin_clzll(w) + 1);                        \
      if (pw > regs[idx]) regs[idx] = pw;                                    \
    }                                                                        \
  }

static inline uint64_t bits_of_double(double d) {
  double z = d == 0.0 ? 0.0 : d;  // collapse -0.0 (Spark semantics)
  uint64_t b;
  std::memcpy(&b, &z, 8);
  return b;
}
static inline uint64_t bits_of_i64(int64_t v) { return (uint64_t)v; }

BLOCK_HLL_IMPL(block_hll_f64, double, bits_of_double)
BLOCK_HLL_IMPL(block_hll_i64, int64_t, bits_of_i64)

void block_hll_strings(const uint8_t* data, const int64_t* offsets,
                       const uint8_t* valid, int64_t n, uint64_t seed,
                       uint8_t* regs) {
  for (int64_t i = 0; i < n; ++i) {
    if (valid != nullptr && !valid[i]) continue;
    uint64_t h = xxh64(data + offsets[i], offsets[i + 1] - offsets[i], seed);
    uint32_t idx = (uint32_t)(h >> (64 - HLL_P));
    uint64_t w = (h << HLL_P) | (1ULL << (HLL_P - 1));
    uint8_t pw = (uint8_t)(__builtin_clzll(w) + 1);
    if (pw > regs[idx]) regs[idx] = pw;
  }
}

// KLL block pre-sample: take <= k valid values at stride 2^h (h minimal so
// the sample fits), sort them, report (m, h, min, max, count). Stride
// sampling over the unsorted block + per-call offset rotation is the
// classical KLL bottom-sampler (items enter level h with weight 2^h); the
// device-side kll_update uses sorted-stride order statistics instead —
// both satisfy the KLL rank-error bound, and a run uses exactly one path.
static int cmp_f64(const void* a, const void* b) {
  double x = *(const double*)a, y = *(const double*)b;
  return (x > y) - (x < y);
}

// KLL pick-only variant: the caller already knows the valid (non-NaN) value
// count from a shared block_stats pass over the same column+mask, so the
// counting pass is skipped — one less memory sweep per column per batch.
// Shared stride policy for the host samplers: pick up to TWO levels denser
// than the stride that fits k items, then (when two levels denser) compact
// the sorted sample once in-kernel — every 2nd item, parity from the batch
// randomness — emitting <= 2k items one level up. The emitted items carry
// the rank accuracy of the 4x-denser sample (compaction error is
// deterministic and tiny vs sampling variance), which a plain k-item pick
// lacks (~2x the rank error of the device path's sorted order statistics;
// validated by the host-tier rank-error tests). The <= 2k emission also
// preserves the state-buffer occupancy invariant: a level may hold up to k
// uncompacted residuals, and 2k + k <= the 4k buffer.
static inline void kll_stride_policy(int32_t k, int64_t nv, int64_t* out_h,
                                     int64_t* out_stride, int64_t* out_cap,
                                     int* out_dense) {
  int64_t h = 0;
  int64_t stride = 1;
  while (stride * (int64_t)k < nv) { stride <<= 1; ++h; }
  int dense = h >= 2 ? 2 : (int)h;
  h -= dense;
  stride >>= dense;
  *out_h = h;
  *out_stride = stride;
  *out_cap = (int64_t)k << dense;
  *out_dense = dense;
}

// In-place compaction of the sorted pick when it was two levels dense:
// emit every 2nd item (parity from r), halving the count and raising the
// weight one level. Returns the new item count; *h is incremented.
static inline int64_t kll_compact_pick(double* items, int64_t taken,
                                       int dense, uint32_t r, int64_t* h) {
  if (dense < 2 || taken <= 1) return taken;
  int64_t parity = (int64_t)((r >> 8) & 1u);
  int64_t m_out = (taken - parity + 1) / 2;
  for (int64_t j = 0; j < m_out; ++j) items[j] = items[parity + 2 * j];
  *h += 1;
  return m_out;
}

// The strided pick over the valid values, selection-identical to numpy's
// vv[offset::stride][:cap]. When every row is a valid non-NaN value
// (nv == n — the common case for clean numeric columns) the pick is a
// DIRECT gather of <= cap elements: O(cap) instead of a full O(n) row walk.
// The general path keeps a countdown to the next pick index instead of the
// old per-valid-row 64-bit modulo (~3x on masked columns).
static inline int64_t kll_strided_pick(const double* v, const uint8_t* m,
                                       int64_t n, int64_t nv, int64_t offset,
                                       int64_t stride, int64_t cap,
                                       double* items) {
  int64_t taken = 0;
  if (nv == n) {
    for (int64_t i = offset; i < n && taken < cap; i += stride) {
      items[taken++] = v[i];
    }
    return taken;
  }
  int64_t next = offset, seen = 0;
  for (int64_t i = 0; i < n && taken < cap; ++i) {
    if (m != nullptr && !m[i]) continue;
    double x = v[i];
    if (x != x) continue;
    if (seen == next) {
      items[taken++] = x;
      next += stride;
    }
    ++seen;
  }
  return taken;
}

void block_kll_pick_f64(const double* v, const uint8_t* m, int64_t n,
                        int32_t k, uint32_t tick, int64_t nv, double* items,
                        int64_t* out_meta) {
  if (k < 1) k = 1;  // a non-positive sketch size must not hang the loop
  int64_t h, stride, cap;
  int dense;
  kll_stride_policy(k, nv, &h, &stride, &cap, &dense);
  uint32_t r = ((tick * 2654435761u) ^ ((uint32_t)nv * 2246822519u)) >> 7;
  int64_t offset = (int64_t)(r % (uint32_t)stride);
  int64_t taken = kll_strided_pick(v, m, n, nv, offset, stride, cap, items);
  qsort(items, (size_t)taken, sizeof(double), cmp_f64);
  taken = kll_compact_pick(items, taken, dense, r, &h);
  out_meta[0] = taken;
  out_meta[1] = h;
}

// Integer-column variant: picks directly from the int64 buffer (values are
// converted to double per PICKED item), so callers skip the full-column
// f64 conversion copy the f64 kernel would require. Integers have no NaN,
// so `nv` is simply the masked-valid count; selection order is identical
// to converting first (int -> double is monotone), keeping the result
// bit-identical to the f64 path for |v| < 2^53.
void block_kll_pick_i64(const int64_t* v, const uint8_t* m, int64_t n,
                        int32_t k, uint32_t tick, int64_t nv, double* items,
                        int64_t* out_meta) {
  if (k < 1) k = 1;
  int64_t h, stride, cap;
  int dense;
  kll_stride_policy(k, nv, &h, &stride, &cap, &dense);
  uint32_t r = ((tick * 2654435761u) ^ ((uint32_t)nv * 2246822519u)) >> 7;
  int64_t offset = (int64_t)(r % (uint32_t)stride);
  int64_t taken = 0;
  if (nv == n) {
    for (int64_t i = offset; i < n && taken < cap; i += stride) {
      items[taken++] = (double)v[i];
    }
  } else {
    int64_t next = offset, seen = 0;
    for (int64_t i = 0; i < n && taken < cap; ++i) {
      if (m != nullptr && !m[i]) continue;
      if (seen == next) {
        items[taken++] = (double)v[i];
        next += stride;
      }
      ++seen;
    }
  }
  qsort(items, (size_t)taken, sizeof(double), cmp_f64);
  taken = kll_compact_pick(items, taken, dense, r, &h);
  out_meta[0] = taken;
  out_meta[1] = h;
}

void block_kll_sample_f64(const double* v, const uint8_t* m, int64_t n,
                          int32_t k, uint32_t tick, double* items,
                          int64_t* out_meta, double* out_minmax) {
  // pass 1: count valid (NaN excluded, like the device path) — branchless
  // multi-lane like BLOCK_STATS_IMPL so it auto-vectorizes
  double inf = __builtin_inf();
  double mn_l[BLOCK_STATS_LANES], mx_l[BLOCK_STATS_LANES];
  int64_t nv_l[BLOCK_STATS_LANES];
  for (int j = 0; j < BLOCK_STATS_LANES; ++j) {
    mn_l[j] = inf; mx_l[j] = -inf; nv_l[j] = 0;
  }
  int64_t main_n = n - (n % BLOCK_STATS_LANES);
  for (int64_t i = 0; i < main_n; i += BLOCK_STATS_LANES) {
    for (int j = 0; j < BLOCK_STATS_LANES; ++j) {
      int64_t live = (m == nullptr) || m[i + j];
      double x = v[i + j];
      int64_t ok = live & (x == x);
      nv_l[j] += ok;
      double xo = ok ? x : inf;
      mn_l[j] = xo < mn_l[j] ? xo : mn_l[j];
      double xh = ok ? x : -inf;
      mx_l[j] = xh > mx_l[j] ? xh : mx_l[j];
    }
  }
  for (int64_t i = main_n; i < n; ++i) {
    int64_t live = (m == nullptr) || m[i];
    double x = v[i];
    int64_t ok = live & (x == x);
    nv_l[0] += ok;
    double xo = ok ? x : inf;
    mn_l[0] = xo < mn_l[0] ? xo : mn_l[0];
    double xh = ok ? x : -inf;
    mx_l[0] = xh > mx_l[0] ? xh : mx_l[0];
  }
  int64_t nv = 0;
  double mn = inf, mx = -inf;
  for (int j = 0; j < BLOCK_STATS_LANES; ++j) {
    nv += nv_l[j];
    mn = mn_l[j] < mn ? mn_l[j] : mn;
    mx = mx_l[j] > mx ? mx_l[j] : mx;
  }
  if (nv == 0) { mn = 0.0; mx = 0.0; }
  if (k < 1) k = 1;  // a non-positive sketch size must not hang the loop
  int64_t h, stride, cap;
  int dense;
  kll_stride_policy(k, nv, &h, &stride, &cap, &dense);
  // offset mixes the batch index AND the valid-value count so a stream
  // whose structure is periodic in the batch size cannot stay phase-locked
  // with the sampler (must match _np_kll_sample in analyzers/sketches.py
  // bit-for-bit)
  uint32_t r = ((tick * 2654435761u) ^ ((uint32_t)nv * 2246822519u)) >> 7;
  int64_t offset = (int64_t)(r % (uint32_t)stride);
  int64_t taken = kll_strided_pick(v, m, n, nv, offset, stride, cap, items);
  qsort(items, (size_t)taken, sizeof(double), cmp_f64);
  taken = kll_compact_pick(items, taken, dense, r, &h);
  out_meta[0] = taken;  // m
  out_meta[1] = h;
  out_meta[2] = nv;     // exact valid count
  out_minmax[0] = mn;
  out_minmax[1] = mx;
}

// ---------------------------------------------------------------------------
// dict_masked_bincount — one pass over a dictionary column's codes shared by
// every per-batch consumer (type-class histogram, HLL present-entry fold,
// frequency counts): out[c] += 1 for each masked row, rows with mask=0 or
// code out of [0, num_cats) land in out[num_cats]. Replaces 3-4 numpy
// passes (where + fancy-index copy + bincount) per consumer per column.
// ---------------------------------------------------------------------------

void dict_masked_bincount(const int32_t* codes, const uint8_t* mask,
                          int64_t n, int64_t num_cats, int64_t* out) {
  for (int64_t i = 0; i <= num_cats; ++i) out[i] = 0;
  for (int64_t i = 0; i < n; ++i) {
    int64_t c = codes[i];
    int64_t slot = (mask[i] && c >= 0 && c < num_cats) ? c : num_cats;
    ++out[slot];
  }
}

}  // extern "C"

// ---------------------------------------------------------------------------
// pattern_match_batch — unanchored regex search per row over the Arrow
// string buffers, GIL-free, via the system PCRE2 library (dlopen'd so the
// build carries no header/link dependency). PCRE2 is Perl-compatible like
// Python `re` — the built-in Patterns use (?:...), (?!...), backreferences
// and \b, all with identical semantics — and PCRE2_UTF|PCRE2_UCP makes
// \d/\w Unicode-aware exactly like Python's default str patterns. A match
// only counts when non-empty (reference `regexp_extract(col, p, 0) != ""`,
// `analyzers/PatternMatch.scala:46-52`). Rows PCRE2 cannot judge (e.g.
// invalid UTF-8) get sentinel 2 so the caller can re-check them under
// Python `re`. Replaces the per-row Python loop flagged by VERDICT r4 #4.
// ---------------------------------------------------------------------------

#include <dlfcn.h>

namespace {

typedef void pcre2_code8;
typedef void pcre2_match_data8;

struct Pcre2Api {
  pcre2_code8* (*compile)(const uint8_t*, size_t, uint32_t, int*, size_t*, void*);
  int (*jit_compile)(pcre2_code8*, uint32_t);
  pcre2_match_data8* (*mdata_create)(const pcre2_code8*, void*);
  int (*match)(const pcre2_code8*, const uint8_t*, size_t, size_t, uint32_t,
               pcre2_match_data8*, void*);
  size_t* (*ovector)(pcre2_match_data8*);
  void (*code_free)(pcre2_code8*);
  void (*mdata_free)(pcre2_match_data8*);
  bool ok = false;
};

const uint32_t kPcre2Utf = 0x00080000u;
const uint32_t kPcre2Ucp = 0x00020000u;
const uint32_t kPcre2JitComplete = 0x00000001u;
const size_t kPcre2ZeroTerminated = ~(size_t)0;

const Pcre2Api& pcre2_api() {
  static Pcre2Api api = [] {
    Pcre2Api a;
    void* lib = dlopen("libpcre2-8.so.0", RTLD_NOW | RTLD_GLOBAL);
    if (lib == nullptr) lib = dlopen("libpcre2-8.so", RTLD_NOW | RTLD_GLOBAL);
    if (lib == nullptr) return a;
    a.compile = reinterpret_cast<decltype(a.compile)>(dlsym(lib, "pcre2_compile_8"));
    a.jit_compile = reinterpret_cast<decltype(a.jit_compile)>(
        dlsym(lib, "pcre2_jit_compile_8"));
    a.mdata_create = reinterpret_cast<decltype(a.mdata_create)>(
        dlsym(lib, "pcre2_match_data_create_from_pattern_8"));
    a.match = reinterpret_cast<decltype(a.match)>(dlsym(lib, "pcre2_match_8"));
    a.ovector = reinterpret_cast<decltype(a.ovector)>(
        dlsym(lib, "pcre2_get_ovector_pointer_8"));
    a.code_free = reinterpret_cast<decltype(a.code_free)>(dlsym(lib, "pcre2_code_free_8"));
    a.mdata_free = reinterpret_cast<decltype(a.mdata_free)>(
        dlsym(lib, "pcre2_match_data_free_8"));
    a.ok = a.compile && a.mdata_create && a.match && a.ovector && a.code_free &&
           a.mdata_free;
    return a;
  }();
  return api;
}

}  // namespace

extern "C" {

// returns 0 on success, -1 if the pattern failed to compile, -2 if PCRE2 is
// unavailable. out[i]: 1 = non-empty match, 0 = no match, 2 = row
// undecidable (caller re-checks under Python re).
int pattern_match_batch(const uint8_t* data, const int64_t* offsets,
                        const uint8_t* valid, int64_t n, const char* pattern,
                        uint8_t* out) {
  const Pcre2Api& api = pcre2_api();
  if (!api.ok) return -2;
  int err = 0;
  size_t err_off = 0;
  pcre2_code8* code = api.compile(reinterpret_cast<const uint8_t*>(pattern),
                                  kPcre2ZeroTerminated, kPcre2Utf | kPcre2Ucp,
                                  &err, &err_off, nullptr);
  if (code == nullptr) return -1;
  if (api.jit_compile != nullptr) {
    api.jit_compile(code, kPcre2JitComplete);  // best-effort; interp fallback
  }
  pcre2_match_data8* md = api.mdata_create(code, nullptr);
  if (md == nullptr) {
    api.code_free(code);
    return -1;
  }
  for (int64_t i = 0; i < n; ++i) {
    if (valid != nullptr && !valid[i]) {
      out[i] = 0;
      continue;
    }
    const uint8_t* s = data + offsets[i];
    size_t len = (size_t)(offsets[i + 1] - offsets[i]);
    int rc = api.match(code, s, len, 0, 0, md, nullptr);
    if (rc >= 0) {
      size_t* ov = api.ovector(md);
      out[i] = ov[1] > ov[0] ? 1 : 0;  // empty first match counts as no match
    } else if (rc == -1 /* PCRE2_ERROR_NOMATCH */) {
      out[i] = 0;
    } else {
      out[i] = 2;  // bad UTF etc.: let the caller decide under Python re
    }
  }
  api.mdata_free(md);
  api.code_free(code);
  return 0;
}

}  // extern "C"
