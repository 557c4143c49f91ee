// shifu_evalacc — the eval's accumulation in one pass over a chunk.
//
// train/loop.py reduces every chunk of (scores, labels, weights) an eval
// pass fetches into ops/metrics.StreamingMetrics (the weighted error, and a
// `2 * bins` float64 histogram for the binned AUC: negatives, then
// positives) and the kept scores into obs/sketch.ScoreSketch.  The numpy
// spelling of that is six or seven calls a chunk, each allocating and
// walking a temporary; this is the same reduction as one read of the rows
// (and a second of the weights alone, for the count of nonzero ones).
//
// Semantics (held to the numpy path by tests/test_eval_accumulate_native.py):
//   - error: (s - t)^2 * w in float64, each operation rounded as numpy
//     rounds it (built with -ffp-contract=off: no fused multiply-add), and
//     summed in np.sum's order (buffers of 8,192 rows added in turn, each
//     summed pairwise in blocks of at most 128 rows, eight partial sums a
//     block);
//   - nonzero: rows whose weight is not 0 (a NaN weight counts); kept: rows
//     whose weight is > 0, the only rows the sketch counts; every other row
//     adds +0.0 to its bin, as np.add.at does, which leaves the bin as it is;
//   - bin: int64(s * bins) taken in float32 (exact for a power-of-two
//     `bins`), clipped to [0, bins - 1], plus `bins` where t >= 0.5; rows
//     are added to their bins in row order, as np.add.at adds them;
//   - sketch: bin int64(double(s) * sketch_bins), clipped; its sum and sum
//     of squares over the kept rows in numpy's pairwise order.
//   float -> int64 truncates as the host's numpy cast does: on x86 NaN and
//   out-of-range values give INT64_MIN, which the clip sends to bin 0.
//
// C ABI (ctypes from Python): shifu_evalacc_update.

#include <algorithm>
#include <cstdint>
#include <vector>

#if defined(__x86_64__)
#include <immintrin.h>
#endif

namespace {

constexpr int64_t kPairwiseBlock = 128;  // numpy's PW_BLOCKSIZE
constexpr int64_t kReduceBuffer = 8192;  // numpy's NPY_BUFSIZE

#if defined(__x86_64__)
inline int64_t trunc_i64(float x) { return _mm_cvttss_si64(_mm_set_ss(x)); }
inline int64_t trunc_i64(double x) { return _mm_cvttsd_si64(_mm_set_sd(x)); }
#else
// a saturating conversion, NaN to 0: what aarch64's fcvtzs gives numpy
inline int64_t trunc_i64(double x) {
  if (!(x == x)) return 0;
  if (x >= 9223372036854775808.0) return INT64_MAX;
  if (x < -9223372036854775808.0) return INT64_MIN;
  return static_cast<int64_t>(x);
}
inline int64_t trunc_i64(float x) { return trunc_i64(static_cast<double>(x)); }
#endif

// branch-free (a label or a score's side is a coin flip a row, and a
// mispredicted branch costs more than the row's whole work)
inline int64_t clip(int64_t i, int64_t hi) {
  return std::max<int64_t>(0, std::min(i, hi));
}

// numpy's pairwise sum (DOUBLE_pairwise_sum) of v[lo..lo + n), v[i] = f(i);
// `leaf(lo, n)` sees each leaf's rows first, leaves in row order
template <class F, class L>
double pairwise(const F& f, const L& leaf, int64_t lo, int64_t n) {
  if (n < 8) {
    leaf(lo, n);
    double res = -0.0;
    for (int64_t i = 0; i < n; ++i) res += f(lo + i);
    return res;
  }
  if (n <= kPairwiseBlock) {
    leaf(lo, n);
    double r[8];
    for (int j = 0; j < 8; ++j) r[j] = f(lo + j);
    int64_t i = 8;
    for (; i < n - (n % 8); i += 8)
      for (int j = 0; j < 8; ++j) r[j] += f(lo + i + j);
    double res = ((r[0] + r[1]) + (r[2] + r[3])) +
                 ((r[4] + r[5]) + (r[6] + r[7]));
    for (; i < n; ++i) res += f(lo + i);
    return res;
  }
  int64_t n2 = n / 2;
  n2 -= n2 % 8;
  return pairwise(f, leaf, lo, n2) + pairwise(f, leaf, lo + n2, n - n2);
}

// what np.sum gives: its reduction hands the pairwise sum a buffer of at
// most 8,192 rows at a time (NPY_BUFSIZE) and adds the buffers in order
template <class F, class L>
double numpy_sum(const F& f, const L& leaf, int64_t n) {
  double res = 0.0;
  for (int64_t lo = 0; lo < n; lo += kReduceBuffer)
    res += pairwise(f, leaf, lo, n - lo < kReduceBuffer ? n - lo : kReduceBuffer);
  return res;
}

template <class F>
double numpy_sum(const F& f, int64_t n) {
  return numpy_sum(f, [](int64_t, int64_t) {}, n);
}

// the kept rows' scores for the sketch's sums, reused across calls
thread_local std::vector<double> kept_scores;

// One chunk's columns and the states they fold into.
template <class T>
struct Chunk {
  const float* s;
  const T* t;
  const float* w;  // null: every weight 1
  int64_t bins;
  double* hist;
  int64_t sk_bins;
  int64_t* sk_hist;  // null: no sketch
  double* kept;      // the kept rows' scores, where there is a sketch
  uint8_t* keep;
  int64_t kept_rows = 0;

  double err(int64_t i) const {
    double d = static_cast<double>(s[i]) - static_cast<double>(t[i]);
    d *= d;
    return w ? d * static_cast<double>(w[i]) : d;
  }

  // rows [lo, lo + n) into the bins and the sketch, in row order.  Every
  // member is copied to a local first: the stores to `keep` may alias
  // anything, and would otherwise reload each member every row
  void bin(int64_t lo, int64_t n) {
    const float* __restrict s_ = s;
    const T* __restrict t_ = t;
    const float* __restrict w_ = w;
    double* __restrict hist_ = hist;
    int64_t* __restrict sk_ = sk_hist;
    double* __restrict kept_ = kept;
    uint8_t* __restrict keep_ = keep;
    const int64_t nb = bins, skb = sk_bins;
    const float fbins = static_cast<float>(nb);
    const double dskb = static_cast<double>(skb);
    int64_t m = kept_rows;
    for (int64_t i = lo; i < lo + n; ++i) {
      // branch-free: a zero-weight row adds +0.0 to its bin, as np.add.at
      // does, and is counted by no sketch
      const float wf = w_ ? w_[i] : 1.0f;
      const bool kp = wf > 0.0f;
      keep_[i] = kp;
      const int64_t pos = static_cast<float>(t_[i]) >= 0.5f;
      hist_[clip(trunc_i64(s_[i] * fbins), nb - 1) + pos * nb] +=
          kp ? static_cast<double>(wf) : 0.0;
      if (sk_) {
        const double sd = static_cast<double>(s_[i]);
        sk_[clip(trunc_i64(sd * dskb), skb - 1)] += kp;
        kept_[m] = sd;
      }
      m += kp;
    }
    kept_rows = m;
  }
};

template <class T>
int64_t update(const float* s, const T* t, const float* w, int64_t n,
               int64_t bins, double* hist, int64_t* sk_hist, int64_t sk_bins,
               double* sums, int64_t* nonzero, uint8_t* keep) {
  double* kept = nullptr;
  if (sk_hist) {
    if (static_cast<int64_t>(kept_scores.size()) < n) kept_scores.resize(n);
    kept = kept_scores.data();
  }
  Chunk<T> c{s, t, w, bins, hist, sk_bins, sk_hist, kept, keep};
  // one read of the rows: each pairwise leaf bins its rows, then sums
  // their errors while they are in cache; leaves come in row order
  sums[0] = numpy_sum([&c](int64_t i) { return c.err(i); },
                      [&c](int64_t lo, int64_t rows) { c.bin(lo, rows); }, n);
  const int64_t m = c.kept_rows;
  sums[1] = sums[2] = 0.0;
  if (kept) {
    sums[1] = numpy_sum([kept](int64_t i) { return kept[i]; }, m);
    sums[2] = numpy_sum([kept](int64_t i) { return kept[i] * kept[i]; }, m);
  }
  // the nonzero count in a loop of its own, which vectorizes (a NaN
  // weight counts, as numpy's w != 0 counts it)
  int64_t nz = n;
  if (w) {
    nz = 0;
    for (int64_t i = 0; i < n; ++i) nz += w[i] != 0.0f;
  }
  *nonzero = nz;
  return m;
}

}  // namespace

extern "C" {

// Fold one chunk of n rows into `hist` (2 * bins float64, bins a power of
// two) and, where `sk_hist` is not null, its kept rows' scores into the
// sketch's int64 histogram of `sk_bins`.  `t` is float32 (t_u8 == 0) or
// uint8 labels; `w` float32 weights or null (every weight 1).  Writes
// `keep` (n bytes: weight > 0), sums[0] the error sum, sums[1] and sums[2]
// the kept scores' sum and sum of squares (0 without a sketch),
// counts[0] the nonzero-weight rows and counts[1] the kept rows.  Returns
// 0, or -1 for arguments it does not take.
int shifu_evalacc_update(const float* s, const void* t, int t_u8,
                         const float* w, int64_t n, int64_t bins,
                         double* hist, int64_t* sk_hist, int64_t sk_bins,
                         uint8_t* keep, double* sums, int64_t* counts) {
  if (n < 0 || bins <= 0 || (bins & (bins - 1)) != 0 || bins > (1 << 30) ||
      (sk_hist && sk_bins <= 0) || !hist || !sums || !counts ||
      (n > 0 && (!s || !t || !keep)))
    return -1;
  counts[1] = t_u8
      ? update(s, static_cast<const uint8_t*>(t), w, n, bins, hist, sk_hist,
               sk_bins, sums, &counts[0], keep)
      : update(s, static_cast<const float*>(t), w, n, bins, hist, sk_hist,
               sk_bins, sums, &counts[0], keep);
  return 0;
}

}  // extern "C"

#ifdef SHIFU_SELFTEST_MAIN
// Sanitizer self-test entry: built with -fsanitize=address,undefined by
// tests/test_sanitizers.py and run directly.  Drives the edge chunks — no
// rows, one row, every weight zero, no weights, uint8 labels, scores past
// both ends and NaN, a chunk over several pairwise blocks — and checks the
// invariants every chunk has to keep.
#include <cmath>
#include <cstdio>
#include <limits>

namespace {

bool run(const std::vector<float>& s, const std::vector<float>& t,
         const std::vector<float>* w, bool sketch, const char* what) {
  const int64_t n = static_cast<int64_t>(s.size()), bins = 1 << 10,
                skb = 64;
  std::vector<double> hist(2 * bins, 0.0);
  std::vector<int64_t> sk(skb, 0);
  std::vector<uint8_t> keep(n > 0 ? n : 1, 7);
  std::vector<uint8_t> tu(n > 0 ? n : 1);
  for (int64_t i = 0; i < n; ++i) tu[i] = t[i] >= 0.5f;
  double sums[3], sums_u8[3];
  int64_t counts[2], counts_u8[2];
  const float* wp = w ? w->data() : nullptr;
  if (shifu_evalacc_update(s.data(), t.data(), 0, wp, n, bins, hist.data(),
                           sketch ? sk.data() : nullptr, skb, keep.data(),
                           sums, counts) != 0) {
    std::fprintf(stderr, "selftest %s: float labels refused\n", what);
    return false;
  }
  std::vector<double> hist_u8(2 * bins, 0.0);
  if (shifu_evalacc_update(s.data(), tu.data(), 1, wp, n, bins,
                           hist_u8.data(), nullptr, 0, keep.data(), sums_u8,
                           counts_u8) != 0) {
    std::fprintf(stderr, "selftest %s: uint8 labels refused\n", what);
    return false;
  }
  double wsum = 0.0, hsum = 0.0;
  int64_t kept = 0, sk_n = 0;
  for (int64_t i = 0; i < n; ++i) {
    const double wi = wp ? wp[i] : 1.0;
    if (wi > 0) {
      wsum += wi;
      ++kept;
    }
    if (keep[i] != (wi > 0)) {
      std::fprintf(stderr, "selftest %s: keep[%lld]\n", what, (long long)i);
      return false;
    }
  }
  for (int64_t b = 0; b < 2 * bins; ++b) {
    hsum += hist[b];
    if (hist[b] != hist_u8[b]) {
      std::fprintf(stderr, "selftest %s: uint8 bin %lld\n", what,
                   (long long)b);
      return false;
    }
  }
  for (int64_t b = 0; b < skb; ++b) sk_n += sk[b];
  const bool ok = counts[1] == kept && counts_u8[1] == kept &&
                  counts[0] == counts_u8[0] &&
                  std::fabs(hsum - wsum) <= 1e-9 * (1.0 + wsum) &&
                  sk_n == (sketch ? kept : 0) &&
                  (sums[0] == sums_u8[0] ||
                   (std::isnan(sums[0]) && std::isnan(sums_u8[0])));
  if (!ok) std::fprintf(stderr, "selftest %s: totals\n", what);
  return ok;
}

}  // namespace

int main() {
  const float nan = std::numeric_limits<float>::quiet_NaN();
  const float inf = std::numeric_limits<float>::infinity();
  bool ok = run({}, {}, nullptr, true, "n=0");
  std::vector<float> w0;
  ok &= run({}, {}, &w0, true, "n=0 weighted");
  std::vector<float> w1{2.0f};
  ok &= run({0.25f}, {1.0f}, &w1, true, "n=1");
  ok &= run({0.25f}, {0.0f}, nullptr, false, "n=1 unweighted");
  const int64_t n = 1000;
  std::vector<float> s(n), t(n), wz(n, 0.0f), w(n);
  for (int64_t i = 0; i < n; ++i) {
    s[i] = static_cast<float>(i % 97) / 96.0f;
    t[i] = static_cast<float>(i % 3 == 0);
    w[i] = static_cast<float>(i % 5) * 0.5f - 0.5f;  // -0.5 .. 1.5
  }
  s[1] = -1e-3f;
  s[2] = 1.0f;
  s[3] = 7.0f;
  s[4] = nan;
  s[5] = inf;
  s[6] = -inf;
  s[7] = 3e38f;
  s[8] = -3e38f;
  ok &= run(s, t, &wz, true, "every weight zero");
  ok &= run(s, t, &w, true, "mixed weights");
  ok &= run(s, t, nullptr, true, "no weights");
  std::vector<float> s129(s.begin(), s.begin() + 129),
      t129(t.begin(), t.begin() + 129), w129(w.begin(), w.begin() + 129);
  ok &= run(s129, t129, &w129, true, "n=129");
  // arguments the function does not take
  double h[2] = {0, 0}, sums[3];
  int64_t counts[2];
  uint8_t keep[1];
  ok &= shifu_evalacc_update(s.data(), t.data(), 0, nullptr, 1, 3, h, nullptr,
                             0, keep, sums, counts) == -1;
  ok &= shifu_evalacc_update(s.data(), t.data(), 0, nullptr, 1, 1, h,
                             nullptr, 0, keep, sums, counts) == 0;
  if (!ok) return 1;
  std::puts("evalacc selftest ok");
  return 0;
}
#endif  // SHIFU_SELFTEST_MAIN
