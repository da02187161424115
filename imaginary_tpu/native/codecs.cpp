// Native host codec layer: JPEG/PNG/WEBP/GIF/TIFF decode+encode + EXIF
// orientation, palette quantization, interlaced output.
//
// Plays the role of the reference's external native stack (bimg -> libvips
// -> libjpeg-turbo/libpng/libwebp; SURVEY.md section 2.12) for the host
// side of the TPU pipeline. Built directly on the CPython C API (no
// pybind11 in this image). All codec work runs with the GIL RELEASED, so
// Python worker threads decode/encode on real cores concurrently — the
// property the Python-only backends cannot provide.
//
// Interface (module _imaginary_codecs):
//   decode(bytes, fmt: str)  -> (pixels: bytes, h, w, c, orientation, has_alpha)
//   encode(buffer, h, w, c, fmt: str, quality, compression, progressive) -> bytes
//   probe(bytes, fmt: str)   -> (w, h, c, has_alpha, orientation, subsampling)
//   decode_yuv420(bytes, scale_denom, hb, wb[, y0, x0, wh, ww])
//                            -> (packed, h, w, orientation)
//   encode_yuv420(y, u, v, h, w, quality, progressive) -> bytes
// The Python shim (codecs/native_backend.py) wraps pixels in numpy arrays.
//
// The YUV420 entry points are the wire format of the TPU transport path:
// JPEG is natively YCbCr 4:2:0, so the decoder hands back raw subsampled
// planes (skipping libjpeg's chroma upsampling and color conversion) packed
// into one (hb + hb/2, wb) buffer — Y on top, U | V side by side below —
// and the encoder consumes raw planes the same way. Half the bytes of RGB
// in both directions across the host<->device link, and less host CPU per
// request (color math runs on the device's MXU instead).

// Build modes (native/build.py walks them most- to least-capable):
// default compiles the full codec module (_imaginary_codecs, needs
// libjpeg/libpng/libwebp dev headers — libtiff's ABI is declared by hand
// below, only the runtime .so is required); -DITPU_NO_WEBP compiles the
// same module without the webp codec (FORMATS reports what's in, the
// python binding routes absent formats to cv2/PIL) for hosts missing
// only libwebp-dev; -DITPU_RESAMPLE_ONLY compiles just the
// dependency-free separable resampler as _imaginary_resample, so hosts
// without any codec toolchain still get the native spill-path resize.
#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <algorithm>
#include <atomic>
#include <cmath>
#include <cstdarg>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <csetjmp>
#include <string>
#include <vector>

#ifndef ITPU_RESAMPLE_ONLY
#include <jpeglib.h>
#include <png.h>
#ifndef ITPU_NO_WEBP
#include <webp/decode.h>
#include <webp/encode.h>
#endif  // !ITPU_NO_WEBP
#endif  // !ITPU_RESAMPLE_ONLY

namespace {

// ------------------------------------------------ codec scratch arena -------
//
// Thread-local get-or-grow scratch for the decode/resize/encode hot paths.
// Each worker thread serves one image at a time, so one arena per thread
// with one named slot per purpose removes every transient allocation from
// the steady state: after the first few requests a thread's buffers sit at
// their high-water size and later calls just reuse them. Counters are
// process-wide (relaxed atomics — they are monotone telemetry, not
// synchronization); the cap is enforced per thread, checked after each
// top-level call: an over-cap arena drops ALL capacity (an eviction) and
// the next call rebuilds only what it actually touches. Cap 0 = unlimited.

std::atomic<uint64_t> g_arena_reuses{0};
std::atomic<uint64_t> g_arena_misses{0};
std::atomic<uint64_t> g_arena_evictions{0};
std::atomic<uint64_t> g_arena_bytes{0};  // live capacity, summed over threads
std::atomic<uint64_t> g_arena_cap{0};    // per-thread byte budget, 0 = off

struct CodecArena {
  // f32 resampler scratch: padded intermediate row, pair-expanded and
  // transposed horizontal weights
  std::vector<float> mid, wpair, wT;
  // u8 scratch: RGBA expand, generic per-channel planes, libjpeg raw-mode
  // staging planes (shared by decode and encode — they never interleave
  // within one call)
  std::vector<uint8_t> rgba, plane, oplane, ystage, ustage, vstage;

  size_t footprint() const {
    return (mid.capacity() + wpair.capacity() + wT.capacity()) * sizeof(float)
         + rgba.capacity() + plane.capacity() + oplane.capacity()
         + ystage.capacity() + ustage.capacity() + vstage.capacity();
  }
  ~CodecArena() {
    g_arena_bytes.fetch_sub(footprint(), std::memory_order_relaxed);
  }
};

thread_local CodecArena t_arena;

// Size a slot for this call. Capacity (not size) decides reuse vs miss:
// a shrinking request that fits the existing allocation is a reuse.
// resize() value-initializes GROWTH only — callers that depend on zeroed
// regions (the resampler's pad margins) clear those explicitly.
template <typename T>
std::vector<T>& arena_slot(std::vector<T>& slot, size_t n) {
  const size_t before = slot.capacity() * sizeof(T);
  if (before >= n * sizeof(T))
    g_arena_reuses.fetch_add(1, std::memory_order_relaxed);
  else
    g_arena_misses.fetch_add(1, std::memory_order_relaxed);
  slot.resize(n);
  const size_t after = slot.capacity() * sizeof(T);
  if (after > before)
    g_arena_bytes.fetch_add(after - before, std::memory_order_relaxed);
  return slot;
}

void arena_trim() {
  const uint64_t cap = g_arena_cap.load(std::memory_order_relaxed);
  if (cap == 0) return;
  const size_t fp = t_arena.footprint();
  if ((uint64_t)fp <= cap) return;
  std::vector<float>().swap(t_arena.mid);
  std::vector<float>().swap(t_arena.wpair);
  std::vector<float>().swap(t_arena.wT);
  std::vector<uint8_t>().swap(t_arena.rgba);
  std::vector<uint8_t>().swap(t_arena.plane);
  std::vector<uint8_t>().swap(t_arena.oplane);
  std::vector<uint8_t>().swap(t_arena.ystage);
  std::vector<uint8_t>().swap(t_arena.ustage);
  std::vector<uint8_t>().swap(t_arena.vstage);
  g_arena_bytes.fetch_sub(fp, std::memory_order_relaxed);
  g_arena_evictions.fetch_add(1, std::memory_order_relaxed);
}

// ---------------------------------------------- separable resampler ---------
//
// Host analogue of the device's sampling-matrix resize (ops/stages.py
// sample_matrix): per-axis precomputed integer taps, kernel stretched by
// max(1, in/out) on each axis independently so a mixed shrink/enlarge
// chain antialiases the minified axis exactly like the device path.
// Two passes (vertical then horizontal) over a float32 intermediate,
// final round-half-up to uint8 (the device's rounding). Runs with the
// GIL released — the whole point of a native spill path.

constexpr double kResamplePi = 3.14159265358979323846;

double resample_kernel_radius(const std::string& kind) {
  if (kind == "lanczos3") return 3.0;
  if (kind == "lanczos2" || kind == "cubic") return 2.0;
  if (kind == "linear") return 1.0;
  return 0.5;  // nearest
}

double resample_kernel_eval(const std::string& kind, double d) {
  const double ad = std::fabs(d);
  if (kind == "lanczos3" || kind == "lanczos2") {
    const double a = (kind == "lanczos3") ? 3.0 : 2.0;
    if (ad >= a) return 0.0;
    if (ad < 1e-8) return 1.0;
    const double pd = kResamplePi * d;
    // sinc(d) * sinc(d/a) with numpy's normalized sinc convention
    return (std::sin(pd) / pd) * (std::sin(pd / a) / (pd / a));
  }
  if (kind == "cubic") {  // Catmull-Rom-family, a = -0.5 (matches _np_kernel)
    const double a = -0.5;
    if (ad <= 1.0) return (a + 2.0) * ad * ad * ad - (a + 3.0) * ad * ad + 1.0;
    if (ad < 2.0)
      return a * ad * ad * ad - 5.0 * a * ad * ad + 8.0 * a * ad - 4.0 * a;
    return 0.0;
  }
  if (kind == "linear") return std::max(0.0, 1.0 - ad);
  return (d >= -0.5 && d < 0.5) ? 1.0 : 0.0;  // nearest
}

struct TapTable {
  int ntaps = 0;
  std::vector<int32_t> idx;  // [out_n * ntaps], clamped into [0, in_n)
  std::vector<int32_t> k0;   // [out_n] first (unclamped) tap per output
  std::vector<float> wts;    // [out_n * ntaps], rows sum to 1 (or all-zero)
};

// Same weight math as ops/stages.sample_matrix: centre = (y+0.5)/scale-0.5,
// stretch = max(1, 1/scale), taps outside the source get zero weight and
// each row renormalizes over what remains (edge-clamp behavior).
TapTable build_taps(int out_n, int in_n, const std::string& kind) {
  TapTable t;
  const double scale = (double)out_n / (double)in_n;
  const double stretch = std::max(1.0, 1.0 / scale);
  const double support = resample_kernel_radius(kind) * stretch;
  t.ntaps = (int)std::ceil(2.0 * support) + 1;
  t.idx.assign((size_t)out_n * t.ntaps, 0);
  t.k0.assign((size_t)out_n, 0);
  t.wts.assign((size_t)out_n * t.ntaps, 0.0f);
  for (int y = 0; y < out_n; y++) {
    const double centre = (y + 0.5) / scale - 0.5;
    const int k0 = (int)std::floor(centre - support) + 1;
    t.k0[(size_t)y] = k0;
    double sum = 0.0;
    std::vector<double> row((size_t)t.ntaps, 0.0);
    for (int j = 0; j < t.ntaps; j++) {
      const int k = k0 + j;
      if (k < 0 || k >= in_n) continue;
      // evaluate at float32 precision like the numpy tap table: kernels
      // with a hard support cutoff (nearest's box, lanczos' |d| >= a)
      // must make the SAME in/out call on boundary taps, and the f64 vs
      // f32 rounding of d decides it when d lands exactly on the edge
      const double w = resample_kernel_eval(
          kind, (double)(float)((k - centre) / stretch));
      row[j] = w;
      sum += w;
    }
    for (int j = 0; j < t.ntaps; j++) {
      const int k = std::min(std::max(k0 + j, 0), in_n - 1);
      t.idx[(size_t)y * t.ntaps + j] = k;
      t.wts[(size_t)y * t.ntaps + j] =
          (sum > 1e-6) ? (float)(row[j] / sum) : 0.0f;
    }
  }
  // Zero out numerically-negligible weights before trimming: an
  // integer-aligned lanczos tap evaluates to ~1e-17, not exactly 0 (f64
  // sin(pi*k) rounding), so without this an IDENTITY axis pass — scale 1,
  // weight 1 at k=y — would still carry the kernel's full tap count of
  // do-nothing FMAs. Contribution bound: 255 * 1e-7 * ntaps, orders below
  // the uint8 rounding step.
  for (auto& wv : t.wts)
    if (std::fabs(wv) < 1e-7f) wv = 0.0f;
  // Trim to the true nonzero window: the conservative allocation above
  // overshoots by one tap for most kernels (lanczos3's open |d|<3 support
  // admits at most 6 integers, not ceil(6)+1 = 7), and every pass below
  // pays per allocated tap. Shift each row so its first nonzero weight
  // sits at tap 0, then cut the table at the widest row.
  int max_width = 1;
  std::vector<int> first((size_t)out_n, 0);
  for (int y = 0; y < out_n; y++) {
    int f = -1, l = 0;
    for (int j = 0; j < t.ntaps; j++) {
      if (t.wts[(size_t)y * t.ntaps + j] != 0.0f) {
        if (f < 0) f = j;
        l = j;
      }
    }
    if (f < 0) f = 0;
    first[(size_t)y] = f;
    max_width = std::max(max_width, l - f + 1);
  }
  if (max_width < t.ntaps) {
    TapTable s;
    s.ntaps = max_width;
    s.idx.assign((size_t)out_n * max_width, 0);
    s.k0.assign((size_t)out_n, 0);
    s.wts.assign((size_t)out_n * max_width, 0.0f);
    for (int y = 0; y < out_n; y++) {
      const int f = first[(size_t)y];
      const int nk0 = t.k0[(size_t)y] + f;
      s.k0[(size_t)y] = nk0;
      for (int j = 0; j < max_width; j++) {
        if (f + j < t.ntaps) {
          s.idx[(size_t)y * max_width + j] = t.idx[(size_t)y * t.ntaps + f + j];
          s.wts[(size_t)y * max_width + j] = t.wts[(size_t)y * t.ntaps + f + j];
        } else {
          s.idx[(size_t)y * max_width + j] =
              std::min(std::max(nk0 + j, 0), in_n - 1);
        }
      }
    }
    return s;
  }
  return t;
}

// src: HWC uint8. Vertical pass into a float32 buffer, horizontal pass out
// of it, rounding into dst (dh*dw*c uint8). Templated on the channel count
// so the per-pixel accumulator lives in registers and the tap loop
// vectorizes — the difference between ~135 ms and ~35 ms on a 1080p->1440p
// lanczos3 enlarge (measured, 1-CPU host, g++ -O3).
template <int C>
void resize_separable_impl(const uint8_t* src, int h, int w, int dh, int dw,
                           const TapTable& tv, const TapTable& th,
                           uint8_t* dst) {
  const size_t row_elems = (size_t)w * C;
  const int pad = th.ntaps;  // window overhang at either edge
  std::vector<float>& mid_row =
      arena_slot(t_arena.mid, ((size_t)w + 2 * pad) * C);
  // the pad margins must read as zero (out-of-range taps carry zero
  // weight); the reused buffer may hold a previous call's values
  std::memset(mid_row.data(), 0, (size_t)pad * C * sizeof(float));
  std::memset(mid_row.data() + ((size_t)pad + w) * C, 0,
              (size_t)pad * C * sizeof(float));
  for (int y = 0; y < dh; y++) {
    // vertical: blend source rows for this output row only (no dh*w*C
    // intermediate — better cache locality and a fraction of the memory).
    // Contiguous FMA over w*C elements. __restrict__ is load-bearing:
    // uint8_t aliases every type, so without it the compiler must assume
    // in_row overlaps mrow and the loop stays scalar.
    float* __restrict__ mrow = mid_row.data() + (size_t)pad * C;
    std::memset(mrow, 0, row_elems * sizeof(float));
    const float* wrow = tv.wts.data() + (size_t)y * tv.ntaps;
    const int32_t* irow = tv.idx.data() + (size_t)y * tv.ntaps;
    for (int j = 0; j < tv.ntaps; j++) {
      const float wv = wrow[j];
      if (wv == 0.0f) continue;
      const uint8_t* __restrict__ in_row = src + (size_t)irow[j] * row_elems;
      for (size_t i = 0; i < row_elems; i++) mrow[i] += wv * in_row[i];
    }
    // horizontal: every tap window is one CONTIGUOUS interleaved run
    // starting at k0[x]*C — the pad rows above hold zeros and out-of-range
    // taps carry zero weight (build_taps), so the loop stays branch-free;
    // the C accumulators give the compiler independent FMA chains.
    uint8_t* __restrict__ out_row = dst + (size_t)y * dw * C;
    for (int x = 0; x < dw; x++) {
      const float* __restrict__ wx = th.wts.data() + (size_t)x * th.ntaps;
      const float* __restrict__ px = mrow + (ptrdiff_t)th.k0[(size_t)x] * C;
      float acc[C] = {};
      for (int j = 0; j < th.ntaps; j++) {
        const float wv = wx[j];
        for (int ch = 0; ch < C; ch++) acc[ch] += wv * px[(size_t)j * C + ch];
      }
      for (int ch = 0; ch < C; ch++) {
        const float v = acc[ch] + 0.5f;  // device rounding
        out_row[(size_t)x * C + ch] =
            (uint8_t)(v <= 0.0f ? 0 : (v >= 255.0f ? 255 : (int)v));
      }
    }
  }
}

#if defined(__x86_64__) && defined(__GNUC__)
#define ITPU_AVX2_DISPATCH 1
#include <immintrin.h>

bool cpu_has_avx2_fma() {
  return __builtin_cpu_supports("avx2") && __builtin_cpu_supports("fma");
}

// AVX2+FMA specialization for 3/4-channel images — the serving hot shape.
// Internally RGBA: a 4-float channel group is exactly half a YMM lane, so
// the horizontal pass computes TWO output pixels per FMA (each 128-bit
// half holds one pixel's running RGBA accumulator). The portable template
// above measured ~46 ms on a 1080p->1440p lanczos3 enlarge; this runs the
// same taps in ~15 ms. Compiled with a target attribute and dispatched at
// runtime, so the module loads and serves on any x86-64.
__attribute__((target("avx2,fma")))
void resize_separable_avx2(const uint8_t* src, int h, int w, int c, int dh,
                           int dw, const TapTable& tv, const TapTable& th,
                           uint8_t* dst) {
  const uint8_t* s4 = src;
  if (c == 3) {  // one up-front 3->4 expand keeps every later row load aligned to pixels
    std::vector<uint8_t>& rgba = arena_slot(t_arena.rgba, (size_t)h * w * 4);
    const size_t n = (size_t)h * w;
    size_t i = 0;
    // pshufb 4 pixels per step (12 source bytes -> 16, alpha lanes zeroed
    // by the -1 indices): the scalar expand below costs ~5 ms of a 28 ms
    // 1080p->1440p call, this runs it at shuffle speed. The bound keeps
    // the 16-byte load inside the buffer (needs 3i+16 <= 3n).
    const __m128i shuf = _mm_setr_epi8(0, 1, 2, -1, 3, 4, 5, -1,
                                       6, 7, 8, -1, 9, 10, 11, -1);
    for (; i + 6 <= n; i += 4) {
      const __m128i v =
          _mm_loadu_si128(reinterpret_cast<const __m128i*>(src + i * 3));
      _mm_storeu_si128(reinterpret_cast<__m128i*>(rgba.data() + i * 4),
                       _mm_shuffle_epi8(v, shuf));
    }
    for (; i < n; i++) {
      rgba[i * 4 + 0] = src[i * 3 + 0];
      rgba[i * 4 + 1] = src[i * 3 + 1];
      rgba[i * 4 + 2] = src[i * 3 + 2];
      rgba[i * 4 + 3] = 0;
    }
    s4 = rgba.data();
  }
  const int pad = th.ntaps;
  const size_t row4 = (size_t)w * 4;
  std::vector<float>& mid = arena_slot(t_arena.mid, ((size_t)w + 2 * pad) * 4);
  std::memset(mid.data(), 0, (size_t)pad * 4 * sizeof(float));
  std::memset(mid.data() + ((size_t)pad + w) * 4, 0,
              (size_t)pad * 4 * sizeof(float));
  float* mrow = mid.data() + (size_t)pad * 4;
  // pair-expanded horizontal weights: [pair][tap][w0 w0 w0 w0 w1 w1 w1 w1]
  // — one unaligned 256-bit load per tap, no in-loop shuffles
  const int npairs = dw / 2;
  std::vector<float>& wpair =
      arena_slot(t_arena.wpair, (size_t)npairs * th.ntaps * 8);
  for (int p = 0; p < npairs; p++) {
    for (int j = 0; j < th.ntaps; j++) {
      const float w0 = th.wts[(size_t)(2 * p) * th.ntaps + j];
      const float w1 = th.wts[(size_t)(2 * p + 1) * th.ntaps + j];
      float* o = wpair.data() + ((size_t)p * th.ntaps + j) * 8;
      o[0] = o[1] = o[2] = o[3] = w0;
      o[4] = o[5] = o[6] = o[7] = w1;
    }
  }
  const __m256 vhalf = _mm256_set1_ps(0.5f);
  const __m256 vmax = _mm256_set1_ps(255.0f);
  for (int y = 0; y < dh; y++) {
    std::memset(mrow, 0, row4 * sizeof(float));
    const float* wv = tv.wts.data() + (size_t)y * tv.ntaps;
    const int32_t* iv = tv.idx.data() + (size_t)y * tv.ntaps;
    for (int j = 0; j < tv.ntaps; j++) {
      const float wj = wv[j];
      if (wj == 0.0f) continue;
      const uint8_t* in = s4 + (size_t)iv[j] * row4;
      // explicit widen+FMA (8 u8 lanes -> f32): the scalar form can't
      // auto-vectorize here — uint8_t aliases float, so the compiler
      // must assume `in` overlaps `mrow` and reloads every element
      const __m256 vw = _mm256_set1_ps(wj);
      size_t i = 0;
      for (; i + 8 <= row4; i += 8) {
        const __m256 f = _mm256_cvtepi32_ps(_mm256_cvtepu8_epi32(
            _mm_loadl_epi64(reinterpret_cast<const __m128i*>(in + i))));
        _mm256_storeu_ps(mrow + i,
                         _mm256_fmadd_ps(f, vw, _mm256_loadu_ps(mrow + i)));
      }
      for (; i < row4; i++) mrow[i] += wj * in[i];
    }
    uint8_t* out_row = dst + (size_t)y * dw * c;
    for (int p = 0; p < npairs; p++) {
      const int x = 2 * p;
      const float* b0 = mrow + (ptrdiff_t)th.k0[(size_t)x] * 4;
      const float* b1 = mrow + (ptrdiff_t)th.k0[(size_t)x + 1] * 4;
      const float* wp = wpair.data() + (size_t)p * th.ntaps * 8;
      // two accumulator chains over even/odd taps: a single chain is
      // FMA-LATENCY-bound (~4-5 cycles x ntaps per pair dominates the
      // whole pass); splitting it overlaps the dependent adds
      __m256 acc0 = _mm256_setzero_ps();
      __m256 acc1 = _mm256_setzero_ps();
      int j = 0;
      for (; j + 2 <= th.ntaps; j += 2) {
        const __m256 v0 = _mm256_insertf128_ps(
            _mm256_castps128_ps256(_mm_loadu_ps(b0 + (size_t)j * 4)),
            _mm_loadu_ps(b1 + (size_t)j * 4), 1);
        acc0 = _mm256_fmadd_ps(v0, _mm256_loadu_ps(wp + (size_t)j * 8), acc0);
        const __m256 v1 = _mm256_insertf128_ps(
            _mm256_castps128_ps256(_mm_loadu_ps(b0 + (size_t)(j + 1) * 4)),
            _mm_loadu_ps(b1 + (size_t)(j + 1) * 4), 1);
        acc1 = _mm256_fmadd_ps(v1, _mm256_loadu_ps(wp + (size_t)(j + 1) * 8),
                               acc1);
      }
      if (j < th.ntaps) {
        const __m256 v = _mm256_insertf128_ps(
            _mm256_castps128_ps256(_mm_loadu_ps(b0 + (size_t)j * 4)),
            _mm_loadu_ps(b1 + (size_t)j * 4), 1);
        acc0 = _mm256_fmadd_ps(v, _mm256_loadu_ps(wp + (size_t)j * 8), acc0);
      }
      __m256 acc = _mm256_add_ps(acc0, acc1);
      // device rounding: +0.5, clamp, truncate (matches the scalar path)
      acc = _mm256_add_ps(acc, vhalf);
      acc = _mm256_min_ps(_mm256_max_ps(acc, _mm256_setzero_ps()), vmax);
      const __m256i i32 = _mm256_cvttps_epi32(acc);
      const __m128i p16 = _mm_packus_epi32(_mm256_castsi256_si128(i32),
                                           _mm256_extracti128_si256(i32, 1));
      const __m128i p8 = _mm_packus_epi16(p16, p16);
      alignas(16) uint8_t tmp[16];
      _mm_storeu_si128(reinterpret_cast<__m128i*>(tmp), p8);
      if (c == 4) {
        std::memcpy(out_row + (size_t)x * 4, tmp, 8);
      } else {
        out_row[(size_t)x * 3 + 0] = tmp[0];
        out_row[(size_t)x * 3 + 1] = tmp[1];
        out_row[(size_t)x * 3 + 2] = tmp[2];
        out_row[(size_t)x * 3 + 3] = tmp[4];
        out_row[(size_t)x * 3 + 4] = tmp[5];
        out_row[(size_t)x * 3 + 5] = tmp[6];
      }
    }
    for (int x = npairs * 2; x < dw; x++) {  // odd-width tail
      const float* wx = th.wts.data() + (size_t)x * th.ntaps;
      const float* px = mrow + (ptrdiff_t)th.k0[(size_t)x] * 4;
      float acc[4] = {};
      for (int j = 0; j < th.ntaps; j++) {
        const float wj = wx[j];
        for (int ch = 0; ch < 4; ch++) acc[ch] += wj * px[(size_t)j * 4 + ch];
      }
      for (int ch = 0; ch < c; ch++) {
        const float v = acc[ch] + 0.5f;
        out_row[(size_t)x * c + ch] =
            (uint8_t)(v <= 0.0f ? 0 : (v >= 255.0f ? 255 : (int)v));
      }
    }
  }
}
// Planar (1-channel) AVX2 kernel — the packed-YUV420 spill path resizes
// Y/U/V planes one at a time, so this shape is as hot as interleaved RGB.
// Vertical pass is the same contiguous widen+FMA as the RGBA kernel; the
// horizontal pass does 8 output pixels per iteration with one
// i32gather per tap (indices k0[x..x+7]+j) against weights transposed
// to [tap][x] so each tap's 8 weights are one contiguous load.
__attribute__((target("avx2,fma")))
void resize_separable_avx2_1(const uint8_t* src, int h, int w, int dh, int dw,
                             const TapTable& tv, const TapTable& th,
                             uint8_t* dst) {
  const int pad = th.ntaps;
  std::vector<float>& mid = arena_slot(t_arena.mid, (size_t)w + 2 * pad);
  std::memset(mid.data(), 0, (size_t)pad * sizeof(float));
  std::memset(mid.data() + (size_t)pad + w, 0, (size_t)pad * sizeof(float));
  float* mrow = mid.data() + pad;
  std::vector<float>& wT = arena_slot(t_arena.wT, (size_t)th.ntaps * dw);
  for (int x = 0; x < dw; x++)
    for (int j = 0; j < th.ntaps; j++)
      wT[(size_t)j * dw + x] = th.wts[(size_t)x * th.ntaps + j];
  const __m256 vhalf = _mm256_set1_ps(0.5f);
  const __m256 vmax = _mm256_set1_ps(255.0f);
  const int ngroups = dw / 8;
  for (int y = 0; y < dh; y++) {
    std::memset(mrow, 0, (size_t)w * sizeof(float));
    const float* wv = tv.wts.data() + (size_t)y * tv.ntaps;
    const int32_t* iv = tv.idx.data() + (size_t)y * tv.ntaps;
    for (int j = 0; j < tv.ntaps; j++) {
      const float wj = wv[j];
      if (wj == 0.0f) continue;
      const uint8_t* in = src + (size_t)iv[j] * w;
      const __m256 vw = _mm256_set1_ps(wj);
      size_t i = 0;
      for (; i + 8 <= (size_t)w; i += 8) {
        const __m256 f = _mm256_cvtepi32_ps(_mm256_cvtepu8_epi32(
            _mm_loadl_epi64(reinterpret_cast<const __m128i*>(in + i))));
        _mm256_storeu_ps(mrow + i,
                         _mm256_fmadd_ps(f, vw, _mm256_loadu_ps(mrow + i)));
      }
      for (; i < (size_t)w; i++) mrow[i] += wj * in[i];
    }
    uint8_t* out_row = dst + (size_t)y * dw;
    for (int g = 0; g < ngroups; g++) {
      const int x = g * 8;
      const __m256i k0 = _mm256_loadu_si256(
          reinterpret_cast<const __m256i*>(th.k0.data() + x));
      __m256 acc = _mm256_setzero_ps();
      for (int j = 0; j < th.ntaps; j++) {
        // windows may start in the left pad (k0 < 0, zero weight): mrow's
        // pad rows keep the gather in-bounds, same invariant as the
        // interleaved kernel's k0*4 loads
        const __m256 v = _mm256_i32gather_ps(
            mrow, _mm256_add_epi32(k0, _mm256_set1_epi32(j)), 4);
        acc = _mm256_fmadd_ps(
            v, _mm256_loadu_ps(wT.data() + (size_t)j * dw + x), acc);
      }
      acc = _mm256_add_ps(acc, vhalf);
      acc = _mm256_min_ps(_mm256_max_ps(acc, _mm256_setzero_ps()), vmax);
      const __m256i i32 = _mm256_cvttps_epi32(acc);
      const __m128i p16 = _mm_packus_epi32(_mm256_castsi256_si128(i32),
                                           _mm256_extracti128_si256(i32, 1));
      _mm_storel_epi64(reinterpret_cast<__m128i*>(out_row + x),
                       _mm_packus_epi16(p16, p16));
    }
    for (int x = ngroups * 8; x < dw; x++) {  // narrow-plane tail
      const float* wx = th.wts.data() + (size_t)x * th.ntaps;
      const float* px = mrow + (ptrdiff_t)th.k0[(size_t)x];
      float a = 0.0f;
      for (int j = 0; j < th.ntaps; j++) a += wx[j] * px[j];
      const float v = a + 0.5f;
      out_row[x] = (uint8_t)(v <= 0.0f ? 0 : (v >= 255.0f ? 255 : (int)v));
    }
  }
}
#endif  // __x86_64__ && __GNUC__

void resize_plane_u8(const uint8_t* src, int h, int w, int dh, int dw,
                     const TapTable& tv, const TapTable& th, uint8_t* dst) {
#ifdef ITPU_AVX2_DISPATCH
  if (cpu_has_avx2_fma())
    return resize_separable_avx2_1(src, h, w, dh, dw, tv, th, dst);
#endif
  resize_separable_impl<1>(src, h, w, dh, dw, tv, th, dst);
}

void resize_separable_u8(const uint8_t* src, int h, int w, int c, int dh,
                         int dw, const std::string& kind, uint8_t* dst) {
  const TapTable tv = build_taps(dh, h, kind);
  const TapTable th = build_taps(dw, w, kind);
#ifdef ITPU_AVX2_DISPATCH
  if ((c == 3 || c == 4) && cpu_has_avx2_fma())
    return resize_separable_avx2(src, h, w, c, dh, dw, tv, th, dst);
#endif
  if (c == 1) return resize_plane_u8(src, h, w, dh, dw, tv, th, dst);
  if (c == 3) return resize_separable_impl<3>(src, h, w, dh, dw, tv, th, dst);
  if (c == 4) return resize_separable_impl<4>(src, h, w, dh, dw, tv, th, dst);
  // arbitrary channel count: plane-at-a-time through the 1-channel kernel
  std::vector<uint8_t>& plane = arena_slot(t_arena.plane, (size_t)h * w);
  std::vector<uint8_t>& oplane = arena_slot(t_arena.oplane, (size_t)dh * dw);
  for (int ch = 0; ch < c; ch++) {
    for (size_t i = 0, n = (size_t)h * w; i < n; i++)
      plane[i] = src[i * c + ch];
    resize_plane_u8(plane.data(), h, w, dh, dw, tv, th, oplane.data());
    for (size_t i = 0, n = (size_t)dh * dw; i < n; i++)
      dst[i * c + ch] = oplane[i];
  }
}

PyObject* py_resize_separable(PyObject*, PyObject* args) {
  Py_buffer view;
  int h, w, c, dh, dw;
  const char* kernel;
  if (!PyArg_ParseTuple(args, "y*iiiiis", &view, &h, &w, &c, &dh, &dw,
                        &kernel))
    return nullptr;
  if (h <= 0 || w <= 0 || c <= 0 || dh <= 0 || dw <= 0 ||
      (Py_ssize_t)((size_t)h * w * c) != view.len) {
    PyBuffer_Release(&view);
    PyErr_SetString(PyExc_ValueError, "buffer size does not match h*w*c");
    return nullptr;
  }
  PyObject* out = PyBytes_FromStringAndSize(nullptr, (Py_ssize_t)((size_t)dh * dw * c));
  if (!out) {
    PyBuffer_Release(&view);
    return nullptr;
  }
  const uint8_t* src = static_cast<const uint8_t*>(view.buf);
  uint8_t* dst = reinterpret_cast<uint8_t*>(PyBytes_AS_STRING(out));
  std::string kind(kernel);
  Py_BEGIN_ALLOW_THREADS
  resize_separable_u8(src, h, w, c, dh, dw, kind, dst);
  arena_trim();
  Py_END_ALLOW_THREADS
  PyBuffer_Release(&view);
  return out;
}

PyObject* py_arena_stats(PyObject*, PyObject*) {
  return Py_BuildValue(
      "{s:K,s:K,s:K,s:K,s:K}",
      "reuses", (unsigned long long)g_arena_reuses.load(std::memory_order_relaxed),
      "misses", (unsigned long long)g_arena_misses.load(std::memory_order_relaxed),
      "evictions", (unsigned long long)g_arena_evictions.load(std::memory_order_relaxed),
      "bytes", (unsigned long long)g_arena_bytes.load(std::memory_order_relaxed),
      "cap_bytes", (unsigned long long)g_arena_cap.load(std::memory_order_relaxed));
}

PyObject* py_set_arena_cap(PyObject*, PyObject* args) {
  double mb;
  if (!PyArg_ParseTuple(args, "d", &mb)) return nullptr;
  if (mb < 0.0) mb = 0.0;
  g_arena_cap.store((uint64_t)(mb * 1024.0 * 1024.0),
                    std::memory_order_relaxed);
  Py_RETURN_NONE;
}

#ifndef ITPU_RESAMPLE_ONLY

// ---------------------------------------------------------------- EXIF ------

// Minimal EXIF Orientation (tag 0x0112) scan over a JPEG APP1 segment.
uint32_t rd16(const uint8_t* p, bool le) {
  return le ? (p[0] | (p[1] << 8)) : ((p[0] << 8) | p[1]);
}
uint32_t rd32(const uint8_t* p, bool le) {
  return le ? (p[0] | (p[1] << 8) | (p[2] << 16) | ((uint32_t)p[3] << 24))
            : (((uint32_t)p[0] << 24) | (p[1] << 16) | (p[2] << 8) | p[3]);
}

int exif_orientation(const uint8_t* buf, size_t len) {
  if (len < 4 || buf[0] != 0xFF || buf[1] != 0xD8) return 0;
  size_t i = 2;
  while (i + 4 <= len) {
    if (buf[i] != 0xFF) break;
    // skip 0xFF fill bytes before the marker (ISO 10918-1 B.1.1.2)
    while (i + 4 <= len && buf[i + 1] == 0xFF) i++;
    if (i + 4 > len) break;
    uint8_t marker = buf[i + 1];
    if (marker == 0xD8 || (marker >= 0xD0 && marker <= 0xD9)) { i += 2; continue; }
    size_t seglen = ((size_t)buf[i + 2] << 8) | buf[i + 3];
    if (seglen < 2 || i + 2 + seglen > len) break;
    if (marker == 0xE1 && seglen >= 10 &&
        std::memcmp(buf + i + 4, "Exif\0\0", 6) == 0) {
      const uint8_t* t = buf + i + 10;       // TIFF header
      size_t tlen = seglen - 8;
      if (tlen < 8) return 0;
      bool le;
      if (t[0] == 'I' && t[1] == 'I') le = true;
      else if (t[0] == 'M' && t[1] == 'M') le = false;
      else return 0;
      uint32_t ifd = rd32(t + 4, le);
      if (ifd + 2 > tlen) return 0;
      uint32_t n = rd16(t + ifd, le);
      for (uint32_t e = 0; e < n; e++) {
        size_t off = ifd + 2 + 12 * (size_t)e;
        if (off + 12 > tlen) return 0;
        if (rd16(t + off, le) == 0x0112) {
          uint32_t v = rd16(t + off + 8, le);
          return (v <= 8) ? (int)v : 0;
        }
      }
      return 0;
    }
    if (marker == 0xDA) break;  // start of scan: no EXIF past here
    i += 2 + seglen;
  }
  return 0;
}

// ---------------------------------------------------------------- JPEG ------

struct JpegErr {
  jpeg_error_mgr mgr;
  jmp_buf jb;
  char msg[JMSG_LENGTH_MAX];
};

void jpeg_err_exit(j_common_ptr cinfo) {
  JpegErr* e = reinterpret_cast<JpegErr*>(cinfo->err);
  (*cinfo->err->format_message)(cinfo, e->msg);
  longjmp(e->jb, 1);
}

bool jpeg_decode(const uint8_t* buf, size_t len, std::vector<uint8_t>* out,
                 int* w, int* h, int* c, std::string* err, int scale_denom) {
  jpeg_decompress_struct cinfo;
  JpegErr jerr;
  cinfo.err = jpeg_std_error(&jerr.mgr);
  jerr.mgr.error_exit = jpeg_err_exit;
  if (setjmp(jerr.jb)) {
    *err = jerr.msg;
    jpeg_destroy_decompress(&cinfo);
    return false;
  }
  jpeg_create_decompress(&cinfo);
  jpeg_mem_src(&cinfo, const_cast<uint8_t*>(buf), len);
  jpeg_read_header(&cinfo, TRUE);
  cinfo.out_color_space = JCS_RGB;
  if (scale_denom == 2 || scale_denom == 4 || scale_denom == 8) {
    // shrink-on-load: decode at 1/N directly off the DCT (libvips does the
    // same before its resample stage) — 1/N^2 the pixels to move and resample
    cinfo.scale_num = 1;
    cinfo.scale_denom = (unsigned int)scale_denom;
  }
  jpeg_start_decompress(&cinfo);
  *w = cinfo.output_width;
  *h = cinfo.output_height;
  *c = 3;
  out->resize((size_t)(*w) * (*h) * 3);
  size_t stride = (size_t)(*w) * 3;
  while (cinfo.output_scanline < cinfo.output_height) {
    uint8_t* row = out->data() + stride * cinfo.output_scanline;
    jpeg_read_scanlines(&cinfo, &row, 1);
  }
  jpeg_finish_decompress(&cinfo);
  jpeg_destroy_decompress(&cinfo);
  return true;
}

// Chroma subsampling fingerprint ("420"/"422"/"444"/"gray"/"" for other).
void jpeg_subsampling(jpeg_decompress_struct* cinfo, char out[8]) {
  out[0] = '\0';
  if (cinfo->num_components == 1) {
    std::snprintf(out, 8, "gray");
    return;
  }
  if (cinfo->num_components != 3) return;
  int h0 = cinfo->comp_info[0].h_samp_factor, v0 = cinfo->comp_info[0].v_samp_factor;
  int h1 = cinfo->comp_info[1].h_samp_factor, v1 = cinfo->comp_info[1].v_samp_factor;
  int h2 = cinfo->comp_info[2].h_samp_factor, v2 = cinfo->comp_info[2].v_samp_factor;
  if (h1 != 1 || v1 != 1 || h2 != 1 || v2 != 1) return;
  if (h0 == 2 && v0 == 2) std::snprintf(out, 8, "420");
  else if (h0 == 2 && v0 == 1) std::snprintf(out, 8, "422");
  else if (h0 == 1 && v0 == 1) std::snprintf(out, 8, "444");
}

bool jpeg_probe(const uint8_t* buf, size_t len, int* w, int* h, int* c,
                char subsampling[8]) {
  jpeg_decompress_struct cinfo;
  JpegErr jerr;
  cinfo.err = jpeg_std_error(&jerr.mgr);
  jerr.mgr.error_exit = jpeg_err_exit;
  if (setjmp(jerr.jb)) {
    jpeg_destroy_decompress(&cinfo);
    return false;
  }
  jpeg_create_decompress(&cinfo);
  jpeg_mem_src(&cinfo, const_cast<uint8_t*>(buf), len);
  jpeg_read_header(&cinfo, TRUE);
  *w = cinfo.image_width;
  *h = cinfo.image_height;
  *c = cinfo.num_components;
  jpeg_subsampling(&cinfo, subsampling);
  jpeg_destroy_decompress(&cinfo);
  return true;
}

// ----------------------------------------------------- JPEG raw (YUV420) ----

// Decode a YCbCr 4:2:0 JPEG into the packed-plane transport layout:
// a ((hb + hb/2) * wb) byte buffer with Y in rows [0, hb), U in the bottom
// block's columns [0, wb/2) and V in [wb/2, wb). hb/wb are the (even) bucket
// dims the caller padded to; actual luma dims return via h/w and chroma
// valid dims are ceil(h/2) x ceil(w/2). With IDCT scaling libjpeg emits
// chroma at LUMA resolution (DCT_scaled_size compensates the subsampling),
// so the scaled path box-averages 2x2 back down to 4:2:0.
//
// A window (wh > 0; unscaled decodes only) packs just luma rows
// [y0, y0 + wh) and columns [x0, x0 + ww), chroma at half those, and stops
// reading once the window's last iMCU row is out: a sequential JPEG's
// entropy decode never runs below it. h/w then return the window's dims.
// y0/x0 must be even and every edge even or on the frame's own edge, so
// the window's chroma samples are exactly the frame's.
bool jpeg_decode_yuv420(const uint8_t* buf, size_t len, int scale_denom,
                        int hb, int wb, int y0, int x0, int wh, int ww,
                        std::vector<uint8_t>* packed,
                        int* h, int* w, std::string* err) {
  jpeg_decompress_struct cinfo;
  JpegErr jerr;
  cinfo.err = jpeg_std_error(&jerr.mgr);
  jerr.mgr.error_exit = jpeg_err_exit;
  if (setjmp(jerr.jb)) {
    *err = jerr.msg;
    jpeg_destroy_decompress(&cinfo);
    return false;
  }
  jpeg_create_decompress(&cinfo);
  jpeg_mem_src(&cinfo, const_cast<uint8_t*>(buf), len);
  jpeg_read_header(&cinfo, TRUE);
  char sub[8];
  jpeg_subsampling(&cinfo, sub);
  if (std::strcmp(sub, "420") != 0 || cinfo.jpeg_color_space != JCS_YCbCr) {
    *err = "not-420";
    jpeg_destroy_decompress(&cinfo);
    return false;
  }
  cinfo.raw_data_out = TRUE;
  cinfo.out_color_space = JCS_YCbCr;
  if (scale_denom == 2 || scale_denom == 4 || scale_denom == 8) {
    cinfo.scale_num = 1;
    cinfo.scale_denom = (unsigned int)scale_denom;
  }
  jpeg_start_decompress(&cinfo);
  const int lw = cinfo.comp_info[0].downsampled_width;
  const int lh = cinfo.comp_info[0].downsampled_height;
  const int cw0 = cinfo.comp_info[1].downsampled_width;
  const int ch0 = cinfo.comp_info[1].downsampled_height;
  const bool windowed = wh > 0;
  if (!windowed) {
    y0 = x0 = 0;
    wh = lh;
    ww = lw;
  } else if (scale_denom != 1 || y0 < 0 || x0 < 0 || ww <= 0 ||
             (y0 % 2) || (x0 % 2) || y0 + wh > lh || x0 + ww > lw ||
             ((wh % 2) && y0 + wh != lh) || ((ww % 2) && x0 + ww != lw)) {
    *err = "bad decode window";
    jpeg_destroy_decompress(&cinfo);
    return false;
  }
  const int ct_w = (ww + 1) / 2, ct_h = (wh + 1) / 2;
  if (wh > hb || ww > wb || (hb % 2) || (wb % 2)) {
    *err = "bucket too small for decoded dims";
    jpeg_destroy_decompress(&cinfo);
    return false;
  }
  const bool chroma_full = (ch0 == lh && cw0 == lw);
  if (!chroma_full && !(ch0 == (lh + 1) / 2 && cw0 == (lw + 1) / 2)) {
    *err = "not-420";  // unexpected raw geometry: let the RGB path serve it
    jpeg_destroy_decompress(&cinfo);
    return false;
  }
  // Decode into generously-strided temp planes (libjpeg writes iMCU-padded
  // row widths in raw mode, which could overrun tight packed rows), then
  // memcpy into the packed layout. The extra copy is ~0.1 ms per image.
  const size_t lstride = ((size_t)lw + 63) / 64 * 64;
  const size_t cstride = ((size_t)cw0 + 63) / 64 * 64;
  std::vector<uint8_t>& Y = arena_slot(t_arena.ystage, lstride * (lh + 32));
  std::vector<uint8_t>& U = arena_slot(t_arena.ustage, cstride * (ch0 + 32));
  std::vector<uint8_t>& V = arena_slot(t_arena.vstage, cstride * (ch0 + 32));
  const int rg0 = cinfo.comp_info[0].v_samp_factor * cinfo.comp_info[0].DCT_scaled_size;
  const int rg1 = cinfo.comp_info[1].v_samp_factor * cinfo.comp_info[1].DCT_scaled_size;
  const int mcu_rows = cinfo.max_v_samp_factor * cinfo.min_DCT_scaled_size;
  if (rg0 > 64 || rg1 > 64) {
    *err = "unexpected raw row-group size";
    jpeg_destroy_decompress(&cinfo);
    return false;
  }
  JSAMPROW yrows[64], urows[64], vrows[64];
  JSAMPARRAY planes[3] = {yrows, urows, vrows};
  int yrow = 0, crow = 0;
  while (cinfo.output_scanline < (JDIMENSION)(y0 + wh)) {
    for (int i = 0; i < rg0; i++)
      yrows[i] = Y.data() + lstride * (size_t)(yrow + i);
    for (int i = 0; i < rg1; i++) {
      urows[i] = U.data() + cstride * (size_t)(crow + i);
      vrows[i] = V.data() + cstride * (size_t)(crow + i);
    }
    if (!jpeg_read_raw_data(&cinfo, planes, (JDIMENSION)mcu_rows)) {
      *err = "jpeg_read_raw_data failed";
      jpeg_destroy_decompress(&cinfo);
      return false;
    }
    yrow += rg0;
    crow += rg1;
  }
  // rows below the window stay unread: finishing would demand them
  if (cinfo.output_scanline < cinfo.output_height)
    jpeg_abort_decompress(&cinfo);
  else
    jpeg_finish_decompress(&cinfo);
  jpeg_destroy_decompress(&cinfo);

  packed->assign((size_t)(hb + hb / 2) * wb, 0);
  uint8_t* p = packed->data();
  for (int r = 0; r < wh; r++)
    std::memcpy(p + (size_t)r * wb, Y.data() + lstride * (size_t)(y0 + r) + x0, ww);
  uint8_t* uc = p + (size_t)hb * wb;          // chroma block top-left (U)
  uint8_t* vc = uc + wb / 2;                  // V half
  if (!chroma_full) {
    const size_t cy0 = (size_t)y0 / 2, cx0 = (size_t)x0 / 2;
    for (int r = 0; r < ct_h; r++) {
      std::memcpy(uc + (size_t)r * wb, U.data() + cstride * (cy0 + r) + cx0, ct_w);
      std::memcpy(vc + (size_t)r * wb, V.data() + cstride * (cy0 + r) + cx0, ct_w);
    }
  } else {
    // 2x2 box average with edge replication for odd trailing row/col
    for (int r = 0; r < ct_h; r++) {
      const int r0 = 2 * r, r1 = (2 * r + 1 < lh) ? 2 * r + 1 : r0;
      const uint8_t* u0 = U.data() + cstride * (size_t)r0;
      const uint8_t* u1 = U.data() + cstride * (size_t)r1;
      const uint8_t* v0 = V.data() + cstride * (size_t)r0;
      const uint8_t* v1 = V.data() + cstride * (size_t)r1;
      uint8_t* ur = uc + (size_t)r * wb;
      uint8_t* vr = vc + (size_t)r * wb;
      for (int x = 0; x < ct_w; x++) {
        const int x0 = 2 * x, x1 = (2 * x + 1 < lw) ? 2 * x + 1 : x0;
        ur[x] = (uint8_t)((u0[x0] + u0[x1] + u1[x0] + u1[x1] + 2) / 4);
        vr[x] = (uint8_t)((v0[x0] + v0[x1] + v1[x0] + v1[x1] + 2) / 4);
      }
    }
  }
  *h = wh;
  *w = ww;
  return true;
}

// Encode raw 4:2:0 planes (Y: h x w, U/V: ceil(h/2) x ceil(w/2), each
// contiguous) without libjpeg's color-convert/downsample stages.
bool jpeg_encode_yuv420(const uint8_t* y, const uint8_t* u, const uint8_t* v,
                        int h, int w, int quality, bool progressive,
                        std::vector<uint8_t>* out, std::string* err) {
  const int ch = (h + 1) / 2, cw = (w + 1) / 2;
  // iMCU-padded planes with edge replication (encoder reads 16-row groups)
  const int pw = (w + 15) / 16 * 16, ph = (h + 15) / 16 * 16;
  const int pcw = pw / 2, pch = ph / 2;
  std::vector<uint8_t>& Y = arena_slot(t_arena.ystage, (size_t)pw * ph);
  std::vector<uint8_t>& U = arena_slot(t_arena.ustage, (size_t)pcw * pch);
  std::vector<uint8_t>& V = arena_slot(t_arena.vstage, (size_t)pcw * pch);
  for (int r = 0; r < ph; r++) {
    const uint8_t* src = y + (size_t)w * ((r < h) ? r : h - 1);
    uint8_t* dst = Y.data() + (size_t)pw * r;
    std::memcpy(dst, src, w);
    std::memset(dst + w, src[w - 1], pw - w);
  }
  for (int r = 0; r < pch; r++) {
    const int sr = (r < ch) ? r : ch - 1;
    const uint8_t* su = u + (size_t)cw * sr;
    const uint8_t* sv = v + (size_t)cw * sr;
    uint8_t* du = U.data() + (size_t)pcw * r;
    uint8_t* dv = V.data() + (size_t)pcw * r;
    std::memcpy(du, su, cw);
    std::memset(du + cw, su[cw - 1], pcw - cw);
    std::memcpy(dv, sv, cw);
    std::memset(dv + cw, sv[cw - 1], pcw - cw);
  }

  jpeg_compress_struct cinfo;
  JpegErr jerr;
  cinfo.err = jpeg_std_error(&jerr.mgr);
  jerr.mgr.error_exit = jpeg_err_exit;
  unsigned char* mem = nullptr;
  unsigned long memlen = 0;
  if (setjmp(jerr.jb)) {
    *err = jerr.msg;
    jpeg_destroy_compress(&cinfo);
    if (mem) free(mem);
    return false;
  }
  jpeg_create_compress(&cinfo);
  jpeg_mem_dest(&cinfo, &mem, &memlen);
  cinfo.image_width = w;
  cinfo.image_height = h;
  cinfo.input_components = 3;
  cinfo.in_color_space = JCS_YCbCr;
  jpeg_set_defaults(&cinfo);
  jpeg_set_quality(&cinfo, quality, TRUE);
  if (progressive) jpeg_simple_progression(&cinfo);
  cinfo.raw_data_in = TRUE;
  cinfo.comp_info[0].h_samp_factor = 2;
  cinfo.comp_info[0].v_samp_factor = 2;
  cinfo.comp_info[1].h_samp_factor = 1;
  cinfo.comp_info[1].v_samp_factor = 1;
  cinfo.comp_info[2].h_samp_factor = 1;
  cinfo.comp_info[2].v_samp_factor = 1;
  jpeg_start_compress(&cinfo, TRUE);
  JSAMPROW yrows[16], urows[8], vrows[8];
  JSAMPARRAY planes[3] = {yrows, urows, vrows};
  while (cinfo.next_scanline < cinfo.image_height) {
    const int base = (int)cinfo.next_scanline;
    for (int i = 0; i < 16; i++) {
      int r = base + i;
      if (r >= ph) r = ph - 1;
      yrows[i] = Y.data() + (size_t)pw * r;
    }
    for (int i = 0; i < 8; i++) {
      int r = base / 2 + i;
      if (r >= pch) r = pch - 1;
      urows[i] = U.data() + (size_t)pcw * r;
      vrows[i] = V.data() + (size_t)pcw * r;
    }
    jpeg_write_raw_data(&cinfo, planes, 16);
  }
  jpeg_finish_compress(&cinfo);
  out->assign(mem, mem + memlen);
  jpeg_destroy_compress(&cinfo);
  free(mem);
  return true;
}

bool jpeg_encode(const uint8_t* pix, int w, int h, int c, int quality,
                 bool progressive, std::vector<uint8_t>* out, std::string* err) {
  // c must be 1 or 3 (alpha pre-flattened by caller)
  jpeg_compress_struct cinfo;
  JpegErr jerr;
  cinfo.err = jpeg_std_error(&jerr.mgr);
  jerr.mgr.error_exit = jpeg_err_exit;
  unsigned char* mem = nullptr;
  unsigned long memlen = 0;
  if (setjmp(jerr.jb)) {
    *err = jerr.msg;
    jpeg_destroy_compress(&cinfo);
    if (mem) free(mem);
    return false;
  }
  jpeg_create_compress(&cinfo);
  jpeg_mem_dest(&cinfo, &mem, &memlen);
  cinfo.image_width = w;
  cinfo.image_height = h;
  cinfo.input_components = c;
  cinfo.in_color_space = (c == 1) ? JCS_GRAYSCALE : JCS_RGB;
  jpeg_set_defaults(&cinfo);
  jpeg_set_quality(&cinfo, quality, TRUE);
  if (progressive) jpeg_simple_progression(&cinfo);
  jpeg_start_compress(&cinfo, TRUE);
  size_t stride = (size_t)w * c;
  while (cinfo.next_scanline < cinfo.image_height) {
    JSAMPROW row = const_cast<uint8_t*>(pix) + stride * cinfo.next_scanline;
    jpeg_write_scanlines(&cinfo, &row, 1);
  }
  jpeg_finish_compress(&cinfo);
  out->assign(mem, mem + memlen);
  jpeg_destroy_compress(&cinfo);
  free(mem);
  return true;
}

// ----------------------------------------------------------------- PNG ------

bool png_decode_buf(const uint8_t* buf, size_t len, std::vector<uint8_t>* out,
                    int* w, int* h, int* c, std::string* err) {
  png_image img;
  std::memset(&img, 0, sizeof(img));
  img.version = PNG_IMAGE_VERSION;
  if (!png_image_begin_read_from_memory(&img, buf, len)) {
    *err = img.message;
    return false;
  }
  bool alpha = (img.format & PNG_FORMAT_FLAG_ALPHA) != 0;
  img.format = alpha ? PNG_FORMAT_RGBA : PNG_FORMAT_RGB;
  *c = alpha ? 4 : 3;
  *w = img.width;
  *h = img.height;
  out->resize(PNG_IMAGE_SIZE(img));
  if (!png_image_finish_read(&img, nullptr, out->data(), 0, nullptr)) {
    *err = img.message;
    return false;
  }
  return true;
}

bool png_probe_buf(const uint8_t* buf, size_t len, int* w, int* h, int* c) {
  png_image img;
  std::memset(&img, 0, sizeof(img));
  img.version = PNG_IMAGE_VERSION;
  if (!png_image_begin_read_from_memory(&img, buf, len)) return false;
  *w = img.width;
  *h = img.height;
  *c = (img.format & PNG_FORMAT_FLAG_ALPHA) ? 4 : 3;
  png_image_free(&img);
  return true;
}

bool png_encode_buf(const uint8_t* pix, int w, int h, int c,
                    std::vector<uint8_t>* out, std::string* err) {
  png_image img;
  std::memset(&img, 0, sizeof(img));
  img.version = PNG_IMAGE_VERSION;
  img.width = w;
  img.height = h;
  img.format = (c == 4) ? PNG_FORMAT_RGBA : (c == 1 ? PNG_FORMAT_GRAY : PNG_FORMAT_RGB);
  png_alloc_size_t size = 0;
  if (!png_image_write_to_memory(&img, nullptr, &size, 0, pix, 0, nullptr)) {
    *err = img.message;
    return false;
  }
  out->resize(size);
  if (!png_image_write_to_memory(&img, out->data(), &size, 0, pix, 0, nullptr)) {
    *err = img.message;
    return false;
  }
  out->resize(size);
  return true;
}

// ---------------------------------------------------------------- WEBP ------

#ifndef ITPU_NO_WEBP
bool webp_decode_buf(const uint8_t* buf, size_t len, std::vector<uint8_t>* out,
                     int* w, int* h, int* c, std::string* err) {
  WebPBitstreamFeatures feat;
  if (WebPGetFeatures(buf, len, &feat) != VP8_STATUS_OK) {
    *err = "invalid webp";
    return false;
  }
  *w = feat.width;
  *h = feat.height;
  *c = feat.has_alpha ? 4 : 3;
  size_t stride = (size_t)(*w) * (*c);
  out->resize(stride * (*h));
  uint8_t* res = feat.has_alpha
      ? WebPDecodeRGBAInto(buf, len, out->data(), out->size(), (int)stride)
      : WebPDecodeRGBInto(buf, len, out->data(), out->size(), (int)stride);
  if (!res) {
    *err = "webp decode failed";
    return false;
  }
  return true;
}

bool webp_encode_buf(const uint8_t* pix, int w, int h, int c, int quality,
                     std::vector<uint8_t>* out, std::string* err) {
  uint8_t* mem = nullptr;
  size_t n = (c == 4)
      ? WebPEncodeRGBA(pix, w, h, w * 4, (float)quality, &mem)
      : WebPEncodeRGB(pix, w, h, w * 3, (float)quality, &mem);
  if (!n || !mem) {
    *err = "webp encode failed";
    return false;
  }
  out->assign(mem, mem + n);
  WebPFree(mem);
  return true;
}
#endif  // !ITPU_NO_WEBP

// ---------------------------------------------------- palette quantizer -----
//
// Median-cut + Floyd-Steinberg, shared by palette-PNG output and the GIF
// encoder (the reference gets both from libvips' quantizer; ours is in-tree
// so palette output is native, not a PIL stand-in — SURVEY.md section 2.12).

struct Box {
  int lo[3], hi[3];
  std::vector<uint32_t> colors;  // packed 0x00RRGGBB, sampled
};

void box_bounds(Box* b) {
  for (int k = 0; k < 3; k++) { b->lo[k] = 255; b->hi[k] = 0; }
  for (uint32_t cc : b->colors) {
    int v[3] = {(int)(cc >> 16) & 255, (int)(cc >> 8) & 255, (int)cc & 255};
    for (int k = 0; k < 3; k++) {
      if (v[k] < b->lo[k]) b->lo[k] = v[k];
      if (v[k] > b->hi[k]) b->hi[k] = v[k];
    }
  }
}

// Quantize RGB(A) pixels to <= max_colors palette entries (RGB). Pixels with
// alpha < 128 are excluded from the statistics (they map to a reserved
// transparent index when the caller asks for one).
void median_cut(const uint8_t* pix, size_t n, int c, int max_colors,
                std::vector<uint8_t>* palette) {
  // bounded sample: quantizer cost must not scale with megapixels
  const size_t kMaxSample = 1 << 16;
  size_t stride = (n > kMaxSample) ? n / kMaxSample : 1;
  std::vector<Box> boxes(1);
  boxes[0].colors.reserve(n / stride + 1);
  for (size_t i = 0; i < n; i += stride) {
    const uint8_t* p = pix + i * c;
    if (c == 4 && p[3] < 128) continue;
    boxes[0].colors.push_back(((uint32_t)p[0] << 16) | ((uint32_t)p[1] << 8) | p[2]);
  }
  if (boxes[0].colors.empty()) boxes[0].colors.push_back(0);
  box_bounds(&boxes[0]);
  while ((int)boxes.size() < max_colors) {
    // widest-range box with >1 color
    int bi = -1, best = -1;
    for (size_t i = 0; i < boxes.size(); i++) {
      if (boxes[i].colors.size() < 2) continue;
      int r = 0;
      for (int k = 0; k < 3; k++) r = std::max(r, boxes[i].hi[k] - boxes[i].lo[k]);
      if (r > best) { best = r; bi = (int)i; }
    }
    if (bi < 0) break;
    Box& b = boxes[bi];
    int axis = 0;
    for (int k = 1; k < 3; k++)
      if (b.hi[k] - b.lo[k] > b.hi[axis] - b.lo[axis]) axis = k;
    const int shift = (axis == 0) ? 16 : (axis == 1) ? 8 : 0;
    std::sort(b.colors.begin(), b.colors.end(),
              [shift](uint32_t a, uint32_t bb) {
                return ((a >> shift) & 255) < ((bb >> shift) & 255);
              });
    Box nb;
    size_t mid = b.colors.size() / 2;
    nb.colors.assign(b.colors.begin() + mid, b.colors.end());
    b.colors.resize(mid);
    box_bounds(&b);
    box_bounds(&nb);
    boxes.push_back(std::move(nb));
  }
  palette->clear();
  for (Box& b : boxes) {
    uint64_t s[3] = {0, 0, 0};
    for (uint32_t cc : b.colors) {
      s[0] += (cc >> 16) & 255; s[1] += (cc >> 8) & 255; s[2] += cc & 255;
    }
    size_t m = b.colors.size();
    palette->push_back((uint8_t)(s[0] / m));
    palette->push_back((uint8_t)(s[1] / m));
    palette->push_back((uint8_t)(s[2] / m));
  }
}

struct NearestCache {
  // 15-bit RGB -> palette index (+1; 0 = empty)
  std::vector<uint16_t> slot = std::vector<uint16_t>(1 << 15, 0);
  const std::vector<uint8_t>* pal;
  int start = 0;  // first searchable entry: skips a reserved transparent
                  // index, else opaque near-black pixels would map to it
                  // and render fully transparent
  int find(int r, int g, int b) {
    const uint32_t key = ((r >> 3) << 10) | ((g >> 3) << 5) | (b >> 3);
    if (slot[key]) return slot[key] - 1;
    int best = start;
    long bestd = 1L << 40;
    const std::vector<uint8_t>& P = *pal;
    for (size_t i = (size_t)start; i * 3 < P.size(); i++) {
      long dr = r - P[i * 3], dg = g - P[i * 3 + 1], db = b - P[i * 3 + 2];
      long d = dr * dr + dg * dg + db * db;
      if (d < bestd) { bestd = d; best = (int)i; }
    }
    slot[key] = (uint16_t)(best + 1);
    return best;
  }
};

// Map pixels to palette indices with Floyd-Steinberg error diffusion.
// transparent_index >= 0 claims that index for alpha < 128 pixels.
void dither_map(const uint8_t* pix, int w, int h, int c,
                const std::vector<uint8_t>& palette, int transparent_index,
                std::vector<uint8_t>* indices) {
  NearestCache cache;
  cache.pal = &palette;
  cache.start = (transparent_index == 0) ? 1 : 0;
  indices->resize((size_t)w * h);
  // error rows: 3 channels, current + next
  std::vector<int> err((size_t)(w + 2) * 3 * 2, 0);
  int* cur = err.data();
  int* nxt = err.data() + (size_t)(w + 2) * 3;
  for (int y = 0; y < h; y++) {
    std::memset(nxt, 0, sizeof(int) * (size_t)(w + 2) * 3);
    for (int x = 0; x < w; x++) {
      const uint8_t* p = pix + ((size_t)y * w + x) * c;
      if (c == 4 && transparent_index >= 0 && p[3] < 128) {
        (*indices)[(size_t)y * w + x] = (uint8_t)transparent_index;
        continue;
      }
      int v[3];
      for (int k = 0; k < 3; k++) {
        int t = p[k] + cur[(x + 1) * 3 + k] / 16;
        v[k] = t < 0 ? 0 : (t > 255 ? 255 : t);
      }
      int idx = cache.find(v[0], v[1], v[2]);
      (*indices)[(size_t)y * w + x] = (uint8_t)idx;
      for (int k = 0; k < 3; k++) {
        int e = v[k] - palette[idx * 3 + k];
        cur[(x + 2) * 3 + k] += e * 7;
        nxt[(x + 0) * 3 + k] += e * 3;
        nxt[(x + 1) * 3 + k] += e * 5;
        nxt[(x + 2) * 3 + k] += e * 1;
      }
    }
    std::swap(cur, nxt);
  }
}

// ------------------------------------------------------- PNG (full-path) ----
//
// The simplified png_image API cannot write interlaced or palette PNGs; this
// low-level writer covers the reference's Interlace and Palette options
// (options.go:44-45 -> vips pngsave interlace/palette) plus the Speed ->
// filter-strategy mapping (cheaper filters = faster encode, larger output).

void png_vec_write(png_structp png, png_bytep data, png_size_t len) {
  auto* out = static_cast<std::vector<uint8_t>*>(png_get_io_ptr(png));
  out->insert(out->end(), data, data + len);
}
void png_vec_flush(png_structp) {}

void png_err_fn(png_structp png, png_const_charp msg) {
  auto* err = static_cast<std::string*>(png_get_error_ptr(png));
  if (err) *err = msg;
  longjmp(png_jmpbuf(png), 1);
}
void png_warn_fn(png_structp, png_const_charp) {}

bool png_encode_full(const uint8_t* pix, int w, int h, int c, int compression,
                     bool interlace, bool palette, int speed,
                     std::vector<uint8_t>* out, std::string* err) {
  png_structp png = png_create_write_struct(PNG_LIBPNG_VER_STRING, err,
                                            png_err_fn, png_warn_fn);
  if (!png) { *err = "png_create_write_struct failed"; return false; }
  png_infop info = png_create_info_struct(png);
  if (!info) {
    png_destroy_write_struct(&png, nullptr);
    *err = "png_create_info_struct failed";
    return false;
  }
  std::vector<uint8_t> indices;          // outlive setjmp
  std::vector<uint8_t> pal;
  std::vector<png_bytep> rows((size_t)h);
  if (setjmp(png_jmpbuf(png))) {
    png_destroy_write_struct(&png, &info);
    return false;
  }
  out->clear();
  png_set_write_fn(png, out, png_vec_write, png_vec_flush);
  png_set_compression_level(png, compression);
  if (speed > 0) {
    // Speed (options.go:47) maps to filter strategy: the filter pass is
    // the CPU-bound part of PNG encode after zlib; high speed drops it.
    int filters = (speed >= 7) ? PNG_FILTER_NONE
                 : (speed >= 4) ? (PNG_FILTER_NONE | PNG_FILTER_SUB)
                                : PNG_ALL_FILTERS;
    png_set_filter(png, 0, filters);
  }
  const int itype = interlace ? PNG_INTERLACE_ADAM7 : PNG_INTERLACE_NONE;
  if (palette && c >= 3) {
    const bool has_alpha = (c == 4);
    // reserve index 0 for transparency when any pixel is see-through
    bool any_transparent = false;
    if (has_alpha) {
      const size_t n = (size_t)w * h;
      for (size_t i = 0; i < n; i++)
        if (pix[i * 4 + 3] < 128) { any_transparent = true; break; }
    }
    const int max_colors = any_transparent ? 255 : 256;
    median_cut(pix, (size_t)w * h, c, max_colors, &pal);
    int transparent_index = -1;
    if (any_transparent) {
      pal.insert(pal.begin(), {0, 0, 0});  // index 0 = fully transparent
      transparent_index = 0;              // opaque search skips it (cache.start)
    }
    const int ncolors = (int)(pal.size() / 3);
    dither_map(pix, w, h, c, pal, transparent_index, &indices);
    png_set_IHDR(png, info, w, h, 8, PNG_COLOR_TYPE_PALETTE, itype,
                 PNG_COMPRESSION_TYPE_DEFAULT, PNG_FILTER_TYPE_DEFAULT);
    std::vector<png_color> plte((size_t)ncolors);
    for (int i = 0; i < ncolors; i++) {
      plte[i].red = pal[i * 3];
      plte[i].green = pal[i * 3 + 1];
      plte[i].blue = pal[i * 3 + 2];
    }
    png_set_PLTE(png, info, plte.data(), ncolors);
    if (transparent_index == 0) {
      png_byte trans[1] = {0};
      png_set_tRNS(png, info, trans, 1, nullptr);
    }
    for (int y = 0; y < h; y++) rows[y] = indices.data() + (size_t)y * w;
  } else {
    const int color_type = (c == 4) ? PNG_COLOR_TYPE_RGBA
                          : (c == 1) ? PNG_COLOR_TYPE_GRAY
                                     : PNG_COLOR_TYPE_RGB;
    png_set_IHDR(png, info, w, h, 8, color_type, itype,
                 PNG_COMPRESSION_TYPE_DEFAULT, PNG_FILTER_TYPE_DEFAULT);
    for (int y = 0; y < h; y++)
      rows[y] = const_cast<uint8_t*>(pix) + (size_t)y * w * c;
  }
  png_write_info(png, info);
  png_write_image(png, rows.data());  // handles Adam7 passes itself
  png_write_end(png, info);
  png_destroy_write_struct(&png, &info);
  return true;
}

// ----------------------------------------------------------------- GIF ------
//
// From-scratch GIF87a/89a codec (LZW both directions). The reference reads
// GIF via libvips/libgif (Dockerfile:15); this host lacks giflib headers, and
// the format is simple enough that an in-tree implementation is smaller than
// an ABI-by-hand binding. First frame only, like vips gifload's default page.

struct BitReader {
  const uint8_t* data;
  size_t len, pos = 0;
  uint32_t acc = 0;
  int nbits = 0;
  bool get(int width, uint32_t* out) {
    while (nbits < width) {
      if (pos >= len) return false;
      acc |= (uint32_t)data[pos++] << nbits;
      nbits += 8;
    }
    *out = acc & ((1u << width) - 1);
    acc >>= width;
    nbits -= width;
    return true;
  }
};

// LZW-decompress GIF image data (sub-blocks already concatenated) into
// `npix` palette indices.
bool gif_lzw_decode(const uint8_t* data, size_t len, int min_code_size,
                    size_t npix, std::vector<uint8_t>* out) {
  if (min_code_size < 2 || min_code_size > 11) return false;
  const int clear = 1 << min_code_size, eoi = clear + 1;
  int code_size = min_code_size + 1, next_code = eoi + 1, prev = -1;
  std::vector<int> prefix(4096, -1);
  std::vector<uint8_t> suffix(4096, 0), stack(4096);
  for (int i = 0; i < clear; i++) suffix[i] = (uint8_t)i;
  out->clear();
  out->reserve(npix);
  BitReader br{data, len};
  uint32_t code;
  while (out->size() < npix && br.get(code_size, &code)) {
    if ((int)code == clear) {
      code_size = min_code_size + 1;
      next_code = eoi + 1;
      prev = -1;
      continue;
    }
    if ((int)code == eoi) break;
    if ((int)code > next_code || ((int)code == next_code && prev < 0))
      return false;  // corrupt stream
    int cur = (int)code;
    int sp = 0;
    uint8_t first;
    if (cur == next_code) {  // KwKwK: string(prev) + first(prev)
      cur = prev;
      // walk prev first to learn its first char, emit later with extra char
      int t = cur;
      while (prefix[t] >= 0) t = prefix[t];
      stack[sp++] = suffix[t];  // placeholder for trailing char (== first)
    }
    int t = cur;
    while (t >= 0) {
      if (sp >= 4096) return false;
      stack[sp++] = suffix[t];
      t = prefix[t];
    }
    first = stack[sp - 1];
    while (sp > 0 && out->size() < npix) out->push_back(stack[--sp]);
    if (prev >= 0 && next_code < 4096) {
      prefix[next_code] = prev;
      suffix[next_code] = first;
      next_code++;
      if (next_code == (1 << code_size) && code_size < 12) code_size++;
    }
    prev = (int)code;
  }
  return out->size() == npix;
}

struct BitWriter {
  std::vector<uint8_t> bytes;
  uint32_t acc = 0;
  int nbits = 0;
  void put(uint32_t code, int width) {
    acc |= code << nbits;
    nbits += width;
    while (nbits >= 8) {
      bytes.push_back((uint8_t)(acc & 255));
      acc >>= 8;
      nbits -= 8;
    }
  }
  void flush() {
    if (nbits > 0) bytes.push_back((uint8_t)(acc & 255));
    acc = 0;
    nbits = 0;
  }
};

void gif_lzw_encode(const uint8_t* indices, size_t n, int min_code_size,
                    BitWriter* bw) {
  const int clear = 1 << min_code_size, eoi = clear + 1;
  int code_size = min_code_size + 1, next_code = eoi + 1;
  // open-addressing hash: key = (prefix << 8) | ch, value = code
  const int HB = 1 << 14;
  std::vector<int> hkey(HB, -1), hval(HB, 0);
  auto reset = [&]() {
    std::fill(hkey.begin(), hkey.end(), -1);
    code_size = min_code_size + 1;
    next_code = eoi + 1;
  };
  bw->put((uint32_t)clear, code_size);
  if (n == 0) {
    bw->put((uint32_t)eoi, code_size);
    bw->flush();
    return;
  }
  // Width-sync invariant: the decoder registers its (j-1)-th entry after
  // reading code j, so it is one entry BEHIND this table. The code-size
  // bump therefore happens after emitting a code but BEFORE registering
  // the new entry (giflib's `free_ent > maxcode` ordering) — bumping
  // after the add desyncs widths one code early on the decoder side.
  auto bump = [&]() {
    if (next_code >= (1 << code_size) && code_size < 12) code_size++;
  };
  int prefix = indices[0];
  for (size_t i = 1; i < n; i++) {
    const int ch = indices[i];
    const int key = (prefix << 8) | ch;
    int slot = (int)(((uint32_t)key * 2654435761u) & (HB - 1));
    int found = -1;
    while (hkey[slot] != -1) {
      if (hkey[slot] == key) { found = hval[slot]; break; }
      slot = (slot + 1) & (HB - 1);
    }
    if (found >= 0) {
      prefix = found;
      continue;
    }
    bw->put((uint32_t)prefix, code_size);
    bump();
    if (next_code < 4096) {
      hkey[slot] = key;
      hval[slot] = next_code;
      next_code++;
    } else {
      bw->put((uint32_t)clear, code_size);
      reset();
    }
    prefix = ch;
  }
  bw->put((uint32_t)prefix, code_size);
  bump();
  bw->put((uint32_t)eoi, code_size);
  bw->flush();
}

uint32_t rd16le(const uint8_t* p) { return p[0] | ((uint32_t)p[1] << 8); }

bool gif_decode_buf(const uint8_t* buf, size_t len, std::vector<uint8_t>* out,
                    int* w, int* h, int* c, std::string* err) {
  if (len < 13 || std::memcmp(buf, "GIF8", 4) != 0 ||
      (buf[4] != '7' && buf[4] != '9') || buf[5] != 'a') {
    *err = "invalid gif";
    return false;
  }
  const int sw = (int)rd16le(buf + 6), sh = (int)rd16le(buf + 8);
  if (sw <= 0 || sh <= 0 || (int64_t)sw * sh > (int64_t)100 * 1000 * 1000) {
    *err = "invalid gif dimensions";
    return false;
  }
  const uint8_t packed = buf[10];
  const int bg = buf[11];
  const uint8_t* gct = nullptr;
  int gct_n = 0;
  size_t i = 13;
  if (packed & 0x80) {
    gct_n = 2 << (packed & 7);
    if (i + (size_t)gct_n * 3 > len) { *err = "truncated gif"; return false; }
    gct = buf + i;
    i += (size_t)gct_n * 3;
  }
  int transparent = -1;
  while (i < len) {
    const uint8_t b0 = buf[i++];
    if (b0 == 0x3B) break;  // trailer before any image
    if (b0 == 0x21) {       // extension
      if (i >= len) break;
      const uint8_t label = buf[i++];
      if (label == 0xF9 && i + 6 <= len && buf[i] == 4) {
        if (buf[i + 1] & 1) transparent = buf[i + 5];
      }
      // skip sub-blocks
      while (i < len && buf[i] != 0) {
        i += 1 + buf[i];
        if (i > len) { *err = "truncated gif"; return false; }
      }
      i++;  // block terminator
      continue;
    }
    if (b0 != 0x2C) { *err = "invalid gif block"; return false; }
    // image descriptor
    if (i + 9 > len) { *err = "truncated gif"; return false; }
    const int fx = (int)rd16le(buf + i), fy = (int)rd16le(buf + i + 2);
    const int fw = (int)rd16le(buf + i + 4), fh = (int)rd16le(buf + i + 6);
    const uint8_t fpacked = buf[i + 8];
    i += 9;
    const uint8_t* lct = gct;
    int lct_n = gct_n;
    if (fpacked & 0x80) {
      lct_n = 2 << (fpacked & 7);
      if (i + (size_t)lct_n * 3 > len) { *err = "truncated gif"; return false; }
      lct = buf + i;
      i += (size_t)lct_n * 3;
    }
    if (!lct || fw <= 0 || fh <= 0 || fx + fw > sw || fy + fh > sh) {
      *err = "invalid gif frame";
      return false;
    }
    const bool interlaced = (fpacked & 0x40) != 0;
    if (i >= len) { *err = "truncated gif"; return false; }
    const int min_code_size = buf[i++];
    // concatenate data sub-blocks
    std::vector<uint8_t> data;
    while (i < len && buf[i] != 0) {
      const size_t bl = buf[i];
      if (i + 1 + bl > len) { *err = "truncated gif"; return false; }
      data.insert(data.end(), buf + i + 1, buf + i + 1 + bl);
      i += 1 + bl;
    }
    std::vector<uint8_t> idx;
    if (!gif_lzw_decode(data.data(), data.size(), min_code_size,
                        (size_t)fw * fh, &idx)) {
      *err = "gif lzw decode failed";
      return false;
    }
    // compose onto the logical screen
    const bool has_alpha = transparent >= 0;
    *c = has_alpha ? 4 : 3;
    *w = sw;
    *h = sh;
    out->assign((size_t)sw * sh * (*c), 0);
    if (!has_alpha && gct && bg < gct_n) {  // background fill
      for (size_t p = 0, np = (size_t)sw * sh; p < np; p++) {
        (*out)[p * 3 + 0] = gct[bg * 3 + 0];
        (*out)[p * 3 + 1] = gct[bg * 3 + 1];
        (*out)[p * 3 + 2] = gct[bg * 3 + 2];
      }
    }
    // interlace pass order
    std::vector<int> row_of(fh);
    if (interlaced) {
      static const int off[4] = {0, 4, 2, 1}, step[4] = {8, 8, 4, 2};
      int r = 0;
      for (int p = 0; p < 4; p++)
        for (int y = off[p]; y < fh; y += step[p]) row_of[r++] = y;
    } else {
      for (int y = 0; y < fh; y++) row_of[y] = y;
    }
    for (int r = 0; r < fh; r++) {
      const int y = row_of[r];
      for (int x = 0; x < fw; x++) {
        const int v = idx[(size_t)r * fw + x];
        if (v >= lct_n) continue;  // out-of-palette index: leave background
        uint8_t* dst = out->data() + (((size_t)(fy + y) * sw) + fx + x) * (*c);
        if (has_alpha) {
          if (v == transparent) continue;  // stays (0,0,0,0)
          dst[0] = lct[v * 3];
          dst[1] = lct[v * 3 + 1];
          dst[2] = lct[v * 3 + 2];
          dst[3] = 255;
        } else {
          dst[0] = lct[v * 3];
          dst[1] = lct[v * 3 + 1];
          dst[2] = lct[v * 3 + 2];
        }
      }
    }
    return true;  // first frame only
  }
  *err = "gif has no image data";
  return false;
}

bool gif_probe_buf(const uint8_t* buf, size_t len, int* w, int* h, int* c) {
  if (len < 13 || std::memcmp(buf, "GIF8", 4) != 0) return false;
  *w = (int)rd16le(buf + 6);
  *h = (int)rd16le(buf + 8);
  // bounded scan for a GCE transparency flag before the first image
  size_t i = 13;
  if (buf[10] & 0x80) i += (size_t)(2 << (buf[10] & 7)) * 3;
  *c = 3;
  while (i + 1 < len && buf[i] == 0x21) {
    const uint8_t label = buf[i + 1];
    size_t j = i + 2;
    if (label == 0xF9 && j + 5 < len && buf[j] == 4 && (buf[j + 1] & 1)) {
      *c = 4;
      break;
    }
    while (j < len && buf[j] != 0) j += 1 + buf[j];
    i = j + 1;
  }
  return *w > 0 && *h > 0;
}

bool gif_encode_buf(const uint8_t* pix, int w, int h, int c,
                    std::vector<uint8_t>* out, std::string* err) {
  if (c != 3 && c != 4) {
    // expand gray to RGB via caller; guard anyway
    *err = "gif encode expects RGB(A)";
    return false;
  }
  bool any_transparent = false;
  if (c == 4) {
    const size_t n = (size_t)w * h;
    for (size_t i = 0; i < n; i++)
      if (pix[i * 4 + 3] < 128) { any_transparent = true; break; }
  }
  std::vector<uint8_t> pal;
  median_cut(pix, (size_t)w * h, c, any_transparent ? 255 : 256, &pal);
  int transparent_index = -1;
  if (any_transparent) {
    pal.insert(pal.begin(), {0, 0, 0});
    transparent_index = 0;
  }
  const int ncolors = (int)(pal.size() / 3);
  std::vector<uint8_t> indices;
  dither_map(pix, w, h, c, pal, transparent_index, &indices);
  // palette size field: 2^(n+1) >= ncolors (pbits=7 covers the 256 max)
  int pbits = 1;
  while ((2 << pbits) < ncolors && pbits < 7) pbits++;
  const int table_n = 2 << pbits;
  out->clear();
  out->reserve((size_t)w * h / 4 + 1024);
  auto put16 = [&](int v) {
    out->push_back((uint8_t)(v & 255));
    out->push_back((uint8_t)((v >> 8) & 255));
  };
  out->insert(out->end(), {'G', 'I', 'F', '8', '9', 'a'});
  put16(w);
  put16(h);
  out->push_back((uint8_t)(0x80 | (7 << 4) | pbits));  // GCT, 8-bit res
  out->push_back(0);                                    // bg color index
  out->push_back(0);                                    // aspect
  for (int i = 0; i < table_n; i++) {
    if (i < ncolors) {
      out->push_back(pal[i * 3]);
      out->push_back(pal[i * 3 + 1]);
      out->push_back(pal[i * 3 + 2]);
    } else {
      out->push_back(0);
      out->push_back(0);
      out->push_back(0);
    }
  }
  if (transparent_index >= 0) {  // GCE
    out->insert(out->end(), {0x21, 0xF9, 4, 0x01, 0, 0,
                             (uint8_t)transparent_index, 0});
  }
  out->push_back(0x2C);  // image descriptor: full frame, no LCT
  put16(0);
  put16(0);
  put16(w);
  put16(h);
  out->push_back(0);
  int min_code_size = pbits + 1;
  if (min_code_size < 2) min_code_size = 2;
  out->push_back((uint8_t)min_code_size);
  BitWriter bw;
  gif_lzw_encode(indices.data(), indices.size(), min_code_size, &bw);
  for (size_t i = 0; i < bw.bytes.size(); i += 255) {
    const size_t bl = std::min<size_t>(255, bw.bytes.size() - i);
    out->push_back((uint8_t)bl);
    out->insert(out->end(), bw.bytes.begin() + i, bw.bytes.begin() + i + bl);
  }
  out->push_back(0);     // block terminator
  out->push_back(0x3B);  // trailer
  (void)err;
  return true;
}

// ---------------------------------------------------------------- TIFF ------
//
// libtiff is on this image as a runtime .so without dev headers, so the
// needed slice of its (stable, versioned LIBTIFF_4.0) C ABI is declared by
// hand: opaque TIFF*, memory-client open, RGBA-oriented read, strip write.
// Covers the reference's TIFF path (Dockerfile:15 libtiff5-dev -> libvips).

extern "C" {
typedef struct tiff TIFF;
typedef int64_t tiff_msize_t;   // tmsize_t: ptrdiff_t on LP64
typedef uint64_t tiff_off_t;    // toff_t
typedef void* tiff_handle_t;    // thandle_t
typedef tiff_msize_t (*TIFFReadWriteProc)(tiff_handle_t, void*, tiff_msize_t);
typedef tiff_off_t (*TIFFSeekProc)(tiff_handle_t, tiff_off_t, int);
typedef int (*TIFFCloseProc)(tiff_handle_t);
typedef tiff_off_t (*TIFFSizeProc)(tiff_handle_t);
typedef int (*TIFFMapFileProc)(tiff_handle_t, void**, tiff_off_t*);
typedef void (*TIFFUnmapFileProc)(tiff_handle_t, void*, tiff_off_t);
typedef void (*TIFFErrorHandler)(const char*, const char*, va_list);
TIFF* TIFFClientOpen(const char*, const char*, tiff_handle_t,
                     TIFFReadWriteProc, TIFFReadWriteProc, TIFFSeekProc,
                     TIFFCloseProc, TIFFSizeProc, TIFFMapFileProc,
                     TIFFUnmapFileProc);
void TIFFClose(TIFF*);
int TIFFGetField(TIFF*, uint32_t, ...);
int TIFFSetField(TIFF*, uint32_t, ...);
int TIFFReadRGBAImageOriented(TIFF*, uint32_t, uint32_t, uint32_t*, int, int);
int TIFFReadScanline(TIFF*, void*, uint32_t, uint16_t);
int TIFFIsTiled(TIFF*);
tiff_msize_t TIFFWriteEncodedStrip(TIFF*, uint32_t, void*, tiff_msize_t);
TIFFErrorHandler TIFFSetErrorHandler(TIFFErrorHandler);
TIFFErrorHandler TIFFSetWarningHandler(TIFFErrorHandler);
}

// tag constants (tiff.h values; the TIFF 6.0 spec, not a private ABI)
enum : uint32_t {
  kTagImageWidth = 256,
  kTagImageLength = 257,
  kTagBitsPerSample = 258,
  kTagCompression = 259,
  kTagPhotometric = 262,
  kTagSamplesPerPixel = 277,
  kTagRowsPerStrip = 278,
  kTagPlanarConfig = 284,
  kTagOrientation = 274,
  kTagExtraSamples = 338,
};
enum : int {
  kCompressionLZW = 5,
  kPhotometricMinIsBlack = 1,
  kPhotometricRGB = 2,
  kPlanarContig = 1,
  kOrientTopLeft = 1,
  kExtraUnassAlpha = 2,
};

struct TiffMemR {
  const uint8_t* data;
  size_t size;
  size_t pos;
};

tiff_msize_t tiffr_read(tiff_handle_t h, void* buf, tiff_msize_t n) {
  auto* m = static_cast<TiffMemR*>(h);
  if (m->pos >= m->size) return 0;
  const size_t take = std::min((size_t)n, m->size - m->pos);
  std::memcpy(buf, m->data + m->pos, take);
  m->pos += take;
  return (tiff_msize_t)take;
}
tiff_msize_t tiffr_write(tiff_handle_t, void*, tiff_msize_t) { return 0; }
tiff_off_t tiffr_seek(tiff_handle_t h, tiff_off_t off, int whence) {
  auto* m = static_cast<TiffMemR*>(h);
  size_t base = (whence == 1) ? m->pos : (whence == 2) ? m->size : 0;
  m->pos = base + (size_t)off;
  return (tiff_off_t)m->pos;
}
int tiffr_close(tiff_handle_t) { return 0; }
tiff_off_t tiffr_size(tiff_handle_t h) {
  return (tiff_off_t)static_cast<TiffMemR*>(h)->size;
}
int tiff_map_none(tiff_handle_t, void**, tiff_off_t*) { return 0; }
void tiff_unmap_none(tiff_handle_t, void*, tiff_off_t) {}

struct TiffMemW {
  std::vector<uint8_t>* out;
  size_t pos;
};

tiff_msize_t tiffw_read(tiff_handle_t h, void* buf, tiff_msize_t n) {
  auto* m = static_cast<TiffMemW*>(h);
  if (m->pos >= m->out->size()) return 0;
  const size_t take = std::min((size_t)n, m->out->size() - m->pos);
  std::memcpy(buf, m->out->data() + m->pos, take);
  m->pos += take;
  return (tiff_msize_t)take;
}
tiff_msize_t tiffw_write(tiff_handle_t h, void* buf, tiff_msize_t n) {
  auto* m = static_cast<TiffMemW*>(h);
  if (m->pos + (size_t)n > m->out->size()) m->out->resize(m->pos + (size_t)n);
  std::memcpy(m->out->data() + m->pos, buf, (size_t)n);
  m->pos += (size_t)n;
  return n;
}
tiff_off_t tiffw_seek(tiff_handle_t h, tiff_off_t off, int whence) {
  auto* m = static_cast<TiffMemW*>(h);
  size_t base = (whence == 1) ? m->pos : (whence == 2) ? m->out->size() : 0;
  m->pos = base + (size_t)off;
  if (m->pos > m->out->size()) m->out->resize(m->pos);
  return (tiff_off_t)m->pos;
}
int tiffw_close(tiff_handle_t) { return 0; }
tiff_off_t tiffw_size(tiff_handle_t h) {
  return (tiff_off_t)static_cast<TiffMemW*>(h)->out->size();
}

void tiff_quiet(const char*, const char*, va_list) {}

bool tiff_decode_buf(const uint8_t* buf, size_t len, std::vector<uint8_t>* out,
                     int* w, int* h, int* c, std::string* err) {
  TiffMemR m{buf, len, 0};
  TIFF* tif = TIFFClientOpen("mem", "rm", &m, tiffr_read, tiffr_write,
                             tiffr_seek, tiffr_close, tiffr_size,
                             tiff_map_none, tiff_unmap_none);
  if (!tif) {
    *err = "invalid tiff";
    return false;
  }
  uint32_t W = 0, H = 0;
  uint16_t spp = 0, bps = 0, photo = 0, planar = 0;
  TIFFGetField(tif, kTagImageWidth, &W);
  TIFFGetField(tif, kTagImageLength, &H);
  if (!TIFFGetField(tif, kTagSamplesPerPixel, &spp)) spp = 1;
  if (!TIFFGetField(tif, kTagBitsPerSample, &bps)) bps = 1;
  if (!TIFFGetField(tif, kTagPhotometric, &photo)) photo = 0;
  if (!TIFFGetField(tif, kTagPlanarConfig, &planar)) planar = kPlanarContig;
  if (W == 0 || H == 0 || (uint64_t)W * H > (uint64_t)100 * 1000 * 1000) {
    TIFFClose(tif);
    *err = "invalid tiff dimensions";
    return false;
  }
  uint16_t orient = 0;
  if (!TIFFGetField(tif, kTagOrientation, &orient)) orient = kOrientTopLeft;
  // Direct scanline path for the common 8-bit contiguous RGB(A) top-left
  // layout: the RGBA convenience reader PREMULTIPLIES unassociated alpha,
  // which would corrupt straight-alpha pixels on a plain decode->encode
  // trip. Non-top-left orientations fall through to the oriented reader
  // (raw scanlines would come back rotated/flipped).
  // spp==4 additionally requires ExtraSamples to declare UNASSOCIATED
  // alpha: raw scanlines of an associated-alpha (premultiplied) file would
  // ship premultiplied planes as straight alpha — those files take
  // TIFFReadRGBAImageOriented, which un-premultiplies correctly.
  bool straight_alpha = true;
  if (spp == 4) {
    uint16_t nextra = 0;
    uint16_t* extra = nullptr;
    straight_alpha = TIFFGetField(tif, kTagExtraSamples, &nextra, &extra) &&
                     nextra >= 1 && extra != nullptr &&
                     extra[0] == kExtraUnassAlpha;
  }
  if (!TIFFIsTiled(tif) && bps == 8 && planar == kPlanarContig &&
      photo == kPhotometricRGB && (spp == 3 || (spp == 4 && straight_alpha)) &&
      orient == kOrientTopLeft) {
    *w = (int)W;
    *h = (int)H;
    *c = (int)spp;
    out->resize((size_t)W * H * spp);
    for (uint32_t row = 0; row < H; row++) {
      if (TIFFReadScanline(tif, out->data() + (size_t)row * W * spp, row, 0) < 0) {
        TIFFClose(tif);
        *err = "tiff decode failed";
        return false;
      }
    }
    TIFFClose(tif);
    return true;
  }
  std::vector<uint32_t> raster((size_t)W * H);
  if (!TIFFReadRGBAImageOriented(tif, W, H, raster.data(), kOrientTopLeft, 0)) {
    TIFFClose(tif);
    *err = "tiff decode failed";
    return false;
  }
  TIFFClose(tif);
  // raster packs ABGR in host order: R in the low byte
  bool has_alpha = false;
  if (spp >= 4) {
    for (size_t i = 0, n = (size_t)W * H; i < n; i++)
      if ((raster[i] >> 24) != 255) { has_alpha = true; break; }
  }
  *w = (int)W;
  *h = (int)H;
  *c = has_alpha ? 4 : 3;
  out->resize((size_t)W * H * (*c));
  uint8_t* dst = out->data();
  if (has_alpha) {
    for (size_t i = 0, n = (size_t)W * H; i < n; i++) {
      const uint32_t v = raster[i];
      dst[i * 4 + 0] = (uint8_t)(v & 255);
      dst[i * 4 + 1] = (uint8_t)((v >> 8) & 255);
      dst[i * 4 + 2] = (uint8_t)((v >> 16) & 255);
      dst[i * 4 + 3] = (uint8_t)(v >> 24);
    }
  } else {
    for (size_t i = 0, n = (size_t)W * H; i < n; i++) {
      const uint32_t v = raster[i];
      dst[i * 3 + 0] = (uint8_t)(v & 255);
      dst[i * 3 + 1] = (uint8_t)((v >> 8) & 255);
      dst[i * 3 + 2] = (uint8_t)((v >> 16) & 255);
    }
  }
  return true;
}

bool tiff_probe_buf(const uint8_t* buf, size_t len, int* w, int* h, int* c) {
  TiffMemR m{buf, len, 0};
  TIFF* tif = TIFFClientOpen("mem", "rm", &m, tiffr_read, tiffr_write,
                             tiffr_seek, tiffr_close, tiffr_size,
                             tiff_map_none, tiff_unmap_none);
  if (!tif) return false;
  uint32_t W = 0, H = 0;
  uint16_t spp = 0;
  TIFFGetField(tif, kTagImageWidth, &W);
  TIFFGetField(tif, kTagImageLength, &H);
  if (!TIFFGetField(tif, kTagSamplesPerPixel, &spp)) spp = 1;
  TIFFClose(tif);
  if (W == 0 || H == 0) return false;
  *w = (int)W;
  *h = (int)H;
  *c = (spp >= 4) ? 4 : (spp >= 3 ? 3 : 1);
  return true;
}

bool tiff_encode_buf(const uint8_t* pix, int w, int h, int c,
                     std::vector<uint8_t>* out, std::string* err) {
  out->clear();
  TiffMemW m{out, 0};
  TIFF* tif = TIFFClientOpen("mem", "wm", &m, tiffw_read, tiffw_write,
                             tiffw_seek, tiffw_close, tiffw_size,
                             tiff_map_none, tiff_unmap_none);
  if (!tif) {
    *err = "tiff writer open failed";
    return false;
  }
  TIFFSetField(tif, kTagImageWidth, (uint32_t)w);
  TIFFSetField(tif, kTagImageLength, (uint32_t)h);
  TIFFSetField(tif, kTagBitsPerSample, 8);
  TIFFSetField(tif, kTagSamplesPerPixel, c);
  TIFFSetField(tif, kTagRowsPerStrip, (uint32_t)h);  // single strip
  TIFFSetField(tif, kTagCompression, kCompressionLZW);
  TIFFSetField(tif, kTagPhotometric,
               (c == 1) ? kPhotometricMinIsBlack : kPhotometricRGB);
  TIFFSetField(tif, kTagPlanarConfig, kPlanarContig);
  TIFFSetField(tif, kTagOrientation, kOrientTopLeft);
  if (c == 4) {
    uint16_t extra[1] = {kExtraUnassAlpha};
    TIFFSetField(tif, kTagExtraSamples, 1, extra);
  }
  const tiff_msize_t nbytes = (tiff_msize_t)((size_t)w * h * c);
  if (TIFFWriteEncodedStrip(tif, 0, const_cast<uint8_t*>(pix), nbytes) < 0) {
    TIFFClose(tif);
    *err = "tiff encode failed";
    return false;
  }
  TIFFClose(tif);  // writes the directory
  return true;
}

// -------------------------------------------------------------- Python ------

PyObject* py_decode(PyObject*, PyObject* args) {
  Py_buffer view;
  const char* fmt;
  int scale_denom = 1;
  if (!PyArg_ParseTuple(args, "y*s|i", &view, &fmt, &scale_denom)) return nullptr;
  const uint8_t* buf = static_cast<const uint8_t*>(view.buf);
  size_t len = view.len;
  std::vector<uint8_t> out;
  int w = 0, h = 0, c = 0, orientation = 0;
  std::string err;
  bool ok = false;
  std::string f(fmt);
  Py_BEGIN_ALLOW_THREADS
  if (f == "jpeg") {
    ok = jpeg_decode(buf, len, &out, &w, &h, &c, &err, scale_denom);
    if (ok) orientation = exif_orientation(buf, len);
  } else if (f == "png") {
    ok = png_decode_buf(buf, len, &out, &w, &h, &c, &err);
  } else if (f == "webp") {
#ifndef ITPU_NO_WEBP
    ok = webp_decode_buf(buf, len, &out, &w, &h, &c, &err);
#else
    err = "webp support not built";
#endif
  } else if (f == "gif") {
    ok = gif_decode_buf(buf, len, &out, &w, &h, &c, &err);
  } else if (f == "tiff") {
    ok = tiff_decode_buf(buf, len, &out, &w, &h, &c, &err);
  } else {
    err = "unsupported format: " + f;
  }
  Py_END_ALLOW_THREADS
  PyBuffer_Release(&view);
  if (!ok) {
    PyErr_SetString(PyExc_ValueError, err.empty() ? "decode failed" : err.c_str());
    return nullptr;
  }
  PyObject* bytes = PyBytes_FromStringAndSize(
      reinterpret_cast<const char*>(out.data()), (Py_ssize_t)out.size());
  if (!bytes) return nullptr;
  return Py_BuildValue("(Niiiii)", bytes, h, w, c, orientation, (c == 4) ? 1 : 0);
}

PyObject* py_encode(PyObject*, PyObject* args) {
  Py_buffer view;
  int w, h, c, quality, compression, progressive;
  int palette = 0, speed = 0;
  const char* fmt;
  if (!PyArg_ParseTuple(args, "y*iiisiii|ii", &view, &h, &w, &c, &fmt,
                        &quality, &compression, &progressive, &palette,
                        &speed))
    return nullptr;
  if ((Py_ssize_t)((size_t)w * h * c) != view.len) {
    PyBuffer_Release(&view);
    PyErr_SetString(PyExc_ValueError, "buffer size does not match h*w*c");
    return nullptr;
  }
  const uint8_t* pix = static_cast<const uint8_t*>(view.buf);
  std::vector<uint8_t> out;
  std::vector<uint8_t> flat;
  std::string err;
  bool ok = false;
  std::string f(fmt);
  Py_BEGIN_ALLOW_THREADS
  if (f == "jpeg") {
    const uint8_t* src = pix;
    int cc = c;
    if (c == 4) {  // flatten alpha onto black (libvips JPEG behavior)
      flat.resize((size_t)w * h * 3);
      for (size_t i = 0, n = (size_t)w * h; i < n; i++) {
        uint32_t a = pix[i * 4 + 3];
        flat[i * 3 + 0] = (uint8_t)((pix[i * 4 + 0] * a + 127) / 255);
        flat[i * 3 + 1] = (uint8_t)((pix[i * 4 + 1] * a + 127) / 255);
        flat[i * 3 + 2] = (uint8_t)((pix[i * 4 + 2] * a + 127) / 255);
      }
      src = flat.data();
      cc = 3;
    }
    ok = jpeg_encode(src, w, h, cc, quality, progressive != 0, &out, &err);
  } else if (f == "png") {
    if (progressive || palette || speed > 0)
      ok = png_encode_full(pix, w, h, c, compression, progressive != 0,
                           palette != 0, speed, &out, &err);
    else
      ok = png_encode_buf(pix, w, h, c, &out, &err);
  } else if (f == "webp") {
#ifndef ITPU_NO_WEBP
    const uint8_t* src = pix;
    int cc = c;
    if (c == 1) {
      flat.resize((size_t)w * h * 3);
      for (size_t i = 0, n = (size_t)w * h; i < n; i++)
        flat[i * 3] = flat[i * 3 + 1] = flat[i * 3 + 2] = pix[i];
      src = flat.data();
      cc = 3;
    }
    ok = webp_encode_buf(src, w, h, cc, quality, &out, &err);
#else
    err = "webp support not built";
#endif
  } else if (f == "gif") {
    const uint8_t* src = pix;
    int cc = c;
    if (c == 1) {
      flat.resize((size_t)w * h * 3);
      for (size_t i = 0, n = (size_t)w * h; i < n; i++)
        flat[i * 3] = flat[i * 3 + 1] = flat[i * 3 + 2] = pix[i];
      src = flat.data();
      cc = 3;
    }
    ok = gif_encode_buf(src, w, h, cc, &out, &err);
  } else if (f == "tiff") {
    ok = tiff_encode_buf(pix, w, h, c, &out, &err);
  } else {
    err = "unsupported format: " + f;
  }
  Py_END_ALLOW_THREADS
  PyBuffer_Release(&view);
  if (!ok) {
    PyErr_SetString(PyExc_ValueError, err.empty() ? "encode failed" : err.c_str());
    return nullptr;
  }
  return PyBytes_FromStringAndSize(reinterpret_cast<const char*>(out.data()),
                                   (Py_ssize_t)out.size());
}

PyObject* py_probe(PyObject*, PyObject* args) {
  Py_buffer view;
  const char* fmt;
  if (!PyArg_ParseTuple(args, "y*s", &view, &fmt)) return nullptr;
  const uint8_t* buf = static_cast<const uint8_t*>(view.buf);
  size_t len = view.len;
  int w = 0, h = 0, c = 0, orientation = 0;
  char subsampling[8] = {0};
  bool ok = false;
  std::string f(fmt);
  Py_BEGIN_ALLOW_THREADS
  if (f == "jpeg") {
    ok = jpeg_probe(buf, len, &w, &h, &c, subsampling);
    if (ok) orientation = exif_orientation(buf, len);
  } else if (f == "png") {
    ok = png_probe_buf(buf, len, &w, &h, &c);
  } else if (f == "webp") {
#ifndef ITPU_NO_WEBP
    WebPBitstreamFeatures feat;
    if (WebPGetFeatures(buf, len, &feat) == VP8_STATUS_OK) {
      w = feat.width; h = feat.height; c = feat.has_alpha ? 4 : 3;
      ok = true;
    }
#endif  // probe stays ok=false without webp: binding falls back to PIL
  } else if (f == "gif") {
    ok = gif_probe_buf(buf, len, &w, &h, &c);
  } else if (f == "tiff") {
    ok = tiff_probe_buf(buf, len, &w, &h, &c);
  }
  Py_END_ALLOW_THREADS
  PyBuffer_Release(&view);
  if (!ok) {
    PyErr_SetString(PyExc_ValueError, "probe failed");
    return nullptr;
  }
  return Py_BuildValue("(iiiiis)", w, h, c, (c == 4) ? 1 : 0, orientation,
                       subsampling);
}

PyObject* py_decode_yuv420(PyObject*, PyObject* args) {
  Py_buffer view;
  int scale_denom, hb, wb, y0 = 0, x0 = 0, wh = 0, ww = 0;
  if (!PyArg_ParseTuple(args, "y*iii|iiii", &view, &scale_denom, &hb, &wb,
                        &y0, &x0, &wh, &ww))
    return nullptr;
  const uint8_t* buf = static_cast<const uint8_t*>(view.buf);
  size_t len = view.len;
  std::vector<uint8_t> packed;
  int h = 0, w = 0, orientation = 0;
  std::string err;
  bool ok;
  Py_BEGIN_ALLOW_THREADS
  ok = jpeg_decode_yuv420(buf, len, scale_denom, hb, wb, y0, x0, wh, ww,
                          &packed, &h, &w, &err);
  if (ok) orientation = exif_orientation(buf, len);
  arena_trim();
  Py_END_ALLOW_THREADS
  PyBuffer_Release(&view);
  if (!ok) {
    PyErr_SetString(PyExc_ValueError, err.empty() ? "decode failed" : err.c_str());
    return nullptr;
  }
  PyObject* bytes = PyBytes_FromStringAndSize(
      reinterpret_cast<const char*>(packed.data()), (Py_ssize_t)packed.size());
  if (!bytes) return nullptr;
  return Py_BuildValue("(Niii)", bytes, h, w, orientation);
}

PyObject* py_encode_yuv420(PyObject*, PyObject* args) {
  Py_buffer yv, uv, vv;
  int h, w, quality, progressive;
  if (!PyArg_ParseTuple(args, "y*y*y*iiii", &yv, &uv, &vv, &h, &w, &quality,
                        &progressive))
    return nullptr;
  const int ch = (h + 1) / 2, cw = (w + 1) / 2;
  if (h <= 0 || w <= 0 || yv.len != (Py_ssize_t)((size_t)h * w) ||
      uv.len != (Py_ssize_t)((size_t)ch * cw) ||
      vv.len != (Py_ssize_t)((size_t)ch * cw)) {
    PyBuffer_Release(&yv);
    PyBuffer_Release(&uv);
    PyBuffer_Release(&vv);
    PyErr_SetString(PyExc_ValueError, "plane sizes do not match h/w");
    return nullptr;
  }
  std::vector<uint8_t> out;
  std::string err;
  bool ok;
  Py_BEGIN_ALLOW_THREADS
  ok = jpeg_encode_yuv420(static_cast<const uint8_t*>(yv.buf),
                          static_cast<const uint8_t*>(uv.buf),
                          static_cast<const uint8_t*>(vv.buf), h, w, quality,
                          progressive != 0, &out, &err);
  arena_trim();
  Py_END_ALLOW_THREADS
  PyBuffer_Release(&yv);
  PyBuffer_Release(&uv);
  PyBuffer_Release(&vv);
  if (!ok) {
    PyErr_SetString(PyExc_ValueError, err.empty() ? "encode failed" : err.c_str());
    return nullptr;
  }
  return PyBytes_FromStringAndSize(reinterpret_cast<const char*>(out.data()),
                                   (Py_ssize_t)out.size());
}

PyMethodDef methods[] = {
    {"decode", py_decode, METH_VARARGS,
     "decode(bytes, fmt[, scale_denom]) -> (pixels, h, w, c, orientation, has_alpha)"},
    {"encode", py_encode, METH_VARARGS,
     "encode(buf, h, w, c, fmt, quality, compression, progressive) -> bytes"},
    {"probe", py_probe, METH_VARARGS,
     "probe(bytes, fmt) -> (w, h, c, has_alpha, orientation, subsampling)"},
    {"decode_yuv420", py_decode_yuv420, METH_VARARGS,
     "decode_yuv420(bytes, scale_denom, hb, wb[, y0, x0, wh, ww]) -> "
     "(packed, h, w, orientation)"},
    {"encode_yuv420", py_encode_yuv420, METH_VARARGS,
     "encode_yuv420(y, u, v, h, w, quality, progressive) -> bytes"},
    {"resize_separable", py_resize_separable, METH_VARARGS,
     "resize_separable(buf, h, w, c, dst_h, dst_w, kernel) -> bytes"},
    {"arena_stats", py_arena_stats, METH_NOARGS,
     "arena_stats() -> {reuses, misses, evictions, bytes, cap_bytes}"},
    {"set_arena_cap", py_set_arena_cap, METH_VARARGS,
     "set_arena_cap(mb) — per-thread scratch-arena byte budget, 0 = unlimited"},
    {nullptr, nullptr, 0, nullptr},
};

PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "_imaginary_codecs",
    "Native JPEG/PNG/WEBP codecs (GIL-released)", -1, methods,
};

#else  // ITPU_RESAMPLE_ONLY

PyMethodDef resample_methods[] = {
    {"resize_separable", py_resize_separable, METH_VARARGS,
     "resize_separable(buf, h, w, c, dst_h, dst_w, kernel) -> bytes"},
    {"arena_stats", py_arena_stats, METH_NOARGS,
     "arena_stats() -> {reuses, misses, evictions, bytes, cap_bytes}"},
    {"set_arena_cap", py_set_arena_cap, METH_VARARGS,
     "set_arena_cap(mb) — per-thread scratch-arena byte budget, 0 = unlimited"},
    {nullptr, nullptr, 0, nullptr},
};

PyModuleDef resample_moduledef = {
    PyModuleDef_HEAD_INIT, "_imaginary_resample",
    "Native separable resampler (GIL-released; codec-toolchain-free build)",
    -1, resample_methods,
};

#endif  // ITPU_RESAMPLE_ONLY

}  // namespace

#ifndef ITPU_RESAMPLE_ONLY

PyMODINIT_FUNC PyInit__imaginary_codecs(void) {
  // silence libtiff's stderr chatter: malformed inputs are an expected,
  // gracefully-failed case on the fuzz path, not something to log
  TIFFSetErrorHandler(tiff_quiet);
  TIFFSetWarningHandler(tiff_quiet);
  PyObject* m = PyModule_Create(&moduledef);
  // 5: +decode_yuv420 window; 4: +scratch arena (arena_stats/
  // set_arena_cap); 3: +gif/tiff codecs, +full PNG (interlace/palette/speed)
  if (m) PyModule_AddIntConstant(m, "ABI", 5);
  // what THIS build carries: the binding routes absent formats to cv2/PIL
#ifndef ITPU_NO_WEBP
  if (m) PyModule_AddStringConstant(m, "FORMATS", "jpeg,png,webp,gif,tiff");
#else
  if (m) PyModule_AddStringConstant(m, "FORMATS", "jpeg,png,gif,tiff");
#endif
  return m;
}

#else  // ITPU_RESAMPLE_ONLY

PyMODINIT_FUNC PyInit__imaginary_resample(void) {
  PyObject* m = PyModule_Create(&resample_moduledef);
  if (m) PyModule_AddIntConstant(m, "ABI", 2);  // 2: +scratch arena
  return m;
}

#endif  // ITPU_RESAMPLE_ONLY
