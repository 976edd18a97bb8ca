// Native CPU GF(2^8) matrix-apply: the AVX2 Reed-Solomon fallback.
//
// Role parity: vendor/github.com/klauspost/reedsolomon/galois_amd64.s —
// the reference's CPU hot path is SIMD GF multiply-accumulate. This is
// an original implementation of the standard split-nibble table-lookup
// technique (Plank, Greenan, Miller: "Screaming Fast Galois Field
// Arithmetic Using Intel SIMD Instructions", FAST'13): for each
// coefficient c, two 16-entry tables map the low/high nibble of every
// input byte through PSHUFB/VPSHUFB, and products accumulate with XOR.
// Field: poly 0x11D, generator 2 — bit-identical with ops/gf256.py and
// the device kernels (verified against the pinned independent goldens).
//
// Used as the `cpp` codec engine (codec/engine.py): the CPU leg of the
// measured size-class crossover policy — the numpy table path does
// ~0.08 GiB/s, far below the single-stripe dispatch cost of the device
// path, which made the crossover a foregone conclusion instead of a
// real policy.

#include <cstdint>
#include <cstring>
#include <mutex>

#if defined(__x86_64__) || defined(__i386__)
#include <immintrin.h>
#define GF_X86 1
#endif

namespace {

constexpr uint16_t POLY = 0x11D;

uint8_t MUL[256][256];
std::once_flag mul_once;

void build_tables() {
  // call_once: ctypes drops the GIL, so concurrent first encodes would
  // otherwise read MUL mid-build (silent wrong parity)
  std::call_once(mul_once, [] {
    uint8_t exp[512];
    int log[256] = {0};
    int x = 1;
    for (int i = 0; i < 255; i++) {
      exp[i] = (uint8_t)x;
      log[x] = i;
      x <<= 1;
      if (x & 0x100) x ^= POLY;
    }
    for (int i = 255; i < 510; i++) exp[i] = exp[i - 255];
    for (int a = 0; a < 256; a++)
      for (int b = 0; b < 256; b++)
        MUL[a][b] = (a && b) ? exp[log[a] + log[b]] : 0;
  });
}

// scalar accumulate: out ^= c * in  (last-resort portable path)
void mulacc_scalar(uint8_t c, const uint8_t* in, uint8_t* out, uint64_t s) {
  const uint8_t* row = MUL[c];
  for (uint64_t k = 0; k < s; k++) out[k] ^= row[in[k]];
}

#ifdef GF_X86
__attribute__((target("ssse3"))) void mulacc_ssse3(uint8_t c,
                                                   const uint8_t* in,
                                                   uint8_t* out, uint64_t s) {
  uint8_t lo[16], hi[16];
  for (int v = 0; v < 16; v++) {
    lo[v] = MUL[c][v];
    hi[v] = MUL[c][v << 4];
  }
  __m128i tlo = _mm_loadu_si128((const __m128i*)lo);
  __m128i thi = _mm_loadu_si128((const __m128i*)hi);
  __m128i mask = _mm_set1_epi8(0x0F);
  uint64_t k = 0;
  for (; k + 16 <= s; k += 16) {
    __m128i x = _mm_loadu_si128((const __m128i*)(in + k));
    __m128i y = _mm_loadu_si128((const __m128i*)(out + k));
    __m128i pl = _mm_shuffle_epi8(tlo, _mm_and_si128(x, mask));
    __m128i ph = _mm_shuffle_epi8(
        thi, _mm_and_si128(_mm_srli_epi64(x, 4), mask));
    y = _mm_xor_si128(y, _mm_xor_si128(pl, ph));
    _mm_storeu_si128((__m128i*)(out + k), y);
  }
  for (; k < s; k++) out[k] ^= MUL[c][in[k]];
}

__attribute__((target("avx2"))) void mulacc_avx2(uint8_t c, const uint8_t* in,
                                                 uint8_t* out, uint64_t s) {
  uint8_t lo[16], hi[16];
  for (int v = 0; v < 16; v++) {
    lo[v] = MUL[c][v];
    hi[v] = MUL[c][v << 4];
  }
  __m256i tlo = _mm256_broadcastsi128_si256(
      _mm_loadu_si128((const __m128i*)lo));
  __m256i thi = _mm256_broadcastsi128_si256(
      _mm_loadu_si128((const __m128i*)hi));
  __m256i mask = _mm256_set1_epi8(0x0F);
  uint64_t k = 0;
  for (; k + 32 <= s; k += 32) {
    __m256i x = _mm256_loadu_si256((const __m256i*)(in + k));
    __m256i y = _mm256_loadu_si256((const __m256i*)(out + k));
    __m256i pl = _mm256_shuffle_epi8(tlo, _mm256_and_si256(x, mask));
    __m256i ph = _mm256_shuffle_epi8(
        thi, _mm256_and_si256(_mm256_srli_epi64(x, 4), mask));
    y = _mm256_xor_si256(y, _mm256_xor_si256(pl, ph));
    _mm256_storeu_si256((__m256i*)(out + k), y);
  }
  for (; k < s; k++) out[k] ^= MUL[c][in[k]];
}
#endif

using MulAccFn = void (*)(uint8_t, const uint8_t*, uint8_t*, uint64_t);

MulAccFn pick_mulacc() {
#ifdef GF_X86
  if (__builtin_cpu_supports("avx2")) return mulacc_avx2;
  if (__builtin_cpu_supports("ssse3")) return mulacc_ssse3;
#endif
  return mulacc_scalar;
}

}  // namespace

extern "C" {

// out[b,i,:] = XOR_j mat[i*n+j] (x) in[b,j,:]   (contiguous uint8 views)
void gf_apply(const uint8_t* mat, uint64_t m, uint64_t n, const uint8_t* in,
              uint8_t* out, uint64_t s, uint64_t batch) {
  build_tables();
  MulAccFn mulacc = pick_mulacc();
  for (uint64_t b = 0; b < batch; b++) {
    const uint8_t* ib = in + b * n * s;
    uint8_t* ob = out + b * m * s;
    for (uint64_t i = 0; i < m; i++) {
      uint8_t* dst = ob + i * s;
      memset(dst, 0, s);
      for (uint64_t j = 0; j < n; j++) {
        uint8_t c = mat[i * n + j];
        if (c == 0) continue;
        mulacc(c, ib + j * s, dst, s);
      }
    }
  }
}

// which SIMD path gf_apply will take: 2=avx2, 1=ssse3, 0=scalar
int gf_cpu_level() {
#ifdef GF_X86
  if (__builtin_cpu_supports("avx2")) return 2;
  if (__builtin_cpu_supports("ssse3")) return 1;
#endif
  return 0;
}

}  // extern "C"
