#include "tbase/crc32c.h"

#if defined(__x86_64__)
#include <nmmintrin.h>
#endif

namespace tpurpc {

namespace {

constexpr uint32_t kPoly = 0x82F63B78u;

// 8 tables of 256 entries, built once (slice-by-8).
struct Tables {
    uint32_t t[8][256];
    Tables() {
        for (uint32_t i = 0; i < 256; ++i) {
            uint32_t c = i;
            for (int k = 0; k < 8; ++k) {
                c = (c & 1) ? (kPoly ^ (c >> 1)) : (c >> 1);
            }
            t[0][i] = c;
        }
        for (int j = 1; j < 8; ++j) {
            for (uint32_t i = 0; i < 256; ++i) {
                t[j][i] = (t[j - 1][i] >> 8) ^ t[0][t[j - 1][i] & 0xff];
            }
        }
    }
};

const Tables& tables() {
    static const Tables tb;
    return tb;
}

inline uint64_t load64(const uint8_t* p) {
    uint64_t w;
    __builtin_memcpy(&w, p, 8);
    return w;
}

inline void store64(uint8_t* d, uint64_t w) { __builtin_memcpy(d, &w, 8); }

// Both paths below are ONE pass for the checksum and for the checksummed
// copy (kCopy; without it `d` is never touched). They work on the raw
// register (the caller inverts going in and coming out).

template <bool kCopy>
uint32_t pass_tables(uint32_t crc, uint8_t* __restrict d,
                     const uint8_t* __restrict p, size_t n) {
    const Tables& tb = tables();
    for (; n > 0 && ((uintptr_t)p & 7) != 0; --n, ++p) {
        crc = tb.t[0][(crc ^ *p) & 0xff] ^ (crc >> 8);
        if (kCopy) *d++ = *p;
    }
    for (; n >= 8; n -= 8, p += 8) {
        const uint64_t word = load64(p);
        const uint64_t w = word ^ crc;
        crc = tb.t[7][w & 0xff] ^ tb.t[6][(w >> 8) & 0xff] ^
              tb.t[5][(w >> 16) & 0xff] ^ tb.t[4][(w >> 24) & 0xff] ^
              tb.t[3][(w >> 32) & 0xff] ^ tb.t[2][(w >> 40) & 0xff] ^
              tb.t[1][(w >> 48) & 0xff] ^ tb.t[0][(w >> 56) & 0xff];
        if (kCopy) {
            store64(d, word);
            d += 8;
        }
    }
    for (; n > 0; --n, ++p) {
        crc = tb.t[0][(crc ^ *p) & 0xff] ^ (crc >> 8);
        if (kCopy) *d++ = *p;
    }
    return crc;
}

#if defined(__x86_64__)
// Hardware path (ISSUE 9): crc32c IS the Castagnoli polynomial the
// SSE4.2 CRC32 instruction implements. One chain of it is bound by the
// instruction's latency (8 bytes per 3 cycles: the 6.6 GB/s the TPU
// host's framer read, PERF.md section 5); the cpu issues one a cycle, so
// three independent chains run over three adjacent lanes and are joined
// afterwards (ISSUE 30). Joining needs "the register after `lane` more
// zero bytes", which is linear in the register: a 32x32 matrix over
// GF(2), applied through four byte-indexed tables.

// Columns of a 32x32 GF(2) matrix; times(m, v) = m * v.
uint32_t times(const uint32_t* m, uint32_t v) {
    uint32_t sum = 0;
    for (; v != 0; v >>= 1, ++m) {
        if (v & 1) sum ^= *m;
    }
    return sum;
}

// shift(crc) = the register `lane` zero bytes after it read `crc`
// (`lane` a power of two).
struct Shift {
    uint32_t t[4][256];
    explicit Shift(size_t lane) {
        uint32_t op[32], sq[32];
        op[0] = kPoly;  // one zero bit
        for (int i = 1; i < 32; ++i) op[i] = 1u << (i - 1);
        for (size_t bits = 1; bits < 8 * lane; bits <<= 1) {
            for (int i = 0; i < 32; ++i) sq[i] = times(op, op[i]);
            __builtin_memcpy(op, sq, sizeof(op));
        }
        for (int k = 0; k < 4; ++k) {
            for (uint32_t b = 0; b < 256; ++b) {
                t[k][b] = times(op, b << (8 * k));
            }
        }
    }
    uint32_t operator()(uint32_t crc) const {
        return t[0][crc & 0xff] ^ t[1][(crc >> 8) & 0xff] ^
               t[2][(crc >> 16) & 0xff] ^ t[3][crc >> 24];
    }
};

// Lanes of 8192 bytes for bulk, of 256 for what is left and for an 8 KB
// link block, so that a few hundred bytes still run three chains. (On
// the TPU host lanes of 1024, 2048 or 4096 read slower than either, with
// and without the copy: PERF.md section 6, PR 30.)
constexpr size_t kLongLane = 8192;
constexpr size_t kShortLane = 256;
// How far ahead of the copy the destination's lines are asked for, for
// writing. In the staging ring they were last read on another core (the
// H2D), and eight-byte stores alone wait for each line's ownership in
// turn: the copy read 266 us a MiB where memcpy reads 172-184; with the
// lines asked for ahead and 16-byte stores, 185-187 (PERF.md section 6,
// PR 30). A cpu without PREFETCHW runs it as a no-op.
constexpr size_t kWriteAhead = 1024;

// As many whole triples of kLane-byte lanes as [p, p+n) holds. The three
// chains each take one 64-byte line of their lane an iteration; the copy
// is ONE stream in address order, three lines an iteration, so a triple is
// read from memory once, by whichever of the two comes to a line first,
// and the other finds it in L1.
template <bool kCopy, size_t kLane>
__attribute__((target("sse4.2"))) inline void
pass_lanes(uint64_t& c0, uint8_t* __restrict& d, const uint8_t* __restrict& p,
           size_t& n, const Shift& shift) {
    for (; n >= 3 * kLane; n -= 3 * kLane, p += 3 * kLane) {
        uint64_t c1 = 0, c2 = 0;
        const uint8_t* q = p;  // the copy's place in the triple
        for (size_t i = 0; i < kLane; i += 64) {
            if (kCopy) {
#pragma GCC unroll 3
                for (size_t line = 0; line < 192; line += 64) {
                    __asm__ volatile("prefetchw %0"
                                     :
                                     : "m"(d[kWriteAhead + line]));
                }
#pragma GCC unroll 12
                for (size_t k = 0; k < 192; k += 16) {
                    _mm_storeu_si128(
                        (__m128i*)(d + k),
                        _mm_loadu_si128((const __m128i*)(q + k)));
                }
                q += 192;
                d += 192;
            }
#pragma GCC unroll 8
            for (size_t j = i; j < i + 64; j += 8) {
                c0 = _mm_crc32_u64(c0, load64(p + j));
                c1 = _mm_crc32_u64(c1, load64(p + kLane + j));
                c2 = _mm_crc32_u64(c2, load64(p + 2 * kLane + j));
            }
        }
        c0 = shift(shift((uint32_t)c0) ^ (uint32_t)c1) ^ (uint32_t)c2;
    }
}

template <bool kCopy>
__attribute__((target("sse4.2"))) uint32_t
pass_sse42(uint32_t crc, uint8_t* __restrict d, const uint8_t* __restrict p,
           size_t n) {
    static const Shift shift_long(kLongLane), shift_short(kShortLane);
    for (; n > 0 && ((uintptr_t)p & 7) != 0; --n, ++p) {
        crc = _mm_crc32_u8(crc, *p);
        if (kCopy) *d++ = *p;
    }
    uint64_t c64 = crc;
    pass_lanes<kCopy, kLongLane>(c64, d, p, n, shift_long);
    pass_lanes<kCopy, kShortLane>(c64, d, p, n, shift_short);
    for (; n >= 8; n -= 8, p += 8) {
        const uint64_t w = load64(p);
        c64 = _mm_crc32_u64(c64, w);
        if (kCopy) {
            store64(d, w);
            d += 8;
        }
    }
    crc = (uint32_t)c64;
    for (; n > 0; --n, ++p) {
        crc = _mm_crc32_u8(crc, *p);
        if (kCopy) *d++ = *p;
    }
    return crc;
}

bool has_sse42() {
    static const bool yes = __builtin_cpu_supports("sse4.2");
    return yes;
}
#endif

template <bool kCopy>
uint32_t pass(uint32_t crc, void* dst, const void* src, size_t n) {
#if defined(__x86_64__)
    if (has_sse42()) {
        return ~pass_sse42<kCopy>(~crc, (uint8_t*)dst, (const uint8_t*)src,
                                  n);
    }
#endif
    return ~pass_tables<kCopy>(~crc, (uint8_t*)dst, (const uint8_t*)src, n);
}

}  // namespace

uint32_t crc32c_extend(uint32_t crc, const void* data, size_t n) {
    return pass<false>(crc, nullptr, data, n);
}

uint32_t crc32c_copy_extend(uint32_t crc, void* dst, const void* src,
                            size_t n) {
    return pass<true>(crc, dst, src, n);
}

uint32_t crc32c_copy_extend_tables(uint32_t crc, void* dst, const void* src,
                                   size_t n) {
    const uint8_t* p = (const uint8_t*)src;
    return dst != nullptr ? ~pass_tables<true>(~crc, (uint8_t*)dst, p, n)
                          : ~pass_tables<false>(~crc, nullptr, p, n);
}

}  // namespace tpurpc
