// Host-side native kernels (C++), loaded via ctypes.
//
// Reference parity: the reference backs its sequential host loops with amd64
// assembly + unsafe Go (SURVEY.md §2.3: encoding/plain BYTE_ARRAY scan,
// encoding/rle run parsing, bloom/xxhash, hashprobe dictionary dedup,
// encoding/delta byte-array prefix reconstruction).  These are exactly the
// loops that cannot vectorize onto TPU lanes (data-dependent byte walks), so
// they get native host code here; everything data-parallel lives in the
// XLA/Pallas kernels instead.
//
// Build: parquet_tpu/native/build.py → _native.so (g++ -O3).  Pure C ABI —
// no pybind11 (not in this image); numpy arrays cross as raw pointers.

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstring>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

#if defined(__SSSE3__)
#include <immintrin.h>  // SSSE3 pshufb (snappy short-offset replication)
#endif
#if defined(__AVX512F__) && defined(__BMI2__)
#ifndef __SSSE3__
#include <immintrin.h>
#endif
#define PQ_HAVE_AVX512 1
#endif

namespace {

// Expand the low `k` bits of `bits` into k 0/1 bytes at dst (order-preserving).
// The magic multiply spreads 8 bits across the 8 bytes of a u64 in one step.
inline void expand_bits_to_bytes(uint64_t bits, int k, uint8_t* dst) {
  int t = 0;
  for (; t + 8 <= k; t += 8, bits >>= 8) {
    // replicate the byte, isolate bit i in byte i, normalize to 0/1
    uint64_t m = ((bits & 0xFF) * 0x0101010101010101ULL) & 0x8040201008040201ULL;
    uint64_t spread = ((m + 0x7F7F7F7F7F7F7F7FULL) >> 7) & 0x0101010101010101ULL;
    std::memcpy(dst + t, &spread, 8);
  }
  for (; t < k; ++t, bits >>= 1) dst[t] = (uint8_t)(bits & 1);
}

// Bounds-checked LSB-first uvarint emit shared by the native encoders.
inline bool put_uvarint(uint8_t* out, int64_t cap, int64_t& o, uint64_t v) {
  while (v >= 0x80) {
    if (o >= cap) return false;
    out[o++] = (uint8_t)(v | 0x80);
    v >>= 7;
  }
  if (o >= cap) return false;
  out[o++] = (uint8_t)v;
  return true;
}

inline uint64_t load8_clamped(const uint8_t* buf, int64_t buf_len, int64_t byte0) {
  uint64_t word = 0;
  if (byte0 + 8 <= buf_len) {
    std::memcpy(&word, buf + byte0, 8);
  } else {
    for (int b = 0; b < 8 && byte0 + b < buf_len; ++b)
      word |= (uint64_t)buf[byte0 + b] << (8 * b);
  }
  return word;
}

// Unpack cnt w-bit values starting at bit offset `bit` into dst.  One 8-byte
// load yields floor(57/w) values (57 = 64 minus the worst bit phase) — level
// streams are 1-3 bits wide, so this is ~20-57 values per load.
inline void unpack_bits_span(const uint8_t* buf, int64_t buf_len, int64_t bit,
                             int32_t w, int64_t cnt, int32_t* dst) {
  const uint64_t mask = (w >= 32) ? 0xFFFFFFFFull : ((1ull << w) - 1);
  if (w <= 28) {
    const int kper = 57 / w;
    int64_t j = 0;
    while (j < cnt) {
      uint64_t word = load8_clamped(buf, buf_len, bit >> 3) >> (bit & 7);
      int m = (int)((cnt - j < kper) ? (cnt - j) : kper);
      for (int t = 0; t < m; ++t)
        dst[j + t] = (int32_t)((word >> (t * w)) & mask);
      j += m;
      bit += (int64_t)m * w;
    }
  } else {
    for (int64_t j = 0; j < cnt; ++j) {
      uint64_t word = load8_clamped(buf, buf_len, bit >> 3);
      dst[j] = (int32_t)((word >> (bit & 7)) & mask);
      bit += w;
    }
  }
}

#ifdef PQ_HAVE_AVX512
// 64-slot bitmap compaction shared by pq_assemble_levels and the fused list
// assembler: write instance validity + leaf validity bytes via pext/spread,
// and per-instance offsets (elements strictly before the instance bit) via a
// tzcnt walk.  Advances *ninst/*elems.
inline void compact_block64(uint64_t inst_w, uint64_t elem_w, uint64_t valge_w,
                            uint64_t eq_w, int64_t* offsets, uint8_t* lvalid,
                            uint8_t* leaf_valid /* may be null */,
                            int64_t* ninst, int64_t* elems) {
  expand_bits_to_bytes(_pext_u64(valge_w, inst_w),
                       (int)_mm_popcnt_u64(inst_w), lvalid + *ninst);
  if (leaf_valid)
    expand_bits_to_bytes(_pext_u64(eq_w, elem_w), (int)_mm_popcnt_u64(elem_w),
                         leaf_valid + *elems);
  uint64_t iw = inst_w;
  while (iw) {
    const int p = (int)_tzcnt_u64(iw);
    iw = _blsr_u64(iw);
    offsets[(*ninst)++] =
        *elems + _mm_popcnt_u64(elem_w & (((uint64_t)1 << p) - 1));
  }
  *elems += _mm_popcnt_u64(elem_w);
}
#endif

}  // namespace

extern "C" {

// ---------------------------------------------------------------------------
// PLAIN BYTE_ARRAY: walk [4B LE length][bytes]... building offsets, and
// optionally compacting the value bytes (prefixes stripped) into out_values.
// Returns total value bytes, or -1 on truncation.
// ---------------------------------------------------------------------------
int64_t pq_plain_byte_array(const uint8_t* data, int64_t size, int64_t n,
                            int64_t* offsets /* n+1 */,
                            uint8_t* out_values /* may be null */) {
  int64_t pos = 0;
  int64_t total = 0;
  offsets[0] = 0;
  for (int64_t i = 0; i < n; i++) {
    if (pos + 4 > size) return -1;
    uint32_t len;
    std::memcpy(&len, data + pos, 4);
    pos += 4;
    if (pos + (int64_t)len > size) return -1;
    if (out_values) std::memcpy(out_values + total, data + pos, len);
    pos += len;
    total += len;
    offsets[i + 1] = total;
  }
  return total;
}

// ---------------------------------------------------------------------------
// PLAIN BYTE_ARRAY encode: values+offsets -> [4B LE length][bytes]...
// (write twin of pq_plain_byte_array).  Returns bytes written.
// ---------------------------------------------------------------------------
int64_t pq_encode_plain_ba(const uint8_t* vals, const int64_t* offs, int64_t n,
                           int64_t vals_len, uint8_t* out) {
  if (n > 0 && (offs[0] != 0 || offs[n] > vals_len)) return -1;
  int64_t o = 0;
  for (int64_t i = 0; i < n; ++i) {
    const int64_t len = offs[i + 1] - offs[i];
    // caller-supplied offsets are untrusted: a negative or oversized length
    // would wrap the uint32 and memcpy far past both buffers
    if (len < 0 || len > 0xFFFFFFFFll) return -1;
    const uint32_t len32 = (uint32_t)len;
    std::memcpy(out + o, &len32, 4);
    o += 4;
    std::memcpy(out + o, vals + offs[i], (size_t)len);
    o += len;
  }
  return o;
}

// ---------------------------------------------------------------------------
// Expand a merged run table (host twin of the device rle_expand kernel, used
// for nested-column level streams that the host record assembler consumes).
// Runs tile the output contiguously: run i covers [ends[i-1], ends[i]).
// Returns values written.
// ---------------------------------------------------------------------------
int64_t pq_expand_runs(const uint8_t* buf, int64_t buf_len, const int64_t* ends,
                       const uint8_t* kinds, const int64_t* payloads,
                       const int64_t* bit_offsets, const int32_t* widths,
                       int64_t nruns, int32_t* out, int64_t n) {
  int64_t pos = 0;
  for (int64_t i = 0; i < nruns && pos < n; ++i) {
    int64_t cnt = ends[i] - pos;
    if (cnt > n - pos) cnt = n - pos;
    if (cnt <= 0) continue;
    if (kinds[i] == 0) {
      const int32_t v = (int32_t)payloads[i];
      for (int64_t j = 0; j < cnt; ++j) out[pos + j] = v;
    } else {
      unpack_bits_span(buf, buf_len, bit_offsets[i], widths[i], cnt, out + pos);
    }
    pos += cnt;
  }
  return pos;
}

// ---------------------------------------------------------------------------
// Dremel record assembly: def/rep level streams → per-repeated-level
// (offsets, validity) + leaf validity, single pass per level.
// ks/dks: rep and def level of each repeated ancestor, outermost first.
// offsets_flat: nlev*(n+1) i64; valid_flat: nlev*n u8; inst_counts: nlev i64.
// leaf_valid: n u8.  Returns leaf element count.
// ---------------------------------------------------------------------------
int64_t pq_assemble_levels(const int32_t* defs, const int32_t* reps, int64_t n,
                           const int32_t* ks, const int32_t* dks, int32_t nlev,
                           int32_t max_def, int64_t* offsets_flat,
                           uint8_t* valid_flat, int64_t* inst_counts,
                           uint8_t* leaf_valid) {
#ifdef PQ_HAVE_AVX512
  // Vectorized: 64-slot bitmaps from AVX-512 compares, then per-word
  // stream compaction — offsets via tzcnt walk over instance bits (instances
  // are ~rows, far fewer than slots), validity bytes via pext + bit spread.
  const int64_t nw = n / 64;
  for (int32_t i = 0; i < nlev; ++i) {
    const int32_t k = ks[i], dk = dks[i];
    const int32_t dprev = (i > 0) ? dks[i - 1] : INT32_MIN;
    const int32_t knext = (i + 1 < nlev) ? ks[i + 1] : INT32_MAX;
    int64_t* offs = offsets_flat + (int64_t)i * (n + 1);
    uint8_t* val = valid_flat + (int64_t)i * n;
    int64_t ninst = 0, elems = 0;
    const __m512i kv = _mm512_set1_epi32(k);
    const __m512i dprevv = _mm512_set1_epi32(dprev);
    const __m512i knextv = _mm512_set1_epi32(knext);
    const __m512i dkv = _mm512_set1_epi32(dk);
    const __m512i dkm1v = _mm512_set1_epi32(dk - 1);
    for (int64_t wi = 0; wi < nw; ++wi) {
      uint64_t inst_w = 0, elem_w = 0, valge_w = 0;
      const int64_t j0 = wi * 64;
      for (int g = 0; g < 4; ++g) {
        const __m512i dv = _mm512_loadu_si512(defs + j0 + g * 16);
        const __m512i rv = _mm512_loadu_si512(reps + j0 + g * 16);
        uint64_t im = _mm512_cmplt_epi32_mask(rv, kv) &
                      _mm512_cmple_epi32_mask(dprevv, dv);
        uint64_t em = _mm512_cmplt_epi32_mask(rv, knextv) &
                      _mm512_cmple_epi32_mask(dkv, dv);
        uint64_t vm = _mm512_cmple_epi32_mask(dkm1v, dv);
        inst_w |= im << (g * 16);
        elem_w |= em << (g * 16);
        valge_w |= vm << (g * 16);
      }
      compact_block64(inst_w, elem_w, valge_w, 0, offs, val, nullptr, &ninst,
                      &elems);
    }
    for (int64_t j = nw * 64; j < n; ++j) {
      const int32_t dj = defs[j], rj = reps[j];
      offs[ninst] = elems;
      val[ninst] = dj >= dk - 1;
      ninst += (rj < k) & (dj >= dprev);
      elems += (rj < knext) & (dj >= dk);
    }
    offs[ninst] = elems;
    inst_counts[i] = ninst;
  }
  const int32_t dr = dks[nlev - 1];
  const __m512i drv = _mm512_set1_epi32(dr);
  const __m512i mdv = _mm512_set1_epi32(max_def);
  int64_t cnt = 0;
  for (int64_t wi = 0; wi < nw; ++wi) {
    uint64_t ge_w = 0, eq_w = 0;
    for (int g = 0; g < 4; ++g) {
      const __m512i dv = _mm512_loadu_si512(defs + wi * 64 + g * 16);
      ge_w |= (uint64_t)_mm512_cmple_epi32_mask(drv, dv) << (g * 16);
      eq_w |= (uint64_t)_mm512_cmpeq_epi32_mask(dv, mdv) << (g * 16);
    }
    const int kk = (int)_mm_popcnt_u64(ge_w);
    expand_bits_to_bytes(_pext_u64(eq_w, ge_w), kk, leaf_valid + cnt);
    cnt += kk;
  }
  for (int64_t j = nw * 64; j < n; ++j) {
    const int32_t dj = defs[j];
    leaf_valid[cnt] = dj == max_def;
    cnt += dj >= dr;
  }
  return cnt;
#else
  for (int32_t i = 0; i < nlev; ++i) {
    const int32_t k = ks[i], dk = dks[i];
    const int32_t dprev = (i > 0) ? dks[i - 1] : INT32_MIN;
    const int32_t knext = (i + 1 < nlev) ? ks[i + 1] : INT32_MAX;
    int64_t* offs = offsets_flat + (int64_t)i * (n + 1);
    uint8_t* val = valid_flat + (int64_t)i * n;
    int64_t ninst = 0, elems = 0;
    // branchless: always store at the cursor, advance conditionally (stale
    // stores are overwritten by the next instance / the final sentinel)
    for (int64_t j = 0; j < n; ++j) {
      const int32_t dj = defs[j], rj = reps[j];
      offs[ninst] = elems;
      val[ninst] = dj >= dk - 1;
      ninst += (rj < k) & (dj >= dprev);
      elems += (rj < knext) & (dj >= dk);
    }
    offs[ninst] = elems;
    inst_counts[i] = ninst;
  }
  const int32_t dr = dks[nlev - 1];
  int64_t cnt = 0;
  for (int64_t j = 0; j < n; ++j) {
    const int32_t dj = defs[j];
    leaf_valid[cnt] = dj == max_def;
    cnt += dj >= dr;
  }
  return cnt;
#endif
}

// ---------------------------------------------------------------------------
// LSB-first bit packing (write-path twin of unpack_bits_span; the hottest
// loop of the RLE/dict encoder).  w <= 56 keeps acc|= from overflowing with
// nb < 8 residual bits.  Returns bytes written, or -1 for unsupported width.
// ---------------------------------------------------------------------------
int64_t pq_pack_bits(const int64_t* vals, int64_t n, int32_t w, uint8_t* out) {
  if (w <= 0) return 0;
  if (w > 56) return -1;
  const uint64_t mask = (1ull << w) - 1;
  uint64_t acc = 0;
  int nb = 0;
  int64_t o = 0;
  for (int64_t i = 0; i < n; ++i) {
    acc |= ((uint64_t)vals[i] & mask) << nb;
    nb += w;
    while (nb >= 8) {
      out[o++] = (uint8_t)acc;
      acc >>= 8;
      nb -= 8;
    }
  }
  if (nb) out[o++] = (uint8_t)acc;
  return o;
}

// ---------------------------------------------------------------------------
// BYTE_ARRAY dictionary gather: indices -> concatenated value bytes +
// offsets.  Two-call pattern: out_vals == null computes offsets and returns
// the total byte count; second call memcpys the bytes.
// ---------------------------------------------------------------------------
int64_t pq_gather_ba(const uint8_t* dvals, const int64_t* doffs, int64_t ndict,
                     const int64_t* indices, int64_t n, int64_t* out_offs,
                     uint8_t* out_vals) {
  int64_t total = 0;
  if (!out_vals) {
    out_offs[0] = 0;
    for (int64_t i = 0; i < n; ++i) {
      const int64_t d = indices[i];
      if (d < 0 || d >= ndict) return -1;
      total += doffs[d + 1] - doffs[d];
      out_offs[i + 1] = total;
    }
    return total;
  }
  for (int64_t i = 0; i < n; ++i) {
    const int64_t d = indices[i];
    std::memcpy(out_vals + out_offs[i], dvals + doffs[d],
                (size_t)(doffs[d + 1] - doffs[d]));
  }
  return out_offs[n];
}

// ---------------------------------------------------------------------------
// RLE/bit-packed hybrid encoder (write-path twin of pq_scan_rle_runs),
// byte-identical to the Python oracle: runs >= max(min_repeat, 8) become RLE
// runs (after donating alignment values to the preceding packed span);
// everything between becomes one bit-packed span of whole 8-value groups.
// Returns bytes written, -1 on insufficient cap, -2 for unsupported width.
// ---------------------------------------------------------------------------
int64_t pq_encode_rle(const int64_t* vals, int64_t n, int32_t w,
                      int32_t min_repeat, uint8_t* out, int64_t cap) {
  if (w <= 0 || w > 56 || n == 0) return -2;
  int64_t o = 0;
  const auto put_uv = [&](uint64_t v) { return put_uvarint(out, cap, o, v); };
  const int vbytes = (w + 7) / 8;
  const uint64_t vmask = (vbytes >= 8) ? ~0ull : ((1ull << (8 * vbytes)) - 1);
  const uint64_t mask = (1ull << w) - 1;
  const int64_t thresh = min_repeat < 8 ? 8 : min_repeat;
  const auto emit_packed = [&](int64_t s, int64_t cnt) -> bool {
    if (!cnt) return true;
    const int64_t ngroups = (cnt + 7) / 8;
    if (!put_uv(((uint64_t)ngroups << 1) | 1)) return false;
    uint64_t acc = 0;
    int nb = 0;
    for (int64_t i = 0; i < ngroups * 8; ++i) {
      const uint64_t v = (i < cnt) ? ((uint64_t)vals[s + i] & mask) : 0;
      acc |= v << nb;
      nb += w;
      while (nb >= 8) {
        if (o >= cap) return false;
        out[o++] = (uint8_t)acc;
        acc >>= 8;
        nb -= 8;
      }
    }
    return true;  // 8*w bits per group: nb always ends at 0
  };
  int64_t pos = 0, i = 0;
  while (i < n) {
    const int64_t v = vals[i];
    int64_t j = i + 1;
    while (j < n && vals[j] == v) ++j;
    const int64_t len = j - i;
    if (len >= thresh) {
      const int64_t pad = (8 - ((i - pos) & 7)) & 7;
      if (len - pad >= min_repeat) {
        if (!emit_packed(pos, i + pad - pos)) return -1;
        if (!put_uv((uint64_t)(len - pad) << 1)) return -1;
        const uint64_t ev = (uint64_t)v & vmask;
        for (int b = 0; b < vbytes; ++b) {
          if (o >= cap) return -1;
          out[o++] = (uint8_t)(ev >> (8 * b));
        }
        pos = j;
      }
    }
    i = j;
  }
  if (!emit_packed(pos, n - pos)) return -1;
  return o;
}

// ---------------------------------------------------------------------------
// DELTA_BINARY_PACKED encoder (write-path twin of pq_delta_prescan),
// byte-identical to the Python oracle: per block, zigzag min delta, per-
// miniblock bit widths, LSB-first packed adjusted deltas (128-bit
// accumulator: widths reach 64).  Returns bytes, -1 on cap, -2 unsupported.
// ---------------------------------------------------------------------------
int64_t pq_encode_delta(const int64_t* vals, int64_t n, int32_t block_size,
                        int32_t nmb, uint8_t* out, int64_t cap) {
  if (block_size <= 0 || nmb <= 0 || nmb > 256 || block_size % nmb) return -2;
  int64_t o = 0;
  const auto put_uv = [&](uint64_t v) { return put_uvarint(out, cap, o, v); };
  const auto zz = [](int64_t v) {
    return ((uint64_t)v << 1) ^ (uint64_t)(v >> 63);
  };
  if (!put_uv((uint64_t)block_size) || !put_uv((uint64_t)nmb) ||
      !put_uv((uint64_t)n))
    return -1;
  if (n == 0) return put_uv(0) ? o : -1;
  if (!put_uv(zz(vals[0]))) return -1;
  if (n == 1) return o;
  const int vpm = block_size / nmb;
  std::vector<uint64_t> adj(block_size);
  for (int64_t bstart = 0; bstart < n - 1; bstart += block_size) {
    const int64_t cnt =
        (n - 1 - bstart < block_size) ? (n - 1 - bstart) : block_size;
    int64_t mind = INT64_MAX;
    for (int64_t i = 0; i < cnt; ++i) {
      const int64_t d = (int64_t)((uint64_t)vals[bstart + i + 1] -
                                  (uint64_t)vals[bstart + i]);
      adj[i] = (uint64_t)d;
      if (d < mind) mind = d;
    }
    if (!put_uv(zz(mind))) return -1;
    for (int64_t i = 0; i < cnt; ++i) adj[i] -= (uint64_t)mind;
    uint8_t widths[256];
    for (int m = 0; m < nmb; ++m) {
      const int64_t lo = (int64_t)m * vpm;
      uint64_t mx = 0;
      for (int64_t i = lo; i < lo + vpm && i < cnt; ++i)
        mx |= adj[i];  // OR has the same MSB as max
      widths[m] = (lo >= cnt || mx == 0) ? 0 : (uint8_t)(64 - __builtin_clzll(mx));
    }
    if (o + nmb > cap) return -1;
    std::memcpy(out + o, widths, nmb);
    o += nmb;
    const int last_nonempty = (int)((cnt - 1) / vpm);
    for (int m = 0; m <= last_nonempty; ++m) {
      const int w = widths[m];
      if (w == 0) continue;
      const int64_t lo = (int64_t)m * vpm;
      unsigned __int128 acc = 0;
      int nb = 0;
      const uint64_t mask = (w >= 64) ? ~0ull : ((1ull << w) - 1);
      for (int i = 0; i < vpm; ++i) {
        const uint64_t v = (lo + i < cnt) ? (adj[lo + i] & mask) : 0;
        acc |= (unsigned __int128)v << nb;
        nb += w;
        while (nb >= 8) {
          if (o >= cap) return -1;
          out[o++] = (uint8_t)acc;
          acc >>= 8;
          nb -= 8;
        }
      }
      if (nb) {
        if (o >= cap) return -1;
        out[o++] = (uint8_t)acc;
      }
    }
  }
  return o;
}

// ---------------------------------------------------------------------------
// DELTA_BINARY_PACKED miniblock pre-scan (host half of the delta split):
// walks uvarint headers once, O(miniblocks).  header_out = {first, total,
// vpm, end_pos}; returns miniblock count, or -1 on truncation/overflow
// (caller falls back to the Python scanner).
// ---------------------------------------------------------------------------
int64_t pq_delta_prescan(const uint8_t* data, int64_t size, int64_t pos,
                         int64_t* header_out, int64_t* offsets,
                         int32_t* widths, int64_t* mins, int64_t cap) {
  const auto uvarint = [&](int64_t& p, uint64_t& v) -> bool {
    v = 0;
    int sh = 0;
    while (true) {
      if (p >= size || sh > 63) return false;
      const uint8_t b = data[p++];
      if (sh == 63 && (b & 0x7E)) return false;  // >= 2^64: reject, don't wrap
      v |= (uint64_t)(b & 0x7F) << sh;
      if (!(b & 0x80)) return true;
      sh += 7;
    }
  };
  const auto unzigzag = [](uint64_t r) {
    return (int64_t)(r >> 1) ^ -(int64_t)(r & 1);
  };
  uint64_t bs, nmb, total, fraw;
  if (!uvarint(pos, bs) || !uvarint(pos, nmb) || !uvarint(pos, total) ||
      !uvarint(pos, fraw))
    return -1;
  // header values are untrusted file bytes: reject shapes whose payload
  // arithmetic could overflow or never advance (bs=0 loops; a total with
  // bit 63 set casts negative and would skip the scan loop as "success";
  // vpm*w*... must stay far inside int64; a real vpm is <= a few hundred)
  if (nmb == 0 || bs == 0 || bs % nmb || bs > (1u << 30)) return -1;
  if (total >> 63) return -1;
  const int64_t vpm = (int64_t)(bs / nmb);
  if (vpm == 0) return -1;
  header_out[0] = unzigzag(fraw);
  header_out[1] = (int64_t)total;
  header_out[2] = vpm;
  int64_t got = 1, k = 0;
  while (got < (int64_t)total) {
    uint64_t mdr;
    if (!uvarint(pos, mdr)) return -1;
    const int64_t mind = unzigzag(mdr);
    if (pos + (int64_t)nmb > size) return -1;
    const uint8_t* wb = data + pos;
    pos += (int64_t)nmb;
    for (uint64_t m = 0; m < nmb && got < (int64_t)total; ++m) {
      if (k >= cap) return -1;
      const int32_t w = wb[m];
      if (w > 64) return -1;
      offsets[k] = pos * 8;
      widths[k] = w;
      mins[k] = mind;
      pos += vpm * w / 8;  // bounded: vpm <= 2^30, w <= 64
      if (pos < 0 || pos > size + (int64_t)(bs * 8)) return -1;
      ++k;
      const int64_t rem = (int64_t)total - got;
      got += rem < vpm ? rem : vpm;
    }
  }
  header_out[3] = pos;
  return k;
}

// Full-avalanche 64-bit finalizer (splitmix64).  Hash-table indexes below
// are taken from the LOW bits, so every input bit must reach them: a single
// multiply+shift leaves the index a function of the key's low bits only, and
// keys differing in mid/high bytes (dictionary strings packed to words,
// varying in trailing characters) cluster into a few slots, degrading linear
// probing to long chains (measured 5x slowdown on packed "catNNN" keys).
static inline uint64_t pq_mix64(uint64_t h) {
  h ^= h >> 30;
  h *= 0xBF58476D1CE4E5B9ull;
  h ^= h >> 27;
  h *= 0x94D049BB133111EBull;
  h ^= h >> 31;
  return h;
}

// ---------------------------------------------------------------------------
// Fixed-width dictionary build (hashprobe analog for INT32/INT64/FLOAT/DOUBLE
// viewed as int64 bits): open-addressing first-occurrence dedup.
// Returns unique count, or -1 when max_unique would be exceeded.
// ---------------------------------------------------------------------------
int64_t pq_dict_build_i64(const int64_t* vals, int64_t n, int64_t max_unique,
                          int64_t* indices, int64_t* uniques) {
  // grow geometrically from a small table (rebuilt from `uniques` at 50%
  // load) instead of pre-sizing to 2*max_unique: a 100M-row mostly-duplicate
  // column must not transiently allocate gigabytes before discovering its
  // cardinality
  int64_t cap = 1024;
  std::vector<int64_t> slot(cap, -1);
  std::vector<int64_t> key(cap);
  int64_t nu = 0;
  const auto hash_full = [](int64_t v) { return pq_mix64((uint64_t)v); };
  const auto grow = [&]() {
    cap <<= 1;
    slot.assign(cap, -1);
    key.resize(cap);
    for (int64_t u = 0; u < nu; ++u) {
      int64_t p = (int64_t)(hash_full(uniques[u]) & (uint64_t)(cap - 1));
      while (slot[p] >= 0) p = (p + 1) & (cap - 1);
      slot[p] = u;
      key[p] = uniques[u];
    }
  };
  constexpr int64_t kAhead = 16;  // hide the random-probe cache miss
  for (int64_t i = 0; i < n; ++i) {
    if (i + kAhead < n) {
      const int64_t pf =
          (int64_t)(hash_full(vals[i + kAhead]) & (uint64_t)(cap - 1));
      __builtin_prefetch(&slot[pf]);
      __builtin_prefetch(&key[pf]);
    }
    const int64_t v = vals[i];
    int64_t p = (int64_t)(hash_full(v) & (uint64_t)(cap - 1));
    while (true) {
      const int64_t s = slot[p];
      if (s < 0) {
        if (nu >= max_unique) return -1;
        if (2 * (nu + 1) > cap) {
          grow();
          p = (int64_t)(hash_full(v) & (uint64_t)(cap - 1));
          continue;
        }
        slot[p] = nu;
        key[p] = v;
        uniques[nu] = v;
        indices[i] = nu;
        ++nu;
        break;
      }
      if (key[p] == v) {
        indices[i] = s;
        break;
      }
      p = (p + 1) & (cap - 1);
    }
  }
  return nu;
}

// ---------------------------------------------------------------------------
// Fused single-repetition-level list assembly straight from the two level
// run tables (no per-slot def/rep materialization).  Host work stays
// metadata-scale: RLE x RLE segments are handled with vector fills; only
// bit-packed spans unpack per slot.  Semantics match pq_assemble_levels for
// nlev == 1: instance iff rep == 0, element iff def >= dk, list non-null iff
// def >= dk-1 at its start slot, leaf valid iff def == max_def.
// out_counts = {ninst, nelems}; returns 0, or -1 on a run table that does
// not tile [0, n).
// ---------------------------------------------------------------------------
struct RunCursor {
  const uint8_t* buf;
  int64_t buf_len;
  const int64_t* ends;
  const uint8_t* kinds;
  const int64_t* pays;
  const int64_t* bits;
  const int32_t* widths;
  int64_t nruns;
  int64_t idx = 0;
  int64_t start = 0;  // first slot of current run

  bool advance_to(int64_t pos) {  // enter the run containing pos
    while (idx < nruns && ends[idx] <= pos) {
      start = ends[idx];
      ++idx;
    }
    return idx < nruns;
  }
  // fill dst[0..cnt) with per-slot values of [pos, pos+cnt), walking runs
  bool fill(int64_t pos, int64_t cnt, int32_t* dst) {
    int64_t done = 0;
    while (done < cnt) {
      if (!advance_to(pos + done)) return false;
      int64_t take = ends[idx] - (pos + done);
      if (take > cnt - done) take = cnt - done;
      if (kinds[idx] == 0) {
        const int32_t v = (int32_t)pays[idx];
        for (int64_t j = 0; j < take; ++j) dst[done + j] = v;
      } else {
        unpack_span(pos + done, take, dst + done);
      }
      done += take;
    }
    return true;
  }
  bool is_rle() const { return kinds[idx] == 0; }
  int32_t value() const { return (int32_t)pays[idx]; }
  int64_t end() const { return ends[idx]; }
  // unpack [pos, pos+cnt) of a bit-packed run into dst
  void unpack_span(int64_t pos, int64_t cnt, int32_t* dst) const {
    const int32_t w = widths[idx];
    unpack_bits_span(buf, buf_len, bits[idx] + (pos - start) * w, w, cnt, dst);
  }
};

int64_t pq_assemble_list_runs(
    const uint8_t* dbuf, int64_t dlen, const int64_t* d_ends,
    const uint8_t* d_kinds, const int64_t* d_pays, const int64_t* d_bits,
    const int32_t* d_widths, int64_t d_nruns, const uint8_t* rbuf, int64_t rlen,
    const int64_t* r_ends, const uint8_t* r_kinds, const int64_t* r_pays,
    const int64_t* r_bits, const int32_t* r_widths, int64_t r_nruns, int64_t n,
    int32_t dk, int32_t max_def, int64_t* offsets, uint8_t* lvalid,
    uint8_t* leaf_valid, int64_t* out_counts) {
  RunCursor dc{dbuf, dlen, d_ends, d_kinds, d_pays, d_bits, d_widths, d_nruns};
  RunCursor rc{rbuf, rlen, r_ends, r_kinds, r_pays, r_bits, r_widths, r_nruns};
  int64_t pos = 0, ninst = 0, elems = 0;
  while (pos < n) {
    if (!dc.advance_to(pos) || !rc.advance_to(pos)) return -1;
    int64_t end = dc.end() < rc.end() ? dc.end() : rc.end();
    if (end > n) end = n;
    const int64_t len = end - pos;
    if (dc.is_rle() && rc.is_rle() && len >= 256) {
      const int32_t dv = dc.value(), rv = rc.value();
      const bool elem = dv >= dk;
      if (rv == 0) {
        if (elem) {
          for (int64_t t = 0; t < len; ++t) offsets[ninst + t] = elems + t;
        } else {
          for (int64_t t = 0; t < len; ++t) offsets[ninst + t] = elems;
        }
        std::memset(lvalid + ninst, dv >= dk - 1 ? 1 : 0, len);
        ninst += len;
      }
      if (elem) {
        std::memset(leaf_valid + elems, dv == max_def ? 1 : 0, len);
        elems += len;
      }
    } else {
      // short/mixed span: run-table-driven fills into L1-resident chunks
      // (continuous across run boundaries — per-run cost is just the fill
      // switch), then compact via 64-slot bitmaps so stores happen only at
      // instances/elements
      alignas(64) int32_t dtmp[576], rtmp[576];
      end = pos + 512 < n ? pos + 512 : n;
      {
        const int64_t seg = pos;
        const int64_t cnt = end - seg;
        if (!dc.fill(seg, cnt, dtmp) || !rc.fill(seg, cnt, rtmp)) return -1;
#ifdef PQ_HAVE_AVX512
        const __m512i zerov = _mm512_setzero_si512();
        const __m512i dkv = _mm512_set1_epi32(dk);
        const __m512i dkm1v = _mm512_set1_epi32(dk - 1);
        const __m512i mdv = _mm512_set1_epi32(max_def);
        for (int64_t j0 = 0; j0 < cnt; j0 += 64) {
          uint64_t inst_w = 0, elem_w = 0, valge_w = 0, eq_w = 0;
          for (int g = 0; g < 4; ++g) {
            const __m512i dv = _mm512_loadu_si512(dtmp + j0 + g * 16);
            const __m512i rv = _mm512_loadu_si512(rtmp + j0 + g * 16);
            inst_w |= (uint64_t)_mm512_cmpeq_epi32_mask(rv, zerov) << (g * 16);
            elem_w |= (uint64_t)_mm512_cmple_epi32_mask(dkv, dv) << (g * 16);
            valge_w |= (uint64_t)_mm512_cmple_epi32_mask(dkm1v, dv) << (g * 16);
            eq_w |= (uint64_t)_mm512_cmpeq_epi32_mask(dv, mdv) << (g * 16);
          }
          if (cnt - j0 < 64) {  // mask out the tail's garbage lanes
            const uint64_t live = (~0ull) >> (64 - (cnt - j0));
            inst_w &= live;
            elem_w &= live;
            valge_w &= live;
            eq_w &= live;
          }
          compact_block64(inst_w, elem_w, valge_w, eq_w, offsets, lvalid,
                          leaf_valid, &ninst, &elems);
        }
#else
        // branchless: always store at the cursor, advance conditionally
        for (int64_t j = 0; j < cnt; ++j) {
          const int32_t dv = dtmp[j], rv = rtmp[j];
          offsets[ninst] = elems;
          lvalid[ninst] = dv >= dk - 1;
          ninst += (rv == 0);
          leaf_valid[elems] = dv == max_def;
          elems += (dv >= dk);
        }
#endif
      }
    }
    pos = end;
  }
  offsets[ninst] = elems;
  out_counts[0] = ninst;
  out_counts[1] = elems;
  return 0;
}

// ---------------------------------------------------------------------------
// RLE/bit-packed hybrid run scan (the host half of the two-pass split).
// Outputs one row per run; returns run count, or -1 on malformed input.
// Caller sizes outputs to n (a run covers >= 1 value).
// ---------------------------------------------------------------------------
int64_t pq_scan_rle_runs(const uint8_t* data, int64_t size, int64_t n,
                         int32_t bit_width, uint8_t* kinds, int64_t* counts,
                         int64_t* payloads, int64_t* byte_offsets) {
  int64_t pos = 0;
  int64_t remaining = n;
  int64_t k = 0;
  const int vbytes = (bit_width + 7) / 8;
  while (remaining > 0) {
    // uvarint header
    uint64_t header = 0;
    int shift = 0;
    while (true) {
      if (pos >= size) return -1;
      uint8_t b = data[pos++];
      header |= (uint64_t)(b & 0x7F) << shift;
      if (!(b & 0x80)) break;
      shift += 7;
      if (shift > 63) return -1;
    }
    // a zero-count run (header >> 1 == 0) covers no values: it never
    // decrements `remaining`, so a crafted stream of them would grow the
    // run table without bound (the caller sizes its arrays as n+1 on the
    // guarantee every run covers >= 1 value) — reject as malformed
    if ((header >> 1) == 0) return -1;
    if (header & 1) {
      int64_t ngroups = (int64_t)(header >> 1);
      int64_t count = ngroups * 8;
      kinds[k] = 1;
      counts[k] = count < remaining ? count : remaining;
      payloads[k] = 0;
      byte_offsets[k] = pos;
      pos += ngroups * bit_width;
      if (pos > size) return -1;
      remaining -= count;
    } else {
      int64_t count = (int64_t)(header >> 1);
      if (pos + vbytes > size) return -1;
      uint64_t value = 0;
      for (int j = 0; j < vbytes; j++) value |= (uint64_t)data[pos + j] << (8 * j);
      // mask to the declared width: the padding bits of the vbytes payload
      // are unspecified, and every consumer (incl. int32 expansion) must see
      // the same value as the Python oracle
      if (bit_width < 64) value &= (1ull << bit_width) - 1;
      pos += vbytes;
      kinds[k] = 0;
      counts[k] = count < remaining ? count : remaining;
      payloads[k] = (int64_t)value;
      byte_offsets[k] = pos;
      remaining -= count;
    }
    k++;
  }
  return k;
}

// ---------------------------------------------------------------------------
// Fused DELTA_BINARY_PACKED decode (multithreaded, one pass): miniblock
// tables (from pq_delta_prescan) → int64 values, unpack + min-add + prefix
// sum inline.  The host route for delta chunks on non-TPU backends
// (BASELINE config 4); pages are independent (each restarts at its own
// first value), so the thread partition is per page.
// ---------------------------------------------------------------------------

static inline uint64_t load_bits64(const uint8_t* buf, int64_t buf_len,
                                   int64_t bit, int w) {
  // w <= 64; value may span 9 bytes — combine two clamped 8-byte loads
  const int64_t byte0 = bit >> 3;
  const int sh = (int)(bit & 7);
  uint64_t lo = load8_clamped(buf, buf_len, byte0) >> sh;
  if (sh + w > 64) {
    uint64_t hi = load8_clamped(buf, buf_len, byte0 + 8);
    lo |= hi << (64 - sh);
  }
  return (w >= 64) ? lo : (lo & (((uint64_t)1 << w) - 1));
}

int64_t pq_delta_decode(const uint8_t* buf, int64_t buf_len,
                        const int64_t* mb_bitoffs, const int32_t* mb_widths,
                        const int64_t* mb_mins, const int64_t* page_mb_start,
                        const int64_t* page_first, const int64_t* page_count,
                        const int64_t* page_out_start, const int64_t* page_vpm,
                        int64_t npages, int64_t* out, int32_t nthreads) {
  auto decode_page = [&](int64_t p) -> bool {
    const int64_t total = page_count[p];
    if (total <= 0) return total == 0;
    const int64_t vpm = page_vpm[p];
    if (vpm <= 0) return false;
    int64_t* o = out + page_out_start[p];
    uint64_t v = (uint64_t)page_first[p];
    o[0] = (int64_t)v;
    int64_t got = 1;
    for (int64_t m = page_mb_start[p]; m < page_mb_start[p + 1] && got < total;
         ++m) {
      const int w = mb_widths[m];
      if (w < 0 || w > 64) return false;
      const uint64_t mn = (uint64_t)mb_mins[m];
      const int64_t take = (total - got < vpm) ? (total - got) : vpm;
      if (w == 0) {
        for (int64_t j = 0; j < take; ++j) {
          v += mn;
          o[got + j] = (int64_t)v;
        }
      } else {
        int64_t bit = mb_bitoffs[m];
        if (bit < 0 || bit + (int64_t)w * take > buf_len * 8) return false;
        if (w <= 28) {
          // narrow widths (the common case): batch-unpack via one 8-byte
          // load per 57/w values, same scheme as unpack_bits_span
          const int kper = 57 / w;
          const uint64_t mask = ((uint64_t)1 << w) - 1;
          int64_t j = 0;
          while (j < take) {
            uint64_t word =
                load8_clamped(buf, buf_len, bit >> 3) >> (bit & 7);
            int mcount = (int)((take - j < kper) ? (take - j) : kper);
            for (int t = 0; t < mcount; ++t) {
              v += ((word >> (t * w)) & mask) + mn;
              o[got + j + t] = (int64_t)v;
            }
            j += mcount;
            bit += (int64_t)mcount * w;
          }
        } else {
          for (int64_t j = 0; j < take; ++j) {
            v += load_bits64(buf, buf_len, bit, w) + mn;
            o[got + j] = (int64_t)v;
            bit += w;
          }
        }
      }
      got += take;
    }
    return got >= total;
  };
  int T = nthreads;
  if (T < 1) T = 1;
  if (T > 16) T = 16;
  if ((int64_t)T > npages) T = (int)npages ? (int)npages : 1;
  if (T == 1) {
    for (int64_t p = 0; p < npages; ++p)
      if (!decode_page(p)) return -1;
    return 0;
  }
  std::vector<std::thread> threads;
  std::vector<char> ok((size_t)T, 1);
  const int64_t per = (npages + T - 1) / T;
  auto run = [&](int t) {
    const int64_t lo = per * t, hi = std::min(npages, per * (t + 1));
    for (int64_t p = lo; p < hi; ++p)
      if (!decode_page(p)) { ok[(size_t)t] = 0; return; }
  };
  for (int t = 1; t < T; ++t) threads.emplace_back(run, t);
  run(0);
  for (auto& th : threads) th.join();
  for (int t = 0; t < T; ++t)
    if (!ok[(size_t)t]) return -1;
  return 0;
}

}  // extern "C" (the helpers below use templates — C++ linkage)

// ---------------------------------------------------------------------------
// Batch page-header scan: walk a column chunk's compact-thrift PageHeader
// stream in one native call (SURVEY.md §3.1 file walk — the reference's
// ReadPageHeader loop; per-page Python thrift parsing was the measured
// dominant cost of the e2e pipeline's host phase).  Only the PageHeader
// subset the decoder needs is extracted; any malformed construct returns -1
// and the caller falls back to the Python reader, which owns error wording.
// ---------------------------------------------------------------------------

namespace {

struct TRd {
  const uint8_t* p;
  int64_t pos, size;
  bool err;
};

inline uint64_t trd_uvarint(TRd& r) {
  uint64_t v = 0;
  int shift = 0;
  while (true) {
    if (r.pos >= r.size || shift > 63) { r.err = true; return 0; }
    uint8_t b = r.p[r.pos++];
    v |= (uint64_t)(b & 0x7F) << shift;
    if (!(b & 0x80)) return v;
    shift += 7;
  }
}

inline int64_t trd_zigzag(TRd& r) {
  uint64_t v = trd_uvarint(r);
  return (int64_t)(v >> 1) ^ -(int64_t)(v & 1);
}

// compact-protocol wire types
enum { CT_STOP = 0, CT_TRUE = 1, CT_FALSE = 2, CT_I8 = 3, CT_I16 = 4,
       CT_I32 = 5, CT_I64 = 6, CT_DOUBLE = 7, CT_BINARY = 8, CT_LIST = 9,
       CT_SET = 10, CT_MAP = 11, CT_STRUCT = 12 };

void trd_skip(TRd& r, int wire, int depth) {
  if (r.err || depth > 16) { r.err = true; return; }
  switch (wire) {
    case CT_TRUE: case CT_FALSE:
      return;  // value lives in the type nibble
    case CT_I8:
      r.pos += 1; if (r.pos > r.size) r.err = true; return;
    case CT_I16: case CT_I32: case CT_I64:
      trd_uvarint(r); return;
    case CT_DOUBLE:
      r.pos += 8; if (r.pos > r.size) r.err = true; return;
    case CT_BINARY: {
      uint64_t n = trd_uvarint(r);
      if (r.err || n > (uint64_t)(r.size - r.pos)) { r.err = true; return; }
      r.pos += (int64_t)n; return;
    }
    case CT_LIST: case CT_SET: {
      if (r.pos >= r.size) { r.err = true; return; }
      uint8_t h = r.p[r.pos++];
      uint64_t n = h >> 4;
      int ew = h & 0x0F;
      if (n == 0xF) n = trd_uvarint(r);
      // any element consumes >= 1 byte, so a count beyond the remaining
      // buffer is malformed — guards the unsigned->signed cast too
      if (n > (uint64_t)(r.size - r.pos)) { r.err = true; return; }
      if (ew == CT_TRUE || ew == CT_FALSE) {  // bools: one byte per element
        r.pos += (int64_t)n;
        return;
      }
      for (uint64_t i = 0; i < n && !r.err; ++i) trd_skip(r, ew, depth + 1);
      return;
    }
    case CT_MAP: {
      uint64_t n = trd_uvarint(r);
      if (r.err) return;
      if (n == 0) return;
      // each pair consumes >= 1 byte: bound the loop against the buffer
      if (n > (uint64_t)(r.size - r.pos)) { r.err = true; return; }
      if (r.pos >= r.size) { r.err = true; return; }
      uint8_t kv = r.p[r.pos++];
      for (uint64_t i = 0; i < n && !r.err; ++i) {
        trd_skip(r, kv >> 4, depth + 1);
        trd_skip(r, kv & 0x0F, depth + 1);
      }
      return;
    }
    case CT_STRUCT: {
      while (!r.err) {
        if (r.pos >= r.size) { r.err = true; return; }
        uint8_t h = r.p[r.pos++];
        if (h == CT_STOP) return;
        if (!(h >> 4)) trd_zigzag(r);  // long-form field id
        trd_skip(r, h & 0x0F, depth + 1);
      }
      return;
    }
    default:
      r.err = true;
      return;
  }
}

// Walk one struct, dispatching (field id, wire) to `fn`; unknown fields skip.
template <typename F>
inline void trd_struct(TRd& r, F&& fn) {
  int64_t fid = 0;
  while (!r.err) {
    if (r.pos >= r.size) { r.err = true; return; }
    uint8_t h = r.p[r.pos++];
    if (h == CT_STOP) return;
    int delta = h >> 4, wire = h & 0x0F;
    fid = delta ? fid + delta : trd_zigzag(r);
    if (!fn(fid, wire)) trd_skip(r, wire, 0);
  }
}

}  // namespace

// out columns per page (int64 each) — keep in sync with native/__init__.py
enum { PG_HEADER_POS = 0, PG_DATA_POS, PG_TYPE, PG_COMP, PG_UNCOMP, PG_CRC,
       PG_NVALS, PG_ENC, PG_DEF_ENC, PG_REP_ENC, PG_RL_BYTES, PG_DL_BYTES,
       PG_NNULLS, PG_IS_COMPRESSED, PG_DICT_NVALS, PG_NROWS, PG_NFIELDS };

static int64_t scan_page_headers_impl(const uint8_t* buf, int64_t size,
                                      int64_t total_values,
                                      int64_t max_pages, int64_t* out,
                                      bool partial, int64_t* consumed_out) {
  int64_t pos = 0, values_seen = 0, k = 0;
  while (values_seen < total_values && pos < size) {
    if (k >= max_pages) {
      if (partial) break;
      return -2;
    }
    TRd r{buf, pos, size, false};
    int64_t* row = out + k * PG_NFIELDS;
    for (int i = 0; i < PG_NFIELDS; ++i) row[i] = -1;
    row[PG_HEADER_POS] = pos;
    trd_struct(r, [&](int64_t fid, int wire) -> bool {
      switch (fid) {
        case 1: if (wire != CT_I32) return false;
                row[PG_TYPE] = trd_zigzag(r); return true;
        case 2: if (wire != CT_I32) return false;
                row[PG_UNCOMP] = trd_zigzag(r); return true;
        case 3: if (wire != CT_I32) return false;
                row[PG_COMP] = trd_zigzag(r); return true;
        case 4: if (wire != CT_I32) return false;
                // thrift i32 crc is signed; normalize to the u32 value
                row[PG_CRC] = (int64_t)(uint32_t)trd_zigzag(r); return true;
        case 5:  // data_page_header
          if (wire != CT_STRUCT) return false;
          trd_struct(r, [&](int64_t f2, int w2) -> bool {
            if (w2 != CT_I32) return false;
            switch (f2) {
              case 1: row[PG_NVALS] = trd_zigzag(r); return true;
              case 2: row[PG_ENC] = trd_zigzag(r); return true;
              case 3: row[PG_DEF_ENC] = trd_zigzag(r); return true;
              case 4: row[PG_REP_ENC] = trd_zigzag(r); return true;
              default: return false;
            }
          });
          return true;
        case 7:  // dictionary_page_header
          if (wire != CT_STRUCT) return false;
          trd_struct(r, [&](int64_t f2, int w2) -> bool {
            if (w2 != CT_I32) return false;
            switch (f2) {
              case 1: row[PG_DICT_NVALS] = trd_zigzag(r); return true;
              case 2: row[PG_ENC] = trd_zigzag(r); return true;
              default: return false;
            }
          });
          return true;
        case 8:  // data_page_header_v2
          if (wire != CT_STRUCT) return false;
          trd_struct(r, [&](int64_t f2, int w2) -> bool {
            if (w2 == CT_TRUE || w2 == CT_FALSE) {
              if (f2 == 7) { row[PG_IS_COMPRESSED] = (w2 == CT_TRUE); return true; }
              return true;  // other bools carry no payload bytes
            }
            if (w2 != CT_I32) return false;
            switch (f2) {
              case 1: row[PG_NVALS] = trd_zigzag(r); return true;
              case 2: row[PG_NNULLS] = trd_zigzag(r); return true;
              case 3: row[PG_NROWS] = trd_zigzag(r); return true;
              case 4: row[PG_ENC] = trd_zigzag(r); return true;
              case 5: row[PG_DL_BYTES] = trd_zigzag(r); return true;
              case 6: row[PG_RL_BYTES] = trd_zigzag(r); return true;
              default: return false;
            }
          });
          return true;
        default:
          return false;  // statistics / index page header / unknown: skip
      }
    });
    if (r.err) {
      // in partial mode a header running past the buffer is just the
      // window edge: stop and report progress, the caller re-reads from
      // `consumed` with a bigger window (true corruption surfaces there)
      if (partial) break;
      return -1;
    }
    int64_t clen = row[PG_COMP];
    if (clen < 0 || row[PG_TYPE] < 0 || row[PG_UNCOMP] < 0) {
      if (partial) break;
      return -1;
    }
    if (clen > size - r.pos) {  // payload past the buffer (no overflow)
      if (partial) break;
      return -1;
    }
    row[PG_DATA_POS] = r.pos;
    if (row[PG_TYPE] == 0 || row[PG_TYPE] == 3) {  // DATA_PAGE / V2
      if (row[PG_NVALS] < 0) {
        if (partial) break;
        return -1;
      }
      values_seen += row[PG_NVALS];
    }
    pos = r.pos + clen;
    ++k;
  }
  if (consumed_out) {
    consumed_out[0] = pos;
    consumed_out[1] = values_seen;
  }
  return k;
}

extern "C" int64_t pq_scan_page_headers(const uint8_t* buf, int64_t size,
                                        int64_t total_values,
                                        int64_t max_pages, int64_t* out) {
  return scan_page_headers_impl(buf, size, total_values, max_pages, out,
                                false, nullptr);
}

// Partial/windowed variant: stops (instead of erroring) at the first page
// whose header or payload runs past the buffer, reporting pages parsed and
// consumed_out = {bytes consumed, data values seen}.
extern "C" int64_t pq_scan_page_headers_partial(
    const uint8_t* buf, int64_t size, int64_t total_values,
    int64_t max_pages, int64_t* out, int64_t* consumed_out) {
  return scan_page_headers_impl(buf, size, total_values, max_pages, out,
                                true, consumed_out);
}

extern "C" {

// ---------------------------------------------------------------------------
// xxhash64 (bloom filter hashing; spec-mandated XXH64 seed 0)
// ---------------------------------------------------------------------------
static inline uint64_t rotl64(uint64_t x, int r) {
  return (x << r) | (x >> (64 - r));
}

static const uint64_t P1 = 11400714785074694791ULL;
static const uint64_t P2 = 14029467366897019727ULL;
static const uint64_t P3 = 1609587929392839161ULL;
static const uint64_t P4 = 9650029242287828579ULL;
static const uint64_t P5 = 2870177450012600261ULL;

uint64_t pq_xxh64(const uint8_t* p, int64_t len, uint64_t seed) {
  const uint8_t* end = p + len;
  uint64_t h;
  if (len >= 32) {
    uint64_t v1 = seed + P1 + P2, v2 = seed + P2, v3 = seed, v4 = seed - P1;
    do {
      uint64_t k;
      std::memcpy(&k, p, 8); v1 = rotl64(v1 + k * P2, 31) * P1; p += 8;
      std::memcpy(&k, p, 8); v2 = rotl64(v2 + k * P2, 31) * P1; p += 8;
      std::memcpy(&k, p, 8); v3 = rotl64(v3 + k * P2, 31) * P1; p += 8;
      std::memcpy(&k, p, 8); v4 = rotl64(v4 + k * P2, 31) * P1; p += 8;
    } while (p + 32 <= end);
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
    uint64_t k;
    std::memcpy(&k, p, 8);
    h ^= rotl64(k * P2, 31) * P1;
    h = rotl64(h, 27) * P1 + P4;
    p += 8;
  }
  if (p + 4 <= end) {
    uint32_t k;
    std::memcpy(&k, p, 4);
    h ^= (uint64_t)k * P1;
    h = rotl64(h, 23) * P2 + P3;
    p += 4;
  }
  while (p < end) {
    h ^= (uint64_t)(*p++) * P5;
    h = rotl64(h, 11) * P1;
  }
  h ^= h >> 33; h *= P2; h ^= h >> 29; h *= P3; h ^= h >> 32;
  return h;
}

void pq_xxh64_batch(const uint8_t* data, const int64_t* offsets, int64_t n,
                    uint64_t* out) {
  for (int64_t i = 0; i < n; i++)
    out[i] = pq_xxh64(data + offsets[i], offsets[i + 1] - offsets[i], 0);
}

// ---------------------------------------------------------------------------
// DELTA_BYTE_ARRAY reconstruction: values[i] = values[i-1][:prefix[i]] + suffix[i]
// (the inherently sequential front-coding chain — SURVEY.md §2.2)
// ---------------------------------------------------------------------------
int64_t pq_delta_byte_array_expand(const int64_t* prefix_lens,
                                   const uint8_t* suffix_data,
                                   const int64_t* suffix_offsets, int64_t n,
                                   uint8_t* out_values,
                                   const int64_t* out_offsets) {
  int64_t prev = 0;
  for (int64_t i = 0; i < n; i++) {
    const int64_t o = out_offsets[i];
    const int64_t pl = prefix_lens[i];
    const int64_t sl = suffix_offsets[i + 1] - suffix_offsets[i];
    if (pl > 0) std::memmove(out_values + o, out_values + prev, pl);
    if (sl > 0) std::memcpy(out_values + o + pl, suffix_data + suffix_offsets[i], sl);
    prev = o;
  }
  return n ? out_offsets[n] : 0;
}

// ---------------------------------------------------------------------------
// Byte-array dictionary build (hashprobe analog): dedup via hash map.
// Returns unique count; fills indices[n] and, when out_* non-null, the
// unique strings compacted in first-seen order.
// ---------------------------------------------------------------------------
struct DictState {
  std::unordered_map<std::string, int64_t> map;
  std::vector<std::string> uniques;
};

int64_t pq_dict_build_ba(const uint8_t* data, const int64_t* offsets,
                         int64_t n, int64_t* indices, int64_t max_unique) {
  // Open-addressing first-occurrence dedup, same scheme as
  // pq_dict_build_i64: slots hold unique ids, keys are compared by memcmp
  // against the FIRST occurrence's bytes (no per-value allocation — the
  // previous unordered_map<string> build paid a heap string per value and
  // was the single largest cost of writing a categorical string column).
  // All loads are fixed-size 8-byte memcpy (a single inlined mov) — a
  // variable-length memcpy is a real library call and dominated the
  // per-value cost.  Loads near the end of the buffer fall back to the
  // slow path so we never read past offsets[n].
  const int64_t total = offsets[n];
  constexpr uint64_t kMix = 0x9E3779B97F4A7C15ull;
  const auto load_masked = [&](int64_t off, int64_t len) -> uint64_t {
    // len in [0, 8]; all-empty-string columns pass data == NULL, so never
    // touch the pointer for a zero-length load
    if (len == 0) return 0;
    if (off + 8 <= total) {
      uint64_t w;
      memcpy(&w, data + off, 8);
      return len >= 8 ? w : w & ((1ull << (8 * len)) - 1);
    }
    uint64_t w = 0;
    memcpy(&w, data + off, (size_t)len);
    return w;
  };
  // hash of the full string; also yields the first 8 bytes zero-padded
  // (k8) — with the length checked separately, k8 settles equality for
  // len <= 8 without touching memcmp
  const auto hkey = [&](int64_t i, uint64_t* k8) -> uint64_t {
    int64_t o = offsets[i];
    int64_t len = offsets[i + 1] - o;
    uint64_t h = kMix ^ (uint64_t)len;
    uint64_t w0 = 0;
    bool first = true;
    while (len >= 8) {
      uint64_t w;
      memcpy(&w, data + o, 8);
      if (first) {
        w0 = w;
        first = false;
      }
      h = (h ^ w) * kMix;
      h ^= h >> 29;
      o += 8;
      len -= 8;
    }
    if (len) {
      uint64_t w = load_masked(o, len);
      if (first) w0 = w;
      h = (h ^ w) * kMix;
      h ^= h >> 29;
    }
    // final avalanche: the index comes from the LOW bits, and the per-word
    // mix above does not push a word's high bytes down into them — strings
    // differing only in trailing characters would otherwise cluster (see
    // pq_mix64).
    h = pq_mix64(h);
    *k8 = w0;
    return h;
  };
  // Short-string fast path: when every value fits in 7 bytes, the whole
  // (bytes, length) identity packs into one tagged word — bytes in the low
  // 56 bits, length in the top byte — so probing is a single-word compare
  // with no memcmp and 16-byte slots.  This is the dominant dictionary
  // write shape (categorical/enum-like string columns: flags, codes,
  // ship modes) and runs ~2x the general loop below.
  {
    int64_t maxlen = 0;
    for (int64_t i = 0; i < n && maxlen <= 7; ++i) {
      const int64_t l = offsets[i + 1] - offsets[i];
      if (l > maxlen) maxlen = l;
    }
    if (maxlen <= 7) {
      // Packed keys are computed on the fly (two loads + mask + tag) — no
      // n-sized transient, so a 100M-row column costs only its table, which
      // grows geometrically from 1024 like pq_dict_build_i64's.
      const auto pack = [&](int64_t i) -> uint64_t {
        const int64_t o = offsets[i];
        const uint64_t len = (uint64_t)(offsets[i + 1] - o);
        if (o + 8 <= total) {
          uint64_t w;
          memcpy(&w, data + o, 8);
          return (w & (((uint64_t)1 << (8 * len)) - 1)) | (len << 56);
        }
        return load_masked(o, (int64_t)len) | (len << 56);
      };
      const auto hashw = pq_mix64;
      int64_t cap = 1024;
      std::vector<int64_t> slot(cap, -1);
      std::vector<uint64_t> key(cap);
      std::vector<uint64_t> ukey;  // unique id -> packed key, for rebuilds
      ukey.reserve(1024);
      int64_t nu = 0;
      const auto grow = [&]() {
        cap <<= 1;
        slot.assign(cap, -1);
        key.resize(cap);
        for (int64_t u = 0; u < nu; ++u) {
          int64_t p = (int64_t)(hashw(ukey[u]) & (uint64_t)(cap - 1));
          while (slot[p] >= 0) p = (p + 1) & (cap - 1);
          slot[p] = u;
          key[p] = ukey[u];
        }
      };
      constexpr int64_t kAhead = 16;  // hide the random-probe cache miss
      for (int64_t i = 0; i < n; ++i) {
        if (i + kAhead < n) {
          const int64_t pf = (int64_t)(hashw(pack(i + kAhead)) &
                                       (uint64_t)(cap - 1));
          __builtin_prefetch(&slot[pf]);
          __builtin_prefetch(&key[pf]);
        }
        const uint64_t v = pack(i);
        int64_t p = (int64_t)(hashw(v) & (uint64_t)(cap - 1));
        while (true) {
          const int64_t s = slot[p];
          if (s < 0) {
            if (nu >= max_unique) return -(i + 1);
            if (2 * (nu + 1) > cap) {
              grow();
              p = (int64_t)(hashw(v) & (uint64_t)(cap - 1));
              continue;
            }
            slot[p] = nu;
            key[p] = v;
            ukey.push_back(v);
            indices[i] = nu;
            ++nu;
            break;
          }
          if (key[p] == v) {
            indices[i] = s;
            break;
          }
          p = (p + 1) & (cap - 1);
        }
      }
      return nu;
    }
  }
  struct BaSlot {       // one cache-line-friendly 32-byte entry per slot
    uint64_t h;         // full hash
    uint64_t k8;        // first 8 bytes, zero-padded
    int64_t len;        // byte length
    int64_t id;         // unique id, -1 = empty
  };
  int64_t cap = 1024;
  std::vector<BaSlot> slots(cap, BaSlot{0, 0, 0, -1});
  std::vector<int64_t> first_i;  // unique id -> first value index
  first_i.reserve(1024);
  const auto grow = [&]() {
    cap <<= 1;
    slots.assign(cap, BaSlot{0, 0, 0, -1});
    for (size_t u = 0; u < first_i.size(); ++u) {
      const int64_t fi = first_i[u];
      uint64_t k8;
      uint64_t h = hkey(fi, &k8);
      int64_t p = (int64_t)(h & (uint64_t)(cap - 1));
      while (slots[p].id >= 0) p = (p + 1) & (cap - 1);
      slots[p] = BaSlot{h, k8, offsets[fi + 1] - offsets[fi], (int64_t)u};
    }
  };
  for (int64_t i = 0; i < n; ++i) {
    uint64_t k8;
    const uint64_t h = hkey(i, &k8);
    const int64_t len = offsets[i + 1] - offsets[i];
    int64_t p = (int64_t)(h & (uint64_t)(cap - 1));
    while (true) {
      const BaSlot& e = slots[p];
      if (e.id < 0) {
        if ((int64_t)first_i.size() >= max_unique)
          return -(i + 1);  // cardinality blew the limit
        if (2 * ((int64_t)first_i.size() + 1) > cap) {
          grow();
          p = (int64_t)(h & (uint64_t)(cap - 1));
          continue;
        }
        slots[p] = BaSlot{h, k8, len, (int64_t)first_i.size()};
        indices[i] = (int64_t)first_i.size();
        first_i.push_back(i);
        break;
      }
      if (e.h == h && e.len == len && e.k8 == k8) {
        const int64_t fi = first_i[e.id];
        if (len <= 8 ||
            memcmp(data + offsets[fi] + 8, data + offsets[i] + 8,
                   (size_t)(len - 8)) == 0) {
          indices[i] = e.id;
          break;
        }
      }
      p = (p + 1) & (cap - 1);
    }
  }
  return (int64_t)first_i.size();
}

// second pass: caller uses indices to materialize uniques (first occurrence)
// min/max over a span of length-prefixed byte strings (unsigned
// lexicographic — BYTE_ARRAY's order domain).  Writes the min and max VALUE
// indexes; used by per-page statistics so the hot write path never
// materializes python bytes objects.
void pq_minmax_ba(const uint8_t* data, const int64_t* offsets, int64_t v0,
                  int64_t v1, int64_t* out_min, int64_t* out_max) {
  int64_t mi = v0, ma = v0;
  for (int64_t i = v0 + 1; i < v1; i++) {
    const uint8_t* a = data + offsets[i];
    int64_t alen = offsets[i + 1] - offsets[i];
    const uint8_t* m = data + offsets[mi];
    int64_t mlen = offsets[mi + 1] - offsets[mi];
    int cmp = memcmp(a, m, alen < mlen ? alen : mlen);
    if (cmp < 0 || (cmp == 0 && alen < mlen)) mi = i;
    const uint8_t* x = data + offsets[ma];
    int64_t xlen = offsets[ma + 1] - offsets[ma];
    cmp = memcmp(a, x, alen < xlen ? alen : xlen);
    if (cmp > 0 || (cmp == 0 && alen > xlen)) ma = i;
  }
  *out_min = mi;
  *out_max = ma;
}

void pq_dict_first_occurrence(const int64_t* indices, int64_t n,
                              int64_t n_unique, int64_t* first_idx) {
  for (int64_t u = 0; u < n_unique; u++) first_idx[u] = -1;
  for (int64_t i = 0; i < n; i++)
    if (first_idx[indices[i]] < 0) first_idx[indices[i]] = i;
}

// ---------------------------------------------------------------------------
// Hadoop-framed LZ4 / generic frame walker is python-side; CRC32 via zlib.
// ---------------------------------------------------------------------------

}  // extern "C"

// ---------------------------------------------------------------------------
// Count level values equal to `target` across a scanned run table (the
// per-page present-count of build_plan: def == max_def).  RLE runs are a
// compare on the payload; bit-packed runs walk the packed bits once.  The
// numpy twin (_count_target_in_runs' gather_bits) was half of config-4's
// host phase at 64 MB.
// ---------------------------------------------------------------------------
extern "C" int64_t pq_count_target_in_runs(
    const uint8_t* body, int64_t body_len, const uint8_t* kinds,
    const int64_t* cnts, const int64_t* payloads, const int64_t* offs,
    int64_t k, int32_t width, int64_t target) {
  if (width <= 0 || width > 32) return -1;
  const uint64_t mask = (width >= 64) ? ~0ull : ((1ull << width) - 1);
  if ((uint64_t)target > mask) return 0;
  int64_t total = 0;
  for (int64_t r = 0; r < k; ++r) {
    if (kinds[r] == 0) {
      if (payloads[r] == target) total += cnts[r];
      continue;
    }
    const int64_t n = cnts[r];
    int64_t bit = offs[r] * 8;
    for (int64_t i = 0; i < n; ++i, bit += width) {
      const int64_t byte0 = bit >> 3;
      const int sh = (int)(bit & 7);
      uint64_t v = load8_clamped(body, body_len, byte0) >> sh;
      if ((v & mask) == (uint64_t)target) ++total;
    }
  }
  return total;
}

// ---------------------------------------------------------------------------
// Page decompression without Python: snappy (a fast in-tree decoder, the
// dlopen'd system libsnappy as its fallback) and zstd via the dlopen'd
// system libzstd — the same libraries codecs/ uses from Python.
// ---------------------------------------------------------------------------

#include <dlfcn.h>

namespace {

typedef int (*snappy_fn)(const char*, size_t, char*, size_t*);
typedef size_t (*zstd_fn)(void*, size_t, const void*, size_t);
typedef unsigned (*zstd_err_fn)(size_t);

inline void* dl_first(const char* a, const char* b) {
  void* h = dlopen(a, RTLD_NOW);
  return h ? h : dlopen(b, RTLD_NOW);
}

inline snappy_fn get_snappy_uncompress() {
  static snappy_fn fn = [] {
    void* h = dl_first("libsnappy.so.1", "libsnappy.so");
    return h ? (snappy_fn)dlsym(h, "snappy_uncompress") : nullptr;
  }();
  return fn;
}

inline zstd_fn get_zstd_decompress() {
  static zstd_fn fn = [] {
    void* h = dl_first("libzstd.so.1", "libzstd.so");
    return h ? (zstd_fn)dlsym(h, "ZSTD_decompress") : nullptr;
  }();
  return fn;
}

inline zstd_err_fn get_zstd_iserror() {
  static zstd_err_fn fn = [] {
    void* h = dl_first("libzstd.so.1", "libzstd.so");
    return h ? (zstd_err_fn)dlsym(h, "ZSTD_isError") : nullptr;
  }();
  return fn;
}

// ---------------------------------------------------------------------------
// Fast snappy raw-stream decoder.  The dlopen'd system libsnappy measured
// 0.5-0.6 GB/s on match-heavy pages (sorted int64 columns) on this class of
// host; this decoder uses 16-byte blind copies for literals and long-offset
// matches and a stack-staged doubled pattern for short-offset matches (the
// RLE-like case that dominates compressible columns).  Falls back to byte
// loops within 16 bytes of either buffer end, so it never writes past dst
// or reads past src.  Returns false on any malformed input (caller then
// retries with the system library, which owns precise error behavior).
// Format per the public snappy spec: varint uncompressed length, then
// literal/copy tags.
inline bool snappy_fast_uncompress(const uint8_t* src, int64_t src_len,
                                   uint8_t* dst, int64_t dst_len) {
  const uint8_t* sp = src;
  const uint8_t* send = src + src_len;
  uint64_t ulen = 0;
  int shift = 0;
  while (true) {
    if (sp >= send || shift > 28) return false;
    const uint8_t b = *sp++;
    ulen |= (uint64_t)(b & 0x7F) << shift;
    if (!(b & 0x80)) break;
    shift += 7;
  }
  if ((int64_t)ulen != dst_len) return false;
  uint8_t* dp = dst;
  uint8_t* dend = dst + dst_len;
  while (sp < send) {
    const uint8_t tag = *sp++;
    if ((tag & 3) == 0) {  // literal
      int64_t len = (tag >> 2) + 1;
      if (len > 60) {
        const int nb = (int)len - 60;  // 1..4 length bytes
        if (sp + nb > send) return false;
        uint32_t l = 0;
        memcpy(&l, sp, (size_t)nb);
        sp += nb;
        len = (int64_t)l + 1;
      }
      if (len > send - sp || len > dend - dp) return false;
      if (len <= 16 && send - sp >= 16 && dend - dp >= 16) {
        memcpy(dp, sp, 16);  // blind wide copy, bounds pre-checked
      } else {
        memcpy(dp, sp, (size_t)len);
      }
      sp += len;
      dp += len;
      continue;
    }
    int64_t len, off;
    if ((tag & 3) == 1) {  // copy1: 4..11 bytes, 11-bit offset
      if (sp >= send) return false;
      len = ((tag >> 2) & 7) + 4;
      off = ((int64_t)(tag & 0xE0) << 3) | *sp++;
    } else if ((tag & 3) == 2) {  // copy2: 16-bit offset
      if (send - sp < 2) return false;
      uint16_t o;
      memcpy(&o, sp, 2);
      sp += 2;
      len = (tag >> 2) + 1;
      off = o;
    } else {  // copy4: 32-bit offset
      if (send - sp < 4) return false;
      uint32_t o;
      memcpy(&o, sp, 4);
      sp += 4;
      len = (tag >> 2) + 1;
      off = o;
    }
    if (off <= 0 || off > dp - dst || len > dend - dp) return false;
    const uint8_t* cp = dp - off;
    if (off >= 16) {
      if (dend - dp >= len + 16) {  // slack for blind 16-byte strides
        uint8_t* o_ = dp;
        const uint8_t* c_ = cp;
        for (int64_t l = len; l > 0; l -= 16) {
          memcpy(o_, c_, 16);
          o_ += 16;
          c_ += 16;
        }
      } else {
        // no wide slack: forward chunks of `off` bytes — each chunk's
        // source lies fully behind its destination, and later chunks see
        // the bytes earlier ones wrote (the self-referencing semantics)
        int64_t done = 0;
        while (done < len) {
          const int64_t n = off < len - done ? off : len - done;
          memcpy(dp + done, cp + done, (size_t)n);
          done += n;
        }
      }
      dp += len;
      continue;
    }
    // short offset: replicate the pattern to a full 16-byte vector with
    // one pshufb (mask[i] = i % off), then blind 16-byte stores advancing
    // by the largest multiple of off <= 16 so the phase stays aligned
    if (dend - dp >= len + 16) {
#if defined(__SSSE3__)
      static const uint8_t kPatShuf[16][16] = {
          {0}, {0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0},
          {0,1,0,1,0,1,0,1,0,1,0,1,0,1,0,1},
          {0,1,2,0,1,2,0,1,2,0,1,2,0,1,2,0},
          {0,1,2,3,0,1,2,3,0,1,2,3,0,1,2,3},
          {0,1,2,3,4,0,1,2,3,4,0,1,2,3,4,0},
          {0,1,2,3,4,5,0,1,2,3,4,5,0,1,2,3},
          {0,1,2,3,4,5,6,0,1,2,3,4,5,6,0,1},
          {0,1,2,3,4,5,6,7,0,1,2,3,4,5,6,7},
          {0,1,2,3,4,5,6,7,8,0,1,2,3,4,5,6},
          {0,1,2,3,4,5,6,7,8,9,0,1,2,3,4,5},
          {0,1,2,3,4,5,6,7,8,9,10,0,1,2,3,4},
          {0,1,2,3,4,5,6,7,8,9,10,11,0,1,2,3},
          {0,1,2,3,4,5,6,7,8,9,10,11,12,0,1,2},
          {0,1,2,3,4,5,6,7,8,9,10,11,12,13,0,1},
          {0,1,2,3,4,5,6,7,8,9,10,11,12,13,14,0}};
      // cp+16 read is safe: cp = dp - off with off < 16 and dp has >= 16
      // bytes of slack checked above
      const __m128i v = _mm_shuffle_epi8(
          _mm_loadu_si128((const __m128i*)cp),
          _mm_loadu_si128((const __m128i*)kPatShuf[off]));
      const int stride = (16 / (int)off) * (int)off;
      for (int64_t w = 0; w < len; w += stride)
        _mm_storeu_si128((__m128i*)(dp + w), v);
#else
      uint8_t pat[32];
      for (int i = 0; i < (int)off; ++i) pat[i] = cp[i];
      int plen = (int)off;
      while (plen < 16) {
        memcpy(pat + plen, pat, (size_t)plen);  // disjoint within pat
        plen <<= 1;
      }
      const int stride = (16 / (int)off) * (int)off;
      for (int64_t w = 0; w < len; w += stride) memcpy(dp + w, pat, 16);
#endif
      dp += len;
    } else {
      for (int64_t i = 0; i < len; ++i) dp[i] = cp[i];  // overlap-safe tail
      dp += len;
    }
  }
  return dp == dend;
}

// decompress `src` into `dst` (exactly dst_len bytes expected). codec is the
// parquet CompressionCodec id: 0 UNCOMPRESSED, 1 SNAPPY, 6 ZSTD.
inline bool page_decompress(int codec, const uint8_t* src, int64_t src_len,
                            uint8_t* dst, int64_t dst_len) {
  if (codec == 0) {
    if (src_len != dst_len) return false;
    std::memcpy(dst, src, (size_t)src_len);
    return true;
  }
  if (codec == 1) {
    if (snappy_fast_uncompress(src, src_len, dst, dst_len)) return true;
    // fast decoder refuses malformed streams; the system library settles
    // whether the input is genuinely bad (and owns exotic cases)
    snappy_fn fn = get_snappy_uncompress();
    if (!fn) return false;
    size_t out_len = (size_t)dst_len;
    if (fn((const char*)src, (size_t)src_len, (char*)dst, &out_len) != 0)
      return false;
    return (int64_t)out_len == dst_len;
  }
  if (codec == 6) {
    zstd_fn fn = get_zstd_decompress();
    zstd_err_fn err = get_zstd_iserror();
    if (!fn || !err) return false;
    size_t r = fn(dst, (size_t)dst_len, src, (size_t)src_len);
    if (err(r)) return false;
    return (int64_t)r == dst_len;
  }
  return false;
}

}  // namespace

extern "C" {

// ---------------------------------------------------------------------------
// Batched PLAIN BYTE_ARRAY parse: many pages' 4-byte-length-prefixed
// string sections → ONE chunk-level (values, offsets) pair, offsets
// already rebased to the concatenated output.  Replaces a size pass + a
// copy pass per page plus a python offsets merge.  offsets_out needs
// sum(counts)+1 slots; values_out capacity >= sum(src_lens) (the
// prefixed form is strictly larger than the raw bytes).  Returns total
// value bytes, or -(page+1) for the first truncated page.
// ---------------------------------------------------------------------------
extern "C" int64_t pq_plain_ba_batch(
    const int64_t* src_ptrs, const int64_t* src_lens, const int64_t* counts,
    int64_t n_pages, int64_t* offsets_out, uint8_t* values_out) {
  int64_t base = 0;
  int64_t oi = 0;
  offsets_out[oi++] = 0;
  for (int64_t p = 0; p < n_pages; ++p) {
    const uint8_t* src = (const uint8_t*)(uintptr_t)src_ptrs[p];
    const int64_t len = src_lens[p];
    int64_t pos = 0;
    const int64_t cnt = counts[p];
    for (int64_t i = 0; i < cnt; ++i) {
      if (pos + 4 > len) return -(p + 1);
      uint32_t l;
      memcpy(&l, src + pos, 4);
      pos += 4;
      if ((int64_t)l > len - pos) return -(p + 1);
      memcpy(values_out + base, src + pos, l);
      base += l;
      pos += l;
      offsets_out[oi++] = base;
    }
  }
  return base;
}

// ---------------------------------------------------------------------------
// Batched RLE_DICTIONARY index decode: one native call per chunk replaces a
// Python scan/expand/astype round-trip per page (~0.3 ms each; a 4M-row
// dictionary string chunk has ~200 pages).  Per page: an optional
// length-prefixed def-level stream that must be ONE RLE run of 1s covering
// the page (all-present; anything else returns the page for the Python
// fallback), then [1-byte bit width][hybrid RLE/bit-packed indices].
// has_prefix[p]: 1 = v1 optional page (parse the prefix), 0 = the body
// starts at the bit-width byte (required columns, or v2 pages whose levels
// live outside the body).  Output int32 indices, concatenated.
// Returns total values written, or -(p+1) for the first failing page.
// ---------------------------------------------------------------------------
extern "C" int64_t pq_rle_dict_batch(
    const int64_t* src_ptrs, const int64_t* src_lens, const int64_t* counts,
    const uint8_t* has_prefix, int64_t n_pages, int32_t* out) {
  int64_t base = 0;
  for (int64_t p = 0; p < n_pages; ++p) {
    const uint8_t* d = (const uint8_t*)(uintptr_t)src_ptrs[p];
    const int64_t len = src_lens[p];
    const int64_t cnt = counts[p];
    int64_t pos = 0;
    if (has_prefix[p]) {
      if (pos + 4 > len) return -(p + 1);
      uint32_t dl;
      memcpy(&dl, d + pos, 4);
      pos += 4;
      const int64_t dend = pos + (int64_t)dl;
      if (dend > len) return -(p + 1);
      // single RLE run of value 1 covering every slot, else fallback
      uint64_t h = 0;
      int shift = 0;
      int64_t q = pos;
      while (true) {
        if (q >= dend || shift > 56) return -(p + 1);
        const uint8_t b = d[q++];
        h |= (uint64_t)(b & 0x7F) << shift;
        if (!(b & 0x80)) break;
        shift += 7;
      }
      if ((h & 1) != 0) return -(p + 1);          // bit-packed def levels
      if ((int64_t)(h >> 1) < cnt) return -(p + 1);  // short run
      if (q >= dend || d[q] != 1) return -(p + 1);   // has nulls
      pos = dend;
    }
    if (pos >= len) return -(p + 1);
    const int w = d[pos++];
    int32_t* o = out + base;
    if (w == 0) {
      for (int64_t i = 0; i < cnt; ++i) o[i] = 0;
      base += cnt;
      continue;
    }
    if (w > 31) return -(p + 1);
    const uint32_t mask = (w == 32) ? 0xFFFFFFFFu : ((1u << w) - 1);
    int64_t got = 0;
    while (got < cnt) {
      // uvarint run header
      uint64_t h = 0;
      int shift = 0;
      while (true) {
        if (pos >= len || shift > 56) return -(p + 1);
        const uint8_t b = d[pos++];
        h |= (uint64_t)(b & 0x7F) << shift;
        if (!(b & 0x80)) break;
        shift += 7;
      }
      if (h & 1) {  // bit-packed: (h>>1) groups of 8 values, w bits each
        const int64_t n_grp = (int64_t)(h >> 1);
        // cap BEFORE multiplying: a crafted 9-byte varint makes n_grp*w
        // overflow int64 and bypass the bounds check (negative-size memcpy)
        if (n_grp <= 0 || n_grp > (len - pos) / w) return -(p + 1);
        const int64_t nbytes = n_grp * w;  // 8 values * w bits = w bytes/grp
        int64_t take = n_grp * 8;
        if (take > cnt - got) take = cnt - got;  // final group may pad
        const uint8_t* bp = d + pos;
        int64_t i = 0;
        // fast path: full 8-byte window loads while they stay in bounds
        // (condition: bit + 64 <= nbytes*8, i.e. bit <= (nbytes-8)*8)
        const int64_t safe = (nbytes >= 8) ? (nbytes - 8) * 8 : -1;
        for (; i < take && i * w <= safe; ++i) {
          const int64_t bit = i * w;
          uint64_t word;
          memcpy(&word, bp + (bit >> 3), 8);
          o[got + i] = (int32_t)((uint32_t)(word >> (bit & 7)) & mask);
        }
        for (; i < take; ++i) {  // tail: byte-at-a-time masked load
          const int64_t bit = i * w;
          uint64_t word = 0;
          const int64_t k0 = bit >> 3;
          const int64_t nb = nbytes - k0 < 8 ? nbytes - k0 : 8;
          memcpy(&word, bp + k0, (size_t)nb);
          o[got + i] = (int32_t)((uint32_t)(word >> (bit & 7)) & mask);
        }
        got += take;
        pos += nbytes;
      } else {  // RLE run: (h>>1) copies of a ((w+7)/8)-byte LE value
        int64_t run = (int64_t)(h >> 1);
        const int vb = (w + 7) / 8;
        if (pos + vb > len) return -(p + 1);
        uint32_t v = 0;
        memcpy(&v, d + pos, (size_t)vb);
        v &= mask;
        pos += vb;
        if (run > cnt - got) run = cnt - got;
        for (int64_t i = 0; i < run; ++i) o[got + i] = (int32_t)v;
        got += run;
      }
    }
    base += cnt;
  }
  return base;
}

// ---------------------------------------------------------------------------
// Batched page decompression: one native call replaces a Python/ctypes
// codec round-trip per page (~0.1 ms each; the 2.7 GB lineitem file has
// ~6,400 pages, where the per-page overhead was the read path's single
// largest cost).  Per-page SOURCE POINTERS so any payload layout works
// (whole-chunk zero-copy views, streamed windows).  Output spans are
// caller-laid-out in one buffer via out_offs.  Threaded across pages.
// Codec ids as page_decompress: 0 UNCOMPRESSED, 1 SNAPPY, 6 ZSTD.
// Returns 0, or -(i+1) for the first failing page.
// ---------------------------------------------------------------------------
extern "C" int64_t pq_decompress_pages(
    const int64_t* src_ptrs, const int64_t* src_lens, int64_t n_pages,
    int32_t codec, uint8_t* out, const int64_t* out_offs, int32_t nthreads) {
  if (n_pages <= 0) return 0;
  std::atomic<int64_t> fail{0};
  auto run = [&](int t, int T) {
    for (int64_t i = t; i < n_pages; i += T) {
      if (!page_decompress(codec, (const uint8_t*)(uintptr_t)src_ptrs[i],
                           src_lens[i], out + out_offs[i],
                           out_offs[i + 1] - out_offs[i])) {
        int64_t cur = 0;
        fail.compare_exchange_strong(cur, -(i + 1));
      }
    }
  };
  int T = nthreads > 0 ? nthreads : 1;
  if ((int64_t)T > n_pages) T = (int)n_pages;
  if (T <= 1) {
    run(0, 1);
  } else {
    std::vector<std::thread> threads;
    threads.reserve((size_t)(T - 1));
    for (int t = 1; t < T; ++t) threads.emplace_back(run, t, T);
    run(0, T);
    for (auto& th : threads) th.join();
  }
  return fail.load();
}

}  // extern "C"
