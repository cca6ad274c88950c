"""Native host shim loader: compiles native.cpp → _native_<key>.so on first use.

Reference parity: stands in for the reference's amd64 assembly + unsafe Go
host kernels (SURVEY.md §2.3).  Pure C ABI over ctypes (no pybind11 in this
image).  Falls back to the numpy oracles when a compiler is missing — the
``purego`` build-tag pattern of the reference; :data:`build_error` says why,
and ``chip_smoke.py`` treats a missing shim as an error.

The library's file name carries a hash of the source, the compiler flags
and the host CPU's feature flags (``-march=native``), so a stale or foreign
build is never loaded: any change to one of them builds anew.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional

import math

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "native.cpp")
_FLAGS = ("-O3", "-march=native", "-shared", "-fPIC", "-std=c++17",
          "-pthread")
from ..utils.locks import make_lock

_lock = make_lock("native.build")
_lib: Optional[ctypes.CDLL] = None
_tried = False
#: why the shim is unavailable (None while it loads, or before first use)
build_error: Optional[str] = None

_i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
_u64p = np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
_u8p_w = np.ctypeslib.ndpointer(np.uint8, flags=("C_CONTIGUOUS", "WRITEABLE"))
_i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
_i64p_w = np.ctypeslib.ndpointer(np.int64, flags=("C_CONTIGUOUS", "WRITEABLE"))
_i32p_w = np.ctypeslib.ndpointer(np.int32, flags=("C_CONTIGUOUS", "WRITEABLE"))


def _cpu_flags() -> bytes:
    try:
        with open("/proc/cpuinfo", "rb") as f:
            for line in f:
                if line.startswith(b"flags"):
                    return line
    except OSError:
        pass
    return b""


def _so_path() -> str:
    """Where the build of the present source, flags and CPU lives."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(_cpu_flags())
    return os.path.join(_HERE, f"_native_{h.hexdigest()[:16]}.so")


def _build(so: str) -> Optional[str]:
    """Build ``so`` unless it exists; returns the error, or None."""
    if os.path.exists(so):
        return None
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(["g++", *_FLAGS, _SRC, "-o", tmp],
                       check=True, capture_output=True, timeout=120)
    except subprocess.CalledProcessError as e:
        return f"g++ failed: {e.stderr.decode(errors='replace')[-2000:]}"
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"g++ could not run: {e}"
    os.replace(tmp, so)
    return None


def _auto_threads() -> int:
    """Default native thread split: all cores, capped at 8 — but 1 inside a
    shared-pool worker (the pool already owns the cores; pool width x native
    threads would oversubscribe).  One rule for every threaded native entry
    point so the guard can't drift per call site."""
    from ..utils.pool import available_cpus, in_shared_pool

    return 1 if in_shared_pool() else min(available_cpus(), 8)


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _tried, build_error
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        from ..utils.env import env_bool

        if env_bool("PARQUET_TPU_NO_NATIVE"):
            build_error = "disabled by PARQUET_TPU_NO_NATIVE"
            return None
        so = _so_path()
        build_error = _build(so)
        if build_error is not None:
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            build_error = f"cannot load {so}: {e}"
            return None
        lib.pq_plain_byte_array.restype = ctypes.c_int64
        lib.pq_plain_byte_array.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, _i64p, ctypes.c_void_p]
        lib.pq_assemble_levels.restype = ctypes.c_int64
        lib.pq_assemble_levels.argtypes = [
            _i32p, _i32p, ctypes.c_int64, _i32p, _i32p, ctypes.c_int32,
            ctypes.c_int32, _i64p_w, _u8p_w, _i64p_w, _u8p_w]
        lib.pq_expand_runs.restype = ctypes.c_int64
        lib.pq_expand_runs.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, _i64p, ctypes.c_void_p, _i64p,
            _i64p, np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int32, flags=("C_CONTIGUOUS", "WRITEABLE")),
            ctypes.c_int64]
        lib.pq_assemble_list_runs.restype = ctypes.c_int64
        lib.pq_assemble_list_runs.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, _i64p, ctypes.c_void_p, _i64p,
            _i64p, _i32p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64, _i64p, ctypes.c_void_p, _i64p,
            _i64p, _i32p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
            _i64p_w, _u8p_w, _u8p_w, _i64p_w]
        lib.pq_delta_prescan.restype = ctypes.c_int64
        lib.pq_delta_prescan.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, _i64p_w, _i64p_w,
            np.ctypeslib.ndpointer(np.int32, flags=("C_CONTIGUOUS", "WRITEABLE")),
            _i64p_w, ctypes.c_int64]
        lib.pq_gather_ba.restype = ctypes.c_int64
        lib.pq_gather_ba.argtypes = [
            ctypes.c_void_p, _i64p, ctypes.c_int64, _i64p, ctypes.c_int64,
            _i64p_w, ctypes.c_void_p]
        lib.pq_encode_plain_ba.restype = ctypes.c_int64
        lib.pq_encode_plain_ba.argtypes = [ctypes.c_void_p, _i64p,
                                           ctypes.c_int64, ctypes.c_int64,
                                           _u8p_w]
        lib.pq_encode_delta.restype = ctypes.c_int64
        lib.pq_encode_delta.argtypes = [_i64p, ctypes.c_int64, ctypes.c_int32,
                                        ctypes.c_int32, _u8p_w, ctypes.c_int64]
        lib.pq_encode_rle.restype = ctypes.c_int64
        lib.pq_encode_rle.argtypes = [_i64p, ctypes.c_int64, ctypes.c_int32,
                                      ctypes.c_int32, _u8p_w, ctypes.c_int64]
        lib.pq_pack_bits.restype = ctypes.c_int64
        lib.pq_pack_bits.argtypes = [_i64p, ctypes.c_int64, ctypes.c_int32,
                                     _u8p_w]
        lib.pq_dict_build_i64.restype = ctypes.c_int64
        lib.pq_dict_build_i64.argtypes = [_i64p, ctypes.c_int64,
                                          ctypes.c_int64, _i64p_w, _i64p_w]
        lib.pq_scan_rle_runs.restype = ctypes.c_int64
        lib.pq_scan_rle_runs.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
            _u8p_w, _i64p, _i64p, _i64p]
        lib.pq_delta_decode.restype = ctypes.c_int64
        lib.pq_delta_decode.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, _i64p, _i32p, _i64p, _i64p,
            _i64p, _i64p, _i64p, _i64p, ctypes.c_int64, _i64p_w,
            ctypes.c_int32]
        lib.pq_scan_page_headers.restype = ctypes.c_int64
        lib.pq_scan_page_headers.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            _i64p_w]
        lib.pq_scan_page_headers_partial.restype = ctypes.c_int64
        lib.pq_scan_page_headers_partial.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            _i64p_w, _i64p_w]
        lib.pq_count_target_in_runs.restype = ctypes.c_int64
        lib.pq_count_target_in_runs.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p, _i64p, _i64p,
            _i64p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int64]
        lib.pq_decompress_pages.restype = ctypes.c_int64
        lib.pq_decompress_pages.argtypes = [
            _i64p, _i64p, ctypes.c_int64, ctypes.c_int32, _u8p_w, _i64p,
            ctypes.c_int32]
        lib.pq_plain_ba_batch.restype = ctypes.c_int64
        lib.pq_plain_ba_batch.argtypes = [
            _i64p, _i64p, _i64p, ctypes.c_int64, _i64p_w, _u8p_w]
        lib.pq_rle_dict_batch.restype = ctypes.c_int64
        lib.pq_rle_dict_batch.argtypes = [
            _i64p, _i64p, _i64p, _u8p, ctypes.c_int64, _i32p_w]
        lib.pq_xxh64.restype = ctypes.c_uint64
        lib.pq_xxh64.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint64]
        lib.pq_xxh64_batch.restype = None
        lib.pq_xxh64_batch.argtypes = [ctypes.c_void_p, _i64p, ctypes.c_int64, _u64p]
        lib.pq_delta_byte_array_expand.restype = ctypes.c_int64
        lib.pq_delta_byte_array_expand.argtypes = [
            _i64p, ctypes.c_void_p, _i64p, ctypes.c_int64, _u8p_w, _i64p]
        lib.pq_dict_build_ba.restype = ctypes.c_int64
        lib.pq_dict_build_ba.argtypes = [
            ctypes.c_void_p, _i64p, ctypes.c_int64, _i64p, ctypes.c_int64]
        lib.pq_minmax_ba.restype = None
        lib.pq_minmax_ba.argtypes = [ctypes.c_void_p, _i64p, ctypes.c_int64,
                                     ctypes.c_int64, _i64p, _i64p]
        lib.pq_dict_first_occurrence.restype = None
        lib.pq_dict_first_occurrence.argtypes = [_i64p, ctypes.c_int64,
                                                 ctypes.c_int64, _i64p]
        _lib = lib
        return _lib


# ---------------------------------------------------------------------------
# numpy-friendly wrappers (None return → caller falls back to the oracle)
# ---------------------------------------------------------------------------


def plain_byte_array(buf: np.ndarray, n: int):
    lib = get_lib()
    if lib is None:
        return None
    buf = np.ascontiguousarray(buf)
    offsets = np.empty(n + 1, dtype=np.int64)
    total = lib.pq_plain_byte_array(buf.ctypes.data, len(buf), n, offsets, None)
    if total < 0:
        raise ValueError("PLAIN BYTE_ARRAY truncated")
    values = np.empty(max(total, 1), dtype=np.uint8)
    lib.pq_plain_byte_array(buf.ctypes.data, len(buf), n, offsets,
                            values.ctypes.data)
    return values[:total], offsets.astype(np.int32)


def plain_ba_batch(srcs, counts):
    """Parse many pages' PLAIN BYTE_ARRAY sections in one native call,
    producing the CHUNK-level (values, int64 offsets) directly (offsets
    rebased across pages — no python merge).  ``srcs`` are bytes-like page
    value sections, ``counts`` the value count per page.  None when the
    shim is unavailable; raises ValueError on truncation."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(srcs)
    ptrs, lens, keep = _src_pointers(srcs)
    total_src = int(lens[:n].sum()) if n else 0
    cnts = np.ascontiguousarray(counts, np.int64)
    if bool((cnts < 0).any()):
        return None
    n_vals = int(cnts.sum())
    offsets = np.empty(n_vals + 1, np.int64)
    values = np.empty(max(total_src, 1), np.uint8)
    total = lib.pq_plain_ba_batch(ptrs, lens, cnts, n, offsets, values)
    if total < 0:
        raise ValueError(
            f"PLAIN BYTE_ARRAY truncated in page {-int(total) - 1}")
    if total * 2 < len(values):
        # short-string chunks: the worst-case buffer (raw section size,
        # i.e. value bytes + 4 per string) would pin 2-5x the data for the
        # column's lifetime — compact when the slack is half or more
        return values[:total].copy(), offsets
    return values[:total], offsets


def rle_dict_batch(srcs, counts, prefixes):
    """Decode many pages' RLE_DICTIONARY index sections in one native call
    → one chunk-level int32 index array.  ``srcs`` are bytes-like page
    payloads (post-decompression), ``counts`` values per page,
    ``prefixes`` per-page bools: True = a v1 optional page whose payload
    leads with a length-prefixed def-level stream (must be one all-1s RLE
    run — all-present; otherwise the caller's python path handles nulls).
    None when the shim is unavailable OR any page needs the fallback."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(srcs)
    ptrs, lens, keep = _src_pointers(srcs)
    cnts = np.ascontiguousarray(counts, np.int64)
    if bool((cnts < 0).any()):
        return None
    pref = np.ascontiguousarray(prefixes, np.uint8)
    out = np.empty(max(int(cnts.sum()), 1), np.int32)
    total = lib.pq_rle_dict_batch(ptrs, lens, cnts, pref, n, out)
    if total < 0:
        return None  # page with nulls / unexpected framing: python path
    return out[:total]


def assemble_levels(defs: np.ndarray, reps: np.ndarray, ks, dks, max_def: int):
    """Dremel assembly: returns (list_offsets, list_validity, leaf_validity)
    per repeated level, or None when the native lib is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(defs)
    nlev = len(ks)
    defs = np.ascontiguousarray(defs, np.int32)
    reps = np.ascontiguousarray(reps, np.int32)
    offsets_flat = np.empty(nlev * (n + 1), np.int64)
    valid_flat = np.empty(max(nlev * n, 1), np.uint8)
    inst_counts = np.empty(nlev, np.int64)
    leaf_valid = np.empty(max(n, 1), np.uint8)
    leaf_count = lib.pq_assemble_levels(
        defs, reps, n, np.ascontiguousarray(ks, np.int32),
        np.ascontiguousarray(dks, np.int32), nlev, max_def,
        offsets_flat, valid_flat, inst_counts, leaf_valid)
    offsets, validity = [], []
    for i in range(nlev):
        c = int(inst_counts[i])
        # copies, not views: a view would pin the whole nlev*n scratch buffer
        # for the lifetime of the decoded Column
        offsets.append(offsets_flat[i * (n + 1) : i * (n + 1) + c + 1].copy())
        validity.append(valid_flat[i * n : i * n + c].astype(bool))
    return offsets, validity, leaf_valid[:leaf_count].astype(bool)


def assemble_list_runs(buf: np.ndarray, def_tables: tuple, rep_tables: tuple,
                       n: int, dk: int, max_def: int):
    """Fused single-level list assembly from level run tables: returns
    (list_offsets, list_validity, leaf_validity) without materializing
    per-slot def/rep levels, or None when the native lib is unavailable.

    ``def_tables``/``rep_tables`` are (ends, kinds, payloads, bit_offsets,
    widths) over the shared level byte stream ``buf``.
    """
    lib = get_lib()
    if lib is None or n == 0:
        return None
    buf = np.ascontiguousarray(buf)
    # keep every coerced table alive by name for the duration of the C call
    de, dkk, dp, db, dw = (np.ascontiguousarray(a, t) for a, t in
                           zip(def_tables, (np.int64, np.uint8, np.int64,
                                            np.int64, np.int32)))
    re_, rk, rp, rb, rw = (np.ascontiguousarray(a, t) for a, t in
                           zip(rep_tables, (np.int64, np.uint8, np.int64,
                                            np.int64, np.int32)))
    offsets = np.empty(n + 1, np.int64)
    lvalid = np.empty(max(n, 1), np.uint8)
    leaf_valid = np.empty(max(n, 1), np.uint8)
    counts = np.empty(2, np.int64)
    rc = lib.pq_assemble_list_runs(
        buf.ctypes.data if len(buf) else None, len(buf),
        de, dkk.ctypes.data, dp, db, dw, len(de),
        buf.ctypes.data if len(buf) else None, len(buf),
        re_, rk.ctypes.data, rp, rb, rw, len(re_),
        n, dk, max_def, offsets, lvalid, leaf_valid, counts)
    if rc != 0:
        return None
    ninst, nelem = int(counts[0]), int(counts[1])
    return (offsets[: ninst + 1].copy(), lvalid[:ninst].astype(bool),
            leaf_valid[:nelem].astype(bool))


def delta_prescan(data: np.ndarray, pos: int = 0):
    """Miniblock table of one DELTA_BINARY_PACKED stream, or None when the
    lib is unavailable / the stream is malformed (caller uses the Python
    scanner, which raises precise errors)."""
    lib = get_lib()
    if lib is None:
        return None
    data = np.ascontiguousarray(data)
    header = np.empty(4, np.int64)
    # exact miniblock bound from the stream header (4 uvarints, cheap):
    # w=0 miniblocks occupy no payload, so a data-length bound would be wrong
    from ..ops import ref as _ref

    try:
        bs, p = _ref.read_uvarint(data, pos)
        nmb, p = _ref.read_uvarint(data, p)
        total, _ = _ref.read_uvarint(data, p)
    except Exception:
        return None
    if nmb == 0 or bs == 0 or bs % nmb:
        return None
    vpm = bs // nmb
    if vpm == 0:
        return None
    # each miniblock consumes one width byte from the stream, so the count
    # can never exceed the remaining bytes — bounds np.empty against absurd
    # untrusted `total` values (header bytes are attacker-controlled)
    cap = min(total // vpm + nmb + 2, len(data) - pos + 2)
    offsets = np.empty(cap, np.int64)
    widths = np.empty(cap, np.int32)
    mins = np.empty(cap, np.int64)
    k = lib.pq_delta_prescan(data.ctypes.data if len(data) else None,
                             len(data), pos, header, offsets, widths, mins,
                             cap)
    if k < 0:
        return None
    return (int(header[0]), int(header[1]), int(header[2]),
            offsets[:k].copy(), widths[:k].copy(), mins[:k].copy(),
            int(header[3]))


def gather_ba(dvals: np.ndarray, doffs: np.ndarray, indices: np.ndarray):
    """Dictionary gather for BYTE_ARRAY: (values, int64 offsets), or None."""
    lib = get_lib()
    if lib is None:
        return None
    dvals = np.ascontiguousarray(dvals)
    doffs = np.ascontiguousarray(doffs, np.int64)
    indices = np.ascontiguousarray(indices, np.int64)
    n = len(indices)
    out_offs = np.empty(n + 1, np.int64)
    total = lib.pq_gather_ba(dvals.ctypes.data if len(dvals) else None, doffs,
                             len(doffs) - 1, indices, n, out_offs, None)
    if total < 0:
        # detected corruption, NOT unavailability: an out-of-range dictionary
        # index must never fall back to numpy (whose fancy indexing would
        # silently wrap negatives)
        raise ValueError("dictionary index out of range")
    out_vals = np.empty(max(total, 1), np.uint8)
    lib.pq_gather_ba(dvals.ctypes.data if len(dvals) else None, doffs,
                     len(doffs) - 1, indices, n, out_offs,
                     out_vals.ctypes.data)
    return out_vals[:total], out_offs


def encode_plain_ba(vals: np.ndarray, offs: np.ndarray) -> Optional[bytes]:
    """PLAIN BYTE_ARRAY stream ([4B LE length][bytes]...), or None."""
    lib = get_lib()
    if lib is None:
        return None
    vals = np.ascontiguousarray(vals)
    offs = np.ascontiguousarray(offs, np.int64)
    n = len(offs) - 1
    out = np.empty(max(int(offs[-1]), 0) + 4 * max(n, 0) + 1, np.uint8)
    wrote = lib.pq_encode_plain_ba(vals.ctypes.data if len(vals) else None,
                                   offs, n, len(vals), out)
    if wrote < 0:
        # detected corruption (non-monotonic / out-of-range offsets), NOT
        # unavailability — never hand these to the numpy fallback
        raise ValueError("malformed BYTE_ARRAY offsets")
    return out[:wrote].tobytes()


def encode_delta(values: np.ndarray, block_size: int = 128,
                 n_miniblocks: int = 4) -> Optional[bytes]:
    """DELTA_BINARY_PACKED stream, byte-identical to the Python oracle, or
    None when the lib is unavailable / the layout is unsupported."""
    lib = get_lib()
    if lib is None or len(values) == 0:
        return None
    values = np.ascontiguousarray(values, np.int64)
    n = len(values)
    # worst case: every delta at 64 bits + headers per block
    nblocks = (n + block_size - 1) // block_size + 1
    cap = 64 + n * 8 + nblocks * (16 + n_miniblocks) + block_size * 8
    out = np.empty(cap, np.uint8)
    wrote = lib.pq_encode_delta(values, n, block_size, n_miniblocks, out, cap)
    if wrote < 0:
        return None
    return out[:wrote].tobytes()


def encode_rle(values: np.ndarray, bit_width: int,
               min_repeat: int = 8) -> Optional[bytes]:
    """Hybrid RLE/bit-packed stream, byte-identical to ref.encode_rle, or
    None when unavailable / the width is unsupported."""
    lib = get_lib()
    if lib is None or bit_width > 56 or len(values) == 0:
        return None
    values = np.ascontiguousarray(values, np.int64)
    n = len(values)
    vbytes = (bit_width + 7) // 8
    cap = 64 + (n + 8) * bit_width // 8 + (n // 8 + 2) * (10 + vbytes)
    out = np.empty(cap, np.uint8)
    wrote = lib.pq_encode_rle(values, n, bit_width, min_repeat, out, cap)
    if wrote < 0:
        return None
    return out[:wrote].tobytes()


def pack_bits(values: np.ndarray, bit_width: int) -> Optional[bytes]:
    """LSB-first bit packing (write path), or None when unavailable/wide."""
    lib = get_lib()
    if lib is None or bit_width > 56:
        return None
    values = np.ascontiguousarray(values, np.int64)
    out = np.empty((len(values) * bit_width + 7) // 8 + 8, np.uint8)
    wrote = lib.pq_pack_bits(values, len(values), bit_width, out)
    if wrote < 0:
        return None
    return out[:wrote].tobytes()


def _window_predicts_overflow(distinct: int, window: int,
                              max_unique: int) -> bool:
    """Cardinality-estimator bail test: from one window's distinct count,
    estimate global cardinality K via E[distinct] = K(1 - exp(-w/K))
    (uniform-draw model) and predict overflow only when the estimate
    clearly exceeds ``max_unique``.  The previous raw >= 7/8-unique test
    falsely predicted overflow for columns whose cardinality is high in a
    32k window yet still under max_unique (e.g. ~45%-of-n cardinality
    against a n/2 budget) and silently disabled dictionary encoding
    (advisor r4).  Skewed data biases K low, i.e. toward attempting the
    build — the safe direction (a wasted build, never a wrong refusal)."""
    if distinct >= window:  # all-unique window: the estimator diverges
        return True
    frac = distinct / window
    if frac <= 0:
        return False
    lo_x, hi_x = 1e-9, 60.0  # solve (1 - e^-x)/x = frac for x = w/K
    for _ in range(40):
        mid = (lo_x + hi_x) / 2
        if (1 - math.exp(-mid)) / mid > frac:
            lo_x = mid
        else:
            hi_x = mid
    est_k = window / ((lo_x + hi_x) / 2)
    return est_k > 1.25 * max_unique


def dict_build_fixed(vals: np.ndarray, max_unique: int):
    """First-occurrence dedup of a fixed-width column (any 4/8-byte dtype,
    compared bitwise).  Returns (uniques in vals.dtype, int64 indices),
    "overflow" past max_unique, or None when the lib is unavailable."""
    lib = get_lib()
    if lib is None or len(vals) == 0:
        return None
    orig = vals.dtype
    if vals.dtype.itemsize == 8:
        keys = np.ascontiguousarray(vals).view(np.int64)
    elif vals.dtype.itemsize == 4:
        # widen via the 32-bit bit pattern so float32 NaNs stay bit-exact
        keys = np.ascontiguousarray(vals).view(np.int32).astype(np.int64)
    else:
        return None
    keys = np.ascontiguousarray(keys)
    n = len(keys)
    # Sample-based early bail: near-unique columns (the overflow case)
    # otherwise pay a full hash pass just to discover they can't dictionary-
    # encode.  Two windows — prefix AND middle — must BOTH estimate a
    # cardinality clearly past max_unique (see _window_predicts_overflow):
    # data whose first occurrences cluster early (sorted keys, then
    # repeats) shows repeats in the middle window and still gets its full
    # build.  Heuristic only affects whether dictionary encoding is
    # attempted, never correctness.
    sample = 1 << 14
    if n > 4 * sample and max_unique >= sample:
        s_idx = np.empty(sample, np.int64)
        s_uniq = np.empty(sample, np.int64)
        nu_a = lib.pq_dict_build_i64(keys[:sample], sample, sample,
                                     s_idx, s_uniq)
        if _window_predicts_overflow(nu_a, sample, max_unique):
            mid = n // 2
            nu_b = lib.pq_dict_build_i64(keys[mid: mid + sample], sample,
                                         sample, s_idx, s_uniq)
            if _window_predicts_overflow(nu_b, sample, max_unique):
                return "overflow"
    indices = np.empty(n, np.int64)
    uniques = np.empty(max(max_unique, 1), np.int64)
    nu = lib.pq_dict_build_i64(keys, n, max_unique, indices, uniques)
    if nu < 0:
        return "overflow"
    uniq = uniques[:nu]
    if vals.dtype.itemsize == 4:
        uniq = uniq.astype(np.int32).view(orig)
    else:
        uniq = uniq.view(orig)
    return uniq.copy(), indices


def expand_runs(buf: np.ndarray, ends: np.ndarray, kinds: np.ndarray,
                payloads: np.ndarray, bit_offsets: np.ndarray,
                widths: np.ndarray, n: int):
    """Expand a merged RLE/bit-packed run table to int32 values (host)."""
    lib = get_lib()
    if lib is None or n == 0:
        return None
    buf = np.ascontiguousarray(buf)
    kinds = np.ascontiguousarray(kinds, dtype=np.uint8)
    out = np.empty(n, dtype=np.int32)
    wrote = lib.pq_expand_runs(
        buf.ctypes.data if len(buf) else None, len(buf),
        np.ascontiguousarray(ends, np.int64), kinds.ctypes.data,
        np.ascontiguousarray(payloads, np.int64),
        np.ascontiguousarray(bit_offsets, np.int64),
        np.ascontiguousarray(widths, np.int32), len(kinds), out, n)
    return out[:wrote]


def select_runs(buf: np.ndarray, kinds, counts, payloads, offsets,
                bit_width: int, take: np.ndarray):
    """Point-select from an RLE/bit-packed run table (the masked-emit hot
    loop, io/fused.py): expand ONLY the runs the sorted ``take`` ordinals
    touch — one native expand pass over the touched subset — then gather.
    Beats per-value bit gathers when takes cluster densely inside runs.
    Returns int64 values, or None when the lib is unavailable / the width is
    out of the int32 expansion range (caller uses the bit-gather oracle)."""
    lib = get_lib()
    if lib is None or bit_width > 31 or len(take) == 0:
        return None
    counts = np.asarray(counts, np.int64)
    take = np.asarray(take, np.int64)
    ends = np.cumsum(counts)
    run = np.searchsorted(ends, take, side="right")
    starts = ends - counts
    touched = np.unique(run)
    t_counts = counts[touched]
    sub_ends = np.cumsum(t_counts)
    total = int(sub_ends[-1])
    expanded = expand_runs(
        buf, sub_ends, np.asarray(kinds, np.uint8)[touched],
        np.asarray(payloads, np.int64)[touched],
        np.asarray(offsets, np.int64)[touched] * 8,
        np.full(len(touched), bit_width, np.int32), total)
    if expanded is None:
        return None
    sub_base = sub_ends - t_counts
    rank = np.searchsorted(touched, run)
    return expanded[sub_base[rank] + (take - starts[run])].astype(np.int64)


def delta_decode(buf: np.ndarray, mb_bitoffs, mb_widths, mb_mins,
                 page_mb_start, page_first, page_count, page_vpm,
                 nthreads: int = 0):
    """Fused DELTA_BINARY_PACKED decode from prescan miniblock tables:
    unpack + min-add + prefix sum in one multithreaded native pass (pages
    are independent).  Returns int64 values or None when the native library
    is unavailable; raises ValueError on malformed tables."""
    lib = get_lib()
    if lib is None:
        return None
    counts = np.ascontiguousarray(page_count, np.int64)
    out_start = np.zeros(len(counts) + 1, np.int64)
    np.cumsum(counts, out=out_start[1:])
    out = np.empty(int(out_start[-1]), np.int64)
    buf = np.ascontiguousarray(buf)
    if not nthreads:
        nthreads = _auto_threads()
    rc = lib.pq_delta_decode(
        buf.ctypes.data if len(buf) else None, len(buf),
        np.ascontiguousarray(mb_bitoffs, np.int64),
        np.ascontiguousarray(mb_widths, np.int32),
        np.ascontiguousarray(mb_mins, np.int64),
        np.ascontiguousarray(page_mb_start, np.int64),
        np.ascontiguousarray(page_first, np.int64),
        counts, out_start,
        np.ascontiguousarray(page_vpm, np.int64),
        len(counts), out, nthreads)
    if rc != 0:
        raise ValueError("malformed DELTA_BINARY_PACKED miniblock tables")
    return out


# column indexes of a pq_scan_page_headers row — keep in sync with the
# PG_* enum in native.cpp
PG_HEADER_POS = 0
PG_DATA_POS = 1
PG_TYPE = 2
PG_COMP = 3
PG_UNCOMP = 4
PG_CRC = 5
PG_NVALS = 6
PG_ENC = 7
PG_DEF_ENC = 8
PG_REP_ENC = 9
PG_RL_BYTES = 10
PG_DL_BYTES = 11
PG_NNULLS = 12
PG_IS_COMPRESSED = 13
PG_DICT_NVALS = 14
PG_NROWS = 15
PG_NFIELDS = 16


def scan_page_headers(buf, total_values: int):
    """Batch-parse a chunk's PageHeader stream.  Returns an (npages,
    PG_NFIELDS) int64 array, or None when the native library is unavailable
    or the stream has a construct the fast scanner doesn't handle (caller
    falls back to the Python thrift walk, which owns error reporting)."""
    lib = get_lib()
    if lib is None:
        return None
    b = buf if isinstance(buf, np.ndarray) else np.frombuffer(buf, np.uint8)
    b = np.ascontiguousarray(b)
    # worst realistic case is ~one value per page; grow geometrically from a
    # generous page-size estimate instead of allocating total_values rows
    cap = max(16, min(int(total_values), len(b) // 64 + 8))
    while True:
        out = np.empty((cap, PG_NFIELDS), dtype=np.int64)
        k = lib.pq_scan_page_headers(b.ctypes.data if len(b) else None,
                                     len(b), total_values, cap, out)
        if k == -2:
            if cap > int(total_values) + 8:
                return None  # more pages than values: malformed; let Python raise
            cap *= 4
            continue
        if k < 0:
            return None
        return out[:k]


def scan_page_headers_partial(buf, total_values: int):
    """Windowed header scan: parse as many complete pages as the buffer
    holds.  Returns (rows, consumed_bytes, values_seen) — rows may be empty
    when not even one header+payload fits — or None without the lib."""
    lib = get_lib()
    if lib is None:
        return None
    b = buf if isinstance(buf, np.ndarray) else np.frombuffer(buf, np.uint8)
    b = np.ascontiguousarray(b)
    cap = max(16, min(int(total_values), len(b) // 64 + 8))
    consumed = np.zeros(2, np.int64)
    while True:
        out = np.empty((cap, PG_NFIELDS), dtype=np.int64)
        k = lib.pq_scan_page_headers_partial(
            b.ctypes.data if len(b) else None, len(b), total_values, cap,
            out, consumed)
        if k == cap:  # may have stopped only for capacity: grow and retry
            cap *= 4
            continue
        if k < 0:
            return None
        return out[:k], int(consumed[0]), int(consumed[1])


def count_target_in_runs(body: np.ndarray, kinds, cnts, payloads, offs,
                         width: int, target: int):
    """Count run-table values equal to ``target`` (def == max_def present
    count) in one native pass, or None without the lib."""
    lib = get_lib()
    if lib is None or width <= 0 or width > 32:
        return None
    body = np.ascontiguousarray(body)
    kinds = np.ascontiguousarray(kinds, np.uint8)
    n = lib.pq_count_target_in_runs(
        body.ctypes.data if len(body) else None, len(body),
        kinds.ctypes.data, np.ascontiguousarray(cnts, np.int64),
        np.ascontiguousarray(payloads, np.int64),
        np.ascontiguousarray(offs, np.int64), len(kinds), width, target)
    return None if n < 0 else int(n)


def scan_rle_runs(buf: np.ndarray, n: int, bit_width: int):
    lib = get_lib()
    if lib is None or n == 0:
        return None
    buf = np.ascontiguousarray(buf)
    cap = n + 1
    kinds = np.empty(cap, dtype=np.uint8)
    counts = np.empty(cap, dtype=np.int64)
    payloads = np.empty(cap, dtype=np.int64)
    offsets = np.empty(cap, dtype=np.int64)
    k = lib.pq_scan_rle_runs(buf.ctypes.data, len(buf), n, bit_width,
                             kinds, counts, payloads, offsets)
    if k < 0:
        raise ValueError("malformed RLE hybrid stream")
    return kinds[:k], counts[:k], payloads[:k], offsets[:k]


def xxh64(data, seed: int = 0):
    lib = get_lib()
    if lib is None:
        return None
    b = np.frombuffer(data, np.uint8) if not isinstance(data, np.ndarray) else data
    b = np.ascontiguousarray(b)
    return int(lib.pq_xxh64(b.ctypes.data if len(b) else None, len(b), seed))


def xxh64_batch(data: np.ndarray, offsets: np.ndarray):
    lib = get_lib()
    if lib is None:
        return None
    data = np.ascontiguousarray(data)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = len(offsets) - 1
    out = np.empty(n, dtype=np.uint64)
    lib.pq_xxh64_batch(data.ctypes.data if len(data) else None, offsets, n, out)
    return out


def delta_byte_array_expand(prefix_lens, suffix_data, suffix_offsets, out_offsets):
    lib = get_lib()
    if lib is None:
        return None
    n = len(prefix_lens)
    prefix_lens = np.ascontiguousarray(prefix_lens, dtype=np.int64)
    suffix_data = np.ascontiguousarray(suffix_data)
    suffix_offsets = np.ascontiguousarray(suffix_offsets, dtype=np.int64)
    out_offsets = np.ascontiguousarray(out_offsets, dtype=np.int64)
    total = int(out_offsets[-1]) if n else 0
    out = np.empty(max(total, 1), dtype=np.uint8)
    lib.pq_delta_byte_array_expand(prefix_lens,
                                   suffix_data.ctypes.data if len(suffix_data) else None,
                                   suffix_offsets, n, out, out_offsets)
    return out[:total]


def _src_pointers(srcs):
    """Marshal bytes-like page payloads into (ptrs, lens, keep) for native
    calls that read per-page raw pointers.  ``keep`` must stay referenced
    for the duration of the call."""
    n = len(srcs)
    ptrs = np.empty(max(n, 1), np.int64)
    lens = np.empty(max(n, 1), np.int64)
    keep = []
    for i, s in enumerate(srcs):
        a = s if isinstance(s, np.ndarray) else np.frombuffer(s, np.uint8)
        if not a.flags.c_contiguous:
            a = np.ascontiguousarray(a)
        keep.append(a)
        ptrs[i] = a.ctypes.data if len(a) else 0
        lens[i] = len(a)
    return ptrs, lens, keep


def decompress_pages(srcs, out_sizes, codec_id: int, nthreads: int = 1):
    """Decompress many page payloads in ONE native call (snappy/zstd via
    the dlopen'd system libs; 0 = memcpy).  ``srcs`` is a sequence of
    bytes-like payloads (any layout — pointers are taken per page),
    ``out_sizes`` their expected uncompressed sizes.  Returns
    ``(buffer, offsets)`` with page i at ``buffer[offsets[i]:offsets[i+1]]``,
    or None when the shim/codec is unavailable or any page fails (callers
    fall back to the per-page codec path, which raises the precise error)."""
    lib = get_lib()
    if lib is None or codec_id not in (0, 1, 6):
        return None
    n = len(srcs)
    if n == 0:
        return np.empty(0, np.uint8), np.zeros(1, np.int64)
    # header-supplied sizes are UNTRUSTED: a negative size (e.g. v2's
    # uncompressed - levels underflowing on a crafted header) would make
    # the native call write before/past the output buffer
    sizes_arr = np.asarray(out_sizes, np.int64)
    if len(sizes_arr) != n or bool((sizes_arr < 0).any()):
        return None
    ptrs, lens, keep = _src_pointers(srcs)
    offs = np.zeros(n + 1, np.int64)
    np.cumsum(sizes_arr, out=offs[1:])
    out = np.empty(max(int(offs[-1]), 1), np.uint8)
    rc = lib.pq_decompress_pages(ptrs, lens, n, codec_id, out, offs,
                                 max(int(nthreads), 1))
    if rc != 0:
        return None
    return out, offs


def dict_build_ba(data: np.ndarray, offsets: np.ndarray, max_unique: int,
                  sample_bail: bool = True):
    """Returns (indices, first_occurrence_rows), "overflow", or None.

    ``sample_bail=False`` disables the near-unique early bail — required
    when the input is a CONCATENATION of internally-unique sets (e.g.
    unifying per-row-group dictionaries): every sample window then lies
    inside one unique set and predicts overflow even though cross-set
    duplicates abound."""
    lib = get_lib()
    if lib is None:
        return None
    data = np.ascontiguousarray(data)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = len(offsets) - 1
    indices = np.empty(max(n, 1), dtype=np.int64)
    # Sample-based early bail, mirroring dict_build_fixed: near-unique
    # string columns should not pay a half-column hash build just to learn
    # they overflow.  Both a prefix and a middle window must ESTIMATE a
    # cardinality clearly past max_unique (_window_predicts_overflow;
    # first occurrences clustering early would fool a prefix-only sample).
    # Affects only whether dictionary encoding is attempted, never
    # correctness.
    sample = 1 << 15
    if sample_bail and n > 4 * sample and max_unique >= sample:
        s_idx = np.empty(sample, np.int64)
        nu_a = lib.pq_dict_build_ba(data.ctypes.data, offsets,
                                    sample, s_idx, sample)
        if _window_predicts_overflow(nu_a, sample, max_unique):
            mid = n // 2
            nu_b = lib.pq_dict_build_ba(data.ctypes.data,
                                        offsets[mid:], sample, s_idx,
                                        sample)
            if _window_predicts_overflow(nu_b, sample, max_unique):
                return "overflow"
    k = lib.pq_dict_build_ba(data.ctypes.data if len(data) else None,
                             offsets, n, indices, max_unique)
    if k < 0:
        return "overflow"
    first = np.empty(max(k, 1), dtype=np.int64)
    lib.pq_dict_first_occurrence(indices, n, k, first)
    return indices[:n], first[:k]

def minmax_ba(data: np.ndarray, offsets: np.ndarray, v0: int, v1: int):
    """(min_idx, max_idx) over byte-string values [v0, v1) in unsigned
    lexicographic order; None when the shim is unavailable."""
    lib = get_lib()
    if lib is None or v1 <= v0:
        return None
    data = np.ascontiguousarray(data)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    mi = np.zeros(1, np.int64)
    ma = np.zeros(1, np.int64)
    lib.pq_minmax_ba(data.ctypes.data if len(data) else None, offsets,
                     v0, v1, mi, ma)
    return int(mi[0]), int(ma[0])
