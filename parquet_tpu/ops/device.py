"""Device (XLA/jnp) decode kernels — the TPU compute path.

Reference parity: these replace the reference's amd64 assembly kernels
(SURVEY.md §2.3: internal/bitpack, encoding/rle asm, delta asm,
bytestreamsplit asm) at the same insertion point — the ``encoding.Encoding``
registry.  Design per SURVEY.md §7:

- All kernels are pure functions of flat uint8 buffers + small metadata
  arrays, jit-compiled with static shapes (bucket-padded by the caller);
  PLAIN fixed-width values come as exact-length uint32 words instead.
- The inherently sequential work (run-header varint scans, miniblock header
  walks) happens on host at *metadata* scale (bytes per run/miniblock), then
  the device does the wide expansion at *data* scale — the two-pass split of
  SURVEY.md §7 hard part 1.
- Everything is a gather/shift/mask/cumsum — no data-dependent control flow,
  so XLA fuses freely.  Pallas variants for the hottest kernels live in
  ``pallas_kernels.py``.

**32-bit-lane discipline (TPU-first):** TPU VPUs are 32-bit-lane machines;
the TPU compiler emulates 64-bit element types, and its float64 is not
bit-exact (a float64 bitcast on a v5e and read back differed from the file's
bytes — PR 21 chip run).  So device kernels NEVER bitcast to 64-bit types:
64-bit columns live on device as ``(n, 2)`` uint32 pairs — byte-exact,
converted to int64/float64 by a zero-copy ``.view()`` at host
materialization — and all bit-unpacking is 32-bit shift/mask arithmetic.
Only DELTA_BINARY_PACKED's int64 prefix-sum uses (emulated) s64
*arithmetic*, which is exact.

int64 note: importing this module enables jax x64 (needed for s64 cumsum and
wide bit offsets) unless PARQUET_TPU_NO_X64 is set.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Optional, Tuple

import numpy as np

import jax

from ..utils.env import env_bool

if not env_bool("PARQUET_TPU_NO_X64"):
    jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp

from ..utils.debug import counters

_U32 = jnp.uint32


# ---------------------------------------------------------------------------
# Word gathers (arithmetic combine — no 64-bit bitcasts anywhere)
#
# TPU-first: per-element loads are the expensive primitive, so an unaligned
# 32-bit read is TWO aligned word gathers + shift-combine (not four byte
# gathers), and all index math runs in int32 lanes — a chunk's staged buffer
# is < 2^27 bytes (enforced at staging), so bit positions fit int32 and the
# compiler never emits emulated-64-bit index vectors on the hot path.
# ---------------------------------------------------------------------------

#: staged buffers larger than this fall back to the host path: bit offsets
#: must fit int32 (2^27 bytes → 2^30 bits), keeping index math in 32-bit lanes
MAX_DEVICE_BUF = 1 << 27


def _as_words(buf: jax.Array) -> jax.Array:
    """uint8 staged buffer → uint32 little-endian word view (zero-padded to a
    word boundary; out-of-range word gathers are clamped by XLA and the
    garbage bits always fall outside the value mask).  Callers slice the
    words they need AFTER the bitcast: the TPU compiler takes minutes over
    a reshape+bitcast of a buffer sliced to an arbitrary length (1M 8-byte
    values: 319 s) and a second over the power-of-two staging bucket."""
    if buf.shape[0] % 4:
        buf = jnp.pad(buf, (0, 4 - buf.shape[0] % 4))
    return jax.lax.bitcast_convert_type(buf.reshape(-1, 4), _U32)


#: row length of :func:`cumsum`'s blocked scan
_SCAN_BLOCK = 1024


def cumsum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum along the last axis (``jnp.cumsum`` semantics
    and dtype; leading axes are independent rows), blocked: the TPU
    compiler takes ~20 s over a flat cumsum of 1M values and under a second
    over rows of 1,024 plus a cumsum of the row totals (recursively, for
    very long inputs)."""
    n, lead = x.shape[-1], x.shape[:-1]
    if n <= _SCAN_BLOCK:
        return jnp.cumsum(x, axis=-1)
    pad = ((0, 0),) * len(lead) + ((0, -n % _SCAN_BLOCK),)
    rows = jnp.pad(x, pad).reshape(*lead, -1, _SCAN_BLOCK)
    inner = jnp.cumsum(rows, axis=-1)
    tot = inner[..., -1]
    return (inner + (cumsum(tot) - tot)[..., None]).reshape(*lead, -1)[..., :n]


def _word_at(bit_starts: jax.Array):
    """(aligned word index, in-word shift) for each unaligned bit position."""
    wi = (bit_starts >> 5).astype(jnp.int32)
    sh = (bit_starts.astype(jnp.int32) & 31).astype(_U32)
    return wi, sh


# ---------------------------------------------------------------------------
# PLAIN fixed-width (the config[0] minimum slice: decode == reinterpret)
#
# Staged as exact-length uint32 words (``device_reader.stage_plan``): the
# host holds PLAIN values as whole 4-byte words, so the device does no
# byte work and touches only the chunk's n values.
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("n", "dtype"))
def bitcast_fixed32(words: jax.Array, n: int, dtype: str) -> jax.Array:
    """uint32 words → {int32,uint32,float32}[n] (PLAIN 4-byte types): a
    same-width bitcast, no byte work."""
    return jax.lax.bitcast_convert_type(words[:n], jnp.dtype(dtype))


@partial(jax.jit, static_argnames=("n",))
def fixed64_pairs(words: jax.Array, n: int) -> jax.Array:
    """uint32 words → uint32[n,2] lo/hi pairs (PLAIN 8-byte types,
    byte-exact), no byte work.  The result is a new buffer (the input is
    neither donated nor returned), so every call runs a
    ``jit_fixed64_pairs`` program.

    A value's lo and hi words sit in neighbouring lanes, and the TPU lays
    ``u32[n,2]`` out as a lo row and a hi row per 128 values: so the words
    go as rows of 256, split by strided lane slices into ``[rows, 2, 128]``
    blocks, which are that layout.  A plain ``reshape(n, 2)`` goes through
    a 64x lane-padded intermediate instead (for a described v5e at 1M
    values: 14x the estimated cycles and 512 MiB of scratch)."""
    m = -(-n // 128) * 128
    rows = jnp.pad(words[: 2 * n], (0, 2 * (m - n))).reshape(-1, 256)
    blocks = jnp.stack([rows[:, 0::2], rows[:, 1::2]], axis=1)
    return blocks.transpose(0, 2, 1).reshape(m, 2)[:n]


@partial(jax.jit, static_argnames=("n",))
def unpack_bools(buf: jax.Array, n: int) -> jax.Array:
    """PLAIN BOOLEAN: LSB-first bit-unpack."""
    nbytes = (n + 7) // 8
    bits = (buf[:nbytes, None] >> jnp.arange(8, dtype=jnp.uint8)) & 1
    return bits.reshape(-1)[:n].astype(jnp.bool_)


# ---------------------------------------------------------------------------
# Generic bit-unpack: the single most load-bearing kernel (SURVEY.md §2.3)
# ---------------------------------------------------------------------------


def unpack_bits_at32(buf: jax.Array, bit_starts: jax.Array, widths) -> jax.Array:
    """One ≤32-bit LSB-first integer per element at absolute bit positions.

    ``widths`` may be scalar or per-element (mixed-width streams: a whole
    chunk of differently-packed pages decodes in ONE call).  uint32 out.
    Covers levels, dictionary indexes, and int32 deltas — the hot 99%.
    Two aligned word gathers per element; int32 index math throughout.
    """
    words = _as_words(buf)
    wi, sh = _word_at(bit_starts)
    w0 = words[wi]
    w1 = words[wi + 1]
    # sh==0 must not shift by 32 (UB): force the hi word's contribution to 0
    hi = jnp.where(sh > 0, w1 << ((_U32(32) - sh) & _U32(31)), _U32(0))
    val = (w0 >> sh) | hi
    w32 = jnp.asarray(widths).astype(_U32)
    mask = jnp.where(w32 >= 32, _U32(0xFFFFFFFF), (_U32(1) << w32) - _U32(1))
    return val & mask


def unpack_bits_at64(buf: jax.Array, bit_starts: jax.Array, widths
                     ) -> Tuple[jax.Array, jax.Array]:
    """Like :func:`unpack_bits_at32` for widths ≤ 64 → (lo, hi) uint32 pair.
    Three aligned word gathers per element."""
    words = _as_words(buf)
    wi, sh = _word_at(bit_starts)
    w0 = words[wi]
    w1 = words[wi + 1]
    w2 = words[wi + 2]
    nz = sh > 0
    inv = (_U32(32) - sh) & _U32(31)
    lo = (w0 >> sh) | jnp.where(nz, w1 << inv, _U32(0))
    hi = jnp.where(nz, (w1 >> sh) | (w2 << inv), w1)
    w32 = jnp.asarray(widths).astype(_U32)
    lo_bits = jnp.minimum(w32, _U32(32))
    hi_bits = jnp.maximum(w32, _U32(32)) - _U32(32)
    lo_mask = jnp.where(lo_bits >= 32, _U32(0xFFFFFFFF), (_U32(1) << lo_bits) - _U32(1))
    hi_mask = jnp.where(hi_bits >= 32, _U32(0xFFFFFFFF), (_U32(1) << hi_bits) - _U32(1))
    return lo & lo_mask, hi & hi_mask


@partial(jax.jit, static_argnames=("n", "width"))
def unpack_bits(buf: jax.Array, n: int, width: int, offset_bits: int = 0) -> jax.Array:
    """Dense LSB-first unpack of ``n`` ``width``-bit integers (≤32 → u32,
    else → (n,2) u32 pairs)."""
    starts = jnp.arange(n, dtype=jnp.int32) * width + offset_bits
    if width <= 32:
        return unpack_bits_at32(buf, starts, width)
    lo, hi = unpack_bits_at64(buf, starts, width)
    return jnp.stack([lo, hi], axis=1)


# ---------------------------------------------------------------------------
# RLE / bit-packed hybrid expansion (device half of the two-pass split)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("n",))
def rle_expand(
    buf: jax.Array,  # uint8 payload (whole chunk, padded +12)
    n: int,  # total output values (static, padded ok)
    run_ends: jax.Array,  # int32/int64[k] cumulative output counts per run
    run_kinds: jax.Array,  # uint8[k] 0=RLE 1=bit-packed
    run_payloads: jax.Array,  # int32[k] repeated value for RLE runs
    run_bit_offsets: jax.Array,  # int32/int64[k] absolute bit offset of packed data
    run_widths: jax.Array,  # int32[k] bit width (per run: pages may differ!)
) -> jax.Array:
    """Expand a pre-scanned hybrid stream (levels / dict indexes, ≤32-bit).
    int32 out; past the last run's end (a padded ``n``) the last run goes on.

    No per-value search and no run-table gather: each run's attributes
    reach its values as deltas scattered at the run's start (k-sized) and
    one blocked prefix sum, so value ``i`` of run ``r`` sees ``r``'s
    attributes (zero-length runs add two deltas at one start and cancel).
    Its bits sit at ``base[r] + i * w[r]`` with ``base[r] = bit_offset[r] -
    start[r] * w[r]`` in wrapping int32, and an RLE run has ``w = 0``, so
    the value is ``payload + unpack(bit_pos, w)``: the two word fetches are
    the only n-wide gathers."""
    ends = run_ends.astype(jnp.int32)
    starts = jnp.pad(ends, (1, 0))[:-1]
    packed = run_kinds != 0
    w = jnp.where(packed, run_widths.astype(jnp.int32), 0)
    base = run_bit_offsets.astype(jnp.int32) - starts * w
    payload = jnp.where(packed, 0, run_payloads.astype(jnp.int32))
    deltas = jnp.diff(jnp.stack([base, w, payload]), axis=1, prepend=0)
    carried = cumsum(jnp.zeros((3, n), jnp.int32).at[:, starts].add(
        deltas, mode="drop", indices_are_sorted=True))
    base_i, w_i, payload_i = carried
    bit_pos = base_i + jnp.arange(n, dtype=jnp.int32) * w_i
    return payload_i + unpack_bits_at32(buf, bit_pos, w_i).astype(jnp.int32)


# ---------------------------------------------------------------------------
# DELTA_BINARY_PACKED (miniblock unpack + cumsum — SURVEY.md §2.2: "excellent fit")
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("n", "vpm"))
def delta_decode32(
    buf: jax.Array, n: int, first_value: jax.Array,
    mb_bit_offsets: jax.Array, mb_widths: jax.Array, mb_min_deltas: jax.Array,
    vpm: int,
) -> jax.Array:
    """INT32 delta decode.  All arithmetic is mod-2^32 (two's complement
    wrap), so 32-bit lanes suffice even though raw deltas span 33 bits."""
    nd = n - 1
    if nd <= 0:
        return jnp.full((max(n, 0),), first_value.astype(jnp.int32))
    i = jnp.arange(nd, dtype=jnp.int32)
    mb = i // vpm
    within = i % vpm
    w = mb_widths[mb]
    bit_pos = mb_bit_offsets[mb].astype(jnp.int32) + within * w
    raw = unpack_bits_at32(buf, bit_pos, w)
    min32 = (mb_min_deltas & jnp.int64(0xFFFFFFFF)).astype(_U32)
    deltas = raw + min32[mb]
    first32 = (first_value.astype(jnp.int64) & jnp.int64(0xFFFFFFFF)).astype(_U32)
    seq = jnp.concatenate([first32.reshape(1), deltas])
    return jax.lax.bitcast_convert_type(cumsum(seq), jnp.int32)


@partial(jax.jit, static_argnames=("n", "vpm"))
def delta_decode64(
    buf: jax.Array, n: int, first_value: jax.Array,
    mb_bit_offsets: jax.Array, mb_widths: jax.Array, mb_min_deltas: jax.Array,
    vpm: int,
) -> jax.Array:
    """INT64 delta decode → (n,2) uint32 pairs.  Unpack is 32-bit lane work;
    only the prefix-sum runs in (emulated) s64 arithmetic."""
    nd = n - 1
    if nd <= 0:
        v = first_value.astype(jnp.int64).reshape(1)
        return _i64_to_pairs(jnp.broadcast_to(v, (max(n, 1),)))[:n]
    i = jnp.arange(nd, dtype=jnp.int32)
    mb = i // vpm
    within = i % vpm
    w = mb_widths[mb]
    bit_pos = mb_bit_offsets[mb].astype(jnp.int32) + within * w
    lo, hi = unpack_bits_at64(buf, bit_pos, w)
    raw = lo.astype(jnp.int64) | (hi.astype(jnp.int64) << 32)
    deltas = raw + mb_min_deltas[mb]
    seq = jnp.concatenate([first_value.astype(jnp.int64).reshape(1), deltas])
    return _i64_to_pairs(cumsum(seq))


def _i64_to_pairs(v: jax.Array) -> jax.Array:
    lo = (v & jnp.int64(0xFFFFFFFF)).astype(_U32)
    hi = ((v >> 32) & jnp.int64(0xFFFFFFFF)).astype(_U32)
    return jnp.stack([lo, hi], axis=1)


def delta_prescan(data: np.ndarray, pos: int = 0):
    """Host pre-scan of a DELTA_BINARY_PACKED stream → device metadata.

    Returns (first_value, total, vpm, mb_bit_offsets, mb_widths,
    mb_min_deltas, end_pos).  O(miniblocks), not O(values).  Routes through
    the C++ shim (one uvarint walk); this Python body is the oracle/fallback
    and the precise-error path for malformed streams."""
    from . import ref
    from .. import native

    nat = native.delta_prescan(data, pos)
    if nat is not None:
        first, total, vpm, offsets, widths, mins, end = nat
        return (first, total, vpm, offsets, widths, mins, end)

    block_size, pos = ref.read_uvarint(data, pos)
    n_miniblocks, pos = ref.read_uvarint(data, pos)
    total, pos = ref.read_uvarint(data, pos)
    first_raw, pos = ref.read_uvarint(data, pos)
    first = ref.unzigzag(first_raw)
    if n_miniblocks == 0 or block_size == 0 or block_size % n_miniblocks:
        raise ValueError(
            f"malformed DELTA_BINARY_PACKED header: block_size={block_size}, "
            f"miniblocks={n_miniblocks}")
    vpm = block_size // n_miniblocks
    offsets, widths, mins = [], [], []
    got = 1
    while got < total:
        md_raw, pos = ref.read_uvarint(data, pos)
        min_delta = ref.unzigzag(md_raw)
        wbytes = data[pos : pos + n_miniblocks]
        pos += n_miniblocks
        for m in range(n_miniblocks):
            if got >= total:
                break
            w = int(wbytes[m])
            offsets.append(pos * 8)
            widths.append(w)
            mins.append(min_delta)
            pos += vpm * w // 8
            got += min(vpm, total - got)
    return (
        first, total, vpm,
        np.asarray(offsets, dtype=np.int64),
        np.asarray(widths, dtype=np.int32),
        np.asarray(mins, dtype=np.int64),
        pos,
    )


# ---------------------------------------------------------------------------
# BYTE_STREAM_SPLIT (plane transpose; 64-bit types → u32 pairs)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("n", "width", "out_dtype"))
def byte_stream_split(buf: jax.Array, n: int, width: int,
                      out_dtype: Optional[str] = None) -> jax.Array:
    planes = buf[: width * n].reshape(width, n)
    interleaved = planes.T  # (n, width) bytes
    if out_dtype is None:
        return interleaved
    if width == 4:
        return jax.lax.bitcast_convert_type(interleaved, jnp.dtype(out_dtype)).reshape(n)
    assert width == 8
    return jax.lax.bitcast_convert_type(
        interleaved.reshape(n, 2, 4), _U32).reshape(n, 2)  # pairs; host views dtype


# ---------------------------------------------------------------------------
# DELTA_BYTE_ARRAY (front coding: host prefix-length prescan, suffix
# gather + prefix resolution by pointer jumping on chip)
# ---------------------------------------------------------------------------


def delta_byte_array_prescan(data: np.ndarray, pos: int = 0):
    """Host pre-scan of one DELTA_BYTE_ARRAY page → device-kernel inputs.

    Returns ``(prefix_lens int64, suffix bytes, suffix_offs int32, end)``.
    O(values) in the length METADATA only — no output byte is expanded on
    host; the suffix stream ships to HBM raw and
    :func:`delta_byte_array_expand` materializes the front-coded output
    there."""
    from . import ref

    return ref.decode_delta_byte_array_parts(data, pos)


def delta_byte_array_iters(prefix_lens: np.ndarray) -> int:
    """Pointer-jumping rounds :func:`delta_byte_array_expand` needs: a
    prefix byte chases parents through at most the longest consecutive
    run of entries with a nonzero prefix (the entry before any run starts
    from scratch, so its bytes all resolve to suffix bytes), and each
    round squares the resolved distance."""
    nz = np.asarray(prefix_lens) > 0
    if not nz.size or not nz.any():
        return 0
    edges = np.flatnonzero(np.diff(
        np.concatenate(([False], nz, [False])).astype(np.int8)))
    depth = int((edges[1::2] - edges[0::2]).max())
    return max(int(np.ceil(np.log2(depth + 1))), 1)


@partial(jax.jit, static_argnames=("total", "iters"))
def delta_byte_array_expand(suffix_buf: jax.Array, prefix_lens: jax.Array,
                            suffix_offs: jax.Array, entry_offs: jax.Array,
                            total: int, iters: int) -> jax.Array:
    """Expand a front-coded byte-array stream on chip.

    Every output byte either lives in the suffix stream (position ≥ the
    entry's prefix length — a direct gather) or repeats the byte at the
    same offset of the PREVIOUS entry's output.  Prefix bytes start as
    pointers into the previous entry and resolve by pointer jumping
    (``ptr = ptr[ptr]``, ``iters`` rounds — log of the deepest prefix
    chain, computed exactly on host); suffix bytes are fixed points.  One
    final gather materializes the output with no sequential dependency —
    the host oracle's entry-by-entry loop does not vectorize."""
    if total == 0:
        return jnp.zeros(0, jnp.uint8)
    pos = jnp.arange(total, dtype=jnp.int32)
    e = jnp.searchsorted(entry_offs, pos, side="right").astype(jnp.int32) - 1
    j = pos - entry_offs[e]
    in_suffix = j >= prefix_lens[e]
    direct = suffix_offs[e] + jnp.where(in_suffix, j - prefix_lens[e], 0)
    prev_start = entry_offs[jnp.maximum(e - 1, 0)]
    ptr = jnp.where(in_suffix, pos, prev_start + j)
    ptr = jax.lax.fori_loop(0, iters, lambda _, p: p[p], ptr)
    return suffix_buf[direct[ptr]]


# ---------------------------------------------------------------------------
# Dictionary gather + level math (trivial but central)
# ---------------------------------------------------------------------------


@jax.jit
def dict_gather(dictionary: jax.Array, indices: jax.Array) -> jax.Array:
    return jnp.take(dictionary, indices, axis=0)


@partial(jax.jit, static_argnames=("max_def",))
def validity_from_def(def_levels: jax.Array, max_def: int) -> jax.Array:
    return def_levels == max_def


@jax.jit
def cumsum_offsets(lengths: jax.Array) -> jax.Array:
    return jnp.concatenate([jnp.zeros(1, jnp.int64),
                            cumsum(lengths.astype(jnp.int64))])


@jax.jit
def scatter_valid(values: jax.Array, validity: jax.Array) -> jax.Array:
    """Dense present values → slot-aligned array (nulls get 0)."""
    slot_of_value = cumsum(validity.astype(jnp.int32)) - 1
    gathered = values[jnp.clip(slot_of_value, 0, values.shape[0] - 1)]
    zero = jnp.zeros((), dtype=values.dtype)
    if values.ndim > 1:
        return jnp.where(validity[:, None], gathered, zero)
    return jnp.where(validity, gathered, zero)


@partial(jax.jit, static_argnames=("is_float", "is_unsigned"))
def pair_range_mask(pairs: jax.Array, lo_pair: jax.Array, hi_pair: jax.Array,
                    has_lo: jax.Array, has_hi: jax.Array,
                    is_float: bool = False,
                    is_unsigned: bool = False) -> jax.Array:
    """lo <= value <= hi over the (n, 2) uint32 pair representation of
    64-bit values, without x64 mode.

    Comparison is lexicographic on (high word as ordering key, low word
    unsigned). For int64 the high word orders as *signed* int32 (unsigned
    logical: plain uint32); for double the IEEE total order needs the
    sign-magnitude flip (negative values order reversed), applied to both
    words of value and bounds. NaN keys are not treated specially (a range
    reaching +inf admits positive NaN bit patterns).
    """
    hw_dt = jnp.uint32 if is_unsigned else jnp.int32
    lo_w = pairs[:, 0]
    hi_w = pairs[:, 1].astype(hw_dt)
    b_lo = lo_pair[0]
    b_hi_lo = hi_pair[0]
    b_lo_hi = lo_pair[1].astype(hw_dt)
    b_hi_hi = hi_pair[1].astype(hw_dt)
    if is_float:
        # IEEE-754 total-order trick: flip all bits of negatives, flip only
        # the sign bit of non-negatives → unsigned lexicographic order
        def flip(h, l):
            neg = h < 0
            h_u = h.astype(jnp.uint32)
            fh = jnp.where(neg, ~h_u, h_u ^ jnp.uint32(0x80000000))
            fl = jnp.where(neg, ~l, l)
            return fh, fl

        hi_w_u, lo_w = flip(hi_w, lo_w)
        b_lo_hi_u, b_lo = flip(b_lo_hi, b_lo)
        b_hi_hi_u, b_hi_lo = flip(b_hi_hi, b_hi_lo)
        ge_lo = (hi_w_u > b_lo_hi_u) | ((hi_w_u == b_lo_hi_u) & (lo_w >= b_lo))
        le_hi = (hi_w_u < b_hi_hi_u) | ((hi_w_u == b_hi_hi_u) & (lo_w <= b_hi_lo))
    else:
        ge_lo = (hi_w > b_lo_hi) | ((hi_w == b_lo_hi) & (lo_w >= b_lo))
        le_hi = (hi_w < b_hi_hi) | ((hi_w == b_hi_hi) & (lo_w <= b_hi_lo))
    return (~has_lo | ge_lo) & (~has_hi | le_hi)


def assemble_single_list(def_levels: jax.Array, rep_levels: jax.Array,
                         dk: int, max_def: int):
    """Device twin of ops/levels.assemble for ONE repeated ancestor
    (SURVEY.md §7 hard part 4: level→(validity, offsets) as vector ops).

    ``dk`` is the repeated ancestor's def level. Returns
    ``(list_offsets, list_validity, leaf_validity)`` as device arrays — the
    same semantics as the host assembler: instances are row starts
    (``rep == 0``), elements are slots with ``def >= dk``, a row's list is
    non-null iff its start slot has ``def >= dk - 1``, and leaf validity
    (over elements) is ``def == max_def``.

    Shapes are data-dependent (rows, elements), so two scalar D2H syncs fix
    the sizes; all heavy math stays on device.
    """
    counts = _asl_cums(def_levels, rep_levels, dk)
    n_rows, n_elem = (int(x) for x in counts)
    return _asl_finish(def_levels, rep_levels, n_rows, n_elem, dk, max_def)


@partial(jax.jit, static_argnames=("dk",))
def _asl_cums(d: jax.Array, r: jax.Array, dk: int):
    """One dispatch for the two data-dependent sizes (rows, elements)."""
    n_elem = jnp.sum((d >= dk).astype(jnp.int32)) if d.shape[0] else jnp.int32(0)
    return jnp.stack([jnp.sum((r == 0).astype(jnp.int32)), n_elem])


@partial(jax.jit, static_argnames=("n_rows", "n_elem", "dk", "max_def"))
def _asl_finish(d, r, n_rows: int, n_elem: int, dk: int, max_def: int):
    inst_mask = r == 0
    elem = d >= dk
    cum = cumsum(elem.astype(jnp.int32))
    inst_idx = jnp.nonzero(inst_mask, size=n_rows, fill_value=0)[0].astype(jnp.int32)
    starts = cum[inst_idx] - elem[inst_idx].astype(jnp.int32)
    offsets = jnp.concatenate(
        [starts, cum[-1:] if d.shape[0] else jnp.zeros(1, jnp.int32)])
    list_validity = d[inst_idx] >= (dk - 1)
    elem_idx = jnp.nonzero(elem, size=n_elem, fill_value=0)[0].astype(jnp.int32)
    leaf_validity = (d == max_def)[elem_idx]
    return offsets, list_validity, leaf_validity


def assemble_nested(def_levels: jax.Array, rep_levels: jax.Array,
                    infos, max_def: int):
    """Device twin of ``ops/levels.assemble`` for ANY repetition depth
    (SURVEY.md §7 hard part 4, beyond the single-list case): per repeated
    level k — instances, element counts, offsets, list validity — all as
    whole-column vector ops over the expanded level streams, mirroring the
    host assembler's exact semantics (instances of level k: ``rep < k`` and
    ``def >= d_{k-1}``; elements: ``rep < k_next`` and ``def >= d_k``; a
    list is non-null iff its start slot has ``def >= d_k - 1``).

    ``infos`` is ``levels_ops.repeated_ancestors(leaf)``.  Returns
    ``(list_offsets, list_validity, leaf_validity)`` where the first two are
    LISTS with one device array per repeated level (outermost first) — the
    multi-level Column layout.  Shapes are data-dependent, so ONE count
    dispatch + D2H sync fixes every level's size; the finish pass is a
    single fused dispatch."""
    reps = tuple(int(i.rep_level) for i in infos)
    defs = tuple(int(i.def_level) for i in infos)
    counts = _an_counts(def_levels, rep_levels, reps, defs)
    sizes = tuple(int(x) for x in np.asarray(counts))
    return _an_finish(def_levels, rep_levels, sizes, reps, defs, max_def)


@partial(jax.jit, static_argnames=("reps", "defs"))
def _an_counts(d: jax.Array, r: jax.Array, reps, defs):
    outs = []
    if not d.shape[0]:
        return jnp.zeros(len(reps) + 1, jnp.int32)
    for i, k in enumerate(reps):
        inst = (r < k) if i == 0 else ((r < k) & (d >= defs[i - 1]))
        outs.append(jnp.sum(inst.astype(jnp.int32)))
    outs.append(jnp.sum((d >= defs[-1]).astype(jnp.int32)))
    return jnp.stack(outs)


@partial(jax.jit, static_argnames=("sizes", "reps", "defs", "max_def"))
def _an_finish(d, r, sizes, reps, defs, max_def: int):
    offsets = []
    validities = []
    nlev = len(reps)
    empty = not d.shape[0]
    for i, (k, dk) in enumerate(zip(reps, defs)):
        inst = (r < k) if i == 0 else ((r < k) & (d >= defs[i - 1]))
        inst_idx = jnp.nonzero(inst, size=sizes[i],
                               fill_value=0)[0].astype(jnp.int32)
        if i + 1 < nlev:
            elem = (r < reps[i + 1]) & (d >= dk)
        else:
            elem = d >= dk
        cum = cumsum(elem.astype(jnp.int32))
        starts = (jnp.where(inst_idx > 0, cum[jnp.maximum(inst_idx - 1, 0)], 0)
                  if not empty else jnp.zeros(0, jnp.int32))
        total = cum[-1:] if not empty else jnp.zeros(1, jnp.int32)
        offsets.append(jnp.concatenate([starts, total]))
        validities.append(d[inst_idx] >= (dk - 1) if not empty
                          else jnp.zeros(0, bool))
    elem_idx = jnp.nonzero(d >= defs[-1], size=sizes[-1],
                           fill_value=0)[0].astype(jnp.int32)
    leaf_validity = ((d == max_def)[elem_idx] if not empty
                     else jnp.zeros(0, bool))
    return offsets, validities, leaf_validity


def pad_to_bucket(arr: np.ndarray, extra: int = 12) -> np.ndarray:
    """Pad a host buffer to a power-of-two bucket (+slack for 12-byte gathers)
    so jit specializations are reused across similarly-sized pages."""
    n = len(arr) + extra
    bucket = 1 << max(int(n - 1).bit_length(), 6)
    if bucket == len(arr):
        return arr
    out = np.zeros(bucket, dtype=arr.dtype)
    out[: len(arr)] = arr
    return out


def pairs_to_host(pairs, dtype) -> np.ndarray:
    """(n,2) u32 device pairs → host int64/float64 array (zero-copy view)."""
    return np.ascontiguousarray(np.asarray(pairs)).view(dtype).reshape(-1)
