"""Device decode pipeline: chunk bytes → HBM → decoded jax.Arrays.

Reference parity: this is the ``PARQUET_GO_DEVICE=tpu`` path of the north star
(BASELINE.json): the per-page decode loop of ``filePages.ReadPage`` rerouted so
that raw page payloads are staged to the device in batched transfers per chunk
and decoded by the kernels in ``ops/device.py``.  Host does only
metadata-scale work (page headers, LZ decompression, run/miniblock pre-scans);
the device does all data-scale work (bit-unpack, RLE expansion, delta cumsum,
gathers) — SURVEY.md §7 steps 4-6.

Whole-chunk single-kernel decode: every encoding family merges ALL of a
chunk's pages into ONE device call —
- PLAIN fixed-width pages are contiguous in the value stage → one bitcast;
- dictionary/bool pages become one run table (per-run widths handle per-page
  bit widths) → one :func:`rle_expand`;
- DELTA pages merge miniblock tables and use a segmented cumsum (global
  cumsum minus per-page base) → one call;
- BYTE_STREAM_SPLIT pages use a page-aware gather → one call.

Column representation stays TPU-friendly: 32-bit types native, 64-bit types as
(n,2) uint32 pairs, BYTE_ARRAY dictionary chunks stay *encoded* (device
dictionary + int32 indexes — the Arrow DictionaryArray analog).

Anything exotic (mixed dict/plain fallback chunks, byte-array deltas) falls
back to the host oracle for the whole chunk — correctness first, the hot
paths stay on device.
"""

from __future__ import annotations

import struct as _struct
from dataclasses import dataclass, field
from functools import partial
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..format.enums import Encoding, FieldRepetitionType as Rep, PageType, Type
from ..io.column import Column
from ..io.reader import ColumnChunkReader, CorruptedError, decode_chunk_host, _bit_width
from ..obs import trace as _otrace
from ..ops import device as dev, levels as levels_ops, ref
from ..schema.types import LogicalKind
from ..utils.debug import counters
from .. import native

_FIXED_WIDTH = {Type.INT32: 4, Type.INT64: 8, Type.FLOAT: 4, Type.DOUBLE: 8,
                Type.INT96: 12}
_IS_PAIR = {Type.INT64, Type.DOUBLE}


class _Unsupported(Exception):
    """Internal: chunk shape the device path doesn't cover → host fallback."""


class _LazyLevels:
    """Per-slot level stream, materialized on first array access.

    The fused list assembler (pq_assemble_list_runs) derives offsets/validity
    straight from the run tables, so most reads never touch per-slot levels;
    consumers that do (row-range trims, struct zips, batch streaming) get
    them transparently via the numpy array protocol."""

    __slots__ = ("_runs", "_buf", "_arr")

    def __init__(self, runs: _RunTable, buf: np.ndarray):
        self._runs, self._buf, self._arr = runs, buf, None

    def _materialize(self) -> np.ndarray:
        if self._arr is None:
            self._arr = self._runs.expand_host(self._buf)
        return self._arr

    def __array__(self, dtype=None, copy=None):
        a = self._materialize()
        return np.asarray(a, dtype=dtype)

    # comparisons/arithmetic materialize and delegate, so a consumer writing
    # `col.def_levels == x` gets elementwise semantics instead of a silent
    # Python identity bool (advisor r2)
    def __eq__(self, other):
        return self._materialize() == np.asarray(other)

    def __ne__(self, other):
        return self._materialize() != np.asarray(other)

    __hash__ = None  # elementwise __eq__: not hashable, like ndarray

    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        inputs = tuple(np.asarray(x) if isinstance(x, _LazyLevels) else x
                       for x in inputs)
        return getattr(ufunc, method)(*inputs, **kwargs)

    def __len__(self):
        return self._runs.total

    def __getitem__(self, i):
        return self._materialize()[i]


@dataclass
class _RunTable:
    """Chunk-level merged RLE/bit-packed run table (host-scanned)."""

    ends: List[np.ndarray] = field(default_factory=list)
    kinds: List[np.ndarray] = field(default_factory=list)
    payloads: List[np.ndarray] = field(default_factory=list)
    bit_offsets: List[np.ndarray] = field(default_factory=list)
    widths: List[np.ndarray] = field(default_factory=list)
    total: int = 0
    # encoded bytes of the hybrid streams the runs were scanned from: with
    # ``total`` the logical work of one expand, fixed by the data
    nbytes: int = 0

    def add_scanned(self, kinds, cnts, payloads, offs, width, base_byte, n,
                    nbytes: int):
        self.nbytes += nbytes
        self.kinds.append(kinds)
        self.payloads.append(payloads)
        self.bit_offsets.append((offs + base_byte) * 8)
        self.widths.append(np.full(len(kinds), width, dtype=np.int32))
        self.ends.append(self.total + np.cumsum(cnts))
        self.total += n

    def add(self, data: np.ndarray, n: int, width: int, base_byte: int) -> tuple:
        single = _single_rle_run(data, n, width)
        if single is not None:
            # the common all-present/all-null stream is ONE RLE run: decode
            # inline and skip the native scan round-trip (~35us/page of
            # dispatch overhead, at every level-stream call site)
            kinds = np.zeros(1, np.uint8)
            cnts = np.array([n])
            payloads = np.array([single[0]], np.int64)
            offs = np.array([single[1]], np.int64)
        else:
            kinds, cnts, payloads, offs, _end = ref.scan_rle_runs(
                data, n, width, 0)
        self.add_scanned(kinds, cnts, payloads, offs, width, base_byte, n,
                         len(data))
        return kinds, cnts, payloads, offs

    def add_bitpacked_span(self, n: int, width: int, base_byte: int):
        """A raw bit-packed span (e.g. PLAIN BOOLEAN page) as a single run."""
        self.nbytes += (n * width + 7) // 8
        self.kinds.append(np.ones(1, np.uint8))
        self.payloads.append(np.zeros(1, np.int64))
        self.bit_offsets.append(np.array([base_byte * 8], np.int64))
        self.widths.append(np.full(1, width, np.int32))
        self.ends.append(np.array([self.total + n], np.int64))
        self.total += n

    def tables_host(self) -> tuple:
        """(ends, kinds, payloads, bit_offsets, widths) as int64-domain host
        arrays — operands of the fused C++ run-table consumers."""
        return (np.concatenate(self.ends).astype(np.int64),
                np.concatenate(self.kinds),
                np.concatenate(self.payloads).astype(np.int64),
                np.concatenate(self.bit_offsets).astype(np.int64),
                np.concatenate(self.widths).astype(np.int32))

    def run_arrays(self) -> tuple:
        """(ends, kinds, payloads, bit_offsets, widths) as flat host arrays —
        the rle_expand kernel operands, stageable to HBM ahead of decode.
        int32 throughout: staged buffers are < 2^27 bytes (bit offsets fit)
        and chunks hold < 2^31 values, keeping device index math in 32-bit
        lanes."""
        return (np.concatenate(self.ends).astype(np.int32),
                np.concatenate(self.kinds),
                np.concatenate(self.payloads).astype(np.int32),
                np.concatenate(self.bit_offsets).astype(np.int32),
                np.concatenate(self.widths))

    def expand(self, dbuf: jax.Array, n: Optional[int] = None,
               tables: Optional[tuple] = None) -> jax.Array:
        n = n or self.total
        tables = tables if tables is not None else self.run_arrays()
        counters.inc("kernel_bytes.rle_expand", self.nbytes + 4 * n)
        counters.inc("kernel_runs.rle_expand", int(tables[0].shape[0]))
        return dev.rle_expand(dbuf, n, *tables)

    def expand_host(self, buf: np.ndarray, n: Optional[int] = None) -> np.ndarray:
        """Numpy twin of :meth:`expand` over the host copy of the byte stream.

        Used for nested columns, whose level streams are consumed by the host
        record assembler — expanding there avoids a D2H sync of data that is
        metadata-sized to begin with."""
        n = n or self.total
        ends, kinds, payloads, offs, widths32 = self.tables_host()
        widths = widths32.astype(np.int64)
        out = native.expand_runs(buf, ends, kinds, payloads, offs, widths32, n)
        if out is not None:
            return out
        if len(widths) and widths.max() > 24:
            # rare wide levels: per-run loop (a 4-byte gather window below
            # only covers widths <= 25 at arbitrary bit phase)
            out = np.empty(n, np.int32)
            pos = 0
            for i in range(len(kinds)):
                cnt = min(int(ends[i]) - pos, n - pos)
                if cnt <= 0:
                    continue
                if kinds[i] == 0:
                    out[pos : pos + cnt] = payloads[i]
                else:
                    bit0 = int(offs[i])
                    out[pos : pos + cnt] = ref.unpack_bits(
                        buf[bit0 // 8 :], cnt, int(widths[i]), bit0 % 8)
                pos += cnt
            return out[:pos]
        starts = np.concatenate([np.zeros(1, np.int64), ends[:-1]])
        counts = np.maximum(np.minimum(ends, n) - starts, 0)
        rid = np.repeat(np.arange(len(kinds)), counts)
        pos = np.arange(int(counts.sum()), dtype=np.int64)
        within = pos - np.repeat(starts, counts)
        packed = kinds[rid] != 0
        # RLE runs take their payload directly; gather position only matters
        # for bit-packed runs (and would otherwise index past the stream)
        bitpos = np.where(packed, offs[rid] + within * widths[rid], 0)
        vals = _gather_bits(buf, bitpos, widths[rid])
        return np.where(packed, vals, payloads[rid]).astype(np.int32)


def _gather_bits(body: np.ndarray, bitpos: np.ndarray, widths) -> np.ndarray:
    """Unpack one value per entry of ``bitpos`` (bit offsets into ``body``)
    via a 4-byte little-endian gather window.  Valid for widths <= 24."""
    pbuf = np.concatenate([np.asarray(body, np.uint8), np.zeros(8, np.uint8)])
    b0 = bitpos >> 3
    w32 = (pbuf[b0].astype(np.uint32)
           | (pbuf[b0 + 1].astype(np.uint32) << 8)
           | (pbuf[b0 + 2].astype(np.uint32) << 16)
           | (pbuf[b0 + 3].astype(np.uint32) << 24))
    mask = (np.uint32(1) << np.asarray(widths).astype(np.uint32)) - np.uint32(1)
    return (w32 >> (bitpos & 7).astype(np.uint32)) & mask


def _count_target_in_runs(kinds, cnts, payloads, offs, body, width, target) -> int:
    """How many level values equal ``target`` (native pass, else vectorized
    numpy — the per-page present count was half of config-4's host phase)."""
    if len(kinds) == 1 and kinds[0] == 0:
        # one RLE run (the dominant all-present / all-null page): direct —
        # the native round-trip costs ~30us/page x 400 pages per 64 MB chunk
        return int(cnts[0]) if int(payloads[0]) == target else 0
    kinds = np.asarray(kinds)
    cnts = np.asarray(cnts, np.int64)
    payloads = np.asarray(payloads, np.int64)
    offs = np.asarray(offs, np.int64)
    fast = native.count_target_in_runs(
        body if isinstance(body, np.ndarray) else np.frombuffer(body, np.uint8),
        kinds, cnts, payloads, offs, width, target)
    if fast is not None:
        return fast
    total = int(cnts[(kinds == 0) & (payloads == target)].sum())
    packed = np.flatnonzero(kinds != 0)
    if not len(packed):
        return total
    if width > 24:
        for k in packed:
            vals = ref.unpack_bits(body[offs[k]:], int(cnts[k]), width)
            total += int(np.count_nonzero(vals == target))
        return total
    pcnts = cnts[packed]
    rid = np.repeat(packed, pcnts)
    starts = np.zeros(len(packed), np.int64)
    np.cumsum(pcnts[:-1], out=starts[1:])
    within = np.arange(int(pcnts.sum()), dtype=np.int64) - np.repeat(starts, pcnts)
    vals = _gather_bits(body, offs[rid] * 8 + within * width, width)
    return total + int(np.count_nonzero(vals == target))


class _ByteAccum:
    """Byte-stream accumulator holding zero-copy views, concatenated ONCE at
    staging time (bytearray.extend copies every page body twice; this class
    keeps the extend()/len() surface build_plan already uses but defers the
    copy to :meth:`padded_array`, which writes straight into the final
    bucket-padded staging buffer — one copy total per byte)."""

    __slots__ = ("_parts", "_n")

    def __init__(self):
        self._parts = []
        self._n = 0

    def __len__(self) -> int:
        return self._n

    def extend(self, b) -> None:
        if not isinstance(b, np.ndarray):
            b = np.frombuffer(b, np.uint8)
        if len(b):
            self._parts.append(b)
            self._n += len(b)

    def array(self) -> np.ndarray:
        """Concatenated uint8 array (one copy; zero-copy for a single part)."""
        if not self._parts:
            return np.empty(0, np.uint8)
        if len(self._parts) == 1:
            return self._parts[0]
        return np.concatenate(self._parts)

    def padded_array(self, extra: int = 12) -> np.ndarray:
        """Like ``dev.pad_to_bucket(self.array(), extra)`` without the
        intermediate concatenation: parts copy directly into the padded
        staging buffer."""
        n = self._n + extra
        bucket = 1 << max(int(n - 1).bit_length(), 6)
        if len(self._parts) == 1 and bucket == self._n:
            return self._parts[0]
        out = np.zeros(bucket, dtype=np.uint8)
        pos = 0
        for p in self._parts:
            out[pos : pos + len(p)] = p
            pos += len(p)
        return out

    def words(self) -> np.ndarray:
        """The stream as exactly ``len(self) // 4`` little-endian uint32
        words, no padding: a zero-copy view of one word-aligned part, else
        one copy into an exact-size buffer.  For PLAIN fixed-width values,
        whose byte count is a whole number of words."""
        a = self.array()
        if a.ctypes.data % 4:
            a = a.copy()
        return a.view(np.uint32)

    def tobytes(self) -> bytes:
        return self.array().tobytes()


@dataclass
class _Plan:
    """Host-built staging plan for one chunk."""

    levels: _ByteAccum = field(default_factory=_ByteAccum)
    values: _ByteAccum = field(default_factory=_ByteAccum)
    def_runs: _RunTable = field(default_factory=_RunTable)
    rep_runs: _RunTable = field(default_factory=_RunTable)
    host_def: List[np.ndarray] = field(default_factory=list)
    value_kind: Optional[str] = None  # 'plain_fixed'|'plain_flba'|'bool'|'dict'|'delta'|'bss'|'dba'|'host_ba'
    # plain
    plain_total: int = 0
    # dict / bool runs
    vruns: _RunTable = field(default_factory=_RunTable)
    # dense single-width dict-index stream (Pallas/jnp gather-free route):
    # bit-packed run payloads compacted into one LSB-first w-bit stream,
    # page-aligned to 32-value groups; (start_value, n_values) per page
    dense: _ByteAccum = field(default_factory=_ByteAccum)
    dense_w: Optional[int] = None
    dense_pages: List[Tuple[int, int]] = field(default_factory=list)
    dense_ok: bool = True
    # delta
    d_firsts: List[int] = field(default_factory=list)
    d_counts: List[int] = field(default_factory=list)
    d_vpms: List[int] = field(default_factory=list)
    # static shape info for the dense (gather-free) delta kernel, set by
    # stage_plan when the chunk is dense-eligible
    d_dense_static: Optional[tuple] = None
    d_mb_offs: List[np.ndarray] = field(default_factory=list)
    d_mb_widths: List[np.ndarray] = field(default_factory=list)
    d_mb_mins: List[np.ndarray] = field(default_factory=list)
    d_vpm: int = 32
    # bss
    bss_pages: List[Tuple[int, int]] = field(default_factory=list)  # (base, n)
    # dba (front-coded byte arrays; suffix bytes live in `values`, the
    # per-page length tables stay host-side until stage time)
    dba_plens: List[np.ndarray] = field(default_factory=list)
    dba_soffs: List[np.ndarray] = field(default_factory=list)
    dba_pages: List[Tuple[int, int]] = field(default_factory=list)  # (base, n)
    # host byte arrays
    host_parts: List = field(default_factory=list)
    total_slots: int = 0
    total_values: int = 0
    dictionary_host = None
    # leaf/physical recorded so stage_plan can stage the dictionary with the
    # chunk instead of inside the decode phase
    leaf = None
    physical: Optional[Type] = None

    def set_kind(self, kind: str):
        if self.value_kind is None:
            self.value_kind = kind
        elif self.value_kind != kind:
            raise _Unsupported(f"mixed page encodings {self.value_kind}/{kind}")


def _single_rle_run(body, n: int, w: int):
    """Parse a level stream that is exactly ONE RLE run covering >= n values
    (the all-present / all-null page shape).  Returns (value, payload_offset)
    or None when the stream is anything else — callers fall back to the full
    run scan.  Mirrors pq_scan_rle_runs's header semantics exactly."""
    m = len(body)
    if not m:
        return None
    header = 0
    shift = 0
    i = 0
    while True:
        if i >= m or shift > 63:
            return None
        b = int(body[i])
        i += 1
        header |= (b & 0x7F) << shift
        if not b & 0x80:
            break
        shift += 7
    if header & 1:
        return None  # bit-packed run
    count = header >> 1
    vbytes = (w + 7) // 8
    if count < n or i + vbytes > m:
        return None
    value = int.from_bytes(bytes(body[i : i + vbytes]), "little")
    if w < 64:
        value &= (1 << w) - 1
    # offset convention matches pq_scan_rle_runs: byte position AFTER the
    # run's value payload
    return value, i + vbytes


def _decompress(codec, payload, size: int):
    """One page's ``codec.decode`` inside a ``decompress`` span: the host
    codec time of the staging phase."""
    with _otrace.span("decompress"):
        return codec.decode(payload, size)


def build_plan(reader: ColumnChunkReader, pages=None) -> _Plan:
    """Host prescan of a chunk's pages into a staging plan.

    ``pages`` (an iterator of PageInfo, e.g. from io/search.seek_pages)
    restricts the plan to a page subset — the pushdown scan path; the
    dictionary page must be included when the chunk is dict-encoded."""
    leaf = reader.leaf
    codec = reader.codec
    physical = Type(reader.meta.type)
    max_def = leaf.max_definition_level
    max_rep = leaf.max_repetition_level
    plan = _Plan()
    plan.leaf = leaf
    plan.physical = physical

    for page in (reader.pages() if pages is None else pages):
        h = page.header
        pt = page.page_type
        if pt == PageType.DICTIONARY_PAGE:
            raw = _decompress(codec, page.payload, h.uncompressed_page_size)
            plan.dictionary_host = ref.decode_plain(
                np.frombuffer(raw, np.uint8), h.dictionary_page_header.num_values,
                physical, leaf.type_length)
            continue
        if pt == PageType.DATA_PAGE:
            dph = h.data_page_header
            n = dph.num_values
            raw = np.frombuffer(_decompress(codec, page.payload,
                                            h.uncompressed_page_size), np.uint8)
            pos = 0
            n_present = n
            if max_rep > 0:
                (length,) = _struct.unpack_from("<I", raw, pos)
                body = raw[pos + 4 : pos + 4 + length]
                plan.rep_runs.add(body, n, _bit_width(max_rep), len(plan.levels))
                plan.levels.extend(body)
                pos += 4 + length
            if max_def > 0:
                enc = Encoding(dph.definition_level_encoding)
                w = _bit_width(max_def)
                if enc == Encoding.RLE:
                    (length,) = _struct.unpack_from("<I", raw, pos)
                    body = raw[pos + 4 : pos + 4 + length]
                    scanned = plan.def_runs.add(body, n, w, len(plan.levels))
                    plan.levels.extend(body)
                    pos += 4 + length
                    n_present = _count_target_in_runs(*scanned, body, w,
                                                      max_def)
                else:  # legacy BIT_PACKED levels: host decode
                    nbytes = (n * w + 7) // 8
                    lv = ref.decode_bit_packed_levels(raw[pos:], n, w)
                    plan.host_def.append(lv)
                    pos += nbytes
                    n_present = int(np.count_nonzero(lv == max_def))
            _stage_values(plan, raw, pos, n_present, Encoding(dph.encoding),
                          physical, leaf)
            plan.total_slots += n
            plan.total_values += n_present
        elif pt == PageType.DATA_PAGE_V2:
            dph2 = h.data_page_header_v2
            n = dph2.num_values
            rl = dph2.repetition_levels_byte_length or 0
            dl = dph2.definition_levels_byte_length or 0
            if max_rep > 0:
                body = np.frombuffer(page.payload[:rl], np.uint8)
                plan.rep_runs.add(body, n, _bit_width(max_rep), len(plan.levels))
                plan.levels.extend(page.payload[:rl])
            if max_def > 0:
                body = np.frombuffer(page.payload[rl : rl + dl], np.uint8)
                plan.def_runs.add(body, n, _bit_width(max_def), len(plan.levels))
                plan.levels.extend(page.payload[rl : rl + dl])
            raw_body = page.payload[rl + dl :]
            if dph2.is_compressed is not False:
                raw_body = _decompress(codec, raw_body,
                                       h.uncompressed_page_size - rl - dl)
            raw = np.frombuffer(raw_body, np.uint8)
            n_present = n - (dph2.num_nulls or 0)
            _stage_values(plan, raw, 0, n_present, Encoding(dph2.encoding),
                          physical, leaf)
            plan.total_slots += n
            plan.total_values += n_present
    return plan


def _dense_mode() -> str:
    """Routing for single-width dense streams: 'auto' (default — the Pallas
    VMEM-tiled kernel on TPU at every width, the jnp twin elsewhere),
    'pallas'/'jnp' to force a path, 'off' (round-1 per-value gather path).
    PARQUET_TPU_PALLAS=1 → pallas, =0 → jnp, =off → off."""
    from ..utils.env import env_str

    v = env_str("PARQUET_TPU_PALLAS")
    if v == "1":
        return "pallas"
    if v == "0":
        return "jnp"
    if v.lower() == "off":
        return "off"
    if v.lower() in ("jnp", "pallas", "auto"):
        return v.lower()
    return "auto"


def _use_pallas(w: int) -> bool:
    """Whether the dense unpack of a ``w``-bit stream runs the Pallas kernel:
    on a TPU backend by default ('auto'), anywhere when forced ('pallas' —
    interpret mode off the TPU).  A kernel that fails to compile or run
    raises; nothing degrades to the jnp twin behind the caller's back.
    Widths >= 17 use the multiply-straddle formulation
    (``pallas_kernels`` module docstring)."""
    mode = _dense_mode()
    if mode == "pallas":
        return True
    return mode == "auto" and jax.default_backend() == "tpu"


def _add_dense_page(plan: _Plan, body: np.ndarray, kinds, cnts, offs,
                    width: int, nvals: int) -> None:
    """Compact one dict page's index stream into the chunk's dense w-bit
    stream when every run is bit-packed (high-cardinality data — the hot
    case). Bit-packed runs encode whole 8-value groups (8·w bits, byte
    aligned), so stripping the varint headers and concatenating payloads
    yields a contiguous LSB-first stream; pages pad to 32-value boundaries
    (4·w bytes) so unpack groups never straddle pages."""
    if not plan.dense_ok or not len(kinds) or not np.all(np.asarray(kinds) == 1):
        plan.dense_ok = False
        return
    if plan.dense_w is None:
        plan.dense_w = width
    elif plan.dense_w != width:
        plan.dense_ok = False
        return
    group_bytes = 4 * width  # 32 values
    pad = -len(plan.dense) % group_bytes
    plan.dense.extend(b"\0" * pad)
    start_val = len(plan.dense) * 8 // width
    bview = np.asarray(body)
    for cnt, off in zip(np.asarray(cnts, np.int64), np.asarray(offs, np.int64)):
        ngroups = (int(cnt) + 7) // 8
        plan.dense.extend(bview[int(off): int(off) + ngroups * width])
    plan.dense_pages.append((start_val, nvals))


def _stage_values(plan: _Plan, raw: np.ndarray, pos: int, nvals: int,
                  encoding: Encoding, physical: Type, leaf) -> None:
    from ..ops.encodings import is_builtin_decode

    if not is_builtin_decode(encoding):
        # a third-party decode shadows this id (ops/encodings.py registry):
        # the accelerated planner only understands the spec encodings, so the
        # chunk must decode on host, where dispatch honors the registry
        raise _Unsupported(
            f"encoding {encoding!r} is overridden by a registered decoder")
    if encoding in (Encoding.PLAIN_DICTIONARY, Encoding.RLE_DICTIONARY):
        plan.set_kind("dict")
        width = int(raw[pos]) if pos < len(raw) else 0
        body = raw[pos + 1 :]
        base = len(plan.values)
        plan.values.extend(body)
        if width == 0:  # single-entry dictionary
            plan.vruns.add_scanned(np.zeros(1, np.uint8), np.array([nvals]),
                                   np.zeros(1, np.int64), np.zeros(1, np.int64),
                                   1, base, nvals, len(body))
            plan.dense_ok = False
        else:
            kinds, cnts, _, offs = plan.vruns.add(body, nvals, width, base)
            _add_dense_page(plan, body, kinds, cnts, offs, width, nvals)
        return
    if encoding == Encoding.PLAIN:
        if physical == Type.BOOLEAN:
            plan.set_kind("bool")
            base = len(plan.values)
            plan.values.extend(raw[pos:])
            plan.vruns.add_bitpacked_span(nvals, 1, base)
            return
        if physical in _FIXED_WIDTH:
            plan.set_kind("plain_fixed")
            w = _FIXED_WIDTH[physical]
            if len(raw) - pos < nvals * w:
                # staged as exact words: a short page has no device form
                # (the host decode reports the corruption)
                raise _Unsupported("PLAIN page shorter than its values")
            plan.values.extend(raw[pos : pos + nvals * w])
            plan.plain_total += nvals
            return
        if physical == Type.FIXED_LEN_BYTE_ARRAY:
            plan.set_kind("plain_flba")
            w = leaf.type_length
            plan.values.extend(raw[pos : pos + nvals * w])
            plan.plain_total += nvals
            return
        plan.set_kind("host_ba")  # PLAIN BYTE_ARRAY: host offsets scan
        plan.host_parts.append(ref.decode_plain(raw[pos:], nvals, physical,
                                                leaf.type_length))
        return
    if encoding == Encoding.DELTA_BINARY_PACKED:
        plan.set_kind("delta")
        base = len(plan.values)
        plan.values.extend(raw[pos:])
        first, total, vpm, offs, widths, mins, _ = dev.delta_prescan(raw, pos)
        plan.d_firsts.append(first)
        plan.d_counts.append(total)
        plan.d_mb_offs.append(offs + (base - pos) * 8)
        plan.d_mb_widths.append(widths)
        plan.d_mb_mins.append(mins)
        plan.d_vpm = vpm
        plan.d_vpms.append(vpm)
        return
    if encoding == Encoding.BYTE_STREAM_SPLIT:
        plan.set_kind("bss")
        w = _FIXED_WIDTH.get(physical, leaf.type_length)
        if not w:  # e.g. BYTE_ARRAY: no fixed width, no BSS plane layout
            raise _Unsupported("byte-stream-split without a fixed width")
        base = len(plan.values)
        plan.values.extend(raw[pos : pos + nvals * w])
        plan.bss_pages.append((base, nvals))
        return
    if encoding == Encoding.RLE and physical == Type.BOOLEAN:
        plan.set_kind("bool")
        (length,) = _struct.unpack_from("<I", raw, pos)
        body = raw[pos + 4 : pos + 4 + length]
        base = len(plan.values)
        plan.values.extend(body)
        plan.vruns.add(body, nvals, 1, base)
        return
    if encoding == Encoding.DELTA_LENGTH_BYTE_ARRAY:
        plan.set_kind("host_ba")
        v, o, _ = ref.decode_delta_length_byte_array(raw, pos)
        plan.host_parts.append((v, o))
        return
    if encoding == Encoding.DELTA_BYTE_ARRAY:
        plan.set_kind("dba")
        plens, suffixes, soffs, _ = dev.delta_byte_array_prescan(raw, pos)
        if len(plens) and int(plens[0]) != 0:
            # front coding is per-page (first entry stores its full
            # value); a nonzero leading prefix would chase a parent
            # in another page — malformed, let the host path raise
            # its precise error
            raise _Unsupported(
                "delta byte array page with nonzero leading prefix")
        base = len(plan.values)
        plan.values.extend(suffixes)
        plan.dba_plens.append(plens)
        plan.dba_soffs.append(soffs.astype(np.int64))
        plan.dba_pages.append((base, len(plens)))
        return
    raise _Unsupported(f"encoding {encoding!r}")


# ---------------------------------------------------------------------------
# Merged multi-page delta decode (segmented cumsum)
# ---------------------------------------------------------------------------


def _nonempty(parts, dtype, fill=0):
    """Concatenate per-page metadata arrays; a zero-miniblock chunk (all
    single-value pages) still needs 1-element tables so device gathers have a
    non-empty operand."""
    out = (np.concatenate(parts).astype(dtype) if parts
           else np.empty(0, dtype))
    return out if out.size else np.full(1, fill, dtype)


def _delta_gather_tables(plan: _Plan) -> tuple:
    """Gather-kernel operands (page_ends, firsts, mb_base, mb_offs, mb_widths,
    mb_mins) as int32 index tables (+ int64 value-domain tables), shared by
    stage_plan and the unstaged decode fallback so the jit traces once."""
    page_ends = np.cumsum(plan.d_counts).astype(np.int32)
    mb_base = np.zeros(len(plan.d_counts), np.int32)
    np.cumsum([len(w) for w in plan.d_mb_widths[:-1]], out=mb_base[1:])
    mb_offs = _nonempty(plan.d_mb_offs, np.int64).astype(np.int32)
    mb_widths = _nonempty(plan.d_mb_widths, np.int32, fill=1)
    mb_mins = _nonempty(plan.d_mb_mins, np.int64)
    firsts = np.asarray(plan.d_firsts, np.int64)
    return page_ends, firsts, mb_base, mb_offs, mb_widths, mb_mins


def _stage_delta_dense(plan: _Plan, meta: dict, put=None) -> bool:
    """Host half of the gather-free delta decode (the TPU-first path).

    Compacts all miniblock payloads into per-width contiguous streams with
    numpy fancy indexing (metadata-scale cost: the compacted bytes ARE the
    compressed data), so the device kernel unpacks with static reshapes and
    never gathers.  Returns False for shapes the dense kernel doesn't cover
    (mixed vpm, >32-bit delta widths, >8 distinct widths) — those use the
    gather kernel.
    """
    if put is None:
        put = jax.device_put
    if not plan.d_counts:
        return False
    vpm = plan.d_vpm
    if len(set(plan.d_vpms)) != 1 or vpm % 32:
        return False
    if len(plan.d_counts) > 512:
        # static per-page slicing unrolls O(pages) into the graph; huge page
        # counts use the O(1)-graph gather kernel instead
        return False
    widths_all = np.concatenate(plan.d_mb_widths)
    uw = np.unique(widths_all)
    n_mb = len(widths_all)
    if n_mb == 0 or len(uw) > 8 or int(uw[-1]) > 32:
        return False
    vals_np = plan.values.array()
    boffs = np.concatenate(plan.d_mb_offs) // 8
    streams, groups = [], []
    for w in uw:
        g = np.where(widths_all == w)[0]
        groups.append(g)
        nb = vpm * int(w) // 8
        # int32 index (staged buffers are < 2^27 bytes): the fancy index is a
        # transient 4x the payload bytes, not 8x
        idx = boffs[g].astype(np.int32)[:, None] + np.arange(nb, dtype=np.int32)
        # the writer may truncate the final miniblock's payload: clip (the
        # garbage lands in delta slots past the page's value count)
        np.minimum(idx, np.int32(len(vals_np) - 1), out=idx)
        streams.append(put(dev.pad_to_bucket(
            vals_np[idx].reshape(-1), extra=4)))
        counters.inc("bytes_h2d", idx.size)
    if len(uw) == 1:
        perm = None
    else:
        # d2 row j holds original miniblock concat_order[j]; restore original
        # order with the inverse permutation
        concat_order = np.concatenate(groups)
        perm = put(np.argsort(concat_order).astype(np.int32))
    mins = put(np.concatenate(plan.d_mb_mins).astype(np.int64))
    firsts = put(np.asarray(plan.d_firsts, np.int64))
    meta["delta_dense"] = (tuple(streams), perm, mins, firsts)
    plan.d_dense_static = (vpm, tuple(int(w) for w in uw),
                           tuple(len(g) for g in groups),
                           tuple(int(c) for c in plan.d_counts))
    return True


@partial(jax.jit, static_argnames=("vpm", "gw", "gk", "pcounts", "pairs",
                                   "use_pk", "interpret"))
def _delta_decode_dense(streams, perm, mins, firsts,
                        vpm: int, gw: tuple, gk: tuple, pcounts: tuple,
                        pairs: bool, use_pk: tuple = (),
                        interpret: bool = False):
    """Gather-free multi-page delta decode (device half).

    Every access pattern is compile-time static: per-width dense unpack
    (reshape + 32 unrolled shift/mask column ops), per-page reassembly by
    static slicing (page structure is host metadata), and a segmented cumsum
    whose page bases are static picks.  The only dynamic indexing is the
    miniblock row permutation for mixed-width chunks (rare).
    """
    from ..ops import pallas_kernels as pk

    parts = []
    for gi, (buf, w, k) in enumerate(zip(streams, gw, gk)):
        if w == 0:
            # constant/fixed-stride data: all deltas equal min_delta, payload
            # is empty
            parts.append(jnp.zeros((k, vpm), jnp.uint32))
            continue
        words = dev._as_words(buf)
        if gi < len(use_pk) and use_pk[gi]:
            up = pk.unpack_bits_dense(words, k * vpm, w, interpret=interpret)
        else:
            up = pk.unpack_bits_dense_jnp(words, k * vpm, w)
        parts.append(up.reshape(k, vpm))
    d2 = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    if perm is not None:
        d2 = d2[perm]
    if pairs:
        deltas = (d2.astype(jnp.int64) + mins[:, None]).reshape(-1)
        dt = jnp.int64
        fvals = firsts
    else:
        # mod-2^32 arithmetic: two's-complement wrap matches the encoding
        deltas = (d2 + (mins & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)[:, None]
                  ).reshape(-1)
        dt = jnp.uint32
        fvals = (firsts & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    seq_parts = []
    mbb = 0
    for p, cnt in enumerate(pcounts):
        seq_parts.append(fvals[p].astype(dt).reshape(1))
        nd = cnt - 1
        if nd > 0:
            seq_parts.append(deltas[mbb * vpm: mbb * vpm + nd].astype(dt))
        mbb += (nd + vpm - 1) // vpm
    seq = jnp.concatenate(seq_parts) if len(seq_parts) > 1 else seq_parts[0]
    gcum = dev.cumsum(seq)
    if len(pcounts) > 1:
        pstarts = np.concatenate([[0], np.cumsum(pcounts)[:-1]])
        base_parts = [
            jnp.broadcast_to(gcum[int(ps) - 1] if ps else jnp.zeros((), dt),
                             (int(cnt),))
            for ps, cnt in zip(pstarts, pcounts)]
        gcum = gcum - jnp.concatenate(base_parts)
    if pairs:
        return dev._i64_to_pairs(gcum)
    return jax.lax.bitcast_convert_type(gcum, jnp.int32)


@partial(jax.jit, static_argnames=("n", "vpm", "pairs"))
def _delta_decode_multi(buf, n, page_ends, firsts, mb_base, mb_offs, mb_widths,
                        mb_mins, vpm, pairs: bool):
    """All delta pages of a chunk in one call.

    seq[i] = first value of its page if i is a page start, else the unpacked
    delta.  out = cumsum(seq) - cumsum_base_of_page (segmented prefix sum).
    """
    idx = jnp.arange(n, dtype=jnp.int32)
    ends = page_ends.astype(jnp.int32)
    page = jnp.searchsorted(ends, idx, side="right")
    page = jnp.minimum(page, ends.shape[0] - 1).astype(jnp.int32)
    pcounts = jnp.diff(ends, prepend=jnp.int32(0))
    pstart = ends[page] - pcounts[page]
    within = idx - pstart
    jc = jnp.maximum(within - 1, 0)  # delta ordinal (page-start slots unused)
    mb = mb_base.astype(jnp.int32)[page] + jc // vpm
    woff = jc % vpm
    w = mb_widths[mb]
    bit_pos = mb_offs.astype(jnp.int32)[mb] + woff * w
    if pairs:
        lo, hi = dev.unpack_bits_at64(buf, bit_pos, w)
        raw = lo.astype(jnp.int64) | (hi.astype(jnp.int64) << 32)
        delta = raw + mb_mins[mb]
        seq = jnp.where(within == 0, firsts[page], delta)
        gcum = dev.cumsum(seq)
        base = gcum[pstart] - seq[pstart]  # exclusive cumsum at page start
        return dev._i64_to_pairs(gcum - base)
    # int32 values: mod-2^32 arithmetic keeps the whole pipeline in 32-bit
    # lanes (two's-complement wrap matches the encoding's semantics)
    raw = dev.unpack_bits_at32(buf, bit_pos, w)
    min32 = (mb_mins & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    delta = raw + min32[mb]
    first32 = (firsts & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    seq = jnp.where(within == 0, first32[page], delta)
    gcum = dev.cumsum(seq)
    base = gcum[pstart] - seq[pstart]
    return jax.lax.bitcast_convert_type(gcum - base, jnp.int32)


@partial(jax.jit,
         static_argnames=("n", "pages", "width", "flba", "dtype4"))
def _bss_decode_multi(buf, n, pages: tuple, width: int,
                      flba: bool = False, dtype4: str = "float32"):
    """Gather-free BYTE_STREAM_SPLIT: byte plane k of a page is the static
    slice [base + k*count, base + (k+1)*count) — page structure is host
    metadata, so every plane extraction is a compile-time slice and the
    transpose is one reshape per page."""
    per_page = []
    for base, cnt in pages:
        planes = buf[base: base + width * cnt].reshape(width, cnt)
        per_page.append(planes.T)  # (cnt, width) bytes
    bytes_ = per_page[0] if len(per_page) == 1 else jnp.concatenate(per_page)
    if flba:
        # FLBA (float16, decimals, ...): ALWAYS the (n, width) byte-row
        # plain_flba form — the output form follows the physical type, not
        # the byte width (an FLBA(4) decimal is not a float32)
        return bytes_
    if width == 4:
        return jax.lax.bitcast_convert_type(
            bytes_, jnp.dtype(dtype4)).reshape(n)
    return jax.lax.bitcast_convert_type(
        bytes_.reshape(n, 2, 4), jnp.uint32).reshape(n, 2)


def _dba_tables(plan: _Plan):
    """Concatenate the per-page DELTA_BYTE_ARRAY prescan tables into
    chunk-global int32 tables for the expand kernel.  Prefix chains never
    cross pages (enforced at plan time: every page's first entry has
    prefix 0), so per-page entry streams concatenate freely with suffix
    offsets rebased by each page's base in the staged suffix stream.
    Returns ``((prefix_lens, suffix_offs, entry_offs), entry_offs_host,
    iters)`` — the host copy of the entry offsets doubles as the output
    Column's int32 offsets."""
    if not plan.dba_pages:
        empty = np.zeros(0, np.int32)
        zero = np.zeros(1, np.int32)
        return (empty, empty, zero), zero, 0
    plens = np.concatenate(plan.dba_plens)
    soffs = np.concatenate([so[:-1] + base
                            for (base, _), so in zip(plan.dba_pages,
                                                     plan.dba_soffs)])
    slens = np.concatenate([so[1:] - so[:-1] for so in plan.dba_soffs])
    entry_offs = np.zeros(len(plens) + 1, np.int64)
    np.cumsum(plens + slens, out=entry_offs[1:])
    if int(entry_offs[-1]) > np.iinfo(np.int32).max:
        # the pointer-jumping kernel indexes output positions in 32-bit
        # lanes; a >2 GiB expansion decodes on host
        raise _Unsupported("front-coded output exceeds 32-bit addressing")
    eoffs32 = entry_offs.astype(np.int32)
    return (plens.astype(np.int32), soffs.astype(np.int32), eoffs32), \
        eoffs32, dev.delta_byte_array_iters(plens)


# ---------------------------------------------------------------------------
# Chunk decode driver
# ---------------------------------------------------------------------------


def stage_plan(plan: _Plan, stage_levels: bool = True, put=None) -> tuple:
    """H2D: put the plan's concatenated level/value byte streams into HBM.

    Split out of :func:`decode_chunk_device` so callers (and the benchmark)
    can overlap staging with decode, or re-run the decode phase on buffers
    already resident in HBM.  ``stage_levels=False`` skips the level stream
    (nested columns assemble levels on host).  ``put`` substitutes for
    ``jax.device_put`` — :func:`prepare_chunks_batched` passes a recorder so
    many chunks' streams ride one batched transfer.
    """
    if _otrace.on():
        # the H2D stage is the device pipeline's overlap partner: its span
        # sitting beside a decode span on another track IS the double
        # buffer working
        with _otrace.span("device.h2d", col=plan.leaf.dotted_path
                          if plan.leaf is not None else None,
                          bytes=len(plan.values) + len(plan.levels)):
            return _stage_plan_impl(plan, stage_levels, put=put)
    return _stage_plan_impl(plan, stage_levels, put=put)


def _stage_plan_impl(plan: _Plan, stage_levels: bool = True,
                     put=None) -> tuple:
    if put is None:
        put = jax.device_put
    dense_route = (plan.value_kind == "dict" and plan.dense_ok
                   and plan.dense_pages and _dense_mode() != "off")
    if len(plan.values) > dev.MAX_DEVICE_BUF or (
            stage_levels and len(plan.levels) > dev.MAX_DEVICE_BUF):
        # device kernels index in 32-bit lanes; oversized chunks decode on host
        raise _Unsupported("chunk stream exceeds 32-bit-lane bit addressing")
    lev_dbuf = None
    if stage_levels and len(plan.levels):
        lev_dbuf = put(plan.levels.padded_array())
        counters.inc("bytes_h2d", len(plan.levels))
    meta = {}
    delta_dense = (plan.value_kind == "delta"
                   and _stage_delta_dense(plan, meta, put=put))
    val_dbuf = None
    if not dense_route and not delta_dense and \
            plan.value_kind not in (None, "host_ba"):
        # staged even when empty (all-null chunks have no value bytes): the
        # kernels need a real buffer operand to slice [:0] from.  PLAIN
        # fixed-width values go as exact-length uint32 words (their kernels
        # only reshape and bitcast words); the other streams keep the
        # padded uint8 bucket their bit-offset gathers read past the end of
        val_dbuf = put(plan.values.words() if plan.value_kind == "plain_fixed"
                       else plan.values.padded_array())
        counters.inc("bytes_h2d", len(plan.values))
    if dense_route:
        # compacted single-width index stream replaces the raw bodies
        meta["dense"] = put(plan.dense.padded_array(extra=4))
        counters.inc("bytes_h2d", len(plan.dense))
    if plan.value_kind == "delta" and not delta_dense:
        if len(set(plan.d_vpms)) > 1:
            # the gather kernel assumes one values-per-miniblock across
            # all pages; reject before paying any H2D
            raise _Unsupported("mixed delta miniblock sizes across pages")
        meta["delta"] = put(_delta_gather_tables(plan))
    if plan.value_kind == "dba":
        # per-entry length tables ride to HBM with the suffix stream so
        # the decode phase is pure on-chip work
        tabs, eoffs_host, iters = _dba_tables(plan)
        meta["dba"] = (put(tabs), eoffs_host, iters)
        counters.inc("bytes_h2d", sum(int(a.nbytes) for a in tabs))
    if plan.value_kind == "dict" and plan.dictionary_host is not None:
        # dictionary pages stage with the chunk, not inside the decode phase
        meta["dictionary"] = _stage_dictionary(plan.dictionary_host,
                                               plan.physical, plan.leaf,
                                               put=put)
    if plan.vruns.total:
        meta["vruns"] = put(plan.vruns.run_arrays())
    if stage_levels and plan.def_runs.total:
        meta["def_runs"] = put(plan.def_runs.run_arrays())
    if stage_levels and plan.rep_runs.total:
        meta["rep_runs"] = put(plan.rep_runs.run_arrays())
    return lev_dbuf, val_dbuf, meta


def _lists_only(leaf) -> bool:
    """Whether every group above ``leaf`` is list machinery: a LIST group
    with one child, or the one-child repeated group directly under one.  A
    struct or map layer anywhere in the chain fails the test."""
    groups = leaf.ancestors[:-1]
    for i, g in enumerate(groups):
        if len(g.children) != 1:
            return False
        if g.logical_kind == LogicalKind.LIST:
            continue
        if (i and g.repetition == Rep.REPEATED
                and groups[i - 1].logical_kind == LogicalKind.LIST):
            continue
        return False
    return True


def stage_levels_on_device(leaf, plan: _Plan) -> bool:
    """Whether the level streams should go to HBM, decided from the schema:
    flat single-def columns with nulls (validity from device RLE expansion)
    and repeated columns whose chain is lists only, whose offsets/validity
    then assemble on device via ``dev.assemble_nested``.  Struct chains —
    flat (max_def > 1) or with a struct/map layer around or inside the lists
    — expand on host: the table assembler reads their host def levels for
    struct nullness."""
    if leaf.max_repetition_level == 0:
        if plan.total_values == plan.total_slots:
            return False  # no nulls anywhere: validity is None, levels unused
        return leaf.max_definition_level <= 1
    return (_lists_only(leaf)
            and bool(plan.def_runs.total) and bool(plan.rep_runs.total)
            and not plan.host_def)


def prepare_chunk(reader: ColumnChunkReader, device=None):
    """Host phase of one chunk's device decode: prescan (pread + decompress +
    run scan) and H2D staging. Safe to call from worker threads — the host
    work releases the GIL in numpy/C++/codec calls, and ``device`` targets
    the put at a specific mesh device."""
    import contextlib

    with _otrace.span("prepare_chunk"):
        plan = build_plan(reader)
        ctx = (jax.default_device(device) if device is not None
               else contextlib.nullcontext())
        with ctx:
            staged = stage_plan(
                plan, stage_levels=stage_levels_on_device(reader.leaf, plan))
    return plan, staged


class _DeferredPut:
    """Placeholder a recording ``put`` returns during batched staging: an
    index into the flat list of host pytrees awaiting the one real
    transfer."""

    __slots__ = ("idx",)

    def __init__(self, idx: int):
        self.idx = idx


def _subst_deferred(obj, outs):
    """Rebuild a staged structure with every :class:`_DeferredPut` replaced
    by its transferred device pytree (containers rebuilt, leaves shared)."""
    if isinstance(obj, _DeferredPut):
        return outs[obj.idx]
    if isinstance(obj, tuple):
        return tuple(_subst_deferred(v, outs) for v in obj)
    if isinstance(obj, list):
        return [_subst_deferred(v, outs) for v in obj]
    if isinstance(obj, dict):
        return {k: _subst_deferred(v, outs) for k, v in obj.items()}
    return obj


def prepare_chunks_batched(readers, device=None):
    """Host phase of MANY chunks' device decode with ONE H2D dispatch.

    Each chunk prescans and routes exactly as :func:`prepare_chunk` (the
    staged structures are interchangeable), but every ``device_put`` a
    chunk's stage would issue is recorded against host arrays instead, and
    the whole collection rides a single batched ``jax.device_put`` at the
    end — a few hundred per-stream dispatches collapse into one.  That is
    the dataset mesh route's per-file staging call: per-chunk dispatch
    overhead is what's left once prescan work is pipelined, and it scales
    with row-group count, not bytes.

    Returns ``[(reader, (plan, staged) | None, error)]`` in input order —
    the per-chunk triple ``decode``-side consumers already handle, with
    ``_Unsupported`` chunks carried as errors rather than raised."""
    calls: list = []

    def put(x):
        calls.append(x)
        return _DeferredPut(len(calls) - 1)

    entries = []
    with _otrace.span("prepare_chunks_batched"):
        for reader in readers:
            try:
                plan = build_plan(reader)
                staged = stage_plan(
                    plan, stage_levels=stage_levels_on_device(reader.leaf,
                                                              plan),
                    put=put)
                entries.append((reader, plan, staged, None))
            except _Unsupported as e:
                entries.append((reader, None, None, e))
        outs = jax.device_put(calls, device) if device is not None \
            else jax.device_put(calls)
    return [(reader,
             None if err is not None else (plan, _subst_deferred(staged,
                                                                 outs)),
             err)
            for reader, plan, staged, err in entries]


def _concat_batch_columns(leaf, cols: List[Column]) -> Column:
    """Concatenate per-page-batch Columns of ONE flat chunk (device decode).

    Only shapes `decode_chunk_batched` admits reach here: max_rep == 0,
    max_def <= 1.  Arrays concatenate in whatever domain the decode produced
    (jnp for device arrays, numpy for host byte-array parts); the concat is
    itself an async device op, so it overlaps later batches' staging."""
    if len(cols) == 1:
        return cols[0]
    xp = jnp if isinstance(cols[0].values if cols[0].values is not None
                           else cols[0].dict_indices, jax.Array) else np
    num_slots = sum(c.num_slots for c in cols)
    validity = None
    if any(c.validity is not None for c in cols):
        parts = [c.validity if c.validity is not None
                 else xp.ones(c.num_slots, bool) for c in cols]
        validity = xp.concatenate(parts)
    if cols[0].is_dictionary_encoded():
        idx = xp.concatenate([c.dict_indices for c in cols])
        return Column(leaf=leaf, values=None, dictionary=cols[0].dictionary,
                      dictionary_host=cols[0].dictionary_host,
                      dict_indices=idx, validity=validity,
                      num_slots=num_slots)
    offsets = None
    if cols[0].offsets is not None:
        offs_parts = []
        base = 0
        for c in cols:
            o = c.offsets
            offs_parts.append((o[:-1] + base) if base else o[:-1])
            base += int(o[-1])
        xo = jnp if isinstance(cols[0].offsets, jax.Array) else np
        offsets = xo.concatenate(
            offs_parts + [xo.asarray([base], dtype=cols[0].offsets.dtype)])
    values = xp.concatenate([c.values for c in cols])
    return Column(leaf=leaf, values=values, offsets=offsets,
                  validity=validity, num_slots=num_slots)


def decode_chunk_batched(reader: ColumnChunkReader,
                         keep_dictionary: bool = True, workers: int = 4,
                         min_batches: int = 2, target_batches: int = 6
                         ) -> Column:
    """Intra-chunk pipelined decode: page batches plan on worker threads
    while the main thread stages and (asynchronously) dispatches each
    batch's decode — so host prescan, H2D staging, and device kernels of a
    SINGLE large chunk overlap instead of running as one serial chain
    (the measured e2e floor; SURVEY.md §7 hard part 5 applied within a
    chunk, not just across chunks).

    Flat columns only (max_rep == 0, max_def <= 1 — configs 1-3 shapes);
    anything else, too few pages, or per-batch kind divergence (e.g. a
    dict→plain fallback mid-chunk) raises _Unsupported and the caller uses
    the single-plan path."""
    from concurrent.futures import ThreadPoolExecutor

    leaf = reader.leaf
    if leaf.max_repetition_level > 0 or leaf.max_definition_level > 1:
        raise _Unsupported("batched decode: flat columns only")
    pages = list(reader.pages())
    dict_pages = [p for p in pages if p.page_type == PageType.DICTIONARY_PAGE]
    data_pages = [p for p in pages
                  if p.page_type in (PageType.DATA_PAGE, PageType.DATA_PAGE_V2)]
    per = max(8, -(-len(data_pages) // target_batches))
    batches = [data_pages[i : i + per] for i in range(0, len(data_pages), per)]
    if len(batches) < min_batches:
        raise _Unsupported("batched decode: chunk too small to pipeline")
    physical = Type(reader.meta.type)

    def plan_batch(i: int, subset) -> _Plan:
        return build_plan(reader,
                          pages=iter(dict_pages + subset if i == 0 else subset))

    from ..utils.pool import instrument_task, mark_pooled

    cols: List[Column] = []
    shared_dict_host = None
    shared_dict_staged = None
    kind0 = None
    # shared-pool idioms on a caller-bounded executor: instrument_task
    # propagates the caller's op scope onto the workers (fresh ctx copy per
    # run — Contexts refuse concurrent re-entry) and lands each batch's
    # queue→run wait in pool.queue_wait_s / pool.tasks; mark_pooled keeps
    # the workers' native thread splits at 1 (utils/pool contract)
    with ThreadPoolExecutor(max_workers=max(workers, 1)) as pool:
        futs = [pool.submit(instrument_task(mark_pooled(plan_batch),
                                            "device.plan_batch"), i, b)
                for i, b in enumerate(batches)]
        for i, fut in enumerate(futs):
            plan = fut.result()
            futs[i] = None  # release: bounds live plan memory to in-flight
            if i == 0:
                kind0 = plan.value_kind
                shared_dict_host = plan.dictionary_host
            else:
                if plan.value_kind != kind0:
                    raise _Unsupported("batched decode: kind diverges across "
                                       "pages (mid-chunk encoding fallback)")
                plan.dictionary_host = None  # staged once, injected below
            stage_levels = stage_levels_on_device(leaf, plan)
            staged = stage_plan(plan, stage_levels=stage_levels)
            if i == 0:
                shared_dict_staged = (staged[2] or {}).get("dictionary")
            elif shared_dict_host is not None:
                plan.dictionary_host = shared_dict_host
                staged[2]["dictionary"] = shared_dict_staged
            cols.append(decode_staged(leaf, physical, plan, staged,
                                      keep_dictionary=keep_dictionary))
    return _concat_batch_columns(leaf, cols)


def decode_chunks_pipelined(chunks, keep_dictionary: bool = True,
                            workers: int = 2):
    """Double-buffered read: stage chunk N+1 while chunk N's kernels run.

    SURVEY.md §7 hard part 5 — the host prep (decompress + prescan) and H2D
    put of later chunks overlap the (asynchronously dispatched) device decode
    of earlier ones. A bounded thread pool keeps at most ``workers`` chunks
    in flight beyond the one decoding, bounding memory to O(workers · chunk).
    Yields decoded Columns in chunk order; falls back to host decode per
    chunk on unsupported shapes.
    """
    import contextlib

    from ..io.prefetch import make_chunk_prefetcher

    chunks = list(chunks)
    # ROADMAP follow-on (PR 3): the staging phase used to pread each chunk
    # serially on its prep thread — plan every chunk's byte range through a
    # per-file chunk prefetcher (advise-backed: madvise(WILLNEED) kernel
    # readahead) so disk readahead of later chunks overlaps the prescan +
    # H2D of earlier ones.  In-memory sources get no prefetcher (nothing to
    # hide) and the route is unchanged.
    with contextlib.ExitStack() as _stack:
        _pres: dict = {}
        for _r in chunks:
            _pf = _r.file
            if id(_pf) not in _pres:
                _pre = make_chunk_prefetcher(_pf.source,
                                             n_streams=min(len(chunks), 4))
                if _pre is not None:
                    _stack.callback(_pre.close)
                    _stack.enter_context(_pf._source_override(_pre))
                _pres[id(_pf)] = _pre
            if _pres[id(_pf)] is not None:
                _pres[id(_pf)].plan(*_r.byte_range)
        yield from _decode_chunks_pipelined_impl(chunks, keep_dictionary,
                                                 workers)


def _decode_chunks_pipelined_impl(chunks, keep_dictionary: bool,
                                  workers: int):
    from concurrent.futures import ThreadPoolExecutor

    from ..utils.pool import available_cpus

    if len(chunks) == 1 and (jax.default_backend() == "tpu"
                             or available_cpus() > 1):
        # nothing to overlap ACROSS chunks: pipeline WITHIN the chunk
        # (page batches) instead — the single-large-chunk e2e shape.
        # Only where overlap can pay: on one CPU core the batch concat
        # and pool overheads are pure loss (measured 2x on dict chunks).
        try:
            col = decode_chunk_batched(chunks[0],
                                       keep_dictionary=keep_dictionary)
            counters.inc("chunks_device_decoded")
            yield col
            return
        except _Unsupported:
            pass  # the single-plan path below routes this chunk
    from ..utils.locks import make_lock

    active = {"n": 0}
    lock = make_lock("device.stage_concurrency")

    def prep(reader):
        with lock:
            active["n"] += 1
            counters.high_water("stage_concurrency_peak", active["n"])
        try:
            try:
                return reader, prepare_chunk(reader), None
            except _Unsupported as e:
                return reader, None, e
        finally:
            with lock:
                active["n"] -= 1
    from ..utils.pool import instrument_task, mark_pooled

    # shared-pool idioms on the bounded stage executor (see
    # decode_chunk_batched): op-scope propagation, queue-wait accounting,
    # and in_shared_pool() marking for every staging task
    def _submit(pool, reader):
        return pool.submit(instrument_task(mark_pooled(prep),
                                           "device.stage"), reader)

    with ThreadPoolExecutor(max_workers=max(workers, 1)) as pool:
        pending = []
        it = iter(chunks)
        for reader in it:
            pending.append(_submit(pool, reader))
            if len(pending) > workers:
                break
        i = 0
        while i < len(pending):
            reader, prepped, err = pending[i].result()
            pending[i] = None  # release the future: keeps plan/staged memory
            i += 1             # bounded to the in-flight window
            nxt = next(it, None)
            if nxt is not None:
                pending.append(_submit(pool, nxt))
            if err is not None:
                counters.inc("chunks_host_fallback")
                yield decode_chunk_host(reader)
                continue
            plan, staged = prepped
            try:
                col = decode_staged(reader.leaf, Type(reader.meta.type), plan,
                                    staged, keep_dictionary=keep_dictionary)
                counters.inc("chunks_device_decoded")
                yield col
            except _Unsupported:
                counters.inc("chunks_host_fallback")
                yield decode_chunk_host(reader)


def decode_chunk_device(reader: ColumnChunkReader, keep_dictionary: bool = True,
                        fallback: bool = True) -> Column:
    try:
        plan = build_plan(reader)
        staged = stage_plan(plan,
                            stage_levels=stage_levels_on_device(reader.leaf, plan))
        col = decode_staged(reader.leaf, Type(reader.meta.type), plan, staged,
                            keep_dictionary=keep_dictionary)
        counters.inc("chunks_device_decoded")
        return col
    except _Unsupported:
        if not fallback:
            raise
        counters.inc("chunks_host_fallback")
        return decode_chunk_host(reader)


def decode_staged(leaf, physical: Type, plan: _Plan, staged: tuple,
                  keep_dictionary: bool = True) -> Column:
    """Device decode phase: staged HBM buffers → decoded :class:`Column`."""
    with (_otrace.span(f"decode_staged:{plan.value_kind}") if _otrace.on()
          else _otrace.NULL_SPAN):
        return _decode_staged(leaf, physical, plan, staged, keep_dictionary)


def _decode_staged(leaf, physical: Type, plan: _Plan, staged: tuple,
                   keep_dictionary: bool = True) -> Column:
    max_def = leaf.max_definition_level
    max_rep = leaf.max_repetition_level
    lev_dbuf, val_dbuf, staged_meta = (staged if len(staged) == 3
                                       else (*staged, None))
    staged_meta = staged_meta or {}
    if not isinstance(staged_meta, dict):  # pre-dict layout: the delta tuple
        staged_meta = {"delta": staged_meta}

    # ---- levels -----------------------------------------------------------
    # Flat optional columns: expand def levels on device (validity mask stays
    # in HBM).  List chains of any depth: expand AND assemble on device
    # (SURVEY.md §7 hard part 4).  Struct chains: the table assembler
    # consumes levels on host, so expand them there once — no device work,
    # no double expansion (stage_levels_on_device).
    def_levels = None
    def_host = rep_host = None
    device_asm = None
    fused_asm = None
    validity = None
    if max_rep > 0:
        infos = levels_ops.repeated_ancestors(leaf)
        if lev_dbuf is not None and stage_levels_on_device(leaf, plan):
            d_dev = plan.def_runs.expand(lev_dbuf,
                                         tables=staged_meta.get("def_runs"))
            r_dev = plan.rep_runs.expand(lev_dbuf,
                                         tables=staged_meta.get("rep_runs"))
            device_asm = dev.assemble_nested(d_dev, r_dev, infos, max_def)
        else:
            lev_host = plan.levels.array()
            if (len(infos) == 1 and plan.def_runs.total and plan.rep_runs.total
                    and plan.def_runs.total == plan.rep_runs.total
                    and not plan.host_def):
                # fused path: offsets/validity straight from the run tables —
                # host work stays metadata-scale (per-run, not per-slot)
                fused_asm = native.assemble_list_runs(
                    lev_host, plan.def_runs.tables_host(),
                    plan.rep_runs.tables_host(), plan.def_runs.total,
                    infos[0].def_level, max_def)
            if fused_asm is None:
                if plan.def_runs.total:
                    def_host = plan.def_runs.expand_host(lev_host)
                elif plan.host_def:
                    def_host = np.concatenate(plan.host_def).astype(np.int32)
                if plan.rep_runs.total:
                    rep_host = plan.rep_runs.expand_host(lev_host)
                else:
                    rep_host = np.zeros(
                        len(def_host) if def_host is not None else 0, np.int32)
            else:
                def_host = _LazyLevels(plan.def_runs, lev_host)
                rep_host = _LazyLevels(plan.rep_runs, lev_host)
    elif max_def > 0 and plan.total_values == plan.total_slots:
        pass  # no nulls anywhere: validity stays None, levels never expand
    else:
        if max_def > 1 and (plan.def_runs.total or plan.host_def):
            # struct layers: the table assembler needs host def levels for
            # struct-validity zips — expand once on host and derive the leaf
            # validity from it (round 1 expanded on device AND host)
            if plan.def_runs.total:
                def_host = plan.def_runs.expand_host(
                    plan.levels.array())
            else:
                def_host = np.concatenate(plan.host_def).astype(np.int32)
            validity = jax.device_put(def_host == max_def)
        elif plan.def_runs.total:
            def_levels = plan.def_runs.expand(lev_dbuf,
                                              tables=staged_meta.get("def_runs"))
        elif plan.host_def:
            def_host = np.concatenate(plan.host_def).astype(np.int32)
            def_levels = jnp.asarray(def_host)

    if max_def > 0 and def_levels is not None:
        validity = dev.validity_from_def(def_levels, max_def)

    # ---- values -----------------------------------------------------------
    dictionary = None
    dict_indices = None
    values = None
    offsets = None
    kind = plan.value_kind
    nvals = plan.total_values

    if kind == "plain_fixed":
        if physical in _IS_PAIR:
            counters.inc("kernel_bytes.fixed64_pairs", 16 * nvals)
            values = dev.fixed64_pairs(val_dbuf, nvals)
        elif physical == Type.INT96:
            values = val_dbuf.reshape(nvals, 3)
        else:
            dt = {Type.INT32: "int32", Type.FLOAT: "float32"}[physical]
            counters.inc("kernel_bytes.bitcast_fixed32", 8 * nvals)
            values = dev.bitcast_fixed32(val_dbuf, nvals, dt)
    elif kind == "plain_flba":
        values = val_dbuf[: nvals * leaf.type_length].reshape(
            nvals, leaf.type_length)
    elif kind == "bool":
        values = plan.vruns.expand(val_dbuf,
                                    tables=staged_meta.get("vruns")).astype(jnp.bool_)
    elif kind == "dict":
        dictionary = staged_meta.get("dictionary")
        if dictionary is None:
            dictionary = _stage_dictionary(plan.dictionary_host, physical, leaf)
        if staged_meta.get("dense") is not None:
            dict_indices, values = _decode_dense_dict(plan, staged_meta["dense"],
                                                      dictionary, physical)
        else:
            dict_indices = plan.vruns.expand(val_dbuf,
                                             tables=staged_meta.get("vruns"))
            if physical == Type.BYTE_ARRAY:
                values = None  # stays encoded (Arrow dictionary form)
            else:
                values = dev.dict_gather(dictionary, dict_indices)
    elif kind == "delta":
        if staged_meta.get("delta_dense") is not None:
            streams, perm, mins, firsts = staged_meta["delta_dense"]
            vpm, gw, gk, pcounts = plan.d_dense_static
            use_pk = tuple(_use_pallas(w) for w in gw)
            interp = jax.default_backend() != "tpu"
            values = _delta_decode_dense(streams, perm, mins, firsts,
                                         vpm, gw, gk, pcounts,
                                         physical != Type.INT32,
                                         use_pk, interp)
        else:
            if len(set(plan.d_vpms)) > 1:
                raise _Unsupported("mixed delta miniblock sizes across pages")
            tables = staged_meta.get("delta")
            if tables is None:
                tables = _delta_gather_tables(plan)
            page_ends, firsts, mb_base, mb_offs, mb_widths, mb_mins = tables
            pairs = physical != Type.INT32
            n_total = int(sum(plan.d_counts))
            values = _delta_decode_multi(val_dbuf, n_total, page_ends,
                                         firsts, mb_base, mb_offs,
                                         mb_widths, mb_mins, plan.d_vpm, pairs)
    elif kind == "bss":
        w = _FIXED_WIDTH.get(physical, leaf.type_length)
        flba = physical == Type.FIXED_LEN_BYTE_ARRAY
        if not flba and w not in (4, 8):
            # e.g. INT96: BSS is undefined for it — clean host fallback
            raise _Unsupported("byte-stream-split over unsupported width")
        if len(plan.bss_pages) > 512:
            # static per-page slicing unrolls O(pages) into the graph
            raise _Unsupported(
                "byte-stream-split chunk with huge page count")
        if len(plan.bss_pages) == 1 and int(plan.bss_pages[0][0]) == 0:
            # single-page chunk (the common writer layout): the
            # canonical ops/device.py plane-transpose kernel — same
            # math as the multi-page twin without its per-page
            # static-slice unrolling
            values = dev.byte_stream_split(
                val_dbuf, nvals, w,
                out_dtype=None if flba else
                ("int32" if physical == Type.INT32 else "float32")
                if w == 4 else "uint32")
        else:
            values = _bss_decode_multi(
                val_dbuf, nvals,
                tuple((int(b), int(n)) for b, n in plan.bss_pages),
                w, flba,
                # 4-byte output dtype follows the PHYSICAL type (an
                # INT32 BSS column is not a float32)
                dtype4="int32" if physical == Type.INT32 else "float32")
    elif kind == "dba":
        staged_dba = staged_meta.get("dba")
        if staged_dba is None:
            tabs_host, eoffs_host, iters = _dba_tables(plan)
            tabs = jax.device_put(tabs_host)
        else:
            tabs, eoffs_host, iters = staged_dba
        plens_d, soffs_d, eoffs_d = tabs
        out = dev.delta_byte_array_expand(val_dbuf, plens_d, soffs_d,
                                          eoffs_d, int(eoffs_host[-1]),
                                          iters)
        if physical == Type.FIXED_LEN_BYTE_ARRAY:
            values = out.reshape(-1, leaf.type_length)
        else:
            # same Column form as host_ba: device value bytes, host int32
            # offsets — every byte-array consumer already speaks it
            values = out
            offsets = eoffs_host
    elif kind == "host_ba":
        if plan.host_parts and isinstance(plan.host_parts[0], tuple):
            vals = np.concatenate([p[0] for p in plan.host_parts])
            offs_parts, base = [], 0
            for p in plan.host_parts:
                o = p[1].astype(np.int64)
                offs_parts.append(o[:-1] + base)
                base += int(o[-1])
            offsets = np.concatenate(offs_parts + [np.array([base])]).astype(np.int32)
            values = jax.device_put(vals)
            counters.inc("bytes_h2d", vals.nbytes)
        else:
            values = jax.device_put(np.concatenate(plan.host_parts))
    elif kind is None:
        values = jnp.zeros(0, jnp.int32)

    # ---- assembly ---------------------------------------------------------
    list_offsets: List[np.ndarray] = []
    list_validity: List[Optional[np.ndarray]] = []
    leaf_validity = validity
    if device_asm is not None:
        list_offsets, list_validity, leaf_validity = device_asm
    elif fused_asm is not None:
        lofs, lval, leaf_validity = fused_asm
        list_offsets, list_validity = [lofs], [lval]
    elif max_rep > 0 and def_host is not None:
        asm = levels_ops.assemble(def_host, rep_host, leaf)
        list_offsets, list_validity = asm.list_offsets, asm.list_validity
        leaf_validity = asm.validity
    col = Column(leaf=leaf, values=values, offsets=offsets,
                 validity=leaf_validity, list_offsets=list_offsets,
                 list_validity=list_validity, num_slots=plan.total_slots,
                 def_levels=def_host, rep_levels=rep_host)
    col.dictionary = dictionary
    col.dictionary_host = plan.dictionary_host
    col.dict_indices = dict_indices
    return col


def _decode_dense_dict(plan: _Plan, dense_buf: jax.Array, dictionary,
                       physical: Type):
    """Gather-free dict-index decode from the compacted dense stream
    (VERDICT r1 item 3 — the Pallas wiring, with the jnp twin as the
    portable default). Returns (indices, values-or-None)."""
    w = plan.dense_w
    # round UP to whole 32-value groups: the final page's tail group may be
    # partial byte-wise; the unpack kernels zero-pad missing words
    total = -(-(len(plan.dense) * 8 // w) // 32) * 32
    use_pk = _use_pallas(w)
    interpret = jax.default_backend() != "tpu"
    pages = tuple((int(s), int(n)) for s, n in plan.dense_pages)
    indices = _dense_unpack_pages(dense_buf, len(plan.dense), total, w,
                                  pages, use_pk, interpret)
    if physical == Type.BYTE_ARRAY:
        return indices, None
    return indices, dev.dict_gather(dictionary, indices)


@partial(jax.jit, static_argnames=("nbytes", "total", "w", "pages", "pallas",
                                   "interpret"))
def _dense_unpack_pages(dense_buf, nbytes: int, total: int, w: int,
                        pages: tuple, pallas: bool, interpret: bool):
    """One dispatch for the dense dict-index decode: word view + unpack +
    per-page compaction (static slices) + dtype cast, all fused."""
    from ..ops import pallas_kernels as pk

    # round word count UP: the stream's byte length need not be 4-aligned and
    # pad_to_bucket(extra=4) guarantees ≥4 zero bytes of slack past the end
    nwords = (nbytes + 3) // 4
    words = dev._as_words(dense_buf)[:nwords]
    if pallas:
        allidx = pk.unpack_bits_dense(words, total, w, interpret=interpret)
    else:
        allidx = pk.unpack_bits_dense_jnp(words, total, w)
    parts = [allidx[s: s + n] for s, n in pages]
    return (parts[0] if len(parts) == 1
            else jnp.concatenate(parts)).astype(jnp.int32)


def _stage_dictionary(dict_host, physical, leaf, put=None):
    if put is None:
        put = jax.device_put
    if dict_host is None:
        raise _Unsupported("dictionary-encoded page without dictionary page")
    if physical == Type.BYTE_ARRAY:
        vals, offs = dict_host
        return (put(vals), put(offs.astype(np.int32)))
    if physical in _IS_PAIR:
        arr = np.ascontiguousarray(dict_host)
        return put(arr.view(np.uint32).reshape(-1, 2))
    return put(np.asarray(dict_host))
