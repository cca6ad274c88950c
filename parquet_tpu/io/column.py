"""Decoded column representation: flat device-friendly buffers.

Reference parity: the reference's decoded page values flow through
``page.Data() encoding.Values`` — a kind-tagged union of flat ``data []byte``
+ ``offsets []int32`` (SURVEY.md §2.2).  ``Column`` is the whole-chunk analog:
dense value buffer + optional offsets (byte arrays) + validity/list structure
from Dremel assembly.  ``to_arrow()`` reconstructs a pyarrow array (the interop
boundary and test oracle); values/offsets/validity may live on device as
jax.Arrays in the TPU path.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List, Optional

import numpy as np

from ..format.enums import Type
from ..schema.schema import Leaf
from ..schema.types import LogicalKind


@dataclass
class Column:
    leaf: Leaf
    values: Any  # np/jax array: dense present values (fixed width) or uint8 bytes
    offsets: Optional[Any] = None  # int32[n+1] for BYTE_ARRAY values
    validity: Optional[Any] = None  # bool per leaf slot (None = all valid)
    list_offsets: List[Any] = field(default_factory=list)  # per repeated level
    list_validity: List[Optional[Any]] = field(default_factory=list)
    num_slots: int = 0  # leaf slot count (== num rows for flat columns)
    # dictionary-encoded representation (device path keeps chunks encoded:
    # the Arrow DictionaryArray analog — reference dictionary.go read side)
    dictionary: Any = None  # device dict values (or (values, offsets) pair)
    dictionary_host: Any = None  # host numpy mirror
    dict_indices: Any = None  # int32 indexes into the dictionary
    # raw Dremel level streams (host decode keeps them for the row model —
    # rows.py record-at-a-time Reconstruct needs struct-level null fidelity
    # that the collapsed validity/list_offsets form cannot carry)
    def_levels: Optional[Any] = None
    rep_levels: Optional[Any] = None

    @property
    def num_values(self) -> int:
        if self.values is None and self.dict_indices is not None:
            return len(self.dict_indices)
        if self.offsets is not None:
            return len(self.offsets) - 1
        return len(self.values)

    def is_dictionary_encoded(self) -> bool:
        return self.values is None and self.dict_indices is not None

    def _host_dictionary(self):
        """Host numpy dictionary, mirroring the device form on demand."""
        if self.dictionary_host is None and self.dictionary is not None:
            d = self.dictionary
            self.dictionary_host = (
                (np.asarray(d[0]), np.asarray(d[1])) if isinstance(d, tuple)
                else np.asarray(d))
        return self.dictionary_host

    def materialize_host(self):
        """Dense host (values, offsets) for dictionary-encoded byte arrays."""
        from ..ops import ref as _ref

        idx = np.asarray(self.dict_indices).astype(np.int64)
        gathered = _ref.gather_dictionary(self._host_dictionary(), idx)
        if isinstance(gathered, tuple):
            self.values, self.offsets = gathered
        else:
            self.values = gathered
        return self

    # ------------------------------------------------------------------
    def to_numpy(self):
        """Present values as numpy; nulls are NOT filled (dense values only)."""
        return np.asarray(self.values)

    def _dict_arrow(self):
        """Dictionary-encoded column → pyarrow DictionaryArray (indices +
        dictionary, both zero-gather).  None = caller falls back."""
        import pyarrow as pa

        dh = self._host_dictionary()
        if dh is None:
            return None
        try:
            if isinstance(dh, tuple):
                dict_arr = _leaf_to_arrow(self.leaf, np.asarray(dh[0]),
                                          np.asarray(dh[1]), None)
            else:
                dict_arr = _leaf_to_arrow(self.leaf, np.asarray(dh), None,
                                          None)
            idx = np.asarray(self.dict_indices).astype(np.int32,
                                                        copy=False)
            if self.validity is not None:
                v = np.asarray(self.validity, bool)
                slot = np.zeros(len(v), np.int32)
                slot[v] = idx
                ia = pa.array(slot, mask=~v)
            else:
                ia = pa.array(idx)
            return pa.DictionaryArray.from_arrays(ia, dict_arr)
        except Exception:
            return None

    def _dict_dense_arrow(self):
        """Dictionary-encoded column → dense arrow via one arrow-C++ cast
        (indices + dictionary → DictionaryArray → value type) instead of a
        host gather over every value.  None = caller falls back."""
        arr = self._dict_arrow()
        return None if arr is None else arr.cast(arr.type.value_type)

    def to_arrow(self, prefer_dictionary: bool = False):
        """pyarrow array for this column.  ``prefer_dictionary=True`` keeps
        a dictionary-encoded flat column AS a DictionaryArray — no
        densifying cast — matching pyarrow's own output for files whose
        embedded arrow schema declares the field dictionary-typed."""
        import pyarrow as pa

        leaf = self.leaf
        arr = None
        if self.is_dictionary_encoded():
            if prefer_dictionary and not self.list_offsets:
                arr = self._dict_arrow()
            if arr is None:
                arr = self._dict_dense_arrow()
            if arr is None:
                self.materialize_host()
        if arr is None:
            values = np.asarray(self.values)
            # device pair representation → host 64-bit view (zero-copy)
            if values.ndim == 2 and values.dtype == np.uint32 and values.shape[1] == 2:
                host_dt = {Type.INT64: np.int64, Type.DOUBLE: np.float64}.get(
                    leaf.physical_type, np.int64)
                values = np.ascontiguousarray(values).view(host_dt).reshape(-1)
            if (leaf.physical_type == Type.INT96 and values.ndim == 2
                    and values.dtype == np.uint32):
                values = values.astype(np.uint32).view(np.int32)
            offsets = None if self.offsets is None else np.asarray(self.offsets)
            validity = None if self.validity is None else np.asarray(self.validity)

            arr = _leaf_to_arrow(leaf, values, offsets, validity)
        # wrap in list layers, innermost last in list_offsets → build outside-in
        for offs, lv in zip(reversed(self.list_offsets), reversed(self.list_validity)):
            offs = np.asarray(offs).astype(np.int32)
            if lv is not None and not bool(np.all(lv)):
                mask = pa.array(~np.asarray(lv))
                arr = pa.ListArray.from_arrays(pa.array(offs), arr, mask=mask)
            else:
                arr = pa.ListArray.from_arrays(pa.array(offs), arr)
        return arr


def _leaf_to_arrow(leaf: Leaf, values, offsets, validity):
    import pyarrow as pa

    k = leaf.logical_kind
    pt = leaf.physical_type
    n_slots = len(validity) if validity is not None else None

    if k == LogicalKind.UNKNOWN:  # Null logical type: always-null column
        n = n_slots if n_slots is not None else len(values)
        return pa.nulls(n)

    if pt == Type.BYTE_ARRAY:
        # chunks past the int32 offset range arrive with int64 offsets and
        # take the arrow LARGE layout (64-bit offsets) end to end
        wide = offsets is not None and _wide_offsets(offsets)
        # string-like logical types build utf8 DIRECTLY from buffers — a
        # binary array cast to string re-walks (and copies) the whole
        # buffer (measured 57 ms per 8M-row column); parquet declares the
        # bytes UTF-8, the writer's responsibility, matching pyarrow's own
        # non-validating read
        if k in (LogicalKind.STRING, LogicalKind.ENUM, LogicalKind.JSON):
            atype = pa.large_utf8() if wide else pa.utf8()
        else:
            atype = pa.large_binary() if wide else pa.binary()
        # expand dense values to slot-aligned with validity
        if validity is not None:
            arr = _ragged_with_nulls(values, offsets, validity, atype)
        else:
            arr = pa.Array.from_buffers(
                atype, len(offsets) - 1,
                [None, pa.py_buffer(np.ascontiguousarray(
                    offsets, dtype=np.int64 if wide else np.int32)),
                 pa.py_buffer(np.ascontiguousarray(np.asarray(values).view(np.uint8)))])
        return arr

    if pt == Type.FIXED_LEN_BYTE_ARRAY:
        width = leaf.type_length
        vals = np.asarray(values, dtype=np.uint8).reshape(-1, width)
        if k == LogicalKind.FLOAT16:
            flat = vals.reshape(-1).view(np.float16)
            return _fixed_with_nulls(flat, validity, pa.float16())
        if k == LogicalKind.DECIMAL:
            p, s = leaf.logical_params.get("precision", 38), leaf.logical_params.get("scale", 0)
            ints = _be_bytes_to_int(vals)
            return _decimal_with_nulls(ints, validity, pa.decimal128(p, s))
        if validity is None:
            return pa.FixedSizeBinaryArray.from_buffers(
                pa.binary(width), len(vals), [None, pa.py_buffer(np.ascontiguousarray(vals))])
        return _fsb_with_nulls(vals, validity, width)

    if pt == Type.INT96:
        # legacy impala timestamp: (lo64 nanos-in-day, hi32 julian day) → ns timestamp
        v = np.asarray(values).reshape(-1, 3)
        nanos = v[:, 0].astype(np.uint32).astype(np.uint64) | (
            v[:, 1].astype(np.uint32).astype(np.uint64) << np.uint64(32))
        days = v[:, 2].astype(np.int64) - 2440588  # julian → unix epoch days
        ts = days * 86400_000_000_000 + nanos.astype(np.int64)
        return _fixed_with_nulls(ts, validity, pa.timestamp("ns"))

    flat = np.asarray(values)
    if k == LogicalKind.INT:
        bw = leaf.logical_params.get("bit_width", 64)
        signed = leaf.logical_params.get("signed", True)
        dt = np.dtype(f"{'i' if signed else 'u'}{max(bw, 8) // 8}")
        flat = flat.astype(dt) if pt == Type.INT32 else flat.view(dt) if flat.dtype.itemsize == dt.itemsize else flat.astype(dt)
        return _fixed_with_nulls(flat, validity, pa.from_numpy_dtype(dt))
    if k == LogicalKind.DATE:
        return _fixed_with_nulls(flat.astype(np.int32, copy=False),
                                 validity, pa.date32())
    if k == LogicalKind.TIMESTAMP_MILLIS:
        return _fixed_with_nulls(flat, validity, pa.timestamp("ms", tz="UTC" if leaf.logical_params.get("utc") else None))
    if k == LogicalKind.TIMESTAMP_MICROS:
        return _fixed_with_nulls(flat, validity, pa.timestamp("us", tz="UTC" if leaf.logical_params.get("utc") else None))
    if k == LogicalKind.TIMESTAMP_NANOS:
        return _fixed_with_nulls(flat, validity, pa.timestamp("ns", tz="UTC" if leaf.logical_params.get("utc") else None))
    if k == LogicalKind.TIME_MILLIS:
        return _fixed_with_nulls(flat.astype(np.int32, copy=False),
                                 validity, pa.time32("ms"))
    if k == LogicalKind.TIME_MICROS:
        return _fixed_with_nulls(flat, validity, pa.time64("us"))
    if k == LogicalKind.DECIMAL and pt in (Type.INT32, Type.INT64):
        p, s = leaf.logical_params.get("precision", 18), leaf.logical_params.get("scale", 0)
        return _decimal_with_nulls(flat.astype(np.int64), validity, pa.decimal128(p, s))

    import pyarrow as pa  # noqa: F811
    return _fixed_with_nulls(flat, validity, pa.from_numpy_dtype(flat.dtype))


def concat_byte_arrays(values_parts, offsets_parts):
    """Concatenate (values, offsets) byte-array pairs with the offsets
    rebased to one buffer.  Offsets are assumed to start at 0 (every
    producer in this codebase emits per-part offsets from 0).  Returns
    (uint8 values, int64 offsets)."""
    off_parts, vbase = [], 0
    for o in offsets_parts:
        o = np.asarray(o, np.int64)
        off_parts.append(o[:-1] + vbase)
        vbase += int(o[-1])
    return (np.concatenate([np.asarray(v) for v in values_parts]),
            np.concatenate(off_parts + [np.array([vbase], np.int64)]))


def empty_column(leaf: Leaf) -> Column:
    """A valid zero-row Column for ``leaf`` (typed empty arrays; nested
    leaves get empty level streams through the assembler) — the shape an
    empty row-group selection or an empty page span decodes to."""
    from ..ops import levels as levels_ops

    nested = leaf.max_repetition_level > 0
    empty_lv = np.zeros(0, np.int32)
    asm = levels_ops.assemble(empty_lv if nested else None,
                              empty_lv if nested else None, leaf)
    if leaf.physical_type == Type.BYTE_ARRAY:
        values = np.empty(0, np.uint8)
        offsets = np.zeros(1, np.int32)
    elif leaf.physical_type == Type.FIXED_LEN_BYTE_ARRAY:
        values = np.empty((0, leaf.type_length or 0), np.uint8)
        offsets = None
    else:
        values = np.empty(0, leaf.np_dtype() or np.uint8)
        offsets = None
    return Column(leaf=leaf, values=values, offsets=offsets,
                  validity=asm.validity, list_offsets=asm.list_offsets,
                  list_validity=asm.list_validity, num_slots=0,
                  def_levels=empty_lv if nested else None,
                  rep_levels=empty_lv if nested else None)


def concat_columns(parts: List[Column]) -> Column:
    """Concatenate per-row-group chunks of the same leaf into one Column.

    Dictionary-encoded chunks stay encoded: per-row-group dictionaries are
    concatenated and the index streams rebased (the host twin of
    host_scan._concat_dictionaries) — materializing 10s of millions of
    strings per column just to concatenate them was the whole-file read's
    biggest cost at lineitem scale."""
    if len(parts) == 1:
        return parts[0]
    if all(p.is_dictionary_encoded() for p in parts):
        merged = _concat_dict_parts(parts)
        if merged is not None:
            return merged
    for p in parts:  # mixed encoded/plain chunks: materialize first
        if p.is_dictionary_encoded():
            p.materialize_host()
    first = parts[0]
    if first.offsets is not None:
        values = _concat_values(parts)
        offs_parts = []
        base = 0
        for p in parts:
            o = np.asarray(p.offsets).astype(np.int64)
            offs_parts.append(o[:-1] + base)
            base += int(o[-1])
        offsets = np.concatenate(offs_parts + [np.array([base])])
        # stay on int64 offsets when the concatenated chunk crosses the
        # int32-offset limit (the arrow LARGE layout downstream) — a bare
        # int32 cast would wrap silently
        from .reader import _OFFSET32_LIMIT

        if base <= _OFFSET32_LIMIT:
            offsets = offsets.astype(np.int32)
    else:
        values = _concat_values(parts)
        offsets = None
    validity, list_offsets, list_validity, def_levels, rep_levels = \
        _concat_structure(parts)
    return Column(leaf=first.leaf, values=values, offsets=offsets,
                  validity=validity, list_offsets=list_offsets,
                  list_validity=list_validity,
                  num_slots=sum(p.num_slots for p in parts),
                  def_levels=def_levels, rep_levels=rep_levels)


def _concat_values(parts: List[Column]):
    """Values stay where they were decoded: device-resident parts
    concatenate on the device (a round trip through the host here would
    hand jit consumers host arrays), anything else on the host."""
    import jax

    vals = [p.values for p in parts]
    if all(isinstance(v, jax.Array) for v in vals):
        import jax.numpy as jnp

        return jnp.concatenate(_on_one_device(vals))
    return np.concatenate([np.asarray(v) for v in vals])


def _on_one_device(tree):
    """Device arrays of one column can sit on different devices: a mesh
    dataset read decodes file i on device i % n. A jnp concat refuses
    that, so move every array onto the first one's (lowest) device; arrays
    that already share one device set are returned as they are."""
    import jax

    arrs = [a for a in jax.tree_util.tree_leaves(tree)
            if isinstance(a, jax.Array)]
    if len({frozenset(a.devices()) for a in arrs}) <= 1:
        return tree
    target = min(arrs[0].devices(), key=lambda d: d.id)
    return jax.device_put(tree, target)


def _concat_structure(parts: List[Column]):
    """Validity / list structure / raw level concatenation shared by the
    plain and dictionary-preserving concat paths."""
    first = parts[0]
    if any(p.validity is not None for p in parts):
        validity = np.concatenate([
            np.asarray(p.validity) if p.validity is not None
            else np.ones(p.num_slots or p.num_values, dtype=bool)
            for p in parts])
    else:
        validity = None
    nlev = len(first.list_offsets)
    list_offsets, list_validity = [], []
    for k in range(nlev):
        base = 0
        offs_parts = []
        for p in parts:
            o = np.asarray(p.list_offsets[k]).astype(np.int64)
            offs_parts.append(o[:-1] + base)
            base += int(o[-1])
        list_offsets.append(np.concatenate(offs_parts + [np.array([base])]))
        if any(p.list_validity[k] is not None for p in parts):
            list_validity.append(np.concatenate([
                np.asarray(p.list_validity[k]) if p.list_validity[k] is not None
                else np.ones(len(p.list_offsets[k]) - 1, dtype=bool)
                for p in parts]))
        else:
            list_validity.append(None)
    def_levels = rep_levels = None
    if all(p.def_levels is not None for p in parts):
        def_levels = np.concatenate([np.asarray(p.def_levels) for p in parts])
    if all(p.rep_levels is not None for p in parts):
        rep_levels = np.concatenate([np.asarray(p.rep_levels) for p in parts])
    return validity, list_offsets, list_validity, def_levels, rep_levels


def _concat_dict_parts(parts: List[Column]) -> Optional[Column]:
    """Dictionary-preserving concat: rebase each chunk's index stream by the
    sizes of the dictionaries before it and concatenate the dictionaries
    (duplicates across row groups kept — correctness over minimality).
    Returns None when a part lacks a host dictionary (device-resident
    chunks concatenate via the main path)."""
    first = parts[0]
    on_device = not isinstance(first.dict_indices, np.ndarray)
    if on_device and all(p.dictionary is not None for p in parts):
        # device-resident chunks: rebase with jnp ops, nothing leaves HBM
        from ..parallel.host_scan import _concat_dictionaries

        dictionary, indices = _concat_dictionaries(_on_one_device(
            [(p.dictionary, p.dict_indices) for p in parts]))
        dict_host = None
    elif all(p.dictionary_host is not None for p in parts):
        idx_parts, base = [], 0
        ba = isinstance(first.dictionary_host, tuple)
        for p in parts:
            idx = np.asarray(p.dict_indices)
            idx_parts.append(idx.astype(np.int32) + np.int32(base))
            base += (len(p.dictionary_host[1]) - 1 if ba
                     else len(p.dictionary_host))
        indices = np.concatenate(idx_parts)
        if ba:
            dict_host = concat_byte_arrays(
                [p.dictionary_host[0] for p in parts],
                [p.dictionary_host[1] for p in parts])
        else:
            dict_host = np.concatenate(
                [np.asarray(p.dictionary_host) for p in parts])
        dictionary = None
    else:
        return None
    validity, list_offsets, list_validity, def_levels, rep_levels = \
        _concat_structure(parts)
    return Column(leaf=first.leaf, values=None, offsets=None,
                  validity=validity, list_offsets=list_offsets,
                  list_validity=list_validity,
                  num_slots=sum(p.num_slots for p in parts),
                  dictionary=dictionary, dictionary_host=dict_host,
                  dict_indices=indices,
                  def_levels=def_levels, rep_levels=rep_levels)


def _be_bytes_to_int(vals: np.ndarray) -> np.ndarray:
    """Big-endian two's-complement FLBA bytes → int64 (fits ≤ 8-byte decimals)."""
    n, width = vals.shape
    out = np.zeros(n, dtype=np.int64)
    for k in range(width):
        out = (out << 8) | vals[:, k].astype(np.int64)
    # sign-extend from width*8 bits
    bits = width * 8
    if bits < 64:
        sign = np.int64(1) << (bits - 1)
        out = (out ^ sign) - sign
    return out


def _spread(values: np.ndarray, validity: np.ndarray, fill=0) -> np.ndarray:
    """Scatter dense present values into slot-aligned array."""
    out = np.full(len(validity), fill, dtype=values.dtype)
    out[validity] = values
    return out


def _fixed_with_nulls(values: np.ndarray, validity, pa_type):
    import pyarrow as pa

    if validity is None:
        arr = pa.array(values)
    else:
        slot_vals = _spread(values, validity)
        arr = pa.array(slot_vals, mask=~np.asarray(validity))
    if arr.type != pa_type:
        arr = arr.cast(pa_type)
    return arr


def _decimal_with_nulls(ints: np.ndarray, validity, pa_type):
    import pyarrow as pa

    vals = ints if validity is None else _spread(ints, validity)
    lo = vals.astype(np.uint64)
    hi = (vals >> np.uint64(63) if vals.dtype == np.uint64 else (vals >> 63)).astype(np.int64)
    raw = np.empty((len(vals), 2), dtype=np.uint64)
    raw[:, 0] = lo
    raw[:, 1] = hi.astype(np.uint64)
    bufs = [None, pa.py_buffer(raw)]
    if validity is not None:
        bufs[0] = pa.py_buffer(np.packbits(validity, bitorder="little"))
    return pa.Array.from_buffers(pa_type, len(vals), bufs)


def _fsb_with_nulls(vals: np.ndarray, validity: np.ndarray, width: int):
    import pyarrow as pa

    out = np.zeros((len(validity), width), dtype=np.uint8)
    out[validity] = vals
    mask = pa.py_buffer(np.packbits(validity, bitorder="little"))
    return pa.Array.from_buffers(pa.binary(width), len(validity),
                                 [mask, pa.py_buffer(out)])


def _wide_offsets(offsets) -> bool:
    """True when chunk offsets address more bytes than int32 allows — the
    signal to take arrow's LARGE (64-bit-offset) layout.  Size-based, not
    dtype-based: small int64 offsets (e.g. dictionary values) stay on the
    standard layout."""
    from .reader import _OFFSET32_LIMIT

    offsets = np.asarray(offsets)
    return (offsets.dtype == np.int64 and len(offsets) > 1
            and int(offsets[-1]) > _OFFSET32_LIMIT)


def _ragged_with_nulls(values: np.ndarray, offsets: np.ndarray,
                       validity: np.ndarray, atype=None):
    import pyarrow as pa

    n = len(validity)
    lens = (offsets[1:] - offsets[:-1]).astype(np.int64)
    slot_lens = np.zeros(n, dtype=np.int64)
    slot_lens[validity] = lens
    slot_offs = np.concatenate([[0], np.cumsum(slot_lens)])
    wide = _wide_offsets(offsets)
    slot_offs = slot_offs.astype(np.int64 if wide else np.int32)
    mask = pa.py_buffer(np.packbits(validity, bitorder="little"))
    if atype is None:
        atype = pa.large_binary() if wide else pa.binary()
    return pa.Array.from_buffers(
        atype, n,
        [mask, pa.py_buffer(slot_offs),
         pa.py_buffer(np.ascontiguousarray(np.asarray(values).view(np.uint8)))])


# ---------------------------------------------------------------------------
# Schema node → arrow type (used by Table.to_arrow for struct/map assembly)
# ---------------------------------------------------------------------------


def arrow_type_of(node):
    """pyarrow DataType for a schema :class:`~parquet_tpu.schema.schema.Node`,
    consistent with the arrays :func:`_leaf_to_arrow` produces."""
    import pyarrow as pa

    from ..format.enums import FieldRepetitionType as Rep

    def base(n):
        if n.is_leaf:
            return _leaf_arrow_type(n)
        k = n.logical_kind
        if k == LogicalKind.LIST and len(n.children) == 1:
            mid = n.children[0]
            if mid.children is not None and len(mid.children) == 1:
                return pa.list_(arrow_type_of(mid.children[0]))  # 3-level
            return pa.list_(base(mid))  # 2-level legacy: repeated element
        if k == LogicalKind.MAP and len(n.children) == 1:
            kv = n.children[0]
            if kv.children is not None and len(kv.children) == 2:
                return pa.map_(base(kv.children[0]), arrow_type_of(kv.children[1]))
        return pa.struct([(c.name, arrow_type_of(c)) for c in n.children])

    t = base(node)
    if node.repetition == Rep.REPEATED:  # legacy repeated field = list
        t = pa.list_(t)
    return t


def _leaf_arrow_type(n):
    import pyarrow as pa

    k = n.logical_kind
    pt = n.physical_type
    p = n.logical_params
    if k == LogicalKind.UNKNOWN:
        return pa.null()
    if pt == Type.BOOLEAN:
        return pa.bool_()
    if pt == Type.BYTE_ARRAY:
        return (pa.string() if k in (LogicalKind.STRING, LogicalKind.ENUM,
                                     LogicalKind.JSON) else pa.binary())
    if pt == Type.FIXED_LEN_BYTE_ARRAY:
        if k == LogicalKind.FLOAT16:
            return pa.float16()
        if k == LogicalKind.DECIMAL:
            return pa.decimal128(p.get("precision", 38), p.get("scale", 0))
        return pa.binary(n.type_length)
    if pt == Type.INT96:
        return pa.timestamp("ns")
    if pt == Type.FLOAT:
        return pa.float32()
    if pt == Type.DOUBLE:
        return pa.float64()
    if k == LogicalKind.INT:
        bw = max(p.get("bit_width", 64), 8)
        return pa.from_numpy_dtype(
            np.dtype(f"{'i' if p.get('signed', True) else 'u'}{bw // 8}"))
    if k == LogicalKind.DATE:
        return pa.date32()
    if k == LogicalKind.DECIMAL:
        return pa.decimal128(p.get("precision", 38), p.get("scale", 0))
    tz = "UTC" if p.get("utc") else None
    if k == LogicalKind.TIMESTAMP_MILLIS:
        return pa.timestamp("ms", tz=tz)
    if k == LogicalKind.TIMESTAMP_MICROS:
        return pa.timestamp("us", tz=tz)
    if k == LogicalKind.TIMESTAMP_NANOS:
        return pa.timestamp("ns", tz=tz)
    if k == LogicalKind.TIME_MILLIS:
        return pa.time32("ms")
    if k == LogicalKind.TIME_MICROS:
        return pa.time64("us")
    if k == LogicalKind.TIME_NANOS:
        return pa.time64("ns")
    return pa.int32() if pt == Type.INT32 else pa.int64()
