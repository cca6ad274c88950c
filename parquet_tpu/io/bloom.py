"""Split-block bloom filters (SBBF) + xxhash64.

Reference parity: ``bloom.go — SplitBlockFilter(bitsPerValue, col)`` and the
AVX2 block kernels in ``bloom/block_amd64.s`` + vendored xxhash
(SURVEY.md §2.3).  The 8×32-bit block structure is a perfect vector fit — the
insert/check math below is fully numpy-vectorized for fixed-width values (the
same formulation runs on device lanes for on-device probes).

Format (Parquet spec bloom_filter.md):
- filter = ``z`` 32-byte blocks, each 8 little-endian uint32 lanes;
- ``block_idx = (high32(xxh64(plain_bytes)) * z) >> 32``;
- in-block: bit ``low32(low32 * SALT[i]) >> 27`` of lane ``i`` for 8 salts;
- stored as BloomFilterHeader (thrift) + raw bitset at
  ``ColumnMetaData.bloom_filter_offset``.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..format import metadata as md, thrift
from ..format.enums import Type
from ..schema.schema import Leaf

_SALT = np.array([
    0x47B6137B, 0x44974D91, 0x8824AD5B, 0xA2B7289D,
    0x705495C7, 0x2DF1424B, 0x9EFC4947, 0x5C6BFB31,
], dtype=np.uint64)

_SALT_U32 = _SALT.astype(np.uint32)


def _device_live() -> bool:
    """True when JAX's default backend is a TPU — the only backend whose
    probe kernel is compiled, not interpreted."""
    import jax

    return jax.default_backend() == "tpu"


_P1 = np.uint64(11400714785074694791)
_P2 = np.uint64(14029467366897019727)
_P3 = np.uint64(1609587929392839161)
_P4 = np.uint64(9650029242287828579)
_P5 = np.uint64(2870177450012600261)
_M = np.uint64(0xFFFFFFFFFFFFFFFF)


@np.errstate(over="ignore")
def _rotl(x, r: int):
    r = np.uint64(r)
    return ((x << r) | (x >> (np.uint64(64) - r))) & _M


@np.errstate(over="ignore")
def _avalanche(h):
    h = h ^ (h >> np.uint64(33))
    h = (h * _P2) & _M
    h = h ^ (h >> np.uint64(29))
    h = (h * _P3) & _M
    h = h ^ (h >> np.uint64(32))
    return h


@np.errstate(over="ignore")
def xxh64_u64(values: np.ndarray, seed: int = 0) -> np.ndarray:
    """xxhash64 of each 8-byte little-endian value (vectorized) — matches
    ``XXH64(&v, 8, seed)``, the hash parquet defines for INT64/DOUBLE."""
    v = values.astype(np.uint64)
    acc = (np.uint64(seed) + _P5 + np.uint64(8)) & _M
    k1 = (_rotl((v * _P2) & _M, 31) * _P1) & _M
    acc = acc ^ k1
    acc = ((_rotl(acc, 27) * _P1) + _P4) & _M
    return _avalanche(acc)


@np.errstate(over="ignore")
def xxh64_u32(values: np.ndarray, seed: int = 0) -> np.ndarray:
    """xxhash64 of each 4-byte little-endian value (vectorized)."""
    v = values.astype(np.uint64) & np.uint64(0xFFFFFFFF)
    acc = (np.uint64(seed) + _P5 + np.uint64(4)) & _M
    acc = acc ^ ((v * _P1) & _M)
    acc = ((_rotl(acc, 23) * _P2) + _P3) & _M
    return _avalanche(acc)


@np.errstate(over="ignore")
def xxh64_bytes(data: bytes, seed: int = 0) -> int:
    """Generic xxhash64 (scalar host reference; byte-array values.  C++ shim
    in native/ takes over on hot paths)."""
    n = len(data)
    p = 0
    if n >= 32:
        v1 = (np.uint64(seed) + _P1 + _P2) & _M
        v2 = (np.uint64(seed) + _P2) & _M
        v3 = np.uint64(seed)
        v4 = (np.uint64(seed) - _P1) & _M

        def rnd(acc, lane):
            return (_rotl((acc + ((lane * _P2) & _M)) & _M, 31) * _P1) & _M

        while p + 32 <= n:
            lanes = np.frombuffer(data[p : p + 32], dtype="<u8")
            v1 = rnd(v1, lanes[0])
            v2 = rnd(v2, lanes[1])
            v3 = rnd(v3, lanes[2])
            v4 = rnd(v4, lanes[3])
            p += 32
        h = (_rotl(v1, 1) + _rotl(v2, 7) + _rotl(v3, 12) + _rotl(v4, 18)) & _M

        def merge(h, v):
            h = h ^ ((_rotl((v * _P2) & _M, 31) * _P1) & _M)
            return ((h * _P1) + _P4) & _M

        h = merge(h, v1)
        h = merge(h, v2)
        h = merge(h, v3)
        h = merge(h, v4)
    else:
        h = (np.uint64(seed) + _P5) & _M
    h = (h + np.uint64(n)) & _M
    while p + 8 <= n:
        (lane,) = np.frombuffer(data[p : p + 8], dtype="<u8")
        h = h ^ ((_rotl((lane * _P2) & _M, 31) * _P1) & _M)
        h = ((_rotl(h, 27) * _P1) + _P4) & _M
        p += 8
    if p + 4 <= n:
        (lane,) = np.frombuffer(data[p : p + 4], dtype="<u4")
        h = h ^ ((np.uint64(lane) * _P1) & _M)
        h = ((_rotl(h, 23) * _P2) + _P3) & _M
        p += 4
    while p < n:
        h = h ^ ((np.uint64(data[p]) * _P5) & _M)
        h = (_rotl(h, 11) * _P1) & _M
        p += 1
    return int(_avalanche(np.uint64(h)))


class SplitBlockFilter:
    """The SBBF bitset: ``blocks`` is uint32[z, 8]."""

    def __init__(self, blocks: np.ndarray):
        self.blocks = blocks

    @classmethod
    def for_ndv(cls, ndv: int, bits_per_value: float = 10.0) -> "SplitBlockFilter":
        nbytes = int(ndv * bits_per_value / 8) + 32
        z = 1 << max(int(nbytes // 32).bit_length(), 0)
        return cls(np.zeros((max(z, 1), 8), dtype=np.uint32))

    @property
    def num_bytes(self) -> int:
        return self.blocks.size * 4

    # -- vectorized insert/check -------------------------------------------
    @np.errstate(over="ignore")
    def _masks(self, hashes: np.ndarray):
        z = np.uint64(self.blocks.shape[0])
        block_idx = ((hashes >> np.uint64(32)) * z) >> np.uint64(32)
        low = hashes & np.uint64(0xFFFFFFFF)
        bit = ((low[:, None] * _SALT[None, :]) & np.uint64(0xFFFFFFFF)) >> np.uint64(27)
        masks = np.uint32(1) << bit.astype(np.uint32)
        return block_idx.astype(np.int64), masks

    def insert_hashes(self, hashes: np.ndarray) -> None:
        block_idx, masks = self._masks(hashes)
        np.bitwise_or.at(self.blocks, block_idx, masks)
        self._blocks_dev = None  # device mirror is stale after mutation

    def check_hashes(self, hashes: np.ndarray) -> np.ndarray:
        block_idx, masks = self._masks(hashes)
        got = self.blocks[block_idx]
        return ((got & masks) == masks).all(axis=1)

    def check(self, value, leaf: Leaf) -> bool:
        """Reference parity: ``ColumnChunk.BloomFilter().Check(value)``."""
        return bool(self.check_hashes(hash_values_single(value, leaf))[0])

    # Design note (SURVEY.md §2.3 bloom row): planner probes are host work —
    # a probe is metadata-scale and the filter lives in host memory next to
    # the footer, so the numpy probe is the production default.  The device
    # probe below exists for the batched case (large IN-lists / semi-join
    # pre-filters, thousands of probes per filter), where one H2D of the
    # filter + one fused gather/test dispatch beats k host probes.
    _DEVICE_PROBE_MIN = 32_768

    def check_hashes_device(self, hashes: np.ndarray):
        """Batched probe on the accelerator: the high hash bits pick blocks
        (computed host-side, O(k) metadata work), XLA gathers the selected
        blocks from the HBM-resident filter, and the Pallas kernel (jnp twin
        off the TPU) tests the salted bits.  Returns a bool
        ``jax.Array`` of length ``len(hashes)``."""
        import jax
        import jax.numpy as jnp

        z = np.uint64(self.blocks.shape[0])
        block_idx = (((hashes >> np.uint64(32)) * z) >> np.uint64(32)) \
            .astype(np.int32)
        low = (hashes & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        dev_blocks = getattr(self, "_blocks_dev", None)
        if dev_blocks is None:
            dev_blocks = self._blocks_dev = jax.device_put(self.blocks)
        gathered = jnp.take(dev_blocks, jnp.asarray(block_idx), axis=0)
        low_dev = jnp.asarray(low)
        if jax.default_backend() == "tpu":
            from ..ops import pallas_kernels as pk

            return pk.bloom_check_blocks(gathered, low_dev)
        bit = ((low_dev[:, None] * jnp.asarray(_SALT_U32)[None, :])
               >> jnp.uint32(27)) & jnp.uint32(31)
        masks = jnp.uint32(1) << bit
        return ((gathered & masks) == masks).all(axis=1)

    def check_hashes_batch(self, hashes: np.ndarray,
                           prefer_device: Optional[bool] = None) -> np.ndarray:
        """Probe many hashes, routing large batches to the accelerator when
        one is live (see design note above). Returns host bool numpy."""
        use_dev = prefer_device
        if use_dev is None:
            use_dev = len(hashes) >= self._DEVICE_PROBE_MIN and _device_live()
        if use_dev:
            return np.asarray(self.check_hashes_device(hashes))
        return self.check_hashes(hashes)

    # -- serialization ------------------------------------------------------
    def to_bytes(self) -> bytes:
        header = md.BloomFilterHeader(
            numBytes=self.num_bytes,
            algorithm=md.BloomFilterAlgorithm(BLOCK=md.SplitBlockAlgorithm()),
            hash=md.BloomFilterHash(XXHASH=md.XxHash()),
            compression=md.BloomFilterCompression(UNCOMPRESSED=md.BloomUncompressed()))
        return thrift.serialize(header) + self.blocks.astype("<u4").tobytes()

    @classmethod
    def from_bytes(cls, raw: bytes, pos: int = 0) -> "SplitBlockFilter":
        header, pos = thrift.deserialize(md.BloomFilterHeader, raw, pos)
        n = header.numBytes
        blocks = np.frombuffer(raw[pos : pos + n], dtype="<u4").reshape(-1, 8).copy()
        return cls(blocks)


def hash_values(leaf: Leaf, values, offsets=None) -> np.ndarray:
    """Hash a column's values per the parquet bloom spec (xxh64 of the
    PLAIN-encoded bytes of each value)."""
    t = leaf.physical_type
    vals = np.asarray(values)
    if t in (Type.INT64, Type.DOUBLE):
        return xxh64_u64(vals.view(np.uint64))
    if t in (Type.INT32, Type.FLOAT):
        return xxh64_u32(vals.view(np.uint32))
    if t == Type.BYTE_ARRAY:
        from .. import native as _native

        offs = np.asarray(offsets, dtype=np.int64)
        nat = _native.xxh64_batch(vals, offs)
        if nat is not None:
            return nat
        b = vals.tobytes()
        return np.array([xxh64_bytes(b[offs[i]: offs[i + 1]])
                         for i in range(len(offs) - 1)], dtype=np.uint64)
    if t == Type.FIXED_LEN_BYTE_ARRAY:
        w = leaf.type_length
        flat = vals.reshape(-1, w)
        return np.array([xxh64_bytes(flat[i].tobytes()) for i in range(len(flat))],
                        dtype=np.uint64)
    raise ValueError(f"unsupported bloom type {t}")


def hash_probe_values(leaf: Leaf, values) -> np.ndarray:
    """Vectorized probe hashing for an IN-list: order-domain probe values →
    uint64 xxh64 per value (writer-side PLAIN byte encoding), ready for
    :meth:`SplitBlockFilter.check_hashes_batch`."""
    from ..algebra.compare import int_to_be_bytes, is_unsigned, normalize
    from ..schema.types import LogicalKind

    t = leaf.physical_type
    vals = [normalize(leaf, v) for v in values]
    if t == Type.INT64:
        dt = np.uint64 if is_unsigned(leaf) else np.int64
        return xxh64_u64(np.array(vals, dtype=dt).view(np.uint64))
    if t == Type.DOUBLE:
        return xxh64_u64(np.array(vals, dtype=np.float64).view(np.uint64))
    if t == Type.INT32:
        dt = np.uint32 if is_unsigned(leaf) else np.int32
        return xxh64_u32(np.array(vals, dtype=dt).view(np.uint32))
    if t == Type.FLOAT:
        return xxh64_u32(np.array(vals, dtype=np.float32).view(np.uint32))
    if leaf.logical_kind == LogicalKind.DECIMAL:
        width = leaf.type_length if t == Type.FIXED_LEN_BYTE_ARRAY else None
        vals = [int_to_be_bytes(v, width) if isinstance(v, int) else v
                for v in vals]
    bs = [bytes(v) for v in vals]
    if t == Type.FIXED_LEN_BYTE_ARRAY:
        # hash_values reshapes to the column width, which would raise for a
        # probe whose byte length differs; hash each probe's raw bytes
        # instead — a wrong-width probe can never equal a stored value, and
        # its raw-byte hash yields at worst a bloom false positive.
        return np.array([xxh64_bytes(b) for b in bs], dtype=np.uint64)
    offs = np.zeros(len(bs) + 1, np.int64)
    np.cumsum([len(b) for b in bs], out=offs[1:])
    return hash_values(leaf, np.frombuffer(b"".join(bs), np.uint8), offs)


def probe_hashes(leaf: Leaf, values) -> Optional[np.ndarray]:
    """Batch-hash an already-normalized probe list for
    :meth:`SplitBlockFilter.check_hashes_batch`, with the conservative
    guard of :func:`bloom_may_contain`: probes whose type has no bloom
    encoding (or that fail to encode) return ``None`` — "inconclusive,
    skip the bloom stage" — instead of raising.  The batched-lookup path
    (io/lookup.py) hashes its whole key set ONCE through this and probes
    every chunk's filter with the same array."""
    try:
        return hash_probe_values(leaf, values)
    except (TypeError, ValueError, OverflowError):
        return None


def hash_values_single(value, leaf: Leaf) -> np.ndarray:
    """Hash one probe value (the batch-of-one case of
    :func:`hash_probe_values`, which owns the writer-side PLAIN probe
    encoding rules)."""
    return hash_probe_values(leaf, [value])


# ---------------------------------------------------------------------------
# writer / reader integration
# ---------------------------------------------------------------------------


def build_split_block_filter(leaf: Leaf, data, dict_values, dict_offsets,
                             bits_per_value: int) -> bytes:
    """Writer side: hash the distinct values (dictionary when built)."""
    if dict_values is not None:
        values, offsets = dict_values, dict_offsets
        ndv = (len(dict_offsets) - 1) if dict_offsets is not None else len(dict_values)
    else:
        values, offsets = data.values, data.offsets
        ndv = (len(offsets) - 1) if offsets is not None else len(np.asarray(values))
    filt = SplitBlockFilter.for_ndv(max(ndv, 8), bits_per_value)
    filt.insert_hashes(hash_values(leaf, values, offsets))
    return filt.to_bytes()


def bloom_may_contain(bf: SplitBlockFilter, value, leaf: Leaf) -> bool:
    """Conservative single-probe consult: False only when the filter
    PROVES the value absent.  Probes not encodable in the column's domain
    (wrong type, out of range) are inconclusive and answer True — the one
    guard shared by row-group pruning (io/search.py) and the scan
    planner's bloom stage (io/planner.py)."""
    try:
        return bool(bf.check(value, leaf))
    except (TypeError, ValueError, OverflowError):
        return True


def read_bloom_filter(reader) -> Optional[SplitBlockFilter]:
    """Reader side: ``ColumnChunk.BloomFilter()`` analog (lazy, like the
    reference's SkipBloomFilters default here — loaded on first call)."""
    meta = reader.meta
    off = meta.bloom_filter_offset
    if off is None:
        return None
    length = meta.bloom_filter_length
    if length is None:
        probe = reader.file.source.pread(off, 64)
        header, hend = thrift.deserialize(md.BloomFilterHeader, probe)
        length = hend + header.numBytes
    raw = reader.file.source.pread(off, length)
    return SplitBlockFilter.from_bytes(raw)
