"""Device (jnp/XLA) kernels vs the numpy oracle — the purego-equivalence
pattern of SURVEY.md §4(4), run on the CPU backend (same XLA semantics as TPU;
the driver's bench exercises the real chip).

Note the 32-bit-lane discipline (see ops/device.py): 64-bit columns come back
as (n,2) uint32 pairs and are viewed as int64/float64 on host.
"""

import numpy as np
import pytest

from parquet_tpu.ops import device, ref
from parquet_tpu.parallel.device_reader import _RunTable


def _pad(b) -> np.ndarray:
    return device.pad_to_bucket(np.frombuffer(b, np.uint8) if isinstance(b, bytes) else b)


#: value counts of the PLAIN kernels: empty (an all-null chunk), one, an
#: odd count, a full 2^20-row group and lineitem SF1's last row group
PLAIN_NS = [0, 1, 777, 1 << 20, 758_335]


def _plain_values(dtype, n, rng) -> np.ndarray:
    """``n`` values of ``dtype`` from random bits; floats carry NaN payloads
    (quiet, signalling, signed) and -0.0, which must survive bit for bit."""
    bits = {4: np.uint32, 8: np.uint64}[np.dtype(dtype).itemsize]
    v = rng.integers(0, np.iinfo(bits).max, size=n, dtype=bits,
                     endpoint=True)
    if np.dtype(dtype).kind == "f":
        special = ([0x7FF8000000000001, 0x7FF0000000000001,
                    0xFFF0000000000123, 0x8000000000000000]
                   if bits is np.uint64 else
                   [0x7FC00001, 0x7F800001, 0xFF800123, 0x80000000])
        k = min(n, len(special))
        v[:k] = np.array(special[:k], bits)
    return v.view(dtype)


@pytest.mark.parametrize("n", PLAIN_NS)
@pytest.mark.parametrize("dtype", ["int32", "float32", "uint32"])
def test_bitcast_fixed32(dtype, n, rng):
    """PLAIN 4-byte values from their staged words: a bitcast, bit for bit
    the numpy view of the same bytes."""
    v = _plain_values(dtype, n, rng)
    out = np.asarray(device.bitcast_fixed32(v.view(np.uint32), n, dtype))
    assert out.dtype == np.dtype(dtype) and out.shape == (n,)
    np.testing.assert_array_equal(out.view(np.uint32), v.view(np.uint32))


@pytest.mark.parametrize("n", PLAIN_NS)
@pytest.mark.parametrize("dtype", ["int64", "float64"])
def test_fixed64_pairs(dtype, n, rng):
    """PLAIN 8-byte values from their staged words: ``(n, 2)`` lo/hi pairs,
    bit for bit the numpy view of the same bytes."""
    v = _plain_values(dtype, n, rng)
    words = v.view(np.uint32)
    out = device.fixed64_pairs(words, n)
    assert out.shape == (n, 2) and out.dtype == np.uint32
    np.testing.assert_array_equal(np.asarray(out), words.reshape(n, 2))
    np.testing.assert_array_equal(
        device.pairs_to_host(out, dtype).view(np.uint64), v.view(np.uint64))


def test_fixed64_pairs_runs_a_program():
    """``fixed64_pairs`` hands back a new buffer made by a program of its
    own: returning (or donating) its input would let JAX forward the call
    without running ``jit_fixed64_pairs``, and the kernel's roofline would
    read nothing."""
    import jax

    n = 777
    words = jax.numpy.asarray(np.arange(2 * n, dtype=np.uint32))
    out = device.fixed64_pairs(words, n)
    assert out.unsafe_buffer_pointer() != words.unsafe_buffer_pointer()
    words.delete()  # the result holds none of the input
    np.testing.assert_array_equal(np.asarray(out),
                                  np.arange(2 * n).reshape(n, 2))
    inner = jax.make_jaxpr(lambda w: device.fixed64_pairs(w, n))(
        jax.ShapeDtypeStruct((2 * n,), np.uint32)).eqns[0].params["jaxpr"]
    assert inner.eqns and inner.jaxpr.outvars[0] not in inner.jaxpr.invars


def test_unpack_bools(rng):
    b = rng.random(1003) < 0.3
    enc = ref.encode_plain(b, ref.Type.BOOLEAN)
    out = device.unpack_bools(_pad(enc), 1003)
    np.testing.assert_array_equal(np.asarray(out), b)


@pytest.mark.parametrize("w", [1, 2, 3, 5, 7, 8, 12, 16, 17, 24, 31, 32])
def test_unpack_bits_32(w, rng):
    n = 1013
    hi = (1 << w) - 1
    v = rng.integers(0, hi, size=n, dtype=np.uint64, endpoint=True)
    packed = ref.pack_bits(v, w)
    out = device.unpack_bits(_pad(packed), n, w)
    np.testing.assert_array_equal(np.asarray(out), v.astype(np.uint32))


@pytest.mark.parametrize("w", [33, 40, 47, 57, 63, 64])
def test_unpack_bits_64(w, rng):
    n = 1013
    hi = (1 << w) - 1
    v = rng.integers(0, min(hi, 2**63 - 1), size=n, dtype=np.uint64, endpoint=True) & np.uint64(hi)
    packed = ref.pack_bits(v, w)
    out = np.asarray(device.unpack_bits(_pad(packed), n, w))
    got = out[:, 0].astype(np.uint64) | (out[:, 1].astype(np.uint64) << np.uint64(32))
    np.testing.assert_array_equal(got, v)


def _run_table(style, w, rng):
    """``(values, stream, ends, kinds, payloads, bit_offsets)`` of one hybrid
    stream of ``w``-bit values, its run table as the device reader stages
    it; ``values`` is None where the caller expands past the last run."""
    n, hi = 3777, 1 << w
    if style in ("runs", "rand", "mixed", "zero_len", "padded"):
        if style == "runs":
            v = np.repeat(rng.integers(0, hi, size=50),
                          rng.integers(1, 200, size=50))[:n]
        elif style == "rand":
            v = rng.integers(0, hi, size=n)
        else:
            v = np.where(rng.random(n) < 0.5, 1, rng.integers(0, hi, size=n))
        enc = np.frombuffer(ref.encode_rle(v, w), np.uint8)
        kinds, counts, payloads, offsets, _ = ref.scan_rle_runs(enc, len(v), w)
        ends, offsets = np.cumsum(counts), offsets * 8
        if style == "zero_len":  # empty runs of both kinds, first and last too
            at = np.sort(rng.integers(0, len(kinds) + 1, size=12))
            at[0], at[-1] = 0, len(kinds)
            ends = np.insert(ends, at, np.concatenate([[0], ends])[at])
            kinds = np.insert(kinds, at, rng.integers(0, 2, size=12))
            payloads = np.insert(payloads, at, rng.integers(0, hi, size=12))
            offsets = np.insert(offsets, at,
                                rng.integers(0, 8 * len(enc), size=12))
        if style == "padded":  # the last run reads on into zero padding
            v, enc = None, np.concatenate([enc, np.zeros(256, np.uint8)])
        return v, enc, ends, kinds, payloads, offsets
    if style == "all_rle":
        counts = rng.integers(1, 200, size=40)
        payloads = rng.integers(0, hi, size=40)
        zeros = np.zeros(40, np.int64)
        return (np.repeat(payloads, counts), np.zeros(8, np.uint8),
                np.cumsum(counts), zeros.astype(np.uint8), payloads, zeros)
    if style == "single":  # the all-present def-level page: one RLE run
        p = int(rng.integers(0, hi))
        return (np.full(n, p), np.zeros(8, np.uint8), np.array([n]),
                np.zeros(1, np.uint8), np.array([p]), np.zeros(1, np.int64))
    v = rng.integers(0, hi, size=n)
    enc = np.frombuffer(ref.pack_bits(v, w), np.uint8)
    if style == "single_packed":  # a PLAIN BOOLEAN page: one bit-packed run
        ends = np.array([n])
    else:  # all_packed: groups of 8 values, the last run cut short
        ends = np.cumsum(8 * rng.integers(1, 64, size=n // 8))
        ends = np.append(ends[ends < n], n)
    starts = np.concatenate([[0], ends[:-1]])
    return (v, enc, ends, np.ones(len(ends), np.uint8),
            np.zeros(len(ends), np.int64), starts * w)


@pytest.mark.parametrize("w", [1, 3, 8, 12, 20, 31, 32])
@pytest.mark.parametrize("style", ["runs", "rand", "mixed", "zero_len",
                                   "padded", "all_rle", "all_packed",
                                   "single", "single_packed"])
def test_rle_expand(w, style, rng):
    """Bit for bit the host twin's expansion (and the encoded values); a
    padded ``n`` past the last end extends the last run."""
    v, enc, ends, kinds, payloads, offsets = _run_table(style, w, rng)
    n = int(ends[-1]) + (37 if v is None else 0)
    widths = np.full(len(kinds), w, dtype=np.int32)
    out = np.asarray(device.rle_expand(
        _pad(enc), n, ends.astype(np.int64), kinds,
        payloads.astype(np.uint32).view(np.int32), offsets, widths))
    if v is not None:
        np.testing.assert_array_equal(out.view(np.uint32), v.astype(np.uint32))
    twin_ends = np.asarray(ends, np.int64).copy()
    twin_ends[-1] = n  # the host twin stops at the last end
    twin = _RunTable(ends=[twin_ends], kinds=[kinds], payloads=[payloads],
                     bit_offsets=[offsets], widths=[widths], total=n)
    np.testing.assert_array_equal(out, twin.expand_host(enc, n))


def test_rle_expand_mixed_widths(rng):
    """Two pages with different bit widths decoded in ONE device call."""
    v1 = rng.integers(0, 1 << 4, size=1000)
    v2 = rng.integers(0, 1 << 9, size=1500)
    e1, e2 = ref.encode_rle(v1, 4), ref.encode_rle(v2, 9)
    buf = e1 + e2
    k1, c1, p1, o1, _ = ref.scan_rle_runs(np.frombuffer(e1, np.uint8), 1000, 4)
    k2, c2, p2, o2, _ = ref.scan_rle_runs(np.frombuffer(e2, np.uint8), 1500, 9)
    kinds = np.concatenate([k1, k2])
    ends = np.cumsum(np.concatenate([c1, c2])).astype(np.int64)
    payloads = np.concatenate([p1, p2]).astype(np.int32)
    offsets = np.concatenate([o1 * 8, (o2 + len(e1)) * 8])
    widths = np.concatenate([np.full(len(k1), 4), np.full(len(k2), 9)]).astype(np.int32)
    out = device.rle_expand(_pad(buf), 2500, ends, kinds, payloads, offsets, widths)
    np.testing.assert_array_equal(np.asarray(out), np.concatenate([v1, v2]))
    twin = _RunTable(ends=[ends], kinds=[kinds], payloads=[payloads],
                     bit_offsets=[offsets], widths=[widths], total=2500)
    np.testing.assert_array_equal(
        np.asarray(out), twin.expand_host(np.frombuffer(buf, np.uint8)))


@pytest.mark.parametrize("n", [1, 2, 33, 128, 129, 1000])
@pytest.mark.parametrize("kind", ["rand", "sorted", "const"])
def test_delta_decode32(n, kind, rng):
    if kind == "rand":
        v = rng.integers(-(2**31), 2**31, size=n).astype(np.int32)
    elif kind == "sorted":
        v = np.sort(rng.integers(0, 2**30, size=n)).astype(np.int32)
    else:
        v = np.full(n, 42, dtype=np.int32)
    enc = ref.encode_delta_binary_packed(v.astype(np.int64))
    buf = np.frombuffer(enc, np.uint8)
    first, total, vpm, offs, widths, mins, _ = device.delta_prescan(buf)
    out = device.delta_decode32(_pad(enc), n, np.int64(first), offs, widths, mins, vpm)
    np.testing.assert_array_equal(np.asarray(out)[:n], v)


@pytest.mark.parametrize("n", [1, 2, 33, 128, 129, 1000])
@pytest.mark.parametrize("kind", ["rand64", "sorted", "const"])
def test_delta_decode64(n, kind, rng):
    if kind == "rand64":
        v = rng.integers(-(2**62), 2**62, size=n)
    elif kind == "sorted":
        v = np.sort(rng.integers(0, 10**12, size=n))
    else:
        v = np.full(n, -7, dtype=np.int64)
    enc = ref.encode_delta_binary_packed(v)
    buf = np.frombuffer(enc, np.uint8)
    first, total, vpm, offs, widths, mins, _ = device.delta_prescan(buf)
    out = device.delta_decode64(_pad(enc), n, np.int64(first), offs, widths, mins, vpm)
    np.testing.assert_array_equal(device.pairs_to_host(out, np.int64)[:n], v)


def test_byte_stream_split_f32(rng):
    f = rng.random(777).astype(np.float32)
    enc = ref.encode_byte_stream_split(np.frombuffer(f.tobytes(), np.uint8), 777, 4)
    out = device.byte_stream_split(_pad(enc), 777, 4, out_dtype="float32")
    np.testing.assert_array_equal(np.asarray(out), f)


def test_byte_stream_split_f64(rng):
    f = rng.random(777)
    enc = ref.encode_byte_stream_split(np.frombuffer(f.tobytes(), np.uint8), 777, 8)
    out = device.byte_stream_split(_pad(enc), 777, 8, out_dtype="float64")
    np.testing.assert_array_equal(device.pairs_to_host(out, np.float64), f)


def test_dict_gather(rng):
    d = rng.integers(0, 10**9, size=1000).astype(np.int64)
    pairs = np.ascontiguousarray(np.frombuffer(d.tobytes(), np.uint32).reshape(-1, 2))
    idx = rng.integers(0, 1000, size=5000).astype(np.int32)
    out = device.dict_gather(pairs, idx)
    np.testing.assert_array_equal(device.pairs_to_host(out, np.int64), d[idx])


def test_scatter_valid(rng):
    validity = rng.random(1000) < 0.7
    vals = rng.integers(0, 100, size=int(validity.sum())).astype(np.int32)
    out = np.asarray(device.scatter_valid(vals, validity))
    expect = np.zeros(1000, dtype=np.int32)
    expect[validity] = vals
    np.testing.assert_array_equal(out, expect)


class TestAssembleNested:
    """dev.assemble_nested == host levels_ops.assemble, any depth."""

    def _compare(self, t, col_name):
        import io

        import pyarrow.parquet as pq

        from parquet_tpu.io.reader import ParquetFile
        from parquet_tpu.ops import device as dev, levels as levels_ops

        b = io.BytesIO()
        pq.write_table(t, b, compression="none", use_dictionary=False)
        pf = ParquetFile(b.getvalue())
        col = pf.read().columns[next(
            p for p in pf.read().columns if p.startswith(col_name))]
        leaf = col.leaf
        d = np.asarray(col.def_levels)
        r = np.asarray(col.rep_levels)
        infos = levels_ops.repeated_ancestors(leaf)
        want = levels_ops.assemble(d, r, leaf)
        import jax.numpy as jnp

        got_offs, got_val, got_leaf = dev.assemble_nested(
            jnp.asarray(d), jnp.asarray(r), infos, leaf.max_definition_level)
        assert len(got_offs) == len(want.list_offsets)
        for go, wo in zip(got_offs, want.list_offsets):
            np.testing.assert_array_equal(np.asarray(go),
                                          np.asarray(wo).astype(np.int32))
        for gv, wv in zip(got_val, want.list_validity):
            if wv is None:
                assert bool(np.asarray(gv).all())
            else:
                np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
        if want.validity is None:
            assert got_leaf is None or bool(np.asarray(got_leaf).all())
        else:
            np.testing.assert_array_equal(np.asarray(got_leaf),
                                          np.asarray(want.validity))

    def test_config4_shape(self, rng):
        import pyarrow as pa

        n = 4000
        lens = rng.integers(0, 8, n)
        lens[rng.random(n) < 0.05] = 0
        offs = np.zeros(n + 1, np.int32)
        np.cumsum(lens, out=offs[1:])
        total = int(offs[-1])
        base = 1_700_000_000 + np.cumsum(rng.integers(0, 1000, max(total, 1)))
        arr = pa.ListArray.from_arrays(pa.array(offs),
                                       pa.array(base[:total].astype(np.int64)))
        self._compare(pa.table({"ts": arr}), "ts")

    def test_depth2_nullable(self, rng):
        import pyarrow as pa

        n = 2500
        rows = []
        for _ in range(n):
            if rng.random() < 0.08:
                rows.append(None)
            else:
                rows.append([None if rng.random() < 0.12 else
                             [int(v) for v in rng.integers(0, 99,
                                                           int(rng.integers(0, 3)))]
                             for _ in range(int(rng.integers(0, 4)))])
        t = pa.table({"vv": pa.array(rows, pa.list_(pa.list_(pa.int64())))})
        self._compare(t, "vv")

    def test_device_route_end_to_end_depth2(self, rng):
        """Full device decode equals the host read for a depth-2 column:
        a list chain assembles on device (VERDICT r3 task 6 'done =' bar)."""
        import io

        import pyarrow as pa
        import pyarrow.parquet as pq

        from parquet_tpu.io.reader import ParquetFile
        from parquet_tpu.parallel import device_reader as dr

        n = 3000
        rows = [[list(map(int, rng.integers(0, 50, int(rng.integers(0, 3)))))
                 for _ in range(int(rng.integers(0, 4)))]
                if rng.random() > 0.06 else None for _ in range(n)]
        t = pa.table({"vv": pa.array(rows, pa.list_(pa.list_(pa.int64())))})
        b = io.BytesIO()
        pq.write_table(t, b, compression="none", use_dictionary=False)
        ch = ParquetFile(b.getvalue()).row_group(0).column(0)
        col = dr.decode_chunk_device(ch, fallback=False)
        assert len(col.list_offsets) == 2  # device-assembled, both levels
        import jax

        assert isinstance(col.list_offsets[0], jax.Array)
        ch2 = ParquetFile(b.getvalue()).row_group(0).column(0)
        from parquet_tpu.io.reader import decode_chunk_host

        host = decode_chunk_host(ch2)
        for lv in range(2):
            np.testing.assert_array_equal(
                np.asarray(col.list_offsets[lv]).astype(np.int64),
                np.asarray(host.list_offsets[lv]).astype(np.int64))
        got_vals = np.asarray(col.values)
        if got_vals.ndim == 2 and got_vals.shape[-1] == 2:
            got_vals = np.ascontiguousarray(got_vals).view(np.int64).reshape(-1)
        np.testing.assert_array_equal(got_vals, np.asarray(host.values))


def test_assemble_nested_depth3(rng):
    """Device assembler equality at depth 3 (the 'ANY depth' claim)."""
    import io

    import pyarrow as pa
    import pyarrow.parquet as pq

    from parquet_tpu.io.reader import ParquetFile
    from parquet_tpu.ops import device as dev, levels as levels_ops
    import jax.numpy as jnp

    n = 1200
    rows = [[[ [int(v) for v in rng.integers(0, 9, int(rng.integers(0, 3)))]
               for _ in range(int(rng.integers(0, 2)))]
             for _ in range(int(rng.integers(0, 3)))]
            if rng.random() > 0.06 else None for _ in range(n)]
    t = pa.table({"v": pa.array(rows, pa.list_(pa.list_(pa.list_(pa.int64()))))})
    b = io.BytesIO()
    pq.write_table(t, b, compression="none", use_dictionary=False)
    tab = ParquetFile(b.getvalue()).read()
    col = next(iter(tab.columns.values()))
    leaf = col.leaf
    d, r = np.asarray(col.def_levels), np.asarray(col.rep_levels)
    infos = levels_ops.repeated_ancestors(leaf)
    assert len(infos) == 3
    want = levels_ops.assemble(d, r, leaf)
    got_offs, got_val, got_leaf = dev.assemble_nested(
        jnp.asarray(d), jnp.asarray(r), infos, leaf.max_definition_level)
    for go, wo in zip(got_offs, want.list_offsets):
        np.testing.assert_array_equal(np.asarray(go),
                                      np.asarray(wo).astype(np.int32))
    for gv, wv in zip(got_val, want.list_validity):
        if wv is None:
            assert bool(np.asarray(gv).all())
        else:
            np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    if want.validity is not None:
        np.testing.assert_array_equal(np.asarray(got_leaf),
                                      np.asarray(want.validity))


@pytest.mark.parametrize("n", [0, 1, 1024, 1025, 70_001])
@pytest.mark.parametrize("dtype", [np.int32, np.uint32, np.int64, np.bool_])
def test_blocked_cumsum_matches_jnp(n, dtype, rng):
    """The blocked prefix sum (fast to compile for the TPU) has
    ``jnp.cumsum``'s values and dtype, wrap-around included."""
    import jax.numpy as jnp

    from parquet_tpu.ops import device as dev

    x = (rng.random(n) < 0.5 if dtype is np.bool_
         else rng.integers(0, 2**31, n).astype(dtype))
    got = dev.cumsum(jnp.asarray(x))
    want = jnp.cumsum(jnp.asarray(x))
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
