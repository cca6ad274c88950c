"""C++ shim vs numpy oracle equivalence (the purego dual-run of SURVEY.md §4.4)."""

import numpy as np
import pytest

from parquet_tpu import native
from parquet_tpu.format.enums import Type
from parquet_tpu.ops import ref


@pytest.fixture(scope="module")
def lib():
    lib = native.get_lib()
    if lib is None:
        pytest.skip("native shim unavailable (no g++?)")
    return lib


def test_plain_byte_array_matches_oracle(lib, rng):
    parts = [(f"value-{i % 97}" * int(rng.integers(0, 4))).encode() for i in range(500)]
    data = np.frombuffer(b"".join(parts), np.uint8)
    offs = np.zeros(501, np.int64)
    np.cumsum([len(p) for p in parts], out=offs[1:])
    enc = np.frombuffer(ref.encode_plain(data, Type.BYTE_ARRAY, offsets=offs), np.uint8)
    vals, offsets = native.plain_byte_array(enc, 500)
    np.testing.assert_array_equal(offsets, offs)
    assert vals.tobytes() == data.tobytes()


def test_scan_rle_runs_matches_oracle(lib, rng):
    for w in [1, 5, 12, 20]:
        v = np.repeat(rng.integers(0, 1 << w, size=60), rng.integers(1, 50, size=60))
        enc = np.frombuffer(ref.encode_rle(v, w), np.uint8)
        nat = native.scan_rle_runs(enc, len(v), w)
        assert nat is not None
        # python fallback explicitly
        import os
        k2 = ref.scan_rle_runs.__wrapped__ if hasattr(ref.scan_rle_runs, "__wrapped__") else None
        dec = ref.decode_rle(enc, len(v), w)
        np.testing.assert_array_equal(dec, v)


def test_xxh64_matches(lib, rng):
    for payload in [b"", b"a", b"abc", b"abcd", bytes(range(100)), bytes(1000)]:
        from parquet_tpu.io import bloom
        assert native.xxh64(payload) == bloom.xxh64_bytes(payload)


def test_xxh64_batch(lib, rng):
    parts = [f"k{i}".encode() * (i % 5) for i in range(200)]
    data = np.frombuffer(b"".join(parts), np.uint8)
    offs = np.zeros(201, np.int64)
    np.cumsum([len(p) for p in parts], out=offs[1:])
    out = native.xxh64_batch(data, offs)
    from parquet_tpu.io import bloom
    for i in [0, 1, 50, 199]:
        assert int(out[i]) == bloom.xxh64_bytes(parts[i])


def test_dict_build(lib, rng):
    parts = [f"cat-{i % 13}".encode() for i in range(1000)]
    data = np.frombuffer(b"".join(parts), np.uint8)
    offs = np.zeros(1001, np.int64)
    np.cumsum([len(p) for p in parts], out=offs[1:])
    indices, first = native.dict_build_ba(data, offs, 600)
    assert len(first) == 13
    # indices reconstruct the input
    uniq = [parts[r] for r in first]
    assert [uniq[i] for i in indices] == parts
    # overflow signal
    uparts = [f"u{i}".encode() for i in range(100)]
    ud = np.frombuffer(b"".join(uparts), np.uint8)
    uo = np.zeros(101, np.int64)
    np.cumsum([len(p) for p in uparts], out=uo[1:])
    assert native.dict_build_ba(ud, uo, 10) == "overflow"


def test_delta_byte_array_native_path(lib, rng):
    parts = sorted((f"prefix-{i // 10:04d}-{i % 10}").encode() for i in range(500))
    data = np.frombuffer(b"".join(parts), np.uint8)
    offs = np.zeros(501, np.int64)
    np.cumsum([len(p) for p in parts], out=offs[1:])
    enc = ref.encode_delta_byte_array(data, offs)
    v, o, _ = ref.decode_delta_byte_array(np.frombuffer(enc, np.uint8))
    assert v.tobytes() == data.tobytes()
    np.testing.assert_array_equal(o, offs)


def test_assemble_list_runs_matches_assemble_oracle(lib, rng):
    """Fused run-table list assembly == per-slot expand + assemble, across
    random level streams (incl. all-RLE, all-bit-packed, and mixed)."""
    from parquet_tpu.ops import levels as levels_ops
    from parquet_tpu.schema import schema as sch
    from parquet_tpu.format.enums import FieldRepetitionType as Rep

    elem = sch.leaf("element", Type.INT64, Rep.OPTIONAL)
    node = sch.list_of("xs", elem, Rep.OPTIONAL)
    schema = sch.message("M", [node])
    leaf = schema.leaves[0]
    max_def, dk = leaf.max_definition_level, None
    infos = levels_ops.repeated_ancestors(leaf)
    dk = infos[0].def_level

    for trial in range(40):
        n = int(rng.integers(1, 6000))
        # def in [0, max_def]; rep in {0,1}; rep[0] must be 0
        style = trial % 4
        if style == 0:  # long constant spans -> RLE-heavy
            d = np.repeat(rng.integers(0, max_def + 1, 20),
                          rng.integers(1, 400, 20)).astype(np.int64)[:n]
            if len(d) < n:
                d = np.pad(d, (0, n - len(d)), constant_values=max_def)
            r = np.zeros(n, np.int64)
        elif style == 1:  # alternating -> bit-packed heavy
            d = rng.integers(0, max_def + 1, n).astype(np.int64)
            r = rng.integers(0, 2, n).astype(np.int64)
        else:  # realistic lists: mostly-present elements, some null/empty
            d = np.full(n, max_def, np.int64)
            d[rng.random(n) < 0.1] = 0
            r = (rng.random(n) < 0.7).astype(np.int64)
        r[0] = 0
        # encode the two streams RLE-hybrid, build run tables via the scanner
        dw = max(1, int(max_def).bit_length())
        denc = np.frombuffer(ref.encode_rle(d, dw), np.uint8)
        renc = np.frombuffer(ref.encode_rle(r, 1), np.uint8)
        buf = np.concatenate([denc, renc])
        dk_, dc, dp, do, _ = ref.scan_rle_runs(denc, n, dw, 0)
        rk_, rc_, rp, ro, _ = ref.scan_rle_runs(renc, n, 1, 0)
        dtab = (np.cumsum(dc), dk_, dp, do * 8, np.full(len(dk_), dw, np.int32))
        rtab = (np.cumsum(rc_), rk_, rp, (ro + len(denc)) * 8,
                np.full(len(rk_), 1, np.int32))
        got = native.assemble_list_runs(buf, dtab, rtab, n, dk, max_def)
        assert got is not None
        asm = levels_ops.assemble(d.astype(np.int32), r.astype(np.int32), leaf)
        np.testing.assert_array_equal(got[0], asm.list_offsets[0], err_msg=f"t{trial}")
        np.testing.assert_array_equal(got[1], asm.list_validity[0], err_msg=f"t{trial}")
        np.testing.assert_array_equal(got[2], asm.validity, err_msg=f"t{trial}")


def test_pack_bits_native_matches_numpy_oracle(lib, rng):
    for w in (1, 2, 3, 7, 8, 13, 15, 20, 31, 32, 40, 56):
        n = int(rng.integers(1, 3000))
        vals = rng.integers(0, 1 << min(w, 62), n, dtype=np.int64)
        got = native.pack_bits(vals, w)
        assert got is not None
        assert got == ref.pack_bits_np(vals, w), f"w={w}"


def test_dict_build_fixed_matches_unique(lib, rng):
    for dt in (np.int64, np.int32, np.float64, np.float32):
        vals = rng.integers(0, 500, 20000).astype(dt)
        out = native.dict_build_fixed(vals, len(vals) // 2 + 16)
        assert out is not None and out != "overflow"
        uniq, idx = out
        # first-occurrence order; gather must reproduce the input bitwise
        np.testing.assert_array_equal(uniq[idx], vals)
        assert len(np.unique(uniq)) == len(uniq)
    # overflow: all-distinct column refuses dictionary
    vals = np.arange(10000, dtype=np.int64)
    assert native.dict_build_fixed(vals, 5016) == "overflow"


def test_delta_prescan_malformed_streams_fail_cleanly(lib):
    """Attacker-controlled DELTA_BINARY_PACKED headers must raise/refuse,
    never segfault, hang, or attempt absurd allocations (review r2 PoCs)."""
    from parquet_tpu.ops import device as dev
    from parquet_tpu.ops.ref import write_uvarint

    def stream(bs, nmb, total, first=0, widths=b""):
        out = bytearray()
        for v in (bs, nmb, total, first):
            write_uvarint(out, v)
        out += b"\x00"  # min_delta for the first block
        out += widths
        out += b"\x00" * 16
        return np.frombuffer(bytes(out), np.uint8)

    # int64-overflow driver: huge block_size with wide miniblocks
    for data in (
        stream(1 << 59, 1, (1 << 59) + 2, widths=bytes([31])),
        stream(4, 4, 1 << 45, widths=bytes([1, 1, 1, 1])),  # absurd total
        stream(0, 5, 100, widths=bytes([1] * 5)),           # bs=0 (vpm=0)
        stream(5, 4, 100, widths=bytes([1] * 4)),           # bs % nmb != 0
    ):
        assert native.delta_prescan(data, 0) is None
        with pytest.raises(Exception):
            dev.delta_prescan(data, 0)


def test_encode_rle_native_byte_identical_to_oracle(lib, rng):
    """pq_encode_rle mirrors the Python encoder's run/span decisions exactly,
    so the streams are byte-identical (and decode round-trips)."""
    for w in (1, 2, 3, 7, 12, 15, 20, 33, 56):
        for style in range(4):
            n = int(rng.integers(1, 4000))
            hi = 1 << min(w, 62)
            if style == 0:  # long runs -> RLE-heavy
                v = np.repeat(rng.integers(0, hi, 30),
                              rng.integers(1, 200, 30))[:n]
                if len(v) < n:
                    v = np.pad(v, (0, n - len(v)))
            elif style == 1:  # unique -> all bit-packed
                v = rng.integers(0, hi, n)
            elif style == 2:  # short runs around the min_repeat threshold
                v = np.repeat(rng.integers(0, hi, n // 7 + 1), 7)[:n]
            else:  # alternating run/noise
                v = rng.integers(0, hi, n)
                v[n // 3: 2 * n // 3] = v[n // 3] if n >= 3 else v[0]
            v = v.astype(np.int64)
            n = len(v)
            got = native.encode_rle(v, w)
            want = ref.encode_rle(v, w, _native=False)
            assert got == want, f"w={w} style={style} n={n}"
            np.testing.assert_array_equal(
                ref.decode_rle(np.frombuffer(got, np.uint8), n, w), v)


def test_delta_prescan_rejects_64bit_header_overflow(lib):
    """uvarint values >= 2^63 in headers must be rejected, not wrapped
    (a negative cast total previously returned 'success' with k=0)."""
    import struct
    from parquet_tpu.ops.ref import write_uvarint

    def stream(bs_bytes, nmb, total_bytes):
        out = bytearray()
        out += bs_bytes
        write_uvarint(out, nmb)
        out += total_bytes
        write_uvarint(out, 0)  # first value
        out += b"\x00" * 16
        return np.frombuffer(bytes(out), np.uint8)

    uv = bytearray(); write_uvarint(uv, 4)
    # total = 2^63 (10-byte uvarint)
    t63 = bytes([0x80] * 9 + [0x01])
    assert native.delta_prescan(stream(bytes(uv), 1, t63), 0) is None
    # block_size = 2^64 + 64 (wraps to 64 if truncated)
    bs_wrap = bytes([0xC0] + [0x80] * 8 + [0x02])
    tv = bytearray(); write_uvarint(tv, 100)
    assert native.delta_prescan(stream(bs_wrap, 1, bytes(tv)), 0) is None


def test_gather_ba_rejects_out_of_range_indices(lib):
    dvals = np.frombuffer(b"abcde", np.uint8)
    doffs = np.array([0, 2, 5], np.int64)
    ok = ref.gather_dictionary((dvals, doffs), np.array([0, 1, 0]))
    assert bytes(ok[0]) == b"ababcab"[:len(ok[0])] or len(ok[0]) == 7
    for bad in ([0, -1, 1], [2], [-3]):
        with pytest.raises(ValueError):
            ref.gather_dictionary((dvals, doffs), np.array(bad, np.int64))


def test_rle_payload_padding_bits_masked(lib):
    """RLE payload bytes can carry garbage above bit_width; both scanners
    must mask so native expansion == Python oracle (review PoC: bw=25,
    payload 0xFFFFFFFF diverged as -1 vs 2^32-1)."""
    stream = np.frombuffer(b"\x10\xff\xff\xff\xff", np.uint8)  # RLE run, 8 values
    got = ref.decode_rle(stream, 8, 25)
    np.testing.assert_array_equal(got, np.full(8, (1 << 25) - 1, np.int64))
    k = ref.scan_rle_runs(stream, 8, 25, 0)
    assert int(k[2][0]) == (1 << 25) - 1


def test_dict_build_clustered_first_occurrences_still_encodes(lib, rng):
    """Data whose unique values all appear in the prefix then repeat must
    still dictionary-encode (the overflow bail samples prefix AND middle)."""
    n = 1 << 19
    uniq = rng.integers(0, 1 << 40, 1 << 16)
    vals = np.concatenate([uniq, uniq[rng.integers(0, len(uniq), n - len(uniq))]])
    out = native.dict_build_fixed(vals.astype(np.int64), n // 2 + 16)
    assert out is not None and out != "overflow"
    u, idx = out
    np.testing.assert_array_equal(u[idx], vals)
    # genuinely all-unique columns still bail
    assert native.dict_build_fixed(
        rng.permutation(np.arange(n, dtype=np.int64)), n // 2 + 16) == "overflow"


def test_encode_delta_native_byte_identical_to_oracle(lib, rng):
    """pq_encode_delta mirrors the Python DELTA_BINARY_PACKED encoder
    byte-for-byte across value shapes, widths, and block layouts."""
    shapes = [
        np.cumsum(rng.integers(0, 1000, 3001)).astype(np.int64),   # monotonic
        rng.integers(-(1 << 62), 1 << 62, 997),                    # wild 64-bit
        np.full(513, 42, np.int64),                                # constant
        np.arange(128, dtype=np.int64),                            # exact block
        np.array([7], np.int64),                                   # single
        rng.integers(-100, 100, 129),                              # block + 1
    ]
    for v in shapes:
        for bs, nmb in ((128, 4), (256, 8), (128, 1)):
            got = native.encode_delta(v, bs, nmb)
            want = ref.encode_delta_binary_packed(v, bs, nmb, _native=False)
            assert got == want, (len(v), bs, nmb)
            dec, _ = ref.decode_delta_binary_packed(
                np.frombuffer(got, np.uint8))
            np.testing.assert_array_equal(dec, v)


def test_encode_plain_ba_native_matches_numpy(lib, rng):
    parts = [f"v{i % 57}".encode() * int(rng.integers(0, 4)) for i in range(3000)]
    data = np.frombuffer(b"".join(parts), np.uint8)
    offs = np.zeros(len(parts) + 1, np.int64)
    np.cumsum([len(p) for p in parts], out=offs[1:])
    got = native.encode_plain_ba(data, offs)
    # decode side is the cross-check (and the numpy body is dual-run tested)
    v, o = native.plain_byte_array(np.frombuffer(got, np.uint8), len(parts))
    assert v.tobytes() == data.tobytes()
    np.testing.assert_array_equal(o, offs)


def test_encode_plain_ba_rejects_malformed_offsets(lib):
    data = np.frombuffer(b"abcdef", np.uint8)
    for bad in ([0, 10, 5, 6], [0, 3, 99], [1, 2, 6]):
        with pytest.raises(ValueError):
            native.encode_plain_ba(data, np.array(bad, np.int64))


def test_scan_page_headers_parity(lib, rng):
    """Native batch header scan == the Python thrift walk, field by field."""
    import io

    import pyarrow as pa
    import pyarrow.parquet as pq

    from parquet_tpu.io.reader import ParquetFile

    n = 50_000
    t = pa.table({"x": pa.array(rng.integers(0, 1 << 40, n))})
    buf = io.BytesIO()
    pq.write_table(t, buf, compression="snappy", data_page_size=16 * 1024)
    ch = ParquetFile(buf.getvalue()).row_group(0).column(0)
    start, size = ch.byte_range
    raw = ch.file.source.pread(start, size)
    desc = native.scan_page_headers(raw, ch.meta.num_values)
    assert desc is not None
    # python walk over the same bytes (bypass the fast path via raw=bytes +
    # a monkeyless trick: call the fallback by feeding scan output through
    # PageInfo comparison instead)
    pages_fast = list(ch.pages())
    import parquet_tpu.io.reader as rmod
    from parquet_tpu.format import metadata as md, thrift

    pos = 0
    fields_py = []
    while pos < size and len(fields_py) < len(pages_fast):
        header, data_pos = thrift.deserialize(md.PageHeader, raw, pos)
        clen = header.compressed_page_size
        fields_py.append((pos, data_pos, header))
        pos = data_pos + clen
    assert len(pages_fast) == len(fields_py)
    for page, (hpos, dpos, h) in zip(pages_fast, fields_py):
        assert page.header.type == h.type
        assert page.header.compressed_page_size == h.compressed_page_size
        assert page.header.uncompressed_page_size == h.uncompressed_page_size
        dph, dph2 = h.data_page_header, page.header.data_page_header
        if dph is not None:
            assert dph2.num_values == dph.num_values
            assert dph2.encoding == dph.encoding
            assert dph2.definition_level_encoding == dph.definition_level_encoding
        assert bytes(page.payload) == raw[dpos : dpos + h.compressed_page_size]


def test_scan_page_headers_huge_size_no_crash(lib):
    """A compressed_page_size near INT64_MAX must return None (fallback),
    not wrap the bounds check and segfault (review r4 finding)."""
    from parquet_tpu.format import metadata as md, thrift

    h = md.PageHeader(type=0, uncompressed_page_size=100,
                      compressed_page_size=(1 << 62),
                      data_page_header=md.DataPageHeader(
                          num_values=10, encoding=0,
                          definition_level_encoding=3,
                          repetition_level_encoding=3))
    raw = thrift.serialize(h) + b"\0" * 64
    assert native.scan_page_headers(raw, 10) is None


def test_scan_rle_runs_rejects_zero_count_runs(lib):
    """A zero-count run header covers no values and never decrements the
    scanner's remaining count — a crafted stream of them must fail fast
    (bounded run table), not loop/overflow.  Both the C++ scanner and the
    Python oracle reject identically."""
    # uvarint 0x00 = RLE run with count 0, followed by its 1 payload byte
    stream = np.frombuffer(b"\x00\x01" * 64, np.uint8)
    with pytest.raises(ValueError):
        native.scan_rle_runs(stream, 8, 3)
    with pytest.raises(ValueError):
        ref.scan_rle_runs(bytes(stream), 8, 3, 0)
    # zero-group bit-packed header (uvarint 0x01) is equally malformed
    stream2 = np.frombuffer(b"\x01" * 64, np.uint8)
    with pytest.raises(ValueError):
        native.scan_rle_runs(stream2, 8, 3)


def test_decompress_pages_rejects_negative_sizes(lib):
    """Header-supplied sizes are untrusted: a negative size must be refused
    before it reaches the raw-pointer native write (review r4 finding)."""
    from parquet_tpu import native

    assert native.decompress_pages([b"xx", b"yyy"], [-999, 1000], 1) is None


def test_decompress_pages_batch_matches_codec(lib, rng):
    from parquet_tpu import native
    from parquet_tpu.codecs import get_codec
    from parquet_tpu.format.enums import CompressionCodec

    codec = get_codec(CompressionCodec.SNAPPY)
    pages = [rng.integers(0, 255, rng.integers(10, 5000), np.uint8
                          ).astype(np.uint8).tobytes() for _ in range(7)]
    comp = [codec.encode(p) for p in pages]
    res = native.decompress_pages(comp, [len(p) for p in pages], 1, 2)
    assert res is not None
    buf, offs = res
    for i, p in enumerate(pages):
        assert bytes(buf[offs[i]:offs[i + 1]]) == p


def test_dict_bail_estimates_cardinality_not_window_uniqueness():
    """High-but-under-budget cardinality columns must BUILD their
    dictionary (the raw 7/8-window-uniqueness bail falsely refused them —
    advisor r4); truly near-unique columns still bail to overflow."""
    import parquet_tpu.native as native

    if native.get_lib() is None:
        pytest.skip("native shim unavailable")
    rng = np.random.default_rng(0)
    n = 1_000_000
    k = rng.integers(0, 450_000, n).astype(np.int64)  # ~36% < n/2 budget
    r = native.dict_build_fixed(k, n // 2 + 16)
    assert r is not None and r != "overflow"
    assert native.dict_build_fixed(np.arange(n, dtype=np.int64),
                                   n // 2 + 16) == "overflow"
    s = np.array([f"s{int(v):06d}"
                  for v in rng.integers(0, 90_000, 400_000)])
    vals = np.ascontiguousarray(
        np.frombuffer("".join(s.tolist()).encode(), np.uint8))
    offs = np.arange(len(s) + 1, dtype=np.int64) * 7
    r2 = native.dict_build_ba(vals, offs, len(s) // 2 + 16)
    assert r2 is not None and r2 != "overflow"
    u = np.array([f"u{i:06d}" for i in range(400_000)])
    uvals = np.ascontiguousarray(
        np.frombuffer("".join(u.tolist()).encode(), np.uint8))
    assert native.dict_build_ba(uvals, offs,
                                len(u) // 2 + 16) == "overflow"
