"""Device read path vs pyarrow across the format matrix (CPU backend; the
driver's bench runs the same path on the real chip)."""

import io

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from parquet_tpu.io.reader import ParquetFile


def _write(t: pa.Table, **kw) -> bytes:
    buf = io.BytesIO()
    pq.write_table(t, buf, **kw)
    return buf.getvalue()


def _check(raw: bytes, t: pa.Table, names=None, paths=None):
    tab = ParquetFile(raw).read(device=True)
    names = names or t.column_names
    for i, name in enumerate(names):
        path = paths[i] if paths else name
        arr = tab[path].to_arrow()
        expect = t[name].combine_chunks()
        if arr.type != expect.type:
            arr = arr.cast(expect.type)
        assert arr.equals(expect), f"{name} mismatch"


def test_device_plain_types(rng):
    t = pa.table({
        "i64": pa.array(rng.integers(-(2**60), 2**60, 5000)),
        "i32": pa.array(rng.integers(-(2**31), 2**31, 5000).astype(np.int32)),
        "f32": pa.array(rng.random(5000, dtype=np.float32)),
        "f64": pa.array(rng.random(5000)),
        "b": pa.array(rng.random(5000) < 0.5),
    })
    _check(_write(t, use_dictionary=False), t)


@pytest.mark.parametrize("compression", ["none", "snappy", "zstd"])
def test_device_compressions(compression, rng):
    t = pa.table({"x": pa.array(np.arange(20000, dtype=np.int64) % 997)})
    _check(_write(t, compression=compression, use_dictionary=False), t)


def test_device_nulls(rng):
    t = pa.table({
        "oi": pa.array([None if i % 3 == 0 else i for i in range(5000)], type=pa.int64()),
        "of": pa.array([None if i % 7 == 0 else float(i) for i in range(5000)]),
    })
    _check(_write(t), t)


def test_device_dictionary(rng):
    t = pa.table({
        "s": pa.array([f"cat-{i % 17}" for i in range(20000)]),
        "i": pa.array(rng.integers(0, 23, 20000)),
        "d": pa.array((rng.integers(0, 5, 20000) * 1.5)),
    })
    raw = _write(t, use_dictionary=True)
    tab = ParquetFile(raw).read(device=True)
    assert tab["s"].is_dictionary_encoded()  # strings stay encoded on device
    _check(raw, t)


def test_device_delta(rng):
    t = pa.table({
        "ts": pa.array(np.sort(rng.integers(0, 2**44, 10000)), type=pa.timestamp("us")),
        "i32": pa.array(rng.integers(-(2**30), 2**30, 10000).astype(np.int32)),
    })
    raw = _write(t, use_dictionary=False,
                 column_encoding={"ts": "DELTA_BINARY_PACKED", "i32": "DELTA_BINARY_PACKED"})
    _check(raw, t)


def test_device_delta_multipage(rng):
    t = pa.table({"x": pa.array(rng.integers(-(2**50), 2**50, 100000))})
    raw = _write(t, use_dictionary=False, data_page_size=4096,
                 column_encoding={"x": "DELTA_BINARY_PACKED"})
    _check(raw, t)


def test_device_bss_multipage(rng):
    t = pa.table({"f": pa.array(rng.random(50000, dtype=np.float32)),
                  "d": pa.array(rng.random(50000))})
    raw = _write(t, use_dictionary=False, data_page_size=8192,
                 column_encoding={"f": "BYTE_STREAM_SPLIT", "d": "BYTE_STREAM_SPLIT"})
    _check(raw, t)


def test_device_multipage_plain_with_nulls(rng):
    t = pa.table({"x": pa.array([None if i % 5 == 0 else i for i in range(60000)],
                                type=pa.int64())})
    raw = _write(t, use_dictionary=False, data_page_size=4096)
    _check(raw, t)


@pytest.mark.parametrize("dpv", ["1.0", "2.0"])
def test_device_lists(dpv, rng):
    t = pa.table({
        "lst": pa.array([[1, 2, 3] if i % 2 else None for i in range(2000)],
                        type=pa.list_(pa.int64())),
    })
    raw = _write(t, data_page_version=dpv)
    _check(raw, t, names=["lst"], paths=["lst.list.element"])


def test_device_strings_plain(rng):
    t = pa.table({"s": pa.array([f"plain-string-{i}" for i in range(5000)])})
    raw = _write(t, use_dictionary=False, column_encoding={"s": "PLAIN"})
    _check(raw, t)


def test_device_multi_row_groups(rng):
    t = pa.table({"x": pa.array(np.arange(50000, dtype=np.int64))})
    raw = _write(t, row_group_size=7000, use_dictionary=False)
    _check(raw, t)


def test_device_matches_host_exactly(rng):
    t = pa.table({
        "a": pa.array(rng.integers(0, 10**12, 10000)),
        "s": pa.array([f"v{i % 29}" for i in range(10000)]),
    })
    raw = _write(t, compression="zstd")
    pf = ParquetFile(raw)
    host = pf.read()
    devi = pf.read(device=True)
    np.testing.assert_array_equal(
        np.asarray(host["a"].values),
        np.ascontiguousarray(np.asarray(devi["a"].values)).view(np.int64).reshape(-1))
    assert devi["s"].to_arrow().cast(pa.string()).equals(host["s"].to_arrow().cast(pa.string()))


def test_single_list_assembles_on_device():
    """Config-4 shape: one-level list columns expand levels AND assemble
    (validity, list_offsets) on device (VERDICT r1 item 7), on every
    backend."""
    import jax

    from parquet_tpu.parallel import device_reader as dr

    rng = np.random.default_rng(13)
    n_lists = 5000
    lens = rng.integers(0, 8, n_lists)
    lens[rng.random(n_lists) < 0.07] = 0
    offs = np.zeros(n_lists + 1, np.int32)
    np.cumsum(lens, out=offs[1:])
    total = int(offs[-1])
    base = np.cumsum(rng.integers(0, 1000, max(total, 1)).astype(np.int64))
    # null lists included: list_validity (def >= dk-1 vs empty lists) matters
    mask = rng.random(n_lists) < 0.05
    arr = pa.ListArray.from_arrays(pa.array(offs), pa.array(base[:total]),
                                   mask=pa.array(mask))
    t = pa.table({"xs": arr})
    buf = io.BytesIO()
    pq.write_table(t, buf, use_dictionary=False,
                   column_encoding={"xs.list.element": "DELTA_BINARY_PACKED"},
                   compression="none")
    pf = ParquetFile(buf.getvalue())
    chunk = pf.row_group(0).column(0)

    plan = dr.build_plan(chunk)
    assert dr.stage_levels_on_device(chunk.leaf, plan)
    col = dr.decode_chunk_device(chunk, fallback=False)
    # assembly outputs are device arrays, host level streams were never built
    assert col.def_levels is None and col.rep_levels is None
    assert isinstance(col.list_offsets[0], jax.Array)
    # oracle: host decode
    host = ParquetFile(buf.getvalue()).read()
    got = col.to_arrow()
    want = host.to_arrow().column("xs")
    assert got.to_pylist() == want.to_pylist() == t.column("xs").to_pylist()


_NESTED_SHAPES = {
    "struct<list<int64>>": (
        pa.struct([("xs", pa.list_(pa.int64()))]),
        [{"xs": [1, 2]}, None, {"xs": None}, {"xs": [3]}, {"xs": []}]),
    "struct<list<string>>": (
        pa.struct([("xs", pa.list_(pa.string()))]),
        [{"xs": ["a", None]}, None, {"xs": None}, {"xs": ["bc"]},
         {"xs": []}]),
    "list<struct<int64>>": (
        pa.list_(pa.struct([("v", pa.int64())])),
        [[{"v": 1}, None, {"v": None}], None, [], [{"v": 4}]]),
    "list<struct<list<int64>>>": (
        pa.list_(pa.struct([("xs", pa.list_(pa.int64()))])),
        [[{"xs": [1, None]}, None, {"xs": None}], None, [],
         [{"xs": []}, {"xs": [5]}]]),
    "list<list<int64>>": (
        pa.list_(pa.list_(pa.int64())),
        [[[1, 2], None, []], None, [], [[None, 3]]]),
}


@pytest.mark.parametrize("shape", list(_NESTED_SHAPES))
def test_list_under_struct_keeps_host_levels_device_read(shape):
    """A repeated leaf with a struct layer anywhere in its chain keeps host
    levels (the table assembler reads them for struct nullness); a chain of
    lists only assembles on device and carries no host def levels.  Each
    shape reads back as pyarrow reads it."""
    from parquet_tpu.parallel import device_reader as dr

    typ, rows = _NESTED_SHAPES[shape]
    t = pa.table({"c": pa.array(rows * 40, type=typ)})
    raw = _write(t, use_dictionary=False)
    got = ParquetFile(raw).read(device=True)
    want = pq.read_table(io.BytesIO(raw))
    assert got.to_arrow().column("c").to_pylist() \
        == want.column("c").to_pylist()
    chunk = ParquetFile(raw).row_group(0).column(0)
    on_device = dr.stage_levels_on_device(chunk.leaf, dr.build_plan(chunk))
    assert on_device is (shape == "list<list<int64>>")
    if on_device:
        assert got[chunk.leaf.dotted_path].def_levels is None


@pytest.mark.parametrize("mode", ["off", "", "0", "1"])
def test_dense_dict_route_modes(mode, monkeypatch, rng):
    """Single-width dict-index streams route through the compacted dense
    stream (jnp twin by default, Pallas with PARQUET_TPU_PALLAS=1, legacy
    gathers with =off) — all three agree with pyarrow (VERDICT r1 item 3)."""
    monkeypatch.setenv("PARQUET_TPU_PALLAS", mode)
    n = 60000
    t = pa.table({
        "k": pa.array(rng.integers(0, 5000, n).astype(np.int64)),
        "s": pa.array(np.array([f"c{i:03d}" for i in range(200)])[
            rng.integers(0, 200, n)]).dictionary_encode(),
        "small": pa.array(rng.integers(0, 7, n).astype(np.int32)),
    })
    raw = _write(t, compression="snappy", use_dictionary=True,
                 data_page_size=1 << 14)  # many pages: alignment padding
    _check(raw, t)


def test_dense_dict_small_dictionary_pallas(monkeypatch, rng):
    """Forced Pallas on a small fixed-width dictionary: the dense unpack
    kernel then the XLA gather (indices materialized; no fused kernel)."""
    from parquet_tpu.parallel import device_reader as dr

    monkeypatch.setenv("PARQUET_TPU_PALLAS", "1")
    n = 30000
    t = pa.table({"v": pa.array((rng.integers(0, 50, n) * 3).astype(np.int32))})
    raw = _write(t, use_dictionary=True, data_page_size=1 << 14)
    pf = ParquetFile(raw)
    chunk = pf.row_group(0).column(0)
    col = dr.decode_chunk_device(chunk, fallback=False)
    assert col.dict_indices is not None and col.values is not None
    np.testing.assert_array_equal(np.asarray(col.values),
                                  t.column("v").to_numpy())


def test_dense_stream_clamped_final_run(monkeypatch, rng):
    """A final bit-packed run clamped mid-group must survive the 32-value
    round-up (regression: floor() dropped the tail page)."""
    monkeypatch.setenv("PARQUET_TPU_PALLAS", "")
    for n in (9, 33, 777, 4099):
        t = pa.table({"v": pa.array(rng.integers(0, 900, n).astype(np.int64))})
        raw = _write(t, use_dictionary=True)
        _check(raw, t)


def test_device_delta_constant_column():
    """Width-0 miniblocks (constant / fixed-stride data → all-zero deltas
    after min extraction) must decode on the dense path, not crash."""
    for vals in (np.full(20000, 42, np.int64),
                 np.arange(20000, dtype=np.int64) * 7 + 3,
                 np.full(20000, -5, np.int32)):
        t = pa.table({"x": pa.array(vals)})
        raw = _write(t, use_dictionary=False, compression="none",
                     column_encoding={"x": "DELTA_BINARY_PACKED"})
        _check(raw, t)


def test_device_struct_no_nulls_vectorized_arrow():
    """All-present struct chains drop levels AND validity on the no-null fast
    path; to_arrow must still build the struct vectorized (not row-by-row)."""
    n = 30000
    t = pa.table({"st": pa.array(
        [{"a": i, "b": float(i)} for i in range(n)],
        type=pa.struct([("a", pa.int64()), ("b", pa.float64())]))})
    raw = _write(t, use_dictionary=False, compression="none")
    got = ParquetFile(raw).read(device=True).to_arrow()
    assert got.column("st").combine_chunks().equals(t.column("st").combine_chunks())


@pytest.mark.parametrize("typ_kw", [
    ("bool", {}), ("str", {}), ("i64", {}), ("f32", {}),
    ("delta", {"use_dictionary": False,
               "column_encoding": {"x": "DELTA_BINARY_PACKED"}}),
    ("bss", {"use_dictionary": False,
             "column_encoding": {"x": "BYTE_STREAM_SPLIT"}}),
], ids=lambda p: p[0])
def test_device_all_null_chunks(typ_kw):
    """All-null chunks stage no value bytes; every device kind must decode
    them (found by fuzzing: rle_expand crashed on the missing buffer)."""
    from parquet_tpu.parallel import device_reader as dr
    from parquet_tpu.format.enums import Type as _T

    kind, kw = typ_kw
    typ = {"bool": pa.bool_(), "str": pa.string(), "i64": pa.int64(),
           "f32": pa.float32(), "delta": pa.int64(), "bss": pa.float64()}[kind]
    t = pa.table({"x": pa.array([None] * 1500, type=typ)})
    raw = _write(t, compression="none", **kw)
    # pin the device path: no silent host fallback may hide a regression
    chunk = ParquetFile(raw).row_group(0).column(0)
    col = dr.decode_chunk_device(chunk, fallback=False)
    arr = col.to_arrow()
    assert len(arr) == 1500 and arr.null_count == 1500


@pytest.mark.parametrize("mode,w,want", [
    ("1", 8, True), ("1", 16, True), ("1", 17, True), ("1", 20, True),
    ("1", 24, True), ("1", 31, True), ("1", 32, True),
    ("0", 8, False), ("0", 20, False),
    ("", 8, "tpu"), ("", 20, "tpu"),
])
def test_use_pallas_gate_wide_widths(mode, w, want, monkeypatch):
    """Forced Pallas admits every width, forced jnp none, and 'auto'
    routes on the backend alone (the kernel on a TPU, the jnp twin
    elsewhere) — with no process-wide 'broken' latch in between."""
    import jax

    from parquet_tpu.parallel import device_reader as dr

    monkeypatch.setenv("PARQUET_TPU_PALLAS", mode)
    if want == "tpu":
        want = jax.default_backend() == "tpu"
    assert dr._use_pallas(w) is want
    assert not hasattr(dr, "_pallas_broken")


def test_byte_stream_split_flba_float16_device(rng):
    """BYTE_STREAM_SPLIT over FLBA(2) (float16) decodes on device as (n, 2)
    byte rows — the plain_flba column form."""
    from parquet_tpu.parallel import device_reader as dr

    t = pa.table({"h": pa.array(rng.random(20000).astype(np.float16))})
    buf = io.BytesIO()
    pq.write_table(t, buf, use_dictionary=False, data_page_size=1 << 12,
                   column_encoding={"h": "BYTE_STREAM_SPLIT"})
    raw = buf.getvalue()
    pf = ParquetFile(raw)
    chunk = pf.row_group(0).column(0)
    col = dr.decode_chunk_device(chunk, fallback=False)
    got = np.asarray(col.values).view(np.float16).reshape(-1)
    np.testing.assert_array_equal(got, t.column("h").to_numpy())
    assert ParquetFile(raw).read(device=True).to_arrow().column("h").to_pylist() == \
        t.column("h").to_pylist()


def test_byte_stream_split_flba_decimal_device(rng):
    """BSS-encoded FLBA decimals must come back as byte rows, not bitcast
    floats (review regression: FLBA(4)/(8) corrupted through the width
    dispatch)."""
    import decimal

    vals = [decimal.Decimal(f"{i}.{i % 100:02d}") for i in range(5000)]
    for prec, name in ((9, "d4"), (18, "d8")):
        t = pa.table({name: pa.array(vals, type=pa.decimal128(prec, 2))})
        buf = io.BytesIO()
        try:
            pq.write_table(t, buf, use_dictionary=False,
                           column_encoding={name: "BYTE_STREAM_SPLIT"},
                           store_decimal_as_integer=False)
        except Exception:
            continue  # this pyarrow build may refuse BSS for this width
        got = ParquetFile(buf.getvalue()).read(device=True).to_arrow()
        assert got.column(name).to_pylist() == vals, name


class TestBatchedDecode:
    """Intra-chunk pipelined decode == single-plan decode == pyarrow."""

    def _roundtrip(self, t, **write_kw):
        import io

        import pyarrow.parquet as pq

        from parquet_tpu.io.reader import ParquetFile
        from parquet_tpu.parallel import device_reader as dr

        b = io.BytesIO()
        pq.write_table(t, b, row_group_size=1 << 30, data_page_size=16 * 1024,
                       **write_kw)
        ch = ParquetFile(b.getvalue()).row_group(0).column(0)
        col_b = next(dr.decode_chunks_pipelined([ch]))
        ch2 = ParquetFile(b.getvalue()).row_group(0).column(0)
        col_s = dr.decode_chunk_device(ch2, fallback=True)
        name = t.column_names[0]
        oracle = t.column(name).combine_chunks()
        got = col_b.to_arrow().cast(oracle.type)
        assert got.equals(oracle)
        assert col_b.to_arrow().equals(col_s.to_arrow())

    def test_plain_int64_nulls(self, rng):
        import pyarrow as pa

        n = 120_000
        v = rng.integers(0, 1 << 50, n)
        mask = rng.random(n) < 0.1
        t = pa.table({"c": pa.array(np.where(mask, None, v), pa.int64())})
        self._roundtrip(t, compression="none", use_dictionary=False)

    def test_dict_strings_zstd(self, rng):
        import pyarrow as pa

        n = 120_000
        t = pa.table({"c": pa.array(
            [f"val{int(i)}" for i in rng.integers(0, 500, n)])})
        self._roundtrip(t, compression="zstd")

    def test_plain_byte_array(self, rng):
        import pyarrow as pa

        n = 60_000
        t = pa.table({"c": pa.array(
            [f"s-{int(i)}" for i in rng.integers(0, 10**9, n)])})
        self._roundtrip(t, compression="snappy", use_dictionary=False)

    def test_double_bss(self, rng):
        import pyarrow as pa

        n = 120_000
        t = pa.table({"c": pa.array(rng.random(n))})
        self._roundtrip(t, compression="none", use_dictionary=False,
                        column_encoding={"c": "BYTE_STREAM_SPLIT"})

    def test_mid_chunk_dict_fallback(self, rng):
        # dict -> plain fallback mid-chunk diverges batch kinds: must fall
        # back (through the pipeline chain) and still be correct
        import pyarrow as pa

        n = 200_000
        t = pa.table({"c": pa.array(rng.integers(0, n, n))})
        self._roundtrip(t, compression="snappy", use_dictionary=True,
                        dictionary_pagesize_limit=4096)


def test_bytearray_source_mutation_safe(rng):
    """Reading from a caller-owned bytearray must not alias its memory into
    decoded columns (review r4 finding)."""
    import io

    import pyarrow as pa
    import pyarrow.parquet as pq

    from parquet_tpu.io.reader import ParquetFile

    n = 50_000
    vals = rng.integers(0, 1 << 40, n)
    t = pa.table({"x": pa.array(vals)})
    b = io.BytesIO()
    pq.write_table(t, b, compression="none", use_dictionary=False)
    buf = bytearray(b.getvalue())
    tbl = ParquetFile(buf).read()
    buf[:] = b"\xff" * len(buf)  # caller reuses its buffer
    got = np.asarray(tbl.to_arrow().column("x").combine_chunks())
    np.testing.assert_array_equal(got, vals)


@pytest.mark.parametrize("table_kind", ["delta", "dict", "plain"])
def test_device_route_pinned_equals_host_route(table_kind, rng):
    """The device decode of each value kind equals the host reader's decode
    and pyarrow's."""
    from parquet_tpu.parallel import device_reader as dr

    n = 150_000
    if table_kind == "delta":
        t = pa.table({"c": pa.array(
            1_000_000 + np.cumsum(rng.integers(0, 500, n)))})
        kw = dict(compression="none", use_dictionary=False,
                  column_encoding={"c": "DELTA_BINARY_PACKED"})
    elif table_kind == "dict":
        v = rng.integers(0, 800, n)
        v[: n // 5] = 13  # long RLE run + bit-packed spans
        t = pa.table({"c": pa.array(v)})
        kw = dict(compression="snappy", use_dictionary=True)
    else:
        t = pa.table({"c": pa.array(rng.integers(0, 1 << 50, n))})
        kw = dict(compression="none", use_dictionary=False,
                  column_encoding={"c": "PLAIN"})
    raw = _write(t, row_group_size=1 << 30, **kw)
    dev_col = dr.decode_chunk_device(
        ParquetFile(raw).row_group(0).column(0), fallback=False)
    host = ParquetFile(raw).read()["c"].to_arrow()
    assert dev_col.to_arrow().cast(host.type).equals(host)
    oracle = t.column("c").combine_chunks()
    assert dev_col.to_arrow().cast(oracle.type).equals(oracle)


@pytest.mark.parametrize("dtype", ["f8", "f4", "i4", "f2"])
def test_bss_route_pinned_equals_host_route(dtype, rng):
    """The BSS device decode equals the host reader's decode and pyarrow's
    (FLOAT16 is FLBA(2): the byte-row form)."""
    from parquet_tpu.parallel import device_reader as dr

    n = 120_000
    if dtype == "i4":
        t = pa.table({"c": pa.array(
            rng.integers(-(2**31), 2**31, n).astype(np.int32))})
    elif dtype == "f2":
        t = pa.table({"c": pa.array(rng.random(n).astype(np.float16))})
    else:
        t = pa.table({"c": pa.array(
            rng.random(n).astype(np.float64 if dtype == "f8"
                                 else np.float32))})
    try:
        raw = _write(t, compression="snappy", use_dictionary=False,
                     column_encoding={"c": "BYTE_STREAM_SPLIT"},
                     row_group_size=1 << 30, data_page_size=16 * 1024)
    except Exception as e:  # pyarrow without extended-BSS support
        pytest.skip(f"pyarrow cannot BSS-encode {dtype}: {e}")
    dev_col = dr.decode_chunk_device(
        ParquetFile(raw).row_group(0).column(0), fallback=False)
    host = ParquetFile(raw).read()["c"].to_arrow()
    assert dev_col.to_arrow().cast(host.type).equals(host)
    oracle = t.column("c").combine_chunks()
    assert dev_col.to_arrow().cast(oracle.type).equals(oracle)


def test_device_asm_rule_is_backend_independent(monkeypatch):
    """Whether a column's levels go to HBM follows from its schema alone:
    the same answer whatever backend JAX reports."""
    import jax

    from parquet_tpu.parallel import device_reader as dr

    t = pa.table({
        "lst": pa.array([[1, 2], [], None, [3]] * 64),
        "st": pa.array([{"xs": [1]}, None, {"xs": None}, {"xs": []}] * 64),
        "opt": pa.array([1, None, 3, 4] * 64),
        "dense": pa.array([1, 2, 3, 4] * 64),
    })
    rg = ParquetFile(_write(t, use_dictionary=False)).row_group(0)
    want = {"lst": True, "st": False, "opt": True, "dense": False}
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        got = {}
        for i, name in enumerate(t.column_names):
            chunk = rg.column(i)
            got[name] = dr.stage_levels_on_device(chunk.leaf,
                                                  dr.build_plan(chunk))
        assert got == want


_ARRAY_KINDS = {
    "plain_int64": (pa.array(np.arange(3000, dtype=np.int64) * 7),
                    dict(use_dictionary=False)),
    "plain_int32": (pa.array(np.arange(3000, dtype=np.int32)),
                    dict(use_dictionary=False)),
    "plain_double": (pa.array(np.linspace(0, 1, 3000)),
                     dict(use_dictionary=False)),
    "plain_flba": (pa.array([bytes([i % 251] * 4) for i in range(3000)],
                            type=pa.binary(4)),
                   dict(use_dictionary=False)),
    "dict_int64": (pa.array(np.arange(3000, dtype=np.int64) % 37),
                   dict(use_dictionary=True)),
    "dict_string": (pa.array([f"s{i % 29}" for i in range(3000)]),
                    dict(use_dictionary=True)),
    "delta": (pa.array(np.cumsum(np.arange(3000, dtype=np.int64))),
              dict(use_dictionary=False,
                   column_encoding={"c": "DELTA_BINARY_PACKED"})),
    "bss": (pa.array(np.linspace(0, 1, 3000, dtype=np.float32)),
            dict(use_dictionary=False,
                 column_encoding={"c": "BYTE_STREAM_SPLIT"})),
    "dba": (pa.array([f"prefix-{i:05d}" for i in range(3000)]),
            dict(use_dictionary=False,
                 column_encoding={"c": "DELTA_BYTE_ARRAY"})),
}


@pytest.mark.parametrize("kind", list(_ARRAY_KINDS))
def test_device_read_returns_jax_arrays(kind):
    """``read(device=True)`` decodes every value kind on the device on
    every backend: the values (or a dictionary column's indices) come back
    as a ``jax.Array``, and the column reads back as written."""
    import jax

    arr, kw = _ARRAY_KINDS[kind]
    t = pa.table({"c": arr})
    raw = _write(t, **kw)
    col = ParquetFile(raw).read(device=True)["c"]
    held = (col.dict_indices if col.is_dictionary_encoded()
            else col.values)
    assert isinstance(held, jax.Array)
    _check(raw, t)


def _kernel_bytes_from_metadata(raw: bytes) -> dict:
    """The logical bytes of ``rle_expand`` and ``fixed64_pairs`` for one
    device read, from the file's page headers alone (DATA_PAGE_V2 carries
    the level-stream lengths): per flat nullable column, its def-level
    bytes + 4 x slots when it holds nulls; per dictionary column its index
    bytes (the value section past the bit-width byte) + 4 x present values;
    per PLAIN 8-byte column 16 x present values."""
    from parquet_tpu.format.enums import Encoding, PageType, Type

    pf = ParquetFile(raw)
    rle = pairs = 0
    for g in range(len(pf.row_groups)):
        for leaf in pf.schema.leaves:
            reader = pf.row_group(g).column(leaf.column_index)
            v2 = [p.header for p in reader.pages()
                  if p.page_type == PageType.DATA_PAGE_V2]
            slots = sum(h.data_page_header_v2.num_values for h in v2)
            nulls = sum(h.data_page_header_v2.num_nulls for h in v2)
            if nulls:
                rle += sum(h.data_page_header_v2.definition_levels_byte_length
                           for h in v2) + 4 * slots
            for h in v2:
                d = h.data_page_header_v2
                present = d.num_values - d.num_nulls
                if Encoding(d.encoding) == Encoding.RLE_DICTIONARY:
                    rle += (h.uncompressed_page_size
                            - d.definition_levels_byte_length
                            - d.repetition_levels_byte_length - 1)
                    rle += 4 * present
                elif leaf.physical_type in (Type.INT64, Type.DOUBLE):
                    pairs += 16 * present
    return {"kernel_bytes.rle_expand": rle,
            "kernel_bytes.fixed64_pairs": pairs}


def _kernel_runs_from_pages(raw: bytes) -> int:
    """The runs ``rle_expand`` is handed for one device read, scanned from
    the file's pages: per flat nullable column with nulls, the runs of its
    def-level streams; per dictionary column, the runs of its index
    streams (past the bit-width byte)."""
    from parquet_tpu.format.enums import Encoding, PageType
    from parquet_tpu.ops import ref

    pf = ParquetFile(raw)
    runs = 0
    for g in range(len(pf.row_groups)):
        for leaf in pf.schema.leaves:
            reader = pf.row_group(g).column(leaf.column_index)
            pages = [p for p in reader.pages()
                     if p.page_type == PageType.DATA_PAGE_V2]
            nulls = sum(p.header.data_page_header_v2.num_nulls for p in pages)
            for p in pages:
                d = p.header.data_page_header_v2
                dl = d.definition_levels_byte_length
                if nulls:
                    levels = np.frombuffer(p.payload[:dl], np.uint8)
                    runs += len(ref.scan_rle_runs(levels, d.num_values, 1)[0])
                if Encoding(d.encoding) == Encoding.RLE_DICTIONARY:
                    body = p.payload[dl:]
                    if d.is_compressed is not False:
                        body = reader.codec.decode(
                            body, p.header.uncompressed_page_size - dl)
                    body = np.frombuffer(body, np.uint8)
                    runs += len(ref.scan_rle_runs(
                        body[1:], d.num_values - d.num_nulls, int(body[0]))[0])
    return runs


def _kernel_counter_file(monkeypatch, rng):
    """A file whose device read runs both kernels: a PLAIN int64, a nullable
    double, a nullable dictionary int32, several V2 pages per chunk."""
    monkeypatch.setenv("PARQUET_TPU_PALLAS", "off")  # indices via the runs
    n = 6000
    t = pa.table({
        "i64": pa.array(rng.integers(-(2**60), 2**60, n)),
        "f64n": pa.array(rng.random(n), mask=rng.random(n) < 0.2),
        "d32n": pa.array(rng.integers(0, 50, n).astype(np.int32),
                         mask=rng.random(n) < 0.3),
    })
    raw = _write(t, use_dictionary=["d32n"], data_page_version="2.0",
                 row_group_size=2500, data_page_size=4096)
    return raw, t


def test_kernel_run_counter_matches_the_page_streams(monkeypatch, rng):
    """``kernel_runs.rle_expand`` is the run-table length summed over a
    read: what the pages' hybrid streams scan to, and what the kernel was
    handed, the same on every read of one file."""
    from parquet_tpu import counters
    from parquet_tpu.ops import device as dev

    raw, t = _kernel_counter_file(monkeypatch, rng)
    want = _kernel_runs_from_pages(raw)
    handed = []
    expand = dev.rle_expand

    def spy(buf, n, run_ends, *rest):
        handed.append(int(run_ends.shape[0]))
        return expand(buf, n, run_ends, *rest)

    monkeypatch.setattr(dev, "rle_expand", spy)
    assert want
    for _ in range(2):
        handed.clear()
        before = counters.snapshot().get("kernel_runs.rle_expand", 0)
        _check(raw, t)
        got = counters.snapshot().get("kernel_runs.rle_expand", 0) - before
        assert got == want == sum(handed)


def test_kernel_byte_counters_match_the_file_metadata(monkeypatch, rng):
    """``kernel_bytes.*`` count the work the data fixes: two reads of one
    file count the same bytes, and those are the page headers' formula."""
    from parquet_tpu import counters

    raw, t = _kernel_counter_file(monkeypatch, rng)
    want = _kernel_bytes_from_metadata(raw)
    assert all(want.values())
    for _ in range(2):
        before = counters.snapshot()
        _check(raw, t)
        after = counters.snapshot()
        got = {k: after.get(k, 0) - before.get(k, 0) for k in want}
        assert got == want


#: PLAIN fixed-width columns by physical type: (arrow type, bytes a value)
_PLAIN_FIXED = {"INT64": (pa.int64(), 8), "DOUBLE": (pa.float64(), 8),
                "INT32": (pa.int32(), 4), "FLOAT": (pa.float32(), 4),
                "INT96": (pa.timestamp("ns"), 12)}


def _plain_fixed_file(physical, rng, shape):
    """One PLAIN column of ``physical``: several pages (``multipage``), a
    share of nulls (``nulls``) or nothing but nulls (``all_null``)."""
    typ, _ = _PLAIN_FIXED[physical]
    n = 9000
    v = rng.integers(-(2**30), 2**30, n)
    if physical in ("DOUBLE", "FLOAT"):
        v = rng.standard_normal(n)
    mask = {"multipage": None, "nulls": rng.random(n) < 0.3,
            "all_null": np.ones(n, bool)}[shape]
    t = pa.table({"x": pa.array(v, mask=mask).cast(typ)})
    raw = _write(t, use_dictionary=False, data_page_size=4096,
                 use_deprecated_int96_timestamps=physical == "INT96")
    return raw, t


@pytest.mark.parametrize("shape", ["multipage", "nulls", "all_null"])
@pytest.mark.parametrize("physical", list(_PLAIN_FIXED))
def test_plain_fixed_stages_exact_words(physical, shape, rng):
    """A PLAIN fixed-width chunk on the device route stages its values as
    one uint32 array of exactly ``nvals * width / 4`` words (no padded
    bucket), ``bytes_h2d`` grows by exactly those bytes, and the read
    counts ``16 n`` for ``fixed64_pairs`` and ``8 n`` for
    ``bitcast_fixed32``."""
    from parquet_tpu import counters
    from parquet_tpu.format.enums import Type
    from parquet_tpu.parallel import device_reader as dr

    raw, t = _plain_fixed_file(physical, rng, shape)
    chunk = ParquetFile(raw).row_group(0).column(0)
    assert Type(chunk.meta.type) == Type[physical]
    assert shape != "multipage" or len(list(chunk.pages())) > 2
    plan = dr.build_plan(chunk)
    width = _PLAIN_FIXED[physical][1]
    nvals = len(t) - t["x"].null_count
    assert plan.total_values == nvals
    put = []
    before = counters.snapshot().get("bytes_h2d", 0)
    _, words, _ = dr.stage_plan(plan, stage_levels=False,
                                put=lambda a: put.append(a) or a)
    assert counters.snapshot().get("bytes_h2d", 0) - before \
        == words.nbytes == nvals * width
    assert any(a is words for a in put)
    assert words.dtype == np.uint32 and words.shape == (nvals * width // 4,)

    want = {"kernel_bytes.fixed64_pairs": 16 * nvals * (width == 8),
            "kernel_bytes.bitcast_fixed32": 8 * nvals * (width == 4)}
    before = counters.snapshot()
    _check(raw, t)
    after = counters.snapshot()
    assert {k: after.get(k, 0) - before.get(k, 0) for k in want} == want


@pytest.mark.parametrize("parts", ["one", "misaligned", "several", "none"])
def test_byte_accum_words_exact(parts):
    """``_ByteAccum.words`` gives exactly ``len // 4`` little-endian words:
    a zero-copy view of one word-aligned part, one copy otherwise."""
    from parquet_tpu.parallel.device_reader import _ByteAccum

    v = np.arange(24, dtype=np.uint32) * np.uint32(0x01010101)
    body = v.view(np.uint8)
    acc = _ByteAccum()
    if parts == "one":
        acc.extend(body)
    elif parts == "misaligned":
        acc.extend(np.concatenate([np.zeros(1, np.uint8), body])[1:])
    elif parts == "several":
        for i in range(0, 96, 28):
            acc.extend(body[i:i + 28])
    words = acc.words()
    want = v if parts != "none" else v[:0]
    assert words.dtype == np.uint32
    np.testing.assert_array_equal(words, want)
    assert np.shares_memory(words, body) == (parts == "one")


@pytest.mark.parametrize("physical", list(_PLAIN_FIXED))
def test_short_plain_page_has_no_device_form(physical):
    """A PLAIN page shorter than its values (a corrupt file) is refused for
    the device: its values stage as exact words, and a zero-padded bucket
    would have decoded the missing bytes as zeros."""
    from parquet_tpu.format.enums import Encoding, Type
    from parquet_tpu.parallel import device_reader as dr

    width = _PLAIN_FIXED[physical][1]
    plan = dr._Plan()
    dr._stage_values(plan, np.zeros(3 * width, np.uint8), 0, 3,
                     Encoding.PLAIN, Type[physical], None)
    assert len(plan.values) == 3 * width
    with pytest.raises(dr._Unsupported, match="shorter"):
        dr._stage_values(plan, np.zeros(3 * width - 1, np.uint8), 0, 3,
                         Encoding.PLAIN, Type[physical], None)
