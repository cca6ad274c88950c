"""Pallas kernels vs numpy oracle, interpret mode (CPU).  The driver's bench
compiles the same kernels on the real chip."""

import numpy as np
import pytest

from parquet_tpu.ops import pallas_kernels as pk, ref


def _pack_words(v: np.ndarray, w: int) -> np.ndarray:
    raw = ref.pack_bits(v, w)
    pad = (-len(raw)) % 4
    return np.frombuffer(raw + b"\0" * pad, dtype="<u4").copy()


@pytest.mark.parametrize("w", [1, 2, 3, 5, 7, 8, 11, 13, 16, 17, 20, 24, 27, 31, 32])
def test_unpack_bits_dense_pallas(w, rng):
    n = 4099
    v = rng.integers(0, 1 << min(w, 62), size=n, dtype=np.uint64) & np.uint64((1 << w) - 1)
    words = _pack_words(v, w)
    out = pk.unpack_bits_dense(words, n, w, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), v.astype(np.uint32))


@pytest.mark.parametrize("w", [3, 8, 17, 31])
def test_unpack_bits_dense_jnp_twin(w, rng):
    n = 2000
    v = rng.integers(0, 1 << w, size=n, dtype=np.uint64)
    words = _pack_words(v, w)
    out = pk.unpack_bits_dense_jnp(words, n, w)
    np.testing.assert_array_equal(np.asarray(out), v.astype(np.uint32))


@pytest.mark.parametrize("dtype", [np.int32, np.float32, np.int64])
def test_dense_dict_unfused_route(dtype, monkeypatch, rng):
    """Forced Pallas on a small fixed-width dictionary: the dense unpack
    kernel (interpret mode off the TPU) then the XLA gather — the one route
    now that the fused unpack+gather kernel is gone (it never compiled for
    the chip)."""
    import io

    import pyarrow as pa
    import pyarrow.parquet as pq

    from parquet_tpu.io.reader import ParquetFile
    from parquet_tpu.parallel import device_reader as dr

    monkeypatch.setenv("PARQUET_TPU_PALLAS", "pallas")
    d = (rng.random(32) * 1000).astype(dtype)
    v = d[rng.integers(0, 32, 5000)]
    buf = io.BytesIO()
    pq.write_table(pa.table({"v": v}), buf, use_dictionary=True,
                   data_page_size=1 << 12)
    chunk = ParquetFile(buf.getvalue()).row_group(0).column(0)
    col = dr.decode_chunk_device(chunk, fallback=False)
    assert col.dict_indices is not None  # indices materialized: unfused
    got = np.asarray(col.values)
    if dtype == np.int64:
        got = got.view(np.int64).reshape(-1)
    np.testing.assert_array_equal(got, v)


def test_bloom_check_blocks(rng):
    from parquet_tpu.io import bloom

    filt = bloom.SplitBlockFilter.for_ndv(1000, 10)
    vals = rng.integers(0, 10**12, 500).astype(np.int64)
    hashes = bloom.xxh64_u64(vals.view(np.uint64))
    filt.insert_hashes(hashes)
    # probe: half present, half absent
    probe_vals = np.concatenate([vals[:250], rng.integers(10**13, 10**14, 250)])
    probes = bloom.xxh64_u64(probe_vals.view(np.uint64))
    block_idx, _ = filt._masks(probes)
    blocks = filt.blocks[block_idx]
    low = (probes & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    out = np.asarray(pk.bloom_check_blocks(blocks, low, interpret=True))
    expect = filt.check_hashes(probes)
    np.testing.assert_array_equal(out, expect)
    assert out[:250].all()  # no false negatives


@pytest.mark.parametrize("w", [17, 20, 24, 31])
@pytest.mark.parametrize("straddle", ["shift", "mul"])
def test_unpack_wide_straddle_variants(w, straddle, rng):
    """Both straddle formulations agree with the oracle in interpret mode
    (on-chip, 'shift' is Mosaic-miscompiled for w >= 17 — the 'mul' variant
    is the candidate dodge; scripts/mosaic_repro.py)."""
    n = 4099
    v = rng.integers(0, 1 << w, size=n, dtype=np.uint64)
    words = _pack_words(v, w)
    out = pk.unpack_bits_dense(words, n, w, interpret=True, straddle=straddle)
    np.testing.assert_array_equal(np.asarray(out), v.astype(np.uint32))


@pytest.mark.parametrize("mode,w,on_tpu_only", [
    ("", 20, True), ("", 8, True), ("auto", 31, True),
    ("pallas", 20, False), ("pallas", 8, False), ("1", 17, False),
])
def test_wide_width_routing(mode, w, on_tpu_only, monkeypatch):
    """Wide widths route like narrow ones: 'auto' takes the kernel on a
    TPU backend only, a forced 'pallas' everywhere (interpret mode off
    the TPU)."""
    from parquet_tpu.parallel import device_reader as dr
    import jax

    monkeypatch.setenv("PARQUET_TPU_PALLAS", mode)
    want = jax.default_backend() == "tpu" if on_tpu_only else True
    assert dr._use_pallas(w) is want


class _KernelBroke(RuntimeError):
    pass


def _broken(*a, **k):
    raise _KernelBroke("injected kernel failure")


def test_injected_unpack_failure_raises_dense_dict(monkeypatch, rng):
    """A Pallas unpack failure on the dense dictionary route raises: it is
    not swapped for the jnp twin behind the caller's back."""
    import io

    import pyarrow as pa
    import pyarrow.parquet as pq

    from parquet_tpu.io.reader import ParquetFile
    from parquet_tpu.parallel import device_reader as dr

    monkeypatch.setenv("PARQUET_TPU_PALLAS", "pallas")
    monkeypatch.setattr(pk, "unpack_bits_dense", _broken)
    dr._dense_unpack_pages.clear_cache()
    buf = io.BytesIO()
    pq.write_table(pa.table({"v": rng.integers(0, 900, 5000)}), buf,
                   use_dictionary=True)
    chunk = ParquetFile(buf.getvalue()).row_group(0).column(0)
    with pytest.raises(_KernelBroke):
        dr.decode_chunk_device(chunk)
    dr._dense_unpack_pages.clear_cache()


def test_injected_unpack_failure_raises_delta(monkeypatch, rng):
    """The same on the dense DELTA_BINARY_PACKED route."""
    import io

    import pyarrow as pa
    import pyarrow.parquet as pq

    from parquet_tpu.io.reader import ParquetFile
    from parquet_tpu.parallel import device_reader as dr

    monkeypatch.setenv("PARQUET_TPU_PALLAS", "pallas")
    monkeypatch.setattr(pk, "unpack_bits_dense", _broken)
    dr._delta_decode_dense.clear_cache()
    buf = io.BytesIO()
    pq.write_table(pa.table({"v": np.cumsum(rng.integers(0, 1000, 5000))}),
                   buf, use_dictionary=False,
                   column_encoding={"v": "DELTA_BINARY_PACKED"})
    chunk = ParquetFile(buf.getvalue()).row_group(0).column(0)
    with pytest.raises(_KernelBroke):
        dr.decode_chunk_device(chunk)
    dr._delta_decode_dense.clear_cache()


def test_injected_bloom_kernel_failure_raises(monkeypatch):
    """On a TPU backend the batched bloom probe runs the Pallas kernel; its
    failure raises instead of quietly taking the jnp twin."""
    import jax

    from parquet_tpu.io import bloom

    filt = bloom.SplitBlockFilter.for_ndv(1000, 10)
    hashes = bloom.xxh64_u64(np.arange(100, dtype=np.uint64))
    filt.insert_hashes(hashes)
    monkeypatch.setattr(pk, "bloom_check_blocks", _broken)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(_KernelBroke):
        filt.check_hashes_device(hashes)


def _compact_inputs(kind, n, rng):
    """The arrays of each form a device scan compacts (none: row ids only)."""
    if kind == "int32":
        return (rng.integers(-2**31, 2**31, n, dtype=np.int64)
                .astype(np.int32),)
    if kind == "float32":
        v = rng.standard_normal(n).astype(np.float32)
        v[::3] = -0.0
        v[1::5] = np.frombuffer(np.uint32(0x7FC01234).tobytes(), np.float32)
        return (v,)
    if kind in ("float64_pairs", "int64_pairs"):
        if kind == "float64_pairs":
            v = rng.standard_normal(n)
            v[::3] = -0.0
            v[1::5] = np.frombuffer(np.uint64(0x7FF8000000ABCDEF).tobytes(),
                                    np.float64)
        else:
            v = rng.integers(-2**63, 2**63 - 1, n, dtype=np.int64)
        return (v.view(np.uint32).reshape(n, 2),)
    if kind == "validity":
        return (rng.random(n) < 0.7,)
    if kind == "dict_indices":
        return (rng.integers(0, 37, n).astype(np.int32),)
    if kind == "flba":  # FIXED_LEN_BYTE_ARRAY rows: padded to whole words
        return tuple(rng.integers(0, 256, (n, L), dtype=np.uint8)
                     for L in (3, 16))
    assert kind == "row_ids"
    return ()


def _compact_mask(shape, n, rng):
    if shape == "all":
        return np.ones(n, bool)
    if shape == "none":
        return np.zeros(n, bool)
    if shape == "alternating":
        return np.arange(n) % 2 == 0
    if shape == "random15":
        return rng.random(n) < 0.15
    assert shape == "last_block"
    m = np.zeros(n, bool)
    last = (n - 1) // pk.COMPACT_BLOCK * pk.COMPACT_BLOCK
    m[last:] = rng.random(n - last) < 0.5
    m[-1] = True
    return m


@pytest.mark.parametrize("n", [
    100,  # under one block
    pk.COMPACT_BLOCK,  # exactly one block
    1000,  # not a multiple of the block: a partial last flush
    pk.COMPACT_STEP * pk.COMPACT_BLOCK + 300,  # the window crosses a step
])
@pytest.mark.parametrize("mask_shape", ["all", "none", "alternating",
                                        "random15", "last_block"])
@pytest.mark.parametrize("kind", ["int32", "float32", "float64_pairs",
                                  "int64_pairs", "validity", "dict_indices",
                                  "flba", "row_ids"])
def test_scan_compact_matches_boolean_index(kind, mask_shape, n, rng):
    """The survivors of every form, bit for bit (NaN payloads and -0.0
    included), in order, with the count; row ids made in the kernel."""
    arrays = _compact_inputs(kind, n, rng)
    mask = _compact_mask(mask_shape, n, rng)
    _check_compact(mask, arrays, not arrays)


def _check_compact(mask, arrays, row_ids):
    count, outs = pk.scan_compact(mask, arrays, row_ids=row_ids,
                                  interpret=True)
    k = int(mask.sum())
    assert int(count) == k
    wants = [a[mask] for a in arrays]
    if row_ids:
        wants.append(np.nonzero(mask)[0].astype(np.int32))
    assert len(outs) == len(wants)
    for got, want in zip(outs, wants):
        got = np.asarray(got)
        assert got.dtype == want.dtype
        assert got.shape == (len(mask),) + want.shape[1:]
        np.testing.assert_array_equal(got[:k].view(np.uint8),
                                      want.view(np.uint8))


@pytest.mark.parametrize("row_ids", [False, True])
def test_scan_compact_splits_wide_rows(row_ids, rng):
    """A scan wider than ``COMPACT_WORDS`` word rows takes one kernel call
    per group of rows over the same offsets: 44 words (a pair straddles
    the first group's end), and the row ids in the last group."""
    n = 1000
    pairs = [_compact_inputs("float64_pairs", n, rng)[0] for _ in range(20)]
    arrays = (_compact_inputs("int32", n, rng) + tuple(pairs)
              + (rng.integers(0, 256, (n, 5), dtype=np.uint8),)
              + _compact_inputs("validity", n, rng))
    assert pk.scan_compact_width(arrays) == 44 > pk.COMPACT_WORDS
    _check_compact(rng.random(n) < 0.15, arrays, row_ids)
