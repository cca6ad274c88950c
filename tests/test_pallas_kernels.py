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
