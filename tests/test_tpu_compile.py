"""The main path's Pallas kernels compile for a TPU v5e, with the package
imported (so ``jax_enable_x64`` is on, as in production).

Ahead-of-time compiles for a *described* v5e (no chip needed): what the
TPU compiler refuses here — an i64 index map, a float reduction, more VMEM
than a kernel may use — it would refuse on the chip, where interpret mode
hides it.  The topology is described in a module fixture, never at import:
only one process may load libtpu, and under xdist only the worker given
this file should.  The describe call and every compile run on a worker
thread with a deadline, so a libtpu hang fails the test instead of running
the suite into the driver's clock.
"""

import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parquet_tpu  # noqa: F401  (x64 on, as the package runs)
from parquet_tpu.ops import pallas_kernels as pk

DESCRIBE_S = 120
# each compile here takes ~2 s; the bound also catches compile-time
# blow-ups (a PLAIN 8-byte bitcast over a sliced buffer took 319 s)
COMPILE_S = 60
N_VALUES = 8 << 20  # 8M values: the real dense-unpack size
ROWS = 1_000_000  # one lineitem row group


def bounded(fn, limit_s, what):
    box = {}

    def run():
        try:
            box["out"] = fn()
        except BaseException as e:  # re-raised on the test thread
            box["err"] = e

    th = threading.Thread(target=run, daemon=True)
    th.start()
    th.join(limit_s)
    if th.is_alive():
        pytest.fail(f"{what} still running after {limit_s}s")
    if "err" in box:
        raise box["err"]
    return box["out"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def describe():
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            return e

    got = bounded(describe, DESCRIBE_S, "describing a v5e:2x2 topology")
    if isinstance(got, Exception):
        pytest.skip(f"no v5e:2x2 topology can be described here: {got}")
    yield got
    jax.config.update("jax_enable_compilation_cache", True)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def compile_for(fn, *shapes):
    """AOT-compile ``fn`` at ``shapes``; returns the compiled HLO text."""
    return bounded(lambda: jax.jit(fn).lower(*shapes).compile().as_text(),
                   COMPILE_S, f"compiling {getattr(fn, '__name__', fn)}")


def test_package_runs_with_x64():
    assert jax.config.jax_enable_x64


@pytest.mark.parametrize("w", [1, 8, 17, 31])
def test_unpack_bits_dense_compiles(w, one_chip):
    words = jax.ShapeDtypeStruct((N_VALUES // 32 * w,), jnp.uint32,
                                 sharding=one_chip)
    hlo = compile_for(lambda x: pk.unpack_bits_dense(x, N_VALUES, w), words)
    assert "tpu_custom_call" in hlo  # the Mosaic kernel, not a fallback


@pytest.mark.parametrize("n", [pk.BLOOM_BLOCK, 100_000])
def test_bloom_check_blocks_compiles(n, one_chip):
    blocks = jax.ShapeDtypeStruct((n, 8), jnp.uint32, sharding=one_chip)
    low = jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=one_chip)
    hlo = compile_for(pk.bloom_check_blocks, blocks, low)
    assert "tpu_custom_call" in hlo


def staged(nbytes, sharding):
    """The staged uint8 buffer the device reader puts: its power-of-two
    bucket (``ops.device.pad_to_bucket``)."""
    from parquet_tpu.ops.device import pad_to_bucket

    n = len(pad_to_bucket(np.zeros(nbytes, np.uint8)))
    return jax.ShapeDtypeStruct((n,), jnp.uint8, sharding=sharding)


@pytest.mark.parametrize("pallas", [True, False])
def test_dense_unpack_pages_compiles(pallas, one_chip):
    """The jitted dense dict-index decode step at a lineitem date column's
    real width: ~4,000 distinct days -> 12-bit indexes, 1M-row row group
    in two 32-aligned pages."""
    from parquet_tpu.parallel.device_reader import _dense_unpack_pages

    w = 12
    total = -(-ROWS // 32) * 32
    nbytes = total * w // 8
    pages = ((0, 524_288), (524_288, ROWS - 524_288))
    hlo = compile_for(
        lambda b: _dense_unpack_pages(b, nbytes, total, w, pages, pallas,
                                      False), staged(nbytes, one_chip))
    assert ("tpu_custom_call" in hlo) is pallas


@pytest.mark.parametrize("n", [ROWS, 758_335])
@pytest.mark.parametrize("width", [4, 8])
def test_plain_fixed_width_compiles(width, n, one_chip):
    """PLAIN int32/int64/double chunk decode from the exact-length uint32
    words the device reader stages, at a row group's 1M values and at
    lineitem SF1's last row group: no bucket, and no compile blow-up at
    an arbitrary length (u8 bitcasts of sliced buffers took minutes)."""
    from parquet_tpu.ops import device as dev

    words = jax.ShapeDtypeStruct((n * width // 4,), jnp.uint32,
                                 sharding=one_chip)
    if width == 8:
        hlo = compile_for(lambda w: dev.fixed64_pairs(w, n), words)
    else:
        hlo = compile_for(lambda w: dev.bitcast_fixed32(w, n, "int32"), words)
    assert "u8[" not in hlo  # no byte view of the values


def test_rle_expand_compiles_fast(one_chip):
    """A row group's def levels or dictionary indices through the run
    table: a k-sized scatter and a blocked prefix sum over ``[3, n]``
    compile in about a second for a described v5e."""
    from parquet_tpu.ops import device as dev

    k = 32_768
    runs = [jax.ShapeDtypeStruct((k,), dt, sharding=one_chip)
            for dt in (jnp.int32, jnp.uint8, jnp.int32, jnp.int32, jnp.int32)]
    bounded(lambda: jax.jit(lambda b, *r: dev.rle_expand(b, ROWS, *r)).lower(
        staged(ROWS * 2, one_chip), *runs).compile(), 15,
        "compiling rle_expand")


def test_prefix_sum_compiles_fast(one_chip):
    """The scan's survivor compaction prefix-sums a row group's mask: a
    flat ``jnp.cumsum`` of 1M values takes ~20 s to compile for the TPU,
    the blocked one under a second (AOT, PR 21)."""
    from parquet_tpu.ops import device as dev

    mask = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    bounded(lambda: jax.jit(dev.cumsum).lower(mask).compile(), 15,
            "compiling the blocked prefix sum")


Q6_SPAN = 1 << 20  # one lineitem row group of Q6's file


def q6_span(sharding, columns=3):
    """A Q6 span's mask and its 64-bit output columns as (n, 2) uint32
    pairs (W = 2 a column)."""
    mask = jax.ShapeDtypeStruct((Q6_SPAN,), jnp.bool_, sharding=sharding)
    return [mask] + [jax.ShapeDtypeStruct((Q6_SPAN, 2), jnp.uint32,
                                          sharding=sharding)
                     for _ in range(columns)]


@pytest.mark.parametrize("row_ids", [False, True])
def test_scan_compact_compiles_without_scatter(row_ids, one_chip):
    """Q6's span, three 64-bit output columns (W = 6), with and without
    the row ids a plain string output makes in the kernel.  The eager
    compaction program is the Mosaic kernel and holds no scatter, which
    XLA runs as a serial loop on the TPU."""
    import re

    mask, *pairs = q6_span(one_chip)
    assert pk.scan_compact_width(pairs) == 6
    hlo = compile_for(lambda m, *a: pk.scan_compact(m, a, row_ids=row_ids),
                      mask, *pairs)
    assert "tpu_custom_call" in hlo
    assert not re.search(r"\bscatter\(", hlo)


def test_scan_compact_compiles_inlined(one_chip):
    """As a fused span program runs it: the mask made from a key column
    and the kernel inlined into the caller's jit."""
    import re

    mask, *pairs = q6_span(one_chip)
    key = jax.ShapeDtypeStruct((Q6_SPAN,), jnp.int32, sharding=one_chip)

    def span(k, *a):
        return pk.scan_compact((k >= 8766) & (k < 9131), a)

    hlo = compile_for(span, key, *pairs)
    assert "tpu_custom_call" in hlo
    assert not re.search(r"\bscatter\(", hlo)


def test_scan_compact_compiles_wide(one_chip):
    """A scan of 100 64-bit columns (W = 200) stays within VMEM: one kernel
    call per ``COMPACT_WORDS`` word rows, not one block of all 200."""
    mask, *pairs = q6_span(one_chip, columns=100)
    hlo = compile_for(lambda m, *a: pk.scan_compact(m, a, row_ids=True),
                      mask, *pairs)
    assert hlo.count("tpu_custom_call") >= -(-201 // pk.COMPACT_WORDS)
