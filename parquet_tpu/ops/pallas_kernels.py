"""Pallas TPU kernels for the decode hot loops.

Reference parity: the role of ``internal/bitpack/unpack_int32_amd64.s`` etc.
(SURVEY.md §2.3) — hand-tuned kernels under the same interfaces as the
portable path.  Tested in interpret mode against the numpy oracle (the
purego-equivalence pattern), compiled for a described v5e by
``tests/test_tpu_compile.py`` with the package's own settings (x64 on), and
run on the chip by ``chip_smoke.py``.

Design note (TPU-first): data-dependent gathers are the enemy on a TPU VPU —
so the flagship kernel is a *gather-free* bit-unpack.  For a static width
``w``, output lane ``j`` of every 32-value group always reads packed word
``(j*w) >> 5`` at shift ``(j*w) & 31``: the access pattern is compile-time
static, and the kernel is 32 unrolled vector shift/or/mask column writes over
a (block, w)-word tile in VMEM.  The generic mixed-width path stays in
ops/device.py (XLA gathers); chunks whose streams are single-width (dict
indexes, most delta miniblocks after host bucketing) route here.

x64: the package runs with ``jax_enable_x64`` on, so index maps return
int32 constants (a bare ``0`` traces as i64, which Mosaic cannot return)
and no kernel reduces with a float op (a boolean ``all`` lowers to an f64
min).  Until PR 21 none of these kernels compiled under x64 on JAX 0.9.

KNOWN MOSAIC BUG (round 2): for w ≥ 17 the shift-formulation kernel
corrupted the word-straddling columns whose shift is 16 on a v5e.  Minimized
standalone repro: ``scripts/mosaic_repro.py`` (``MOSAIC_REPRO_ONCHIP.json``;
upstream text ``UPSTREAM_ISSUE_mosaic.md``).  :func:`unpack_bits_dense`
reformulates the straddle as a MULTIPLY (``hi * 2**(32-sh)``) for w ≥ 17,
which the same trial found exact.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MASK32 = 0xFFFFFFFF


def _row_block(i):
    """Index map of a grid over row blocks.  The constants are int32: the
    package runs with ``jax_enable_x64`` on, where a bare ``0`` traces as
    an i64 that Mosaic cannot return next to the i32 grid index."""
    return i, jnp.int32(0)


def _unpack_block_kernel(words_ref, out_ref, *, w: int, straddle: str):
    """One VMEM block: (B, w) packed uint32 words → (B, 32) values.

    ``straddle`` picks the word-straddle formulation: ``"shift"`` is the
    classic ``lo | (hi << (32-sh))``; ``"mul"`` replaces the left-shift with
    an equivalent multiply (``hi * 2**(32-sh)``) to dodge the Mosaic w ≥ 17
    shift-16 miscompile (scripts/mosaic_repro.py)."""
    words = words_ref[:]
    mask = jnp.uint32((1 << w) - 1 if w < 32 else _MASK32)
    cols = []
    for j in range(32):
        bitpos = j * w
        k = bitpos >> 5
        sh = bitpos & 31
        lo = words[:, k] >> jnp.uint32(sh)
        if sh + w > 32:
            if straddle == "mul":
                hi = words[:, k + 1] * jnp.uint32(1 << (32 - sh))
            else:
                hi = words[:, k + 1] << jnp.uint32(32 - sh)
            val = lo | hi
        else:
            val = lo
        cols.append((val & mask).reshape(-1, 1))
    out_ref[:] = jnp.concatenate(cols, axis=1)


@functools.partial(jax.jit,
                   static_argnames=("n", "w", "block", "interpret", "straddle"))
def unpack_bits_dense(packed_words: jax.Array, n: int, w: int,
                      block: int = 512, interpret: bool = False,
                      straddle: Optional[str] = None) -> jax.Array:
    """Unpack ``n`` LSB-first ``w``-bit integers from a dense stream.

    ``packed_words``: uint32[ceil(n/32)*w] (caller pads).  Returns uint32[n].
    Grid over groups of 32 values; each grid step unpacks ``block`` groups.
    ``straddle`` defaults to ``"shift"`` for w ≤ 16 and ``"mul"`` for wider
    widths (the Mosaic-miscompile dodge — module docstring).
    """
    if w == 32:
        return packed_words[:n]
    if straddle is None:
        straddle = "mul" if w >= 17 else "shift"
    groups = (n + 31) // 32
    gpad = (groups + block - 1) // block * block
    need_words = gpad * w
    if packed_words.shape[0] < need_words:
        packed_words = jnp.pad(packed_words, (0, need_words - packed_words.shape[0]))
    words2d = packed_words[: gpad * w].reshape(gpad, w)
    out = pl.pallas_call(
        functools.partial(_unpack_block_kernel, w=w, straddle=straddle),
        out_shape=jax.ShapeDtypeStruct((gpad, 32), jnp.uint32),
        grid=(gpad // block,),
        in_specs=[pl.BlockSpec((block, w), _row_block,
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((block, 32), _row_block,
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(words2d)
    return out.reshape(-1)[:n]


def unpack_bits_dense_jnp(packed_words: jax.Array, n: int, w: int) -> jax.Array:
    """jnp twin of :func:`unpack_bits_dense` — identical static-select
    formulation, no Pallas (runs anywhere; XLA fuses it to vector code)."""
    if w == 32:
        return packed_words[:n]
    groups = (n + 31) // 32
    need = groups * w
    if packed_words.shape[0] < need:
        packed_words = jnp.pad(packed_words, (0, need - packed_words.shape[0]))
    words = packed_words[:need].reshape(groups, w)
    mask = jnp.uint32((1 << w) - 1)
    cols = []
    for j in range(32):
        bitpos = j * w
        k = bitpos >> 5
        sh = bitpos & 31
        val = words[:, k] >> jnp.uint32(sh)
        if sh + w > 32:
            val = val | (words[:, k + 1] << jnp.uint32(32 - sh))
        cols.append(val & mask)
    return jnp.stack(cols, axis=1).reshape(-1)[:n]


# ---------------------------------------------------------------------------
# SBBF bloom block math (vector twin of bloom.py; probes a batch of hashes
# against gathered blocks — the gather happens outside, the 8-salt block math
# is the vector part, matching the reference's AVX2 block kernel split)
# ---------------------------------------------------------------------------

_SALT = np.array([
    0x47B6137B, 0x44974D91, 0x8824AD5B, 0xA2B7289D,
    0x705495C7, 0x2DF1424B, 0x9EFC4947, 0x5C6BFB31,
], dtype=np.uint32)


#: probes per grid step of :func:`bloom_check_blocks` (lane axis): VMEM
#: holds (8 + 1 + 1) x 2048 words per step whatever the batch size
BLOOM_BLOCK = 2048


def _bloom_check_kernel(blocks_ref, low_ref, out_ref):
    """blocks: (8, B) gathered filter blocks, one salt lane per row; low:
    (1, B) low-32 hash bits.  The all-salts test is an unrolled AND of
    integer compares — no reduction (a boolean ``all`` lowers to a float
    min that Mosaic refuses under x64)."""
    low = low_ref[0, :]
    ok = None
    for i, salt in enumerate(_SALT):
        bit = (low * jnp.uint32(int(salt))) >> jnp.uint32(27)
        mask = jnp.uint32(1) << bit
        hit = (blocks_ref[i, :] & mask) == mask
        ok = hit if ok is None else ok & hit
    out_ref[0, :] = ok.astype(jnp.int32)


def _lane_block(i):
    return jnp.int32(0), i


@functools.partial(jax.jit, static_argnames=("interpret",))
def bloom_check_blocks(blocks: jax.Array, low_bits: jax.Array,
                       interpret: bool = False) -> jax.Array:
    """Check pre-gathered SBBF blocks ``(n, 8)`` against hash low bits
    ``(n,)`` (vector part of the probe; block gather by high bits happens
    in XLA).  A grid over probe blocks bounds VMEM for any ``n``."""
    n = blocks.shape[0]
    npad = -(-max(n, 1) // BLOOM_BLOCK) * BLOOM_BLOCK
    lanes = jnp.pad(blocks.astype(jnp.uint32).T, ((0, 0), (0, npad - n)))
    low = jnp.pad(low_bits.astype(jnp.uint32), (0, npad - n)).reshape(1, -1)
    out = pl.pallas_call(
        _bloom_check_kernel,
        out_shape=jax.ShapeDtypeStruct((1, npad), jnp.int32),
        grid=(npad // BLOOM_BLOCK,),
        in_specs=[pl.BlockSpec((8, BLOOM_BLOCK), _lane_block,
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((1, BLOOM_BLOCK), _lane_block,
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, BLOOM_BLOCK), _lane_block,
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(lanes, low)
    return out[0, :n] != 0
