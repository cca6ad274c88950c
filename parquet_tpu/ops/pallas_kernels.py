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


# ---------------------------------------------------------------------------
# Survivor compaction of a device scan: rows whose mask is set move to a
# prefix, in order, without a scatter or a gather.  XLA lowers a scatter
# with repeated indices to a serial loop on the TPU (~70 ns a row); here a
# one-hot placement matrix moves each block's rows on the MXU.
# ---------------------------------------------------------------------------

#: rows of a block, and the width of the placement matrix: v5e's MXU is 128
#: wide, and building the (2B, B) matrix costs 2B compares a row
COMPACT_BLOCK = 128
#: blocks per grid step (16,384 rows)
COMPACT_STEP = 128
#: survivor offsets per SMEM block: a rank-1 s32 array is tiled by 1,024
#: in HBM, and Mosaic takes rank-1 SMEM blocks only at that tiling
_COMPACT_OFFS = 1024
#: word rows per kernel call: a step's input block takes 64 KiB a word row,
#: twice over (double-buffered), so 32 rows hold it near 4 MiB of v5e's
#: 16 MiB scoped VMEM whatever the scan's width; wider scans make one call
#: per group of rows over the same offsets
COMPACT_WORDS = 32


def _scan_compact_kernel(start_ref, end_ref, mask_ref, x_ref, out_ref,
                         win_ref, stage_ref, sem, nflush_ref, excl_ref, *,
                         wp: int, row_ids: bool, blocks: int):
    """One grid step: ``COMPACT_STEP`` blocks of B rows, in order.

    ``x_ref`` holds the step's words as (blocks, wp, B); ``start_ref`` /
    ``end_ref`` each block's first and one-past-last output slot.  The
    window (``win_ref``, 2B output rows as four byte planes of f32) holds
    the output block that begins at ``start & -B``: a block's survivors
    land there through ``planes @ P.T`` with ``P[j, i] = (j == slot of row
    i)``.  Bytes are exact in bf16, and each output element has one
    non-zero term, so the words come back bit for bit.  Once the window's
    first B rows are full they go to ``out_ref`` (HBM) by DMA, through two
    staging slots, and the window shifts down by B."""
    B = COMPACT_BLOCK
    i32 = jnp.int32
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        win_ref[...] = jnp.zeros_like(win_ref)
        nflush_ref[0] = i32(0)

    # exclusive prefix of each block's mask: one matmul with a strictly
    # upper-triangular ones matrix (exact in f32: sums stay under 2^24)
    rows = jax.lax.broadcasted_iota(i32, (B, B), 0)
    cols = jax.lax.broadcasted_iota(i32, (B, B), 1)
    excl_ref[...] = jnp.dot(
        mask_ref[...].astype(jnp.float32).astype(jnp.bfloat16),
        (rows < cols).astype(jnp.bfloat16),
        preferred_element_type=jnp.float32)
    slot = jax.lax.broadcasted_iota(i32, (2 * B, B), 0)

    def copy(k, group):
        return pltpu.make_async_copy(stage_ref.at[k], out_ref.at[group],
                                     sem.at[k])

    def flush(base):
        f = nflush_ref[0]
        k = f & i32(1)

        @pl.when(f >= 2)
        def _reuse():  # the slot's previous copy must have landed
            copy(k, i32(0)).wait()

        b = win_ref[:, :B].astype(i32).astype(jnp.uint32)
        word = b[0:wp]
        for p in range(1, 4):
            word = word | (b[p * wp:(p + 1) * wp] << jnp.uint32(8 * p))
        stage_ref[k] = word
        group = jax.lax.shift_right_logical(base, i32(B.bit_length() - 1))
        copy(k, group).start()
        win_ref[:, :B] = win_ref[:, B:]
        win_ref[:, B:] = jnp.zeros((4 * wp, B), jnp.float32)
        nflush_ref[0] = f + 1

    def block(s, carry):
        o = (t & i32(_COMPACT_OFFS // COMPACT_STEP - 1)) * COMPACT_STEP + s
        g0 = start_ref[o]
        base = g0 & i32(-B)
        keep = mask_ref[pl.ds(s, 1), :]
        excl = excl_ref[pl.ds(s, 1), :].astype(i32)
        tgt = jnp.where(keep != 0, (g0 - base) + excl, i32(-1))  # (1, B)
        place = (slot == tgt).astype(jnp.bfloat16)  # (2B, B)
        words = x_ref[s]  # (wp, B)
        if row_ids:  # the last word row carries each row's index
            rid = ((t * COMPACT_STEP + s) * B
                   + jax.lax.broadcasted_iota(i32, (wp, B), 1))
            last = jax.lax.broadcasted_iota(i32, (wp, B), 0) == wp - 1
            words = jnp.where(last, rid.astype(jnp.uint32), words)
        planes = jnp.concatenate(
            [((words >> jnp.uint32(8 * p)) & jnp.uint32(255)).astype(i32)
             for p in range(4)], axis=0)
        win_ref[...] += jax.lax.dot_general(
            planes.astype(jnp.float32).astype(jnp.bfloat16), place,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

        @pl.when((end_ref[o] & i32(-B)) > base)
        def _full():
            flush(base)

        return carry

    jax.lax.fori_loop(0, jnp.minimum(i32(COMPACT_STEP),
                                     blocks - t * COMPACT_STEP), block, i32(0))

    @pl.when(t == pl.num_programs(0) - 1)
    def _last():
        total = end_ref[(blocks - 1) % _COMPACT_OFFS]

        @pl.when((total & i32(B - 1)) > 0)
        def _partial():
            flush(total & i32(-B))

        f = nflush_ref[0]

        @pl.when(f >= 1)
        def _drain1():
            copy((f - 1) & i32(1), i32(0)).wait()

        @pl.when(f >= 2)
        def _drain2():
            copy(f & i32(1), i32(0)).wait()


def _word_rows(a: jax.Array) -> jax.Array:
    """A compacted array as (n, c) uint32 words: 32-bit values bitcast,
    bools as 0/1, byte rows (FIXED_LEN_BYTE_ARRAY) padded to whole words."""
    if a.dtype == jnp.bool_:
        a = a.astype(jnp.uint32)
    if a.dtype.itemsize == 1:
        a = jnp.pad(a, ((0, 0), (0, -a.shape[1] % 4)))
        a = a.reshape(a.shape[0], -1, 4)
    w = jax.lax.bitcast_convert_type(a, jnp.uint32)
    return w.reshape(w.shape[0], -1)


def _from_groups(g: jax.Array, like: jax.Array, n: int) -> jax.Array:
    """(G, c, B) compacted words → ``like``'s dtype and trailing shape."""
    w = g.transpose(0, 2, 1).reshape(-1, g.shape[1])[:n]
    if like.dtype == jnp.bool_:
        return w[:, 0] != 0
    if like.dtype.itemsize == 1:
        b = jax.lax.bitcast_convert_type(w, like.dtype).reshape(n, -1)
        return b[:, :like.shape[1]]
    out = jax.lax.bitcast_convert_type(w, like.dtype)
    return out.reshape((n,) + like.shape[1:])


def scan_compact_width(arrays) -> int:
    """W, the uint32 words a row of ``arrays`` takes in :func:`scan_compact`
    (a row of bytes is padded to whole words)."""
    return sum(-(-int(np.prod(a.shape[1:], dtype=np.int64))
                 * a.dtype.itemsize // 4) for a in arrays)


@functools.partial(jax.jit, static_argnames=("row_ids", "interpret"))
def scan_compact(mask: jax.Array, arrays, row_ids: bool = False,
                 interpret: bool = False):
    """Move the rows of every array in ``arrays`` whose ``mask`` is set to
    a prefix, in order, in one pass: ``(count, compacted arrays)``.

    ``arrays``: a tuple of (n,) or (n, c) arrays of 32-bit values, bools or
    bytes (64-bit columns as (n, 2) uint32 pairs).  Each comes back in its
    own dtype and shape, n rows long; rows past ``count`` hold no data.
    With ``row_ids`` a last int32 array holds the surviving rows' indices.

    The arrays travel as W rows of uint32 words in groups of B rows,
    ``(G, W, B)``: an (n, 2) pair array laid out by the TPU as a lo and a
    hi row per 128 values is that form already, so no lane-padded
    transpose is made.  Each group's survivor offsets come from an XLA
    reduction and prefix over the mask, as scalars for the kernel's DMA
    addresses; the placement itself runs in :func:`_scan_compact_kernel`,
    one call per ``COMPACT_WORDS`` word rows (the row ids take a row).
    """
    from . import device as dev

    B = COMPACT_BLOCK
    n = mask.shape[0]
    arrays = tuple(arrays)
    count = jnp.sum(mask.astype(jnp.int32), dtype=jnp.int32)
    if n == 0 or not (arrays or row_ids):
        return count, arrays + ((jnp.zeros(n, jnp.int32),) if row_ids else ())
    step = COMPACT_STEP * B
    npad = -(-n // step) * step
    G = npad // B
    blocks = -(-n // B)
    keep = jnp.pad(mask.astype(jnp.int32), (0, npad - n)).reshape(G, B)
    per = jnp.pad(jnp.sum(keep, axis=1, dtype=jnp.int32),
                  (0, -G % _COMPACT_OFFS))
    end = dev.cumsum(per)
    start = end - per
    parts = []
    for a in arrays:
        w = _word_rows(a)
        parts.append(jnp.pad(w, ((0, npad - n), (0, 0)))
                     .reshape(G, B, w.shape[1]).transpose(0, 2, 1))
    words = (jnp.concatenate(parts, axis=1) if parts
             else jnp.zeros((G, 0, B), jnp.uint32))
    width = words.shape[1]

    steps_per_offs = _COMPACT_OFFS // COMPACT_STEP  # a power of two

    def offs(i):  # a shift: `//` on a traced int32 fails to lower under x64
        return (jax.lax.shift_right_logical(
            i, jnp.int32(steps_per_offs.bit_length() - 1)),)

    def call(x, rid):
        wp = x.shape[1]
        return pl.pallas_call(
            functools.partial(_scan_compact_kernel, wp=wp, row_ids=rid,
                              blocks=blocks),
            out_shape=jax.ShapeDtypeStruct((G, wp, B), jnp.uint32),
            grid=(npad // step,),
            in_specs=[pl.BlockSpec((_COMPACT_OFFS,), offs,
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec((_COMPACT_OFFS,), offs,
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec((COMPACT_STEP, B), _row_block,
                                   memory_space=pltpu.VMEM),
                      pl.BlockSpec((COMPACT_STEP, wp, B),
                                   lambda i: (i, jnp.int32(0), jnp.int32(0)),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((4 * wp, 2 * B), jnp.float32),
                            pltpu.VMEM((2, wp, B), jnp.uint32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((COMPACT_STEP, B), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(start, end, keep, x)

    # groups of at most COMPACT_WORDS word rows, each padded to a multiple
    # of 8 (a sublane tile); the row ids take the last row of the last group
    total = width + row_ids
    done, rid = [], None
    for r0 in range(0, total, COMPACT_WORDS):
        r1 = min(r0 + COMPACT_WORDS, total)
        last = row_ids and r1 == total
        x = words[:, r0:min(r1, width)]
        wp = -(-(r1 - r0) // 8) * 8
        out = call(jnp.pad(x, ((0, 0), (0, wp - x.shape[1]), (0, 0))), last)
        done.append(out[:, :x.shape[1]])
        if last:
            rid = out[:, wp - 1:wp]
    out = jnp.concatenate(done, axis=1)
    outs, r = [], 0
    for a, p in zip(arrays, parts):
        outs.append(_from_groups(out[:, r:r + p.shape[1]], a, n))
        r += p.shape[1]
    if row_ids:
        outs.append(_from_groups(rid, jnp.zeros((0,), jnp.int32), n))
    return count, tuple(outs)
