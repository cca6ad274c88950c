"""Write path: column buffers → encoded pages → row groups → footer.

Reference parity (SURVEY.md §3.2): ``GenericWriter[T].Write``/``Close`` —
deconstruct rows into per-leaf column buffers, dictionary-insert when
dict-encoding, flush row groups (encode → compress → page headers →
statistics / column+offset indexes / bloom filters), then footer (thrift
FileMetaData, "PAR1") — footer-last atomicity (SURVEY.md §5
checkpoint/resume: a crashed write is invalid, a finished one immutable).

TPU-first differences: input is columnar from the start (numpy / jax arrays /
pyarrow — no row shredding needed for flat data; Dremel levels are computed
by the vectorized write-direction math in ops/levels.py), encoders are the
vectorized numpy oracles (device encode is a later optimization — write is
not the north-star hot path), and decoded 64-bit device pairs are accepted
directly.

Pipelining (the write-side twin of io/prefetch.py): the encode phase is
pure and offset-free (:class:`_EncodedChunk`; offsets are assigned at emit
time), so ``write_row_group`` double-buffers — group N+1 encodes on the
shared pool while group N's chunks flush through ``_emit_chunk`` to the
sink.  Group N+1's encode only STARTS after group N's encode finished
(never concurrently with it), so the sticky dictionary-fallback state and
therefore the output bytes are identical with overlap on or off.  Path
sinks additionally ride a :class:`~parquet_tpu.io.sink.BufferedSink` that
coalesces page writes into vectored flushes (``os.writev`` on raw-fd
sinks).  ``PARQUET_TPU_WRITE_OVERLAP`` (``0`` off / auto / ``force``) and
``PARQUET_TPU_WRITE_BUFFER`` are the knobs — with neither pinned, the
buffer auto-tunes from observed ``sink_flushes`` per row group
(``PARQUET_TPU_WRITE_AUTOTUNE=0`` opts out);
:class:`~parquet_tpu.io.sink.WriteStats` (``writer.write_stats``) meters
the pipeline.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import struct
import threading
import time
import zlib
from collections import deque
from dataclasses import dataclass, field as dc_field
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .. import codecs
from ..errors import MAX_ROW_GROUPS, TooManyRowGroupsError
from ..format import enums, metadata as md, thrift
from ..utils.env import env_bytes, env_int, env_str
from ..utils.locks import make_condition
from ..obs.ledger import (ledger_account as _ledger_account,
                          maybe_check_pressure as _maybe_pressure)
from ..format.enums import (CompressionCodec, ConvertedType, Encoding,
                            FieldRepetitionType as Rep, PageType, Type)
from ..ops import levels as levels_ops, ref
from ..schema import schema as sch
from ..schema.schema import Leaf, Schema
from ..obs import scope as _oscope
from ..obs import trace as _otrace
from ..schema.types import LogicalKind

# shared stateless pass-through for writer methods running under a
# caller's ambient op scope (nullcontext is safely re-enterable)
_NULL_CM = contextlib.nullcontext()

DEFAULT_CREATED_BY = "parquet-tpu version 0.1.0"

# below this much input per row group, pool dispatch (and the deferred-emit
# bookkeeping of the overlap pipeline) costs more than it hides — the same
# measured crossover as the parallel-encode gate
_PARALLEL_ENCODE_BYTES = 8 << 20




def write_depth() -> int:
    """``PARQUET_TPU_WRITE_DEPTH``: how many fully-ENCODED row groups may
    queue behind a slow sink before ``write_row_group`` blocks (≥1;
    default 1 = today's behavior, emit inline on the caller thread).
    Depth ≥ 2 moves emit onto a per-writer background thread: the caller
    keeps encoding while earlier groups' pages flush — the carried-over
    ROADMAP write-overlap-depth follow-on, with the memory it pins
    bounded by the ledger's ``write.pended`` account."""
    d = env_int("PARQUET_TPU_WRITE_DEPTH")
    return d if d >= 1 else 1


def write_pended_cap_bytes() -> int:
    """``PARQUET_TPU_WRITE_PENDED``: byte cap on encoded groups queued
    for emit (default 256 MiB; the depth bound still applies).  The cap
    the ROADMAP item was waiting on — supplied by the ledger account."""
    return env_bytes("PARQUET_TPU_WRITE_PENDED")


# resource-ledger account (obs/ledger.py): bytes of encoded row groups
# queued for emit across every depth>1 writer in the process
_ACC_PENDED = _ledger_account("write.pended",
                              capacity=write_pended_cap_bytes)


def _encs_nbytes(encs) -> int:
    """Resident bytes of one collected encoded group: compressed page
    bodies + dictionary pages + bloom blobs (headers are noise)."""
    total = 0
    for enc in encs:
        if enc.dict_page is not None:
            total += len(enc.dict_page[1])
        for page in enc.pages:
            total += len(page[1])
        if enc.bloom_blob is not None:
            total += len(enc.bloom_blob)
    return total


def _overlap_mode() -> str:
    """Resolve ``PARQUET_TPU_WRITE_OVERLAP`` to off | auto | force.

    ``force`` pipelines every row group regardless of size (equivalence
    tests, benches on small data); auto (the default) overlaps only where
    it pays: >1 CPU and ≥ :data:`_PARALLEL_ENCODE_BYTES` of input per
    group.  Inside a shared-pool worker the write always stays serial —
    collecting a future from within the pool can deadlock the pool."""
    v = env_str("PARQUET_TPU_WRITE_OVERLAP").lower()
    if v in ("0", "off", "false", "no"):
        return "off"
    if v == "force":
        return "force"
    return "auto"


@dataclass
class WriterOptions:
    """Reference parity: config.go — WriterConfig + functional options
    (Compression, DataPageVersion, PageBufferSize, MaxRowsPerRowGroup,
    CreatedBy, KeyValueMetadata, SortingColumns, bloom filters...)."""

    compression: Union[str, CompressionCodec] = CompressionCodec.SNAPPY
    data_page_version: int = 1
    data_page_size: int = 1 << 20  # bytes of values per page (PageBufferSize)
    row_group_size: int = 1 << 20  # max rows per row group (MaxRowsPerRowGroup)
    dictionary: Union[bool, Sequence[str]] = True
    dictionary_page_limit: int = 1 << 20  # fall back to plain beyond this
    write_statistics: bool = True
    write_page_index: bool = True
    # spec-standard and cheap (one zlib.crc32 per page); lets readers catch
    # bit rot at the page that rotted instead of as a codec decode error
    write_crc: bool = True
    # path sinks write to <dest>.<rand>.tmp and fsync+rename on close(), so
    # the destination is either absent or a complete committed file — never
    # torn (io/sink.py).  False falls back to direct-to-path writes.
    atomic_commit: bool = True
    fsync: bool = True
    bloom_filters: Dict[str, int] = dc_field(default_factory=dict)  # path → bits/value
    created_by: str = DEFAULT_CREATED_BY
    key_value_metadata: Dict[str, str] = dc_field(default_factory=dict)
    sorting_columns: List[Tuple[str, bool, bool]] = dc_field(default_factory=list)
    # (path, descending, nulls_first) — recorded in row-group metadata
    column_encoding: Dict[str, Encoding] = dc_field(default_factory=dict)
    # page-index min/max truncation for byte-ordered types (reference
    # ColumnIndexSizeLimit; pyarrow's column_index_truncate_length). 0 = off.
    column_index_truncate_length: int = 64

    def __post_init__(self):
        if self.row_group_size < 1:
            raise ValueError("row_group_size must be >= 1")
        if self.data_page_size < 1:
            raise ValueError("data_page_size must be >= 1")
        if self.column_index_truncate_length < 0:
            raise ValueError("column_index_truncate_length must be >= 0")
        if self.data_page_version not in (1, 2):
            raise ValueError("data_page_version must be 1 or 2")

    def codec_id(self) -> CompressionCodec:
        if isinstance(self.compression, str):
            return {
                "none": CompressionCodec.UNCOMPRESSED,
                "uncompressed": CompressionCodec.UNCOMPRESSED,
                "snappy": CompressionCodec.SNAPPY,
                "gzip": CompressionCodec.GZIP,
                "zstd": CompressionCodec.ZSTD,
                "brotli": CompressionCodec.BROTLI,
                "lz4": CompressionCodec.LZ4_RAW,
                "lz4_raw": CompressionCodec.LZ4_RAW,
            }[self.compression.lower()]
        return CompressionCodec(self.compression)

    def use_dictionary(self, path: str) -> bool:
        if isinstance(self.dictionary, bool):
            return self.dictionary
        return path in self.dictionary


@dataclass
class ColumnData:
    """Normalized per-leaf input: dense present values + structure."""

    values: Any  # numpy array (fixed) or uint8 bytes for BYTE_ARRAY
    offsets: Optional[np.ndarray] = None  # BYTE_ARRAY offsets
    validity: Optional[np.ndarray] = None  # per slot
    list_offsets: Optional[np.ndarray] = None  # single-level list support
    list_validity: Optional[np.ndarray] = None
    # raw Dremel level streams (rows.py row path); when set they bypass
    # _build_levels, enabling arbitrary-depth nested writes
    def_levels: Optional[np.ndarray] = None
    rep_levels: Optional[np.ndarray] = None


@dataclass
class _EncodedChunk:
    """Offset-free result of the pure encode phase of one column chunk."""

    leaf: Leaf
    dict_page: Optional[tuple]  # (PageHeader, compressed bytes)
    pages: List[tuple]  # (PageHeader, compressed body, rows, stats, n_vals)
    stats: Optional[md.Statistics]
    bloom_blob: Optional[bytes]
    encodings_used: set
    n_slots: int


class ParquetWriter:
    """Streaming writer: accumulate columns, flush row groups, footer on close."""

    def __init__(self, sink, schema: Schema, options: Optional[WriterOptions] = None):
        from .sink import WriteStats

        self.schema = schema
        self.options = options or WriterOptions()
        self.write_stats = WriteStats()
        self._own_sink = isinstance(sink, (str, os.PathLike))
        # request scope for the writer LIFETIME (obs/scope.py): created
        # here, activated around each public method body (a writer is a
        # multi-call operation), finished at close/abort.  A caller's
        # active op_scope wins — the writer then attributes ambiently.
        self._op = (_oscope.OpScope(
            "write.file",
            {"sink": os.fspath(sink) if self._own_sink
             else type(sink).__name__})
            if _oscope.current_op() is None else None)
        if self._own_sink:
            from .sink import BufferedSink, FileSink, atomic_path_sink

            base = (atomic_path_sink(sink, fsync=self.options.fsync)
                    if self.options.atomic_commit
                    else FileSink(sink, fsync=self.options.fsync))
            try:
                # magic goes through the BASE sink, before the coalescing
                # layer: fail fast on an unwritable sink instead of
                # deferring the first write — and its error — into the
                # first row group's flush
                base.write(md.MAGIC)
            except BaseException:
                # a failed first write must not leak the freshly opened
                # file or leave its temp/partial file behind
                base.abort()
                raise
            # writeback coalescing for every path sink (buffer size 0 keeps
            # a counting pass-through, so stats stay uniform)
            self._f = BufferedSink(base, stats=self.write_stats)
            self.write_stats.bytes_flushed += len(md.MAGIC)
        else:
            self._f = sink
            self._f.write(md.MAGIC)
        self._pos = 4
        self._row_groups: List[md.RowGroup] = []
        self._column_indexes: List[List[Optional[md.ColumnIndex]]] = []
        self._offset_indexes: List[List[Optional[md.OffsetIndex]]] = []
        self._bloom_blobs: List[List[Optional[bytes]]] = []
        self._num_rows = 0
        self._closed = False
        self._aborted = False
        self._codec = codecs.get_codec(self.options.codec_id())
        self._dict_overflowed: set = set()  # sticky per-column fallback
        # buffered rows for write() accumulation
        self._buffer: Optional[Dict[str, ColumnData]] = None
        self._buffered_rows = 0
        # pipeline slot: (encode futures in leaf order, num_rows) of the one
        # row group whose background encode may still be running while its
        # predecessor's pages flush — emitted by the next write_row_group,
        # flush(), or close()
        self._inflight: Optional[Tuple[list, int]] = None
        # write-overlap depth > 1 (PARQUET_TPU_WRITE_DEPTH): a bounded
        # queue of fully-ENCODED groups drained by a per-writer emitter
        # thread, so a slow sink no longer stalls the caller between
        # groups.  Emits stay strictly FIFO on ONE thread — offsets are
        # assigned in queue order, so output bytes are identical to
        # depth 1.  Memory pinned by the queue lives in the ledger's
        # write.pended account, capped by PARQUET_TPU_WRITE_PENDED.
        self._depth = write_depth()
        self._pend_q: "deque" = deque()  # (ctx, encs, num_rows, nbytes)
        self._pend_cv = make_condition("write.pended_cv")
        self._pend_bytes = 0
        self._emit_err: Optional[BaseException] = None
        self._emitter: Optional[threading.Thread] = None
        self._emitter_stop = False
        self._discard_pended = False

    # ------------------------------------------------------------------
    def write(self, columns: Dict[str, ColumnData], num_rows: int) -> None:
        """Buffer columnar data; full row groups are written as they fill
        (MaxRowsPerRowGroup), the sub-group tail stays buffered so streaming
        writes never fragment the file into tiny groups."""
        self._check_open()
        if self._buffer is None:
            # shallow wrap: buffering never mutates array contents (extend
            # rebinds via np.concatenate, slicing takes views), so sharing
            # the caller's arrays is safe and avoids doubling peak memory on
            # one-shot writes
            self._buffer = {k: _shallow_cd(v) for k, v in columns.items()}
        else:
            for k, v in columns.items():
                _extend_cd(self._buffer[k], v)
        self._buffered_rows += num_rows
        if self._buffered_rows >= self.options.row_group_size:
            self._drain(final=False)

    def flush(self) -> None:
        """Write everything buffered, including the sub-group tail, any
        row group whose background encode is still in flight, and (depth
        > 1) every encoded group queued for the background emitter."""
        with self._op_active():
            self._check_open()
            self._drain(final=True)
            self._drain_inflight()
            self._drain_pended()

    def _check_open(self) -> None:
        # buffering rows into a finalized writer would drop them silently —
        # the buffer can never drain once close()/abort() ran
        if self._closed or self._aborted:
            raise ValueError("write on a "
                             + ("closed" if self._closed else "aborted")
                             + " writer")

    def _drain(self, final: bool) -> None:
        if self._buffer is None or self._buffered_rows == 0:
            return
        total = self._buffered_rows
        rgs = self.options.row_group_size
        emit = total if final else (total // rgs) * rgs
        if emit == 0:
            return
        if emit == total and total <= rgs:
            self.write_row_group(self._buffer, total)
            self._buffer = None
            self._buffered_rows = 0
            return
        key_leaf = {k: next((l for l in self.schema.leaves
                             if l.dotted_path == k or l.path[0] == k), None)
                    for k in self._buffer}
        ctxs = {k: {} for k in self._buffer}  # per-column slice-table cache
        for start in range(0, emit, rgs):
            end = min(start + rgs, emit)
            part = {k: _slice_cd(key_leaf[k], cd, start, end, ctxs[k])
                    if key_leaf[k] is not None else cd
                    for k, cd in self._buffer.items()}
            self.write_row_group(part, end - start)
        if emit == total:
            self._buffer = None
            self._buffered_rows = 0
        else:  # retain the tail — COPIED so the drained buffer's memory frees
            self._buffer = {
                k: _copy_cd(_slice_cd(key_leaf[k], cd, emit, total, ctxs[k]))
                if key_leaf[k] is not None else cd
                for k, cd in self._buffer.items()}
            self._buffered_rows = total - emit

    # ------------------------------------------------------------------
    def write_row_group(self, columns: Dict[str, ColumnData], num_rows: int) -> None:
        """Encode + emit one row group, pipelined (module docstring):

        1. wait for the PREVIOUS group's background encode (not its emit),
        2. submit THIS group's encode to the shared pool,
        3. emit the previous group's pages to the sink.

        Step 3's sink IO overlaps step 2's encode compute; the strict
        encode ordering (collect before submit) keeps the sticky
        dictionary-fallback state — and the output bytes — identical to
        the serial path.  The deferred group is emitted by the next call,
        :meth:`flush`, or :meth:`close`.

        Array ownership: the writer shares the caller's arrays without
        copying (the same zero-copy contract :meth:`write` has always
        had), and with overlap active this group's encode may still be
        reading them after this call returns — do not mutate arrays handed
        to the writer until it has flushed (rebinding fresh arrays per
        group, as every built-in front end does, is always safe)."""
        with self._op_active():
            self._write_row_group_impl(columns, num_rows)

    def _op_active(self):
        """Activation of this writer's own op scope — the encode pool
        submissions inside inherit it.  Checked per CALL, not just at
        construction: a caller's op_scope active right now always wins
        (the documented precedence), even for a writer built outside
        any scope."""
        if self._op is None or _oscope.current_op() is not None:
            return _NULL_CM
        return self._op.active()

    def _write_row_group_impl(self, columns: Dict[str, ColumnData],
                              num_rows: int) -> None:
        self._check_open()
        if self._emit_err is not None:
            self._raise_emit_err()
        if len(self._row_groups) + len(self._pend_q) \
                + (1 if self._inflight is not None else 0) >= MAX_ROW_GROUPS:
            raise TooManyRowGroupsError(
                f"file would exceed {MAX_ROW_GROUPS} row groups "
                "(RowGroup.ordinal is an i16); raise row_group_size")
        leaves = self.schema.leaves
        datas = []
        for leaf in leaves:
            data = columns.get(leaf.dotted_path) or columns.get(leaf.path[0])
            if data is None:
                raise KeyError(f"missing column {leaf.dotted_path!r}")
            datas.append(data)
        # encode is pure per column and offset-free (codecs are thread-safe:
        # snappy is stateless, zstd contexts are thread-local); emit is
        # serial since page offsets depend on file position.  On a
        # multi-core host the encode phase fans out across columns — the
        # native encoders and compressors release the GIL — at the cost of
        # buffering the row group's compressed pages until emit.  On one
        # core a pool measured ~15% SLOWER (GIL'd numpy dispatch), so the
        # serial one-chunk-buffered interleave is kept there.
        from ..utils.pool import available_cpus, in_shared_pool
        from ..utils.pool import submit as pool_submit

        ncpu = available_cpus()
        work_bytes = sum(getattr(np.asarray(d.values), "nbytes", 0)
                         for d in datas)
        # small row groups stay serial even on multi-core: GIL'd numpy
        # dispatch beats the parallelism below ~8 MB of input.  The fan-out
        # runs on the process-wide shared pool (utils/pool.py) — a fresh
        # ThreadPoolExecutor here cost pool setup PER ROW GROUP on
        # multi-row-group writes; mark_pooled keeps the workers' native
        # thread splits at 1 (no pool x native oversubscription).
        mode = _overlap_mode()
        pooled = (ncpu > 1 and len(leaves) > 1
                  and work_bytes >= _PARALLEL_ENCODE_BYTES
                  and not in_shared_pool())
        overlap = mode != "off" and not in_shared_pool() and (
            mode == "force"
            or (ncpu > 1 and work_bytes >= _PARALLEL_ENCODE_BYTES))
        # step 1: the previous group's encode must COMPLETE before this
        # group's encode starts — concurrent encodes would race on the
        # sticky dictionary-fallback state and make the bytes depend on
        # scheduling.  Its results are held (not yet emitted) so this
        # group's encode can be in flight behind its emit.
        prev = self._inflight
        self._inflight = None
        if prev is not None:
            prev = (self._collect(prev[0]), prev[1])
        if overlap or pooled:
            encs = [pool_submit(self._timed_encode, leaf, data, num_rows)
                    for leaf, data in zip(leaves, datas)]
        else:
            encs = self._timed_encode_iter(leaves, datas, num_rows)
        if prev is not None:
            try:
                self._dispatch_emit(*prev)
            except BaseException:
                # the previous group's emit failed with THIS group's encode
                # already submitted: tear those futures down (abort() can't
                # reach them — they were never stored in _inflight)
                if overlap or pooled:
                    from ..utils.pool import cancel_futures

                    cancel_futures(encs)
                raise
        if overlap:
            self._inflight = (encs, num_rows)
            self.write_stats.overlapped_groups += 1
        else:
            self._dispatch_emit(self._collect(encs) if pooled else encs,
                                num_rows)

    def _timed_encode(self, leaf: Leaf, data: ColumnData, num_rows: int):
        # the write.encode span runs on whatever thread encodes — pool
        # worker under the overlap pipeline, caller thread serially — so
        # encode/emit overlap shows as parallel bars on two tracks
        enc_span = (_otrace.span("write.encode", col=leaf.dotted_path,
                                 rows=num_rows)
                    if _otrace.on() else _otrace.NULL_SPAN)
        with enc_span:
            t0 = time.perf_counter()
            enc = self._encode_chunk(leaf, data, num_rows)
            return enc, time.perf_counter() - t0

    def _timed_encode_iter(self, leaves, datas, num_rows):
        """Serial path: lazy per-chunk encode (consumed interleaved with
        emit — the measured-fast one-chunk-buffered form on one core)."""
        for leaf, data in zip(leaves, datas):
            enc, dt = self._timed_encode(leaf, data, num_rows)
            self.write_stats.encode_s += dt
            yield enc

    def _collect(self, futures) -> list:
        """Resolve a submitted group's encode futures in leaf order; the
        blocking portion is the pipeline bubble (``pool_wait_s``)."""
        t0 = time.perf_counter()
        out = []
        try:
            for i, f in enumerate(futures):
                enc, dt = f.result()
                self.write_stats.encode_s += dt
                out.append(enc)
        except BaseException:
            # one chunk's encode failed: the siblings' results are dead —
            # tear them down so no exception goes unretrieved
            from ..utils.pool import cancel_futures

            cancel_futures(futures[i + 1:])
            raise
        finally:
            self.write_stats.pool_wait_s += time.perf_counter() - t0
        return out

    def _drain_inflight(self) -> None:
        if self._inflight is None:
            return
        encs, num_rows = self._inflight
        self._inflight = None
        self._dispatch_emit(self._collect(encs), num_rows)

    # -------------------------------------------------- depth>1 emit queue
    def _dispatch_emit(self, encs, num_rows: int) -> None:
        """Route one encode-complete group to emit: inline at depth 1
        (today's path, generator consumed lazily) — at depth ≥ 2, pend it
        on the bounded queue for the emitter thread.  Pending blocks while
        the queue holds ``depth`` groups or the ledger's ``write.pended``
        account is over its cap (with at least one group pended — a
        single giant group must admit alone, never deadlock)."""
        if self._depth <= 1:
            self._emit_group(encs, num_rows)
            return
        if not isinstance(encs, list):
            # serial-encode generator: materialize on the CALLER thread —
            # encode order (and the sticky dictionary-fallback state, and
            # therefore the bytes) must not depend on emitter scheduling
            encs = list(encs)
        nb = _encs_nbytes(encs)
        cap = write_pended_cap_bytes()
        ctx = contextvars.copy_context()  # the op scope follows the emit
        with self._pend_cv:
            while self._emit_err is None and self._pend_q and (
                    len(self._pend_q) >= self._depth
                    or (cap > 0 and self._pend_bytes + nb > cap)):
                self._pend_cv.wait()
            if self._emit_err is not None:
                self._raise_emit_err()
            self._pend_q.append((ctx, encs, num_rows, nb))
            self._pend_bytes += nb
            _ACC_PENDED.add(nb)
            self._ensure_emitter_locked()
            self._pend_cv.notify_all()
        _maybe_pressure()  # pended encodes are a growth site too

    def _ensure_emitter_locked(self) -> None:
        if self._emitter is None or not self._emitter.is_alive():
            self._emitter_stop = False
            self._emitter = threading.Thread(
                target=self._emit_loop, name="pq-write-emit", daemon=True)
            self._emitter.start()

    def _emit_loop(self) -> None:
        """The per-writer emitter: pops encoded groups strictly FIFO and
        runs ``_emit_group`` — the ONE thread assigning offsets and
        touching the sink while the queue drains, so output bytes are
        identical to inline emit.  A group stays at the queue head while
        it emits (its pages are still resident; the ledger must say so).
        On error the queue drops (those groups can never emit over a
        failed sink) and the error re-raises on the caller's next call."""
        while True:
            with self._pend_cv:
                while not self._pend_q and not self._emitter_stop \
                        and not self._discard_pended:
                    self._pend_cv.wait()
                if self._discard_pended or (self._emitter_stop
                                            and not self._pend_q):
                    self._drop_pended_locked()
                    return
                ctx, encs, num_rows, nb = self._pend_q[0]
            err = None
            try:
                ctx.copy().run(self._emit_group, encs, num_rows)
            # ptlint: disable=PT005 -- not swallowed: emitter-thread
            # errors go sticky into _emit_err and re-raise on the
            # caller's next write/flush/close
            except BaseException as e:  # InjectedWriterCrash included
                err = e
            with self._pend_cv:
                self._pend_q.popleft()
                self._pend_bytes -= nb
                _ACC_PENDED.sub(nb)
                if err is not None:
                    self._emit_err = err
                    self._drop_pended_locked()  # dead groups: the sink
                    # failed; release their bytes, they can never emit
                self._pend_cv.notify_all()
                if err is not None or self._emitter_stop:
                    return

    def _drop_pended_locked(self) -> None:
        while self._pend_q:
            _, _, _, nb = self._pend_q.popleft()
            self._pend_bytes -= nb
            _ACC_PENDED.sub(nb)
        self._pend_cv.notify_all()

    def _drain_pended(self) -> None:
        """Block until every pended group emitted (flush/close barrier);
        re-raises a background emit failure on the caller thread."""
        if self._depth <= 1:
            return
        with self._pend_cv:
            while self._pend_q and self._emit_err is None:
                self._pend_cv.wait()
            if self._emit_err is not None:
                self._raise_emit_err()

    def _raise_emit_err(self):
        # sticky: once the background emit failed, the file can never be
        # completed — every later call surfaces the same root cause
        raise self._emit_err

    def _stop_emitter(self) -> None:
        with self._pend_cv:
            self._emitter_stop = True
            self._pend_cv.notify_all()
            t = self._emitter
        if t is not None and t.is_alive() \
                and t is not threading.current_thread():
            t.join()

    def _teardown_pended(self) -> None:
        """Failure-path teardown (abort, failed close): queued groups must
        never emit over a sink that is about to be aborted, and their
        ledger bytes must release — a leaked ``write.pended`` balance
        would fake memory pressure for the rest of the process.  Joins
        the emitter BEFORE the caller aborts the sink, so a mid-emit
        write can't race the teardown."""
        with self._pend_cv:
            self._discard_pended = True
            self._pend_cv.notify_all()
        self._stop_emitter()
        with self._pend_cv:  # emitter gone (or never started): sweep
            self._drop_pended_locked()

    def _emit_group(self, encs, num_rows: int) -> None:
        """Serial emit of one fully-encoded row group: assign offsets,
        write pages, append row-group metadata.  ``encs`` is a list (pooled
        encodes) or the lazy serial generator."""
        opts = self.options
        chunks: List[md.ColumnChunk] = []
        cis: List[Optional[md.ColumnIndex]] = []
        ois: List[Optional[md.OffsetIndex]] = []
        blooms: List[Optional[bytes]] = []
        rg_start = self._pos
        total_bytes = 0
        total_comp = 0
        emit_span = (_otrace.span("write.emit",
                                  rg=len(self._row_groups), rows=num_rows)
                     if _otrace.on() else _otrace.NULL_SPAN)
        with emit_span:  # `with`: a failed emit must still record the span
            for enc in encs:
                t0 = time.perf_counter()
                chunk, ci, oi, bloom, ubytes, cbytes = self._emit_chunk(enc)
                self.write_stats.emit_s += time.perf_counter() - t0
                chunks.append(chunk)
                cis.append(ci)
                ois.append(oi)
                blooms.append(bloom)
                total_bytes += ubytes
                total_comp += cbytes
        sorting = [
            md.SortingColumn(
                column_idx=self.schema.leaf(p).column_index,
                descending=desc, nulls_first=nf)
            for p, desc, nf in opts.sorting_columns
        ] or None
        self._row_groups.append(md.RowGroup(
            columns=chunks, total_byte_size=total_bytes, num_rows=num_rows,
            sorting_columns=sorting, file_offset=rg_start,
            total_compressed_size=total_comp, ordinal=len(self._row_groups)))
        self._column_indexes.append(cis)
        self._offset_indexes.append(ois)
        self._bloom_blobs.append(blooms)
        self._num_rows += num_rows
        self.write_stats.row_groups += 1

    # ------------------------------------------------------------------
    def _encode_chunk(self, leaf: Leaf, data: ColumnData, num_rows: int):
        """Pure encode phase of one chunk: levels, dictionary, page bodies,
        statistics, bloom — no file offsets, so row-group columns encode
        concurrently.  Returns an :class:`_EncodedChunk` for _emit_chunk."""
        opts = self.options
        physical = leaf.physical_type
        path = leaf.dotted_path

        # ---- levels -------------------------------------------------------
        def_levels, rep_levels = _build_levels(leaf, data, num_rows)
        n_slots = len(def_levels) if def_levels is not None else num_rows
        nvalues = (int(np.count_nonzero(def_levels == leaf.max_definition_level))
                   if def_levels is not None else num_rows)

        # ---- choose encoding ---------------------------------------------
        forced = opts.column_encoding.get(path)
        dict_values = dict_offsets = indices = None
        if (forced is None and opts.use_dictionary(path)
                and physical != Type.BOOLEAN
                and path not in self._dict_overflowed):
            dict_values, dict_offsets, indices = _build_dictionary(
                leaf, data, opts.dictionary_page_limit)
            if indices is None and nvalues:
                # overflow/limit on a chunk that HAD values: later row
                # groups of this column carry the same distribution — skip
                # their builds (and the sampling probes) instead of
                # rediscovering the overflow per group; the sticky fallback
                # mainstream writers use.  An empty/all-null chunk says
                # nothing about cardinality and must not disable the column.
                self._dict_overflowed.add(path)
        if indices is not None:
            value_encoding = Encoding.RLE_DICTIONARY
        elif forced is not None:
            value_encoding = forced
        else:
            value_encoding = Encoding.PLAIN

        # ---- statistics / bloom ------------------------------------------
        stats = None
        if opts.write_statistics:
            if indices is not None and nvalues:
                # every dictionary entry is referenced by construction:
                # chunk min/max == dictionary min/max (O(dict), not O(rows))
                mn, mx = _min_max_from_dict(leaf, dict_values, dict_offsets,
                                            None, 0)
                stats = md.Statistics(null_count=n_slots - nvalues,
                                      min_value=mn, max_value=mx,
                                      min=mn, max=mx)
            else:
                stats = _compute_statistics(leaf, data, n_slots, nvalues)
        bloom_blob = None
        if path in opts.bloom_filters:
            from .bloom import build_split_block_filter

            bloom_blob = build_split_block_filter(
                leaf, data, dict_values, dict_offsets, opts.bloom_filters[path])

        encodings_used = {Encoding.RLE}
        dict_page = None
        if indices is not None:
            dict_n = (len(dict_offsets) - 1 if dict_offsets is not None
                      else len(dict_values))
            raw_dict = ref.encode_plain(
                dict_values, physical,
                offsets=dict_offsets) if physical == Type.BYTE_ARRAY else ref.encode_plain(
                dict_values, physical)
            comp = self._codec.encode(raw_dict)
            hdr = md.PageHeader(
                type=int(PageType.DICTIONARY_PAGE),
                uncompressed_page_size=len(raw_dict),
                compressed_page_size=len(comp),
                crc=(zlib.crc32(comp) & 0xFFFFFFFF) if opts.write_crc else None,
                dictionary_page_header=md.DictionaryPageHeader(
                    num_values=dict_n,
                    encoding=int(Encoding.PLAIN), is_sorted=False))
            dict_page = (hdr, comp)
            encodings_used.add(Encoding.PLAIN)
            encodings_used.add(Encoding.RLE_DICTIONARY)
        else:
            dict_n = 0
            encodings_used.add(value_encoding)

        # per-chunk order-domain ranks of the dictionary: page statistics
        # become a rank gather + min/max instead of a bincount over the
        # whole dictionary per page (local — chunks encode concurrently)
        rank_cache = None
        if opts.write_statistics and indices is not None and dict_n:
            rank_cache = _dict_rank_cache(
                leaf, dict_values, dict_offsets, dict_n)

        # ---- paginate -----------------------------------------------------
        rows_per_page = _rows_per_page(leaf, data, nvalues, n_slots, opts.data_page_size)
        pages: List[tuple] = []  # (hdr, comp_body, take_rows, pstat, n_vals)
        slot_cursor = 0
        value_cursor = 0
        row_cursor = 0
        while row_cursor < num_rows or (num_rows == 0 and not pages):
            take_rows = min(rows_per_page, num_rows - row_cursor) if num_rows else 0
            s0, s1, v0, v1 = _page_slice(leaf, data, def_levels, rep_levels,
                                         row_cursor, take_rows, slot_cursor,
                                         value_cursor)
            body, n_slot_page, n_val_page, pstat = self._encode_page(
                leaf, data, def_levels, rep_levels, s0, s1, v0, v1,
                value_encoding, indices, dict_values, dict_n, dict_offsets,
                rank_cache)
            comp_body, hdr = self._page_header(leaf, body, n_slot_page,
                                               n_val_page, value_encoding,
                                               def_levels, rep_levels, s0, s1,
                                               pstat)
            pages.append((hdr, comp_body, take_rows, pstat, n_val_page))
            row_cursor += take_rows
            slot_cursor = s1
            value_cursor = v1
            if num_rows == 0:
                break
        return _EncodedChunk(leaf=leaf, dict_page=dict_page, pages=pages,
                             stats=stats, bloom_blob=bloom_blob,
                             encodings_used=encodings_used, n_slots=n_slots)

    def _emit_chunk(self, enc: "_EncodedChunk"):
        """Serial emit phase: assign file offsets, write pages, build the
        chunk metadata + page index."""
        opts = self.options
        leaf = enc.leaf
        # deferred: algebra/__init__ imports back into io.writer (cycle)
        from ..algebra.compare import truncate_stat_max, truncate_stat_min

        chunk_start = self._pos
        # pages accumulate and hit the sink in ONE write per chunk — the
        # per-page write() call overhead was a measured ~13% of write time.
        # Offsets advance on a LOCAL cursor; self._pos commits only at the
        # write, so a mid-loop exception cannot desync the writer's position
        # from the bytes actually on disk.
        parts: List[bytes] = []
        pos = chunk_start
        uncomp_acc = 0

        def emit(header: md.PageHeader, comp_body) -> None:
            nonlocal pos, uncomp_acc
            blob = thrift.serialize(header)
            parts.append(blob)
            parts.append(comp_body)
            pos += len(blob) + len(comp_body)
            uncomp_acc += header.uncompressed_page_size + len(blob)

        dict_page_offset = None
        if enc.dict_page is not None:
            dict_page_offset = pos
            emit(*enc.dict_page)
        data_page_offset = pos
        first_row = 0
        page_locs: List[md.PageLocation] = []
        ci_nulls: List[bool] = []
        ci_mins: List[bytes] = []
        ci_maxs: List[bytes] = []
        ci_null_counts: List[int] = []
        for hdr, comp_body, take_rows, pstat, n_val_page in enc.pages:
            page_off = pos
            emit(hdr, comp_body)
            page_locs.append(md.PageLocation(
                offset=page_off,
                compressed_page_size=pos - page_off,
                first_row_index=first_row))
            if pstat is not None:
                ci_nulls.append(n_val_page == 0)
                mn, mx = pstat.min_value or b"", pstat.max_value or b""
                lim = opts.column_index_truncate_length
                if (lim and leaf.physical_type in (
                        Type.BYTE_ARRAY, Type.FIXED_LEN_BYTE_ARRAY)
                        and leaf.logical_kind not in (LogicalKind.DECIMAL,
                                                      LogicalKind.FLOAT16)):
                    # bytewise-ordered types only: decimals order by
                    # two's-complement value and float16 by float order,
                    # where a byte prefix is NOT a bound
                    mn = truncate_stat_min(mn, lim)
                    tmx = truncate_stat_max(mx, lim)
                    mx = tmx if tmx is not None else mx
                ci_mins.append(mn)
                ci_maxs.append(mx)
                ci_null_counts.append(pstat.null_count or 0)
            first_row += take_rows

        self._f.writelines(parts)
        self._pos = pos
        total_comp_size = pos - chunk_start
        meta = md.ColumnMetaData(
            type=int(leaf.physical_type),
            encodings=sorted({int(e) for e in enc.encodings_used}),
            path_in_schema=list(leaf.path),
            codec=int(opts.codec_id()),
            num_values=enc.n_slots,
            total_uncompressed_size=uncomp_acc,
            total_compressed_size=total_comp_size,
            data_page_offset=data_page_offset,
            dictionary_page_offset=dict_page_offset,
            statistics=enc.stats,
        )
        chunk = md.ColumnChunk(file_offset=chunk_start, meta_data=meta)
        ci = oi = None
        if opts.write_page_index:
            oi = md.OffsetIndex(page_locations=page_locs)
            if ci_mins:
                ci = md.ColumnIndex(
                    null_pages=ci_nulls, min_values=ci_mins,
                    max_values=ci_maxs,
                    boundary_order=int(_boundary_order(ci_mins, ci_maxs, leaf,
                                                       ci_nulls)),
                    null_counts=ci_null_counts)
        return chunk, ci, oi, enc.bloom_blob, uncomp_acc, total_comp_size

    # ------------------------------------------------------------------
    def _page_header(self, leaf, body, n_slots, n_vals, value_encoding,
                     def_levels, rep_levels, s0, s1, pstat):
        opts = self.options
        if opts.data_page_version == 2:
            # levels sit uncompressed in front of the (compressed) values
            rep_bytes, def_bytes, values = body
            comp_values = self._codec.encode(values)
            payload = rep_bytes + def_bytes + comp_values
            hdr = md.PageHeader(
                type=int(PageType.DATA_PAGE_V2),
                uncompressed_page_size=len(rep_bytes) + len(def_bytes) + len(values),
                compressed_page_size=len(payload),
                crc=(zlib.crc32(payload) & 0xFFFFFFFF) if opts.write_crc else None,
                data_page_header_v2=md.DataPageHeaderV2(
                    num_values=n_slots,
                    num_nulls=n_slots - n_vals,
                    num_rows=self._page_num_rows(leaf, rep_levels, s0, s1, n_slots),
                    encoding=int(value_encoding),
                    definition_levels_byte_length=len(def_bytes),
                    repetition_levels_byte_length=len(rep_bytes),
                    is_compressed=True,
                    statistics=pstat))
            return payload, hdr
        raw = body  # v1: levels already embedded
        comp = self._codec.encode(raw)
        hdr = md.PageHeader(
            type=int(PageType.DATA_PAGE),
            uncompressed_page_size=len(raw),
            compressed_page_size=len(comp),
            crc=(zlib.crc32(comp) & 0xFFFFFFFF) if opts.write_crc else None,
            data_page_header=md.DataPageHeader(
                num_values=n_slots,
                encoding=int(value_encoding),
                definition_level_encoding=int(Encoding.RLE),
                repetition_level_encoding=int(Encoding.RLE),
                statistics=pstat))
        return comp, hdr

    @staticmethod
    def _page_num_rows(leaf, rep_levels, s0, s1, n_slots):
        if rep_levels is None:
            return n_slots
        return int(np.count_nonzero(rep_levels[s0:s1] == 0))

    def _encode_page(self, leaf, data, def_levels, rep_levels, s0, s1, v0, v1,
                     value_encoding, indices, dict_values, dict_n=0,
                     dict_offsets=None, rank_cache=None):
        """Encode one page → body (+counts, stats).  v1: bytes; v2: 3-tuple."""
        opts = self.options
        physical = leaf.physical_type
        n_slot_page = s1 - s0
        n_val_page = v1 - v0
        # levels
        rep_bytes = b""
        def_bytes = b""
        if rep_levels is not None:
            w = _bw(leaf.max_repetition_level)
            enc = ref.encode_rle(rep_levels[s0:s1], w)
            rep_bytes = enc if opts.data_page_version == 2 else struct.pack("<I", len(enc)) + enc
        if def_levels is not None:
            w = _bw(leaf.max_definition_level)
            enc = ref.encode_rle(def_levels[s0:s1], w)
            def_bytes = enc if opts.data_page_version == 2 else struct.pack("<I", len(enc)) + enc
        # values
        if indices is not None:
            idx = indices[v0:v1]
            # bit width ≥ 1: several readers reject zero-width index streams
            width = max(_bw(max(dict_n - 1, 0)), 1)
            values = ref.encode_rle_dict_indices(idx, width)
        else:
            values = _encode_values(leaf, data, v0, v1, value_encoding)
        pstat = None
        if opts.write_statistics:
            if indices is not None:
                # dictionary-encoded page: min/max over the page's REFERENCED
                # dictionary entries, not its materialized values — the stats
                # pass drops from O(page values) to O(dict) (measured as the
                # single largest cost of writing a categorical column)
                if rank_cache is not None and v1 > v0:
                    ranks, sorted_ids = rank_cache
                    r = ranks[indices[v0:v1]]
                    sel = np.array([sorted_ids[r.min()], sorted_ids[r.max()]],
                                   dtype=np.int64)
                    mn, mx = _min_max_from_dict(
                        leaf, dict_values, dict_offsets, sel, dict_n)
                else:
                    mn, mx = _min_max_from_dict(
                        leaf, dict_values, dict_offsets,
                        indices[v0:v1], dict_n)
                pstat = md.Statistics(
                    null_count=(s1 - s0) - (v1 - v0),
                    min_value=mn, max_value=mx, min=mn, max=mx)
            else:
                pstat = self._page_statistics(leaf, data, def_levels,
                                              s0, s1, v0, v1)
        if opts.data_page_version == 2:
            return (rep_bytes, def_bytes, values), n_slot_page, n_val_page, pstat
        return rep_bytes + def_bytes + values, n_slot_page, n_val_page, pstat

    def _page_statistics(self, leaf, data, def_levels, s0, s1, v0, v1):
        nulls = (s1 - s0) - (v1 - v0)
        mn, mx = _min_max(leaf, data, v0, v1)
        return md.Statistics(
            null_count=nulls,
            min_value=mn, max_value=mx,
            min=mn, max=mx)

    # ------------------------------------------------------------------
    def close(self) -> None:
        """Finalize: drain buffers, write blooms / page index / footer, and
        commit the sink.  ``_closed`` flips only after EVERYTHING — including
        the path sink's fsync+rename — succeeded; a failure mid-footer
        aborts the sink (no committed destination file is left behind) and
        re-raises with the writer in the aborted state."""
        if self._closed:
            return
        if self._aborted:
            raise ValueError("cannot close an aborted writer")
        with self._op_active():
            try:
                self._close_impl()
            except BaseException:
                self._aborted = True
                self._teardown_pended()  # discard queued groups + release
                # their ledger bytes before the sink abort
                if self._own_sink:
                    self._f.abort()
                if self._op is not None:
                    # abort() early-returns once _aborted — finalize the
                    # op HERE or the failed write (exactly the op slow-op
                    # capture exists for) never records
                    self._op.finish()
                raise
            self._closed = True
            self._stop_emitter()  # idle by now (_close_impl drained)
            # one publish per writer: the unified registry gets this
            # write's totals exactly once, at the moment the bytes are
            # committed (publish() itself is idempotent as a backstop)
            self.write_stats.publish()
        if self._op is not None:
            self._op.finish()
        if getattr(self._f, "_tunable", False):
            # feed the flush rate back to the process-wide buffer tuner
            # (sink.py): the NEXT writer's writeback buffer grows when this
            # one still flushed many times per row group
            from .sink import write_autotune

            write_autotune().observe(self.write_stats)

    def abort(self) -> None:
        """Discard the write: no footer is serialized, a writer-owned path
        sink removes its temp (or partial) file so no destination is left
        behind, and any background encode still in flight is cancelled
        (queued tasks never run; a started one finishes into the void — it
        is pure compute that touches neither the sink nor writer state).
        Caller-owned sinks are left untouched (their bytes are the caller's
        to clean up).  Idempotent; a no-op after a successful
        :meth:`close`."""
        if self._closed or self._aborted:
            return
        self._aborted = True
        self._buffer = None
        self._buffered_rows = 0
        if self._inflight is not None:
            from ..utils.pool import cancel_futures

            encs, _ = self._inflight
            self._inflight = None
            cancel_futures(encs)
        # depth>1: discard queued groups and join the emitter before the
        # sink abort (the head group mid-emit finishes into the doomed
        # temp file — harmless, the abort unlinks it)
        self._teardown_pended()
        if self._own_sink:
            self._f.abort()
        if self._op is not None:
            self._op.finish()

    def _close_impl(self) -> None:
        self.flush()
        opts = self.options
        # bloom filters (before page index, like common writers)
        for rg_i, rg in enumerate(self._row_groups):
            for col_i, chunk in enumerate(rg.columns):
                blob = self._bloom_blobs[rg_i][col_i]
                if blob is None:
                    continue
                chunk.meta_data.bloom_filter_offset = self._pos
                self._f.write(blob)
                self._pos += len(blob)
                chunk.meta_data.bloom_filter_length = len(blob)
        # page index: all ColumnIndex then all OffsetIndex (spec layout)
        if opts.write_page_index:
            for rg_i, rg in enumerate(self._row_groups):
                for col_i, chunk in enumerate(rg.columns):
                    ci = self._column_indexes[rg_i][col_i]
                    if ci is None:
                        continue
                    blob = thrift.serialize(ci)
                    chunk.column_index_offset = self._pos
                    chunk.column_index_length = len(blob)
                    self._f.write(blob)
                    self._pos += len(blob)
            for rg_i, rg in enumerate(self._row_groups):
                for col_i, chunk in enumerate(rg.columns):
                    oi = self._offset_indexes[rg_i][col_i]
                    if oi is None:
                        continue
                    blob = thrift.serialize(oi)
                    chunk.offset_index_offset = self._pos
                    chunk.offset_index_length = len(blob)
                    self._f.write(blob)
                    self._pos += len(blob)
        fmd = md.FileMetaData(
            version=2,
            schema=self.schema.to_elements(),
            num_rows=self._num_rows,
            row_groups=self._row_groups,
            key_value_metadata=[md.KeyValue(key=k, value=v)
                                for k, v in opts.key_value_metadata.items()] or None,
            created_by=opts.created_by,
            column_orders=[md.ColumnOrder(TYPE_ORDER=md.TypeDefinedOrder())
                           for _ in self.schema.leaves])
        blob = thrift.serialize(fmd)
        # footer + length + magic in ONE write: a torn tail then lacks the
        # terminal PAR1 and can never parse as a complete file
        self._f.write(blob + struct.pack("<I", len(blob)) + md.MAGIC)
        self._f.flush()
        if self._own_sink:
            self._f.close()  # sink commit: fsync (+ atomic rename)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        # an in-flight exception means the stream is mid-row-group or
        # mid-footer: serializing a footer now would produce a VALID-LOOKING
        # file over torn data — abort (unlink temp / partial) instead.  A
        # caller who already abort()ed inside the block gets a clean exit.
        if exc_type is not None:
            self.abort()
        elif not self._aborted:
            self.close()


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _bw(v: int) -> int:
    return int(v).bit_length()


def _dict_size(dict_values) -> int:
    if isinstance(dict_values, tuple):
        return len(dict_values[1]) - 1
    return len(dict_values)


def _slice_cd(leaf: Leaf, cd: ColumnData, r0: int, r1: int,
              ctx: Optional[dict] = None) -> ColumnData:
    """Rows [r0, r1) of buffered ColumnData (row-group splitting).  Uses the
    shared Dremel span arithmetic (ops/levels); ``ctx`` (a mutable per-column
    dict) caches the row-start and cumulative-present tables so splitting a
    buffer into P parts is O(N), not O(N·P)."""
    max_def = leaf.max_definition_level
    ctx = ctx if ctx is not None else {}

    def cum_present(mask_src) -> np.ndarray:
        if "cum" not in ctx:
            cum = np.zeros(len(mask_src) + 1, np.int64)
            np.cumsum(mask_src, out=cum[1:])
            ctx["cum"] = cum
        return ctx["cum"]

    def vals_span(v0, v1):
        if cd.offsets is not None:
            offs = np.asarray(cd.offsets)
            base = int(offs[v0])
            return (np.asarray(cd.values)[base : int(offs[v1])],
                    offs[v0 : v1 + 1] - base)
        return np.asarray(cd.values)[v0:v1], None

    if cd.def_levels is not None or cd.rep_levels is not None:
        d, r = cd.def_levels, cd.rep_levels
        n_slots = len(d) if d is not None else len(r)
        if r is not None and "starts" not in ctx:
            ctx["starts"] = levels_ops.row_slot_starts(r)
        s0, s1 = levels_ops.slot_span(r, r0, r1, n_slots,
                                      row_starts=ctx.get("starts"))
        if d is None:
            v0, v1 = s0, s1
        else:
            cum = cum_present(np.asarray(d) == max_def)
            v0, v1 = int(cum[s0]), int(cum[s1])
        vals, offs = vals_span(v0, v1)
        return ColumnData(values=vals, offsets=offs,
                          def_levels=None if d is None else d[s0:s1],
                          rep_levels=None if r is None else r[s0:s1])
    if cd.list_offsets is not None:
        lo = np.asarray(cd.list_offsets)
        e0, e1 = int(lo[r0]), int(lo[r1])
        validity = cd.validity
        if validity is None:
            v0, v1 = e0, e1
        else:
            validity = np.asarray(validity)
            cum = cum_present(validity)
            v0, v1 = int(cum[e0]), int(cum[e1])
        vals, offs = vals_span(v0, v1)
        return ColumnData(
            values=vals, offsets=offs,
            validity=None if cd.validity is None else validity[e0:e1],
            list_offsets=lo[r0 : r1 + 1] - e0,
            list_validity=None if cd.list_validity is None
            else np.asarray(cd.list_validity)[r0:r1])
    if cd.validity is None:
        vals, offs = vals_span(r0, r1)
        return ColumnData(values=vals, offsets=offs)
    validity = np.asarray(cd.validity)
    cum = cum_present(validity)
    v0, v1 = int(cum[r0]), int(cum[r1])
    vals, offs = vals_span(v0, v1)
    return ColumnData(values=vals, offsets=offs, validity=validity[r0:r1])


def _shallow_cd(cd: ColumnData) -> ColumnData:
    """New ColumnData object sharing the caller's arrays (field rebinding in
    the buffer must not reach the caller; array contents are never mutated)."""
    import dataclasses

    return dataclasses.replace(cd)


def _copy_cd(cd: ColumnData) -> ColumnData:
    return ColumnData(values=np.asarray(cd.values).copy(),
                      offsets=None if cd.offsets is None else cd.offsets.copy(),
                      validity=None if cd.validity is None else cd.validity.copy(),
                      list_offsets=None if cd.list_offsets is None else cd.list_offsets.copy(),
                      list_validity=None if cd.list_validity is None else cd.list_validity.copy(),
                      def_levels=None if cd.def_levels is None else cd.def_levels.copy(),
                      rep_levels=None if cd.rep_levels is None else cd.rep_levels.copy())


def _extend_cd(dst: ColumnData, src: ColumnData) -> None:
    if (dst.def_levels is None) != (src.def_levels is None) or (
            dst.rep_levels is None) != (src.rep_levels is None):
        raise ValueError(
            "cannot mix raw-level ColumnData (rows path) with vectorized "
            "ColumnData in one buffered chunk; flush between them")
    dst.values = np.concatenate([np.asarray(dst.values), np.asarray(src.values)])
    if dst.def_levels is not None:
        dst.def_levels = np.concatenate([dst.def_levels, src.def_levels])
    if dst.rep_levels is not None:
        dst.rep_levels = np.concatenate([dst.rep_levels, src.rep_levels])
    if dst.offsets is not None:
        base = dst.offsets[-1]
        dst.offsets = np.concatenate([dst.offsets[:-1], src.offsets + base])
    if dst.validity is not None or src.validity is not None:
        a = dst.validity if dst.validity is not None else np.ones(_cd_len_v(dst) - _cd_len_v(src), bool)
        b = src.validity if src.validity is not None else np.ones(_cd_len_v(src), bool)
        dst.validity = np.concatenate([a, b])
    if dst.list_offsets is not None:
        base = dst.list_offsets[-1]
        dst.list_offsets = np.concatenate([dst.list_offsets[:-1], src.list_offsets + base])
        if dst.list_validity is not None or src.list_validity is not None:
            a = dst.list_validity if dst.list_validity is not None else None
            dst.list_validity = np.concatenate([
                a if a is not None else np.ones(len(dst.list_offsets) - len(src.list_offsets), bool),
                src.list_validity if src.list_validity is not None
                else np.ones(len(src.list_offsets) - 1, bool)])


def _cd_len_v(cd: ColumnData) -> int:
    if cd.offsets is not None:
        return len(cd.offsets) - 1
    return len(np.asarray(cd.values))


def _build_levels(leaf: Leaf, data: ColumnData, num_rows: int):
    max_def = leaf.max_definition_level
    max_rep = leaf.max_repetition_level
    if data.def_levels is not None or data.rep_levels is not None:
        return data.def_levels, data.rep_levels
    if max_rep == 0:
        if max_def == 0:
            return None, None
        # nested optional groups (struct fields): validity covers the chain;
        # intermediate struct nulls are collapsed to leaf nulls (v1 writer).
        d = levels_ops.levels_for_flat(data.validity, num_rows, max_def)
        return d, None
    if data.list_offsets is None:
        raise ValueError(f"column {leaf.dotted_path}: repeated leaf needs list_offsets")
    d, r = levels_ops.levels_for_list(
        np.asarray(data.list_offsets), data.list_validity, data.validity, leaf)
    return d, r


def _build_dictionary(leaf: Leaf, data: ColumnData, limit_bytes: int):
    physical = leaf.physical_type
    vals = np.asarray(data.values)
    if physical == Type.BYTE_ARRAY:
        from .. import native as _native

        offs = np.asarray(data.offsets, dtype=np.int64)
        n = len(offs) - 1
        if n == 0:
            return None, None, None
        max_unique = n // 2 + 16
        nat = _native.dict_build_ba(vals, offs, max_unique)
        if nat == "overflow":
            return None, None, None
        if nat is not None:
            # C++ hash-table dedup (hashprobe analog); first-seen order
            indices, first_rows = nat
            lens = (offs[1:] - offs[:-1])[first_rows]
            doffs = np.zeros(len(first_rows) + 1, np.int64)
            np.cumsum(lens, out=doffs[1:])
            if int(doffs[-1]) + 4 * len(first_rows) > limit_bytes:
                return None, None, None
            idx = np.repeat(offs[:-1][first_rows], lens) + _iota_segments(lens)
            dvals = vals[idx] if len(idx) else vals[:0]
            return dvals, doffs, indices
        items = [vals[offs[i]:offs[i + 1]].tobytes() for i in range(n)]
        uniq = sorted(set(items))
        if sum(len(u) + 4 for u in uniq) > limit_bytes or len(uniq) > max_unique:
            return None, None, None
        lookup = {u: i for i, u in enumerate(uniq)}
        indices = np.fromiter((lookup[it] for it in items), dtype=np.int64, count=n)
        dvals = np.frombuffer(b"".join(uniq), np.uint8)
        doffs = np.zeros(len(uniq) + 1, np.int64)
        np.cumsum([len(u) for u in uniq], out=doffs[1:])
        return dvals, doffs, indices
    if physical in (Type.INT96, Type.FIXED_LEN_BYTE_ARRAY):
        return None, None, None  # keep plain for v1
    if len(vals) == 0:
        return None, None, None
    max_unique = len(vals) // 2 + 16
    from .. import native as _native

    nat = _native.dict_build_fixed(vals, max_unique)
    if nat == "overflow":
        return None, None, None
    if nat is not None:
        uniq, indices = nat  # C++ hash dedup, first-seen order
    else:
        uniq, indices = np.unique(vals, return_inverse=True)
        indices = indices.astype(np.int64)
    if uniq.nbytes > limit_bytes or len(uniq) > max_unique:
        return None, None, None
    return uniq, None, indices


def _encode_values(leaf: Leaf, data: ColumnData, v0: int, v1: int,
                   encoding: Encoding) -> bytes:
    physical = leaf.physical_type
    vals = np.asarray(data.values)
    if physical == Type.BYTE_ARRAY:
        offs = np.asarray(data.offsets, dtype=np.int64)
        sub_offs = offs[v0 : v1 + 1] - offs[v0]
        sub_vals = vals[offs[v0] : offs[v1]]
        if encoding == Encoding.PLAIN:
            return ref.encode_plain(sub_vals, physical, offsets=sub_offs)
        if encoding == Encoding.DELTA_LENGTH_BYTE_ARRAY:
            return ref.encode_delta_length_byte_array(sub_vals, sub_offs)
        if encoding == Encoding.DELTA_BYTE_ARRAY:
            return ref.encode_delta_byte_array(sub_vals, sub_offs)
        raise ValueError(f"bad encoding {encoding} for BYTE_ARRAY")
    sub = vals[v0:v1]
    if encoding == Encoding.PLAIN:
        return ref.encode_plain(sub, physical)
    if encoding == Encoding.DELTA_BINARY_PACKED:
        return ref.encode_delta_binary_packed(sub.astype(np.int64))
    if encoding == Encoding.BYTE_STREAM_SPLIT:
        width = {Type.FLOAT: 4, Type.DOUBLE: 8, Type.INT32: 4, Type.INT64: 8}.get(
            physical, leaf.type_length)
        raw = np.frombuffer(np.ascontiguousarray(sub).tobytes(), np.uint8)
        return ref.encode_byte_stream_split(raw, len(sub), width)
    if encoding == Encoding.RLE and physical == Type.BOOLEAN:
        body = ref.encode_rle(sub.astype(np.int64), 1)
        return struct.pack("<I", len(body)) + body
    raise ValueError(f"unsupported write encoding {encoding!r}")


def _rows_per_page(leaf: Leaf, data: ColumnData, nvalues: int, n_slots: int,
                   page_bytes: int) -> int:
    width = {Type.BOOLEAN: 1, Type.INT32: 4, Type.INT64: 8, Type.FLOAT: 4,
             Type.DOUBLE: 8, Type.INT96: 12}.get(leaf.physical_type)
    if width is None:
        if data.offsets is not None and len(data.offsets) > 1:
            width = max(int(data.offsets[-1]) // max(len(data.offsets) - 1, 1), 1) + 4
        else:
            width = leaf.type_length or 16
    per = max(page_bytes // max(width, 1), 1)
    return per


def _page_slice(leaf, data, def_levels, rep_levels, row0, nrows, s0, v0):
    """Map a row range onto slot + value ranges.  The Dremel span arithmetic
    is shared with the streaming reader (ops/levels: slot_span /
    present_count); ``s0``/``v0`` are the caller's cursors, which advance in
    lockstep with the row cursor."""
    n_slots = len(rep_levels) if rep_levels is not None else 0
    _, s1 = levels_ops.slot_span(rep_levels, row0, row0 + nrows, n_slots)
    return s0, s1, v0, v0 + levels_ops.present_count(
        def_levels, s0, s1, leaf.max_definition_level)


def _compute_statistics(leaf, data: ColumnData, n_slots, nvalues):
    mn, mx = _min_max(leaf, data, 0, nvalues)
    return md.Statistics(null_count=n_slots - nvalues, min_value=mn,
                         max_value=mx, min=mn, max=mx)


def _dict_rank_cache(leaf: Leaf, dict_values, dict_offsets, dict_n: int):
    """Order-domain ranks of the dictionary entries, computed once per
    chunk: (ranks[id] -> rank, sorted_ids[rank] -> id).  Page statistics
    then cost a rank gather + min/max over the page's index span instead of
    a bincount over the whole dictionary per page.  None when entries are
    not cleanly rankable (NaN floats, INT96) — callers fall back to the
    bincount path."""
    from ..algebra import compare

    if leaf.physical_type == Type.INT96:
        return None
    try:
        dense = compare._dense_order_values(
            leaf, ColumnData(values=dict_values, offsets=dict_offsets),
            0, dict_n)
    except Exception:
        return None
    if dense.dtype.kind == "f" and np.isnan(dense).any():
        return None
    sorted_ids = np.argsort(dense, kind="stable")
    ranks = np.empty(dict_n, np.int64)
    ranks[sorted_ids] = np.arange(dict_n)
    return ranks, sorted_ids


def _min_max_from_dict(leaf: Leaf, dict_values, dict_offsets, idx_span,
                       dict_n: int):
    """Encoded (min, max) for a dictionary-encoded span: select the
    referenced dictionary entries (bincount over the index span; the whole
    dictionary when ``idx_span`` is None) and min/max over THOSE — O(dict)
    instead of O(values)."""
    from ..algebra import compare

    if idx_span is None:
        sel_vals, sel_offs = dict_values, dict_offsets
        count = (len(dict_offsets) - 1 if dict_offsets is not None
                 else len(dict_values))
    else:
        if len(idx_span) == 0:
            return None, None
        # tiny spans (the rank cache passes exactly {min_id, max_id}) skip
        # the dict_n-sized bincount allocation
        ids = (np.unique(idx_span) if len(idx_span) <= 64 else
               np.flatnonzero(np.bincount(idx_span, minlength=max(dict_n, 1))))
        if dict_offsets is not None:
            sel_vals, sel_offs = ref.gather_dictionary(
                (dict_values, dict_offsets), ids.astype(np.int64))
        else:
            sel_vals, sel_offs = np.asarray(dict_values)[ids], None
        count = len(ids)
    mn, mx = compare.min_max(
        leaf, ColumnData(values=sel_vals, offsets=sel_offs), 0, count)
    if mn is None:
        return None, None
    return (compare.encode_order_value(mn, leaf),
            compare.encode_order_value(mx, leaf))


def _min_max(leaf: Leaf, data: ColumnData, v0: int, v1: int):
    """Encoded (min, max) statistics bytes for a dense value span.

    Ordering and encoding delegate to algebra/compare (reference
    compare.go): unsigned logical ints compare and encode unsigned, decimals
    compare by unscaled integer, FLBA emits bytewise min/max."""
    from ..algebra import compare

    mn, mx = compare.min_max(leaf, data, v0, v1)
    if mn is None:
        return None, None
    return (compare.encode_order_value(mn, leaf),
            compare.encode_order_value(mx, leaf))


def _boundary_order(mins: List[bytes], maxs: List[bytes], leaf: Leaf,
                    null_pages: Optional[List[bool]] = None):
    from ..format.enums import BoundaryOrder
    from .statistics import decode_stat_value

    if null_pages is not None:
        # all-null pages carry placeholder min/max (null_pages flags them);
        # the ordering is defined over the remaining pages only
        mins = [m for m, np_ in zip(mins, null_pages) if not np_]
        maxs = [m for m, np_ in zip(maxs, null_pages) if not np_]
    if len(mins) <= 1:
        return BoundaryOrder.ASCENDING
    dmins = [decode_stat_value(m, leaf) for m in mins]
    dmaxs = [decode_stat_value(m, leaf) for m in maxs]
    if any(v is None for v in dmins) or any(v is None for v in dmaxs):
        return BoundaryOrder.UNORDERED
    asc = all(dmins[i] <= dmins[i + 1] for i in range(len(dmins) - 1)) and \
        all(dmaxs[i] <= dmaxs[i + 1] for i in range(len(dmaxs) - 1))
    if asc:
        return BoundaryOrder.ASCENDING
    desc = all(dmins[i] >= dmins[i + 1] for i in range(len(dmins) - 1)) and \
        all(dmaxs[i] >= dmaxs[i + 1] for i in range(len(dmaxs) - 1))
    return BoundaryOrder.DESCENDING if desc else BoundaryOrder.UNORDERED


# ---------------------------------------------------------------------------
# High-level helpers: arrow/dict-of-arrays in, file out
# ---------------------------------------------------------------------------


def write_table(table, sink, options: Optional[WriterOptions] = None,
                schema: Optional[Schema] = None):
    """Write a pyarrow.Table or {name: numpy array} mapping to Parquet.

    Reference parity: ``parquet.WriteFile`` / ``GenericWriter[T]`` front end
    (typed writes become columnar here — the TPU framework is columnar-first).
    """
    import pyarrow as pa

    if isinstance(table, dict):
        table = pa.table(table)
    if schema is None:
        schema = schema_from_arrow(table.schema)
    options = options or WriterOptions()
    w = ParquetWriter(sink, schema, options)
    try:
        n = table.num_rows
        rg_size = min(options.row_group_size, n) if n else n
        for start in range(0, max(n, 1), max(rg_size, 1)):
            end = min(start + rg_size, n) if rg_size else n
            part = table.slice(start, end - start) if (start or end < n) else table
            cols = columns_from_arrow(part, schema)
            w.write_row_group(cols, part.num_rows)
            if n == 0:
                break
        w.close()
    except BaseException:
        # same contract as the context manager: a failed write aborts (path
        # sinks unlink their temp/partial file) instead of leaking it
        w.abort()
        raise
    return w


def schema_from_arrow(aschema) -> Schema:
    """Map a pyarrow schema to a parquet schema tree."""
    import pyarrow as pa

    def field_node(f: "pa.Field") -> sch.Node:
        rep = Rep.OPTIONAL if f.nullable else Rep.REQUIRED
        t = f.type
        if pa.types.is_list(t) or pa.types.is_large_list(t):
            elem = field_node(pa.field("element", t.value_type,
                                       nullable=t.value_field.nullable))
            return sch.list_of(f.name, elem, rep)
        if pa.types.is_struct(t):
            children = [field_node(t.field(i)) for i in range(t.num_fields)]
            return sch.group(f.name, children, rep)
        if pa.types.is_map(t):
            key = field_node(pa.field("key", t.key_type, nullable=False))
            val = field_node(pa.field("value", t.item_type))
            return sch.map_of(f.name, key, val, rep)
        phys, kind, params, tl = _arrow_leaf_type(t)
        return sch.leaf(f.name, phys, rep, kind, type_length=tl, **params)

    root = sch.Node(name="schema", children=[field_node(f) for f in aschema])
    return Schema(root)


def _arrow_leaf_type(t):
    import pyarrow as pa

    K = LogicalKind
    if pa.types.is_null(t):
        # arrow's untyped all-null column: parquet Null logical type over
        # optional INT32 (pyarrow's mapping)
        return Type.INT32, K.UNKNOWN, {}, None
    if pa.types.is_boolean(t):
        return Type.BOOLEAN, K.NONE, {}, None
    if pa.types.is_int8(t):
        return Type.INT32, K.INT, {"bit_width": 8, "signed": True}, None
    if pa.types.is_int16(t):
        return Type.INT32, K.INT, {"bit_width": 16, "signed": True}, None
    if pa.types.is_int32(t):
        return Type.INT32, K.NONE, {}, None
    if pa.types.is_int64(t):
        return Type.INT64, K.NONE, {}, None
    if pa.types.is_uint8(t):
        return Type.INT32, K.INT, {"bit_width": 8, "signed": False}, None
    if pa.types.is_uint16(t):
        return Type.INT32, K.INT, {"bit_width": 16, "signed": False}, None
    if pa.types.is_uint32(t):
        return Type.INT32, K.INT, {"bit_width": 32, "signed": False}, None
    if pa.types.is_uint64(t):
        return Type.INT64, K.INT, {"bit_width": 64, "signed": False}, None
    if pa.types.is_float16(t):
        return Type.FIXED_LEN_BYTE_ARRAY, K.FLOAT16, {}, 2
    if pa.types.is_float32(t):
        return Type.FLOAT, K.NONE, {}, None
    if pa.types.is_float64(t):
        return Type.DOUBLE, K.NONE, {}, None
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        return Type.BYTE_ARRAY, K.STRING, {}, None
    if pa.types.is_binary(t) or pa.types.is_large_binary(t):
        return Type.BYTE_ARRAY, K.NONE, {}, None
    if pa.types.is_fixed_size_binary(t):
        return Type.FIXED_LEN_BYTE_ARRAY, K.NONE, {}, t.byte_width
    if pa.types.is_date32(t):
        return Type.INT32, K.DATE, {}, None
    if pa.types.is_timestamp(t):
        unit = {"ms": "timestamp_millis", "us": "timestamp_micros",
                "ns": "timestamp_nanos"}.get(t.unit, "timestamp_micros")
        return Type.INT64, unit, {"utc": t.tz is not None}, None
    if pa.types.is_time32(t):
        return Type.INT32, K.TIME_MILLIS, {"utc": True}, None
    if pa.types.is_time64(t):
        return Type.INT64, K.TIME_MICROS, {"utc": True}, None
    if pa.types.is_decimal(t):
        if t.precision <= 9:
            return Type.INT32, K.DECIMAL, {"scale": t.scale, "precision": t.precision}, None
        if t.precision <= 18:
            return Type.INT64, K.DECIMAL, {"scale": t.scale, "precision": t.precision}, None
        return Type.FIXED_LEN_BYTE_ARRAY, K.DECIMAL, \
            {"scale": t.scale, "precision": t.precision}, 16
    raise TypeError(f"unsupported arrow type {t!r}")


def columns_from_arrow(table, schema: Schema) -> Dict[str, ColumnData]:
    """Per-leaf ColumnData from an arrow table (or slice) — the single arrow
    ingestion entry point (used by write_table and TableBuffer.write_arrow),
    so struct-null def-level fidelity is applied uniformly."""
    import pyarrow as pa

    cols: Dict[str, ColumnData] = {}
    for leaf in schema.leaves:
        arr = table[leaf.path[0]]
        if isinstance(arr, pa.ChunkedArray):
            # a single-chunk column (the common write_table slice) is a
            # zero-copy view; combine_chunks would memcpy the whole slice
            arr = (arr.chunk(0) if arr.num_chunks == 1
                   else arr.combine_chunks())
        cd = _column_from_arrow(arr, leaf)
        if (len(leaf.path) > 1 and leaf.max_repetition_level == 0
                and cd.def_levels is None
                and _struct_chain_has_nulls(arr, leaf)):
            # an intermediate struct layer is null somewhere: emit exact
            # def levels so None-struct vs struct-of-None round-trips
            cd.def_levels = _struct_def_levels(arr, schema, leaf)
        cols[leaf.dotted_path] = cd
    return cols


def _struct_chain_has_nulls(arr, leaf: Leaf) -> bool:
    """True if any non-leaf struct layer on the path to ``leaf`` has nulls."""
    import pyarrow as pa

    a = arr
    for name in leaf.path[1:]:
        if not pa.types.is_struct(a.type):
            return False
        if a.null_count:
            return True
        a = a.field(name)
    return False


def _struct_def_levels(arr, schema: Schema, leaf: Leaf) -> np.ndarray:
    """Exact per-row def levels for a flat (max_rep == 0) struct chain.

    Walks the schema nodes along ``leaf.path`` top-down, counting one def
    level per OPTIONAL layer that is present, and stopping the count at the
    first null ancestor (child slots under a null parent are unspecified in
    arrow, so an ``alive`` mask gates deeper contributions).
    """
    import pyarrow as pa

    node = schema.root
    n = len(arr)
    d = np.zeros(n, np.int32)
    alive = np.ones(n, bool)
    a = arr
    for i, name in enumerate(leaf.path):
        node = next(c for c in node.children if c.name == name)
        if node.repetition == Rep.OPTIONAL:
            if a.null_count:
                ok = alive & ~np.asarray(a.is_null())
            else:
                ok = alive
            d[ok] += 1
            alive = ok
        if i + 1 < len(leaf.path):
            a = a.field(leaf.path[i + 1])
    return d


def _column_from_arrow(arr, leaf: Leaf, pos: int = 1) -> ColumnData:
    """Extract flat buffers from an arrow array for one leaf.

    ``arr`` is the top-level (or descended) arrow array; ``pos`` indexes the
    next component of ``leaf.path`` still to resolve below it. Struct layers
    descend by field name with parent-struct nulls folded into the child
    (the v1 writer collapses intermediate struct nulls to leaf nulls — see
    write_row_group); list/map machinery consumes its two path components
    ('list'/'element', 'key_value'/'key|value') per level. Deeply mixed
    chains (a list *below* a struct that is itself a list element) are not
    expressible in the single-level ColumnData form and keep the pre-existing
    pure-list-chain limitation.
    """
    import pyarrow as pa
    import pyarrow.compute as pc

    t = arr.type
    if pa.types.is_struct(t):
        if arr.null_count and leaf.max_repetition_level > 0:
            raise NotImplementedError(
                f"column {leaf.dotted_path}: null struct values mixed with "
                "repetition are not supported by the arrow ingestion path "
                "(write via rows/typed API for exact def levels)")
        # fold parent-struct nulls into the child so dense value extraction
        # (drop_null below) excludes slots under a null ancestor; exact def
        # levels for the chain are emitted separately (_struct_def_levels)
        child = arr.field(leaf.path[pos])
        if arr.null_count:
            child = pc.if_else(pc.is_valid(arr), child,
                               pa.scalar(None, type=child.type))
        return _column_from_arrow(child, leaf, pos + 1)
    if pa.types.is_list(t) or pa.types.is_large_list(t) or pa.types.is_map(t):
        # walk the (possibly multi-level) list chain collecting per-level
        # offsets/validity, then emit either the single-level ColumnData form
        # or raw Dremel levels (levels_for_nested) for depth > 1
        offsets_per_level, validity_per_level = [], []
        a = arr
        while True:
            ty = a.type
            if pa.types.is_map(ty):
                child = a.keys if leaf.path[pos + 1] == "key" else a.items
            elif pa.types.is_list(ty) or pa.types.is_large_list(ty):
                child = a.values
            else:
                break
            lv = ~np.asarray(a.is_null()) if a.null_count else None
            raw = np.asarray(a.offsets, dtype=np.int64)
            pos += 2
            if raw[0] != 0 or len(child) != raw[-1]:  # sliced parent array
                child = child.slice(raw[0], raw[-1] - raw[0])
            offs = raw - raw[0]
            if lv is not None:
                # arrow permits a NULL list's offset span to still cover
                # child values; parquet has no slots for them — drop the
                # spanned values and zero the null rows' lengths
                lens = np.diff(offs)
                if lens[~lv].any():
                    child = child.filter(pa.array(np.repeat(lv, lens)))
                    offs = np.zeros(len(offs), np.int64)
                    np.cumsum(np.where(lv, lens, 0), out=offs[1:])
            offsets_per_level.append(offs)
            validity_per_level.append(lv)
            a = child
        inner = _column_from_arrow(a, leaf, pos)
        if len(offsets_per_level) == 1:
            inner.list_offsets = offsets_per_level[0]
            inner.list_validity = validity_per_level[0]
            return inner
        d, r = levels_ops.levels_for_nested(
            offsets_per_level, validity_per_level, inner.validity, leaf)
        inner.def_levels = d
        inner.rep_levels = r
        return inner
    if pa.types.is_null(t):  # untyped all-null column: zero dense values
        return ColumnData(values=np.empty(0, np.int32),
                          validity=np.zeros(len(arr), bool))
    validity = None
    if arr.null_count:
        validity = ~np.asarray(arr.is_null())
    if pa.types.is_string(t) or pa.types.is_binary(t) or \
            pa.types.is_large_string(t) or pa.types.is_large_binary(t):
        # dense present values, read straight from the arrow buffers
        # (offsets + data) — no python bytes objects on the write hot path
        dense = arr.drop_null()
        large = pa.types.is_large_string(t) or pa.types.is_large_binary(t)
        bufs = dense.buffers()
        odt = np.int64 if large else np.int32
        o0 = dense.offset
        offs_raw = np.frombuffer(bufs[1], odt)[o0 : o0 + len(dense) + 1] \
            .astype(np.int64)
        data = np.frombuffer(bufs[2], np.uint8)[offs_raw[0] : offs_raw[-1]] \
            if len(dense) else np.empty(0, np.uint8)
        return ColumnData(values=data, offsets=offs_raw - offs_raw[0],
                          validity=validity)
    if pa.types.is_boolean(t):
        dense = arr.drop_null()
        return ColumnData(values=np.asarray(dense), validity=validity)
    if pa.types.is_float16(t):
        dense = np.asarray(arr.drop_null()).astype(np.float16)
        return ColumnData(values=dense.view(np.uint8).reshape(-1, 2), validity=validity)
    if pa.types.is_fixed_size_binary(t):
        dense = arr.drop_null()
        w = t.byte_width
        flat = np.frombuffer(dense.buffers()[1], np.uint8)[
            dense.offset * w : (dense.offset + len(dense)) * w]
        return ColumnData(values=flat.reshape(-1, w), validity=validity)
    if pa.types.is_decimal(t):
        dense = arr.drop_null()
        ints = np.asarray([int(x.as_py().scaleb(t.scale)) for x in dense], dtype=np.int64)
        phys = leaf.physical_type
        if phys == Type.INT32:
            return ColumnData(values=ints.astype(np.int32), validity=validity)
        if phys == Type.INT64:
            return ColumnData(values=ints, validity=validity)
        w = leaf.type_length
        be = np.zeros((len(ints), w), np.uint8)
        for k in range(w):
            be[:, w - 1 - k] = (ints >> (8 * k)) & 0xFF
        return ColumnData(values=be, validity=validity)
    # fixed-width numerics incl. date/time/timestamp
    dense = arr.drop_null()
    np_arr = np.asarray(dense.cast(_storage_type(t)))
    return ColumnData(values=np_arr, validity=validity)


def _storage_type(t):
    import pyarrow as pa

    if pa.types.is_date32(t):
        return pa.int32()
    if pa.types.is_timestamp(t) or pa.types.is_time64(t):
        return pa.int64()
    if pa.types.is_time32(t):
        return pa.int32()
    return t


def _iota_segments(lengths: np.ndarray) -> np.ndarray:
    total = int(lengths.sum())
    if total == 0:
        return np.empty(0, np.int64)
    seg_starts = np.zeros(len(lengths), np.int64)
    np.cumsum(lengths[:-1], out=seg_starts[1:])
    return np.arange(total, dtype=np.int64) - np.repeat(seg_starts, lengths)
