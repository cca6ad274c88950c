"""File / row-group / column-chunk / page readers (L3) + host decode loop.

Reference parity (SURVEY.md §3.1): ``OpenFile`` validates the PAR1 magic at
both ends, thrift-decodes the footer, and lazily exposes
``RowGroup → ColumnChunk → Pages``; ``filePages.ReadPage`` is the per-page hot
loop (header → raw bytes → CRC → decompress → levels → values).  Here the host
path decodes with the numpy oracle in ``ops/ref.py``; the TPU path
(``parallel/device_reader.py``) replaces step 5-6 with batched device kernels —
the same rerouting point the north star names (``encoding.Encoding`` /
``compress.Codec`` registries).
"""

from __future__ import annotations

import struct
import time
import zlib
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Tuple, Union

import numpy as np

from .. import codecs, native as _native
from ..format import enums, metadata as md, thrift
from ..format.enums import Encoding, PageType, Type
from ..ops import levels as levels_ops, ref
from ..schema.schema import Leaf, Schema
from ..utils.env import env_bool
from ..obs import scope as _oscope
from ..obs import trace as _otrace
from ..obs.metrics import histogram as _ohistogram

# resolved once: per-read observation must not take the registry's
# get-or-create lock (only the metric's own)
_M_READ_FILE_S = _ohistogram("read.file_s")
from ..utils.debug import counters
from .column import Column, concat_columns
from .source import Source, as_source


from ..errors import (CorruptedError, DeadlineError,  # noqa: F401
                      MAX_COLUMN_INDEX_SIZE,  # re-exported: historical home
                      MAX_PAGE_HEADER_SIZE, MAX_PAGE_SIZE, ReadError)
from .faults import (FaultPolicy, PolicySource, ReadReport, read_context,
                     resolve_policy)


def _corrupt(msg: str, page_offset: Optional[int] = None) -> CorruptedError:
    """CorruptedError tagged with the failing page's absolute offset; the
    resilience layer's :func:`read_context` lifts the tag into the
    :class:`ReadError` it raises, so every surfaced failure is locatable."""
    e = CorruptedError(msg)
    if page_offset is not None:
        e.page_offset = page_offset
    return e


@dataclass
class ReadOptions:
    """Reference parity: config.go — FileConfig/ReaderConfig functional options."""

    skip_page_index: bool = True  # lazy: load on demand (reference: SkipPageIndex)
    skip_bloom_filters: bool = True
    verify_crc: bool = False
    footer_read_size: int = 64 * 1024  # speculative tail read to avoid 2 IOs


# ---------------------------------------------------------------------------
# Pages
# ---------------------------------------------------------------------------
@dataclass
class PageInfo:
    """One parsed page: header + raw (still compressed) payload."""

    header: md.PageHeader
    payload: bytes  # compressed bytes as stored
    offset: int  # absolute file offset of the page header

    @property
    def page_type(self) -> PageType:
        return PageType(self.header.type)

    @property
    def num_values(self) -> int:
        h = self.header
        if h.data_page_header is not None:
            return h.data_page_header.num_values
        if h.data_page_header_v2 is not None:
            return h.data_page_header_v2.num_values
        if h.dictionary_page_header is not None:
            return h.dictionary_page_header.num_values
        return 0


def _checked_page_size(header: md.PageHeader, at: int) -> int:
    """Shared page-size sanity check for the three page iterators.  A
    flipped header can still thrift-parse with the size field MISSING
    (None) — that is corruption too, not a TypeError."""
    clen = header.compressed_page_size
    if clen is None or not 0 <= clen <= MAX_PAGE_SIZE:
        raise _corrupt(
            f"page at {at}: compressed size {clen} out of range", at)
    return clen


_UNSET = object()  # lazy-memo sentinel (None is a valid cached value)


class ColumnChunkReader:
    """Reference parity: column_chunk.go — ColumnChunk + file.go — filePages."""

    def __init__(self, file: "ParquetFile", rg_index: int, chunk: md.ColumnChunk,
                 leaf: Leaf):
        self.file = file
        self.rg_index = rg_index
        self.chunk = chunk
        self.leaf = leaf
        self.meta = chunk.meta_data
        self._ci = self._oi = self._bf = _UNSET

    @property
    def codec(self) -> codecs.Codec:
        return codecs.get_codec(self.meta.codec)

    @property
    def num_values(self) -> int:
        return self.meta.num_values

    @property
    def byte_range(self) -> Tuple[int, int]:
        """(start, size) of this chunk's page bytes in the file."""
        m = self.meta
        start = m.data_page_offset
        if m.dictionary_page_offset is not None and 0 < m.dictionary_page_offset < start:
            start = m.dictionary_page_offset
        return start, m.total_compressed_size

    def raw_bytes(self) -> bytes:
        start, size = self.byte_range
        return self.file.source.pread(start, size)

    def pages(self, raw: Optional[bytes] = None) -> Iterator[PageInfo]:
        """Parse the page stream.  One contiguous read for the whole chunk —
        batching H2D-friendly (SURVEY.md §7 hard part 5) and 1 syscall.

        Headers batch-parse in one native call and payloads are zero-copy
        views of the chunk buffer (the per-page Python thrift walk + slice
        copies were the measured floor of the e2e pipeline); the Python walk
        below is the fallback and owns error reporting."""
        start, size = self.byte_range
        if raw is None:
            # without the native scanner, pread_view's numpy buffer would
            # just be re-copied to bytes for the Python walk — read bytes
            # directly in that case
            raw = (self.file.source.pread_view(start, size)
                   if _native.get_lib() is not None
                   else self.file.source.pread(start, size))
        fast = _native.scan_page_headers(raw, self.meta.num_values)
        if fast is not None:
            yield from self._pages_from_scan(raw, start, fast)
            return
        if isinstance(raw, (np.ndarray, memoryview)):
            raw = bytes(raw)  # the Python thrift walk indexes per byte
        pos = 0
        values_seen = 0
        total = self.meta.num_values
        while values_seen < total and pos < size:
            try:
                header, data_pos = thrift.deserialize(md.PageHeader, raw, pos)
            except Exception as e:
                raise _corrupt(f"bad page header at {start+pos}: {e}",
                               start + pos) from e
            clen = _checked_page_size(header, start + pos)
            payload = raw[data_pos : data_pos + clen]
            if len(payload) != clen:
                raise _corrupt("truncated page payload", start + pos)
            page = PageInfo(header=header, payload=payload, offset=start + pos)
            if page.page_type in (PageType.DATA_PAGE, PageType.DATA_PAGE_V2):
                values_seen += page.num_values
            yield page
            pos = data_pos + clen

    def _pages_from_scan(self, raw, start: int, desc) -> Iterator[PageInfo]:
        """Materialize PageInfos from a native header scan (payloads are
        zero-copy uint8 views into ``raw``)."""
        from ..native import (PG_COMP, PG_CRC, PG_DATA_POS, PG_DEF_ENC,
                              PG_DICT_NVALS, PG_DL_BYTES, PG_ENC,
                              PG_HEADER_POS, PG_IS_COMPRESSED, PG_NNULLS,
                              PG_NROWS, PG_NVALS, PG_REP_ENC, PG_RL_BYTES,
                              PG_TYPE, PG_UNCOMP)

        rawv = raw if isinstance(raw, np.ndarray) else np.frombuffer(raw, np.uint8)
        for row in desc.tolist():
            clen = row[PG_COMP]
            if not 0 <= clen <= MAX_PAGE_SIZE:
                raise _corrupt(
                    f"page at {start + row[PG_HEADER_POS]}: "
                    f"compressed size {clen} out of range",
                    start + row[PG_HEADER_POS])
            pt = row[PG_TYPE]
            h = md.PageHeader(
                type=pt, uncompressed_page_size=row[PG_UNCOMP],
                compressed_page_size=clen,
                crc=row[PG_CRC] if row[PG_CRC] >= 0 else None)
            if pt == PageType.DATA_PAGE:
                h.data_page_header = md.DataPageHeader(
                    num_values=row[PG_NVALS], encoding=row[PG_ENC],
                    definition_level_encoding=row[PG_DEF_ENC],
                    repetition_level_encoding=row[PG_REP_ENC])
            elif pt == PageType.DATA_PAGE_V2:
                h.data_page_header_v2 = md.DataPageHeaderV2(
                    num_values=row[PG_NVALS],
                    num_nulls=row[PG_NNULLS] if row[PG_NNULLS] >= 0 else None,
                    num_rows=row[PG_NROWS] if row[PG_NROWS] >= 0 else None,
                    encoding=row[PG_ENC],
                    # -1 = field absent: map to None so consumers' `or 0`
                    # lenience matches the Python walk exactly
                    definition_levels_byte_length=(
                        row[PG_DL_BYTES] if row[PG_DL_BYTES] >= 0 else None),
                    repetition_levels_byte_length=(
                        row[PG_RL_BYTES] if row[PG_RL_BYTES] >= 0 else None),
                    is_compressed=(None if row[PG_IS_COMPRESSED] < 0
                                   else bool(row[PG_IS_COMPRESSED])))
            elif pt == PageType.DICTIONARY_PAGE:
                h.dictionary_page_header = md.DictionaryPageHeader(
                    num_values=row[PG_DICT_NVALS], encoding=row[PG_ENC])
            data_pos = row[PG_DATA_POS]
            yield PageInfo(header=h, payload=rawv[data_pos : data_pos + clen],
                           offset=start + row[PG_HEADER_POS])

    def pages_streamed(self, window: int = 1 << 20,
                       source: Optional[Source] = None) -> Iterator[PageInfo]:
        """Bounded-memory page iterator: windowed incremental preads instead
        of one whole-chunk read — the analog of the reference's
        ``PageBufferSize`` streaming (SURVEY.md §5).  Memory is O(window)
        per cursor (default 1 MB ≈ one data page).  Consumers that stop
        early (a row-range cursor mid-chunk) never touch the remaining
        bytes.  Headers batch-parse per window through the native partial
        scanner (the per-page Python thrift walk was 22% of the streamed
        whole-file read); the Python walk below is the fallback and owns
        precise error reporting.

        NOTE: each ``PageInfo.payload`` is a buffer-protocol view
        (memoryview/ndarray), not ``bytes`` — wrap in ``bytes(...)`` before
        concatenation/hashing/pickling — and a retained payload pins its
        whole read window (~``window`` bytes); copy out pages you keep
        past the iteration.

        ``source`` overrides where the windowed preads go (the stream
        layer passes its per-drain :class:`~parquet_tpu.io.prefetch.
        PrefetchSource` here so windows are served from the readahead
        ring/page cache); default is the file's source."""
        start, size = self.byte_range
        # proportional bound: never pull more than 1/16 of the chunk per
        # pread (64 KB floor), so small chunks keep page-scale reads while
        # large chunks get full readahead windows
        window = max(min(window, size // 16), 1 << 16)
        if _native.get_lib() is None:
            yield from self._pages_streamed_python(window, 0, 0, source)
            return
        src_ = source if source is not None else self.file.source
        pos = 0
        values_seen = 0
        total = self.meta.num_values
        win = window
        while values_seen < total and pos < size:
            view = src_.pread_view(start + pos, min(win, size - pos))
            res = _native.scan_page_headers_partial(view,
                                                    total - values_seen)
            if res is None:  # scanner refused: python walk from here on
                yield from self._pages_streamed_python(window, pos,
                                                       values_seen, source)
                return
            rows, consumed, seen = res
            if len(rows) == 0:
                if len(view) >= min(MAX_PAGE_HEADER_SIZE, size - pos):
                    # the header must fit in this view: parse it once via
                    # the python walk to either learn the blocking page's
                    # true size (grow exactly, no doubling sweep over a
                    # corrupt clen) or raise the precise CorruptedError
                    try:
                        header, data_pos = thrift.deserialize(
                            md.PageHeader, bytes(view[:MAX_PAGE_HEADER_SIZE]),
                            0)
                    except Exception:
                        yield from self._pages_streamed_python(
                            window, pos, values_seen, source)
                        return
                    clen = _checked_page_size(header, start + pos)
                    if pos + data_pos + clen > size:
                        raise _corrupt("truncated page payload", start + pos)
                    if len(view) >= data_pos + clen:
                        # the whole claimed page was visible and the
                        # scanner still refused it (bad uncompressed size,
                        # missing num_values, ...): the python walk owns
                        # it — growing again would loop forever
                        yield from self._pages_streamed_python(
                            window, pos, values_seen, source)
                        return
                    win = data_pos + clen  # exactly this oversized page
                    continue
                win = min(win * 4, size - pos)  # header larger than window
                continue
            yield from self._pages_from_scan(view, start + pos, rows)
            pos += consumed
            values_seen += seen
            win = window

    def _pages_streamed_python(self, window: int, pos: int,
                               values_seen: int,
                               source: Optional[Source] = None
                               ) -> Iterator[PageInfo]:
        """Python thrift fallback for pages_streamed (precise errors)."""
        start, size = self.byte_range
        src = source if source is not None else self.file.source
        total = self.meta.num_values
        buf = b""
        boff = 0
        while values_seen < total and pos < size:
            if boff >= len(buf):
                buf = src.pread(start + pos, min(window, size - pos))
                boff = 0
            while True:
                try:
                    header, data_pos = thrift.deserialize(md.PageHeader, buf,
                                                          boff)
                    break
                except Exception as e:
                    if len(buf) - boff >= min(MAX_PAGE_HEADER_SIZE,
                                              size - pos):
                        raise _corrupt(
                            f"bad page header at {start+pos}: {e}",
                            start + pos) from e
                    buf = src.pread(start + pos,
                                    min(max(window, (len(buf) - boff) * 4),
                                        size - pos))
                    boff = 0
            hdr_len = data_pos - boff
            clen = _checked_page_size(header, start + pos)
            if pos + hdr_len + clen > size:
                # a payload running past the chunk would silently read the
                # NEXT chunk's bytes here — same corruption pages() detects
                raise _corrupt("truncated page payload", start + pos)
            if data_pos + clen <= len(buf):
                payload = memoryview(buf)[data_pos : data_pos + clen]
            else:
                payload = src.pread(start + pos + hdr_len, clen)
            if len(payload) != clen:
                raise _corrupt("truncated page payload", start + pos)
            page = PageInfo(header=header, payload=payload, offset=start + pos)
            if page.page_type in (PageType.DATA_PAGE, PageType.DATA_PAGE_V2):
                values_seen += page.num_values
            yield page
            pos += hdr_len + clen
            boff = data_pos + clen

    def pages_at(self, offset: int, size: int,
                 num_pages: Optional[int] = None) -> Iterator[PageInfo]:
        """Parse pages from one byte span of the chunk (offset-index seek:
        one pread covering just the selected pages)."""
        raw = self.file.source.pread(offset, size)
        pos = 0
        yielded = 0
        while pos < size and (num_pages is None or yielded < num_pages):
            try:
                header, data_pos = thrift.deserialize(md.PageHeader, raw, pos)
            except Exception as e:
                raise _corrupt(f"bad page header at {offset+pos}: {e}",
                               offset + pos) from e
            clen = _checked_page_size(header, offset + pos)
            payload = raw[data_pos : data_pos + clen]
            if len(payload) != clen:
                raise _corrupt("truncated page payload", offset + pos)
            yield PageInfo(header=header, payload=payload, offset=offset + pos)
            yielded += 1
            pos = data_pos + clen

    # ------------------------------------------------------------------ decode
    def read(self) -> Column:
        """Decode the whole chunk on host (numpy oracle path)."""
        return decode_chunk_host(self)

    # ------------------------------------------------------- indexes / filters
    def _read_index_blob(self, offset, length, what: str) -> bytes:
        """pread an index structure with the shared length sanity guard
        (limits.go MaxColumnIndexSize analog); a missing or out-of-range
        length with the offset present is corruption, not a crash."""
        if length is None or not 0 <= length <= MAX_COLUMN_INDEX_SIZE:
            raise _corrupt(f"{what} length {length} out of range", offset)
        return self.file.source.pread(offset, length)

    def column_index(self) -> Optional[md.ColumnIndex]:
        if self._ci is not _UNSET:
            return self._ci
        c = self.chunk
        if c.column_index_offset is None:
            self._ci = None
            return None
        raw = self._read_index_blob(c.column_index_offset,
                                    c.column_index_length, "column index")
        try:
            ci, _ = thrift.deserialize(md.ColumnIndex, raw)
        except Exception as e:
            raise _corrupt(f"bad column index: {e}",
                           c.column_index_offset) from e
        self._ci = ci
        return ci

    def offset_index(self) -> Optional[md.OffsetIndex]:
        if self._oi is not _UNSET:
            return self._oi
        c = self.chunk
        if c.offset_index_offset is None:
            self._oi = None
            return None
        raw = self._read_index_blob(c.offset_index_offset,
                                    c.offset_index_length, "offset index")
        try:
            oi, _ = thrift.deserialize(md.OffsetIndex, raw)
        except Exception as e:
            raise _corrupt(f"bad offset index: {e}",
                           c.offset_index_offset) from e
        self._oi = oi
        return oi

    def bloom_filter(self):
        # memoized like the index structures: the file is immutable after
        # open, and the batched-lookup path probes the same chunk's filter
        # on every call — re-preading a multi-MB bitset per batch was pure
        # waste.  (A filter pins host memory for the life of this reader,
        # same as the parsed indexes; both live in file._chunk_cache.)
        if self._bf is not _UNSET:
            return self._bf
        from .bloom import read_bloom_filter

        self._bf = read_bloom_filter(self)
        return self._bf

    def statistics(self):
        from .statistics import decode_statistics

        return decode_statistics(self.meta.statistics, self.leaf)


class RowGroupReader:
    """Reference parity: row_group.go — RowGroup (file-backed)."""

    def __init__(self, file: "ParquetFile", index: int, rg: md.RowGroup):
        self.file = file
        self.index = index
        self.rg = rg

    @property
    def num_rows(self) -> int:
        return self.rg.num_rows

    @property
    def sorting_columns(self):
        return self.rg.sorting_columns

    def column(self, which: Union[int, str, Tuple[str, ...]]) -> ColumnChunkReader:
        if isinstance(which, int):
            i = which
        else:
            i = self.file.schema.leaf(which).column_index
        # memoized: the file is immutable after open (reference semantics), so
        # chunk readers — and the index structures they lazily parse — are
        # shared across repeated scans
        key = (self.index, i)
        reader = self.file._chunk_cache.get(key)
        if reader is None:
            reader = ColumnChunkReader(self.file, self.index,
                                       self.rg.columns[i],
                                       self.file.schema.leaves[i])
            self.file._chunk_cache[key] = reader
        return reader

    def columns(self) -> List[ColumnChunkReader]:
        return [self.column(i) for i in range(len(self.rg.columns))]


# whole-file reads above this many (uncompressed row-group) bytes route
# through the streaming cursors — windowed IO beats whole-chunk decode's
# 100MB+ allocation churn at scale (paired 2.7GB lineitem: ~25% faster)
_STREAMED_READ_BYTES = 256 << 20


class ParquetFile:
    """Reference parity: file.go — File/OpenFile (magic check both ends,
    thrift footer decode, lazy page-index/bloom access)."""

    def __init__(self, source, options: Optional[ReadOptions] = None,
                 policy: Optional[FaultPolicy] = None):
        self.options = options or ReadOptions()
        self.policy = policy
        self._chunk_cache = {}
        self.source: Source = as_source(source)
        if policy is not None:
            # every pread from any layer (footer, page streams, indexes,
            # blooms) now retries transient OSErrors per the policy and
            # honors the active operation deadline
            self.source = PolicySource(self.source, policy)
        self._base_source = self.source  # per-call overrides revert to this
        self._override_stack: List[Source] = []
        # caching identity: only plain path-backed opens qualify — wrapped
        # sources (fault injectors, arbitrary Source subclasses) may
        # transform bytes, so their decodes must never populate or be
        # served from the shared caches (io/cache.py).  The key is the
        # source's open-time fstat (stat_key), pairing identity with the
        # bytes this fd/map actually serves — a path re-stat here could
        # race an atomic-rename replace and cache old bytes under the new
        # file's identity
        from .remote import HttpSource
        from .source import FileSource, MmapSource

        inner = self.source.inner if isinstance(self.source, PolicySource) \
            else self.source
        # remote opens key on the HEAD validators (url, ETag,
        # Last-Modified, length) instead of fstat; an HttpSource whose
        # server sends no validator (or whose transport is a chaos
        # wrapper) carries stat_key=None and is never cached
        self._cache_key = (inner.stat_key
                           if isinstance(inner, (FileSource, MmapSource,
                                                 HttpSource))
                           else None)
        try:
            with self._resilient_op(None, None, "open"), \
                    read_context(path=self._path,
                                 kinds=(CorruptedError, OSError)):
                self._open_footer()
        except BaseException:
            # a failed open must not leak the fd (FileSource has no
            # finalizer, and the flaky-mount retry loops this layer exists
            # for would otherwise exhaust the process fd limit)
            self.source.close()
            raise
        counters.inc("files_opened")

    def _open_footer(self) -> None:
        if _otrace.on():
            with _otrace.span("open.footer", file=self._path):
                self._open_footer_impl()
            return
        self._open_footer_impl()

    def _open_footer_impl(self) -> None:
        from .cache import FOOTERS

        if self._cache_key is not None:
            hit = FOOTERS.get(self._cache_key)
            if hit is not None:
                # hot re-open: the footer (and schema) of these exact bytes
                # was parsed before — skip the tail preads, magic checks,
                # and thrift walk entirely (metadata is immutable after
                # open, so sharing the parsed objects is safe)
                self.metadata, self.schema = hit
                return
        size = self.source.size()
        if size < 12:
            raise CorruptedError(f"file too small ({size} bytes) to be parquet")
        tail_len = min(self.options.footer_read_size, size)
        tail = self.source.pread(size - tail_len, tail_len)
        if tail[-4:] != md.MAGIC:
            raise CorruptedError("missing PAR1 magic at end of file")
        footer_len = struct.unpack("<I", tail[-8:-4])[0]
        if footer_len + 8 > size:
            raise CorruptedError(f"footer length {footer_len} exceeds file size {size}")
        if footer_len + 8 <= tail_len:
            footer = tail[-8 - footer_len : -8]
        else:
            footer = self.source.pread(size - 8 - footer_len, footer_len)
        head = self.source.pread(0, 4)
        if head != md.MAGIC:
            raise CorruptedError("missing PAR1 magic at start of file")
        try:
            self.metadata, _ = thrift.deserialize(md.FileMetaData, footer)
        except Exception as e:
            raise CorruptedError(f"bad footer: {e}") from e
        if self.metadata.schema in (None, []):
            raise CorruptedError("footer has no schema")
        self.schema = Schema.from_elements(self.metadata.schema)
        if self._cache_key is not None:
            # nbytes = the serialized footer length: what the resource
            # ledger's cache.footer account charges for the parsed entry
            FOOTERS.put(self._cache_key, (self.metadata, self.schema),
                        nbytes=footer_len)

    # ---------------------------------------------------------- resilience
    @property
    def _path(self) -> Optional[str]:
        """File path for error context (None for in-memory sources)."""
        return getattr(self.source, "path", None)

    def _resilient_op(self, policy: Optional[FaultPolicy],
                      report: Optional[ReadReport], what: str = "read"):
        """Scope for one top-level read operation: ensures ``self.source``
        applies the effective policy (the open-time one, or a per-call
        override temporarily installed — chunk readers resolve
        ``self.file.source`` at call time, so the install covers every
        layer), starts the deadline clock, and collects retry counts into
        ``report``.

        Per-call overrides keep a stack (not a saved-source swap): two
        interleaved operations — generators closed out of order, threads —
        each remove only their own wrapper, so ``self.source`` always
        reverts to a live wrapper or the open-time source, never to a stale
        one.  While overrides overlap, reads of both operations run under
        the most recently installed policy (instance-level by design)."""
        import contextlib

        pol = policy if policy is not None else self.policy

        @contextlib.contextmanager
        def scope():
            if pol is None:
                yield None
                return
            base = self._base_source
            if isinstance(base, PolicySource) and base.policy is pol \
                    and self.source is base:
                with base.operation(report, what) as dl:
                    yield dl
                return
            inner = base.inner if isinstance(base, PolicySource) else base
            tmp = PolicySource(inner, pol)
            self._override_stack.append(tmp)
            self.source = tmp
            try:
                with tmp.operation(report, what) as dl:
                    yield dl
            finally:
                st = self._override_stack
                if tmp in st:
                    st.remove(tmp)
                self.source = st[-1] if st else base

        return scope()

    def _source_override(self, src: Source):
        """Temporarily route every pread of this file through ``src`` (a
        wrapper over the current source — e.g. the device staging route's
        chunk prefetcher).  Shares the override stack with
        :meth:`_resilient_op`, so LIFO-nested scopes always restore to a
        live wrapper or the open-time source; the caller owns closing the
        wrapper it installed."""
        import contextlib

        @contextlib.contextmanager
        def scope():
            self._override_stack.append(src)
            self.source = src
            try:
                yield src
            finally:
                st = self._override_stack
                if src in st:
                    st.remove(src)
                self.source = st[-1] if st else self._base_source

        return scope()

    def _decode_chunk_ctx(self, chunk: "ColumnChunkReader") -> "Column":
        """Host chunk decode with structured error context — any low-level
        failure surfaces as a :class:`ReadError` naming file, row group,
        column, and (when known) page offset.  Whole-chunk decodes of
        path-backed files go through the shared bounded decoded-chunk LRU
        (io/cache.py): a hot file re-read serves the Column without
        touching chunk bytes."""
        dec_span = (_otrace.span("decode.chunk", rg=chunk.rg_index,
                                 col=chunk.leaf.dotted_path)
                    if _otrace.on() else _otrace.NULL_SPAN)
        with dec_span, \
                read_context(path=self._path, row_group=chunk.rg_index,
                             column=chunk.leaf.dotted_path):
            from ..utils.pool import read_admission
            from .cache import CHUNKS, freeze_column

            key = self._cache_key
            if key is None:
                # uniform mutability contract: whole-chunk read results
                # are read-only whether or not this source is cacheable —
                # code must not validate against a writable result in one
                # configuration and break in another.  The IO+decode span
                # passes the unified read gate (scan tier) like every
                # other in-flight read; nested admits pass through.
                with read_admission().admit(
                        chunk.meta.total_uncompressed_size or 0,
                        tier="scan"):
                    return freeze_column(decode_chunk_host(chunk))
            ck = (key, chunk.rg_index, chunk.leaf.dotted_path,
                  self.options.verify_crc)
            col = CHUNKS.get(ck)
            if col is None:
                # miss: the whole-chunk IO+decode is an in-flight read
                # span — admitted through the unified budget (the cache
                # HIT path above stays gate-free: a warm read pins no
                # new bytes, and must pay zero admission overhead)
                with read_admission().admit(
                        chunk.meta.total_uncompressed_size or 0,
                        tier="scan"):
                    col = decode_chunk_host(chunk)
                # hand out the FROZEN instance (read-only buffers) so the
                # miss caller cannot mutate what later hits will serve
                frozen = CHUNKS.put_and_freeze(ck, col)
                col = frozen if frozen is not None else freeze_column(col)
            return col

    # ------------------------------------------------------------------
    @property
    def num_rows(self) -> int:
        return self.metadata.num_rows or 0

    @property
    def created_by(self) -> Optional[str]:
        return self.metadata.created_by

    def key_value_metadata(self) -> Dict[str, str]:
        return {kv.key: kv.value for kv in (self.metadata.key_value_metadata or [])}

    @property
    def arrow_dictionary_fields(self) -> frozenset:
        """Top-level field names the embedded ``ARROW:schema`` declares as
        arrow dictionary type.  Readers use this to emit DictionaryArray
        directly (indices + dictionary, pyarrow's own behavior for such
        files) instead of densifying a column parquet stored
        dictionary-encoded.  Empty when no arrow schema is embedded."""
        got = getattr(self, "_arrow_dict_fields", None)
        if got is None:
            got = frozenset()
            blob = self.key_value_metadata().get("ARROW:schema")
            if blob:
                try:
                    import base64

                    import pyarrow as pa

                    schema = pa.ipc.read_schema(
                        pa.BufferReader(base64.b64decode(blob)))
                    got = frozenset(f.name for f in schema
                                    if pa.types.is_dictionary(f.type))
                except Exception:
                    got = frozenset()
            self._arrow_dict_fields = got
        return got

    @property
    def row_groups(self) -> List[RowGroupReader]:
        return [RowGroupReader(self, i, rg)
                for i, rg in enumerate(self.metadata.row_groups or [])]

    def row_group(self, i: int) -> RowGroupReader:
        return RowGroupReader(self, i, self.metadata.row_groups[i])

    # ------------------------------------------------------------------
    def iter_batches(self, columns: Optional[Sequence[str]] = None,
                     batch_rows: int = 65536,
                     strict_batch_rows: bool = False,
                     policy: Optional[FaultPolicy] = None,
                     report: Optional[ReadReport] = None):
        """Bounded-memory streaming read: yield row-aligned :class:`Table`
        batches holding O(pages-per-batch) memory — the reference's
        ``PageBufferSize`` + ``GenericReader.Read`` streaming mode
        (see io/stream.py; batch sizes vary at row-group boundaries unless
        ``strict_batch_rows=True``).  ``policy``/``report`` thread the
        resilience layer through the stream (io/faults.py): retries and the
        drain-wide deadline at the source, ``skip_row_group`` dropping the
        un-yielded remainder of a corrupt row group."""
        from .stream import iter_batches as _iter

        return _iter(self, columns=columns, batch_rows=batch_rows,
                     strict_batch_rows=strict_batch_rows, policy=policy,
                     report=report)

    def find_rows(self, path, keys, columns: Optional[Sequence[str]] = None,
                  policy: Optional[FaultPolicy] = None,
                  report: Optional[ReadReport] = None):
        """Batched point lookup: the rows where column ``path`` equals each
        of ``keys``, answered via the cheapest-first probe cascade (chunk
        stats → batched bloom → page-index binary search → single-page
        reads with coalesced preads and page-granular caching) without
        materializing any whole chunk — see :mod:`parquet_tpu.io.lookup`.
        Returns a :class:`~parquet_tpu.io.lookup.LookupResult` aligned
        with ``keys``."""
        from .lookup import find_rows as _find_rows

        return _find_rows(self, path, keys, columns=columns, policy=policy,
                          report=report)

    def aggregate(self, aggs, where=None, group_by=None,
                  policy: Optional[FaultPolicy] = None,
                  report: Optional[ReadReport] = None):
        """Answer aggregate queries — COUNT/MIN/MAX/SUM/COUNT DISTINCT/
        top-k, optionally grouped — WITHOUT decoding wherever the footer
        statistics, page-index zone maps, or dictionary pages can prove
        the result exactly; only contended pages decode (see
        :mod:`parquet_tpu.io.aggregate`).  ``aggs`` is a list of
        :mod:`parquet_tpu.algebra.aggregate` nodes (``count()``,
        ``min_("x")``, …); ``where`` a predicate tree; ``group_by`` a flat
        column path.  Returns an
        :class:`~parquet_tpu.io.aggregate.AggregateResult` (mapping-like,
        with per-tier ``counters`` and ``explain()``)."""
        from .aggregate import aggregate_file

        return aggregate_file(self, aggs, where=where, group_by=group_by,
                              policy=policy, report=report)

    def read(self, columns: Optional[Sequence[str]] = None,
             device: bool = False,
             row_groups: Optional[Sequence[int]] = None,
             policy: Optional[FaultPolicy] = None,
             report: Optional[ReadReport] = None) -> "Table":
        """Read and decode the whole file.

        ``device=False``: host numpy oracle path.  ``device=True``: the TPU
        path — batched H2D staging + XLA kernels (parallel/device_reader.py).
        ``row_groups`` selects a subset by index (reference parity: callers
        of ``File.RowGroups()`` read chosen groups; also the unit the mesh
        shards over).

        ``policy`` (default: the open-time policy) applies the resilience
        layer: transient preads retry with jittered backoff, the whole call
        runs under ``deadline_s``, and ``on_corrupt='skip_row_group'``
        returns a valid partial Table of the intact row groups (host path;
        the device pipeline raises on corruption).  Pass ``report`` (a
        :class:`~parquet_tpu.io.faults.ReadReport`) to collect rows read/
        dropped, skipped row-group ordinals, and retry counts.
        """
        pol, report = resolve_policy(self, policy, report)
        t0 = time.perf_counter()
        # request scope (obs/scope.py): per-op attribution + sampling;
        # joins the caller's op_scope (or the dataset layer's) if active
        with _oscope.maybe_op_scope("file.read", file=self._path):
            try:
                if pol is not None or report is not None:
                    with self._resilient_op(policy, report):
                        t = self._read_impl(columns, device, row_groups,
                                            pol, report)
                    report.rows_read += t.num_rows
                    t.report = report
                    return t
                return self._read_impl(columns, device, row_groups, None,
                                       None)
            finally:
                # per-operation latency: metrics_snapshot() answers read
                # p50/p99 without any caller-side timing (failures count
                # too — a retry storm that dies at the deadline IS the
                # tail)
                _M_READ_FILE_S.observe(time.perf_counter() - t0)

    def _read_impl(self, columns, device, row_groups,
                   pol: Optional[FaultPolicy],
                   report: Optional[ReadReport]) -> "Table":
        leaves = _select_leaves(self.schema, columns)
        all_rg = range(len(self.metadata.row_groups or []))
        if row_groups is None:
            rg_sel = list(all_rg)
            total_rows = self.num_rows
        else:
            rg_sel = list(row_groups)
            for i in rg_sel:
                if i not in all_rg:
                    raise IndexError(
                        f"row group {i} out of range [0, {len(all_rg)})")
            total_rows = sum(self.metadata.row_groups[i].num_rows
                             for i in rg_sel)
        n_rg = len(rg_sel)
        if not rg_sel:  # empty selection → a valid zero-row table
            from .column import empty_column

            return Table(self.schema,
                         {leaf.dotted_path: empty_column(leaf)
                          for leaf in leaves}, 0)
        if pol is not None and pol.skip_corrupt:
            if device:
                # the device pipeline's batched generator can't resume past
                # a poisoned chunk — refuse loudly rather than silently
                # downgrading a clean device read to the host decode path
                raise ValueError(
                    "on_corrupt='skip_row_group' is not supported with "
                    "device=True; read on host, or use on_corrupt='raise'")
            return self._read_degraded(leaves, rg_sel, report)
        if device:
            # double-buffered pipeline across every (leaf, row-group) chunk:
            # host prescan + H2D of later chunks overlaps device decode of
            # earlier ones (SURVEY.md §7 hard part 5)
            from ..parallel.device_reader import decode_chunks_pipelined

            chunks = [self.row_group(i).column(leaf.column_index)
                      for leaf in leaves for i in rg_sel]
            decoded = decode_chunks_pipelined(chunks)

            def _pull(chunk):  # per-chunk error context for the pipeline
                with read_context(path=self._path, row_group=chunk.rg_index,
                                  column=chunk.leaf.dotted_path):
                    return next(decoded)

            it = iter(chunks)
            dparts = {leaf.dotted_path: [_pull(next(it)) for _ in range(n_rg)]
                      for leaf in leaves}
            return Table(self.schema, None, total_rows, parts=dparts)
        # Large files route through the streaming cursors: windowed 1 MB
        # preads + page-batch decodes hold working sets that fit the cache
        # hierarchy, where whole-chunk decode churns 100MB+ allocations per
        # (leaf, row-group) — measured 1.7x faster on the 2.7 GB lineitem
        # read (12.2 s -> 7.2 s) and identical values (the batch Tables'
        # parts concatenate lazily; to_arrow emits chunked arrays either
        # way).  Small files keep the whole-chunk path (lower per-page
        # overhead; measured faster below ~8 row-group-chunks x 64 MB).
        # gate on the SELECTED columns' bytes (a narrow selection over a
        # wide file decodes little and belongs on the chunk path), and
        # dedup overlapping selectors: the streaming cursors are per-path
        total_sel = sum(
            (self.metadata.row_groups[i].columns[leaf.column_index]
             .meta_data.total_uncompressed_size or 0)
            for leaf in {l.dotted_path: l for l in leaves}.values()
            for i in rg_sel)
        if (row_groups is None and total_sel > _STREAMED_READ_BYTES
                and env_bool("PARQUET_TPU_READ_STREAMED")):
            # policy reads keep this route (the flaky-mount + big-file case
            # is exactly what it exists for): the caller's operation scope
            # is already active, so drive the stream internals directly —
            # no nested deadline scope, no double rows_read accounting.
            # (skip_corrupt was dispatched to _read_degraded above.)
            from .stream import _iter_batches_impl

            paths = list(dict.fromkeys(leaf.dotted_path for leaf in leaves))
            got = self._read_streamed(paths, total_rows)
            if got is not None:
                return got
            # row count surprise (footer vs row-group metadata): fall
            # through and let the chunk path report precisely
        # fan the (leaf, row-group) chunks across the shared pool — the
        # reference's read path is goroutine-parallel by design (SURVEY.md
        # §2.5a caller-driven fan-out); decompress/decode release the GIL in
        # the codec and native layers, so threads scale on host.  Chunk
        # readers are built serially (metadata memoization isn't locked).
        chunks = [[self.row_group(i).column(leaf.column_index)
                   for i in rg_sel] for leaf in leaves]
        # same measured crossover as parallel/host_scan.py: under ~2M cells
        # the per-task dispatch overhead beats the decode win.  On a single
        # core, threads are a pure loss for whole-chunk decode: per-thread
        # malloc arenas defeat buffer reuse for the large decode buffers
        # (measured 2x slower), so the fan-out needs real cores.
        # inside a pool worker (the dataset layer's per-file fan-out), keep
        # the decode serial: nested submitters blocking on futures no free
        # worker can run would deadlock the shared pool
        from ..utils.pool import available_cpus, in_shared_pool

        if (n_rg * len(leaves) > 1 and available_cpus() > 1
                and not in_shared_pool()
                and total_rows * len(leaves) >= 2_000_000):
            from ..utils.pool import submit as pool_submit

            futs = {leaf.dotted_path: [pool_submit(self._decode_chunk_ctx, c)
                                       for c in per_leaf]
                    for leaf, per_leaf in zip(leaves, chunks)}
            parts = {p: [f.result() for f in fs] for p, fs in futs.items()}
        else:
            # serial decode.  (A one-chunk IO-lookahead thread was tried
            # here and REGRESSED on a single core: with the page cache
            # mostly warm, pread is a CPU memcpy that competes with decode
            # instead of overlapping disk wait — 15.0 s vs 10.3 s on the
            # 2.7 GB lineitem read.  Multi-core hosts already overlap via
            # the pool branch above.)
            parts = {leaf.dotted_path: [self._decode_chunk_ctx(c)
                                        for c in per_leaf]
                     for leaf, per_leaf in zip(leaves, chunks)}
        return Table(self.schema, None, total_rows, parts=parts,
                     dict_fields=self.arrow_dictionary_fields)

    def _read_streamed(self, paths, total_rows) -> Optional["Table"]:
        """Whole-file read over the streaming cursors (the >256 MB route),
        at per-ROW-GROUP decoded-chunk cache granularity: row groups whose
        every selected column is resident in the shared LRU (io/cache.py)
        are served from it without touching their bytes; only the rest
        stream, and each streamed group's columns are offered back to the
        cache (when they fit under the per-item cap) — a warm re-read of a
        file too big to cache wholesale pays only for what the LRU
        evicted.  When the file is cache-eligible, streamed pieces are
        frozen like every other cached-path read result, so a mixed
        cached/streamed table has one mutability contract.  Returns None
        on a footer-vs-row-group row count mismatch (the caller's chunk
        path reports precisely)."""
        from .cache import (CHUNKS, chunk_cache_bytes, column_nbytes,
                            freeze_column)
        from .column import concat_columns
        from .stream import _iter_batches_impl

        n_rg = len(self.row_groups)
        cap = chunk_cache_bytes()
        cacheable = self._cache_key is not None and cap > 0

        def ck(i, p):
            return (self._cache_key, i, p, self.options.verify_crc)

        parts_by_rg: Dict[int, Dict[str, List[Column]]] = {}
        if cacheable:
            for i in range(n_rg):
                if not all(CHUNKS.contains(ck(i, p)) for p in paths):
                    continue
                got = {p: CHUNKS.get(ck(i, p)) for p in paths}
                if all(c is not None for c in got.values()):  # eviction race
                    parts_by_rg[i] = {p: [c] for p, c in got.items()}
        served = set(parts_by_rg)
        stream_rgs = [i for i in range(n_rg) if i not in served]

        def rg_done(rg_index, cols):
            parts_by_rg[rg_index] = {
                p: ([freeze_column(c) for c in cs] if cacheable else list(cs))
                for p, cs in cols.items()}
            if not cacheable:
                return
            rg = self.row_group(rg_index)
            for p, cs in cols.items():
                if not cs:
                    continue
                est = rg.column(p).meta.total_uncompressed_size or 0
                if est > cap // 2:
                    continue  # the concat is a copy: only pay it for
                    # chunks the cache would accept (put re-checks exactly)
                try:
                    whole = concat_columns(list(cs))
                except Exception:
                    continue  # exotic part mix: population is best-effort
                if column_nbytes(whole) <= cap // 2:
                    CHUNKS.put_and_freeze(ck(rg_index, p), whole)

        got_rows = sum(self.row_groups[i].num_rows for i in served)
        read_stats = None
        for batch in _iter_batches_impl(self, paths, 1 << 20,
                                        strict_batch_rows=False,
                                        skip=False, report=None,
                                        row_groups=stream_rgs,
                                        rg_done=rg_done):
            got_rows += batch.num_rows
            read_stats = batch.read_stats
        if got_rows != total_rows:
            return None  # release the streamed copy; chunk path reports
        parts: Dict[str, List[Column]] = {p: [] for p in paths}
        for i in range(n_rg):
            for p, cs in parts_by_rg.get(i, {}).items():
                parts[p].extend(cs)
        t = Table(self.schema, None, total_rows, parts=parts,
                  dict_fields=self.arrow_dictionary_fields)
        t.read_stats = read_stats
        return t

    def _read_degraded(self, leaves, rg_sel, report: ReadReport) -> "Table":
        """``on_corrupt='skip_row_group'`` host read: decode row-group-major
        so one corrupt group drops as a unit; intact groups' rows return
        exactly (row groups are row-aligned across columns, so the partial
        Table stays valid).  Deadline overruns still raise — a timeout is
        not corruption."""
        from ..utils.pool import (available_cpus, in_shared_pool,
                                  submit as pool_submit)

        uniq = list({l.dotted_path: l for l in leaves}.values())
        parts: Dict[str, List[Column]] = {l.dotted_path: [] for l in uniq}
        kept_rows = 0
        pooled = (len(uniq) > 1 and available_cpus() > 1
                  and not in_shared_pool())
        for i in rg_sel:
            rg = self.row_group(i)
            try:
                chunk_readers = [rg.column(l.column_index) for l in uniq]
                if pooled:
                    futs = [pool_submit(self._decode_chunk_ctx, c)
                            for c in chunk_readers]
                    cols = [f.result() for f in futs]
                else:
                    cols = [self._decode_chunk_ctx(c) for c in chunk_readers]
            except DeadlineError:
                raise
            except CorruptedError as e:
                report.record_skip(i, rows=rg.num_rows, error=e)
                continue
            for l, col in zip(uniq, cols):
                parts[l.dotted_path].append(col)
            kept_rows += rg.num_rows
        if kept_rows == 0:
            from .column import empty_column

            return Table(self.schema,
                         {l.dotted_path: empty_column(l) for l in uniq}, 0)
        return Table(self.schema, None, kept_rows, parts=parts,
                     dict_fields=self.arrow_dictionary_fields)

    def close(self):
        self.source.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def _select_leaves(schema: Schema, columns) -> List[Leaf]:
    if columns is None:
        return list(schema.leaves)
    out = []
    for c in columns:
        matches = [l for l in schema.leaves
                   if l.dotted_path == c or l.path[0] == c]
        if not matches:
            raise KeyError(f"no column {c!r} in schema")
        out.extend(matches)
    return out


class Table:
    """A decoded set of columns (dict-like).  ``to_arrow`` → pyarrow.Table.

    Multi-row-group reads may construct the table from per-row-group
    ``parts``: per-leaf concatenation happens lazily on first ``columns``
    access, and ``to_arrow`` emits pyarrow *chunked* arrays straight from the
    parts (pyarrow's own layout) — the whole-file read then never pays a
    values memcpy at all."""

    def __init__(self, schema: Schema, columns: Optional[Dict[str, Column]],
                 num_rows: int,
                 parts: Optional[Dict[str, List[Column]]] = None,
                 dict_fields: frozenset = frozenset()):
        self.schema = schema
        self._columns = columns
        self._parts = parts if columns is None else None
        self.num_rows = num_rows
        # fields the file's embedded arrow schema declares dictionary-typed:
        # to_arrow preserves them as DictionaryArray (pyarrow's behavior)
        self._dict_fields = dict_fields
        # populated by policy/report reads (io/faults.py ReadReport):
        # degraded reads record skipped row groups and retry counts here
        self.report = None
        # populated by prefetching reads (io/prefetch.py ReadStats):
        # hits/misses, bytes prefetched vs discarded, pool wait time
        self.read_stats = None

    @property
    def columns(self) -> Dict[str, Column]:
        if self._columns is None:
            self._columns = {p: (concat_columns(ps) if len(ps) != 1
                                 else ps[0])
                             for p, ps in self._parts.items()}
        return self._columns

    def __getitem__(self, path: str) -> Column:
        return self.columns[path]

    # name queries must not force the per-leaf concatenation
    def __contains__(self, path: str) -> bool:
        d = self._columns if self._columns is not None else self._parts
        return path in d

    def keys(self):
        d = self._columns if self._columns is not None else self._parts
        return d.keys()

    def _chunked_to_arrow(self):
        """Chunked fast path: every selected top-level field is a plain leaf
        or pure list chain → build one ChunkedArray per field from the
        per-row-group parts, no concatenation.  None = caller falls back."""
        import pyarrow as pa

        from ..schema.types import LogicalKind

        names, arrays = [], []
        for child in self.schema.root.children:
            leaves = [l for l in self.schema.leaves if l.path[0] == child.name]
            present = [l for l in leaves if l.dotted_path in self._parts]
            if not present:
                continue
            if (len(present) != 1 or not (
                    child.is_leaf or child.logical_kind == LogicalKind.LIST)
                    or self._needs_row_assembly(child, under_rep=False)):
                return None
            ps = self._parts[present[0].dotted_path]
            names.append(child.name)
            prefer = child.name in self._dict_fields
            arrs = [p.to_arrow(prefer_dictionary=prefer) for p in ps]
            if any(pa.types.is_large_string(a.type)
                   or pa.types.is_large_binary(a.type) for a in arrs):
                # a >2 GiB chunk took the LARGE layout: normalize the
                # narrow chunks up so the chunked array is one type
                wide_t = next(a.type for a in arrs
                              if pa.types.is_large_string(a.type)
                              or pa.types.is_large_binary(a.type))
                arrs = [a if a.type == wide_t else a.cast(wide_t)
                        for a in arrs]
            if prefer and any(not pa.types.is_dictionary(a.type)
                              for a in arrs):
                # a chunk fell back to dense (dictionary overflow
                # mid-file): re-encode it so every chunk carries the
                # DECLARED dictionary type — pyarrow's own behavior, and
                # the only choice that keeps types uniform across
                # iter_batches tables (a batch can't see other batches to
                # normalize dense)
                arrs = [a if pa.types.is_dictionary(a.type)
                        else a.dictionary_encode() for a in arrs]
            arrays.append(pa.chunked_array(arrs) if len(arrs) > 1
                          else arrs[0])
        return pa.Table.from_arrays(arrays, names=names)

    def to_arrow(self):
        """Reassemble a pyarrow table, including structs and maps.

        Three tiers per top-level field: plain leaves and pure list chains use
        the vectorized :meth:`Column.to_arrow`; structs *above* any repetition
        are zipped vectorized from their children with validity derived from
        def levels; structs/maps *inside* lists go through the row model
        (record-at-a-time Dremel assembly — correct, not the hot path)."""
        import pyarrow as pa

        if self._parts is not None and self._columns is None:
            t = self._chunked_to_arrow()
            if t is not None:
                return t
        names, arrays = [], []
        for child in self.schema.root.children:
            leaves = [l for l in self.schema.leaves if l.path[0] == child.name]
            present = [l for l in leaves if l.dotted_path in self.columns]
            if not present:
                continue
            if len(present) != len(leaves):
                # partial column selection: emit present leaves flat
                for l in present:
                    col = self.columns[l.dotted_path]
                    names.append(child.name if len(l.path) == 1 or col.list_offsets
                                 else l.dotted_path)
                    arrays.append(col.to_arrow())
                continue
            names.append(child.name)
            arrays.append(self._field_to_arrow(child, leaves))
        return pa.Table.from_arrays(arrays, names=names)

    # -- to_arrow helpers ------------------------------------------------
    def _field_to_arrow(self, node, leaves):
        if self._needs_row_assembly(node, under_rep=False):
            arr = self._field_nested_vectorized(node)
            if arr is not None:
                return arr
            return self._field_via_rows(node)
        return self._build_arrow(node, (node.name,), 0)

    def _field_nested_vectorized(self, node):
        """Vectorized tier for structs and maps INSIDE repetition (SURVEY.md
        §7 hard part 4): every layer — list offsets, struct/map nullness,
        leaf validity — is derived from the raw Dremel level streams with
        whole-column vector ops and zipped bottom-up; no per-record python.

        Works at "granularity" (k, d_elem): the element set of the k-th
        repeated ancestor, i.e. leaf slots with ``rep <= k`` and
        ``def >= d_elem`` (k=0 → rows).  All leaves under a node agree on
        that element set because levels are shared up to the common ancestor.
        Returns None when any leaf lacks raw levels (device-resident decode)
        — the caller falls back to the row model."""
        import pyarrow as pa

        from ..format.enums import FieldRepetitionType as Rep
        from ..schema.types import LogicalKind
        from .column import _leaf_to_arrow

        prefix = (node.name,)
        sub = [l for l in self.schema.leaves if l.path[0] == node.name]
        if not sub:
            return None
        for l in sub:
            col = self.columns[l.dotted_path]
            if col.def_levels is None or (l.max_repetition_level
                                          and col.rep_levels is None):
                return None

        def levels_of(leaf):
            col = self.columns[leaf.dotted_path]
            d = np.asarray(col.def_levels)
            r = (np.asarray(col.rep_levels) if col.rep_levels is not None
                 else np.zeros(len(d), np.int32))
            return d, r

        def any_leaf(pfx):
            return next(l for l in sub if l.path[: len(pfx)] == pfx)

        def elem_mask(d, r, k, d_elem):
            return (r <= k) & (d >= d_elem)

        def list_layer(pfx, k, d_elem, d_list, d_mid, inner_arr,
                       nullable_list):
            """Offsets (+ null lists) for one repetition layer around
            ``inner_arr`` (already at granularity (k+1, d_mid))."""
            d, r = levels_of(any_leaf(pfx))
            inst = elem_mask(d, r, k, d_elem)
            elem2 = elem_mask(d, r, k + 1, d_mid)
            cum = np.cumsum(elem2, dtype=np.int64)
            inst_idx = np.flatnonzero(inst)
            starts = (cum[inst_idx] - elem2[inst_idx]).astype(np.int32)
            total = np.int32(cum[-1] if len(cum) else 0)
            offs = np.concatenate([starts, [total]]).astype(np.int32)
            if nullable_list:
                valid = d[inst_idx] >= d_list
                if not valid.all():
                    # null-bearing offsets encode null lists/maps
                    pa_offs = pa.array(offs, mask=np.concatenate(
                        [~valid, [False]]))
                    return pa_offs
            return pa.array(offs)

        def build(n, pfx, k, d_elem, d_par):
            """Arrow array for ``n`` at granularity (k, d_elem)."""
            own_def = d_par + (1 if n.repetition != Rep.REQUIRED else 0)
            if n.is_leaf:
                leaf = any_leaf(pfx)
                col = self.columns[leaf.dotted_path]
                if col.is_dictionary_encoded():
                    col.materialize_host()
                d, r = levels_of(leaf)
                mask = elem_mask(d, r, k, d_elem)
                d_sub = d[mask]
                validity = (d_sub == leaf.max_definition_level
                            if leaf.max_definition_level > d_elem else None)
                if validity is not None and bool(validity.all()):
                    validity = None
                values = np.asarray(col.values)
                if (values.ndim == 2 and values.dtype == np.uint32
                        and values.shape[1] == 2):
                    host_dt = {Type.INT64: np.int64,
                               Type.DOUBLE: np.float64}.get(
                                   leaf.physical_type, np.int64)
                    values = np.ascontiguousarray(values).view(host_dt) \
                        .reshape(-1)
                offsets = (None if col.offsets is None
                           else np.asarray(col.offsets))
                return _leaf_to_arrow(leaf, values, offsets, validity)
            kind = n.logical_kind
            if kind == LogicalKind.LIST and len(n.children) == 1 \
                    and n.children[0].repetition == Rep.REPEATED:
                mid = n.children[0]
                d_list = own_def
                d_mid = d_list + 1
                if mid.children is not None and len(mid.children) == 1:
                    inner = mid.children[0]
                    inner_pfx = pfx + (mid.name, inner.name)
                else:
                    inner = mid
                    inner_pfx = pfx + (mid.name,)
                if inner is mid:
                    # 2-level list form: repeated element directly
                    inner_arr = build_repeated_elem(mid, pfx + (mid.name,),
                                                    k + 1, d_mid)
                else:
                    inner_arr = build(inner, inner_pfx, k + 1, d_mid, d_mid)
                offs = list_layer(pfx, k, d_elem, d_list, d_mid, inner_arr,
                                  n.repetition != Rep.REQUIRED)
                return pa.ListArray.from_arrays(offs, inner_arr)
            if kind == LogicalKind.MAP and len(n.children) == 1:
                mid = n.children[0]  # repeated key_value
                d_map = own_def
                d_mid = d_map + 1
                kv_pfx = pfx + (mid.name,)
                keys = build(mid.children[0], kv_pfx + (mid.children[0].name,),
                             k + 1, d_mid, d_mid)
                items = build(mid.children[1],
                              kv_pfx + (mid.children[1].name,),
                              k + 1, d_mid, d_mid)
                offs = list_layer(pfx, k, d_elem, d_map, d_mid, keys,
                                  n.repetition != Rep.REQUIRED)
                return pa.MapArray.from_arrays(offs, keys, items)
            if n.repetition == Rep.REPEATED:
                # legacy repeated group (list<struct> without LIST wrapper)
                d_mid = d_par + 1
                inner_arr = build_repeated_elem(n, pfx, k + 1, d_mid)
                offs = list_layer(pfx, k, d_elem, d_mid, d_mid, inner_arr,
                                  False)
                return pa.ListArray.from_arrays(offs, inner_arr)
            # plain struct at the current granularity
            kids = [(c.name, build(c, pfx + (c.name,), k, d_elem, own_def))
                    for c in n.children]
            arrs = [a for _, a in kids]
            names = [nm for nm, _ in kids]
            if n.repetition == Rep.REQUIRED or own_def == d_elem:
                return pa.StructArray.from_arrays(arrs, names)
            d, r = levels_of(any_leaf(pfx))
            valid = d[elem_mask(d, r, k, d_elem)] >= own_def
            if bool(valid.all()):
                return pa.StructArray.from_arrays(arrs, names)
            return pa.StructArray.from_arrays(arrs, names,
                                              mask=pa.array(~valid))

        def build_repeated_elem(n, pfx, k, d_elem):
            """The element of a repeated group: a struct of n's children (or
            n's own leaf value) at the deeper granularity."""
            if n.is_leaf:
                return build(_required_view(n), pfx, k, d_elem, d_elem)
            kids = [(c.name, build(c, pfx + (c.name,), k, d_elem, d_elem))
                    for c in n.children]
            return pa.StructArray.from_arrays([a for _, a in kids],
                                              [nm for nm, _ in kids])

        def _required_view(n):
            return n

        try:
            return build(node, prefix, 0, 0, 0)
        except NotImplementedError:
            return None

    def _needs_row_assembly(self, node, under_rep: bool) -> bool:
        """True if a plain (non-list-machinery) group sits under repetition —
        structs/maps inside lists have no row-aligned child arrays to zip."""
        from ..format.enums import FieldRepetitionType as Rep
        from ..schema.types import LogicalKind

        if node.is_leaf:
            return False
        rep_here = under_rep or node.repetition == Rep.REPEATED
        if node.logical_kind == LogicalKind.LIST and len(node.children) == 1:
            mid = node.children[0]
            inner = (mid.children[0] if mid.children is not None
                     and len(mid.children) == 1 else mid)
            return self._needs_row_assembly(inner, under_rep=True) \
                if not inner.is_leaf else False
        if node.logical_kind == LogicalKind.MAP:
            return True  # key_value struct is always under repetition
        if rep_here:
            return True  # plain repeated group / struct under a list
        return any(self._needs_row_assembly(c, under_rep=False)
                   for c in node.children if not c.is_leaf)

    def _build_arrow(self, node, prefix, def_above: int):
        """Vectorized tier: leaves / list chains via Column.to_arrow, struct
        layers zipped with validity = (def_levels >= own def level)."""
        import pyarrow as pa

        from ..format.enums import FieldRepetitionType as Rep
        from ..schema.types import LogicalKind

        if node.is_leaf or node.logical_kind == LogicalKind.LIST:
            sub = [l for l in self.schema.leaves
                   if l.path[: len(prefix)] == prefix]
            return self.columns[sub[0].dotted_path].to_arrow()
        own_def = def_above + (1 if node.repetition != Rep.REQUIRED else 0)
        children = [(c.name, self._build_arrow(c, prefix + (c.name,), own_def))
                    for c in node.children]
        arrs = [a for _, a in children]
        names = [n for n, _ in children]
        if node.repetition == Rep.REQUIRED:
            return pa.StructArray.from_arrays(arrs, names)
        # optional struct: null iff def level stops above own_def.  Prefer a
        # flat leaf (def levels are per-row); a repeated leaf's levels are
        # per-slot, so take the row-start slots (rep == 0) there.
        subleaves = [l for l in self.schema.leaves
                     if l.path[: len(prefix)] == prefix]
        rep_leaf = min(subleaves, key=lambda l: l.max_repetition_level)
        col = self.columns[rep_leaf.dotted_path]
        if col.def_levels is None:
            if col.validity is None and rep_leaf.max_repetition_level == 0:
                # the no-null fast paths drop both levels and validity: every
                # ancestor (this struct included) is fully present
                return pa.StructArray.from_arrays(arrs, names)
            if rep_leaf.max_definition_level == own_def and col.validity is not None \
                    and rep_leaf.max_repetition_level == 0:
                valid = np.asarray(col.validity)
            else:
                # no levels to derive nulls; fall back to row assembly with
                # the full-path prefix so sub-schema leaves resolve
                return self._field_via_rows(node, prefix, def_above)
        else:
            d = np.asarray(col.def_levels)
            if rep_leaf.max_repetition_level > 0:
                d = d[np.asarray(col.rep_levels) == 0]
            valid = d >= own_def
        if bool(np.all(valid)):
            return pa.StructArray.from_arrays(arrs, names)
        return pa.StructArray.from_arrays(arrs, names, mask=pa.array(~valid))

    def _field_via_rows(self, node, prefix=None, def_above: int = 0):
        """Row-model tier: assemble this field's python objects row by row,
        then build the arrow array with the schema-derived type.

        ``prefix`` is the full dotted path of ``node`` in the table schema
        (ending with ``node.name``); the sub-schema's leaf paths start at
        ``node.name``, so table columns are looked up at
        ``prefix + leaf.path[1:]``. Defaults to top-level (``(node.name,)``).
        ``def_above`` is the def-level contribution of ancestors above
        ``node``: the sub-schema roots the tree at ``node``, so absolute def
        levels must shift down by it (rows whose level stops above ``node``
        — a null ancestor — clamp to 0, i.e. null at the top of the
        sub-tree; the enclosing struct's mask hides them anyway).
        """
        import dataclasses

        import pyarrow as pa

        from ..rows import _Assembler, rows_from_columns
        from ..schema.schema import Schema, message
        from .column import arrow_type_of

        if prefix is None:
            prefix = (node.name,)
        sub_schema = message("root", [node])

        def _sub_col(leaf):
            col = self.columns[".".join(prefix + leaf.path[1:])]
            if def_above and col.def_levels is not None:
                col = dataclasses.replace(
                    col, def_levels=np.maximum(
                        np.asarray(col.def_levels) - def_above, 0))
            return col

        cols = {l.dotted_path: _sub_col(l) for l in sub_schema.leaves}
        asm = _Assembler(sub_schema)
        objs = [asm.assemble(row)[node.name]
                for row in rows_from_columns(sub_schema, cols, self.num_rows)]
        return pa.array(objs, type=arrow_type_of(node))


# ---------------------------------------------------------------------------
# Host decode loop (the ★ HOT LOOP of SURVEY.md §3.1, oracle edition)
# ---------------------------------------------------------------------------


def _bit_width(maxval: int) -> int:
    return int(maxval).bit_length()


def verify_page_crc(reader: ColumnChunkReader, page: PageInfo) -> None:
    """Optional page CRC32 check (reference: page read path, `verify_crc`)."""
    h = page.header
    if reader.file.options.verify_crc and h.crc is not None:
        crc = zlib.crc32(page.payload) & 0xFFFFFFFF
        if crc != (h.crc & 0xFFFFFFFF):
            raise _corrupt(f"page CRC mismatch at offset {page.offset}",
                           page.offset)


def decode_dictionary_page(reader: ColumnChunkReader, page: PageInfo):
    """Decompress + decode one dictionary page (shared by the chunk decoder
    and the streaming cursor so CRC/decode semantics stay in one place)."""
    h = page.header
    raw = reader.codec.decode(page.payload, h.uncompressed_page_size)
    dictionary = _decode_dictionary(raw, h.dictionary_page_header, reader.leaf,
                                    Type(reader.meta.type))
    counters.inc("dict_pages_decoded")
    return dictionary


# int32 offsets address chunks up to this many value bytes; beyond it the
# chunk keeps int64 offsets and converts to arrow large_binary/large_string.
# Module-level so tests can lower it and exercise the wide path cheaply.
_OFFSET32_LIMIT = int(np.iinfo(np.int32).max)


def _offsets_int32(offs: np.ndarray) -> np.ndarray:
    """Chunk-level byte-array offsets: int32 (arrow binary layout) while the
    value bytes fit; a chunk past ``_OFFSET32_LIMIT`` keeps int64 offsets —
    ``to_arrow`` then emits the arrow large_binary/large_string layout
    (``page.go — Page.Data`` imposes no such size limit upstream)."""
    if len(offs) and int(offs[-1]) > _OFFSET32_LIMIT:
        return offs.astype(np.int64, copy=False)
    return offs.astype(np.int32, copy=False)


@dataclass
class _PendingPlainBA:
    """A PLAIN BYTE_ARRAY page deferred to the chunk-level batch parse."""
    raw: np.ndarray
    pos: int
    nvals: int


def _maybe_defer_plain_ba(raw, pos, nvals, encoding, physical):
    """Defer a builtin-PLAIN BYTE_ARRAY page to one chunk-level native
    parse (pq_plain_ba_batch).  None → decode through the registry."""
    if (encoding == Encoding.PLAIN and physical == Type.BYTE_ARRAY
            and _is_builtin_decode(Encoding.PLAIN)
            and _native.get_lib() is not None):
        return _PendingPlainBA(raw, pos, nvals)
    return None


def _batch_decompress(page_list, codec):
    """Decompress every data page of ``page_list`` in one native call
    (snappy/zstd — the codecs with a dlopen'd system lib in the shim).
    Returns {page index -> decompressed uint8 view} or None to use the
    per-page codec path (identity/other codecs, shim unavailable, or any
    page failing — the per-page path then raises the precise error)."""
    cid = getattr(codec, "codec_id", None)
    if cid is None or int(cid) not in (1, 6):  # SNAPPY, ZSTD
        return None
    srcs, sizes, idxs = [], [], []
    for i, page in enumerate(page_list):
        h = page.header
        if page.page_type == PageType.DATA_PAGE:
            srcs.append(page.payload)
            sizes.append(h.uncompressed_page_size)
            idxs.append(i)
        elif page.page_type == PageType.DATA_PAGE_V2:
            dph2 = h.data_page_header_v2
            if dph2.is_compressed is False:
                continue
            rl = dph2.repetition_levels_byte_length or 0
            dl = dph2.definition_levels_byte_length or 0
            srcs.append(page.payload[rl + dl:])
            sizes.append(h.uncompressed_page_size - rl - dl)
            idxs.append(i)
    if len(srcs) < 2:  # a single page gains nothing over the direct call
        return None
    from .. import native as _nat

    # read() already fans chunks across the shared pool — a per-chunk
    # thread split on top would oversubscribe (pool width x 8 native
    # threads); keep the split for single-chunk/streaming callers only.
    # The pool dispatch marks its workers explicitly (utils/pool.py submit).
    res = _nat.decompress_pages(srcs, sizes, int(cid), _nat._auto_threads())
    if res is None:
        return None
    buf, offs = res
    return {idx: buf[offs[j]:offs[j + 1]] for j, idx in enumerate(idxs)}


_PLAIN_FIXED_ITEM = {Type.INT32: np.int32, Type.INT64: np.int64,
                     Type.FLOAT: np.float32, Type.DOUBLE: np.float64}


def _plain_fixed_chunk_fast(reader: ColumnChunkReader, page_list, pre_dec,
                            leaf: Leaf, physical: Type) -> Optional[Column]:
    """Whole-chunk fast path for flat, all-present PLAIN fixed-width columns.

    For such a chunk every data page's decompressed payload is a (possibly
    empty) def-level prefix followed by raw value bytes, so the chunk array
    is just the concatenation of the per-page value regions: one copy, or
    ZERO copies when no page carries a prefix (required columns, or v2
    pages whose levels live outside the compressed body) since the batched
    decompressor already produced one contiguous buffer.  The general path
    instead pays a per-page decode copy plus a chunk-level concatenate.
    Returns None when any precondition fails (nulls present, mixed
    encodings, dictionary pages, framing surprises); the general path then
    runs on the same ``pre_dec`` without duplicated work."""
    dtype = _PLAIN_FIXED_ITEM.get(physical)
    if (dtype is None or leaf.max_repetition_level > 0
            or leaf.max_definition_level > 1
            or not _is_builtin_decode(Encoding.PLAIN)):
        return None
    max_def = leaf.max_definition_level
    itemsize = np.dtype(dtype).itemsize
    codec = reader.codec
    slices: List[np.ndarray] = []
    total_vals = 0
    n_pages = 0
    contiguous_base = None  # buffer all slices view into, when zero-copy-able
    for page_i, page in enumerate(page_list):
        h = page.header
        pt = page.page_type
        if pt == PageType.DICTIONARY_PAGE:
            return None  # dict-encoded pages follow; not a pure-plain chunk
        if pt not in (PageType.DATA_PAGE, PageType.DATA_PAGE_V2):
            continue
        verify_page_crc(reader, page)
        pre = pre_dec.get(page_i) if pre_dec is not None else None
        if pt == PageType.DATA_PAGE:
            dph = h.data_page_header
            if Encoding(dph.encoding) != Encoding.PLAIN:
                return None
            n = dph.num_values
            raw = pre if pre is not None else np.frombuffer(
                codec.decode(page.payload, h.uncompressed_page_size),
                np.uint8)
            pos = 0
            if max_def > 0:
                if Encoding(dph.definition_level_encoding) != Encoding.RLE:
                    return None
                pv, pos = ref.rle_len_prefixed_single_value(raw, n, 0)
                if pv != 1:
                    return None  # nulls (or multi-run levels): general path
        else:
            dph2 = h.data_page_header_v2
            if (Encoding(dph2.encoding) != Encoding.PLAIN
                    or (dph2.num_nulls or 0)
                    or (dph2.repetition_levels_byte_length or 0)):
                return None
            n = dph2.num_values
            dl = dph2.definition_levels_byte_length or 0
            if dph2.is_compressed is not False:
                body = pre if pre is not None else np.frombuffer(
                    codec.decode(page.payload[dl:],
                                 h.uncompressed_page_size - dl), np.uint8)
            else:
                body = np.frombuffer(page.payload, np.uint8)[dl:]
            raw, pos = body, 0
        if len(raw) - pos != n * itemsize:
            return None  # unexpected framing — let the general path say why
        sl = raw[pos:] if pos else raw
        if n_pages == 0:
            contiguous_base = sl.base if pos == 0 else None
        elif pos != 0 or sl.base is None or sl.base is not contiguous_base:
            contiguous_base = None
        slices.append(sl)
        total_vals += n
        n_pages += 1
    if not slices:
        return None
    values = None
    if len(slices) == 1:
        values = slices[0].view(dtype)
    elif isinstance(contiguous_base, np.ndarray):
        # all slices view one buffer; zero-copy iff they tile it end to end
        ptr = slices[0].__array_interface__["data"][0]
        for sl in slices:
            if sl.__array_interface__["data"][0] != ptr:
                break
            ptr += sl.nbytes
        else:
            base0 = contiguous_base.__array_interface__["data"][0]
            start = slices[0].__array_interface__["data"][0] - base0
            values = contiguous_base[start:start + total_vals * itemsize] \
                .view(dtype)
    if values is None:
        values = np.concatenate(slices).view(dtype)
    counters.inc("data_pages_decoded", n_pages)
    counters.inc("plain_fixed_chunk_fast")
    return Column(leaf=leaf, values=values, offsets=None, validity=None,
                  list_offsets=[], list_validity=[], num_slots=total_vals)


def _rle_dict_chunk_fast(reader: ColumnChunkReader, page_list, pre_dec,
                         leaf: Leaf, dictionary):
    """Whole-chunk fast path for flat, all-present RLE_DICTIONARY
    BYTE_ARRAY columns: every page's index section decodes in ONE native
    call (pq_rle_dict_batch) into one int32 index array — replacing a
    Python scan/expand round-trip per page (~0.3 ms each; the dominant
    non-decompress cost of dictionary string columns at lineitem scale).

    Returns ``(column, pre_dec, dictionary)``: ``column`` is None when a
    precondition fails (nulls, mixed encodings, repetition, shim
    unavailable) and the general path should run.  Header-only checks run
    BEFORE any decompression; pages this path had to decompress itself
    and the decoded dictionary are handed back so the fallback never
    repeats that work."""
    if (leaf.max_repetition_level > 0 or leaf.max_definition_level > 1
            or not _is_builtin_decode(Encoding.RLE_DICTIONARY)
            or _native.get_lib() is None):
        return None, pre_dec, None
    max_def = leaf.max_definition_level
    codec = reader.codec
    # pass 1 — header-only preconditions: no decompression yet, so a mixed
    # chunk (dictionary-overflow PLAIN fallback pages) bails for free
    seen_data = False
    for page in page_list:
        pt = page.page_type
        h = page.header
        if pt == PageType.DICTIONARY_PAGE:
            if seen_data:
                return None, pre_dec, None
            continue
        if pt == PageType.DATA_PAGE:
            dph = h.data_page_header
            if Encoding(dph.encoding) != Encoding.RLE_DICTIONARY:
                return None, pre_dec, None
            if max_def and Encoding(dph.definition_level_encoding) \
                    != Encoding.RLE:
                return None, pre_dec, None
            seen_data = True
        elif pt == PageType.DATA_PAGE_V2:
            dph2 = h.data_page_header_v2
            if (Encoding(dph2.encoding) != Encoding.RLE_DICTIONARY
                    or (dph2.num_nulls or 0)
                    or (dph2.repetition_levels_byte_length or 0)):
                return None, pre_dec, None
            seen_data = True
    if not seen_data:
        return None, pre_dec, None
    # pass 2 — decompress (reusing pre_dec) and collect index sections
    srcs: List = []
    counts: List[int] = []
    prefixes: List[int] = []
    own_dec: Dict[int, np.ndarray] = {}
    for page_i, page in enumerate(page_list):
        h = page.header
        pt = page.page_type
        if pt == PageType.DICTIONARY_PAGE:
            verify_page_crc(reader, page)
            dictionary = decode_dictionary_page(reader, page)
            continue
        if pt not in (PageType.DATA_PAGE, PageType.DATA_PAGE_V2):
            continue
        verify_page_crc(reader, page)
        pre = pre_dec.get(page_i) if pre_dec is not None else None
        if pt == PageType.DATA_PAGE:
            dph = h.data_page_header
            if pre is None:
                pre = np.frombuffer(
                    codec.decode(page.payload, h.uncompressed_page_size),
                    np.uint8)
                own_dec[page_i] = pre
            raw = pre
            prefixes.append(1 if max_def else 0)
            counts.append(dph.num_values)
        else:
            dph2 = h.data_page_header_v2
            dl = dph2.definition_levels_byte_length or 0
            if dph2.is_compressed is not False:
                if pre is None:
                    pre = np.frombuffer(
                        codec.decode(page.payload[dl:],
                                     h.uncompressed_page_size - dl),
                        np.uint8)
                    own_dec[page_i] = pre
                raw = pre
            else:
                raw = np.frombuffer(page.payload, np.uint8)[dl:]
            prefixes.append(0)
            counts.append(dph2.num_values)
        srcs.append(raw)
    merged = pre_dec
    if own_dec:
        merged = dict(pre_dec or {})
        merged.update(own_dec)
    if dictionary is None:
        return None, merged, None
    indices = _native.rle_dict_batch(srcs, counts, prefixes)
    if indices is None or len(indices) != sum(counts):
        # e.g. a v1 page with nulls: python path — hand back the work
        # already done (decompressed pages AND the decoded dictionary)
        return None, merged, dictionary
    counters.inc("data_pages_decoded", len(srcs))
    counters.inc("rle_dict_chunk_fast")
    col = Column(leaf=leaf, values=None, offsets=None, validity=None,
                 list_offsets=[], list_validity=[],
                 num_slots=len(indices), dictionary_host=dictionary,
                 dict_indices=indices)
    return col, merged, dictionary


def decode_chunk_host(reader: ColumnChunkReader, pages=None,
                      dictionary=None,
                      keep_dictionary: bool = False) -> Column:
    """Decode a chunk (or, with ``pages``, a selected page subset — the
    SeekToRow / pushdown path of io/search.py).  ``dictionary`` injects an
    already-decoded dictionary so page-at-a-time streaming consumers don't
    re-decode the dictionary page per batch.  ``keep_dictionary=True``
    keeps a fully dict-encoded chunk of ANY physical type in
    ``(dictionary, indices)`` form — BYTE_ARRAY chunks already stay
    encoded by default; this extends the no-gather contract to
    fixed-width columns for consumers that aggregate over indices
    (io/aggregate.py's dictionary tier) instead of expanding values."""
    leaf = reader.leaf
    meta = reader.meta
    codec = reader.codec
    max_def = leaf.max_definition_level
    max_rep = leaf.max_repetition_level
    physical = Type(meta.type)
    all_def: List[np.ndarray] = []
    all_rep: List[np.ndarray] = []
    index_parts: List[np.ndarray] = []  # dict-encoded pages
    value_parts: List = []  # directly decoded pages (arrays or (vals, offs))
    part_order: List[Tuple[str, int]] = []  # ("idx"/"val", part index) per page

    page_list = list(pages) if pages is not None else list(reader.pages())
    pre_dec = _batch_decompress(page_list, codec)
    if dictionary is None:
        fast = _plain_fixed_chunk_fast(reader, page_list, pre_dec, leaf,
                                       physical)
        if fast is not None:
            return fast
    if physical == Type.BYTE_ARRAY:
        fast, pre_dec, dict_out = _rle_dict_chunk_fast(
            reader, page_list, pre_dec, leaf, dictionary)
        if fast is not None:
            return fast
        if dict_out is not None:
            dictionary = dict_out

    for page_i, page in enumerate(page_list):
        h = page.header
        pt = page.page_type
        verify_page_crc(reader, page)
        if pt == PageType.DICTIONARY_PAGE:
            if dictionary is None:
                dictionary = decode_dictionary_page(reader, page)
            continue
        pre = pre_dec.get(page_i) if pre_dec is not None else None
        if pt == PageType.DATA_PAGE:
            dph = h.data_page_header
            n = dph.num_values
            raw = pre if pre is not None else np.frombuffer(
                codec.decode(page.payload, h.uncompressed_page_size), np.uint8)
            pos = 0
            rep = defs = None
            if max_rep > 0:
                if Encoding(dph.repetition_level_encoding) == Encoding.BIT_PACKED:
                    raise CorruptedError("BIT_PACKED rep levels with no length are unsupported in v1 pages")
                rep, pos = ref.decode_rle_len_prefixed(raw, n, _bit_width(max_rep), pos)
            if max_def > 0:
                enc = Encoding(dph.definition_level_encoding)
                if enc == Encoding.RLE:
                    if max_def == 1 and max_rep == 0:
                        # flat optional: a page with no nulls is one RLE run
                        # of 1s — skip the expansion (the common case)
                        pv, end = ref.rle_len_prefixed_single_value(raw, n, pos)
                        if pv == 1:
                            defs, pos = None, end
                        else:
                            defs, pos = ref.decode_rle_len_prefixed(
                                raw, n, 1, pos)
                    else:
                        defs, pos = ref.decode_rle_len_prefixed(
                            raw, n, _bit_width(max_def), pos)
                else:  # legacy BIT_PACKED levels
                    w = _bit_width(max_def)
                    nbytes = (n * w + 7) // 8
                    defs = ref.decode_bit_packed_levels(raw[pos:], n, w)
                    pos += nbytes
            nvals = n if defs is None else int(np.count_nonzero(defs == max_def))
            encoding = Encoding(dph.encoding)
            decoded = _maybe_defer_plain_ba(raw, pos, nvals, encoding,
                                            physical)
            if decoded is None:
                decoded = _decode_values(raw, pos, nvals, encoding, leaf,
                                         physical, dictionary)
            counters.inc("data_pages_decoded")
        elif pt == PageType.DATA_PAGE_V2:
            dph2 = h.data_page_header_v2
            n = dph2.num_values
            rl = dph2.repetition_levels_byte_length or 0
            dl = dph2.definition_levels_byte_length or 0
            raw_levels = np.frombuffer(page.payload[: rl + dl], np.uint8)
            rep = defs = None
            if max_rep > 0:
                rep = ref.decode_rle(raw_levels, n, _bit_width(max_rep), 0)
            if max_def > 0 and not (max_def == 1 and max_rep == 0
                                    and dph2.num_nulls == 0):
                # v2 headers carry num_nulls: a null-free flat page skips the
                # def expansion entirely
                defs = ref.decode_rle(raw_levels[rl:], n, _bit_width(max_def), 0)
            body = page.payload[rl + dl :]
            if dph2.is_compressed is not False:
                body = pre if pre is not None else codec.decode(
                    body, h.uncompressed_page_size - rl - dl)
            raw = np.frombuffer(body, np.uint8)
            nvals = n - (dph2.num_nulls or 0)
            encoding = Encoding(dph2.encoding)
            decoded = _maybe_defer_plain_ba(raw, 0, nvals, encoding,
                                            physical)
            if decoded is None:
                decoded = _decode_values(raw, 0, nvals, encoding, leaf,
                                         physical, dictionary)
            counters.inc("data_pages_decoded")
        else:
            continue  # index pages etc.

        if rep is not None:
            all_rep.append(rep)
        if defs is not None:
            all_def.append(defs)
        elif max_def > 0 and max_rep == 0:
            # all-present fast path took this page: record the slot count so a
            # later page WITH nulls still concatenates aligned def levels
            all_def.append(n)
        if isinstance(decoded, _DictIndices):
            part_order.append(("idx", len(index_parts)))
            index_parts.append(decoded.indices)
        else:
            part_order.append(("val", len(value_parts)))
            value_parts.append(decoded)

    # ---- deferred PLAIN BYTE_ARRAY pages: one native parse for the chunk --
    pend = [(i, v) for i, v in enumerate(value_parts)
            if isinstance(v, _PendingPlainBA)]
    batched = None
    if pend:
        if len(pend) == len(value_parts) and not index_parts:
            # pure plain-BA chunk: the batch call yields the final
            # chunk-level (values, offsets) directly — _combine_parts is
            # bypassed below (re-concatenating would copy the chunk again)
            batched = _native.plain_ba_batch(
                [v.raw[v.pos:] for _, v in pend],
                [v.nvals for _, v in pend])
        if batched is None:  # mixed with dict parts, or shim unavailable
            for i, v in pend:
                value_parts[i] = _decode_values(
                    v.raw, v.pos, v.nvals, Encoding.PLAIN, leaf, physical,
                    dictionary)

    # ---- combine pages: dictionary form for BYTE_ARRAY chunks -------------
    # A fully dict-encoded byte-array chunk keeps (dictionary, indices) —
    # no gather: Column consumers handle dictionary form everywhere (rows,
    # scans, convert, concat), to_arrow emits a DictionaryArray zero-copy,
    # and the gather for a 4M-row categorical column was the read path's
    # second-largest cost after decompression.
    dict_host = dict_idx = None
    if batched is not None:
        values = batched[0]
        offsets = _offsets_int32(batched[1])
    elif ((physical == Type.BYTE_ARRAY or keep_dictionary)
            and dictionary is not None and part_order
            and all(kind == "idx" for kind, _ in part_order)):
        values, offsets = None, None
        dict_host = dictionary
        dict_idx = (np.concatenate(index_parts) if len(index_parts) > 1
                    else index_parts[0])
    else:
        values, offsets = _combine_parts(part_order, index_parts, value_parts,
                                         dictionary, leaf, physical)
    if all_def and not all(isinstance(d, (int, np.integer)) for d in all_def):
        # mixed fast-path/expanded pages: back-fill the all-present ones
        def_levels = np.concatenate(
            [np.full(d, max_def, np.int32)
             if isinstance(d, (int, np.integer)) else d for d in all_def])
    else:
        def_levels = None  # no def streams, or every page all-present
    rep_levels = np.concatenate(all_rep) if all_rep else None
    asm = levels_ops.assemble(def_levels, rep_levels, leaf)
    num_slots = len(def_levels) if def_levels is not None else (
        len(dict_idx) if dict_idx is not None else
        len(offsets) - 1 if offsets is not None else
        (len(values) if np.ndim(values) else 0))
    return Column(leaf=leaf, values=values, offsets=offsets,
                  validity=asm.validity, list_offsets=asm.list_offsets,
                  list_validity=asm.list_validity, num_slots=num_slots,
                  dictionary_host=dict_host, dict_indices=dict_idx,
                  def_levels=def_levels, rep_levels=rep_levels)


from ..ops.encodings import (DictIndices as _DictIndices, EncodingSpec,
                             is_builtin_decode as _is_builtin_decode,
                             lookup as _lookup_encoding, register_encoding)


def _decode_dictionary(raw: bytes, dph: md.DictionaryPageHeader, leaf: Leaf,
                       physical: Type):
    n = dph.num_values
    buf = np.frombuffer(raw, np.uint8)
    dec = ref.decode_plain(buf, n, physical, leaf.type_length)
    if physical == Type.BYTE_ARRAY:
        return dec  # (values, offsets)
    return dec


def _decode_values(raw: np.ndarray, pos: int, nvals: int, encoding: Encoding,
                   leaf: Leaf, physical: Type, dictionary):
    """Page value decode, dispatched through the pluggable encoding registry
    (reference parity: ``encoding/encoding.go — Encoding`` lookup; the eight
    spec encodings below are the registered defaults)."""
    spec = _lookup_encoding(encoding)
    if spec is None:
        raise CorruptedError(
            f"unsupported encoding {encoding!r} for {physical!r}")
    return spec.decode(raw, pos, nvals, leaf, physical, dictionary)


# -- built-in encodings: the registered defaults ---------------------------


def _dec_dict(raw, pos, nvals, leaf, physical, dictionary):
    if dictionary is None:
        raise CorruptedError("dictionary-encoded page before dictionary page")
    return _DictIndices(ref.decode_rle_dict_indices(raw, nvals, pos))


def _dec_plain(raw, pos, nvals, leaf, physical, dictionary):
    return ref.decode_plain(raw[pos:], nvals, physical, leaf.type_length)


def _dec_delta(raw, pos, nvals, leaf, physical, dictionary):
    vals, _ = ref.decode_delta_binary_packed(raw, pos)
    vals = vals[:nvals]
    return vals.astype(np.int32) if physical == Type.INT32 else vals


def _dec_delta_len_ba(raw, pos, nvals, leaf, physical, dictionary):
    v, o, _ = ref.decode_delta_length_byte_array(raw, pos)
    return v, o


def _dec_delta_ba(raw, pos, nvals, leaf, physical, dictionary):
    v, o, _ = ref.decode_delta_byte_array(raw, pos)
    if physical == Type.FIXED_LEN_BYTE_ARRAY:
        return v.reshape(nvals, leaf.type_length)
    return v, o


def _dec_bss(raw, pos, nvals, leaf, physical, dictionary):
    width = {Type.FLOAT: 4, Type.DOUBLE: 8,
             Type.INT32: 4, Type.INT64: 8}.get(physical, leaf.type_length)
    b = ref.decode_byte_stream_split(raw[pos:], nvals, width)
    if physical == Type.FLOAT:
        return b.reshape(-1).view(np.float32)
    if physical == Type.DOUBLE:
        return b.reshape(-1).view(np.float64)
    if physical == Type.INT32:
        return b.reshape(-1).view(np.int32)
    if physical == Type.INT64:
        return b.reshape(-1).view(np.int64)
    return b  # FLBA: (n, width) bytes


def _dec_rle_bool(raw, pos, nvals, leaf, physical, dictionary):
    if physical != Type.BOOLEAN:
        raise CorruptedError(
            f"RLE value encoding is defined for BOOLEAN, not {physical!r}")
    # RLE-encoded booleans (v2): 4-byte length prefix, bit width 1
    vals, _ = ref.decode_rle_len_prefixed(raw, nvals, 1, pos)
    return vals.astype(np.bool_)


# Masked-emit twins (fused decode+filter path, io/fused.py): same dispatch
# arguments plus the sorted ``take`` ordinal array after nvals.


def _dec_dict_masked(raw, pos, nvals, take, leaf, physical, dictionary):
    if dictionary is None:
        raise CorruptedError("dictionary-encoded page before dictionary page")
    return _DictIndices(ref.decode_rle_dict_indices_masked(raw, nvals, take, pos))


def _dec_plain_masked(raw, pos, nvals, take, leaf, physical, dictionary):
    return ref.decode_plain_masked(raw[pos:], nvals, take, physical,
                                   leaf.type_length)


def _dec_delta_masked(raw, pos, nvals, take, leaf, physical, dictionary):
    vals = ref.decode_delta_binary_packed_masked(raw, nvals, take, pos)
    return vals.astype(np.int32) if physical == Type.INT32 else vals


for _spec in (
        EncodingSpec(Encoding.PLAIN, "PLAIN", _dec_plain, _dec_plain_masked),
        EncodingSpec(Encoding.PLAIN_DICTIONARY, "PLAIN_DICTIONARY", _dec_dict,
                     _dec_dict_masked),
        EncodingSpec(Encoding.RLE_DICTIONARY, "RLE_DICTIONARY", _dec_dict,
                     _dec_dict_masked),
        EncodingSpec(Encoding.DELTA_BINARY_PACKED, "DELTA_BINARY_PACKED",
                     _dec_delta, _dec_delta_masked),
        EncodingSpec(Encoding.DELTA_LENGTH_BYTE_ARRAY,
                     "DELTA_LENGTH_BYTE_ARRAY", _dec_delta_len_ba),
        EncodingSpec(Encoding.DELTA_BYTE_ARRAY, "DELTA_BYTE_ARRAY",
                     _dec_delta_ba),
        EncodingSpec(Encoding.BYTE_STREAM_SPLIT, "BYTE_STREAM_SPLIT",
                     _dec_bss),
        EncodingSpec(Encoding.RLE, "RLE", _dec_rle_bool),
):
    # Idempotent under module re-execution (importlib.reload, or the module
    # reached under two names) — but never clobber a user's registered
    # shadow of a builtin id.
    if _lookup_encoding(_spec.id) is None or _is_builtin_decode(_spec.id):
        register_encoding(_spec, overwrite=True, _builtin=True)


def _combine_parts(part_order, index_parts, value_parts, dictionary, leaf, physical):
    """Merge per-page results into one chunk array; dictionary chunks do ONE
    gather over the concatenated index stream (TPU-friendly: a single big
    gather instead of per-page gathers — SURVEY.md §2.2 RLE_DICTIONARY note)."""
    if not part_order:
        empty = np.empty(0, dtype=leaf.np_dtype() or np.uint8)
        return (empty, np.zeros(1, np.int32)) if physical == Type.BYTE_ARRAY else (empty, None)
    only_idx = all(kind == "idx" for kind, _ in part_order)
    if only_idx:
        idx = np.concatenate(index_parts) if len(index_parts) > 1 else index_parts[0]
        gathered = ref.gather_dictionary(dictionary, idx)
        if isinstance(gathered, tuple):
            return gathered[0], gathered[1]
        return gathered, None
    # mixed or pure-plain: materialize each page, concatenate
    mats = []
    for kind, i in part_order:
        if kind == "idx":
            mats.append(ref.gather_dictionary(dictionary, index_parts[i]))
        else:
            mats.append(value_parts[i])
    if isinstance(mats[0], tuple):  # byte arrays: (values, offsets) pairs
        vals = np.concatenate([m[0] for m in mats])
        # one vector add per page, no per-page astype (the add materializes
        # a fresh array anyway; segmented np.repeat measured far slower)
        offs_parts = []
        base = 0
        for m in mats:
            o = m[1]
            offs_parts.append(o[:-1] + np.int64(base))
            base += int(o[-1])
        offs_parts.append(np.array([base], np.int64))
        return vals, _offsets_int32(np.concatenate(offs_parts))
    if len(mats) == 1:
        return mats[0], None
    return np.concatenate(mats), None
