"""Bounded-memory streaming reads: O(pages-per-batch), never O(chunk).

Reference parity: the reference reads with O(page) memory — ``config.go —
PageBufferSize`` bounds what a reader holds, and ``GenericReader[T].Read``
streams batches (SURVEY.md §5, "bounded-batch streaming").  This module is
that mode for the new framework: :func:`iter_batches` yields row-aligned
:class:`~parquet_tpu.io.reader.Table` batches while holding, per column, only
the decoded pages that cover the current batch.

Mechanics: each (row-group, column) gets a cursor over
``ColumnChunkReader.pages_streamed()`` (incremental preads — the file is
never read a whole chunk at a time), decoding one page per pull with the
chunk's dictionary decoded once.  Batch boundaries rarely align with page
boundaries, so rows are sliced out of decoded page columns by slicing the
Dremel level streams and re-running the (linear, metadata-scale) level
assembler on the slice — this handles flat, struct, and arbitrarily nested
list columns with one rule.

Pages are assumed record-aligned (a row never splits across pages), which
every mainstream writer guarantees and DataPageV2 requires.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence

import numpy as np

from ..errors import CorruptedError, DeadlineError
from ..utils.env import env_bool
from ..format.enums import PageType
from ..obs import scope as _oscope
from ..obs import trace as _trace
from ..ops import levels as levels_ops
from .column import Column
from .faults import FaultPolicy, ReadReport, read_context, resolve_policy
from .reader import (ParquetFile, Table, decode_chunk_host,
                     decode_dictionary_page, verify_page_crc)

__all__ = ["iter_batches"]

# same measured crossover as parallel/host_scan.py and the whole-file read:
# below ~2M cells the per-task pool dispatch beats the decode win
_PARALLEL_MIN_CELLS = 2_000_000


@dataclass
class _PagePiece:
    col: Column
    rows: int
    # row → slot start positions within this piece (identity for flat)
    row_starts: Optional[np.ndarray] = None


def piece_from_column(col: Column) -> "_PagePiece":
    """Wrap a decoded column (any page subset) as a sliceable piece: row
    count and row→slot starts derived from the rep levels (identity for
    flat columns).  Shared by the streaming cursor and the row cursor's
    seek path."""
    rep = col.rep_levels
    if rep is not None:
        starts = levels_ops.row_slot_starts(np.asarray(rep))
        return _PagePiece(col=col, rows=len(starts), row_starts=starts)
    return _PagePiece(col=col, rows=col.num_slots or col.num_values,
                      row_starts=None)


@dataclass
class _ChunkCursor:
    """Incremental decoder for one column chunk: pulls pages on demand,
    holds only decoded-but-unconsumed pieces.  ``source`` overrides where
    the windowed preads go (the per-drain prefetcher)."""

    chunk: object  # ColumnChunkReader
    source: object = None
    pages: Iterator = None
    dictionary: object = None
    pieces: List[_PagePiece] = field(default_factory=list)
    consumed: int = 0  # rows consumed from pieces[0]
    exhausted: bool = False

    def __post_init__(self):
        self.pages = self.chunk.pages_streamed(source=self.source)

    def _pull_pages(self, need_rows: int) -> bool:
        """Pull the pages covering the next ``need_rows`` rows and decode
        them in ONE ``decode_chunk_host`` call (the fused multi-page path the
        whole-chunk read uses) instead of a per-page call — the per-page
        Python/dispatch overhead was the streaming read's entire deficit vs
        the whole-file read.  Page row counts come from the headers
        (DataPageHeaderV2.num_rows; v1 num_values, which over-counts rows
        for repeated columns — an over-estimate only makes a pull stop
        early, and ``take`` pulls again)."""
        batch = []
        est = 0
        for page in self.pages:
            if page.page_type == PageType.DICTIONARY_PAGE:
                verify_page_crc(self.chunk, page)
                self.dictionary = decode_dictionary_page(self.chunk, page)
                continue
            batch.append(page)
            v2 = getattr(page.header, "data_page_header_v2", None)
            # num_values over-counts rows for repeated columns and is 0 for
            # unknown page types (both only make the pull stop early or
            # late by one page — take() pulls again)
            est += v2.num_rows if v2 is not None else page.num_values
            if est >= need_rows:
                break
        if not batch:
            self.exhausted = True
            return False
        col = decode_chunk_host(self.chunk, pages=iter(batch),
                                dictionary=self.dictionary)
        self.pieces.append(piece_from_column(col))
        return True

    def take(self, n_rows: int):
        """Consume up to ``n_rows`` rows → (sliced column pieces, rows)."""
        out: List[Column] = []
        need = n_rows
        while need > 0:
            if not self.pieces and not self._pull_pages(need):
                break
            piece = self.pieces[0]
            avail = piece.rows - self.consumed
            if avail <= 0:
                self.pieces.pop(0)
                self.consumed = 0
                continue
            take = min(avail, need)
            out.append(_slice_rows(piece, self.consumed,
                                   self.consumed + take))
            self.consumed += take
            need -= take
            if self.consumed >= piece.rows:
                self.pieces.pop(0)
                self.consumed = 0
        return out, n_rows - need


def _slice_rows(piece: _PagePiece, r0: int, r1: int) -> Column:
    """Rows [r0, r1) of a decoded page column, as a self-contained Column.

    Levels are sliced in slot space and re-assembled (linear in the slice);
    values/indices/offsets are sliced in value space via the def levels.
    """
    col = piece.col
    leaf = col.leaf
    if r0 == 0 and r1 >= piece.rows:
        return col
    max_def = leaf.max_definition_level
    d = None if col.def_levels is None else np.asarray(col.def_levels)
    r = None if col.rep_levels is None else np.asarray(col.rep_levels)
    s0, s1 = levels_ops.slot_span(r, r0, r1, 0 if r is None else len(r),
                                  row_starts=piece.row_starts)
    if d is None:
        v0, v1 = s0, s1  # required flat: slots == values
        d_sl = r_sl = None
    else:
        v0 = levels_ops.present_count(d, 0, s0, max_def)
        v1 = v0 + levels_ops.present_count(d, s0, s1, max_def)
        d_sl = d[s0:s1]
        r_sl = None if r is None else r[s0:s1]
    asm = levels_ops.assemble(d_sl, r_sl, leaf)
    values = col.values
    offsets = None
    dict_indices = None
    if col.is_dictionary_encoded():
        dict_indices = np.asarray(col.dict_indices)[v0:v1]
        values = None
    elif col.offsets is not None:
        offs = np.asarray(col.offsets)
        base = int(offs[v0])
        offsets = (offs[v0 : v1 + 1] - base).astype(offs.dtype)
        values = np.asarray(values)[base : int(offs[v1])]
    elif values is not None:
        values = np.asarray(values)[v0:v1]
    return Column(leaf=leaf, values=values, offsets=offsets,
                  validity=asm.validity, list_offsets=asm.list_offsets,
                  list_validity=asm.list_validity, num_slots=s1 - s0,
                  dictionary=col.dictionary,
                  dictionary_host=col.dictionary_host,
                  dict_indices=dict_indices,
                  def_levels=d_sl, rep_levels=r_sl)


def iter_batches(pf: ParquetFile, columns: Optional[Sequence[str]] = None,
                 batch_rows: int = 65536,
                 strict_batch_rows: bool = False,
                 policy: Optional[FaultPolicy] = None,
                 report: Optional[ReadReport] = None) -> Iterator[Table]:
    """Stream the file as row-aligned :class:`Table` batches of at most
    ``batch_rows`` rows, holding O(pages-per-batch) memory per column.

    ``columns`` selects leaves by dotted path (default: all).  Batches are
    snapped to row-group boundaries when at least half of ``batch_rows``
    is pending (same behavior as pyarrow's ``iter_batches`` — avoids the
    cross-group column concat); only under-half remainders of small row
    groups accumulate across the boundary.  Batch sizes therefore VARY,
    bounded by ``batch_rows`` (a behavior change in r4 — callers that
    relied on fixed-size batches should pass ``strict_batch_rows=True``,
    which restores exactly ``batch_rows`` rows per batch except the last
    at the cost of cross-group concatenation).  Concatenating every batch
    equals a full :meth:`ParquetFile.read`.

    ``policy`` (default: the file's open-time policy) applies the
    resilience layer (io/faults.py): source preads retry transient errors,
    the whole drain runs under one ``deadline_s`` clock (started at the
    first pull), and with ``on_corrupt='skip_row_group'`` a corrupt row
    group's **un-yielded** rows are dropped — batches already yielded from
    it stay valid — with the loss accounted in ``report``.
    """
    if batch_rows <= 0:
        raise ValueError("batch_rows must be positive")
    gen = _iter_batches_gen(pf, columns, batch_rows, strict_batch_rows,
                            policy, report)
    # request scope around each pull (obs/scope.py): the drain gets its
    # own op identity unless the caller already opened one
    return _oscope.scoped_iter("file.iter_batches", gen, file=pf._path)


def _iter_batches_gen(pf, columns, batch_rows, strict_batch_rows, policy,
                      report) -> Iterator[Table]:
    pol, report = resolve_policy(pf, policy, report)
    skip = pol is not None and pol.skip_corrupt
    leaves = [pf.schema.leaf(c) for c in columns] if columns is not None \
        else list(pf.schema.leaves)
    paths = [leaf.dotted_path for leaf in leaves]
    with pf._resilient_op(policy, report, "iter_batches"):
        yield from _iter_batches_impl(pf, paths, batch_rows,
                                      strict_batch_rows, skip, report)


def _take_contextual(pf, cursor, path, rg_index, take):
    """One column's take, wrapped in read_context so failures — on this
    thread or a pool worker — surface as located ReadErrors.  The
    ``decode.stream`` span carries the thread it decoded on: with the
    pooled fan-out active, columns of one batch step show as parallel
    bars on different worker tracks."""
    dec_span = (_trace.span("decode.stream", rg=rg_index, col=path,
                            rows=take)
                if _trace.on() else _trace.NULL_SPAN)
    with dec_span, \
            read_context(path=pf._path, row_group=rg_index, column=path):
        pieces, got = cursor.take(take)
        if got != take:
            raise CorruptedError(
                f"streaming cursor yielded {got} of {take} rows "
                "(page stream shorter than row-group metadata)")
        return pieces


def _iter_batches_impl(pf, paths, batch_rows, strict_batch_rows, skip,
                       report, row_groups=None,
                       rg_done=None) -> Iterator[Table]:
    """``row_groups`` restricts the drain to those row-group indices (in
    the given order); ``rg_done(rg_index, {path: [Column, ...]})`` fires
    after each row group fully streams (never for a skipped group) with
    the column pieces that went into the yielded batches — the whole-file
    streamed read uses it to populate the decoded-chunk cache at
    row-group granularity."""
    from ..utils.pool import available_cpus, in_shared_pool
    from .prefetch import make_prefetcher

    rg_sel = list(row_groups) if row_groups is not None \
        else list(range(len(pf.row_groups)))
    n_rg = len(rg_sel)
    # ---- layer 1: prefetching IO (io/prefetch.py).  One per drain; plans
    # are registered per row group, double-buffered: when row group N's
    # cursors are built, N+1's chunk ranges are planned too, so page decode
    # of N overlaps readahead of N+1.
    pre = make_prefetcher(pf.source, n_streams=len(paths))
    stats = pre.stats if pre is not None else None
    planned = -1

    def plan_rg(pos: int) -> None:
        nonlocal planned
        if pre is None or pos >= n_rg or pos <= planned:
            return
        planned = pos
        for p in paths:
            pre.plan(*pf.row_group(rg_sel[pos]).column(p).byte_range)

    # ---- layer 2: parallel streamed decode.  Per batch step, the
    # per-column takes (pread + decompress + decode — all GIL-releasing in
    # the codec/native layers) fan out across the shared pool.  Serial
    # below the measured crossover, on one core (threads are a pure loss
    # against a warm page cache there), and when already inside a pool
    # worker (no nested-fanout deadlocks).
    use_pool = (len(paths) > 1 and available_cpus() > 1
                and not in_shared_pool()
                and pf.num_rows * len(paths) >= _PARALLEL_MIN_CELLS
                and env_bool("PARQUET_TPU_STREAM_PARALLEL"))

    pos_iter = iter(range(n_rg))
    cursors: Optional[Dict[str, _ChunkCursor]] = None
    rg_rows_left = 0
    pending: Dict[str, List[Column]] = {p: [] for p in paths}
    pending_rows = 0
    rg_parts: Dict[str, List[Column]] = {p: [] for p in paths}

    def flush() -> Table:
        nonlocal pending, pending_rows
        # parts-form Table: per-leaf concat stays lazy, and to_arrow takes
        # the chunked path (zero-concat chunked arrays + DictionaryArray
        # passthrough for arrow-dictionary-typed fields) exactly like the
        # whole-file read
        t = Table(pf.schema, None, pending_rows,
                  parts={p: list(parts) for p, parts in pending.items()},
                  dict_fields=pf.arrow_dictionary_fields)
        if report is not None:
            report.rows_read += pending_rows
            t.report = report
        t.read_stats = stats
        pending = {p: [] for p in paths}
        pending_rows = 0
        return t

    def take_all(take: int) -> None:
        """All columns' takes for one step, pooled or serial; extends
        ``pending`` only after every column succeeded (order-stable)."""
        if use_pool:
            from ..utils.pool import submit as pool_submit

            futs = [(p, pool_submit(_take_contextual, pf, cursors[p], p,
                                    rg_index, take)) for p in paths]
            results, first_err = {}, None
            for p, f in futs:
                try:
                    results[p] = f.result()
                except DeadlineError:
                    raise
                except Exception as e:
                    if first_err is None:
                        first_err = e
            if first_err is not None:
                raise first_err
        else:
            results = {p: _take_contextual(pf, cursors[p], p, rg_index,
                                           take) for p in paths}
        for p in paths:
            pending[p].extend(results[p])
            if rg_done is not None:
                rg_parts[p].extend(results[p])

    try:
        while True:
            if rg_rows_left == 0:
                pos = next(pos_iter, None)
                if pos is None:
                    break
                rg_index = rg_sel[pos]
                rg = pf.row_group(rg_index)
                plan_rg(pos)
                plan_rg(pos + 1)  # double buffer: readahead of N+1
                cursors = {p: _ChunkCursor(chunk=rg.column(p), source=pre)
                           for p in paths}
                rg_rows_left = rg.num_rows
                if rg_done is not None:
                    rg_parts = {p: [] for p in paths}
            take = min(batch_rows - pending_rows, rg_rows_left)
            # snapshot so a mid-take corruption can roll back this step's
            # partial, column-misaligned contributions
            marks = {p: len(pending[p]) for p in paths}
            try:
                take_all(take)
            except DeadlineError:
                raise
            except CorruptedError as e:
                if not skip:
                    raise
                for p in paths:
                    del pending[p][marks[p]:]
                # rows of this group already yielded (or aligned in pending
                # from earlier steps) decoded fine and stay; only the
                # remainder drops
                report.record_skip(rg_index, rows=rg_rows_left, error=e)
                rg_rows_left = 0
                if pre is not None:
                    # the abandoned group's plans would otherwise pin their
                    # issued windows for the rest of the drain (they retire
                    # on consumption, which will never come)
                    for p in paths:
                        pre.unplan(*rg.column(p).byte_range)
                continue
            pending_rows += take
            rg_rows_left -= take
            if rg_rows_left == 0 and rg_done is not None:
                rg_done(rg_index, rg_parts)
            # Flush at row-group boundaries too (batches are "at most
            # batch_rows" — a snapped batch is legal and value-identical in
            # concatenation): a batch spanning row groups would pay a full
            # column concat at flush, the measured remainder of the
            # streaming read's deficit vs the whole-file read.  Keep
            # accumulating only when the pending batch is under half target
            # (tiny row groups).
            if pending_rows >= batch_rows or (
                    not strict_batch_rows and rg_rows_left == 0
                    and pending_rows * 2 >= batch_rows):
                yield flush()
        if pending_rows:
            yield flush()
    finally:
        if pre is not None:
            pre.close()  # cancel queued windows; the file stays open
