"""Threaded predicate-pushdown scan over row groups.

Reference parity: the reference has no internal parallelism — its documented
concurrency model is the *caller* fanning goroutines out over row groups /
column chunks (SURVEY.md §2.5, "caller-driven goroutine fan-out"; the read
path is immutable-after-open and goroutine-safe).  This module packages that
fan-out as a first-class API: zone-map pruning picks the covering pages
(io/search.py), a thread pool decodes the surviving (row-group, column)
chunks concurrently — the host decoders spend their time in numpy / the C++
shim / the codec libraries, all of which release the GIL — and the exact
predicate is applied to the decoded keys.
"""

from __future__ import annotations

import time as _time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..errors import CorruptedError, DeadlineError
from ..obs import scope as _oscope
from ..obs import trace as _otrace
from ..obs.metrics import counter as _ocounter
from ..obs.metrics import histogram as _ohistogram
from ..obs.metrics import pool_wait_seconds as _pool_wait_seconds

# resolved once: per-file observation must not take the registry's
# get-or-create lock (only the metric's own)
_M_SCAN_FILE_S = _ohistogram("dataset.scan_file_s")
_M_ROWS_PRUNED = _ocounter("scan.rows_pruned")
_M_ROWS_DECODED = _ocounter("scan.rows_decoded")
from ..io.faults import (FaultPolicy, ReadReport, read_context,
                         resolve_policy)
from ..io.reader import ParquetFile
from ..io.search import BA_ARRAYS, plan_scan, read_row_range

__all__ = ["scan", "scan_expr", "scan_filtered", "scan_filtered_device",
           "scan_filtered_sharded", "scan_files", "merge_scan_results",
           "expr_mask"]

from ..utils.pool import (in_shared_pool as _in_pool,
                          instrument_task as _instrument_task,
                          mark_pooled as _mark_pooled,
                          read_admission as _read_admission,
                          shared_pool as _pool)

# decoded_scan: spans between survivor-count syncs (bounds device residency
# at ~_SYNC_EVERY spans of uncompacted output while amortizing the RTT)
_SYNC_EVERY = 8


def _materialize_ba(values: np.ndarray, offs: np.ndarray,
                    sel: np.ndarray) -> List[bytes]:
    """Python bytes for the SELECTED value ordinals only (native gather of
    the survivors, then one materialization pass)."""
    if len(sel) == 0:
        return []
    from .. import native as _native

    g = _native.gather_ba(values, offs, sel)
    if g is None:  # shim unavailable: direct per-selected materialization
        return [values[offs[i]:offs[i + 1]].tobytes() for i in sel]
    gv, go = g
    return [gv[go[i]:go[i + 1]].tobytes() for i in range(len(sel))]


def scan_filtered(pf: ParquetFile, path: str, lo=None, hi=None,
                  columns: Optional[Sequence[str]] = None,
                  num_threads: Optional[int] = None,
                  use_bloom: bool = True,
                  values: Optional[Sequence] = None,
                  policy: Optional[FaultPolicy] = None,
                  report: Optional[ReadReport] = None) -> Dict[str, np.ndarray]:
    """Scan ``columns`` for rows where ``lo <= file[path] <= hi`` — or, with
    ``values``, where ``file[path] ∈ values`` (IN-list pushdown: statistics,
    zone maps and bloom filters all prune against the probe set).

    This is the single-column face of :func:`scan_expr`: the predicate
    becomes a one-leaf tree and the unified planner (io/planner.py) runs
    the pushdown cascade.  Output forms, null semantics, and the
    resilience contract are documented there; this signature is kept
    stable for existing callers."""
    from ..algebra.expr import single_pred

    return scan_expr(pf, single_pred(path, lo=lo, hi=hi, values=values),
                     columns=columns, num_threads=num_threads,
                     use_bloom=use_bloom, policy=policy, report=report)


def scan_expr(pf: ParquetFile, where, columns: Optional[Sequence[str]] = None,
              num_threads: Optional[int] = None, use_bloom: bool = True,
              policy: Optional[FaultPolicy] = None,
              report: Optional[ReadReport] = None) -> Dict[str, object]:
    """Scan ``columns`` for rows matching a predicate tree ``where``
    (:mod:`parquet_tpu.algebra.expr`): ``And``/``Or``/``Not`` over range,
    IN-list, equality, and null-ness leaves across any number of columns.

    The unified planner prunes cheapest-first — chunk statistics, then
    page-index zone maps (intersected/unioned through the tree), then
    bloom filters for equality leaves — and the scan then **late-
    materializes**: only the filter columns' candidate pages decode first;
    output columns decode only the pages covering rows that survived the
    exact predicate, so a selective scan never touches most of its output
    bytes.

    Returns ``{column: values}`` with the predicate applied.  Rows where
    any compared column is NULL fail that leaf (SQL three-valued
    semantics; ``col(x).is_null()`` selects them).  Nullable numeric
    output columns come back as ``np.ma.MaskedArray`` (mask=True at
    nulls); BYTE_ARRAY columns as lists with ``None`` entries.  Flat
    columns only (nested columns have no single row-aligned array to
    mask; read them via :func:`read_row_range` per surviving span
    instead) — the default selection takes every flat column not used in
    the predicate.

    ``policy`` (default: the file's open-time policy) applies the
    resilience layer (io/faults.py): span reads retry transient errors,
    the whole scan runs under ``deadline_s``, and with
    ``on_corrupt='skip_row_group'`` a corrupt row group's candidate spans
    drop from the result (other groups' matches still return), accounted
    in ``report``.  Failures surface as ``ReadError`` naming
    file/row-group/column.
    """
    pol, report = resolve_policy(pf, policy, report)
    # request scope (obs/scope.py): joins the caller's (or the dataset
    # layer's) op when one is active, else this scan is its own op
    with _oscope.maybe_op_scope("file.scan", file=pf._path):
        with pf._resilient_op(policy, report, "scan_expr"):
            return _scan_expr_impl(pf, where, columns, num_threads,
                                   use_bloom, pol, report)


class _SpanFailure:
    """Sentinel for one failed (span, column) read task."""

    __slots__ = ("rg_index", "error")

    def __init__(self, rg_index, error):
        self.rg_index = rg_index
        self.error = error


def _expr_mask(expr, env: Dict[str, tuple], n: int) -> np.ndarray:
    """Exact row mask of a prepared tree over one span's aligned filter
    columns (``env[path] -> (values, validity)``)."""
    from ..algebra.expr import And as _And, Const as _Const, Pred as _Pred

    if isinstance(expr, _Const):
        return np.full(n, expr.value, bool)
    if isinstance(expr, _Pred):
        return _pred_mask(expr, env[expr.path], n)
    masks = [_expr_mask(c, env, n) for c in expr.children]
    out = masks[0].copy()
    for m in masks[1:]:
        if isinstance(expr, _And):
            out &= m
        else:
            out |= m
    return out


def expr_mask(expr, env: Dict[str, tuple], n: int) -> np.ndarray:
    """Public face of :func:`_expr_mask` for the aggregation cascade
    (io/aggregate.py): the EXACT row mask of a prepared tree over
    row-aligned ``(values, validity)`` spans — byte-for-byte the same
    order-domain comparison semantics every filtered scan applies, so a
    decoded aggregate and a scan-then-aggregate can never disagree."""
    return _expr_mask(expr, env, n)


def _fused_span_mask(pf, rg_i: int, s: int, count: int,
                     fcols: Sequence[str], expr) -> np.ndarray:
    """Phase 1, fused: the span's filter pages are decoded, evaluated,
    and DISCARDED one block at a time on the union page grid (each block
    lies inside one page per filter column; a cursor's previous page —
    and its ledger bytes — release as it advances).  The full predicate
    mask comes back without a whole filter span ever being alive.
    Raises :class:`~parquet_tpu.io.fused.FusedUnsupported` when any
    filter column lacks an offset index (caller falls back)."""
    from ..io.fused import _M_SCAN_SPANS, PageCursor

    rg = pf.row_groups[rg_i]
    cursors = {c: PageCursor(rg, pf.schema.leaf(c)) for c in fcols}
    e = s + count
    mask = np.empty(count, bool)
    cuts = sorted({cc for cur in cursors.values() for cc in cur.grid(s, e)})
    bounds = [s] + cuts + [e]
    for bs, be in zip(bounds, bounds[1:]):
        env = {c: cursors[c].aligned(bs, be) for c in fcols}
        mask[bs - s:be - s] = _expr_mask(expr, env, be - bs)
    _oscope.account(_M_SCAN_SPANS)
    return mask


def _pred_mask(pred, span_val: tuple, n: int) -> np.ndarray:
    """One leaf's exact mask, in the leaf's order domain — the same
    comparison semantics the pruning cascade used (str → bytes, decimals
    by unscaled int, unsigned keys in the unsigned view; NULL never
    matches a range/IN leaf, negated or not)."""
    from ..algebra.compare import decode_order_value, is_unsigned

    keys, key_valid = span_val
    leaf = pred.leaf
    if pred.kind == "null":
        return (np.zeros(n, bool) if key_valid is None
                else ~np.asarray(key_valid, bool))
    if pred.kind == "notnull":
        return (np.ones(n, bool) if key_valid is None
                else np.asarray(key_valid, bool))
    lo, hi = pred.lo, pred.hi
    flba_rows = (not isinstance(keys, list)
                 and getattr(keys, "ndim", 1) == 2
                 and keys.dtype == np.uint8)
    if isinstance(keys, list) or flba_rows:
        # BYTE_ARRAY / FLBA keys: Python comparisons in the order domain
        # (decode_order_value handles decimal two's-complement ordering)
        if flba_rows:
            keys = [bytes(r) for r in np.asarray(keys)]
            if key_valid is not None:
                keys = [k if v else None for k, v in zip(keys, key_valid)]
        keys = [None if x is None else decode_order_value(bytes(x), leaf)
                for x in keys]
        if pred.kind == "in":
            probe_set = set(pred.values)
            base = np.fromiter((x is not None and x in probe_set
                                for x in keys), bool, count=len(keys))
        else:
            base = np.fromiter(
                ((x is not None
                  and (lo is None or x >= lo) and (hi is None or x <= hi))
                 for x in keys), bool, count=len(keys))
        if pred.negated:
            present = np.fromiter((x is not None for x in keys), bool,
                                  count=len(keys))
            return present & ~base
        return base
    if is_unsigned(leaf) and keys.dtype in (np.dtype(np.int32),
                                            np.dtype(np.int64)):
        keys = keys.view(np.uint32 if keys.dtype == np.dtype(np.int32)
                         else np.uint64)
    if pred.kind == "in":
        probes = np.array(pred.values, dtype=keys.dtype)
        base = np.isin(keys, probes)
    else:
        base = np.ones(len(keys), bool)
        if lo is not None:
            base &= keys >= lo
        if hi is not None:
            base &= keys <= hi
    valid = None if key_valid is None else np.asarray(key_valid, bool)
    if pred.negated:
        return ~base if valid is None else valid & ~base
    if valid is not None:
        base &= valid  # SQL semantics: NULL fails the predicate
    return base


def aligned_key_mask(leaf, key, values, validity) -> np.ndarray:
    """Exact equality mask of one NORMALIZED key over a row-aligned span —
    the point-lookup face of the scan's :func:`_pred_mask`, so batched
    ``find_rows`` (io/lookup.py) matches keys with byte-for-byte the same
    order-domain comparison semantics every filtered scan uses (unsigned
    views, decimal unscaled ints, NULL never matches)."""
    from ..algebra.expr import Pred

    if isinstance(values, list) or isinstance(values, tuple):
        n = len(values)
        values = list(values)
    elif validity is not None:
        n = len(validity)
    else:
        n = len(values)
    pred = Pred(leaf.dotted_path, "range", lo=key, hi=key, leaf=leaf,
                prepared=True)
    return _pred_mask(pred, (values, validity), n)


_NESTED_MSG = ("column {c!r} is nested; scan_filtered returns row-aligned "
               "arrays — use read_row_range per plan for nested columns")


def _scan_expr_impl(pf, where, columns, num_threads, use_bloom, pol,
                    report) -> Dict[str, object]:
    from ..algebra.expr import Expr, prepare
    from ..io.planner import ScanPlanner, _collect_preds

    if not isinstance(where, Expr):
        raise TypeError("where must be an Expr tree (build with col(); "
                        f"got {type(where).__name__})")
    leaves = {leaf.dotted_path for leaf in pf.schema.leaves}
    flat = {leaf.dotted_path for leaf in pf.schema.leaves
            if leaf.max_repetition_level == 0}
    want = sorted(where.columns())
    for c in want:
        if c not in leaves:
            raise KeyError(f"unknown predicate column {c!r}")
        if c not in flat:
            raise ValueError(_NESTED_MSG.format(c=c))
    # default selection: every flat column not in the predicate (nested
    # ones have no single row-aligned array to mask — read them via
    # read_row_range per plan)
    out_cols = list(columns) if columns is not None else sorted(flat
                                                                - set(want))
    for c in out_cols:
        if c not in leaves:
            raise KeyError(f"unknown column {c!r}")
        if c not in flat:
            raise ValueError(_NESTED_MSG.format(c=c))

    expr = prepare(where, pf.schema)
    plan = ScanPlanner(pf, policy=pol, report=report).plan(
        expr, use_bloom=use_bloom)
    fcols = sorted({p.path for p in _collect_preds(expr)})

    rg_base = np.zeros(len(pf.row_groups), np.int64)
    np.cumsum([rg.num_rows for rg in pf.row_groups[:-1]], out=rg_base[1:])
    # surviving (row group, global row range) spans, in row order
    spans = [(d.rg_index, int(rg_base[d.rg_index]) + s, e - s)
             for d in plan.survivors for (s, e) in d.ranges]
    rg_cand = {}
    for rg_i, _, count in spans:
        rg_cand[rg_i] = rg_cand.get(rg_i, 0) + count

    skip = pol is not None and pol.skip_corrupt

    # unified read budget (utils/pool.py): every phase-1/2 decode span
    # admits its estimated uncompressed bytes through the same FIFO gate
    # the lookup path uses, so PARQUET_TPU_READ_BUDGET bounds scan +
    # lookup in-flight bytes together.  Estimate = the chunk's footer
    # uncompressed size prorated to the span's rows (zero IO; memoized
    # per (row group, column)).  Default budget for the scan tier is off,
    # so this costs one env read per task until an operator opts in.
    admission = _read_admission()
    bytes_per_row: Dict[tuple, float] = {}

    def _span_bytes(rg_i: int, c: str, count: int) -> int:
        got = bytes_per_row.get((rg_i, c))
        if got is None:
            rg_meta = pf.metadata.row_groups[rg_i]
            col_i = pf.schema.leaf(c).column_index
            tot = (rg_meta.columns[col_i].meta_data
                   .total_uncompressed_size or 0)
            got = tot / max(rg_meta.num_rows or 1, 1)
            bytes_per_row[(rg_i, c)] = got
        return int(got * count)

    def read_one(task):
        rg_i, start, count, c, form = task
        try:
            with read_context(path=pf._path, row_group=rg_i, column=c):
                with admission.admit(_span_bytes(rg_i, c, count),
                                     tier="scan"):
                    return read_row_range(pf, c, start, count, aligned=form)
        except DeadlineError:
            raise
        except CorruptedError as e:
            # captured per task (pool map would otherwise drop sibling
            # results on the floor); re-raised or skipped below
            return _SpanFailure(rg_i, e)

    def fan_out(fn, tasks, cells):
        # thread-pool dispatch costs ~100us/task: serial decode wins for
        # small plans (measured crossover around a few hundred thousand
        # cells).  Inside a pool worker (the dataset layer's per-FILE
        # fan-out) the scan stays serial: a nested _pool().map blocking on
        # futures no free worker can run would deadlock the shared pool.
        if num_threads == 1 or len(tasks) <= 1 or (num_threads is None
                                                   and (cells < 2_000_000
                                                        or _in_pool())):
            return [fn(t) for t in tasks]
        if num_threads is None:
            # fan out per (span, column): the decode work releases the GIL
            # in numpy/C++/codec calls.  mark_pooled keeps the per-worker
            # native decompress split at 1 (no pool x native
            # oversubscription).
            # instrument_task: this map's queue waits must reach
            # pool.queue_wait_s — the scan router's saturation delta for
            # the host route is measured from exactly these tasks
            return list(_pool().map(
                _instrument_task(_mark_pooled(fn), name="scan_read"),
                tasks))
        with ThreadPoolExecutor(max_workers=num_threads) as pool:
            return list(pool.map(_mark_pooled(fn), tasks))

    def drop_bad_rgs(failures):
        """Degraded scan: drop every span of each corrupt row group (spans
        are sub-row-group; partial groups would misalign filter vs output
        columns), account the loss, keep scanning the rest."""
        bad = {}
        for f in failures:
            bad.setdefault(f.rg_index, f.error)
        if not skip:
            raise failures[0].error
        for rg_i in sorted(bad):
            report.record_skip(rg_i, rows=rg_cand.get(rg_i, 0),
                               error=bad[rg_i])
        return set(bad)

    # ---- phase 1: decode only the FILTER columns' candidate pages and
    # evaluate the exact predicate (aligned=True: order-domain compares
    # are per-value).  Fused variant (PARQUET_TPU_FUSED / choose_fused on
    # the plan's filter-column byte estimate): each span's filter pages
    # are evaluated and DISCARDED page-by-page on the union page grid —
    # phase 1 never holds a whole filter span, at the cost of re-reading
    # filter columns that are also output columns in phase 2.
    from ..io.planner import choose_fused
    use_fused = bool(fcols) and bool(spans) \
        and choose_fused(plan.est_bytes([]))
    cand_rows = sum(count for _, _, count in spans)

    from ..io.fused import FusedUnsupported

    def mask_one(si):
        rg_i, gstart, count = spans[si]
        s = int(gstart - rg_base[rg_i])
        try:
            with read_context(path=pf._path, row_group=rg_i):
                try:
                    return _fused_span_mask(pf, rg_i, s, count, fcols,
                                            expr)
                except FusedUnsupported:
                    from ..io.fused import _M_FALLBACKS
                    _oscope.account(_M_FALLBACKS)
                    env = {}
                    for c in fcols:
                        with admission.admit(_span_bytes(rg_i, c, count),
                                             tier="scan"):
                            env[c] = read_row_range(pf, c, gstart, count,
                                                    aligned=True)
                    return _expr_mask(expr, env, count)
        except DeadlineError:
            raise
        except CorruptedError as e:
            return _SpanFailure(rg_i, e)

    p1_span = (_otrace.span("scan.phase1", file=pf._path,
                            spans=len(spans), cand_rows=cand_rows)
               if _otrace.on() else _otrace.NULL_SPAN)
    # `with`: a failing fan-out (deadline, unskippable corruption) must
    # still record the span — the failed run is the one worth tracing
    with p1_span:
        if use_fused:
            res1 = fan_out(mask_one, list(range(len(spans))),
                           cand_rows * max(len(fcols), 1))
            failures = [r for r in res1 if isinstance(r, _SpanFailure)]
            if failures:
                bad = drop_bad_rgs(failures)
                keep = [i for i, s in enumerate(spans) if s[0] not in bad]
                res1 = [res1[i] for i in keep]
                spans = [spans[i] for i in keep]
            # filter pages were folded and dropped: nothing to reuse
            envs = [{} for _ in spans]
            masks = res1
        else:
            tasks1 = [(rg_i, start, count, c, True)
                      for (rg_i, start, count) in spans for c in fcols]
            res1 = fan_out(read_one, tasks1,
                           cand_rows * max(len(fcols), 1))
            failures = [r for r in res1 if isinstance(r, _SpanFailure)]
            if failures:
                bad = drop_bad_rgs(failures)
                keep = [i for i, s in enumerate(spans) if s[0] not in bad]
                res1 = [res1[i * len(fcols) + j] for i in keep
                        for j in range(len(fcols))]
                spans = [spans[i] for i in keep]
            k = len(fcols)
            envs = [{c: res1[i * k + j] for j, c in enumerate(fcols)}
                    for i in range(len(spans))]
            masks = [_expr_mask(expr, env, count)
                     for (rg_i, start, count), env in zip(spans, envs)]

    # ---- phase 2: late materialization — output columns decode only the
    # pages covering rows that SURVIVED the exact predicate (the span is
    # trimmed to [first survivor, last survivor]; a span with no survivors
    # is never read).  Columns that also filter reuse the phase-1 decode.
    trims = []
    for mask in masks:
        idx = np.flatnonzero(mask)
        trims.append((int(idx[0]), int(idx[-1]) + 1) if len(idx) else None)
    # output columns stay columnar ("arrays"): python bytes objects are
    # materialized only for surviving rows — per-row materialization of
    # the full span was the scan's dominant cost on string output columns
    # fused phase 1 discards filter pages as it folds them, so filter
    # columns that are also output re-read (survivor-trimmed) in phase 2
    fset = set() if use_fused else set(fcols)
    read2_cols = [c for c in out_cols if c not in fset]
    tasks2 = [(spans[si][0], spans[si][1] + t0, t1 - t0, c, "arrays")
              for si, trim in enumerate(trims) if trim is not None
              for t0, t1 in [trim] for c in read2_cols]
    cells2 = sum(t1 - t0 for t in trims if t is not None
                 for t0, t1 in [t]) * max(len(read2_cols), 1)
    p2_span = (_otrace.span("scan.phase2", file=pf._path,
                            tasks=len(tasks2), cells=cells2)
               if _otrace.on() else _otrace.NULL_SPAN)
    with p2_span:  # `with`: record the span even when the fan-out raises
        res2 = fan_out(read_one, tasks2, cells2)
    failures = [r for r in res2 if isinstance(r, _SpanFailure)]
    if failures:
        bad = drop_bad_rgs(failures)
        # remove the corrupt row groups' phase-1 contributions too
        res2_by_span = {}
        ti = 0
        for si, trim in enumerate(trims):
            if trim is None:
                continue
            res2_by_span[si] = res2[ti:ti + len(read2_cols)]
            ti += len(read2_cols)
        keep = [i for i, s in enumerate(spans) if s[0] not in bad]
        spans = [spans[i] for i in keep]
        envs = [envs[i] for i in keep]
        masks = [masks[i] for i in keep]
        trims = [trims[i] for i in keep]
        res2 = [r for i in keep if i in res2_by_span
                for r in res2_by_span[i]]

    # ---- assembly: identical output forms to the historical scan
    parts: Dict[str, List] = {c: [] for c in out_cols}
    vparts: Dict[str, List] = {c: [] for c in out_cols}
    ti = 0
    for si, ((rg_i, start, count), mask, trim) in enumerate(
            zip(spans, masks, trims)):
        if trim is None:
            continue  # no survivors: output pages never decoded
        t0, t1 = trim
        span2 = {c: res2[ti + j] for j, c in enumerate(read2_cols)}
        ti += len(read2_cols)
        idx = np.flatnonzero(mask)
        m_t = mask[t0:t1]
        for c in out_cols:
            if c in envs[si]:
                vals, valid = envs[si][c]  # phase-1 aligned=True form
                if isinstance(vals, list):
                    parts[c].append([vals[i] for i in idx])
                else:
                    parts[c].append(np.asarray(vals)[mask])
                    if valid is not None:
                        vparts[c].append(np.asarray(valid, bool)[mask])
                    elif vparts[c]:  # earlier span had nulls: keep aligned
                        vparts[c].append(np.ones(int(mask.sum()), bool))
                continue
            vals, valid = span2[c]
            if isinstance(vals, tuple) and vals and vals[0] == BA_ARRAYS:
                _, v_u8, offs = vals
                idx_t = np.flatnonzero(m_t)
                if valid is None:
                    parts[c].append(_materialize_ba(v_u8, offs, idx_t))
                else:
                    ords = np.cumsum(valid) - 1  # row -> dense ordinal
                    tv = np.asarray(valid, bool)[idx_t]
                    got = _materialize_ba(v_u8, offs, ords[idx_t][tv])
                    woven = [None] * len(idx_t)
                    for p, v in zip(np.flatnonzero(tv), got):
                        woven[p] = v
                    parts[c].append(woven)
            elif isinstance(vals, list):
                parts[c].append([vals[i] for i in np.flatnonzero(m_t)])
            else:
                parts[c].append(np.asarray(vals)[m_t])
                if valid is not None:
                    vparts[c].append(np.asarray(valid, bool)[m_t])
                elif vparts[c]:  # earlier span had nulls: keep alignment
                    vparts[c].append(np.ones(int(m_t.sum()), bool))

    from ..format.enums import Type

    out: Dict[str, object] = {}
    for c in out_cols:
        if parts[c] and isinstance(parts[c][0], list):
            out[c] = [v for chunk in parts[c] for v in chunk]
        elif parts[c]:
            vals = np.concatenate(parts[c])
            if vparts[c]:
                n_missing = len(vals) - sum(len(v) for v in vparts[c])
                valid = np.concatenate(
                    ([np.ones(n_missing, bool)] if n_missing else []) + vparts[c])
                mask = ~valid
                if vals.ndim == 2:  # FLBA/INT96: (n, width) byte rows need a
                    mask = np.broadcast_to(mask[:, None], vals.shape)
                out[c] = np.ma.MaskedArray(vals, mask=mask)
            else:
                out[c] = vals
        elif pf.schema.leaf(c).physical_type == Type.BYTE_ARRAY:
            out[c] = []  # same host form as the non-empty path
        else:
            dt = pf.schema.leaf(c).np_dtype()
            out[c] = np.empty(0, dt or np.uint8)
    if report is not None and out_cols:
        report.rows_read += len(out[out_cols[0]])
    # OpReport attribution: rows the pushdown never decoded vs survivor
    # rows materialized (masks are final here — degraded drops included)
    _oscope.account(_M_ROWS_PRUNED, int(pf.num_rows) - cand_rows)
    _oscope.account(_M_ROWS_DECODED,
                    int(sum(int(m.sum()) for m in masks)))
    return out


# ---------------------------------------------------------------------------
# Multi-file scan (the dataset layer's fan-out; parquet_tpu/dataset.py)
# ---------------------------------------------------------------------------


def merge_scan_results(parts: List[Dict[str, object]],
                       out_cols: Sequence[str]) -> Dict[str, object]:
    """Concatenate per-file :func:`scan_filtered` results in list order —
    deterministic global output order for the dataset scan.  BYTE_ARRAY
    columns (python lists) chain; numeric columns concatenate, promoting to
    ``np.ma.MaskedArray`` when any file's span carried nulls.  Zero-row
    parts are dropped before concatenation: a file whose pages all pruned
    returns the 1-D typed empty even for (n, width)-shaped FLBA/INT96
    columns, and concatenating the two ranks would raise."""
    out: Dict[str, object] = {}
    for c in out_cols:
        vals = [p[c] for p in parts]
        if any(isinstance(v, list) for v in vals):
            out[c] = [x for v in vals for x in v]
            continue
        filled = [v for v in vals if len(v)]
        if not filled:
            out[c] = vals[0]
        elif len(filled) == 1:
            out[c] = filled[0]
        elif any(isinstance(v, np.ma.MaskedArray) for v in filled):
            out[c] = np.ma.concatenate(filled)
        else:
            out[c] = np.concatenate(filled)
    return out


def scan_files(pfs: Sequence[ParquetFile], path: Optional[str] = None,
               lo=None, hi=None,
               columns: Optional[Sequence[str]] = None,
               use_bloom: bool = True,
               values: Optional[Sequence] = None,
               policy: Optional[FaultPolicy] = None,
               report: Optional[ReadReport] = None,
               skip_files: bool = False, where=None,
               devices: Optional[Sequence] = None) -> Dict[str, object]:
    """:func:`scan_filtered` across many already-opened files, fanned out on
    the shared pool (each file's scan runs serial inside its worker — the
    pool parallelism moves up a level) with results merged in file order.
    ``where`` takes a predicate tree (each file then scans via
    :func:`scan_expr`; pass a PREPARED tree to normalize probe values once
    for the whole fleet).  Per-file row-group skips under a degraded
    ``policy`` are folded into ``report``.  ``skip_files=True`` extends
    the degraded contract to whole files: one whose scan fails outright
    (deleted mid-scan, footer fine but chunks unreadable) drops as a unit,
    recorded with its full row count as candidate rows — its partial
    row-group accounting is discarded so the loss is not double-counted.
    Returns ``{}`` when nothing (or no file) survived.  Deadline overruns
    and environment errors always propagate.  ``devices`` (a sequence of
    jax devices) round-robins each file's scan under
    ``jax.default_device(devices[i % n])`` — the Dataset device-scan
    route's per-chip assignment; results are unchanged."""
    from ..io.faults import NON_DATA_ERRORS
    from ..utils.pool import map_in_order

    if skip_files and report is None:
        # skipping whole files with nowhere to record them would be
        # silent, unaccounted data loss — refuse up front
        raise ValueError("skip_files=True requires a report to account "
                         "the dropped files")
    if (where is None) == (path is None):
        raise ValueError("pass exactly one of path (+ lo/hi/values) or "
                         "where= (a predicate tree)")
    if not pfs:
        return {}

    def one(item):
        import contextlib

        idx, pf = item
        sub = ReadReport() if report is not None else None
        if devices:
            import jax

            dev_ctx = jax.default_device(devices[idx % len(devices)])
        else:
            dev_ctx = contextlib.nullcontext()
        t0 = _time.perf_counter()
        try:
            with dev_ctx:
                if where is not None:
                    got = scan_expr(pf, where, columns=columns,
                                    use_bloom=use_bloom, policy=policy,
                                    report=sub)
                else:
                    got = scan_filtered(pf, path, lo=lo, hi=hi,
                                        columns=columns,
                                        use_bloom=use_bloom, values=values,
                                        policy=policy, report=sub)
        except DeadlineError:
            raise
        except NON_DATA_ERRORS:
            raise
        except (CorruptedError, OSError) as e:
            if not skip_files:
                raise
            return None, sub, e
        finally:
            # per-FILE scan latency: metrics_snapshot() answers the
            # dataset scan's p50/p99 per file (ROADMAP lookup-meter prep)
            _M_SCAN_FILE_S.observe(_time.perf_counter() - t0)
        return got, sub, None

    results = map_in_order(one, list(enumerate(pfs)))
    oks = []
    for pf, (got, sub, err) in zip(pfs, results):
        if got is None:
            if report is not None:
                if sub is not None:
                    # the skipped file's RETRIES really happened; only its
                    # row accounting is superseded by the file skip below
                    report.retries += sub.retries
                report.record_file_skip(pf._path or "<memory>",
                                        rows=pf.num_rows, error=err)
            continue
        if report is not None and sub is not None:
            report.merge(sub)
        oks.append(got)
    if not oks:
        return {}
    return merge_scan_results(oks, list(oks[0]))


# ---------------------------------------------------------------------------
# Device pushdown scan (SURVEY.md §3.3 on the chip; VERDICT r1 item 4)
# ---------------------------------------------------------------------------


def stage_scan(pf: ParquetFile, path: str, lo=None, hi=None,
               columns: Optional[Sequence[str]] = None,
               use_bloom: bool = True, devices: Optional[Sequence] = None,
               values: Optional[Sequence] = None,
               policy: Optional[FaultPolicy] = None,
               report: Optional[ReadReport] = None):
    """Pushdown plan + host prescan + H2D staging for a device scan.

    Split from :func:`scan_filtered_device` so callers (and the benchmark)
    can separate the host/transfer phase from on-device decode+filter.
    Returns an opaque staged-scan state consumed by :func:`decoded_scan`.
    ``devices`` stages surviving span i onto ``devices[i % len(devices)]``
    (the sharded scan's round-robin placement); default is jax's default
    device for everything.

    ``policy``/``report`` apply the resilience layer to the *staging*
    phase, where all file IO happens: preads retry under the policy, and
    ``on_corrupt='skip_row_group'`` drops the spans of a corrupt row group
    at stage time (recorded in ``report``) instead of failing the scan.
    Device-route refusals (``ValueError: ... use the host scan``) are
    routing signals, not corruption, and always propagate unchanged.
    """
    from ..io.prefetch import make_chunk_prefetcher

    pol, report = resolve_policy(pf, policy, report)
    # one span over the whole staging phase (pruning, then every chunk's
    # pread, decompress, prescan and H2D enqueue)
    with _otrace.span("stage_scan"), \
            pf._resilient_op(policy, report, "stage_scan"):
        # device-route prefetch (ROADMAP follow-on, PR 3): surviving spans'
        # chunk ranges are planned through an advise-backed prefetcher so
        # kernel readahead of later chunks overlaps prescan + H2D of
        # earlier ones, instead of one cold serial pread per chunk
        pre = make_chunk_prefetcher(
            pf.source, n_streams=(len(columns) + 2 if columns else 4))
        if pre is None:
            return _stage_scan_impl(pf, path, lo, hi, columns, use_bloom,
                                    devices, values, pol, report)
        try:
            with pf._source_override(pre):
                return _stage_scan_impl(pf, path, lo, hi, columns, use_bloom,
                                        devices, values, pol, report,
                                        prefetcher=pre)
        finally:
            pre.close()


def _stage_scan_impl(pf, path, lo, hi, columns, use_bloom, devices, values,
                     pol, report, prefetcher=None):
    import contextlib

    import jax

    from . import device_reader as dr

    from ..format.enums import Type
    from ..io.search import pages_and_base

    flat = {leaf.dotted_path for leaf in pf.schema.leaves
            if leaf.max_repetition_level == 0}
    out_cols = list(columns) if columns is not None else sorted(flat - {path})
    for c in [path] + out_cols:
        if c not in flat:
            raise ValueError(f"column {c!r} is nested or unknown; the "
                             "device scan handles flat columns — use the "
                             "host scan")
    from ..schema.types import LogicalKind

    key_leaf = pf.schema.leaf(path)
    if key_leaf.physical_type in (Type.FIXED_LEN_BYTE_ARRAY, Type.INT96):
        raise ValueError(f"device scan key {path!r} has physical type "
                         f"{key_leaf.physical_type.name}; use the host scan")
    if (key_leaf.physical_type == Type.BYTE_ARRAY
            and key_leaf.logical_kind == LogicalKind.DECIMAL):
        # decimal BYTE_ARRAY orders by unscaled two's-complement value, not
        # by bytes — the per-entry bytewise predicate below would be wrong
        raise ValueError(f"device scan key {path!r} is a decimal byte array; "
                         "use the host scan")
    if values is not None and key_leaf.physical_type in (Type.INT64,
                                                         Type.DOUBLE):
        # 64-bit keys travel as (n, 2) uint32 pairs; exact IN over pairs has
        # no scalar order for the device searchsorted — use the host scan
        raise ValueError(f"device scan IN-list on 64-bit key {path!r} is not "
                         "supported; use the host scan (scan_filtered)")
    # other BYTE_ARRAY keys are fine when dictionary-encoded (per-entry
    # predicate + device gather); plain-encoded chunks are rejected per
    # chunk below
    plans = plan_scan(pf, path, lo=lo, hi=hi, use_bloom=use_bloom,
                      values=values, policy=pol, report=report)
    if prefetcher is not None:
        # pushdown already pruned: plan exactly the surviving spans' chunk
        # byte ranges (deduped — several spans can share one row group)
        seen_ranges = set()
        for p0 in plans:
            for c in [path] + out_cols:
                br = pf.row_group(p0.rg_index).column(c).byte_range
                if br not in seen_ranges:
                    seen_ranges.add(br)
                    prefetcher.plan(*br)
    from ..algebra.compare import normalize_probe

    probe = (sorted({normalize_probe(key_leaf, v) for v in values} - {None})
             if values is not None else None)
    rg_base = np.zeros(len(pf.row_groups), np.int64)
    np.cumsum([rg.num_rows for rg in pf.row_groups[:-1]], out=rg_base[1:])
    skip = pol is not None and pol.skip_corrupt
    failed_rgs: Dict[int, object] = {}
    spans = []
    jit_cache: Dict[tuple, object] = {}
    for si, plan in enumerate(plans):
        if plan.rg_index in failed_rgs:
            continue
        rg = pf.row_group(plan.rg_index)
        row_start, row_end = plan.first_row, plan.first_row + plan.row_count
        per_col = {}
        ctx = (jax.default_device(devices[si % len(devices)]) if devices
               else contextlib.nullcontext())
        try:
            with ctx:
                for c in [path] + out_cols:
                    # kinds narrows the wrap to IO/decode failures — the
                    # device-route refusal ValueErrors below pass through
                    # unwrapped, keeping their type for scan()'s host
                    # fallback
                    with read_context(path=pf._path,
                                      row_group=plan.rg_index, column=c,
                                      kinds=(CorruptedError, OSError)):
                        chunk = rg.column(c)
                        pages, first = pages_and_base(chunk, row_start,
                                                      row_end)
                        try:
                            dplan = dr.build_plan(chunk, pages=iter(pages))
                            unsupported = (
                                chunk.leaf.physical_type == Type.BYTE_ARRAY
                                and dplan.value_kind != "dict")
                            if not unsupported:
                                staged = dr.stage_plan(dplan)
                        except dr._Unsupported as e:
                            raise ValueError(
                                f"device scan column {c!r}: {e}; use the "
                                "host scan (scan_filtered)") from None
                        if unsupported:
                            if c == path:
                                raise ValueError(
                                    f"device scan key {c!r}: plain-encoded "
                                    "BYTE_ARRAY has no row-aligned device "
                                    "form; use the host scan")
                            # plain-string OUTPUT column: keep it
                            # host-resident (slot-aligned ragged pair); the
                            # device filters on the key and only SURVIVORS'
                            # bytes materialize — the same survivor-only
                            # rule as the host scan
                            per_col[c] = ("host_ragged",) + _host_ragged_span(
                                pf, c, rg_base, plan)
                            continue
                        per_col[c] = (chunk, dplan, staged, row_start - first)
        except DeadlineError:
            raise
        except CorruptedError as e:
            if not skip:
                raise
            failed_rgs[plan.rg_index] = e
            continue
        fused = None
        if all(per_col[c][0] != "host_ragged"
               and per_col[c][1].value_kind != "dict"
               for c in [path] + out_cols):
            # lazily-built fused program, shared across same-shape spans
            # via the signature cache; the jit is only constructed from the
            # second decoded_scan call on this state (use_count below), so
            # one-shot queries never pay a trace+compile per span
            sig = (plan.row_count,
                   tuple((c, per_col[c][3],
                          per_col[c][1].total_values
                          == per_col[c][1].total_slots)
                         for c in [path] + out_cols))
            fused = _FusedFactory(jit_cache, sig, path, out_cols, per_col,
                                  lo, hi, probe, plan.row_count)
        spans.append((plan, per_col, fused))
    if failed_rgs:
        for rg_i, e in sorted(failed_rgs.items()):
            report.record_skip(
                rg_i, rows=sum(p.row_count for p in plans
                               if p.rg_index == rg_i), error=e)
        spans = [s for s in spans if s[0].rg_index not in failed_rgs]
    # per-COLUMN form consistency: a column dict-encoded in one row group
    # and plain in another must not mix device-dict and host-ragged parts
    # (the assemble routes a column by its first part's shape) — demote
    # every span of such a column to the host-ragged form
    for c in out_cols:
        kinds = {per_col[c][0] == "host_ragged"
                 for _, per_col, _ in spans}
        if kinds == {True, False}:
            for plan, per_col, _f in spans:
                if per_col[c][0] != "host_ragged":
                    per_col[c] = ("host_ragged",) + _host_ragged_span(
                        pf, c, rg_base, plan)
            # fused programs were built against the device form: disable
            # them (host_ragged spans run the eager path)
            spans = [(plan, per_col, None) for plan, per_col, _f in spans]
    return {"path": path, "out_cols": out_cols, "lo": lo, "hi": hi,
            "values": probe, "spans": spans, "use_count": [0],
            "leaves": {c: pf.schema.leaf(c) for c in out_cols}}


def _empty_device_result(leaf):
    """Typed empty matching the documented per-column output forms."""
    import jax.numpy as jnp

    from ..format.enums import Type

    t = leaf.physical_type
    if t == Type.BYTE_ARRAY:
        return ((jnp.zeros(0, jnp.uint8), jnp.zeros(1, jnp.int32)),
                jnp.zeros(0, jnp.int32))
    if t in (Type.INT64, Type.DOUBLE):
        return jnp.zeros((0, 2), jnp.uint32)
    dt = {Type.INT32: jnp.int32, Type.FLOAT: jnp.float32,
          Type.BOOLEAN: jnp.bool_}.get(t, jnp.uint8)
    return jnp.zeros(0, dt)


def _concat_dictionaries(parts):
    """Per-span (dictionary, gathered indices) → one rebased dictionary +
    concatenated indices.  Each row group carries its own dictionary page, so
    indices from span i are offset by the sizes of dictionaries 0..i-1 and
    the dictionaries concatenated (duplicate entries across spans are kept —
    correctness over minimality)."""
    import jax.numpy as jnp

    if len(parts) == 1:
        return parts[0]
    rebased, base = [], 0
    flba_or_fixed = not isinstance(parts[0][0], tuple)
    for dictionary, indices in parts:
        rebased.append(indices + base)
        if flba_or_fixed:
            base += dictionary.shape[0]
        else:
            base += dictionary[1].shape[0] - 1
    indices = jnp.concatenate(rebased)
    if flba_or_fixed:
        return jnp.concatenate([d for d, _ in parts], axis=0), indices
    # (values, offsets) byte-array form: concat values, rebase offsets
    vals_parts = [d[0] for d, _ in parts]
    off_parts, vbase = [], 0
    for d, _ in parts:
        off = d[1]
        off_parts.append(off[:-1] + vbase)
        vbase += int(off[-1])
    offsets = jnp.concatenate(off_parts + [jnp.asarray([vbase], off.dtype)])
    return (jnp.concatenate(vals_parts), offsets), indices


class _ScanCarrier:
    """In-flight per-span results between the dispatch and finalize phases."""

    def __init__(self, out_cols):
        self.parts: Dict[str, List] = {c: [] for c in out_cols}
        self.vparts: Dict[str, List] = {c: [] for c in out_cols}
        self.any_valid = {c: False for c in out_cols}
        self.counts: List = []
        self.ks_all: List[int] = []
        self.flushed = 0

    def flush(self, out_cols, upto: int) -> None:
        """Sync survivor counts for spans [flushed, upto) — ONE blocking
        stack — then trim each span's outputs with cheap device slices."""
        import jax
        import jax.numpy as jnp

        if upto <= self.flushed:
            return
        ks = [int(k) for k in np.asarray(jax.block_until_ready(
            jnp.stack(self.counts[self.flushed:upto])))]
        self.ks_all.extend(ks)
        for si, k in zip(range(self.flushed, upto), ks):
            for c in out_cols:
                p = self.parts[c][si]
                if isinstance(p, tuple) and p and p[0] == "host_ragged":
                    # trim only the device index leg; host arrays stay
                    self.parts[c][si] = p[:4] + (p[4][:k],)
                elif isinstance(p, tuple):
                    self.parts[c][si] = (p[0], p[1][:k])
                else:
                    self.parts[c][si] = p[:k]
                if self.vparts[c][si] is not None:
                    self.vparts[c][si] = self.vparts[c][si][:k]
        self.flushed = upto


def _span_arrays(forms):
    """The device arrays of ``forms`` (``{column: (values, validity)}``,
    values an array or a ``(dictionary, indices)`` pair, validity an array
    or None) that a span compacts, in :func:`_compact_span`'s order: a
    dictionary column compacts its indices and keeps its dictionary."""
    arrays = []
    for vals, valid in forms.values():
        arrays.append(vals[1] if isinstance(vals, tuple) else vals)
        if valid is not None:
            arrays.append(valid)
    return arrays


def _compact_span(mask, forms, row_ids: bool):
    """Compact a span's survivors: one ``pallas_kernels.scan_compact`` pass
    over :func:`_span_arrays` of ``forms``.  Returns ``(count, outs, vouts,
    row ids or None)`` in the forms given."""
    import jax

    from ..ops import pallas_kernels as pk

    cnt, packed = pk.scan_compact(mask, tuple(_span_arrays(forms)),
                                  row_ids=row_ids,
                                  interpret=jax.default_backend() != "tpu")
    got = iter(packed)
    outs, vouts = {}, {}
    for c, (vals, valid) in forms.items():
        outs[c] = ((vals[0], next(got)) if isinstance(vals, tuple)
                   else next(got))
        vouts[c] = next(got) if valid is not None else None
    return cnt, outs, vouts, (next(got) if row_ids else None)


class _FlatForm:
    """Minimal column shim for the fused span filter: the traced helpers
    only touch these members on non-dictionary columns."""

    __slots__ = ("values", "validity")

    def __init__(self, values, validity):
        self.values = values
        self.validity = validity

    def is_dictionary_encoded(self):
        return False


def _make_fused_span(path, out_cols, per_col, lo, hi, probe, n_rows):
    """One jitted program for a span's whole filter phase (mask, then one
    ``scan_compact`` pass over every output column).  Eagerly these are
    ~a dozen separate dispatches of ~100k-element ops, and dispatch
    overhead — not compute — dominated the device scan (measured 3 ms of
    6 ms per span on the config-5 shape).  Built once at stage time; the
    jit object lives in the staged state, so repeated decoded_scan calls
    reuse the compile.
    Only non-dictionary spans qualify (the dictionary key path folds host
    dictionary entries at trace time via a different route)."""
    import jax

    key_chunk, key_dplan, _, key_trim = per_col[path]
    key_no_nulls = key_dplan.total_values == key_dplan.total_slots
    infos = [(c, per_col[c][1], per_col[c][3]) for c in out_cols]

    def run(key_form, col_forms):
        kcol = _FlatForm(*key_form)
        mask = _key_mask_device(key_chunk.leaf, kcol, lo, hi, key_trim,
                                n_rows, key_no_nulls, values=probe)
        forms = {c: _row_aligned_device(
                     _FlatForm(*col_forms[c]), trim_c, n_rows,
                     no_nulls=dplan_c.total_values == dplan_c.total_slots)
                 for c, dplan_c, trim_c in infos}
        cnt, outs, vouts, _ = _compact_span(mask, forms, row_ids=False)
        return cnt, outs, vouts

    return jax.jit(run)


def _host_ragged_span(pf, c, rg_base, plan):
    """Host (dense values, dense offsets, validity) for one span of a
    plain-string output column — aligned=\"arrays\" keeps it columnar:
    offsets cover the DENSE present values and ``validity`` maps rows to
    value ordinals (None when null-free)."""
    start = int(rg_base[plan.rg_index]) + plan.first_row
    vals_form, valid = read_row_range(pf, c, start, plan.row_count,
                                      aligned="arrays")
    tag, vals, offs = vals_form
    assert tag == "ba_arrays", tag
    return (np.asarray(vals), np.asarray(offs, np.int64),
            None if valid is None else np.asarray(valid, bool))


class _FusedFactory:
    """Builds (once) and returns the span's fused jitted program.  Spans
    with the same shape signature share one program via ``cache``."""

    __slots__ = ("cache", "sig", "args")

    def __init__(self, cache, sig, *args):
        self.cache = cache
        self.sig = sig
        self.args = args

    def __call__(self):
        fn = self.cache.get(self.sig)
        if fn is None:
            fn = _make_fused_span(*self.args)
            self.cache[self.sig] = fn
        return fn


def _scan_dispatch(state, carrier: _ScanCarrier,
                   sync_every: Optional[int] = None) -> None:
    """Phase A — dispatch with (almost) no syncs: per span, survivors of
    every output column are compacted to a prefix in one ``scan_compact``
    pass (device-shape-static; no data-dependent host round-trip per span).
    With ``sync_every``, counts are synced in batches so device residency
    stays bounded by a few spans' worth of uncompacted output."""
    from ..format.enums import Type
    from ..ops import pallas_kernels as pk
    from ..utils.debug import counters
    from . import device_reader as dr

    path, out_cols = state["path"], state["out_cols"]
    lo, hi = state["lo"], state["hi"]
    probe = state.get("values")
    # the fused program is only worth its compile when the staged state is
    # reused; callers bump use_count once per scan call (decoded_scan /
    # sharded), so one-shot queries stay on the eager path
    amortized = state.get("use_count", [2])[0] >= 2
    for plan, per_col, fused in state["spans"]:
        n_rows = plan.row_count
        chunk, dplan, staged, trim = per_col[path]
        key = dr.decode_staged(chunk.leaf, Type(chunk.meta.type), dplan, staged)
        cols = {}
        ragged_cols = [c for c in out_cols
                       if per_col[c][0] == "host_ragged"]
        for c in out_cols:
            if per_col[c][0] == "host_ragged":
                continue
            chunk_c, dplan_c, staged_c, trim_c = per_col[c]
            cols[c] = dr.decode_staged(chunk_c.leaf, Type(chunk_c.meta.type),
                                       dplan_c, staged_c)
        if fused is not None and amortized:
            cnt, outs, vouts = fused()(
                (key.values, key.validity),
                {c: (col.values, col.validity) for c, col in cols.items()})
        else:
            no_nulls = dplan.total_values == dplan.total_slots
            mask = _key_mask_device(chunk.leaf, key, lo, hi, trim, n_rows,
                                    no_nulls, values=probe)
            forms = {c: _row_aligned_device(
                         col, per_col[c][3], n_rows,
                         no_nulls=(per_col[c][1].total_values
                                   == per_col[c][1].total_slots))
                     for c, col in cols.items()}
            arrays = _span_arrays(forms)
            if arrays or ragged_cols:
                # counted here, where jit_scan_compact runs as its own
                # program: a fused span inlines the kernel into its jit
                counters.inc("kernel_bytes.scan_compact",
                             (4 * pk.scan_compact_width(arrays) + 1)
                             * mask.shape[0])
                counters.inc("kernel_runs.scan_compact")
            cnt, outs, vouts, ragged_idx = _compact_span(
                mask, forms, row_ids=bool(ragged_cols))
            for c in ragged_cols:
                # survivor ROW indices ride the device; byte gather
                # happens host-side at assemble (survivor-only)
                _, hv, ho, hvalid = per_col[c]
                outs[c] = ("host_ragged", hv, ho, hvalid, ragged_idx)
                vouts[c] = None
        carrier.counts.append(cnt)
        for c in out_cols:
            carrier.parts[c].append(outs[c])
            if vouts[c] is not None:
                carrier.any_valid[c] = True
            carrier.vparts[c].append(vouts[c])
        if sync_every and len(carrier.counts) - carrier.flushed >= sync_every:
            carrier.flush(out_cols, len(carrier.counts))


def _assemble_host_ragged(col_parts, carrier):
    """Host-side survivor gather for a plain-string output column: per
    span, take the device-compacted row indices (already trimmed to the
    synced counts), map rows → dense value ordinals through the span
    validity, and emit ONE (uint8 values, int64 offsets) pair over all
    survivors — null survivors are zero-length entries — wrapped as
    ``(form, validity)`` when any null survives."""
    from .. import native as _nat
    from ..ops import ref as _ref

    pieces = []
    valid_parts = []
    any_nulls = False
    for i, part in enumerate(col_parts):
        _, hv, ho, hvalid, idx_dev = part
        k = int(carrier.ks_all[i])
        rows = np.asarray(idx_dev)[:k].astype(np.int64)
        if hvalid is None:
            v = np.ones(k, bool)
            ords = rows
        else:
            v = hvalid[rows]
            ords = (np.cumsum(hvalid.astype(np.int64)) - 1)[rows]
            any_nulls = any_nulls or not bool(v.all())
        sel = ords[v]
        got = _nat.gather_ba(hv, ho, sel)
        if got is not None:
            gvals = np.asarray(got[0])
        else:  # shim unavailable: numpy gather
            lens_d = ho[sel + 1] - ho[sel]
            idx = np.repeat(ho[sel], lens_d) + _ref._ranges(lens_d)
            gvals = np.asarray(hv)[idx]
        lens = np.zeros(max(k, 1), np.int64)[:k]
        lens[v] = ho[sel + 1] - ho[sel]
        offs = np.zeros(k + 1, np.int64)
        np.cumsum(lens, out=offs[1:])
        pieces.append((gvals, offs))
        valid_parts.append(v)
    vals = (np.concatenate([p[0] for p in pieces])
            if len(pieces) > 1 else pieces[0][0])
    offs_parts = [pieces[0][1]]
    base = int(pieces[0][1][-1])
    for vo in pieces[1:]:
        offs_parts.append(vo[1][1:] + base)
        base += int(vo[1][-1])
    offs = (np.concatenate(offs_parts) if len(offs_parts) > 1
            else offs_parts[0])
    form = (vals, offs)
    if any_nulls:
        return form, np.concatenate(valid_parts)
    return form


def _scan_assemble(state, carrier: _ScanCarrier) -> Dict[str, object]:
    """Phase B — sync remaining counts, slice, concatenate across spans."""
    import jax.numpy as jnp

    out_cols = state["out_cols"]
    carrier.flush(out_cols, len(carrier.counts))
    parts, vparts = carrier.parts, carrier.vparts
    out: Dict[str, object] = {}
    for c in out_cols:
        if not parts[c]:
            out[c] = _empty_device_result(state["leaves"][c])
            continue
        if (isinstance(parts[c][0], tuple)
                and parts[c][0][0] == "host_ragged"):
            out[c] = _assemble_host_ragged(parts[c], carrier)
            continue
        if isinstance(parts[c][0], tuple):  # dictionary-encoded
            form = _concat_dictionaries(parts[c])
        else:
            form = (parts[c][0] if len(parts[c]) == 1
                    else jnp.concatenate(parts[c]))
        if carrier.any_valid[c]:
            lens = [(p[1] if isinstance(p, tuple) else p).shape[0]
                    for p in parts[c]]
            valid = jnp.concatenate(
                [v if v is not None else jnp.ones(n, bool)
                 for v, n in zip(vparts[c], lens)])
            out[c] = (form, valid)
        else:
            out[c] = form
    return out


def decoded_scan(state) -> Dict[str, object]:
    """On-device phase of the pushdown scan: decode staged pages, evaluate
    the range predicate on the chip, and gather the surviving rows.

    Per-column output forms (typed empties when nothing survives):
    fixed-width → ``jax.Array`` (64-bit types in the (n, 2) uint32 pair
    representation — ``ops.device.pairs_to_host`` converts); dictionary-
    encoded byte arrays → ``(dictionary, indices)`` with per-row-group
    dictionaries rebased into one; PLAIN (non-dictionary) byte arrays →
    a host ``(uint8 values, int64 offsets)`` pair over the survivors
    (the chip filters on the key and compacts row indices; only
    survivors' bytes materialize, host-side); nullable columns wrap
    their form in a ``(form, validity)`` tuple.
    """
    state.setdefault("use_count", [0])[0] += 1
    carrier = _ScanCarrier(state["out_cols"])
    _scan_dispatch(state, carrier, sync_every=_SYNC_EVERY)
    return _scan_assemble(state, carrier)


def scan(pf: ParquetFile, path: str, lo=None, hi=None,
         columns: Optional[Sequence[str]] = None, use_bloom: bool = True,
         values: Optional[Sequence] = None,
         policy: Optional[FaultPolicy] = None,
         report: Optional[ReadReport] = None):
    """Pushdown scan, host-vs-device routed by the planner's COST MODEL
    (:func:`parquet_tpu.io.planner.choose_route`): backend, static shape
    support (the footer-level mirror of the device route's documented
    refusals — checked up front, not by throwing), estimated bytes to
    decode and stats-level selectivity from a zero-IO plan, and the
    process-wide :class:`~parquet_tpu.io.planner.RouteHistory` of measured
    per-route throughput.  On the cpu backend the threaded host route
    always wins (measured 1.8-2.7x pyarrow vs the device route's emulated
    kernels); ``PARQUET_TPU_ROUTE=host|device`` pins the choice.  The
    documented-refusal fallback (``ValueError: ... use the host scan``)
    is retained as a safety net for shapes only visible at page level
    (e.g. a dictionary chunk that fell back to plain mid-file), but it is
    no longer the router.
    NOTE the two routes' output forms differ (decoded_scan device forms
    vs scan_filtered host arrays / byte lists), and on accelerator
    backends the chosen route — hence the result form — can change with
    the plan's size and the measured history.  Callers that need ONE
    stable form should call :func:`scan_filtered` /
    :func:`scan_filtered_device` directly, or pin
    ``PARQUET_TPU_ROUTE=host|device``.  Plain-string OUTPUT columns ride
    the device route as host (values, offsets) survivor pairs."""
    # request scope over route + attempt(s): the route decision and any
    # device-attempt fallback all attribute to one op
    with _oscope.maybe_op_scope("file.scan", file=pf._path):
        return _scan_routed(pf, path, lo, hi, columns, use_bloom, values,
                            policy, report)


def _scan_routed(pf, path, lo, hi, columns, use_bloom, values, policy,
                 report):
    import dataclasses
    import time

    from ..io.planner import route_history, route_scan

    pol = policy if policy is not None else pf.policy
    with _otrace.span("route"):
        decision = route_scan(pf, path, lo=lo, hi=hi, columns=columns,
                              values=values)
    t0 = time.monotonic()
    w0 = _pool_wait_seconds()
    if decision.route == "device":
        # the device attempt works on a scratch report: a refusal fallback
        # discards its staging-phase skips (the host scan re-plans and
        # re-records them — the same report twice would double-count every
        # skipped row group) but keeps its retries, which really happened
        scratch = ReadReport() if report is not None else None
        if scratch is not None:
            # scratch skips don't publish to the metrics registry at
            # record time: a refusal fallback discards them (the host scan
            # re-records, which would double the registry totals); the
            # success path below publishes them in one shot instead
            scratch._publish = False
        try:
            got = scan_filtered_device(pf, path, lo=lo, hi=hi,
                                       columns=columns, use_bloom=use_bloom,
                                       values=values, policy=policy,
                                       report=scratch)
            route_history().observe("device", decision.est_bytes,
                                    time.monotonic() - t0,
                                    pool_wait_s=_pool_wait_seconds() - w0)
            if report is not None:
                report.merge(scratch)
                scratch.publish_skips()
            return got
        except ValueError as e:
            # only the DOCUMENTED device-route refusals fall back (their
            # messages all direct to the host scan); any other ValueError
            # is a real failure and must surface, not silently change the
            # caller's result forms
            if "use the host scan" not in str(e):
                raise
            if report is not None and scratch is not None:
                report.retries += scratch.retries
        if pol is not None and pol.deadline_s is not None:
            # the fallback continues the SAME scan: it runs on whatever
            # budget the device attempt left, not a fresh deadline
            remaining = pol.deadline_s - (time.monotonic() - t0)
            if remaining <= 0:
                raise DeadlineError(
                    "deadline exceeded during scan (device attempt spent "
                    "the budget before falling back to the host scan)")
            policy = dataclasses.replace(pol, deadline_s=remaining)
    t0 = time.monotonic()
    w0 = _pool_wait_seconds()
    got = scan_filtered(pf, path, lo=lo, hi=hi, columns=columns,
                        use_bloom=use_bloom, values=values, policy=policy,
                        num_threads=decision.pool_width, report=report)
    # hand the router the measured pool saturation of THIS scan (queue
    # waits + prefetch stalls, process-wide deltas): RouteHistory then
    # discounts the host route's effective GB/s, not just its wall clock
    route_history().observe("host", decision.est_bytes,
                            time.monotonic() - t0,
                            pool_wait_s=_pool_wait_seconds() - w0)
    return got


def scan_filtered_device(pf: ParquetFile, path: str, lo=None, hi=None,
                         columns: Optional[Sequence[str]] = None,
                         use_bloom: bool = True,
                         values: Optional[Sequence] = None,
                         policy: Optional[FaultPolicy] = None,
                         report: Optional[ReadReport] = None) -> Dict[str, object]:
    """Device-mode :func:`scan_filtered`: pushdown selects pages, the chip
    decodes them, evaluates ``lo <= key <= hi`` (or ``key ∈ values``), and
    gathers survivors — the TPU analog of SURVEY.md §3.3's
    Find→SeekToRow→decode flow.  ``policy``/``report`` guard the staging
    phase (see :func:`stage_scan`)."""
    return decoded_scan(stage_scan(pf, path, lo=lo, hi=hi, columns=columns,
                                   use_bloom=use_bloom, values=values,
                                   policy=policy, report=report))


def _key_mask_device(leaf, col, lo, hi, trim: int, n_rows: int,
                     no_nulls: bool = False, values=None):
    """Row-aligned predicate mask on device for the key column; lo/hi (or an
    IN-list ``values``) are normalized to the leaf's order domain (unsigned-
    logical keys compare in the unsigned view, matching zone-map pruning)."""
    import jax
    import jax.numpy as jnp

    from ..algebra.compare import is_unsigned, normalize
    from ..format.enums import Type
    from ..ops import device as dev

    lo, hi = normalize(leaf, lo), normalize(leaf, hi)
    vals, valid = _row_aligned_device(col, trim, n_rows, no_nulls=no_nulls)
    if isinstance(vals, tuple):
        # dictionary-encoded byte-array key: evaluate the predicate once per
        # dictionary entry on host (metadata-scale), then one device gather
        # maps entry verdicts onto the index stream
        dvals, doffs = col.dictionary_host
        doffs = np.asarray(doffs, np.int64)
        entries = [bytes(dvals[doffs[i]: doffs[i + 1]])
                   for i in range(len(doffs) - 1)]
        if values is not None:
            probe_set = set(values)
            match = np.array([e in probe_set for e in entries], bool)
        else:
            match = np.array([(lo is None or e >= lo)
                              and (hi is None or e <= hi)
                              for e in entries], bool)
        _, indices = vals
        mask = jnp.take(jnp.asarray(match), indices, axis=0)
        if valid is not None:
            mask &= valid
        return mask
    if values is not None:
        # single-word numeric key: exact IN via device searchsorted over the
        # (host-sorted) probe array — O(n log k), no probabilistic filter
        unsigned = is_unsigned(leaf)
        np_dt = {Type.INT32: np.uint32 if unsigned else np.int32,
                 Type.FLOAT: np.float32,
                 Type.BOOLEAN: np.bool_}.get(leaf.physical_type)
        if np_dt is None:
            raise ValueError("device IN-list needs a single-word key")
        probes = np.array(values, dtype=np_dt)
        if unsigned and vals.dtype == jnp.int32:
            vals = jax.lax.bitcast_convert_type(vals, jnp.uint32)
        pv = jnp.asarray(np.sort(probes))
        idx = jnp.clip(jnp.searchsorted(pv, vals), 0, len(pv) - 1)
        mask = jnp.take(pv, idx) == vals
        if valid is not None:
            mask &= valid
        return mask
    physical = leaf.physical_type
    unsigned = is_unsigned(leaf)
    if vals.ndim == 2 and vals.shape[-1] == 2 and vals.dtype == jnp.uint32:
        is_float = physical == Type.DOUBLE

        def pair_of(v):
            if v is None:
                return np.zeros(2, np.uint32)
            host = np.array([v], np.float64 if is_float
                            else np.uint64 if unsigned else np.int64)
            return host.view(np.uint32)

        mask = dev.pair_range_mask(vals, jnp.asarray(pair_of(lo)),
                                   jnp.asarray(pair_of(hi)),
                                   jnp.asarray(lo is not None),
                                   jnp.asarray(hi is not None),
                                   is_float=is_float, is_unsigned=unsigned)
    else:
        if unsigned and vals.dtype == jnp.int32:
            vals = jax.lax.bitcast_convert_type(vals, jnp.uint32)

            def bound(v):
                return jnp.uint32(np.uint32(v))
        else:
            def bound(v):
                return v
        mask = jnp.ones(vals.shape[0], bool)
        if lo is not None:
            mask &= vals >= bound(lo)
        if hi is not None:
            mask &= vals <= bound(hi)
    if valid is not None:
        mask &= valid  # SQL semantics: NULL never matches
    return mask


def _row_aligned_device(col, trim: int, n_rows: int, no_nulls: bool = False):
    """Decoded flat Column → row-aligned (values, validity) device arrays,
    trimmed to the plan's row span (pages may cover extra leading rows).
    ``no_nulls`` (known host-side from the staging plan's slot/value counts,
    so no device sync) drops the all-true validity a nullable-but-null-free
    column carries, skipping the dense→slot scatter."""
    import dataclasses

    from ..ops import device as dev

    if no_nulls and col.validity is not None:
        col = dataclasses.replace(col, validity=None)
    if col.is_dictionary_encoded():
        idx = col.dict_indices
        if col.validity is not None:
            idx = dev.scatter_valid(idx, col.validity)
        return ((col.dictionary, idx[trim:trim + n_rows]),
                None if col.validity is None
                else col.validity[trim:trim + n_rows])
    vals = col.values
    if col.validity is not None:
        vals = dev.scatter_valid(vals, col.validity)
        return (vals[trim:trim + n_rows],
                col.validity[trim:trim + n_rows])
    return vals[trim:trim + n_rows], None


def scan_filtered_sharded(pf: ParquetFile, path: str, lo=None, hi=None,
                          columns: Optional[Sequence[str]] = None,
                          mesh=None, use_bloom: bool = True,
                          policy: Optional[FaultPolicy] = None,
                          report: Optional[ReadReport] = None):
    """Distributed pushdown scan: surviving row-group spans are staged
    round-robin across the mesh's devices and decoded+filtered there —
    BASELINE.md config 5 at v5e-8 scale (SURVEY.md §2.5 data parallelism
    over row groups, applied to the §3.3 Find→decode flow).

    Returns ``{column: [per-device results]}`` plus ``"#rows"`` (total
    survivors).  Each per-device entry follows :func:`decoded_scan`'s
    per-column forms and stays resident on its device; concatenation
    across devices is the caller's choice (host gather or collectives).
    """
    import jax

    from .mesh import default_mesh

    mesh = mesh or default_mesh()
    devs = list(mesh.devices.flat)
    state = stage_scan(pf, path, lo=lo, hi=hi, columns=columns,
                       use_bloom=use_bloom, devices=devs, policy=policy,
                       report=report)
    state["use_count"][0] += 1
    out_cols = state["out_cols"]
    if "#rows" in out_cols:
        raise ValueError('a column named "#rows" collides with the result '
                         "total; select it via scan_filtered instead")
    shards = []  # (device, sub-state, carrier)
    for di, dev in enumerate(devs):
        spans = [sp for si, sp in enumerate(state["spans"])
                 if si % len(devs) == di]
        if spans:
            shards.append((dev, dict(state, spans=spans),
                           _ScanCarrier(out_cols)))
    # dispatch EVERY device's phase A before any sync, so the chips decode
    # concurrently; the per-device finalize then only waits, it doesn't idle
    # the rest of the mesh.  (Residency is bounded per device by its own
    # span share — the single-device sync_every batching doesn't apply.)
    for dev, sub, carrier in shards:
        # staged bytes are uncommitted: pin this shard's execution (and its
        # outputs) to its device
        with jax.default_device(dev):
            _scan_dispatch(sub, carrier)
    per_dev: Dict[str, List] = {c: [] for c in out_cols}
    total = 0
    for dev, sub, carrier in shards:
        with jax.default_device(dev):
            got = _scan_assemble(sub, carrier)
        for c in out_cols:
            per_dev[c].append(got[c])
        total += sum(carrier.ks_all)
    result: Dict[str, object] = dict(per_dev)
    result["#rows"] = total
    return result
