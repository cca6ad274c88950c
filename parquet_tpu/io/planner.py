"""Unified scan planner: one choke point for every filtered read.

Before this module, pruning lived in four places — footer statistics
(``prune_file``), chunk statistics + bloom (``prune_row_group``), page zone
maps (``plan_scan``), and host-vs-device selection by matching documented
refusal strings in ``parallel/host_scan.scan``.  The planner unifies them:

- **Input** is a prepared predicate tree (:mod:`parquet_tpu.algebra.expr`)
  over any number of columns; the legacy single-column ``lo/hi``/IN-list
  signatures build a one-leaf tree.
- **Cheapest-first probe cascade** per row group: footer min/max statistics
  (already parsed — zero IO) → page index zone maps (one small pread per
  chunk, memoized) → bloom filters (the big pread, equality leaves only).
  ``And``/``Or`` branches short-circuit; a row group a cheap probe kills is
  *never* touched by the costlier probes, and its chunk bytes are never
  read.  :meth:`ScanPlan.explain` shows which probe killed what, and
  :attr:`ScanPlan.counters` carries the cascade's short-circuit counters.
- **Output** is a :class:`ScanPlan`: surviving (row-group, row-range)
  slices (per-leaf page intervals intersected/unioned through the tree),
  plus byte estimates feeding the cost model.
- **Cost-based routing** (:func:`choose_route`): host vs device picked
  from a small cost model — backend, static shape support (the mirror of
  the device route's documented refusals, checked up front instead of by
  throwing), bytes to decode, stats-level selectivity, and a process-wide
  :class:`RouteHistory` of measured route throughput.  The documented-
  refusal fallback in ``parallel/host_scan.scan`` stays as a safety net,
  not the router.

Resilience composes exactly as in the old ``plan_scan``: planning does IO
(index/bloom preads), so under ``policy.on_corrupt='skip_row_group'`` a row
group whose index structures are corrupt is skipped and recorded in the
``report`` with its full row count as candidate rows.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..algebra.expr import And, Const, Expr, Or, Pred, prepare, single_pred
from ..utils.env import env_str
from ..utils.locks import make_lock
from ..format.enums import Type
from ..obs import trace as _trace
from ..obs.export import register_debugz_provider as _register_debugz
from ..obs.metrics import counter as _mcounter
from ..obs.scope import account as _maccount
from ..obs.metrics import gauge as _mgauge

__all__ = ["ScanPlanner", "ScanPlan", "RowGroupDecision",
           "CostInputs", "RouteDecision", "RouteHistory", "choose_route",
           "device_route_supported", "route_history",
           "count_device_refusal", "device_encoding_supported"]

# plan-counter key -> registry counter name where they differ (the
# Prometheus renderer appends _total to counters; publishing rg_total
# verbatim would make the family parquet_tpu_planner_rg_total_total)
_REGISTRY_KEY = {"rg_total": "rg_considered",
                 "pages_total": "pages_considered"}

# local row intervals: half-open (start, end)
_Intervals = List[Tuple[int, int]]


def _merge_intervals(iv: _Intervals) -> _Intervals:
    if len(iv) <= 1:
        return iv
    iv = sorted(iv)
    out = [iv[0]]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _intersect_intervals(a: _Intervals, b: _Intervals) -> _Intervals:
    out: _Intervals = []
    i = j = 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


@dataclass
class RowGroupDecision:
    """One row group's fate through the cascade."""

    rg_index: int
    num_rows: int
    pruned_by: Optional[str] = None  # "stats" | "pages" | "bloom" |
    #                                  "corrupt" | "const" | None (survived)
    killer: Optional[str] = None  # repr of the leaf that killed it
    ranges: _Intervals = field(default_factory=list)  # local [start, end)
    page_sel: Dict[str, Tuple[int, int]] = field(default_factory=dict)
    # legacy single-pred page info: (ords, first_row, row_count) or
    # ("all",) for the no-usable-index full row group
    _legacy: Optional[tuple] = None

    @property
    def candidate_rows(self) -> int:
        return sum(e - s for s, e in self.ranges)


class ScanPlan:
    """Survivors + cascade accounting for one file's filtered read."""

    def __init__(self, pf, expr: Expr, decisions: List[RowGroupDecision],
                 counters: Dict[str, int], stages: Tuple[str, ...]):
        self.pf = pf
        self.expr = expr
        self.decisions = decisions
        self.counters = counters
        self.stages = stages

    @property
    def survivors(self) -> List[RowGroupDecision]:
        return [d for d in self.decisions if d.pruned_by is None and d.ranges]

    @property
    def candidate_rows(self) -> int:
        return sum(d.candidate_rows for d in self.survivors)

    def est_bytes(self, out_cols: Sequence[str] = ()) -> int:
        """Compressed bytes the scan is expected to decode: selected pages
        of the filter columns (when a page index narrowed them) plus the
        output columns' chunk bytes prorated by the candidate-row
        fraction.  Feeds the routing cost model."""
        total = 0
        filter_cols = {p.path for p in _collect_preds(self.expr)}
        for d in self.survivors:
            rg = self.pf.row_group(d.rg_index)
            frac = d.candidate_rows / max(d.num_rows, 1)
            for path in set(out_cols) | filter_cols:
                chunk = rg.column(path)
                nbytes = chunk.meta.total_compressed_size or 0
                sel = d.page_sel.get(path)
                if sel is not None and sel[1]:
                    total += int(nbytes * (sel[0] / sel[1]))
                else:
                    total += int(nbytes * frac)
        return total

    def page_plans(self) -> list:
        """The legacy single-column ``plan_scan`` output: one covering
        :class:`~parquet_tpu.io.search.PagePlan` per surviving row group.
        Only defined for one-leaf positive range/IN trees (what the legacy
        signatures build)."""
        from .search import PagePlan

        out = []
        for d in self.decisions:
            if d.pruned_by is not None:
                continue
            info = d._legacy
            if info is None:
                raise ValueError(
                    "page_plans() is the legacy single-predicate form; "
                    "this plan was built from a multi-leaf tree — use "
                    ".survivors / .decisions instead")
            if info[0] == "all":
                oi = self.pf.row_group(d.rg_index) \
                    .column(info[1]).offset_index()
                n = len(oi.page_locations) if oi and oi.page_locations else 0
                out.append(PagePlan(d.rg_index, list(range(n)) if oi else [],
                                    0, d.num_rows))
            else:
                ords, first_row, row_count = info
                out.append(PagePlan(d.rg_index, ords, first_row, row_count))
        return out

    def explain(self) -> str:
        """Human-readable cascade trace: which probe killed which row
        group, surviving candidate ranges, and the probe totals."""
        c = self.counters
        lines = [f"scan plan: {self.pf._path or '<memory>'}",
                 f"  predicate: {self.expr!r}",
                 f"  stages: {' -> '.join(self.stages)}"]
        for d in self.decisions:
            if d.pruned_by is not None:
                why = d.pruned_by + (f" ({d.killer})" if d.killer else "")
                lines.append(f"  rg {d.rg_index} ({d.num_rows} rows): "
                             f"pruned by {why}")
                continue
            pages = ", ".join(f"{p}={s}/{t}"
                              for p, (s, t) in sorted(d.page_sel.items()))
            lines.append(
                f"  rg {d.rg_index} ({d.num_rows} rows): "
                f"{len(d.ranges)} range(s), {d.candidate_rows} candidate "
                f"rows" + (f", pages {pages}" if pages else ""))
        total_rows = sum(d.num_rows for d in self.decisions)
        cand = self.candidate_rows
        pct = 100.0 * cand / total_rows if total_rows else 0.0
        lines.append(
            f"  probes: stats={c.get('stats_probes', 0)} "
            f"pages={c.get('page_probes', 0)} "
            f"bloom={c.get('bloom_probes', 0)}; pruned row groups: "
            f"stats={c.get('rg_pruned_stats', 0)} "
            f"pages={c.get('rg_pruned_pages', 0)} "
            f"bloom={c.get('rg_pruned_bloom', 0)}; candidates "
            f"{cand}/{total_rows} rows ({pct:.2f}%)")
        return "\n".join(lines)


# fused streaming pays per-page header parses + mask bookkeeping; under
# this many estimated decode bytes the materializing exact tier's single
# big span read wins (auto mode only — on/off pin the choice)
FUSED_AUTO_MIN_BYTES = 8 << 20


def choose_fused(est_bytes: int) -> bool:
    """Cost gate for the fused decode+mask+fold path (``PARQUET_TPU_FUSED``):
    ``on``/``off`` pin it; ``auto`` (default) fuses once ``est_bytes`` —
    the bytes the exact tier would otherwise materialize — clears
    :data:`FUSED_AUTO_MIN_BYTES` (peak-memory and bandwidth savings then
    dominate the per-page overhead)."""
    mode = (env_str("PARQUET_TPU_FUSED") or "").strip().lower() or "auto"
    if mode in ("on", "1", "true", "always"):
        return True
    if mode in ("off", "0", "false", "never"):
        return False
    return int(est_bytes) >= FUSED_AUTO_MIN_BYTES


def _collect_preds(expr: Expr) -> List[Pred]:
    if isinstance(expr, Pred):
        return [expr]
    if isinstance(expr, (And, Or)):
        out = []
        for c in expr.children:
            out.extend(_collect_preds(c))
        return out
    return []


def _eval_tree(expr: Expr, leaf_fn) -> Tuple[bool, Optional[Pred]]:
    """Three-probe boolean fold with short-circuit: returns (may_match,
    killing_pred).  ``leaf_fn(pred) -> bool`` is conservative ("may this
    row group contain a matching row?")."""
    if isinstance(expr, Const):
        return expr.value, None
    if isinstance(expr, Pred):
        ok = leaf_fn(expr)
        return ok, (None if ok else expr)
    if isinstance(expr, And):
        for c in expr.children:
            ok, killer = _eval_tree(c, leaf_fn)
            if not ok:
                return False, killer
        return True, None
    assert isinstance(expr, Or), expr
    last = None
    for c in expr.children:
        ok, killer = _eval_tree(c, leaf_fn)
        if ok:
            return True, None
        last = killer if killer is not None else last
    return False, last


def _tree_intervals(expr: Expr, leaf_fn) -> Optional[_Intervals]:
    """Candidate row intervals through the tree (``None`` = the full row
    group — no leaf narrowed it)."""
    if isinstance(expr, Const):
        return None if expr.value else []
    if isinstance(expr, Pred):
        return leaf_fn(expr)
    if isinstance(expr, And):
        acc: Optional[_Intervals] = None
        for c in expr.children:
            got = _tree_intervals(c, leaf_fn)
            if got is None:
                continue
            acc = got if acc is None else _intersect_intervals(acc, got)
            if acc == []:
                return []
        return acc
    assert isinstance(expr, Or), expr
    acc = []
    for c in expr.children:
        got = _tree_intervals(c, leaf_fn)
        if got is None:
            return None
        acc.extend(got)
    return _merge_intervals(acc)


class ScanPlanner:
    """Plans filtered reads of one :class:`ParquetFile` via the cascade.

    ``policy``/``report`` carry the resilience contract of the old
    ``plan_scan``: corrupt index structures skip the row group (recorded
    with its full row count) under ``on_corrupt='skip_row_group'``."""

    def __init__(self, pf, policy=None, report=None):
        self.pf = pf
        self.policy = policy
        self.report = report

    def any_match_stats(self, expr: Expr) -> bool:
        """Cheapest possible answer to "may ANY row group match?": the
        stats stage only (zero IO), returning at the FIRST surviving row
        group — the early exit ``prune_file`` always had.  Shares the
        leaf probes with the full cascade so file- and row-group-level
        pruning cannot drift."""
        expr = prepare(expr, self.pf.schema)
        if isinstance(expr, Const):
            return expr.value and bool(self.pf.row_groups)
        for rg in self.pf.row_groups:
            alive, _ = _eval_tree(expr, lambda p: _stats_alive(p, rg))
            if alive:
                return True
        return False

    def plan(self, expr: Expr, use_bloom: bool = True,
             stages: Tuple[str, ...] = ("stats", "pages", "bloom")
             ) -> ScanPlan:
        """Run the cascade over every row group.  ``stages`` restricts how
        deep the cascade goes (the router plans with ``("stats",)`` — zero
        IO); ``use_bloom=False`` skips bloom preads like the legacy
        signatures did."""
        from ..errors import CorruptedError, DeadlineError
        from .faults import read_context

        expr = prepare(expr, self.pf.schema)
        preds = _collect_preds(expr)
        if not use_bloom:
            stages = tuple(s for s in stages if s != "bloom")
        single = self._single_positive(expr)
        counters: Dict[str, int] = {
            "rg_total": len(self.pf.row_groups), "rg_pruned_stats": 0,
            "rg_pruned_pages": 0, "rg_pruned_bloom": 0,
            "rg_pruned_const": 0, "rg_skipped_corrupt": 0,
            "rg_survivors": 0, "stats_probes": 0, "page_probes": 0,
            "bloom_probes": 0, "pages_total": 0, "pages_selected": 0}
        decisions: List[RowGroupDecision] = []
        ctx_col = ",".join(sorted({p.path for p in preds})) or None
        skip = self.policy is not None and self.policy.skip_corrupt
        plan_span = (_trace.span("planner.plan", file=self.pf._path,
                                 stages=",".join(stages))
                     if _trace.on() else _trace.NULL_SPAN)
        with plan_span:  # `with`: a probe raising must still close the span
            for rg in self.pf.row_groups:
                d = RowGroupDecision(rg.index, rg.num_rows)
                try:
                    with read_context(path=self.pf._path, row_group=rg.index,
                                      column=ctx_col,
                                      kinds=(CorruptedError, OSError)):
                        self._plan_rg(rg, expr, d, counters, stages, single)
                except DeadlineError:
                    raise
                except CorruptedError as e:
                    if not skip:
                        raise
                    if self.report is not None:
                        self.report.record_skip(rg.index, rows=rg.num_rows,
                                                error=e)
                    d.pruned_by = "corrupt"
                    d.killer = None
                    d.ranges = []
                if d.pruned_by is None:
                    counters["rg_survivors"] += 1
                elif d.pruned_by == "corrupt":
                    counters["rg_skipped_corrupt"] += 1
                else:
                    counters[f"rg_pruned_{d.pruned_by}"] += 1
                decisions.append(d)
        # publish the cascade's counters into the unified registry — the
        # ScanPlan.counters dict stays the per-plan view, the registry
        # accumulates process totals under planner.*.  The *_total plan
        # keys rename to *_considered: Prometheus appends _total to
        # counters and rg_total_total would trap every dashboard
        for k, v in counters.items():
            if v:
                _maccount(_mcounter("planner." + _REGISTRY_KEY.get(k, k)),
                          v)
        return ScanPlan(self.pf, expr, decisions, counters, stages)

    # ------------------------------------------------------------------
    @staticmethod
    def _single_positive(expr: Expr) -> Optional[Pred]:
        """The one positive range/IN leaf of a legacy-shaped tree, or None."""
        if isinstance(expr, Pred) and not expr.negated \
                and expr.kind in ("range", "in"):
            return expr
        return None

    def _plan_rg(self, rg, expr, d: RowGroupDecision,
                 counters: Dict[str, int], stages, single: Optional[Pred]
                 ) -> None:
        if isinstance(expr, Const):
            if expr.value:
                d.ranges = [(0, rg.num_rows)]
            else:
                d.pruned_by = "const"
            return
        # ---- stage 1: chunk statistics (already parsed; zero IO)
        if "stats" in stages:
            def stats_probe(p: Pred) -> bool:
                counters["stats_probes"] += 1
                return _stats_alive(p, rg)

            alive, killer = _eval_tree(expr, stats_probe)
            if not alive:
                d.pruned_by = "stats"
                d.killer = repr(killer) if killer is not None else None
                return
        # ---- stage 2: page-index zone maps (small memoized preads)
        if "pages" in stages:
            if single is not None:
                if not self._pages_single(rg, single, d, counters):
                    return
            else:
                if not self._pages_tree(rg, expr, d, counters):
                    return
        else:
            d.ranges = [(0, rg.num_rows)]
        # ---- stage 3: bloom filters (the big pread; equality leaves only)
        if "bloom" in stages:
            def bloom_probe(p: Pred) -> bool:
                if not p.is_equality:
                    return True
                chunk = rg.column(p.leaf.column_index)
                # inner context: a corrupt bloom structure is attributed
                # to ITS column (the rg-level wrapper passes through
                # already-contextualized ReadErrors untouched)
                with self._probe_context(rg, p):
                    bf = chunk.bloom_filter()
                if bf is None:
                    return True
                counters["bloom_probes"] += 1
                return _bloom_alive(p, bf)

            alive, killer = _eval_tree(expr, bloom_probe)
            if not alive:
                d.pruned_by = "bloom"
                d.killer = repr(killer) if killer is not None else None
                d.ranges = []
                return

    def _pages_single(self, rg, pred: Pred, d: RowGroupDecision,
                      counters: Dict[str, int]) -> bool:
        """Legacy single-predicate page selection: the surviving candidate
        range is the covering span of the selected page ordinals (gaps
        included), byte-identical to the old ``plan_scan`` so every
        existing caller — the device staging route, sharded scans, page
        accounting under degraded policies — sees the exact plans it saw
        before."""
        from .search import (_npages, pages_overlapping,
                             pages_overlapping_values)

        chunk = rg.column(pred.leaf.column_index)
        ci = chunk.column_index()
        oi = chunk.offset_index()
        if ci is None or oi is None:
            d.ranges = [(0, rg.num_rows)]
            d._legacy = ("all", pred.leaf.column_index)
            return True
        counters["page_probes"] += 1
        if pred.kind == "in":
            ords = pages_overlapping_values(ci, pred.leaf, pred.values)
        else:
            ords = pages_overlapping(ci, pred.leaf, pred.lo, pred.hi)
        n_pages = _npages(oi)
        counters["pages_total"] += n_pages
        counters["pages_selected"] += len(ords)
        d.page_sel[pred.path] = (len(ords), n_pages)
        if not ords:
            d.pruned_by = "pages"
            d.killer = repr(pred)
            return False
        locs = oi.page_locations
        first_row = locs[ords[0]].first_row_index
        last = ords[-1]
        end_row = (locs[last + 1].first_row_index if last + 1 < len(locs)
                   else rg.num_rows)
        d.ranges = [(first_row, end_row)]
        d._legacy = (ords, first_row, end_row - first_row)
        return True

    def _probe_context(self, rg, pred: Pred):
        """Per-predicate IO context: index/bloom corruption names the
        column whose structures were actually corrupt, not the whole
        predicate's column list."""
        from ..errors import CorruptedError
        from .faults import read_context

        return read_context(path=self.pf._path, row_group=rg.index,
                            column=pred.path,
                            kinds=(CorruptedError, OSError))

    def _pages_tree(self, rg, expr, d: RowGroupDecision,
                    counters: Dict[str, int]) -> bool:
        def page_iv(p: Pred) -> Optional[_Intervals]:
            chunk = rg.column(p.leaf.column_index)
            with self._probe_context(rg, p):
                ci = chunk.column_index()
                oi = chunk.offset_index()
            if ci is None or oi is None or not oi.page_locations:
                return None
            counters["page_probes"] += 1
            ords = _pred_page_ords(p, ci)
            locs = oi.page_locations
            n = len(locs)
            counters["pages_total"] += n
            counters["pages_selected"] += len(ords)
            prev = d.page_sel.get(p.path)
            if prev is None or len(ords) > prev[0]:
                d.page_sel[p.path] = (len(ords), n)
            iv = []
            for o in ords:
                s = locs[o].first_row_index
                e = (locs[o + 1].first_row_index if o + 1 < n
                     else rg.num_rows)
                iv.append((s, e))
            return _merge_intervals(iv)

        iv = _tree_intervals(expr, page_iv)
        if iv == []:
            d.pruned_by = "pages"
            return False
        d.ranges = iv if iv is not None else [(0, rg.num_rows)]
        return True


# ---------------------------------------------------------------------------
# leaf probes
# ---------------------------------------------------------------------------


def _not_in_covers(sorted_vals, mn, mx) -> bool:
    """Does the sorted unique probe list cover EVERY value in [mn, mx]?
    Only provable for integer order domains: the span holds exactly
    ``mx - mn + 1`` distinct values, so (vals strictly increasing) the
    probes cover it iff ``vals[i0] == mn`` and ``vals[i0 + span] == mx``
    — an O(log n) bisect, no enumeration.  This is the ``NOT IN`` page/
    chunk probe beyond the old constant-page case (``mn == mx``): a page
    of small-cardinality integer codes dies when the probe list blankets
    its range.  Non-integer domains (floats, bytes — uncountable or
    unbounded between any two points) answer False: inconclusive."""
    from bisect import bisect_left

    try:
        if mn == mx:  # constant page/chunk: any domain, the legacy case
            return _bisect_contains(sorted_vals, mn)
        if isinstance(mn, bool) or isinstance(mx, bool) \
                or not isinstance(mn, (int, np.integer)) \
                or not isinstance(mx, (int, np.integer)):
            return False
        span = int(mx) - int(mn)
        i0 = bisect_left(sorted_vals, mn)
        if i0 + span >= len(sorted_vals):
            return False
        v0, v1 = sorted_vals[i0], sorted_vals[i0 + span]
        return v0 == mn and v1 == mx \
            and isinstance(v0, (int, np.integer)) \
            and not isinstance(v0, bool)
    except TypeError:
        return False


def _bisect_contains(sorted_vals, v) -> bool:
    from bisect import bisect_left

    i = bisect_left(sorted_vals, v)
    return i < len(sorted_vals) and sorted_vals[i] == v


def _stats_alive(pred: Pred, rg) -> bool:
    """May this row group contain a row matching ``pred``?  Conservative:
    inconclusive statistics answer True."""
    chunk = rg.column(pred.leaf.column_index)
    st = chunk.statistics()
    nv = chunk.meta.num_values
    null_count = st.null_count if st is not None else None
    if pred.kind == "null":
        if pred.leaf.max_definition_level == 0:
            return False  # required column: no null can exist
        return null_count is None or null_count > 0
    if pred.kind == "notnull":
        if null_count is not None and nv is not None and null_count >= nv:
            return False  # every value is null
        return True
    # range / in require a non-null value
    if null_count is not None and nv is not None and null_count >= nv:
        return False
    if st is None or st.min_value is None or st.max_value is None:
        return True
    mn, mx = st.min_value, st.max_value
    try:
        if pred.kind == "range":
            if not pred.negated:
                from .statistics import may_contain_range

                return may_contain_range(st, pred.lo, pred.hi)
            # negated: dead only when every value provably lies inside
            return not ((pred.lo is None or pred.lo <= mn)
                        and (pred.hi is None or mx <= pred.hi))
        # in-list
        from .search import _any_in_range

        if not pred.negated:
            return _any_in_range(pred.values, mn, mx)
        # negated IN: dead when the probe list provably covers EVERY
        # value the chunk can hold — the constant chunk (mn == mx) or,
        # for integer domains, a probe run blanketing [mn, mx]
        return not _not_in_covers(pred.values, mn, mx)
    except TypeError:
        # probe not comparable with the decoded stats domain: inconclusive
        return True


def _tree_covers(expr: Expr, leaf_fn) -> bool:
    """Boolean fold of the COVERAGE dual: may ``expr`` provably match
    EVERY row?  ``leaf_fn(pred) -> bool`` must answer True only on proof
    (an And covers when all children cover; an Or when any child does —
    sufficient, conservative).  The aggregation cascade promotes a row
    group this returns True for from pruning to *answering*."""
    if isinstance(expr, Const):
        return expr.value
    if isinstance(expr, Pred):
        return leaf_fn(expr)
    if isinstance(expr, And):
        return all(_tree_covers(c, leaf_fn) for c in expr.children)
    assert isinstance(expr, Or), expr
    return any(_tree_covers(c, leaf_fn) for c in expr.children)


def _bounds_cover(pred: Pred, mn, mx, nulls, nv, page_rows=None) -> bool:
    """Do conservative [mn, mx] bounds + null accounting PROVE that every
    row of the span matches ``pred``?  The exact dual of
    :func:`_stats_alive`, shared by the footer-stats, page-index, and
    manifest zone-map coverage probes so the three can never drift.

    Soundness under stat truncation: stored bounds are conservative
    (``mn`` <= true min, ``mx`` >= true max — algebra/compare.py's
    ``truncate_stat_min``/``max`` guarantee exactly this), and every
    proof below only widens with wider bounds, so a truncated bound can
    only fail to prove coverage, never prove it wrongly.  Any missing
    input answers False (not provable).

    Soundness under NaN: float statistics DROP NaN, so bounds can never
    prove a POSITIVE range/in leaf covers every row — a lurking NaN
    fails the exact mask while the non-NaN bounds look covering.
    Positive value proofs on float domains therefore answer False
    outright.  Negated range/in leaves stay provable: a NaN row fails
    the base comparison too, so it MATCHES the negation exactly like
    the proof assumes.  (Pruning is unaffected either way: NaN rows
    fail positive leaves, which only ever widens a may-match answer.)"""
    if pred.kind == "null":
        # every row null: all null_pages, or null_count == the span's rows
        rows = page_rows if page_rows is not None else nv
        return nulls is not None and rows is not None and nulls >= rows \
            and rows > 0
    if pred.kind == "notnull":
        return nulls == 0
    # range / in need every row non-null (NULL fails the leaf, negated or
    # not) and provable value coverage
    if nulls != 0 or mn is None or mx is None:
        return False
    if not pred.negated and (isinstance(mn, float) or isinstance(mx, float)):
        return False  # float domain: a NaN row would fail the positive
        # leaf, and NaN-dropping stats cannot rule one out
    try:
        if pred.kind == "range":
            if not pred.negated:
                return (pred.lo is None or pred.lo <= mn) and \
                    (pred.hi is None or mx <= pred.hi)
            # negated range: every value provably OUTSIDE [lo, hi]
            return (pred.lo is not None and mx < pred.lo) or \
                (pred.hi is not None and mn > pred.hi)
        # in-list
        if not pred.negated:
            # every value in [mn, mx] is a probe: the constant span, or an
            # integer span the sorted probe list blankets
            return _not_in_covers(pred.values, mn, mx)
        from .search import _any_in_range

        return not _any_in_range(pred.values, mn, mx)
    except TypeError:
        return False  # probe not comparable with the bounds domain


def _stats_covers(pred: Pred, rg) -> bool:
    """Does the row group's footer chunk statistics PROVE that every row
    matches ``pred``?  (The answering dual of :func:`_stats_alive`.)"""
    chunk = rg.column(pred.leaf.column_index)
    st = chunk.statistics()
    if st is None:
        return False
    nv = chunk.meta.num_values
    return _bounds_cover(pred, st.min_value, st.max_value, st.null_count,
                         nv)


def _bloom_alive(pred: Pred, bf) -> bool:
    """False only when the bloom filter proves the equality probe absent."""
    if pred.kind == "range":  # one-point range
        from .bloom import bloom_may_contain

        return bloom_may_contain(bf, pred.lo, pred.leaf)
    hashes = pred._hashes
    if hashes is None:
        from .bloom import hash_probe_values

        try:
            hashes = hash_probe_values(pred.leaf, pred.values)
        except ValueError:
            hashes = False  # type has no bloom encoding (e.g. BOOLEAN)
        pred._hashes = hashes  # memoized once per prepared tree (dataset)
    if hashes is False:
        return True
    return bool(bf.check_hashes_batch(hashes).any())


def _pred_page_ords(pred: Pred, ci) -> List[int]:
    """Page ordinals that may contain a matching row, per leaf kind."""
    from .search import (decoded_bounds, pages_overlapping,
                         pages_overlapping_values)

    if not pred.negated and pred.kind == "range":
        return pages_overlapping(ci, pred.leaf, pred.lo, pred.hi)
    if not pred.negated and pred.kind == "in":
        return pages_overlapping_values(ci, pred.leaf, pred.values)
    nulls = list(ci.null_pages or [])
    n = len(nulls)
    if pred.kind == "null":
        ncounts = ci.null_counts
        return [i for i in range(n)
                if nulls[i] or ncounts is None or (ncounts[i] or 0) > 0]
    if pred.kind == "notnull":
        return [i for i in range(n) if not nulls[i]]
    # negated range / in: a page is dead when provably all-inside (or all
    # null — no non-null value to match the negation); bounds come decoded
    # once per chunk from the memo on the parsed index (io/search.py)
    mins, maxs = decoded_bounds(ci, pred.leaf)
    out = []
    probe_set = set(pred.values) if pred.kind == "in" else None
    for i in range(n):
        if nulls[i]:
            continue
        if i >= len(mins) or mins[i] is None or maxs[i] is None:
            out.append(i)
            continue
        try:
            if probe_set is not None:
                # beyond the constant-page case: an integer page whose
                # whole [min, max] span the probe list covers is dead too
                dead = _not_in_covers(pred.values, mins[i], maxs[i])
            else:
                dead = ((pred.lo is None or pred.lo <= mins[i])
                        and (pred.hi is None or maxs[i] <= pred.hi))
        except TypeError:
            dead = False
        if not dead:
            out.append(i)
    return out


# ---------------------------------------------------------------------------
# cost-based host/device routing
# ---------------------------------------------------------------------------

# priors until the history has measured this process (decoded GB/s of
# compressed input; intentionally favor host on small plans — staging +
# dispatch dominates the device route there)
_HOST_PRIOR_GBPS = 1.5
_DEVICE_PRIOR_GBPS = 6.0
_DEVICE_FIXED_S = 0.03  # plan/stage/compile overhead per fresh scan
_DEVICE_MIN_BYTES = 4 << 20
_POOL_MIN_CELLS = 2_000_000  # mirror of the host scan's measured crossover


@dataclass
class CostInputs:
    """Everything :func:`choose_route` looks at — pure data, so routing is
    unit-testable with stubbed inputs."""

    backend: str  # jax.default_backend(): "cpu" | "tpu" | "gpu"
    supported: bool  # static device-shape support (mirror of refusals)
    reason: str = ""  # why unsupported, when it is
    est_bytes: int = 0  # compressed bytes the scan will decode
    est_rows: int = 0  # stats-level candidate rows
    total_rows: int = 0
    n_columns: int = 1  # filter + output columns
    reuse: int = 1  # expected reuses of the staged scan state
    host_gbps: Optional[float] = None  # measured (RouteHistory)
    device_gbps: Optional[float] = None
    pin: Optional[str] = None  # PARQUET_TPU_ROUTE env override


@dataclass
class RouteDecision:
    route: str  # "host" | "device"
    reason: str
    pool_width: Optional[int] = None  # host fan-out: None=auto, 1=serial
    est_host_s: Optional[float] = None
    est_device_s: Optional[float] = None
    est_bytes: int = 0  # what the history observes against elapsed time


def route_history() -> "RouteHistory":
    """The process-wide measured-throughput history feeding the router."""
    return _HISTORY


class RouteHistory:
    """EWMA of measured scan throughput per route — the feedback loop that
    replaces refusal-string matching: the router starts from priors and
    converges on what THIS host/chip pair actually does.  Rates are
    normalized by the router's own byte ESTIMATE (both routes observe the
    same estimate for the same query shape, so the host/device comparison
    stays apples-to-apples even where the estimate is off in absolute
    terms), and device observations include staging/compile wall clock —
    :func:`choose_route` therefore skips its fixed-overhead prior once a
    measured device rate exists."""

    def __init__(self, alpha: float = 0.3):
        self._lock = make_lock("planner.route_history")
        self._alpha = alpha
        self._gbps: Dict[str, float] = {}
        self._wait_frac: Dict[str, float] = {}
        self._n: Dict[str, int] = {}

    @staticmethod
    def _key(route: str, mesh_size: int) -> str:
        """EWMA bucket per (route, mesh size): a 1-chip observation must
        not misprice the 8-chip path.  Mesh size 1 keeps the bare route
        name, so histories recorded before the split read back
        unchanged (old keys ARE mesh-size-1 keys)."""
        return route if mesh_size <= 1 else f"{route}@{mesh_size}"

    def observe(self, route: str, nbytes: int, seconds: float,
                pool_wait_s: float = 0.0, mesh_size: int = 1) -> None:
        # tiny scans are dominated by fixed per-call cost, not transfer/
        # decode rate: folding them in would drag the EWMA toward a
        # meaningless rate and misroute the LARGE scans the model exists
        # for (same floor the device route needs to amortize staging)
        if seconds <= 0 or nbytes < _DEVICE_MIN_BYTES:
            return
        gbps = nbytes / seconds / 1e9
        # pool saturation discounts the route's EFFECTIVE rate beyond its
        # wall clock: a scan that spent 40% of its time queued behind
        # other work on the shared pool already paid that wait in wall
        # clock, but the congestion it observed predicts the next scan's
        # — so gbps() scales the measured rate down by the waited
        # fraction.  ReadStats.pool_wait_s (prefetch window stalls) and
        # the pool's queue-wait meter both feed this (the
        # obs.metrics.pool_wait_seconds delta the scan router passes).
        # The delta is PROCESS-wide by design: concurrent scans see each
        # other's waits, i.e. the discount measures ambient saturation
        # during the scan, not this scan's own queueing — the clamp below
        # and the EWMA keep a burst of cross-attributed waits from
        # pinning the route at the floor.
        wf = min(max(pool_wait_s, 0.0) / seconds, 0.95)
        key = self._key(route, mesh_size)
        with self._lock:
            cur = self._gbps.get(key)
            self._gbps[key] = gbps if cur is None else \
                (1 - self._alpha) * cur + self._alpha * gbps
            curw = self._wait_frac.get(key)
            self._wait_frac[key] = wf if curw is None else \
                (1 - self._alpha) * curw + self._alpha * wf
            self._n[key] = self._n.get(key, 0) + 1
            eff = self._gbps[key] * (1.0 - self._wait_frac[key])
        # the gauge label carries the full bucket key: per-mesh-size
        # series stay distinguishable on a scrape (PT001 holds — the
        # family is pre-declared; label VALUES are runtime data)
        _mgauge("route.gbps", labels={"route": key},
                help="EWMA effective GB/s per route").set(round(eff, 4))
        _maccount(_mcounter("route.observations", labels={"route": key}))

    def gbps(self, route: str, mesh_size: int = 1) -> Optional[float]:
        """Effective EWMA GB/s: the measured wall-clock rate discounted by
        the EWMA pool-wait fraction (0 when no waits were reported — the
        historical behavior, byte-for-byte)."""
        key = self._key(route, mesh_size)
        with self._lock:
            g = self._gbps.get(key)
            if g is None:
                return None
            return g * (1.0 - self._wait_frac.get(key, 0.0))

    def observations(self, route: str, mesh_size: int = 1) -> int:
        with self._lock:
            return self._n.get(self._key(route, mesh_size), 0)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """Per-bucket effective rates and sample counts — the /debugz
        routes section's data."""
        with self._lock:
            return {k: {"gbps": round(
                self._gbps[k] * (1.0 - self._wait_frac.get(k, 0.0)), 4),
                "observations": self._n.get(k, 0)}
                for k in sorted(self._gbps)}

    def reset(self) -> None:
        with self._lock:
            self._gbps.clear()
            self._wait_frac.clear()
            self._n.clear()


_HISTORY = RouteHistory()


def choose_route(inp: CostInputs) -> RouteDecision:
    """Pick host vs device (and the host pool fan-out width) from the cost
    inputs.  Pure function of ``inp`` — the routing unit tests stub it."""
    cells = inp.est_rows * max(inp.n_columns, 1)
    width = 1 if cells < _POOL_MIN_CELLS else None
    if inp.pin in ("host", "device"):
        if inp.pin == "device" and not inp.supported:
            return RouteDecision("host", "PARQUET_TPU_ROUTE=device pinned "
                                 f"but shape unsupported: {inp.reason}",
                                 width)
        return RouteDecision(inp.pin, f"PARQUET_TPU_ROUTE={inp.pin} pin",
                             width if inp.pin == "host" else None)
    if inp.backend == "cpu":
        return RouteDecision(
            "host", "cpu backend: threaded host scan beats emulated "
            "device kernels", width)
    if not inp.supported:
        return RouteDecision("host", f"device route unsupported: "
                             f"{inp.reason}", width)
    if inp.est_bytes < _DEVICE_MIN_BYTES:
        return RouteDecision(
            "host", f"plan too small ({inp.est_bytes} bytes) to amortize "
            "H2D staging", width)
    host_s = inp.est_bytes / ((inp.host_gbps or _HOST_PRIOR_GBPS) * 1e9)
    # a MEASURED device rate already embeds staging/compile overhead (the
    # history observes end-to-end wall clock), so the fixed term applies
    # only on the priors — adding both would double-count the overhead
    # and bias the calibrated model against the device route
    dev_s = inp.est_bytes / ((inp.device_gbps or _DEVICE_PRIOR_GBPS) * 1e9)
    if inp.device_gbps is None:
        dev_s += _DEVICE_FIXED_S / max(inp.reuse, 1)
    if dev_s <= host_s:
        return RouteDecision(
            "device", f"cost model: device {dev_s * 1e3:.1f}ms <= host "
            f"{host_s * 1e3:.1f}ms", None, host_s, dev_s)
    return RouteDecision(
        "host", f"cost model: host {host_s * 1e3:.1f}ms < device "
        f"{dev_s * 1e3:.1f}ms", width, host_s, dev_s)


def device_route_supported(pf, path: str, columns: Optional[Sequence[str]],
                           values: Optional[Sequence] = None
                           ) -> Tuple[bool, str]:
    """Static mirror of the device route's documented refusals, answered
    from the footer alone (no IO, nothing thrown).  The refusal
    ``ValueError``\\ s in ``stage_scan`` remain as the safety net for
    shapes only visible at page level (e.g. a dictionary chunk that fell
    back to plain mid-file)."""
    from ..format.enums import Encoding
    from ..schema.types import LogicalKind

    flat = {leaf.dotted_path for leaf in pf.schema.leaves
            if leaf.max_repetition_level == 0}
    out_cols = list(columns) if columns is not None else sorted(flat - {path})
    for c in [path] + out_cols:
        if c not in flat:
            return False, f"column {c!r} is nested or unknown"
    key_leaf = pf.schema.leaf(path)
    t = key_leaf.physical_type
    if t in (Type.FIXED_LEN_BYTE_ARRAY, Type.INT96):
        return False, f"key {path!r} has physical type {t.name}"
    if t == Type.BYTE_ARRAY and key_leaf.logical_kind == LogicalKind.DECIMAL:
        return False, f"key {path!r} is a decimal byte array"
    if values is not None and t in (Type.INT64, Type.DOUBLE):
        return False, f"IN-list on 64-bit key {path!r}"
    dict_encs = {Encoding.PLAIN_DICTIONARY, Encoding.RLE_DICTIONARY}
    for c in [path] + out_cols:
        leaf = pf.schema.leaf(c)
        if leaf.physical_type in (Type.FIXED_LEN_BYTE_ARRAY, Type.INT96) \
                and c != path:
            return False, f"output column {c!r} has physical type " \
                f"{leaf.physical_type.name}"
        if c == path and t == Type.BYTE_ARRAY:
            # a plain-encoded byte-array KEY has no row-aligned device form
            for rg in pf.metadata.row_groups or []:
                encs = rg.columns[leaf.column_index].meta_data.encodings or []
                if not any(Encoding(e) in dict_encs for e in encs):
                    return False, f"key {path!r} has a non-dictionary chunk"
    return True, ""


def device_encoding_supported(pf, columns: Optional[Sequence[str]] = None
                              ) -> Tuple[bool, str]:
    """Static per-ENCODING mirror of ``parallel/device_reader``'s stage
    dispatch, answered from the footer alone: True when every chunk of
    the selected leaves carries an encoding the device decode plan can
    place on chip (PLAIN / RLE / dictionary / DELTA_BINARY_PACKED /
    DELTA_LENGTH_BYTE_ARRAY / DELTA_BYTE_ARRAY / BYTE_STREAM_SPLIT).
    The dynamic ``_Unsupported`` → host fallback remains the safety net
    for shapes only visible at page level; this mirror lets the mesh
    router refuse a file BEFORE staging any of its bytes."""
    from ..format.enums import Encoding

    ok = {Encoding.PLAIN, Encoding.RLE, Encoding.PLAIN_DICTIONARY,
          Encoding.RLE_DICTIONARY, Encoding.DELTA_BINARY_PACKED,
          Encoding.DELTA_LENGTH_BYTE_ARRAY, Encoding.DELTA_BYTE_ARRAY,
          Encoding.BYTE_STREAM_SPLIT}
    want = set(columns) if columns is not None else None
    for leaf in pf.schema.leaves:
        if want is not None and leaf.dotted_path not in want:
            continue
        for rg in pf.metadata.row_groups or []:
            encs = rg.columns[leaf.column_index].meta_data.encodings or []
            for e in encs:
                try:
                    enc = Encoding(e)
                except ValueError:
                    return False, (f"column {leaf.dotted_path!r} carries "
                                   f"unknown encoding {e}")
                if enc not in ok:
                    return False, (f"column {leaf.dotted_path!r} carries "
                                   f"encoding {enc.name} with no device "
                                   "kernel")
    return True, ""


def route_scan(pf, path: str, lo=None, hi=None,
               columns: Optional[Sequence[str]] = None,
               values: Optional[Sequence] = None,
               backend: Optional[str] = None,
               reuse: int = 1) -> RouteDecision:
    """Build :class:`CostInputs` from the footer (stats-stage plan — zero
    IO) and route.  ``backend`` overrides the jax backend for tests."""
    if backend is None:
        import jax

        backend = jax.default_backend()
    pin = _route_pin()
    if pin == "host" or (backend == "cpu" and pin is None):
        # the common cpu case needs no cost inputs at all: choose_route
        # would answer host unconditionally, so skip the stats-stage plan
        # and the footer support walk entirely (scan_filtered's own
        # measured crossover handles the pool width from the REAL plan)
        reason = (f"PARQUET_TPU_ROUTE={pin} pin" if pin == "host"
                  else "cpu backend: threaded host scan beats emulated "
                  "device kernels")
        _maccount(_mcounter("route.chosen", labels={"route": "host"}))
        return RouteDecision("host", reason)
    supported, reason = True, ""
    try:
        supported, reason = device_route_supported(pf, path, columns, values)
    except KeyError as e:
        supported, reason = False, f"unknown column {e}"
    est_bytes = est_rows = 0
    flat = {leaf.dotted_path for leaf in pf.schema.leaves
            if leaf.max_repetition_level == 0}
    out_cols = list(columns) if columns is not None else sorted(flat - {path})
    try:
        plan = ScanPlanner(pf).plan(single_pred(path, lo, hi, values),
                                    stages=("stats",))
        est_rows = plan.candidate_rows
        est_bytes = plan.est_bytes(out_cols)
    except (KeyError, ValueError):
        pass  # host path raises the precise error
    h = _HISTORY
    inp = CostInputs(
        backend=backend, supported=supported, reason=reason,
        est_bytes=est_bytes, est_rows=est_rows, total_rows=pf.num_rows,
        n_columns=1 + len(out_cols), reuse=reuse,
        host_gbps=h.gbps("host"), device_gbps=h.gbps("device"),
        pin=pin)
    decision = choose_route(inp)
    decision.est_bytes = est_bytes
    _maccount(_mcounter("route.chosen", labels={"route": decision.route}))
    return decision


def _route_pin() -> Optional[str]:
    v = env_str("PARQUET_TPU_ROUTE").lower()
    if v in ("host", "cpu"):
        return "host"
    if v in ("device", "tpu"):
        return "device"
    return None


# ---------------------------------------------------------------------------
# device-route refusal accounting + /debugz routes section
# ---------------------------------------------------------------------------

# the closed label set device.route_refusals is declared with; anything
# else folds into "other" so a novel refusal can't mint an unscraped
# series mid-flight
_REFUSAL_REASONS = ("unsupported", "policy", "budget", "error", "other")
_REFUSAL_KEEP = 16  # most-recent refusal details kept for /debugz
_refusal_lock = make_lock("planner.refusals")
_refusal_recent: List[Tuple[str, str]] = []


def count_device_refusal(reason: str, detail: str = "") -> None:
    """Meter one device-route refusal (the mesh/scan paths call this at
    every host fallback) and remember its detail for the /debugz routes
    section — counters say HOW OFTEN the device route is refused,
    the detail ring says WHY, next to the throughput history that says
    what the refusals cost."""
    label = reason if reason in _REFUSAL_REASONS else "other"
    _maccount(_mcounter("device.route_refusals", labels={"reason": label}))
    with _refusal_lock:
        _refusal_recent.append((label, detail or reason))
        del _refusal_recent[:-_REFUSAL_KEEP]


def _routes_debugz() -> Dict[str, object]:
    """/debugz "routes" section: the measured per-(route, mesh-size)
    throughput history beside the recent device-route refusals."""
    with _refusal_lock:
        recent = [{"reason": r, "detail": d} for r, d in _refusal_recent]
    return {"history": _HISTORY.snapshot(), "refusals_recent": recent}


_register_debugz("routes", _routes_debugz)
