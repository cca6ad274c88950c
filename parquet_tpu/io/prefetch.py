"""Prefetching I/O layer: overlap disk latency with decode (SURVEY.md §2.5).

The streamed read path (io/stream.py) used to alternate one blocking pread
with one page-batch decode, per cursor, per column — the disk idled during
decode and the core idled during the pread.  This module packages readahead
as a :class:`~parquet_tpu.io.source.Source` wrapper the stream layer (or any
caller) installs for the duration of one drain:

- **ring backend** (any inner source): planned ranges are carved into
  coalesced windows and issued N windows ahead on the shared pool
  (utils/pool.py); ``pread``/``pread_view`` are served zero-copy out of a
  bounded ring of completed window buffers.  Because the background reads go
  through the *wrapped* chain, the resilience stack composes: a
  :class:`~parquet_tpu.io.faults.PolicySource` underneath retries transient
  errors and enforces the operation deadline inside the worker, and any
  surviving error is re-raised on the consuming thread at the ``pread`` that
  needed the bytes — inside the caller's ``read_context``, so the surfaced
  ``ReadError`` still names file/row-group/column.
- **advise backend** (chain bottoming out at an
  :class:`~parquet_tpu.io.source.MmapSource`): reads are already zero-copy
  views of the page cache, so no buffers are staged; planned ranges are
  instead hinted to the kernel with ``madvise(WILLNEED)`` N windows ahead of
  the consumption frontier — asynchronous readahead by DMA, no threads, and
  therefore profitable even on a single core.

Env knobs (documented in README "Read pipeline"):

- ``PARQUET_TPU_PREFETCH``: ``0`` off, ``1``/``auto`` (default) pick per
  chain (advise for mmap-backed chains; ring when >1 CPU), ``ring`` force
  the pool backend (chaos tests on small hosts), ``mmap`` advise-only.
- ``PARQUET_TPU_PREFETCH_WINDOW``: window bytes (default 2 MiB).
- ``PARQUET_TPU_PREFETCH_DEPTH``: windows issued ahead per planned range
  (default 2).
"""

from __future__ import annotations

import time
from concurrent.futures import TimeoutError as _FutTimeout
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

from ..errors import DeadlineError
from ..utils.env import env_bool, env_opt_int, env_str
from ..utils.locks import make_lock
from ..obs import scope as _oscope
from ..obs import trace as _trace
from ..obs.ledger import ledger_account
from ..obs.metrics import counter as _counter
from ..obs.metrics import histogram as _histogram
from .source import MmapSource, Source

# resource-ledger accounts (obs/ledger.py): ring = bytes of issued
# windows not yet consumed/discarded, segments = the shared carve
# buffers those windows fill slices of.  Updated inside the prefetcher's
# own lock at every ring/plan mutation, summed across all live
# prefetchers — both drain to 0 when the last drain closes.
_ACC_RING = ledger_account("prefetch.ring")
_ACC_SEG = ledger_account("prefetch.segments")

__all__ = ["ReadStats", "PrefetchSource", "prefetch_mode", "make_prefetcher",
           "make_chunk_prefetcher", "autotune_enabled", "prefetch_autotune"]

DEFAULT_WINDOW_BYTES = 2 << 20
DEFAULT_DEPTH = 2
# ring windows fill slices of a shared per-plan segment buffer this many
# windows long, so cursor reads spanning a window join inside one segment
# serve zero-copy instead of concatenating the chain
_SEG_WINDOWS = 4


def prefetch_mode() -> str:
    """Resolve ``PARQUET_TPU_PREFETCH`` to off | auto | ring | mmap."""
    v = env_str("PARQUET_TPU_PREFETCH").lower()
    if v in ("0", "off", "false", "no"):
        return "off"
    if v in ("ring", "pool"):
        return "ring"
    if v in ("mmap", "advise"):
        return "mmap"
    return "auto"


def autotune_enabled() -> bool:
    """``PARQUET_TPU_PREFETCH_AUTOTUNE`` opt-out (default on)."""
    return env_bool("PARQUET_TPU_PREFETCH_AUTOTUNE")


# tuned knobs react to the bubble meter, normalized PER WINDOW so a long
# drain doesn't ratchet the state just by accumulating wall time: a drain
# whose average wait per issued window exceeds the raise threshold deepens
# readahead; one under the decay threshold steps back toward the defaults
_TUNE_RAISE_S_PER_WINDOW = 5e-3
_TUNE_DECAY_S_PER_WINDOW = 5e-4
_MAX_DEPTH = 8
_MAX_WINDOW_BYTES = 16 << 20

# per-latency-class readahead baselines (depth, window): a source chain's
# class comes from its innermost source (``latency_class`` attribute —
# io/remote.py HttpSource reports "remote", or "remote_far" once its
# observed pread EWMA crosses the far threshold; local chains have none).
# High-latency sources START with deeper pipelines and bigger windows —
# at network RTTs the two-window default leaves the pipe mostly idle —
# and the auto-tuner's learned state is kept PER CLASS, so a remote
# drain's feedback never bloats local readahead (or vice versa).
_CLASS_DEFAULTS = {
    "local": (DEFAULT_DEPTH, DEFAULT_WINDOW_BYTES),
    "remote": (4, 4 << 20),
    "remote_far": (6, 8 << 20),
}


class _AutoTuneState:
    """Process-wide feedback from observed :class:`ReadStats` to the next
    drain's readahead defaults (ROADMAP follow-on: tune
    ``PARQUET_TPU_PREFETCH_DEPTH``/``WINDOW`` from ``pool_wait_s`` instead
    of fixed constants).  A drain whose average wait PER ISSUED WINDOW
    exceeds :data:`_TUNE_RAISE_S_PER_WINDOW` deepens readahead — depth
    first, then window size; one under the decay threshold steps back
    toward the class baseline (:data:`_CLASS_DEFAULTS` — remote classes
    floor higher than local).  State is kept per latency class.  Explicit
    env pins and ``PARQUET_TPU_PREFETCH_AUTOTUNE=0`` bypass the state
    entirely."""

    def __init__(self):
        self._lock = make_lock("prefetch.autotune")
        # class -> [depth override | None, window override | None]
        self._state = {}

    def _cls(self, cls: str):
        st = self._state.get(cls)
        if st is None:
            st = self._state[cls] = [None, None]
        return st

    def suggest(self, cls: str = "local"):
        with self._lock:
            return tuple(self._cls(cls))

    def observe(self, stats: "ReadStats", cls: str = "local") -> None:
        if stats.windows_issued <= 0:
            return
        wait_per_window = stats.pool_wait_s / stats.windows_issued
        base_d, base_w = _CLASS_DEFAULTS.get(cls, _CLASS_DEFAULTS["local"])
        with self._lock:
            st = self._cls(cls)
            d = st[0] or base_d
            w = st[1] or base_w
            if wait_per_window > _TUNE_RAISE_S_PER_WINDOW:
                if d < _MAX_DEPTH:
                    st[0] = d + 1
                elif w < _MAX_WINDOW_BYTES:
                    st[1] = w * 2
            elif wait_per_window < _TUNE_DECAY_S_PER_WINDOW:
                if w > base_w:
                    w //= 2
                    st[1] = None if w <= base_w else w
                elif d > base_d:
                    d -= 1
                    st[0] = None if d <= base_d else d

    # back-compat views of the default (local) class — the historical
    # attribute shape (tests and any external pokers read these)
    @property
    def depth(self) -> Optional[int]:
        with self._lock:
            return self._cls("local")[0]

    @property
    def window(self) -> Optional[int]:
        with self._lock:
            return self._cls("local")[1]

    def reset(self) -> None:
        with self._lock:
            self._state = {}


_AUTOTUNE = _AutoTuneState()

# per-wait latency distribution (the bubble meter's shape, not just its
# sum): p99 here is "how long does a consumer stall when readahead loses"
_WAIT_HIST = _histogram("prefetch.wait_s",
                        help="per-wait stall on an unfinished window")


def prefetch_autotune() -> _AutoTuneState:
    """The process-wide auto-tune state (tests reset it between cases)."""
    return _AUTOTUNE


@dataclass
class ReadStats:
    """What the prefetching read actually did (observability; surfaced as
    ``Table.read_stats`` and in bench.py's lineitem config).

    ``prefetch_hits``/``prefetch_misses`` count preads served from (vs.
    around) the readahead state; ``bytes_prefetched`` counts window bytes
    issued ahead (ring: read into the ring; advise: hinted to the kernel),
    ``bytes_discarded`` window bytes dropped unconsumed (evictions, close),
    and ``pool_wait_s`` time the consuming thread blocked on a window whose
    background read had not finished — the pipeline's bubble meter: ~0 means
    IO fully hid behind decode."""

    backend: str = ""
    prefetch_hits: int = 0
    prefetch_misses: int = 0
    windows_issued: int = 0
    bytes_prefetched: int = 0
    bytes_discarded: int = 0
    bytes_dropbehind: int = 0
    pool_wait_s: float = 0.0

    def as_dict(self) -> dict:
        return {"backend": self.backend,
                "prefetch_hits": self.prefetch_hits,
                "prefetch_misses": self.prefetch_misses,
                "windows_issued": self.windows_issued,
                "bytes_prefetched": self.bytes_prefetched,
                "bytes_discarded": self.bytes_discarded,
                "bytes_dropbehind": self.bytes_dropbehind,
                "pool_wait_s": round(self.pool_wait_s, 4)}

    def publish(self) -> None:
        """Fold this drain's totals into the process-wide metrics registry
        (parquet_tpu/obs) and the current op scope — called when the
        drain's prefetcher closes.  Idempotent: a double-close (or a
        direct second call) publishes exactly once, so registry totals
        can never double."""
        if getattr(self, "_published", False):
            return
        self._published = True
        _oscope.account(_counter("prefetch.hits"), self.prefetch_hits)
        _oscope.account(_counter("prefetch.misses"), self.prefetch_misses)
        _oscope.account(_counter("prefetch.windows_issued"),
                        self.windows_issued)
        _oscope.account(_counter("prefetch.bytes_prefetched"),
                        self.bytes_prefetched)
        _oscope.account(_counter("prefetch.bytes_discarded"),
                        self.bytes_discarded)
        _oscope.account(_counter("prefetch.bytes_dropbehind"),
                        self.bytes_dropbehind)
        _oscope.account(_counter("prefetch.pool_wait_s"), self.pool_wait_s)


class _Window:
    """One in-flight or completed window read.  ``seg``/``seg_start`` name
    the shared per-plan segment buffer this window fills a slice of (chunk-
    aligned carving: reads spanning window joins inside one segment serve
    zero-copy out of the segment)."""

    __slots__ = ("offset", "end", "future", "plan", "seg", "seg_start")

    def __init__(self, offset: int, end: int, future, plan,
                 seg=None, seg_start: int = 0):
        self.offset = offset
        self.end = end
        self.future = future
        self.plan = plan
        self.seg = seg
        self.seg_start = seg_start


def _as_u8(buf) -> np.ndarray:
    """Window buffer (ndarray, memoryview, or bytes — injector wrappers
    return bytes) as a sliceable uint8 array, zero-copy where possible."""
    if isinstance(buf, np.ndarray):
        return buf
    return np.frombuffer(buf, np.uint8)


class _Plan:
    """One registered sequential range [start, end); ``issue`` is the
    readahead frontier — bytes below it are already issued/hinted.  Ring
    plans carve their windows out of shared contiguous segment buffers
    (``seg_buf`` spanning [seg_start, seg_end)) so intra-segment window
    joins serve zero-copy."""

    __slots__ = ("start", "issue", "end", "seg_buf", "seg_start", "seg_end",
                 "dropped", "pending", "dead")

    def __init__(self, start: int, end: int):
        self.start = start
        self.issue = start
        self.end = end
        self.seg_buf = None
        self.seg_start = 0
        self.seg_end = 0
        self.dropped = start  # drop-behind frontier (advise backend)
        self.pending = 0      # windows claimed but not yet in the ring
        self.dead = False     # unplanned while a claim was in flight


def _innermost(src: Source) -> Source:
    seen = set()
    while hasattr(src, "inner") and id(src) not in seen:
        seen.add(id(src))
        src = src.inner
    return src


class PrefetchSource(Source):
    """Readahead wrapper over any :class:`Source` (see module docstring).

    ``backend='ring'`` issues coalesced window reads on the shared pool and
    serves from a bounded ring; ``backend='advise'`` (mmap-backed chains)
    hints the kernel instead and reads through.  Callers declare upcoming
    sequential ranges with :meth:`plan` (the stream layer plans the current
    and next row group's chunk byte ranges — the row-group double buffer);
    reads outside planned windows fall through to the inner source and are
    counted as misses.

    The wrapper is transient — one per drain — and does **not** own the
    inner source unless ``owns_inner=True``: ``close()`` cancels outstanding
    window reads and drops buffers, leaving the file open for the next
    operation.
    """

    def __init__(self, inner: Source, backend: str = "ring",
                 window_bytes: Optional[int] = None,
                 depth: Optional[int] = None,
                 max_windows: int = 32,
                 stats: Optional[ReadStats] = None,
                 owns_inner: bool = False):
        if backend not in ("ring", "advise"):
            raise ValueError(f"unknown prefetch backend {backend!r}")
        self.inner = inner
        self.backend = backend
        env_window = env_opt_int("PARQUET_TPU_PREFETCH_WINDOW")
        env_depth = env_opt_int("PARQUET_TPU_PREFETCH_DEPTH")
        # the chain's latency class (innermost source's declaration —
        # remote sources report "remote"/"remote_far", everything else is
        # local): picks the readahead baseline and keys the tuner state
        self.latency_class = getattr(_innermost(inner), "latency_class",
                                     "local")
        base_depth, base_window = _CLASS_DEFAULTS.get(
            self.latency_class, _CLASS_DEFAULTS["local"])
        # explicit args and env pins beat the auto-tuner; with neither, the
        # depth/window come from observed pool_wait_s of earlier drains
        tuned_depth, tuned_window = ((None, None) if not autotune_enabled()
                                     else _AUTOTUNE.suggest(
                                         self.latency_class))
        self._tunable = (autotune_enabled() and window_bytes is None
                         and depth is None and env_window is None
                         and env_depth is None)
        self.window_bytes = int(window_bytes or env_window or tuned_window
                                or base_window)
        if self.window_bytes <= 0:
            raise ValueError("window_bytes must be positive")
        self.depth = int(depth or env_depth or tuned_depth or base_depth)
        self.max_windows = max(2, int(max_windows))
        self.stats = stats if stats is not None else ReadStats()
        self.stats.backend = backend
        self._owns_inner = owns_inner
        self._lock = make_lock("prefetch.ring")
        self._plans: List[_Plan] = []
        self._ring: List[_Window] = []  # issue order (oldest first)
        self._pending = 0   # windows claimed but not yet in the ring
        self._pump_rr = 0   # round-robin cursor across plans
        self._segs: dict = {}  # id(segment buffer) -> nbytes (ledger)
        self._mmap = _innermost(inner) if backend == "advise" else None
        if backend == "advise" and not isinstance(self._mmap, MmapSource):
            raise ValueError("advise backend needs an MmapSource-backed chain")
        # drop-behind (PARQUET_TPU_MMAP_DROPBEHIND): one-shot streamed
        # drains release consumed pages behind the frontier and drop the
        # whole planned span at close, so a cold bulk scan can't evict
        # the page cache the lookup serving path depends on
        from .source import dropbehind_enabled

        self._dropbehind = backend == "advise" and dropbehind_enabled()
        self._advised_sequential = False
        self._closed = False

    @property
    def path(self):
        return getattr(self.inner, "path", None)

    # ------------------------------------------------------------- planning
    def plan(self, offset: int, size: int) -> None:
        """Declare an upcoming sequential read range; the prefetcher keeps
        up to ``depth`` windows of each plan issued ahead of consumption."""
        if size <= 0 or self._closed:
            return
        from ..obs.ledger import maybe_check_pressure

        # readahead is a growth site too: let the ledger respond BEFORE
        # staging more window buffers (outside our lock — the reclaimers
        # take the cache locks)
        maybe_check_pressure()
        with self._lock:
            self._plans.append(_Plan(offset, offset + size))
        self._pump()

    def plan_many(self, ranges) -> None:
        """Declare a batch of (offset, size) ranges in one call: one ledger
        pressure check and one pump for the whole batch.  The mesh staging
        path plans every chunk of a file at once — per-range plan() would
        pay a pressure check and a pump lap per chunk for ranges that were
        all known up front."""
        batch = [(off, size) for off, size in ranges if size > 0]
        if not batch or self._closed:
            return
        from ..obs.ledger import maybe_check_pressure

        maybe_check_pressure()
        with self._lock:
            for off, size in batch:
                self._plans.append(_Plan(off, off + size))
        self._pump()

    def unplan(self, offset: int, size: int) -> None:
        """Cancel the plan registered as (offset, size) and drop its
        windows.  The stream layer calls this for every chunk of a row
        group ``skip_row_group`` abandons — otherwise the dead plans would
        pin their issued windows in the ring for the rest of the drain
        (plans retire on consumption, which will never come) and later row
        groups would prefetch nothing."""
        end = offset + size
        with self._lock:
            dead = [p for p in self._plans
                    if p.start == offset and p.end == end]
            for p in dead:
                p.dead = True
                self._plans.remove(p)
            dropped = [w for w in self._ring if w.plan in dead]
            for w in dropped:
                w.future.cancel()
                self._ring.remove(w)
                self.stats.bytes_discarded += w.end - w.offset
                _ACC_RING.sub(w.end - w.offset)
            self._gc_segs_locked()
        if dropped:
            self._pump()

    def _claim_one_locked(self):
        """Claim the next window to issue — round-robin across plans
        (consumption interleaves across column chunks the same way),
        bounded by ring capacity and ``depth`` windows per plan, both
        counting claims still in flight (``_pending``).  Advances the
        frontier and accounts the bytes INSIDE the ring lock (ledger
        discipline); returns ``(plan, offset, end, seg, seg_start)`` or
        None when nothing more can be issued."""
        if self._closed:
            return None
        if len(self._ring) + self._pending >= self.max_windows:
            return None
        plans = list(self._plans)
        if not plans:
            return None
        n = len(plans)
        start = self._pump_rr % n
        for k in range(n):
            plan = plans[(start + k) % n]
            if plan.issue >= plan.end:
                if plan.pending == 0 and plan in self._plans:
                    self._plans.remove(plan)
                continue
            # per-plan depth bound: at most `depth` un-consumed windows
            # of this plan in the ring at a time (adjacent plans — the
            # next chunk's byte range — must not absorb this plan's
            # budget, so windows are tagged with their plan)
            if (sum(1 for w in self._ring if w.plan is plan)
                    + plan.pending >= self.depth):
                continue
            self._pump_rr = (start + k) % n + 1
            end = min(plan.issue + self.window_bytes, plan.end)
            if plan.seg_buf is None or plan.issue >= plan.seg_end:
                # chunk-aligned carving: the next few windows share one
                # contiguous segment buffer, so a cursor read spanning
                # a window join inside it stays a zero-copy view
                self._gc_segs_locked()  # release dead segs first (and
                # retire their ids before a fresh buffer can reuse one)
                seg_len = min(_SEG_WINDOWS * self.window_bytes,
                              plan.end - plan.issue)
                plan.seg_buf = np.empty(seg_len, np.uint8)
                plan.seg_start = plan.issue
                plan.seg_end = plan.issue + seg_len
                self._segs[id(plan.seg_buf)] = seg_len
                _ACC_SEG.add(seg_len)
            end = min(end, plan.seg_end)
            offset = plan.issue
            self.stats.windows_issued += 1
            self.stats.bytes_prefetched += end - offset
            _ACC_RING.add(end - offset)
            plan.issue = end
            plan.pending += 1
            self._pending += 1
            return plan, offset, end, plan.seg_buf, plan.seg_start
        return None

    def _pump(self) -> None:
        """Keep windows issued ahead.  Callers must NOT hold the ring
        lock: claims and their accounting run inside it, but the
        executor submission itself is a declared blocking site
        (utils/locks.note_blocking flags submits under tier locks) and
        runs between critical sections — in-flight claims are reserved
        via the ``_pending`` counters so capacity and per-plan depth
        stay exact."""
        if self.backend == "advise":
            with self._lock:
                self._advise_locked()
            return
        from ..utils.pool import submit as pool_submit

        while True:
            with self._lock:
                spec = self._claim_one_locked()
            if spec is None:
                return
            plan, offset, end, seg, seg_start = spec
            try:
                fut = pool_submit(self._fill_window, seg,
                                  offset - seg_start, offset, end - offset)
            except BaseException:
                # executor teardown: un-reserve; the range reads through
                with self._lock:
                    self._pending -= 1
                    plan.pending -= 1
                    self.stats.bytes_discarded += end - offset
                    _ACC_RING.sub(end - offset)
                    self._gc_segs_locked()
                raise
            # retrieve abandoned errors so a window cancelled/failed
            # after close never logs "exception was never retrieved";
            # consumers still see the error through result()
            fut.add_done_callback(
                lambda f: None if f.cancelled() else f.exception())
            win = _Window(offset, end, fut, plan,
                          seg=seg, seg_start=seg_start)
            with self._lock:
                self._pending -= 1
                plan.pending -= 1
                if self._closed or plan.dead:
                    # closed/unplanned while submitting: never serve it
                    fut.cancel()
                    self.stats.bytes_discarded += end - offset
                    _ACC_RING.sub(end - offset)
                    self._gc_segs_locked()
                else:
                    self._ring.append(win)

    def _gc_segs_locked(self) -> None:
        """Release the ledger's segment bytes for carve buffers no plan
        or ring window references anymore (the buffers themselves free by
        refcount; this keeps the ``prefetch.segments`` account matching
        what is actually reachable)."""
        if not self._segs:
            return
        live = {id(p.seg_buf) for p in self._plans
                if p.seg_buf is not None}
        live |= {id(w.seg) for w in self._ring if w.seg is not None}
        for sid in [s for s in self._segs if s not in live]:
            _ACC_SEG.sub(self._segs.pop(sid))

    def _fill_window(self, seg: np.ndarray, rel: int, offset: int,
                     size: int) -> np.ndarray:
        """Background window read into its segment slice.  Returns the
        FILLED slice — a short inner read yields a short slice, which the
        serving path detects (the chain-covered fast path requires every
        window full) so uninitialized segment bytes are never served."""
        if _trace.on():
            # window fills run on pool workers: the span's thread id is
            # what makes IO/decode overlap visible on the Perfetto tracks
            with _trace.span("prefetch.window", offset=offset, bytes=size):
                return self._fill_window_impl(seg, rel, offset, size)
        return self._fill_window_impl(seg, rel, offset, size)

    def _fill_window_impl(self, seg: np.ndarray, rel: int, offset: int,
                          size: int) -> np.ndarray:
        data = self.inner.pread_view(offset, size)
        a = _as_u8(data)
        n = min(len(a), size)
        seg[rel : rel + n] = a[:n]
        return seg[rel : rel + n]

    def _advise_locked(self) -> None:
        """Hint the kernel ``depth`` windows ahead of each plan's frontier.
        Exhausted plans stay registered (they cost nothing and keep the
        hit/miss classification of late re-reads honest)."""
        if self._dropbehind and not self._advised_sequential:
            self._advised_sequential = True
            self._mmap.madvise_sequential()
        for plan in self._plans:
            ahead = min(plan.issue + self.depth * self.window_bytes,
                        plan.end)
            if ahead > plan.issue:
                self._mmap.madvise_willneed(plan.issue, ahead - plan.issue)
                self.stats.windows_issued += 1
                self.stats.bytes_prefetched += ahead - plan.issue
                plan.issue = ahead

    def _advance_advise(self, upto: int,
                        drop_upto: Optional[int] = None) -> None:
        """Consumption reached ``upto``: keep the willneed horizon ``depth``
        windows ahead of it for the plan covering it.  ``drop_upto`` is
        the drop-behind bound — the START of the read that just advanced
        the frontier, NOT its end: the caller holds a zero-copy view of
        [drop_upto, upto) it has not decoded yet, and dropping those
        pages would force a disk refault of bytes readahead just paid
        for.  Only the span strictly behind the current read drops."""
        with self._lock:
            for plan in self._plans:
                if plan.start <= upto <= plan.end:
                    ahead = min(upto + (self.depth + 1) * self.window_bytes,
                                plan.end)
                    if ahead > plan.issue:
                        self._mmap.madvise_willneed(plan.issue,
                                                    ahead - plan.issue)
                        self.stats.windows_issued += 1
                        self.stats.bytes_prefetched += ahead - plan.issue
                        plan.issue = ahead
                    bound = upto if drop_upto is None else drop_upto
                    if self._dropbehind and bound > plan.dropped:
                        # release fully-consumed pages behind the frontier
                        # (rounded inward — a partially-read page stays)
                        self.stats.bytes_dropbehind += \
                            self._mmap.madvise_dontneed(
                                plan.dropped, bound - plan.dropped)
                        plan.dropped = bound
                    break

    # ------------------------------------------------------------- serving
    def _deadline(self):
        """The active operation deadline of a PolicySource underneath, if
        any — waits on in-flight windows honor it so injected latency in a
        queued prefetch cannot stall past ``deadline_s``."""
        src = self.inner
        seen = set()
        while src is not None and id(src) not in seen:
            seen.add(id(src))
            dl = getattr(src, "_deadline", None)
            if dl is not None:
                return dl
            src = getattr(src, "inner", None)
        return None

    def _await(self, win: _Window):
        """Wait for a window's background read, deadline-aware: even with a
        prefetch queued behind injected latency, ``deadline_s`` fires
        promptly on the consuming thread instead of blocking until the
        worker returns."""
        fut = win.future
        if fut.done():
            return fut.result()
        t0 = time.perf_counter()
        wait_span = (_trace.span("prefetch.wait", offset=win.offset)
                     if _trace.on() else _trace.NULL_SPAN)
        wait_span.__enter__()
        try:
            while True:
                dl = self._deadline()
                rem = dl.remaining() if dl is not None else None
                if rem is not None and rem <= 0:
                    raise DeadlineError(
                        f"deadline exceeded waiting for prefetched window "
                        f"at {win.offset}")
                try:
                    # bounded wait even with no deadline: re-check each lap
                    # so a deadline INSTALLED after the wait began (a new
                    # operation scope) still fires promptly
                    return fut.result(timeout=min(rem, 0.05)
                                      if rem is not None else 0.05)
                except (_FutTimeout, TimeoutError):
                    continue
        finally:
            wait_span.__exit__(None, None, None)
            waited = time.perf_counter() - t0
            _WAIT_HIST.observe(waited)
            # per-op mirror of the live wait (the close-time
            # prefetch.pool_wait_s counter lumps a drain's stalls into
            # one moment; this one lands as each wait ends)
            _oscope.add_to_current("prefetch.wait_s", waited)
            with self._lock:
                self.stats.pool_wait_s += waited

    def _serve(self, offset: int, size: int, want_view: bool):
        end = offset + size
        if self.backend == "advise":
            with self._lock:
                covered = any(p.start <= offset and end <= p.end
                              and p.issue >= end for p in self._plans)
                self.stats.prefetch_hits += covered
                self.stats.prefetch_misses += not covered
            out = (self.inner.pread_view(offset, size) if want_view
                   else self.inner.pread(offset, size))
            # drop-behind trails the read: [.., offset) is consumed, the
            # [offset, end) view just handed out is not decoded yet
            self._advance_advise(end, drop_upto=offset)
            return out
        # ring: find a covering chain of windows (cursor reads rarely align
        # with window boundaries, so a read often spans two)
        with self._lock:
            chain = sorted((w for w in self._ring
                            if w.offset < end and w.end > offset),
                           key=lambda w: w.offset)
            covered = bool(chain) and chain[0].offset <= offset \
                and chain[-1].end >= end
            pos = offset
            for w in chain:
                if covered and w.offset > pos:
                    covered = False
                pos = w.end
        from ..utils.pool import in_shared_pool

        if covered and in_shared_pool():
            # secure the chain: a window still QUEUED (not started) may sit
            # behind our own caller's tasks on the shared pool — a pool
            # worker waiting on it would deadlock (all workers blocked on
            # futures none of them will run).  cancel() succeeds exactly
            # for never-started futures; those bytes are read through
            # instead (counted as a miss, not a stall).  Non-pool
            # consumers wait normally — their windows always get a worker.
            cancelled = [w for w in chain if w.future.cancel()]
            if cancelled:
                with self._lock:
                    for w in cancelled:
                        if w in self._ring:
                            self._ring.remove(w)
                            _ACC_RING.sub(w.end - w.offset)
                        self.stats.bytes_discarded += w.end - w.offset
                    self._gc_segs_locked()
                covered = False
        if not covered:
            with self._lock:
                self.stats.prefetch_misses += 1
            return (self.inner.pread_view(offset, size) if want_view
                    else self.inner.pread(offset, size))
        bufs = []
        for w in chain:
            try:
                bufs.append(self._await(w))
            except BaseException:
                # a failed window must not be served (or waited on) again —
                # drop it so retrying consumers read through / get fresh
                # windows, and surface the error HERE, on the consuming
                # thread, inside the caller's read_context
                with self._lock:
                    if w in self._ring:
                        self._ring.remove(w)
                        _ACC_RING.sub(w.end - w.offset)
                    self._gc_segs_locked()
                self._pump()
                raise
        with self._lock:
            self.stats.prefetch_hits += 1
        full = all(len(b) == (w.end - w.offset) for w, b in zip(chain, bufs))
        if len(chain) == 1:
            w = chain[0]
            out = bufs[0][offset - w.offset : end - w.offset]
        elif full and all(w.seg is chain[0].seg for w in chain):
            # the chain sits in one segment buffer: the join is already
            # contiguous — serve a zero-copy view instead of concatenating
            out = chain[0].seg[offset - chain[0].seg_start
                               : end - chain[0].seg_start]
        else:
            out = np.concatenate(
                [_as_u8(b)[max(offset - w.offset, 0)
                           : min(end, w.end) - w.offset]
                 for w, b in zip(chain, bufs)])
        # consume windows the sequential reader has fully passed
        with self._lock:
            drop = [w for w in chain if w.end <= end]
            for w in drop:
                if w in self._ring:
                    self._ring.remove(w)
                    _ACC_RING.sub(w.end - w.offset)
            if drop:
                self._gc_segs_locked()
        if drop:
            self._pump()
        if want_view:
            return out
        return out.tobytes() if hasattr(out, "tobytes") else bytes(out)

    def pread(self, offset: int, size: int) -> bytes:
        return self._serve(offset, size, want_view=False)

    def pread_view(self, offset: int, size: int):
        return self._serve(offset, size, want_view=True)

    def size(self) -> int:
        return self.inner.size()

    def close(self) -> None:
        with self._lock:
            first_close = not self._closed
            self._closed = True
            if self._dropbehind and first_close:
                # post-drain drop: the one-shot read is over — release
                # each plan's REMAINING tail ([dropped, end); the span
                # behind the frontier was already dropped and counted
                # incrementally, re-dropping it would double the meter)
                for plan in self._plans:
                    self.stats.bytes_dropbehind += \
                        self._mmap.madvise_dontneed(
                            plan.dropped, plan.end - plan.dropped)
            self._plans.clear()
            for w in self._ring:
                if not w.future.cancel() and w.future.done():
                    try:
                        w.future.result()
                    # ptlint: disable=PT005 -- abandoned-window teardown:
                    # retrieving the error is the point (suppresses the
                    # "exception was never retrieved" warning); nobody is
                    # left to deliver it to
                    except BaseException:
                        pass
                self.stats.bytes_discarded += w.end - w.offset
                if first_close:
                    _ACC_RING.sub(w.end - w.offset)
            self._ring.clear()
            self._gc_segs_locked()  # plans+ring empty: releases every seg
        if first_close:
            # one publish per drain: the registry gets this prefetcher's
            # lifetime totals exactly once (close() may be called again)
            self.stats.publish()
        if self.backend == "ring" and self._tunable:
            # feed the drain's bubble meter back into the next drain's
            # readahead defaults for THIS latency class (no-op when env
            # pins or opt-out disabled)
            _AUTOTUNE.observe(self.stats, self.latency_class)
        if self._owns_inner:
            self.inner.close()


def make_prefetcher(source: Source,
                    stats: Optional[ReadStats] = None,
                    n_streams: int = 1) -> Optional[PrefetchSource]:
    """Build the prefetcher the auto policy picks for ``source``, or None
    when prefetching is off / cannot pay here.

    advise for chains bottoming out at an :class:`MmapSource` (zero threads,
    single-core-safe); ring when the host has cores to spare for background
    IO (on one core a pread against a warm page cache is a memcpy that
    *competes* with decode — measured regression, so auto never rings
    there); ``PARQUET_TPU_PREFETCH=ring`` forces the pool backend anyway
    (chaos tests, known-cold caches).  ``n_streams`` sizes the ring so
    interleaved column cursors don't evict each other's windows.
    """
    from ..utils.pool import available_cpus, in_shared_pool
    from .source import FileLikeSource, FileSource

    mode = prefetch_mode()
    if mode == "off":
        return None
    deepest = _innermost(source)
    if mode in ("auto", "mmap") and isinstance(deepest, MmapSource):
        return PrefetchSource(source, backend="advise", stats=stats)
    if mode == "mmap":
        return None
    # remote chains ring REGARDLESS of core count (except inside pool
    # workers — the nested-submitter deadlock guard): a network pread
    # spends its time blocked in the socket with the GIL released, so
    # background readahead hides real RTT latency even on one core —
    # exactly the case where the local-ring "memcpy competes with
    # decode" regression does not apply
    remote = getattr(deepest, "latency_class", "local") != "local"
    # auto rings only chains that bottom out in real IO: an in-memory
    # BytesSource has no disk latency to hide, so background "reads" would
    # be pure pool-dispatch overhead.  Forced ring mode skips the gate
    # (chaos tests wrap BytesSource deliberately).
    real_io = isinstance(deepest, (FileSource, FileLikeSource))
    if mode == "ring" or (mode == "auto" and not in_shared_pool()
                          and (remote or (real_io
                                          and available_cpus() > 1))):
        return PrefetchSource(source, backend="ring", stats=stats,
                              max_windows=max(8, 2 * n_streams))
    return None


def make_chunk_prefetcher(source: Source,
                          stats: Optional[ReadStats] = None,
                          n_streams: int = 1) -> Optional[PrefetchSource]:
    """Prefetcher for WHOLE-CHUNK pread consumers — the device staging
    route (``decode_chunks_pipelined`` / ``stage_scan``), whose ``build_plan``
    reads each column chunk in one pread.  A chunk-sized read arriving
    before its ring windows are issued can never be covered (only ``depth``
    windows are ever ahead), so the auto policy here uses only the advise
    backend: plan the chunk ranges, let ``madvise(WILLNEED)`` run kernel
    readahead under the prescan + H2D of earlier chunks, and serve the
    preads as zero-copy mmap views.  ``PARQUET_TPU_PREFETCH=ring`` still
    forces the ring (chaos tests exercise the read-through path); ``off``
    disables as usual."""
    mode = prefetch_mode()
    if mode == "off":
        return None
    if mode == "ring":
        return make_prefetcher(source, stats=stats, n_streams=n_streams)
    if isinstance(_innermost(source), MmapSource):
        return PrefetchSource(source, backend="advise", stats=stats)
    return None
