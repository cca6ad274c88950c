"""Process-wide worker pool for CPU-bound columnar work: the pushdown scan,
the whole-file chunk fan-out, the streamed read's parallel column decode,
the prefetcher's background window reads (io/prefetch.py), and the writer's
≥8 MB parallel-encode path.

One shared executor: pool construction costs ~1ms, which would dominate
small operations if paid per call, and the numpy/C++/codec work it runs
releases the GIL.  ``PARQUET_TPU_POOL_WORKERS`` pins the width (equivalence
smokes run width 1 vs N; results must be identical).
"""

from __future__ import annotations

import contextvars
import itertools
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

from . import locks as _locks
from .env import env_int, env_opt_bytes
from .locks import make_condition, make_lock
from ..obs import ledger as _ledger
from ..obs import scope as _scope
from ..obs import trace as _trace
from ..obs.metrics import counter as _counter
from ..obs.metrics import gauge as _gauge
from ..obs.metrics import histogram as _histogram

_POOL: Optional[ThreadPoolExecutor] = None
_LOCK = make_lock("pool.build")
_IN_POOL = threading.local()

# queue→run wait per task: the pool-saturation meter every operation's
# dispatch feeds (obs.metrics.pool_wait_seconds sums it for the router)
_QUEUE_WAIT = _histogram("pool.queue_wait_s")
_TASKS = _counter("pool.tasks", help="tasks dispatched to the shared pool")
_ACTIVE = _gauge("pool.active", help="pool tasks currently running")

# admission-control meters: per-tier wait counters (the lookup family
# keeps its PR-9 names; scan/stream waits land in the read.* family)
_M_ADM_WAITS = _counter("lookup.admission_waits",
                        help="lookup admissions that had to block")
_ADM_WAIT_S = _histogram("lookup.admission_wait_s")
_M_READ_WAITS = _counter("read.admission_waits",
                         help="scan/stream admissions that had to block")
_READ_WAIT_S = _histogram("read.admission_wait_s")
_M_ADMITTED = _gauge("lookup.admitted_bytes",
                     help="bytes currently admitted through the read gate")
_ACC_ADMITTED = _ledger.ledger_account("admission.in_flight")

# re-entrancy guard: a decode span already running under an admission
# grant must not acquire again (the lookup chunk-fallback admits, then
# _decode_chunk_ctx would admit the same bytes — a nested FIFO wait
# behind other tickets while holding budget is a self-deadlock).  A
# context variable, so the flag follows an op onto pool workers exactly
# like its scope does.
_ADMISSION_HELD: "contextvars.ContextVar[bool]" = \
    contextvars.ContextVar("parquet_tpu_admission_held", default=False)

# ---------------------------------------------------------------------------
# Tenant QoS (the serving daemon's multi-tenant layer over the one gate)
# ---------------------------------------------------------------------------

# priority classes, best first: a `latency` ticket is always considered
# before a `bulk` one regardless of arrival order — the scheduling
# property the serve starvation test asserts.  Untagged (library) traffic
# rides the default rank, keeping its exact FIFO semantics.
_CLASS_RANKS = {"latency": 0, "default": 1, "bulk": 2}


@dataclass(frozen=True)
class TenantSpec:
    """One tenant's QoS contract at the admission gate: a byte budget
    (its private clamp INSIDE the shared budgets — 0/None = unlimited),
    a weighted-fair ``weight`` (2.0 drains twice the bytes of 1.0 under
    contention within a class), a priority ``klass`` (``latency`` |
    ``default`` | ``bulk``) that orders it against other tenants, and an
    optional request-RATE limit: ``qps`` tokens/second with up to
    ``burst`` banked (None/0 qps = unlimited; burst defaults to
    ``max(qps, 1)``) — enforced by :meth:`AdmissionController.
    try_request`, the serving daemon's 429 gate."""

    name: str
    budget_bytes: Optional[int] = None
    weight: float = 1.0
    klass: str = "default"
    qps: Optional[float] = None
    burst: Optional[float] = None


# the active (tenant, klass) of the current request — a context variable
# so every nested admission a request performs (scan spans, lookup page
# reads, chunk-fallback decodes, even work fanned onto pool workers via
# instrument_task's context copy) attributes to the tenant that asked
_TENANT: "contextvars.ContextVar[Optional[Tuple[str, str]]]" = \
    contextvars.ContextVar("parquet_tpu_tenant", default=None)


class _Ticket:
    """One waiter at the admission gate.  ``key`` is the scheduling
    order (class rank, tenant virtual time at enqueue, arrival seq);
    untagged tickets share (1, 0.0, seq) — exact FIFO."""

    __slots__ = ("key", "tenant", "tier", "grant")

    def __init__(self, key, tenant, tier, grant):
        self.key = key
        self.tenant = tenant
        self.tier = tier
        self.grant = grant


def current_tenant() -> "Optional[Tuple[str, str]]":
    """The active ``(tenant, class)`` pair, or None outside a tenant
    context (library use: exactly the pre-daemon behavior)."""
    return _TENANT.get()


@contextmanager
def tenant_context(name: str, klass: str = "default"):
    """Run a block as ``name`` in priority class ``klass``: every
    admission inside it is scheduled and accounted against the tenant's
    :class:`TenantSpec` (weighted-fair within the class, clamped by the
    tenant's budget).  The serving daemon wraps each request in one."""
    token = _TENANT.set((name, klass if klass in _CLASS_RANKS
                         else "default"))
    try:
        yield
    finally:
        _TENANT.reset(token)


def in_shared_pool() -> bool:
    """True inside work dispatched via :func:`submit` — callees consult this
    to keep their own native thread splits at 1 instead of oversubscribing
    (pool width x native threads).  Explicit context, not thread-name
    matching: user-named worker threads must not defeat the limit."""
    return getattr(_IN_POOL, "flag", False)


def mark_pooled(fn):
    """Wrap ``fn`` so in_shared_pool() is True while it runs — for work
    dispatched to ANY executor (the shared pool or a caller-bounded one)."""

    def run(*args, **kwargs):
        prev = getattr(_IN_POOL, "flag", False)
        _IN_POOL.flag = True
        try:
            return fn(*args, **kwargs)
        finally:
            _IN_POOL.flag = prev

    return run


def instrument_task(fn, name: "Optional[str]" = None):
    """Wrap an about-to-be-dispatched pool task with the telemetry every
    shared-pool entry point must apply: the task's queue→run wait lands in
    the ``pool.queue_wait_s`` histogram (the saturation signal the scan
    router discounts effective GB/s by — dispatch time is captured NOW, at
    wrap), ``pool.tasks`` counts it, and with tracing on it runs inside a
    ``pool.task`` span carrying its worker-thread id.  Used by
    :func:`submit` and by direct ``shared_pool().map`` dispatchers
    (host_scan's fan-out) — a map that skipped this would hide exactly the
    queueing the router exists to observe.

    The dispatcher's context is captured here too (``contextvars.
    copy_context``) and each run executes inside a fresh copy of it, so
    the active op scope (obs/scope.py) — its per-op accounting, trace
    track, and sampling ring — follows the work onto the worker thread.
    A fresh ``ctx.copy()`` per run, not one shared ctx: one wrapped fn is
    mapped over many items concurrently (host_scan's fan-out), and a
    Context object refuses concurrent re-entry."""
    t_submit = time.perf_counter()
    ctx = contextvars.copy_context()

    def run(*a, **k):
        return ctx.copy().run(_run_instrumented, fn, name, t_submit, a, k)

    return run


def _run_instrumented(fn, name, t_submit: float, a, k):
    wait = time.perf_counter() - t_submit
    _QUEUE_WAIT.observe(wait)
    # per-op mirror of the queue wait: runs inside the propagated
    # context, so the wait attributes to the op that dispatched the task
    _scope.add_to_current("pool.queue_wait_s", wait)
    _scope.account(_TASKS)
    _ACTIVE.inc()  # the /debugz "running now" meter
    try:
        if _trace.on():
            with _trace.span("pool.task", fn=name):
                return fn(*a, **k)
        return fn(*a, **k)
    finally:
        _ACTIVE.dec()


def submit(fn, *args, **kwargs):
    """Submit to the shared pool, marking the worker for in_shared_pool().

    Every task's queue→run wait lands in the ``pool.queue_wait_s``
    histogram (the saturation signal the scan router discounts effective
    GB/s by), and with tracing on each task runs inside a ``pool.task``
    span carrying its worker-thread id — pipeline overlap is visible as
    overlapping bars on worker tracks."""
    if _locks.LOCKCHECK_ENABLED:
        _locks.note_blocking("pool.submit",
                            detail=getattr(fn, "__name__", "") or "")
    wrapped = instrument_task(mark_pooled(fn),
                              name=getattr(fn, "__name__", None))
    return shared_pool().submit(wrapped, *args, **kwargs)


def cancel_futures(futures) -> None:
    """Best-effort teardown of abandoned background work: cancel what never
    started, and attach an error-retrieving callback to the rest so a task
    failing after its consumer gave up (writer abort, prefetcher close)
    never logs "exception was never retrieved".  Does not wait — abandoned
    work is pure compute whose results nobody reads."""
    for f in futures:
        if not f.cancel():
            f.add_done_callback(
                lambda g: None if g.cancelled() else g.exception())


def map_in_order(fn, items, parallel: "Optional[bool]" = None) -> list:
    """Run ``fn`` over ``items`` and return results in input order.

    Fans out on the shared pool unless parallelism cannot help (one item,
    one CPU) or would deadlock (already inside a pool worker: a nested
    submitter blocking on futures no free worker can run wedges the pool —
    the same guard the stream layer applies).  On failure every task still
    runs to completion (abandoned futures would warn and waste workers
    anyway), then the FIRST failing item's exception is raised — callers
    that want per-item failure isolation catch inside ``fn``.  Used by the
    dataset layer's per-file fan-out and the CLI's parallel verify."""
    items = list(items)
    if parallel is None:
        parallel = (len(items) > 1 and available_cpus() > 1
                    and not in_shared_pool())
    if not parallel:
        return [fn(it) for it in items]
    futs = [submit(fn, it) for it in items]
    out, first_err = [], None
    try:
        for f in futs:
            try:
                out.append(f.result())
            except Exception as e:
                if first_err is None:
                    first_err = e
                out.append(None)
    except BaseException:
        # KeyboardInterrupt/SystemExit on the waiting thread: cancel what
        # never started and get out NOW — blocking through the remaining
        # futures would make Ctrl-C appear hung
        cancel_futures(futs)
        raise
    if first_err is not None:
        raise first_err
    return out


class AdmissionController:
    """FIFO bytes-budget gate over EVERY in-flight read span — the
    unified generalization of the PR-9 lookup-only gate (ROADMAP item 3's
    "one budget governs all in-flight read bytes" follow-on).

    The shared pool bounds *width* (how many tasks run) but not *memory*
    (how many bytes the running + queued tasks pin) or *order* (a flood of
    late arrivals can starve an earlier waiter indefinitely under a plain
    semaphore).  This controller fixes both at once, for every read tier:

    - **bytes budget** — ``acquire(nbytes, tier=...)`` blocks until the
      request fits, so total in-flight read bytes never exceed the cap no
      matter the concurrency.  ``PARQUET_TPU_READ_BUDGET`` is the one
      global budget; the per-tier sub-budgets are optional clamps inside
      it: ``PARQUET_TPU_LOOKUP_BUDGET`` (the PR-9 env, kept as an alias —
      with no global budget it still defaults the lookup tier to 64 MiB,
      exactly the old behavior) and ``PARQUET_TPU_SCAN_BUDGET`` for scan
      phase-1/2 decode spans and streamed whole-chunk decodes (default
      off: bulk reads are unbudgeted unless an operator opts in, so the
      PR-3..9 throughput baselines are untouched).  A request larger
      than the whole budget is clamped and admits alone — it must not
      deadlock, and alone it cannot compound.
    - **FIFO fairness** — waiters are granted strictly in arrival order
      (a ticket queue, not a herd on a semaphore), across tiers: a scan's
      large span cannot be starved by a stream of later small lookups,
      and bursts drain in bounded, predictable order.
    - **hard-pressure blocking** — while the resource ledger
      (obs/ledger.py) is over ``PARQUET_TPU_MEM_HARD``, new admissions
      block (after triggering the reclaim pass) until the total drops
      below the watermark; releases never block, so held budget always
      drains.

    Nested acquires are re-entrant no-ops (a decode running under a
    grant gets grant 0 from inner gates — the outer span already
    reserved its bytes), tracked by a context variable so the guard
    follows work onto pool workers.

    **Tenant QoS** (the serving daemon's layer — :func:`tenant_context`
    + :meth:`configure_tenants`): tickets carry the active tenant's
    priority class and weighted-fair virtual time, and the FIFO queue
    generalizes into a scheduler with three properties the plain queue
    cannot give a multi-tenant daemon:

    - **priority classes** — among waiting tickets, ``latency`` class is
      considered before ``default`` before ``bulk``, regardless of
      arrival order: a flood of bulk scans cannot starve a p99-sensitive
      lookup (the starvation test holds both tenants' budgets and
      asserts the lookup p99).
    - **per-tenant budgets** — each tenant's in-flight bytes are clamped
      by its own ``TenantSpec.budget_bytes``; a ticket blocked ONLY by
      its own tenant's budget is skipped over (its lane waits; other
      tenants proceed), while a ticket blocked on the SHARED tier/global
      budget reserves it (no later-keyed ticket may leapfrog — exactly
      the old FIFO anti-starvation guarantee, now per scheduling key).
      Untagged (library) traffic has no tenant lane, so its semantics
      are byte-for-byte the old strict FIFO.
    - **weighted fairness** — within a class, tickets order by their
      tenant's virtual time (cumulative granted bytes / weight), so a
      weight-2 tenant drains twice the bytes of a weight-1 rival under
      contention instead of splitting by arrival luck.

    ``high_water`` records the max bytes ever admitted concurrently (the
    budget-held proof the admission tests assert), and
    ``tenant_high_water[name]`` the same per tenant.  Waits are metered
    per tier: ``lookup.admission_waits``/``lookup.admission_wait_s`` and
    ``read.admission_waits``/``read.admission_wait_s``; the granted
    bytes publish as the ``admission.in_flight`` ledger account."""

    def __init__(self, env_var: str = "PARQUET_TPU_LOOKUP_BUDGET",
                 default_bytes: int = 64 << 20):
        # env_var: the lookup tier's sub-budget env (overridable so the
        # PR-9 admission unit tests can pin an isolated controller)
        self._tier_envs = {"lookup": env_var,
                           "scan": "PARQUET_TPU_SCAN_BUDGET"}
        self._default_lookup = default_bytes
        self._cv = make_condition("pool.admission")
        # request-rate token buckets, separate lock: try_request is a
        # pre-admission fast path and must not contend with the byte
        # gate's scheduler walk
        self._qps_lock = make_lock("pool.qps")
        self._qps_state: "Dict[str, list]" = {}  # name -> [tokens, t_last]
        self._queue: list = []  # _Ticket objects, arrival order
        self._seq = itertools.count()
        self._in_use = 0
        self._tier_use: dict = {}
        self._tenants: "Dict[str, TenantSpec]" = {}
        self._tenant_use: "Dict[str, int]" = {}
        self._vtime: "Dict[str, float]" = {}
        self._vfloor = 0.0  # global virtual clock (see acquire)
        self.tenant_high_water: "Dict[str, int]" = {}
        self.tenant_waits: "Dict[str, int]" = {}
        self.high_water = 0
        self.waits = 0

    # ------------------------------------------------------------ tenants
    def configure_tenants(self, specs) -> None:
        """Install the tenant table (``{name: TenantSpec}`` or an
        iterable of specs) — the serving daemon calls this from its
        config at boot.  Unknown tenants admit with no private budget at
        the default class (the spec-less library behavior)."""
        if isinstance(specs, dict):
            specs = specs.values()
        table = {}
        for s in specs:
            if not isinstance(s, TenantSpec):
                raise TypeError(f"expected TenantSpec, got "
                                f"{type(s).__name__}")
            if s.weight <= 0:
                raise ValueError(f"tenant {s.name!r} weight must be > 0")
            if s.qps is not None and s.qps < 0:
                raise ValueError(f"tenant {s.name!r} qps must be >= 0")
            if s.burst is not None and s.burst < 1:
                raise ValueError(f"tenant {s.name!r} burst must be >= 1")
            table[s.name] = s
        with self._cv:
            self._tenants = table
        with self._qps_lock:
            # stale buckets from a previous config must not carry debt
            # (or banked burst) into the new contracts
            self._qps_state = {}

    def clear_tenants(self) -> None:
        """Forget the tenant table and its accounting (test isolation;
        in-flight grants release against the generic counters)."""
        with self._cv:
            self._tenants = {}
            self._tenant_use = {}
            self._vtime = {}
            self._vfloor = 0.0
            self.tenant_high_water = {}
            self.tenant_waits = {}
        with self._qps_lock:
            self._qps_state = {}

    def try_request(self, name: str) -> "Optional[float]":
        """Token-bucket request-rate gate for ONE arriving request of
        tenant ``name``: returns None when admitted (one token consumed)
        or the seconds until a token will exist — the ``Retry-After`` a
        429 should advertise.  Tenants without a ``qps`` contract (and
        unknown tenants) always admit; the bucket banks up to ``burst``
        tokens (default ``max(qps, 1)``) so idle tenants absorb bursts
        without paying steady-state latency."""
        with self._cv:
            spec = self._tenants.get(name)
        if spec is None or not spec.qps:
            return None
        rate = float(spec.qps)
        cap = float(spec.burst) if spec.burst is not None \
            else max(rate, 1.0)
        now = time.monotonic()
        with self._qps_lock:
            state = self._qps_state.get(name)
            if state is None:
                state = self._qps_state[name] = [cap, now]
            tokens, t_last = state
            tokens = min(cap, tokens + (now - t_last) * rate)
            if tokens >= 1.0:
                state[0] = tokens - 1.0
                state[1] = now
                return None
            state[0] = tokens
            state[1] = now
            return (1.0 - tokens) / rate

    def tenant_spec(self, name: str) -> "Optional[TenantSpec]":
        with self._cv:
            return self._tenants.get(name)

    def tenant_debug(self) -> dict:
        """Per-tenant live state for ``/debugz``: configured contract,
        bytes in flight, lifetime high water, and blocked-acquire
        count."""
        with self._cv:
            names = set(self._tenants) | set(self._tenant_use) \
                | set(self.tenant_high_water)
            out = {}
            for n in sorted(names):
                spec = self._tenants.get(n)
                out[n] = {
                    "class": spec.klass if spec else "default",
                    "weight": spec.weight if spec else 1.0,
                    "budget_bytes": spec.budget_bytes if spec else None,
                    "in_flight_bytes": self._tenant_use.get(n, 0),
                    "high_water_bytes": self.tenant_high_water.get(n, 0),
                    "waits": self.tenant_waits.get(n, 0),
                }
            return out

    def global_budget_bytes(self) -> Optional[int]:
        """``PARQUET_TPU_READ_BUDGET`` — the unified cap (None = unset,
        ``0`` = admission explicitly off for every tier)."""
        return env_opt_bytes("PARQUET_TPU_READ_BUDGET")

    def budget_bytes(self, tier: str = "lookup") -> int:
        """Effective budget for ``tier``, read per acquire (tests repoint
        the env without rebuilding the controller); ``0`` disables
        admission for the tier.  Sub-budget env wins, then the global
        budget, then the tier default (64 MiB for lookups — the PR-9
        contract — off for scans)."""
        g = self.global_budget_bytes()
        if g == 0:
            return 0
        t = env_opt_bytes(self._tier_envs.get(tier, ""))
        if t is not None:
            return t
        if g is not None:
            return g
        return self._default_lookup if tier == "lookup" else 0

    def _tenant_budget(self, name: "Optional[str]") -> int:
        # under self._cv; 0 = no private clamp
        if name is None:
            return 0
        spec = self._tenants.get(name)
        if spec is None or not spec.budget_bytes:
            return 0
        return int(spec.budget_bytes)

    def _may_grant_locked(self, ticket, budget: int,
                          g: "Optional[int]", hard: bool) -> bool:
        """The scheduling decision, under the gate's lock: may ``ticket``
        be granted NOW?  Walks the queue in scheduling-key order
        (class rank, weighted virtual time, arrival): a ticket blocked
        only by its OWN tenant budget blocks its whole LANE — later
        tickets of the same tenant wait behind it (the intra-lane FIFO
        anti-starvation guarantee: a stream of small same-tenant
        requests cannot leapfrog a big one) while OTHER lanes pass; a
        ticket that fits its lane but not the shared tier/global budget
        RESERVES the shared capacity (no later key may leapfrog — the
        old cross-queue FIFO guarantee); an earlier-keyed ticket that
        fits outright wins first."""
        if hard:
            return False
        # tier budgets resolved once per evaluation, not once per queued
        # ticket (budget_bytes is an env parse)
        tier_budgets = {ticket.tier: budget}
        blocked_lanes = set()
        for t in sorted(self._queue, key=lambda t: t.key):
            tb = self._tenant_budget(t.tenant)
            tier_b = tier_budgets.get(t.tier)
            if tier_b is None:
                tier_b = tier_budgets[t.tier] = self.budget_bytes(t.tier)
            lane_blocked = t.tenant is not None \
                and t.tenant in blocked_lanes
            fits_tenant = tb <= 0 or (self._tenant_use.get(t.tenant, 0)
                                      + t.grant <= tb)
            fits_tier = tier_b <= 0 or (self._tier_use.get(t.tier, 0)
                                        + t.grant <= tier_b)
            fits_global = g is None or g <= 0 \
                or self._in_use + t.grant <= g
            if t is ticket:
                return fits_tenant and fits_tier and fits_global \
                    and not lane_blocked
            if not fits_tenant or lane_blocked:
                # its lane is full (or an earlier lane-mate is): the
                # whole lane waits in key order; other lanes pass
                if t.tenant is not None:
                    blocked_lanes.add(t.tenant)
                continue
            # an earlier-keyed ticket either fits (its thread will take
            # the grant first) or is blocked on SHARED capacity (which
            # it reserves) — either way this ticket waits
            return False
        raise AssertionError("ticket not in queue")  # pragma: no cover

    def acquire(self, nbytes: int, tier: str = "lookup",
                give_up=None) -> int:
        """Block until ``nbytes`` fit under the scheduler (and the ledger
        is below the hard watermark); returns the granted amount to hand
        back to :meth:`release` (0 when admission is disabled or the
        caller already holds a grant).  Untagged callers get strict FIFO
        (the PR-9/PR-10 contract); callers inside a
        :func:`tenant_context` are scheduled weighted-fair by priority
        class with their tenant's private budget applied (class
        docstring).  ``give_up`` (a zero-arg predicate, checked each
        wait lap) lets a waiter withdraw: its ticket leaves the queue
        and 0 is granted — without it, an abandoned waiter (a hedged
        read whose primary already won) would sit at the queue head and
        head-of-line-block every other admission until unrelated budget
        freed."""
        if _ADMISSION_HELD.get():
            return 0
        budget = self.budget_bytes(tier)
        g = self.global_budget_bytes()
        hard_gate = _ledger.hard_watermark_bytes() > 0
        tkt_tenant = _TENANT.get()
        tenant = tkt_tenant[0] if tkt_tenant is not None else None
        klass = tkt_tenant[1] if tkt_tenant is not None else "default"
        with self._cv:
            tenant_budget = self._tenant_budget(tenant)
            spec = self._tenants.get(tenant) if tenant else None
        if budget <= 0 and tenant_budget <= 0 and not hard_gate:
            return 0
        grant = min(max(int(nbytes), 0),
                    *(b for b in (budget, tenant_budget) if b > 0)) \
            if (budget > 0 or tenant_budget > 0) else 0
        if g is not None and g > 0:
            grant = min(grant, g)
        t0 = time.perf_counter()
        waited = False
        if hard_gate and _ledger.LEDGER.check_pressure() == "hard":
            # reclaim runs HERE, outside the gate's lock: a blocked
            # admission drives the eviction it is waiting on without
            # serializing every other acquire/release behind cache locks
            waited = True
        with self._cv:
            # scheduling key: class rank first, then the tenant's
            # weighted virtual time AT ENQUEUE (WFQ start time), then
            # arrival — untagged tickets share rank 1 / vtime 0, which
            # reduces to exact arrival order.  The start time is floored
            # at the global virtual clock (_vfloor, advanced at every
            # grant): a newly-added or long-idle tenant joins at NOW
            # instead of replaying its lifetime deficit as absolute
            # priority over tenants that kept working.
            rank = _CLASS_RANKS.get(klass, 1)
            # untagged tickets also join at the floor (still exact FIFO
            # among themselves — the floor is monotone): pinning them at
            # 0.0 would let sustained library traffic permanently
            # outrank every default-class tenant's positive vtime.  With
            # no tenants configured the floor never moves, so pure
            # library use keeps the exact pre-daemon FIFO keys.
            vt = max(self._vtime.get(tenant, 0.0), self._vfloor) \
                if tenant else self._vfloor
            ticket = _Ticket((rank, vt, next(self._seq)), tenant, tier,
                             grant)
            self._queue.append(ticket)
            while not self._may_grant_locked(
                    ticket, budget, g,
                    hard_gate and _ledger.LEDGER.state() == "hard"):
                if give_up is not None and give_up():
                    # withdraw: the ticket must not keep later arrivals
                    # waiting behind a grant nobody wants anymore
                    self._queue.remove(ticket)
                    self._cv.notify_all()
                    return 0
                waited = True
                # bounded lap: hard-pressure state changes (env flips,
                # cache evictions elsewhere) have no notifier of their
                # own.  state() is the CHEAP refresh (account sum, no
                # reclaim, no cache locks) — safe under the gate's lock.
                self._cv.wait(timeout=0.05)
            self._queue.remove(ticket)
            self._in_use += grant
            self._tier_use[tier] = self._tier_use.get(tier, 0) + grant
            if self._in_use > self.high_water:
                self.high_water = self._in_use
            if tenant is not None:
                use = self._tenant_use.get(tenant, 0) + grant
                self._tenant_use[tenant] = use
                if use > self.tenant_high_water.get(tenant, 0):
                    self.tenant_high_water[tenant] = use
                # weighted virtual time: the fairness clock — a tenant
                # pays granted bytes / weight from its floored start
                # time, so heavier weights drain proportionally more
                # under contention; the global clock advances with every
                # grant so idle lanes cannot bank priority
                w = spec.weight if spec is not None else 1.0
                self._vfloor = max(self._vfloor, vt)
                self._vtime[tenant] = vt + grant / max(w, 1e-9)
                if waited:
                    self.tenant_waits[tenant] = \
                        self.tenant_waits.get(tenant, 0) + 1
            if waited:
                self.waits += 1  # inside the lock: exact under herds
            _M_ADMITTED.set(self._in_use)
            _ACC_ADMITTED.set(self._in_use)
            # the next waiter may also fit (grants are not exclusive):
            # wake the queue so admission drains as wide as the budget
            self._cv.notify_all()
        if waited:
            wait_s = time.perf_counter() - t0
            if tier == "lookup":
                _ADM_WAIT_S.observe(wait_s)
                _scope.account(_M_ADM_WAITS)
                _scope.add_to_current("lookup.admission_wait_s", wait_s)
            else:
                _READ_WAIT_S.observe(wait_s)
                _scope.account(_M_READ_WAITS)
                _scope.add_to_current("read.admission_wait_s", wait_s)
        return grant

    def release(self, grant: int, tier: str = "lookup",
                tenant: "Optional[str]" = None) -> None:
        if grant <= 0:
            return
        if tenant is None:
            got = _TENANT.get()
            tenant = got[0] if got is not None else None
        with self._cv:
            self._in_use -= grant
            self._tier_use[tier] = self._tier_use.get(tier, 0) - grant
            if tenant is not None and tenant in self._tenant_use:
                self._tenant_use[tenant] -= grant
            _M_ADMITTED.set(self._in_use)
            _ACC_ADMITTED.set(self._in_use)
            self._cv.notify_all()

    def queue_depth(self) -> int:
        """Waiters currently queued at the gate (the /debugz meter)."""
        with self._cv:
            return len(self._queue)

    def in_flight_bytes(self) -> int:
        with self._cv:
            return self._in_use

    @contextmanager
    def admit(self, nbytes: int, tier: str = "lookup"):
        """``with admission.admit(span_bytes): pread + decode`` — the
        shape every admitted IO/decode span wraps.  Marks the context as
        holding a grant so nested gates pass through."""
        got = _TENANT.get()
        tenant = got[0] if got is not None else None
        grant = self.acquire(nbytes, tier=tier)
        token = _ADMISSION_HELD.set(True)
        try:
            yield grant
        finally:
            _ADMISSION_HELD.reset(token)
            self.release(grant, tier=tier, tenant=tenant)

    def _reset(self) -> None:
        """Test isolation only: forget the high-water marks and wait
        counts (the budget itself is env-driven)."""
        with self._cv:
            self.high_water = self._in_use
            self.waits = 0
            self.tenant_high_water = {t: n for t, n
                                      in self._tenant_use.items() if n}
            self.tenant_waits = {}


_ADMISSION = AdmissionController()


def lookup_admission() -> AdmissionController:
    """The process-wide admission gate the batched-lookup path shares —
    one budget across every concurrent ``find_rows``, every file.
    (Alias of :func:`read_admission`: since the unified budget there is
    ONE gate for every read tier.)"""
    return _ADMISSION


def read_admission() -> AdmissionController:
    """The process-wide unified read gate: scan phase-1/2 decode spans,
    streamed whole-chunk decodes, and batched lookups all admit through
    this one FIFO bytes budget (``PARQUET_TPU_READ_BUDGET``)."""
    return _ADMISSION


def pool_debug() -> dict:
    """Live shared-pool state for ``/debugz``: configured width, tasks
    running now, and the dispatch queue depth (0s when the pool was
    never built — nothing has fanned out yet)."""
    with _LOCK:
        pool = _POOL
    queued = 0
    if pool is not None:
        try:
            queued = pool._work_queue.qsize()
        except (AttributeError, NotImplementedError):
            queued = 0
    return {"width": pool_width(), "built": pool is not None,
            "active": _ACTIVE.value, "queued": queued}


def available_cpus() -> int:
    """CPUs actually available to THIS process (cgroup/affinity-aware —
    os.cpu_count() reports physical cores and misfires in pinned
    containers)."""
    try:
        return len(os.sched_getaffinity(0)) or 1
    except (AttributeError, OSError):
        return os.cpu_count() or 1


def pool_width() -> int:
    """Worker count the shared pool is (or will be) built with.
    ``PARQUET_TPU_POOL_WORKERS`` overrides; read at first use."""
    width = env_int("PARQUET_TPU_POOL_WORKERS")
    if width > 0:
        return width
    # size to the machine: far more workers than cores just thrashes the
    # GIL on the python slices between the GIL-releasing numpy/C++/codec
    # calls (measured ~1.6x slowdown at 16 workers on one core); 2 is the
    # floor so IO still overlaps decode
    return max(2, min(16, available_cpus()))


def shared_pool() -> ThreadPoolExecutor:
    global _POOL
    with _LOCK:
        if _POOL is None:
            _POOL = ThreadPoolExecutor(max_workers=pool_width(),
                                       thread_name_prefix="pq-work")
        return _POOL
