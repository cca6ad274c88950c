"""Unified telemetry (parquet_tpu/obs): registry accounting under shared-pool
concurrency, histogram percentile sanity, the disabled-tracer zero-allocation
contract, Perfetto trace-file validity, Prometheus exposition lint, and
back-compat of the six legacy stats views (ReadStats, WriteStats, CacheStats,
ReadReport, planner counters, RouteHistory) that now publish into the
registry."""

import io
import json
import os
import re
import threading

import numpy as np
import pyarrow as pa
import pytest

import parquet_tpu.utils.pool as pool_mod
from parquet_tpu import Dataset, ParquetFile, obs
from parquet_tpu.io.cache import cache_stats, clear_caches
from parquet_tpu.io.faults import ReadReport
from parquet_tpu.io.planner import RouteHistory, ScanPlanner
from parquet_tpu.io.writer import WriterOptions, write_table
from parquet_tpu.obs import (metrics_delta, metrics_snapshot,
                             render_prometheus)
from parquet_tpu.obs.metrics import (Counter, Gauge, Histogram,
                                     MetricsRegistry, REGISTRY)
from parquet_tpu.obs.trace import NULL_SPAN


@pytest.fixture(autouse=True)
def _clean_trace_state():
    """Tracing is process-global: every test starts and ends disabled with
    an empty buffer so span assertions never see a neighbor's events."""
    obs.disable_tracing()
    obs.reset_trace()
    yield
    obs.disable_tracing()
    obs.reset_trace()


def _counter_value(name, labels=None):
    return REGISTRY.counter(name, labels).value


def _write_file(path, n=100_000, row_groups=4, seed=0, **opts):
    t = pa.table({"a": pa.array(np.arange(n, dtype=np.int64)),
                  "b": pa.array(np.random.default_rng(seed).random(n))})
    write_table(t, path, WriterOptions(row_group_size=n // row_groups,
                                       **opts))
    return t


# ---------------------------------------------------------------- registry

def test_counter_exact_accounting_under_pool_concurrency(monkeypatch):
    """The concurrency contract: 8 workers hammering one counter and one
    histogram through the SHARED pool account exactly — no lost updates."""
    monkeypatch.setenv("PARQUET_TPU_POOL_WORKERS", "8")
    monkeypatch.setattr(pool_mod, "_POOL", None)
    try:
        reg = MetricsRegistry()
        c = reg.counter("t.hammer")
        h = reg.histogram("t.hammer_s")
        per_task, tasks = 2_000, 32

        def work(i):
            for _ in range(per_task):
                c.inc()
                h.observe(1e-4 * (i + 1))

        futs = [pool_mod.submit(work, i) for i in range(tasks)]
        for f in futs:
            f.result()
        assert c.value == per_task * tasks
        assert h.count == per_task * tasks
        s = h.summary()
        assert s["count"] == per_task * tasks
        assert s["min"] == pytest.approx(1e-4)
        assert s["max"] == pytest.approx(1e-4 * tasks)
    finally:
        # the 8-wide pool must not leak into later tests on a 1-core box
        monkeypatch.setattr(pool_mod, "_POOL", None)


def test_histogram_percentiles_sane():
    h = Histogram("t.lat", buckets=tuple(i / 1000 for i in range(1, 1001)))
    for ms in range(1, 1001):  # uniform 1ms..1000ms
        h.observe(ms / 1000)
    s = h.summary()
    # fixed-bucket estimation error is bounded by one bucket width (1ms)
    assert s["p50"] == pytest.approx(0.500, abs=0.002)
    assert s["p95"] == pytest.approx(0.950, abs=0.002)
    assert s["p99"] == pytest.approx(0.990, abs=0.002)
    assert s["min"] == pytest.approx(0.001) and s["max"] == pytest.approx(1.0)
    assert s["sum"] == pytest.approx(sum(ms / 1000 for ms in range(1, 1001)))


def test_histogram_single_sample_answers_itself():
    """Clamping to observed min/max: one sample yields its own value from
    every percentile, not a bucket edge."""
    h = Histogram("t.one")
    h.observe(0.00042)
    s = h.summary()
    assert s["p50"] == s["p95"] == s["p99"] == pytest.approx(0.00042)


def test_histogram_overflow_and_cumulative_buckets():
    h = Histogram("t.over", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    bc = h.bucket_counts()
    assert bc == [(0.1, 1), (1.0, 2), (float("inf"), 4)]
    assert h.percentile(0.99) <= 50.0  # clamped to observed max


def test_registry_get_or_create_and_type_conflict():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    assert reg.counter("x", {"k": "a"}) is not reg.counter("x", {"k": "b"})
    with pytest.raises(TypeError):
        reg.gauge("x")  # same name, different type: loud, not a shadow


def test_counter_monotonic():
    c = Counter("t.mono")
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_inc_dec():
    g = Gauge("t.g")
    g.set(10); g.inc(5); g.dec(3)
    assert g.value == 12


def test_metrics_snapshot_and_delta():
    before = metrics_snapshot()
    REGISTRY.counter("t.delta_probe").inc(7)
    REGISTRY.histogram("t.delta_h").observe(0.25)
    d = metrics_delta(before, metrics_snapshot())
    assert d["counters"]["t.delta_probe"] == 7
    assert d["histograms"]["t.delta_h"]["count"] == 1
    assert d["histograms"]["t.delta_h"]["sum"] == pytest.approx(0.25)
    # zero-change counters are dropped from the delta
    assert "cache.footer_hits" not in d["counters"] or \
        d["counters"]["cache.footer_hits"] > 0


def test_core_families_predeclared():
    """`stats --prom` contract: cache/prefetch/planner/route/read/write
    families render (at 0) before any operation runs — scrapers alert on
    absence, not on zero."""
    snap = metrics_snapshot()
    for fam in ("cache.footer_hits", "cache.chunk_hits", "prefetch.hits",
                "planner.rg_pruned_stats", "read.retries",
                "write.row_groups"):
        assert fam in snap["counters"], fam
    assert 'route.chosen{route=host}' in snap["counters"]
    assert 'route.chosen{route=device}' in snap["counters"]


# ----------------------------------------------------------------- tracing

def test_disabled_tracer_allocates_nothing():
    """OFF is the production default: trace_span returns one shared
    singleton (identity-stable — no per-call span object) and records no
    events."""
    assert not obs.enabled()
    spans = {id(obs.trace_span("decode")) for _ in range(100)}
    assert spans == {id(NULL_SPAN)}
    with obs.trace_span("decode.chunk"):
        pass
    assert obs.trace_events() == []
    # the module-level gate the hot sites read directly
    from parquet_tpu.obs import trace as trace_mod
    assert trace_mod.TRACE_ENABLED is False


def test_span_records_thread_id_and_args():
    obs.enable_tracing()
    got = {}

    def worker():
        with obs.trace_span("t.work", rg=3, col="a.b"):
            got["tid"] = threading.get_ident()

    th = threading.Thread(target=worker)
    th.start(); th.join()
    with obs.trace_span("t.main"):
        pass
    obs.disable_tracing()
    evs = {e["name"]: e for e in obs.trace_events() if e["ph"] == "X"}
    assert evs["t.work"]["tid"] == got["tid"]
    assert evs["t.work"]["args"] == {"rg": 3, "col": "a.b"}
    assert evs["t.main"]["tid"] == threading.get_ident()
    assert evs["t.work"]["tid"] != evs["t.main"]["tid"]
    # while tracing, each span also feeds a latency histogram
    assert REGISTRY.histogram("span.t.work_s").count >= 1


def test_trace_buffer_bounded(monkeypatch):
    from parquet_tpu.obs import trace as trace_mod
    monkeypatch.setattr(trace_mod, "MAX_EVENTS", 8)
    obs.enable_tracing()
    before = _counter_value("trace.events_dropped")
    for _ in range(32):
        with obs.trace_span("t.flood"):
            pass
    obs.disable_tracing()
    assert len(obs.trace_events()) <= 8
    assert _counter_value("trace.events_dropped") - before >= 24


def test_trace_file_is_perfetto_loadable(tmp_path):
    """Chrome trace-event schema: a top-level traceEvents list whose "X"
    entries carry name/cat/ph/ts/dur/pid/tid with JSON-able args — the
    shape ui.perfetto.dev and chrome://tracing load directly."""
    path = tmp_path / "trace.json"
    obs.enable_tracing(path)
    with obs.trace_span("open.footer", file="f.parquet"):
        with obs.trace_span("decode.chunk", rg=0, col="a"):
            pass
    obs.disable_tracing()
    written = obs.flush_trace()
    assert written == str(path)
    body = json.loads(path.read_text())
    assert isinstance(body["traceEvents"], list) and body["traceEvents"]
    seen_meta = False
    for ev in body["traceEvents"]:
        assert ev["ph"] in ("X", "M")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
            assert ev["cat"] == ev["name"].split(".", 1)[0]
        else:
            seen_meta = True
            assert ev["name"] == "thread_name"
    assert seen_meta, "thread_name metadata labels the Perfetto tracks"
    names = {e["name"] for e in body["traceEvents"] if e["ph"] == "X"}
    assert {"open.footer", "decode.chunk"} <= names


def test_flush_without_path_returns_none():
    obs.enable_tracing()
    with obs.trace_span("t.x"):
        pass
    obs.disable_tracing()
    # no path configured in this test: nothing to write, no crash
    from parquet_tpu.obs import trace as trace_mod
    if trace_mod._TRACE_PATH is None:
        assert obs.flush_trace() is None


def _profiler_span_names(log_dir):
    """Host event names of the one ``.xplane.pb`` under ``log_dir``."""
    import glob

    import jax

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [e.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_spans_land_in_a_profiler_session(tmp_path):
    """Inside a jax.profiler session a span is a ``pq.<name>`` annotation,
    exactly (no attributes in the name), from the main thread and from a
    shared-pool worker alike."""
    import jax

    from parquet_tpu.obs import trace as trace_mod

    jax.profiler.start_trace(str(tmp_path))
    try:
        assert trace_mod.on() and not obs.enabled()
        with obs.span("t.main", rg=1):
            pass

        def worker():
            with obs.span("t.pool", col="a"):
                return threading.get_ident()

        assert pool_mod.submit(worker).result() != threading.get_ident()
    finally:
        jax.profiler.stop_trace()
    assert not trace_mod.on()
    names = _profiler_span_names(str(tmp_path))
    assert names.count("pq.t.main") == 1
    assert names.count("pq.t.pool") == 1
    assert "pq.pool.task" in names  # the pool's own span, same sink
    assert obs.trace_events() == []  # the Chrome-JSON sink stayed off


def test_span_is_null_with_both_sinks_off():
    from parquet_tpu.obs import trace as trace_mod

    assert not trace_mod.on()
    assert trace_mod.span("x") is NULL_SPAN
    assert trace_mod.span("x", col="a", rows=3) is NULL_SPAN


def test_chrome_json_names_carry_no_prefix_in_either_session(tmp_path):
    """The Chrome-JSON sink keeps the bare name; with a profiler session on
    as well, the same span also reaches the profiler as ``pq.<name>``."""
    import jax

    obs.enable_tracing()
    with obs.span("x", col="a"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("x2"):
            pass
    finally:
        jax.profiler.stop_trace()
    obs.disable_tracing()
    chrome = [e["name"] for e in obs.trace_events() if e["ph"] == "X"]
    assert chrome == ["x", "x2"]
    assert _profiler_span_names(str(tmp_path)).count("pq.x2") == 1


# ------------------------------------------------------- end-to-end traces

def test_traced_dataset_scan_acceptance(tmp_path, monkeypatch):
    """The PR's acceptance shape: one warm Dataset drain with tracing on
    yields spans from >= 4 distinct stages across >= 2 worker threads, and
    the flushed file is Perfetto-loadable."""
    monkeypatch.setenv("PARQUET_TPU_PREFETCH", "ring")
    monkeypatch.setenv("PARQUET_TPU_POOL_WORKERS", "4")
    monkeypatch.setattr(pool_mod, "_POOL", None)
    # the fan-out gates consult the core count; this box may have 1
    monkeypatch.setattr(pool_mod, "available_cpus", lambda: 8)
    try:
        for i in range(2):
            _write_file(str(tmp_path / f"f{i}.parquet"), n=200_000, seed=i)
        trace_path = tmp_path / "trace.json"
        with Dataset(str(tmp_path / "*.parquet")) as ds:
            ds.read()  # warm: footers + chunks cached
            obs.enable_tracing(trace_path)
            ds.read()
            for _ in ds.iter_batches(batch_rows=50_000):
                pass
            ds.scan("a", lo=100, hi=20_000, columns=["b"])
            obs.disable_tracing()
        obs.flush_trace()
        evs = [e for e in json.loads(trace_path.read_text())["traceEvents"]
               if e["ph"] == "X"]
        cats = {e["name"].split(".", 1)[0] for e in evs}
        assert len(cats & {"open", "decode", "scan", "prefetch", "pool",
                           "planner"}) >= 4, cats
        assert "decode" in cats and "scan" in cats, cats
        assert "prefetch" in cats, cats
        assert len({e["tid"] for e in evs}) >= 2
    finally:
        monkeypatch.setattr(pool_mod, "_POOL", None)


# -------------------------------------------------------------- prometheus

_PROM_SAMPLE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})? '
    r'(-?\d+(\.\d+)?([eE][+-]?\d+)?|\+Inf|-Inf|NaN)$')


def test_prometheus_format_lint(tmp_path):
    """Exposition-format 0.0.4 lint over real post-workload output: HELP/
    TYPE pairs precede their family's samples, every sample line parses,
    histogram buckets are cumulative and end at +Inf == _count."""
    _write_file(str(tmp_path / "p.parquet"))
    ParquetFile(str(tmp_path / "p.parquet")).read()
    text = render_prometheus()
    assert text.endswith("\n")
    lines = text.splitlines()
    typed = {}
    for ln in lines:
        if ln.startswith("# HELP "):
            continue
        if ln.startswith("# TYPE "):
            _, _, fam, typ = ln.split(" ", 3)
            assert fam not in typed, f"duplicate TYPE for {fam}"
            assert typ in ("counter", "gauge", "histogram")
            typed[fam] = typ
            continue
        assert _PROM_SAMPLE.match(ln), ln
        name = ln.split("{")[0].split(" ")[0]
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert name in typed or base in typed, f"sample before TYPE: {ln}"
        assert name.startswith("parquet_tpu_")
    # counters render as *_total; histograms carry bucket/sum/count
    assert any(f.endswith("_total") and t == "counter"
               for f, t in typed.items())
    hist_fams = [f for f, t in typed.items() if t == "histogram"]
    assert hist_fams
    for fam in hist_fams:
        # group per SERIES: a labeled histogram family (e.g.
        # serve.request_s{class=...}) renders one cumulative ladder per
        # label set — cumulativeness holds within a series, not across
        buckets = {}
        count = {}

        def series_of(ln):
            if "{" not in ln:
                return ""
            inner = ln.split("{", 1)[1].rsplit("}", 1)[0]
            return ",".join(p for p in inner.split(",")
                            if not p.startswith('le="'))

        for ln in lines:
            if ln.startswith(fam + "_bucket") and 'le="' in ln:
                buckets.setdefault(series_of(ln), []).append(
                    (ln.rsplit('le="', 1)[1].split('"')[0],
                     int(ln.rsplit(" ", 1)[1])))
            elif ln.startswith(fam + "_count"):
                count[series_of(ln)] = int(ln.rsplit(" ", 1)[1])
        if not buckets:
            continue  # label-variant family rendered elsewhere
        for series, bs in buckets.items():
            counts = [n for _, n in bs]
            assert counts == sorted(counts), \
                f"{fam}{{{series}}} buckets not cumulative"
            assert bs[-1][0] == "+Inf" and bs[-1][1] == count[series], \
                (fam, series)


def test_prometheus_required_families_after_scan(tmp_path):
    """The acceptance criterion's family list: cache/prefetch/planner/route
    counters all present in the rendered text after one warm scan."""
    for i in range(2):
        _write_file(str(tmp_path / f"f{i}.parquet"), seed=i)
    with Dataset(str(tmp_path / "*.parquet")) as ds:
        ds.scan("a", lo=10, hi=1000, columns=["b"])
        ds.scan("a", lo=10, hi=1000, columns=["b"])  # warm pass
    text = render_prometheus()
    for fam in ("parquet_tpu_cache_footer_hits_total",
                "parquet_tpu_cache_chunk_hits_total",
                "parquet_tpu_prefetch_hits_total",
                "parquet_tpu_planner_rg_considered_total",
                "parquet_tpu_route_chosen_total",
                # trace-buffer pressure + sampling decisions (ISSUE 8):
                # fleets alert on these, so they must render even at 0
                "parquet_tpu_trace_events_dropped_total",
                "parquet_tpu_trace_ops_sampled_total",
                "parquet_tpu_trace_ops_skipped_total",
                "parquet_tpu_trace_ops_slow_kept_total",
                "parquet_tpu_read_bytes_read_total"):
        assert fam in text, fam
    # the planner cascade really ran: its registry counters moved
    m = re.search(r"parquet_tpu_planner_rg_considered_total (\d+)", text)
    assert m and int(m.group(1)) > 0


def test_stats_cli(tmp_path, capsys):
    from parquet_tpu.__main__ import main
    path = str(tmp_path / "c.parquet")
    _write_file(path)
    assert main(["stats", "--prom"]) == 0
    out = capsys.readouterr().out
    assert "# TYPE parquet_tpu_cache_footer_hits_total counter" in out
    assert main(["stats", path, "--json"]) == 0
    snap = json.loads(capsys.readouterr().out)
    assert snap["histograms"]["read.file_s"]["count"] >= 1
    assert main(["stats"]) == 0
    human = capsys.readouterr().out
    assert re.search(r"^cache\.footer_hits \d+$", human, re.M)
    assert main(["stats", str(tmp_path / "nope*.parquet")]) == 1


# --------------------------------------------- legacy stats views (6 of 6)

def test_readstats_view_publishes_to_registry(tmp_path, monkeypatch):
    """View 1/6 — ReadStats: the per-drain dataclass keeps its API and its
    close-time totals land exactly once in the prefetch.* counters."""
    monkeypatch.setenv("PARQUET_TPU_PREFETCH", "ring")
    path = str(tmp_path / "r.parquet")
    _write_file(path, n=200_000)
    before = metrics_snapshot()
    pf = ParquetFile(path)
    last = None
    for last in pf.iter_batches(batch_rows=50_000):
        pass
    pf.close()
    rs = last.read_stats
    assert rs is not None and rs.windows_issued > 0  # the legacy view
    d = metrics_delta(before, metrics_snapshot())["counters"]
    assert d.get("prefetch.windows_issued", 0) == rs.windows_issued
    assert d.get("prefetch.bytes_prefetched", 0) == rs.bytes_prefetched


def test_writestats_view_publishes_to_registry(tmp_path):
    """View 2/6 — WriteStats: writer close publishes its totals once."""
    before = metrics_snapshot()
    t = _write_file(str(tmp_path / "w.parquet"), n=50_000, row_groups=2)
    d = metrics_delta(before, metrics_snapshot())["counters"]
    assert d["write.row_groups"] == 2
    assert d["write.bytes_flushed"] > 0
    assert d["write.sink_flushes"] >= 1


def test_cachestats_view_publishes_to_registry(tmp_path):
    """View 3/6 — CacheStats: the dataclass snapshot and the registry agree
    delta-for-delta across a cold+warm open pair."""
    path = str(tmp_path / "c.parquet")
    _write_file(path)
    s0, m0 = cache_stats(), metrics_snapshot()
    for _ in range(2):
        pf = ParquetFile(path)
        pf.read()
        pf.close()
    s1, m1 = cache_stats(), metrics_snapshot()
    d = metrics_delta(m0, m1)["counters"]
    assert s1.footer_hits - s0.footer_hits == d.get("cache.footer_hits", 0)
    assert s1.chunk_hits - s0.chunk_hits == d.get("cache.chunk_hits", 0) > 0
    assert s1.chunk_misses - s0.chunk_misses == d.get("cache.chunk_misses", 0)
    assert m1["gauges"]["cache.chunk_entries"] == s1.chunk_entries


def test_readreport_view_publishes_to_registry():
    """View 4/6 — ReadReport: record sites publish, merge() does NOT
    re-record (totals stay exact when sub-reports fold in)."""
    before = metrics_snapshot()
    r = ReadReport()
    r.record_skip(2, rows=100, error=ValueError("x"))
    r.record_file_skip("/p.parquet", rows=50, error=OSError("y"))
    sub = ReadReport()
    sub.record_skip(0, rows=25, error=ValueError("z"))
    r.merge(sub)
    assert r.rows_dropped == 175  # the legacy view
    d = metrics_delta(before, metrics_snapshot())["counters"]
    assert d["read.rows_dropped"] == 175
    assert d["read.row_groups_skipped"] == 2
    assert d["read.files_skipped"] == 1


def test_scratch_report_publishes_exactly_once():
    """The device-attempt scratch path: a non-publishing report's record
    sites touch nothing (a refusal fallback re-records via the host scan),
    and publish_skips() lands the totals in one shot when the attempt's
    result is kept — never both."""
    before = metrics_snapshot()
    scratch = ReadReport()
    scratch._publish = False
    scratch.record_skip(0, rows=10, error=ValueError("x"))
    d = metrics_delta(before, metrics_snapshot())["counters"]
    assert "read.rows_dropped" not in d and "read.row_groups_skipped" not in d
    scratch.publish_skips()
    d = metrics_delta(before, metrics_snapshot())["counters"]
    assert d["read.rows_dropped"] == 10
    assert d["read.row_groups_skipped"] == 1


def test_planner_counters_publish_to_registry(tmp_path):
    """View 5/6 — planner cascade counters: ScanPlan.counters stays the
    per-plan view; the registry accumulates the same totals."""
    from parquet_tpu import col
    path = str(tmp_path / "pl.parquet")
    _write_file(path, n=80_000, row_groups=8)
    before = metrics_snapshot()
    pf = ParquetFile(path)
    plan = ScanPlanner(pf).plan(col("a").between(0, 5000))
    pf.close()
    assert plan.counters["rg_total"] == 8
    d = metrics_delta(before, metrics_snapshot())["counters"]
    # the plan's rg_total key publishes as planner.rg_considered (the
    # Prometheus renderer appends _total to counters)
    assert d.get("planner.rg_considered", 0) == plan.counters["rg_total"]
    for k in ("rg_pruned_stats", "rg_survivors", "stats_probes"):
        if plan.counters.get(k):
            assert d.get("planner." + k, 0) == plan.counters[k], k


def test_routehistory_pool_wait_discounts_effective_gbps():
    """View 6/6 — RouteHistory (+ the satellite): pool saturation discounts
    a route's effective GB/s; with no waits reported the historical
    behavior is byte-for-byte unchanged."""
    h = RouteHistory(alpha=1.0)
    nb = 1 << 30
    h.observe("host", nbytes=nb, seconds=1.0)
    assert h.gbps("host") == pytest.approx(nb / 1e9)  # no-wait: unchanged
    h.observe("host", nbytes=nb, seconds=1.0, pool_wait_s=0.4)
    assert h.gbps("host") == pytest.approx(nb / 1e9 * 0.6)
    # saturation beyond wall clock clamps (a 8-wide pool can wait > wall)
    h.observe("host", nbytes=nb, seconds=1.0, pool_wait_s=10.0)
    assert h.gbps("host") == pytest.approx(nb / 1e9 * 0.05)
    assert h.observations("host") == 3
    g = REGISTRY.gauge("route.gbps", {"route": "host"})
    assert g.value == pytest.approx(round(nb / 1e9 * 0.05, 4))
    h.reset()
    assert h.gbps("host") is None


def test_scan_feeds_pool_wait_into_route_history(tmp_path, monkeypatch):
    """The scan router hands pool_wait_seconds() deltas to observe() — the
    route.observations counter moves with a real routed scan.  The CPU
    backend short-circuits to host with est_bytes=0 (nothing to learn), so
    the device pin drives the full cost-model path here."""
    from parquet_tpu import scan
    from parquet_tpu.io.planner import route_history
    monkeypatch.setenv("PARQUET_TPU_ROUTE", "device")
    path = str(tmp_path / "rt.parquet")
    # large enough to clear the tiny-scan EWMA floor (est_bytes >= 4 MiB)
    _write_file(path, n=1_500_000, row_groups=2)
    route_history().reset()
    before = metrics_snapshot()
    pf = ParquetFile(path)
    scan(pf, "a", lo=0, hi=1_400_000)
    pf.close()
    d = metrics_delta(before, metrics_snapshot())["counters"]
    assert d.get("route.chosen{route=device}", 0) >= 1
    assert route_history().observations("device") >= 1
    assert route_history().gbps("device") is not None
    route_history().reset()


def test_pool_wait_seconds_sums_queue_and_prefetch():
    """Both components are the LIVE meters (per-wait observations), so a
    delta window only sees waits that happened inside it — the close-time
    prefetch.pool_wait_s counter must NOT feed this."""
    before = obs.pool_wait_seconds()
    REGISTRY.histogram("pool.queue_wait_s").observe(0.125)
    REGISTRY.histogram("prefetch.wait_s").observe(0.25)
    assert obs.pool_wait_seconds() - before == pytest.approx(0.375)
    REGISTRY.counter("prefetch.pool_wait_s").inc(1.0)  # close-time total
    assert obs.pool_wait_seconds() - before == pytest.approx(0.375)


def test_dataset_latency_histograms(tmp_path):
    """Satellite: Dataset.read/scan land whole-operation and per-file
    latencies so metrics_snapshot() answers p50/p99 per operation."""
    for i in range(2):
        _write_file(str(tmp_path / f"f{i}.parquet"), seed=i)
    before = metrics_snapshot()
    with Dataset(str(tmp_path / "*.parquet")) as ds:
        ds.read()
        ds.scan("a", lo=5, hi=500)
    d = metrics_delta(before, metrics_snapshot())["histograms"]
    assert d["dataset.read_s"]["count"] == 1
    assert d["dataset.scan_s"]["count"] == 1
    assert d["dataset.scan_file_s"]["count"] == 2
    assert d["read.file_s"]["count"] == 2
    assert d["dataset.read_s"]["p99"] is not None
