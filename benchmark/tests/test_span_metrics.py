"""The readers of the program's spans and kernel byte counters, on
synthetic ``(name, start_ns, end_ns)`` events, and in a traced run of each
kind on the CPU (which has no TPU plane: the rooflines read nothing there,
and nothing here is a device number)."""

import time
from types import SimpleNamespace

import pytest

from lib import cell, common

WINDOW = (0, 1000)


def _ctx(host, devices=None, counters=None, completed=2, peaks=None):
    host = [("bench.window",) + WINDOW] + host
    return SimpleNamespace(
        events={"host": host, "devices": devices or {}},
        reduced={"window_ns": WINDOW}, counters=counters or {},
        completed=completed, peaks=peaks)


def _read(metric, ctx):
    return common.load_module(f"metrics/{metric}.py").read(ctx)


def test_plan_ms_is_the_union_of_route_and_planner_spans():
    host = [("pq.route", 100, 200), ("pq.planner.plan", 150, 180),
            ("pq.planner.plan", 400, 500),  # pruning inside staging
            ("pq.stage_scan", 300, 700),
            ("pq.route", 990, 1100)]  # clipped at the window's end
    # union [100,200] + [400,500] + [990,1000] = 210 ns over 2 queries
    assert _read("plan_ms.query", _ctx(host)) == pytest.approx(105e-6)


def test_stage_ms_is_staging_self_time():
    host = [("pq.route", 100, 200), ("pq.planner.plan", 150, 180),
            ("pq.stage_scan", 300, 700), ("pq.planner.plan", 400, 500),
            ("pq.decompress", 550, 600),  # inside staging: still staging
            ("pq.stage_scan", 650, 800)]  # overlaps the first (threads)
    # staging covers [300,800] = 500, less the planner's [400,500]
    ctx = _ctx(host, completed=4)  # two host-route queries count too
    assert _read("stage_ms.query", ctx) == pytest.approx(400 / 1e6 / 4)


def test_decompress_ms_counts_overlapping_threads_once():
    host = [("pq.decompress", 10, 30), ("pq.decompress", 20, 40),
            ("pq.decompress", 500, 510), ("pq.decompressor", 0, 999)]
    assert _read("decompress_ms.read", _ctx(host)) == pytest.approx(
        (30 + 10) / 1e6 / 2)


def test_readers_find_nothing_in_a_program_without_the_spans():
    host = [("pq.prepare_chunk", 0, 500), ("bench.scan", 0, 900)]
    for metric in ("plan_ms.query", "stage_ms.query", "decompress_ms.read",
                   "rle_expand_roofline.read",
                   "fixed64_pairs_roofline.read"):
        assert _read(metric, _ctx(host, peaks={"hbm_bytes_per_s": 1e9})) \
            is None, metric


def test_kernel_rooflines():
    devices = {
        "/device:TPU:0": {"ops": [], "modules": [
            ("jit_rle_expand(12)", 100, 300),
            ("jit_fixed64_pairs(3)", 300, 400),
            ("jit_rle_expand(12)", 950, 1050)]},  # half inside the window
        "/device:TPU:1": {"ops": [], "modules": [
            ("jit_rle_expand(40)", 0, 50)]}}
    counters = {"kernel_bytes.rle_expand": 300,
                "kernel_bytes.fixed64_pairs": 50}
    ctx = _ctx([], devices, counters, peaks={"hbm_bytes_per_s": 1e9})
    # rle_expand: 200 + 50 + 50 ns over both devices; 300 B at 1 GB/s is
    # 300 ns: 100%
    assert _read("rle_expand_roofline.read", ctx) == pytest.approx(100.0)
    # fixed64_pairs: 50 B is 50 ns of the 100 ns it ran
    assert _read("fixed64_pairs_roofline.read", ctx) == pytest.approx(50.0)
    ctx.peaks = None
    assert _read("rle_expand_roofline.read", ctx) is None


LINEITEM = {"rows": 24_000, "row_group_rows": 4_000}
SEED = 2**31 + 777


@pytest.mark.parametrize("workload,metrics", [
    ("lineitem_sf1.full_read", ["decompress_ms.read"]),
    ("lineitem_sf1.q6_power", ["plan_ms.query", "stage_ms.query"]),
])
def test_traced_cpu_run_reports_the_span_metrics(workload, metrics,
                                                 monkeypatch):
    # the CPU router sends every scan to the host: pin the device route
    monkeypatch.setenv("PARQUET_TPU_ROUTE", "device")
    r = cell.run(workload, SEED, 0.5, 1, t_process=time.perf_counter(),
                 need_tpu=False, cfg_override=LINEITEM)
    assert r["correct"], r["checks"]
    for m in metrics:
        assert r["metrics"][m]["value"] > 0, m
