"""``scan_compact_roofline.query`` and the counters it reads: the reader on
synthetic module intervals, and the program's counting on the CPU (the
kernel in interpret mode; nothing here is a device number)."""

import io
from types import SimpleNamespace

import numpy as np
import pytest

from lib import common

WINDOW = (0, 1000)
PEAKS = {"hbm_bytes_per_s": 1e9}


def _read(modules, counters):
    ctx = SimpleNamespace(
        events={"host": [("bench.window",) + WINDOW],
                "devices": {"/device:TPU:0": {"ops": [], "modules": modules}}},
        reduced={"window_ns": WINDOW}, counters=counters, completed=2,
        peaks=PEAKS)
    return common.load_module("metrics/scan_compact_roofline.query.py").read(
        ctx)


def test_roofline_reads_the_counter_over_the_kernels_time():
    modules = [("jit_scan_compact(7)", 100, 300),
               ("jit_scatter(2)", 300, 900),
               ("jit_scan_compact(7)", 900, 1100)]  # half inside the window
    # 75 B at 1 GB/s is 75 ns of the 300 ns the kernel ran
    assert _read(modules, {"kernel_bytes.scan_compact": 75}) == \
        pytest.approx(25.0)


def test_roofline_reads_nothing_without_the_counter_or_the_module():
    """A program that compacts by scatter has neither."""
    assert _read([("jit_scan_compact(7)", 100, 300)], {}) is None
    assert _read([("jit_scatter(2)", 100, 900)],
                 {"kernel_bytes.scan_compact": 75}) is None


def test_only_the_eager_program_counts():
    """The eager scan counts (4 W + 1) n bytes and one run a span; the
    fused span program (a reused staged state) inlines the kernel into its
    own jit, whose time ``jit_scan_compact`` does not show, and adds
    nothing."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from parquet_tpu import ParquetFile, counters
    from parquet_tpu.parallel.host_scan import decoded_scan, stage_scan

    buf = io.BytesIO()
    pq.write_table(pa.table({"k": np.arange(3000, dtype=np.int32),
                             "v": np.arange(3000, dtype=np.float64)}),
                   buf, row_group_size=1000, use_dictionary=False)
    state = stage_scan(ParquetFile(buf.getvalue()), "k", 100, 2500,
                       columns=["v"])
    rows = [plan.row_count for plan, _, _ in state["spans"]]
    keys = ("kernel_bytes.scan_compact", "kernel_runs.scan_compact")

    def counted():
        before = counters.snapshot()
        out = decoded_scan(state)
        after = counters.snapshot()
        return out, [after.get(k, 0) - before.get(k, 0) for k in keys]

    eager, got = counted()
    assert got == [(4 * 2 + 1) * sum(rows), len(rows)]  # W = 2: one pair
    fused, got = counted()
    assert got == [0, 0]
    want = np.arange(100, 2501, dtype=np.float64)
    for out in (eager, fused):
        np.testing.assert_array_equal(
            np.asarray(out["v"]).view(np.float64).ravel(), want)
