"""The trace reduction, on synthetic device events and on a small trace
recorded here on the CPU (which has no TPU plane: its device side reads
empty, and nothing from it is a device number)."""

import jax
import jax.numpy as jnp
import pytest

from lib import traces


def _events():
    host = [("bench.window", 0, 100), ("bench.request", 0, 100),
            ("pq.prepare_chunks_batched", 20, 45),
            ("pq.prepare_chunk", 22, 30), ("other", 60, 100)]
    dev = {"ops": [("a", 5, 10), ("b", 8, 20), ("c", 50, 60),
                   ("d", 95, 130)],
           "modules": [("jit_x(1)", 5, 20), ("jit_y(2)", 50, 60),
                       ("jit_x(3)", 95, 130)]}
    return {"host": host, "devices": {"/device:TPU:0": dev,
                                      "/device:TPU:1": {"ops": [],
                                                        "modules": []}}}


def test_union_clip_gaps():
    assert traces.union([(8, 20), (5, 10), (50, 60), (3, 3)]) == [
        [5, 20], [50, 60]]
    assert traces.clip([(-5, 10), (95, 130), (200, 300)], 0, 100) == [
        (0, 10), (95, 100)]
    assert traces.gaps([[5, 20], [50, 60]], 0, 100) == [
        (0, 5), (20, 50), (60, 100)]
    assert traces.covered_ns([(0, 10), (5, 15), (20, 25)]) == 20.0


def test_label_gap_prefers_the_innermost_covering_annotation():
    host = _events()["host"]
    # (20, 50): prepare_chunks_batched covers 25 of 30 ns, the request all
    assert traces.label_gap((20, 50), host) == "pq.prepare_chunks_batched"
    assert traces.label_gap((22, 30), host) == "pq.prepare_chunk"
    # only the request and an unlabelled host event are open
    assert traces.label_gap((60, 95), host) == "bench.request"
    assert traces.label_gap((60, 95), [("other", 0, 200)]) == "host:none"


def test_reduce_busy_idle_ops_and_gaps():
    r = traces.reduce(_events())
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["devices"] == 2
    # device 0: [5,20] + [50,60] + [95,100] = 30 ns; device 1 idle
    assert r["busy_s_sum"] == pytest.approx(30e-9)
    assert r["busy_s"] == pytest.approx(15e-9)
    ops = dict((k, v) for k, v in r["device_ops"])
    assert ops == pytest.approx({"jit_x": 20e-9, "jit_y": 10e-9})
    longest = r["idle_gaps"][0]
    # device 1 idles throughout; the window itself names no gap
    assert longest == ["bench.request", pytest.approx(100e-9)]
    assert ["pq.prepare_chunks_batched", pytest.approx(30e-9)] in \
        r["idle_gaps"]


def test_host_spans_prefix_and_window():
    ev = _events()
    spans = traces.host_spans(ev, ["pq.prepare_chunk*"], (0, 25))
    assert sorted(spans) == [(20, 25), (22, 25)]
    assert traces.covered_ns(spans) == 5.0


def test_recorded_cpu_trace(tmp_path):
    """A real .xplane.pb from this CPU: the window and the program-style
    annotations come back on the host side."""
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    traces.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(traces.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("pq.prepare_chunk"):
                y = f(x)
            y.block_until_ready()
    traces.stop()
    ev = traces.load(traces.find_xplane(str(tmp_path)))
    lo, hi = traces.window_of(ev["host"])
    assert hi > lo
    prep = traces.host_spans(ev, ["pq.prepare_chunk"], (lo, hi))
    assert len(prep) == 3
    assert ev["devices"] == {}  # the CPU has no TPU plane
    r = traces.reduce(ev)
    assert r["devices"] == 0 and r["busy_s"] == 0.0
