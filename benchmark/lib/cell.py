"""One run of one cell: data, warm-up, the measured window, the traced
window's reduction, and the comparison that decides ``correct``.

Everything the cell needs is found by name: the configuration file and its
generator (``gens/<generator>.py``), the traffic file and its kind
(``kinds/<kind>.py``), and one reader file per metric (``e2e/<name>.py``,
``metrics/<name>.py``)."""

import gc
import json
import shutil
import tempfile
import time
from types import SimpleNamespace

from lib import common, traces
from lib.common import say


def write_parquet(groups, cfg) -> bytes:
    """The configuration's writer, one row group per table of ``groups``,
    into memory: a file in the page cache without the disk writes (the
    program reads both zero-copy)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    w = cfg["writer"]
    if w["library"] != "pyarrow":
        raise SystemExit(f"benchmark: writer {w['library']!r} is unknown")
    sink = pa.BufferOutputStream()
    with pq.ParquetWriter(sink, groups[0].schema,
                          compression=w["compression"],
                          use_dictionary=w["use_dictionary"],
                          write_page_index=w["write_page_index"]) as writer:
        for group in groups:
            writer.write_table(group, row_group_size=group.num_rows)
    return sink.getvalue().to_pybytes()


def uncompressed_bytes(data: bytes) -> int:
    """The footer's ``total_uncompressed_size`` over every column chunk."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    md = pq.ParquetFile(pa.BufferReader(data)).metadata
    return sum(md.row_group(g).column(c).total_uncompressed_size
               for g in range(md.num_row_groups)
               for c in range(md.num_columns))


def metric_entries(bench, cell, section):
    """The metrics of ``section`` that this cell reports."""
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif section == "end_to_end":
            out.append(m)
        else:  # a per-layer metric without the key: every cell of its moves
            moved = {e["name"]: e for e in bench["end_to_end"]}[m["moves"]]
            if "workloads" not in moved or cell["name"] in moved["workloads"]:
                out.append(m)
    return out


def run(workload, seed, seconds, trace, *, t_process, need_tpu=True,
        control=False, cfg_override=None, patch=None):
    """One run; returns the result line as a dict (``checks`` last)."""
    bench, cell, cfg, traffic = common.find_cell(workload)
    cfg = dict(cfg, **(cfg_override or {}))
    common.use_cache_in_checkout()
    import jax
    import pyarrow as pa

    devices, peaks = common.devices_for(cell["chips"], need_tpu)
    from parquet_tpu import counters, native

    if native.get_lib() is None:
        raise SystemExit(f"benchmark: native shim unavailable: "
                         f"{native.build_error}")
    ctx = SimpleNamespace()  # what the traffic kinds and readers see
    ctx.cell, ctx.cfg, ctx.traffic = cell, cfg, traffic
    ctx.seed, ctx.control, ctx.devices, ctx.peaks = seed, control, devices, peaks
    compiles = common.Compiles()

    t = time.perf_counter()
    gen = common.load_module(f"gens/{cfg['generator']}.py")
    groups = gen.build(cfg, seed)
    ctx.data = write_parquet(groups, cfg)
    ctx.table = pa.concat_tables(groups)
    ctx.arrow_bytes = ctx.table.nbytes
    ctx.uncompressed_bytes = uncompressed_bytes(ctx.data)
    say(f"data: {ctx.table.num_rows} rows, {ctx.arrow_bytes} Arrow bytes, "
        f"{len(ctx.data)} file bytes, {ctx.uncompressed_bytes} uncompressed"
        f" page bytes, {time.perf_counter() - t:.3f}s")

    kind = common.load_module(f"kinds/{traffic['kind']}.py").Traffic(ctx)
    ctx.kind_work_bytes = kind.work_bytes
    if patch is not None:  # tests break the timed path here
        patch(kind)
    t = time.perf_counter()
    n_warm, hits = len(compiles.events), compiles.cache_hits
    kind.warm()
    say(f"warm-up: {time.perf_counter() - t:.3f}s, "
        f"{len(compiles.events) - n_warm} programs, "
        f"{compiles.cache_hits - hits} of them from the cache")

    noise = common.PackageNoise()
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        traces.start(tdir)
    before = counters.snapshot()
    latencies, infos, errors = [], [], []
    failed = 0
    t_start = time.perf_counter()
    ctx.setup_s = t_start - t_process
    deadline = t_start + seconds
    with jax.profiler.TraceAnnotation(traces.WINDOW):
        i = 0
        while True:
            f0, n0 = common.failure_counts(), len(noise.seen)
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("bench.request"):
                    infos.append(kind.request(i))
            except Exception as e:  # counted, and the run is not correct
                errors.append(f"request {i}: {type(e).__name__}: {e}")
                infos.append(None)
            t1 = time.perf_counter()
            latencies.append(t1 - t0)
            if (infos[-1] is None or infos[-1].get("failed")
                    or common.failure_counts() != f0
                    or len(noise.seen) != n0):
                failed += 1
            i += 1
            if t1 >= deadline:
                break
    t_end = time.perf_counter()
    if trace:
        traces.stop()
    ctx.window_s = t_end - t_start
    ctx.attempted = len(latencies)
    ctx.completed = sum(x is not None for x in infos)
    ctx.latencies = [x for x, info in zip(latencies, infos) if info is not None]
    ctx.infos = [x for x in infos if x is not None]
    after = counters.snapshot()
    ctx.counters = {k: after.get(k, 0) - before.get(k, 0)
                    for k in set(after) | set(before)}
    ctx.compiles_in_window = compiles.between(t_start, t_end)
    for t_done, sec, fn in ctx.compiles_in_window:
        say(f"compile inside the window: {sec:.3f}s {fn}")
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               for d in devices) if need_tpu else 0
    say(f"window: {ctx.attempted} requests, {failed} failed, "
        f"{ctx.window_s:.3f}s")
    for line in noise.seen[:5] + errors[:5]:
        say(f"failure: {line}")

    result = {"correct": False, "attempted": ctx.attempted, "failed": failed,
              "metrics": {}, "device": {
                  "platform": devices[0].platform,
                  "kind": devices[0].device_kind,
                  "count": len(devices), "memory_peak_bytes": peak}}
    if trace:
        ctx.events = traces.load(traces.find_xplane(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        ctx.reduced = traces.reduce(ctx.events)
        result["device"]["busy_s"] = ctx.reduced["busy_s"]
        result["device"]["window_s"] = ctx.reduced["window_s"]
        result["breakdown"] = {"device_ops": ctx.reduced["device_ops"],
                               "idle_gaps": ctx.reduced["idle_gaps"]}
        section, folder = "per_layer", "metrics"
    else:
        section, folder = "end_to_end", "e2e"
    for m in metric_entries(bench, cell, section):
        reader = common.load_module(f"{folder}/{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            say(f"metric {m['name']}: nothing to read")

    # the reference runs once the window has closed, the peak is read and
    # the program's state is freed
    kind.release()
    gc.collect()
    t = time.perf_counter()
    got = kind.check(control)
    checks = {k: {"value": v, "limit": traffic["limits"][k]}
              for k, v in got.items()}
    say(f"reference comparison: {time.perf_counter() - t:.3f}s")
    result["correct"] = (not errors and all(
        c["value"] <= c["limit"] for c in checks.values()))
    result["checks"] = checks
    for k, c in checks.items():
        say(f"check {k} = {c['value']!r} (limit {c['limit']!r})")
    return result


def emit(result) -> None:
    print(json.dumps(result), flush=True)
