"""The arithmetic behind the metric files.  Each file under ``e2e/`` and
``metrics/`` names one of these as its ``read``; a reader that finds nothing
to read returns ``None`` and the harness leaves the metric out."""

from lib import traces

# --------------------------------------------------------------- end to end


def setup_s(ctx):
    """Process start to the first timed request."""
    return ctx.setup_s


def read_gbps(ctx):
    """Arrow bytes of every read completed in the window over its seconds
    (decimal GB)."""
    if ctx.kind_work_bytes is None:
        return None
    return ctx.completed * ctx.kind_work_bytes / ctx.window_s / 1e9


def query_ms(ctx):
    """Window milliseconds per query completed."""
    return ctx.window_s * 1000.0 / ctx.completed if ctx.completed else None


# ---------------------------------------------------------------- per layer


def host_prep_ms_read(ctx):
    """Wall time covered by ``pq.prepare_chunk*`` annotations, per read."""
    spans = traces.host_spans(ctx.events, ["pq.prepare_chunk",
                                           "pq.prepare_chunks_batched"],
                              ctx.reduced["window_ns"])
    if not spans or not ctx.completed:
        return None
    return traces.covered_ns(spans) / 1e6 / ctx.completed


def host_prep_ms_query(ctx):
    """Per query, from the scan's entry to its first ``pq.decode_staged``
    (the whole scan when the host route decodes nothing on the device):
    planning, pread, decompress, prescan and H2D enqueue."""
    window = ctx.reduced["window_ns"]
    scans = traces.host_spans(ctx.events, ["bench.scan"], window)
    decodes = sorted(s for s, _ in traces.host_spans(
        ctx.events, ["pq.decode_staged:*"], window))
    if not scans:
        return None
    total = 0.0
    for s, e in scans:
        inside = [d for d in decodes if s <= d <= e]
        total += (inside[0] if inside else e) - s
    return total / 1e6 / len(scans)


def h2d_per_decoded(ctx):
    """``bytes_h2d`` over the decoded Arrow bytes of the window's reads."""
    h2d = ctx.counters.get("bytes_h2d", 0)
    if not h2d or not ctx.completed or ctx.kind_work_bytes is None:
        return None
    return h2d / (ctx.completed * ctx.kind_work_bytes)


def device_route_pct(ctx):
    """Share of queries whose scan took the device route."""
    routes = [i.get("route") for i in ctx.infos if i.get("route")]
    if not routes:
        return None
    return 100.0 * routes.count("device") / len(routes)


def decode_roofline(ctx):
    """(Footer uncompressed bytes + decoded Arrow bytes) per read, times
    the reads, at the HBM peak, over the devices' summed busy time: both
    byte counts are fixed by the data, not by the code."""
    busy = ctx.reduced["busy_s_sum"]
    if busy <= 0 or not ctx.completed or ctx.peaks is None:
        return None
    need = ctx.completed * (ctx.uncompressed_bytes + ctx.arrow_bytes)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / busy


def device_idle_pct(ctx):
    """100 x (1 - device busy / traced window), averaged over devices."""
    r = ctx.reduced
    if not r["devices"] or r["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])


def window_compiles(ctx):
    """XLA compiles that ended inside the window."""
    return len(ctx.compiles_in_window)
