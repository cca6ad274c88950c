"""The arithmetic behind the metric files that read the program's own
spans (``pq.<name>`` annotations of ``parquet_tpu.obs.trace.span``) and its
``kernel_bytes.*`` counters.  A program without those spans or counters
leaves the reader nothing to read: it returns ``None`` and the harness
leaves the metric out."""

from lib import traces


def _spans(ctx, names):
    return traces.host_spans(ctx.events, names, ctx.reduced["window_ns"])


def plan_ms_query(ctx):
    """Wall time covered by ``pq.route`` or ``pq.planner.plan``, per query
    completed: the route choice and every pruning plan."""
    spans = _spans(ctx, ["pq.route", "pq.planner.plan"])
    if not spans or not ctx.completed:
        return None
    return traces.covered_ns(spans) / 1e6 / ctx.completed


def stage_ms_query(ctx):
    """Wall time covered by ``pq.stage_scan`` and not by ``pq.planner.plan``
    (the staging phase's self time: pread, decompress, prescan, H2D
    enqueue), per query completed, host-route queries included."""
    stage = _spans(ctx, ["pq.stage_scan"])
    if not stage or not ctx.completed:
        return None
    plan = _spans(ctx, ["pq.planner.plan"])
    # staging less planning: what the union gains over planning alone
    self_ns = traces.covered_ns(stage + plan) - traces.covered_ns(plan)
    return self_ns / 1e6 / ctx.completed


def decompress_ms_read(ctx):
    """Wall time covered by ``pq.decompress`` (host codec work), per read."""
    spans = _spans(ctx, ["pq.decompress"])
    if not spans or not ctx.completed:
        return None
    return traces.covered_ns(spans) / 1e6 / ctx.completed


def module_seconds(ctx, jit_name: str) -> float:
    """In-window seconds of the XLA modules named ``jit_name``, summed over
    the devices."""
    lo, hi = ctx.reduced["window_ns"]
    total = 0
    for dev in ctx.events["devices"].values():
        for name, s, e in dev["modules"]:
            if traces.strip_id(name) == jit_name:
                total += max(0, min(e, hi) - max(s, lo))
    return total / 1e9


def kernel_roofline(ctx, kernel: str):
    """100 x the kernel's logical bytes in the window (its
    ``kernel_bytes.<kernel>`` counter) at the HBM peak, over the in-window
    time of its ``jit_<kernel>`` modules."""
    need = ctx.counters.get("kernel_bytes." + kernel, 0)
    if not need or ctx.peaks is None:
        return None
    busy = module_seconds(ctx, "jit_" + kernel)
    if busy <= 0:
        return None
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / busy
