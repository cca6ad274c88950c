"""The reduction from a profiler trace (``.xplane.pb``) to numbers.

Device busy time is the union of the op intervals on each TPU plane's
"XLA Ops" line, clipped to the window, which is the benchmark's own
``bench.window`` host annotation on the same clock.  An idle gap inside the
window is named by the host annotation open during it.  The reduction is
kept as plain functions over ``(name, start_ns, end_ns)`` tuples so that a
test can check it without a chip (``tests/test_traces.py``)."""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW = "bench.window"
# host events that can name an idle gap: the program's annotations and the
# benchmark's own; the window itself names nothing
LABEL_PREFIXES = ("pq.", "bench.")


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # a Python tracer would slow host prep
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str) -> dict:
    """Host events (every host thread) and, per TPU plane, its ops and its
    XLA modules, each as ``(name, start_ns, end_ns)``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    host, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events)
        elif DEVICE_PLANE.match(plane.name):
            lines = {line.name: [(e.name, e.start_ns, e.end_ns)
                                 for e in line.events]
                     for line in plane.lines}
            devices[plane.name] = {"ops": lines.get("XLA Ops", []),
                                   "modules": lines.get("XLA Modules", [])}
    return {"host": host, "devices": devices}


def union(intervals):
    """Sorted, merged ``[start, end]`` pairs."""
    merged = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered_ns(intervals) -> float:
    return float(sum(e - s for s, e in union(intervals)))


def gaps(busy, lo, hi):
    """The idle stretches of ``[lo, hi]`` between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label_gap(gap, host):
    """The host annotation open during an idle gap: of those that cover at
    least half of it, the innermost (shortest); else the one that covers
    most of it; else ``host:none``."""
    s, e = gap
    best, best_cover = None, 0.0
    half = []
    for name, hs, he in host:
        if name == WINDOW or not name.startswith(LABEL_PREFIXES):
            continue
        cover = min(e, he) - max(s, hs)
        if cover <= 0:
            continue
        if cover * 2 >= e - s:
            half.append((he - hs, name))
        if cover > best_cover:
            best, best_cover = name, cover
    if half:
        return min(half)[1]
    return best or "host:none"


def window_of(host):
    spans = [(s, e) for name, s, e in host if name == WINDOW]
    if not spans:
        raise RuntimeError(f"trace has no {WINDOW!r} annotation")
    return spans[0]


def strip_id(name: str) -> str:
    """``jit_decode(123)`` and ``jit_decode(456)`` are one program."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(events: dict, top: int = 10) -> dict:
    """Busy and idle per device inside the window, the device programs that
    took most time, and the longest idle gaps named by the host."""
    host = events["host"]
    lo, hi = window_of(host)
    busy_ns, by_name, all_gaps = [], {}, []
    for dev in events["devices"].values():
        ops = clip([(s, e) for _, s, e in dev["ops"]], lo, hi)
        merged = union(ops)
        busy_ns.append(sum(e - s for s, e in merged))
        named = dev["modules"] or dev["ops"]
        for name, s, e in named:
            part = min(e, hi) - max(s, lo)
            if part > 0:
                key = strip_id(name)
                by_name[key] = by_name.get(key, 0.0) + part / 1e9
        all_gaps += gaps(merged, lo, hi)
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    labels = [h for h in host if h[0] != WINDOW
              and h[0].startswith(LABEL_PREFIXES)]
    labelled = [((e - s) / 1e9, label_gap((s, e), labels))
                for s, e in longest]
    n = max(len(busy_ns), 1)
    return {
        "window_s": (hi - lo) / 1e9,
        "window_ns": (lo, hi),
        "devices": len(busy_ns),
        "busy_s": sum(busy_ns) / n / 1e9,
        "busy_s_sum": sum(busy_ns) / 1e9,
        "device_ops": sorted(([k, v] for k, v in by_name.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[label, sec] for sec, label in labelled],
    }


def host_spans(events: dict, names, window) -> list:
    """Host annotations with one of ``names`` (a prefix ends in ``*``),
    clipped to the window."""
    lo, hi = window

    def match(n):
        return any(n.startswith(p[:-1]) if p.endswith("*") else n == p
                   for p in names)

    return clip([(s, e) for n, s, e in events["host"] if match(n)], lo, hi)
