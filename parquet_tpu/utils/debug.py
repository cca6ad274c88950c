"""Debug counters and the profiler-trace region.

Lightweight always-on counters (chunks decoded, bytes H2D, kernel bytes)
exported as ``parquet_tpu.counters``; spans go through
:func:`parquet_tpu.obs.trace.span`.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Optional

from .env import env_str
from .locks import make_lock


class Counters:
    """Thread-safe named counters; cheap when unused."""

    def __init__(self):
        self._lock = make_lock("debug.counters")
        self._counts = defaultdict(int)

    def inc(self, name: str, by: int = 1) -> None:
        with self._lock:
            self._counts[name] += by

    def high_water(self, name: str, value: int) -> None:
        """Record a peak (e.g. concurrent staging threads)."""
        with self._lock:
            if value > self._counts.get(name, 0):
                self._counts[name] = value

    def get(self, name: str) -> int:
        return self._counts.get(name, 0)

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._counts)

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()


counters = Counters()


def profiler_trace(out_dir: Optional[str] = None):
    """Context manager: capture a ``jax.profiler`` trace (Perfetto/XPlane)
    around a decode/scan region — SURVEY.md §5's jax.profiler + Perfetto
    integration.  ``out_dir`` defaults to $PARQUET_TPU_TRACE_DIR; when
    neither is set the context is a no-op, so call sites can wrap hot
    regions unconditionally.

    Usage::

        with profiler_trace("/tmp/pq_trace"):
            table = pf.read(device=True)
        # then: load the xplane/trace.json.gz in Perfetto or TensorBoard
    """
    import contextlib

    out_dir = out_dir or env_str("PARQUET_TPU_TRACE_DIR") or None
    if not out_dir:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.trace(out_dir)

