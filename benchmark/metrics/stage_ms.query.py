"""stage_ms.query: pq.stage_scan self time (less pq.planner.plan) per query (ms)."""

from lib.span_readers import stage_ms_query as read  # noqa: F401
