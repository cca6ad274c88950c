"""plan_ms.query: pq.route and pq.planner.plan wall time per query (ms)."""

from lib.span_readers import plan_ms_query as read  # noqa: F401
