"""query_ms: window milliseconds per completed query."""

from lib.readers import query_ms as read  # noqa: F401
