"""decompress_ms.read: pq.decompress wall time per read (ms)."""

from lib.span_readers import decompress_ms_read as read  # noqa: F401
