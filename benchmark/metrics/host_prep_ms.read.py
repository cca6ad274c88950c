"""host_prep_ms.read: pq.prepare_chunk* wall time per read (ms)."""

from lib.readers import host_prep_ms_read as read  # noqa: F401
