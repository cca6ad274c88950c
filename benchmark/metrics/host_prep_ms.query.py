"""host_prep_ms.query: scan entry to first device decode, per query (ms)."""

from lib.readers import host_prep_ms_query as read  # noqa: F401
