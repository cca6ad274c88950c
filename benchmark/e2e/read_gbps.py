"""read_gbps: Arrow bytes of the window's completed reads per second (GB/s)."""

from lib.readers import read_gbps as read  # noqa: F401
