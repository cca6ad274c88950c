"""device_route_pct.query: share of Q6 scans on the device route (%)."""

from lib.readers import device_route_pct as read  # noqa: F401
