"""device_idle_pct.query: device idle share of the traced window (%)."""

from lib.readers import device_idle_pct as read  # noqa: F401
