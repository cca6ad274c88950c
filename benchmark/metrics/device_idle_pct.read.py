"""device_idle_pct.read: device idle share of the traced window (%)."""

from lib.readers import device_idle_pct as read  # noqa: F401
