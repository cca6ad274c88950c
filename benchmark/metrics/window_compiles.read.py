"""window_compiles.read: XLA compiles inside the window."""

from lib.readers import window_compiles as read  # noqa: F401
