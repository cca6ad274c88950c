"""window_compiles.query: XLA compiles inside the window."""

from lib.readers import window_compiles as read  # noqa: F401
