"""setup_s: process start to the first timed request (s)."""

from lib.readers import setup_s as read  # noqa: F401
