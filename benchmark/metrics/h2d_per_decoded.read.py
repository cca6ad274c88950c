"""h2d_per_decoded.read: bytes_h2d per decoded Arrow byte."""

from lib.readers import h2d_per_decoded as read  # noqa: F401
