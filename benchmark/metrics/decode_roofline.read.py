"""decode_roofline.read: the read's HBM bytes at peak over device busy time (%)."""

from lib.readers import decode_roofline as read  # noqa: F401
