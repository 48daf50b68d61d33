"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window."""

from bench.readers import idle_share as read  # noqa: F401
