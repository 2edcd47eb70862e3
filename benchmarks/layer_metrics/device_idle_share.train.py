"""The device's idle share of the traced window (``trace.idle_share``)."""

from benchmarks.trace import idle_share as read  # noqa: F401
