"""1 - (union of device-operation intervals) / traced stretch."""

from trace_reduce import idle_share as read  # noqa: F401
