"""1 - (time in which an operation ran on the device) / traced window."""
from benchmark.reduce.breakdown import idle_share as read  # noqa: F401
