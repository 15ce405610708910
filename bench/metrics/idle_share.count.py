"""idle_share.count: percent of the traced window in which no operation ran
on the device, in the counting cells."""

from bench.harness.record import idle_share as read  # noqa: F401
