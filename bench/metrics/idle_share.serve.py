"""idle_share.serve: percent of the traced window in which no operation ran
on the device, in the service cells."""

from bench.harness.record import idle_share as read  # noqa: F401
