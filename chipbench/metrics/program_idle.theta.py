"""Percent of the traced window in which no operation ran on the device
while one of the program's own telemetry spans was open on the host (the
idle time its entry points hold, not the benchmark loop's)."""
from chipbench.program import idle_percent as read  # noqa: F401
