"""Percent of the traced window in which no operation ran on the device."""
from chipbench.trace import idle_percent as read  # noqa: F401
