"""Host milliseconds per step inside the program's own outermost telemetry
spans (its entry points' dispatch, not the device's work)."""
from chipbench.trace import span_ms_per_step as read  # noqa: F401
