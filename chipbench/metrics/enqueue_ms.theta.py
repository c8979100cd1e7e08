"""Host milliseconds per step inside the program's ``*.enqueue`` spans:
its calls into compiled executables, split from option resolution, cache
lookups and eager work around them."""
from chipbench.program import enqueue_ms_per_step as read  # noqa: F401
