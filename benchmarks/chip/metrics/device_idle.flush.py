"""device_idle in the async cell, where it moves flush_ms_p95."""
from device_idle import read  # noqa: F401
