"""step_mfu in the async cell, where it moves flush_ms_p95."""
from step_mfu import read  # noqa: F401
