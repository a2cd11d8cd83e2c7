"""update_ms_per_ktok in the async cell, where it moves flush_ms_p95."""
from update_ms_per_ktok import read  # noqa: F401
