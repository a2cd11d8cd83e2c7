"""Evaluation device ms per async flush (either task)."""
from _eval import eval_ms as read  # noqa: F401
