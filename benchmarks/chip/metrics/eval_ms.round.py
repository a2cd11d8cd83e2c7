"""Evaluation device ms per sync round (both tasks)."""
from _eval import eval_ms as read  # noqa: F401
