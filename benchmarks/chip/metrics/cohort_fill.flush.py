"""Real clients as a share of the async cohorts' padded rows."""
from _spans import cohort_fill as read  # noqa: F401
