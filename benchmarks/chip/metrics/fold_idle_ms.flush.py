"""Device-idle ms per async flush under the server fold and its deltas."""
from _spans import fold_idle_ms as read  # noqa: F401
