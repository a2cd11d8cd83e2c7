"""Device-idle ms per sync round under the server fold (mmfl.fold)."""
from _spans import fold_idle_ms as read  # noqa: F401
