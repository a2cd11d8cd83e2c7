"""Device-idle ms per sync round under the host control plane's own code."""
from _spans import control_idle_ms as read  # noqa: F401
