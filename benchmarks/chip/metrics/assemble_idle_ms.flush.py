"""Device-idle ms per async flush under input assembly (mmfl.assemble)."""
from _spans import assemble_idle_ms as read  # noqa: F401
