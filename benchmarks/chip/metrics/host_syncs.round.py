"""Device values read to the host per sync round."""
from _spans import host_syncs as read  # noqa: F401
