"""Device values read to the host per async flush."""
from _spans import host_syncs as read  # noqa: F401
