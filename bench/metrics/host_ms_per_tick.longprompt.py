"""Host milliseconds per engine tick outside its executables."""

from benchlib.readers import host_ms_per_tick as read  # noqa: F401
