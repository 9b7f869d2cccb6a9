"""The 99th percentile of the gaps between output tokens over the window."""

from benchlib.readers import itl_p99_ms as read  # noqa: F401
