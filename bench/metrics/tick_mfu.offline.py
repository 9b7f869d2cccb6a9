"""Model FLOP/s utilization of the serving engine's executables (percent)."""

from benchlib.readers import tick_mfu as read  # noqa: F401
