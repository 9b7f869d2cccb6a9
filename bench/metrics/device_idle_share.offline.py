"""Idle share of the chip while serving (percent)."""

from benchlib.readers import device_idle_share as read  # noqa: F401
