"""Idle share of the chips during training (percent)."""

from benchlib.readers import device_idle_share as read  # noqa: F401
