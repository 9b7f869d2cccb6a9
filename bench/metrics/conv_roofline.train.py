"""Roofline share of the training step's convolutions (percent)."""

from benchlib.readers import conv_roofline_train as read  # noqa: F401
