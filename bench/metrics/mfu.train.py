"""Model FLOP/s utilization of the whole training step (percent)."""

from benchlib.readers import mfu_train as read  # noqa: F401
