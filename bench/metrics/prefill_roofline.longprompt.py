"""Roofline share of the serving engine's prefill executions (percent)."""

from benchlib.readers import prefill_roofline as read  # noqa: F401
