"""HBM roofline share of the serving engine's decode executions (percent)."""

from benchlib.readers import decode_hbm_roofline as read  # noqa: F401
