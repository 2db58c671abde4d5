"""device_idle.topk: idle share of the device in the traced window of a
cell that reports topk_p95_ms (tracereduce.idle_percent)."""

from tracereduce import idle_percent as read  # noqa: F401
