"""device_idle.radius: idle share of the device in the traced window of a
cell that reports radius_qps (tracereduce.idle_percent)."""

from tracereduce import idle_percent as read  # noqa: F401
