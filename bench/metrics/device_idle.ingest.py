"""device_idle.ingest: idle share of the device in the traced window of a
cell that reports ingest_rows_per_s (tracereduce.idle_percent)."""

from tracereduce import idle_percent as read  # noqa: F401
