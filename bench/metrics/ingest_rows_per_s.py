"""ingest_rows_per_s: rows acknowledged by add_sparse over the window,
whose time includes the retention removes and compactions and ends with
the device's work synced."""


def read(ctx):
    if ctx.ingest is None:
        return None
    rows, seconds = ctx.ingest
    return rows / seconds
