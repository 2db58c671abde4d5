"""cabin_sparse_roofline.ingest: the sketch kernel's memory-bound least
time over its device time in the traced window, in percent.

Least time: per kernel event, the bytes its operands and outputs hold as
the trace's HLO text gives their shapes (the padded COO indices and values
read, the packed sketches written; bench/roofline.py), over the chip's HBM
bandwidth (bench/peaks.json).  Device time: the summed durations of the
kernel's ops in the trace."""

import roofline

KERNEL = "%cabin_build_sparse"


def read(ctx):
    if ctx.trace is None:
        return None
    events = ctx.trace.kernel_events(KERNEL)
    seconds = sum(s for _, _, s in events)
    if not events or seconds <= 0:
        return None
    least = sum(n * roofline.least_seconds(roofline.custom_call_bytes(t),
                                           ctx.device["kind"])
                for t, n, _ in events)
    return 100.0 * least / seconds
