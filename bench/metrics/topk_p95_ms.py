"""topk_p95_ms: 95th percentile of the latency of every top-k request due
in the window, from its due time to its answer."""


def read(ctx):
    return ctx.latency_ms("topk", 95)
