"""frontdoor_queue_ms.topk: mean wait of a request in the front door's
queue before its flush (frontdoor_queue_wait_ms sum over count)."""


def read(ctx):
    h = ctx.obs.histogram("frontdoor_queue_wait_ms")
    if not h or not h[0]:
        return None
    return h[1] / h[0]
