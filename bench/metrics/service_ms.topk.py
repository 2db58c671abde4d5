"""service_ms.topk: mean host wall time of one topk flush in the engine
(frontdoor_service_ms{op=topk} sum over count)."""


def read(ctx):
    h = ctx.obs.histogram("frontdoor_service_ms", op="topk")
    if not h or not h[0]:
        return None
    return h[1] / h[0]
