"""service_ms.radius: mean host wall time of one radius flush in the engine
(frontdoor_service_ms{op=radius} sum over count)."""


def read(ctx):
    h = ctx.obs.histogram("frontdoor_service_ms", op="radius")
    if not h or not h[0]:
        return None
    return h[1] / h[0]
