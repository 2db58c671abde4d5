"""walk_host_ms.topk: host time of the top-k band walk per flush: the self
time of the program's `allpairs.walk` spans less their `allpairs.walk.score`
children (where the host waits on the device), summed over the traced
window, over the `frontdoor.flush` spans that end in it."""

import spans


def read(ctx):
    w = spans.window(ctx)
    if w is None or not w.ended("frontdoor.flush"):
        return None
    host = w.self_time("allpairs.walk", minus=("allpairs.walk.score",))
    return 1e3 * spans.length(host) / w.ended("frontdoor.flush")
