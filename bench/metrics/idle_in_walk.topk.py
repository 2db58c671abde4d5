"""idle_in_walk.topk: device-idle seconds inside the band walk's host time
(the self time of `allpairs.walk` less its `allpairs.walk.score`
children), as a share of the traced window, in percent."""

import spans


def read(ctx):
    w = spans.window(ctx)
    if w is None:
        return None
    host = w.self_time("allpairs.walk", minus=("allpairs.walk.score",))
    return 100.0 * w.idle_in(host) / w.seconds
