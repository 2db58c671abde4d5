"""walk_rounds.topk: band rounds the top-k walk ran per front-door flush
(index_walk_rounds_total over frontdoor_flushes_total, window
differences)."""


def read(ctx):
    rounds = ctx.obs.counter("index_walk_rounds_total")
    flushes = ctx.obs.counter("frontdoor_flushes_total")
    if rounds is None or not flushes:
        return None
    return rounds / flushes
