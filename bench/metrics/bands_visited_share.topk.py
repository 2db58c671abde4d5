"""bands_visited_share.topk: weight bands the top-k walk visited, as a
share of the bands it could have visited (visited + pruned), in percent."""


def read(ctx):
    visited = ctx.obs.counter("index_bands_visited_total")
    pruned = ctx.obs.counter("index_bands_pruned_total")
    if not visited:
        return None
    return 100.0 * visited / (visited + (pruned or 0))
