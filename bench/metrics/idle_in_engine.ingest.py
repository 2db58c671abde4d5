"""idle_in_engine.ingest: device-idle seconds under the program's
`engine.add_sparse` and `engine.remove` spans (host validation, transfer,
drift count, the remove loop), as a share of the traced window, in
percent."""

import spans


def read(ctx):
    w = spans.window(ctx)
    if w is None:
        return None
    return 100.0 * w.idle_in(w.covered("engine.add_sparse",
                                       "engine.remove")) / w.seconds
