"""setup_s: seconds from process start to the first measured request —
loading, corpus, sketching, layout, compiles and warm-up."""


def read(ctx):
    return ctx.setup_s
