"""radius_qps: radius requests due in the window and answered, over the
time from the window's start to the last of their answers."""


def read(ctx):
    return ctx.rate("radius")
