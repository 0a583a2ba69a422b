"""Process start to window open: weights, compile or cache load,
warm-up, slot fill."""


def read(ctx):
    return ctx.setup_s
