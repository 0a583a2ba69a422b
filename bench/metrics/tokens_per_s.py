"""Output tokens streamed in the window over the window's length."""


def read(ctx):
    w = ctx.window
    n = sum(1 for s in w.served for t in s.times if w.t_open < t <= w.t_close)
    return n / (w.t_close - w.t_open) if n else None
