"""95th percentile of every gap between consecutive tokens of one
request inside the window, in ms."""
from bench import stats


def read(ctx):
    w = ctx.window
    gaps = stats.itl_gaps([s.times for s in w.served], w.t_open, w.t_close)
    return 1e3 * stats.percentile(gaps, 95) if gaps else None
