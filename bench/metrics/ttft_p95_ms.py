"""95th percentile, over the requests due in the window, of first token
minus due time, in ms; a request still waiting at the close counts with
what it has waited so far."""
from bench import stats


def read(ctx):
    w = ctx.window
    v = stats.ttfts([s.due for s in w.served],
                    [s.times[0] if s.times else None for s in w.served],
                    w.t_open, w.t_close)
    return 1e3 * stats.percentile(v, 95) if v else None
