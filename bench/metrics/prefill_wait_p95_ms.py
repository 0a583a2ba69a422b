"""95th percentile, over the requests due in the window and admitted by
its close, of the dispatch of their first prefill chunk minus admission,
in ms, on the engine's clock; one still waiting for its first chunk at
the close counts what it has waited (``bench/spans.py``).  None where
the run's context holds no request times."""
from bench import spans, stats


def read(ctx):
    times = getattr(ctx, "request_times", None)
    if times is None:
        return None
    v = spans.waits(ctx.window, times, "admitted", "first_chunk")
    return 1e3 * stats.percentile(v, 95) if v else None
