"""Mean device time of one prefill chunk, in ms: the runs of the engine's
``jit_prefill_chunk`` program in the traced span (``bench/spans.py``).
None where the run's context holds no engine trace."""


def read(ctx):
    et = getattr(ctx, "engine_trace", None)
    runs = et.run_seconds("jit_prefill_chunk") if et is not None else []
    return 1e3 * sum(runs) / len(runs) if runs else None
