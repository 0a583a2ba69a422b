"""Device time of the paged KV path per decode tick, in ms: the ops of
the ``jit_decode_tick`` runs in the traced span whose ``op_name`` lies
under the ``paged_kv`` scope of the compiled decode program (the page
write and the gather of the slot views; ``bench/spans.py``), over the
runs.  None where the run's context holds no engine trace or program."""


def read(ctx):
    et = getattr(ctx, "engine_trace", None)
    text = (getattr(ctx, "programs", None) or {}).get("jit_decode_tick")
    if et is None or text is None:
        return None
    seconds, ticks = et.scope_seconds("jit_decode_tick", text, "paged_kv")
    return 1e3 * seconds / ticks if ticks else None
