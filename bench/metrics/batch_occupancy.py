"""Tokens of each decode tick over the engine's slots, in %, averaged
over the decode ticks of the traced span (the benchmark's count of the
program's stream events)."""


def read(ctx):
    ticks = [s.decode_tokens for s in ctx.traced_steps if s.decode_tokens]
    return 100.0 * sum(ticks) / (len(ticks) * ctx.n_slots) if ticks else None
