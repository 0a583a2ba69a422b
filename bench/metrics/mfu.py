"""Model FLOPs of the prompt and output tokens processed in the traced
span, over the span and the chip's bf16 peak, in %.  The same work is
counted whichever arithmetic runs it (``bench/flops.py``)."""
from bench import flops


def read(ctx):
    if ctx.trace is None or not ctx.traced_steps:
        return None
    work = 0.0
    for s in ctx.traced_steps:
        work += flops.decode_tick_flops(ctx.sizes, s.contexts)
        if s.prefill_rows:
            work += flops.prefill_chunk_flops(ctx.sizes, s.prefill_offset,
                                              s.prefill_rows)
    return 100.0 * work / (ctx.trace.window_s * ctx.peak.bf16_flops
                           * ctx.trace.n_devices)
