"""The flash-decode kernel's share of its roofline, in %: the least time
of its calls in the traced span (one per layer and decode tick, over the
gathered slot views, at the configuration's served dtype;
``bench/flops.py``) over the kernel's device time in the trace."""
import jax.numpy as jnp

from bench import flops

KERNEL = r"^flash_decode"


def read(ctx):
    if ctx.trace is None:
        return None
    seconds, n = ctx.trace.op_seconds(KERNEL)
    if not n:
        return None
    ticks = sum(1 for s in ctx.traced_steps if s.decode_tokens)
    call = flops.flash_decode_call(ctx.sizes, ctx.n_slots, ctx.capacity,
                                   jnp.dtype(ctx.dtype).itemsize)
    return 100.0 * flops.least_time([call] * (ticks * ctx.sizes.layers),
                                    ctx.peak) / seconds
