"""Operations and bytes of the served model's work, from its sizes.

Model FLOPs count what the model needs, whatever arithmetic runs it:
2 x the matmul parameters per processed token, attention's score and
value products at the token's context (4 x heads x head_dim x context
per layer), and the logits (2 x d_model x vocab) for each row whose
logits a step computes.  A kernel's bytes are the least its work needs:
each operand and output read or written once, at its true shape, in the
dtype the configuration serves (``itemsize`` bytes a value); a kernel
that pads, or converts to a wider type first, moves more, and reads
further below its roofline.
"""
from __future__ import annotations

from bench.weights import Sizes

__all__ = ["token_flops", "logits_flops", "decode_tick_flops",
           "prefill_chunk_flops", "flash_decode_call", "least_time"]


def token_flops(s: Sizes, context: int) -> float:
    """One processed token at ``context`` positions (itself included)."""
    return (2.0 * s.layers * s.layer_params()
            + 4.0 * s.layers * s.heads * s.head_dim * context)


def logits_flops(s: Sizes) -> float:
    return 2.0 * s.d * s.vocab


def decode_tick_flops(s: Sizes, contexts) -> float:
    """A decode tick over the live slots at these contexts (after the
    tick's token is written)."""
    return sum(token_flops(s, c) + logits_flops(s) for c in contexts)


def prefill_chunk_flops(s: Sizes, offset: int, n: int) -> float:
    """A prefill chunk of ``n`` tokens after ``offset`` stored ones."""
    return (sum(token_flops(s, offset + i + 1) for i in range(n))
            + logits_flops(s))


def flash_decode_call(s: Sizes, slots: int, capacity: int, itemsize: int):
    """(flops, bytes) of one layer's flash-decode call over the gathered
    slot views: scores and values for every head at every slot position;
    q, K, V and the output read or written once at ``itemsize`` bytes a
    value, the int32 slot positions once."""
    flops = 4.0 * slots * s.heads * s.head_dim * capacity
    kv = 2 * slots * capacity * s.kv_heads * s.head_dim
    qo = 2 * slots * s.heads * s.head_dim
    return flops, itemsize * (kv + qo) + 4 * slots * capacity


def least_time(calls, peak) -> float:
    """Sum over calls of max(flops / peak FLOP/s, bytes / peak bytes/s)."""
    return sum(max(f / peak.bf16_flops, b / peak.hbm_bytes_per_s)
               for f, b in calls)
