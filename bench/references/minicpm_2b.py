"""Plain reference forward of MiniCPM-2B (arXiv:2404.06395), in float32.

A llama-style decoder as the published config describes it: RMSNorm
before attention and MLP, rotary embeddings (half rotation, theta from
the config), multi-head causal attention, a SwiGLU MLP
(``silu(x w1) * (x w3)`` then ``w2``), a final RMSNorm and logits
against the tied embedding.  Departure, shared with the program: the
published muP scalings (``scale_emb``, ``scale_depth``,
``dim_model_base``) are not applied.

It imports nothing of the program: the weights come from
``bench.weights`` and the seed, one layer at a time, so the reference
fits beside nothing else on the chip.  Matmuls run at
``Precision.HIGHEST``.  ``precision="int8"`` is the control: every
linear layer, logits included, on int8 weights (per output column) and
int8 activations (per row), accumulated in int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

__all__ = ["final_hidden", "logits"]

HI = jax.lax.Precision.HIGHEST


def _mm(x, w, precision: str):
    if precision == "float32":
        return jnp.dot(x, w, precision=HI)
    if precision == "int8":
        sw = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
        sx = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
        qw = jnp.round(w / jnp.where(sw > 0, sw, 1.0)).astype(jnp.int8)
        qx = jnp.round(x / jnp.where(sx > 0, sx, 1.0)).astype(jnp.int8)
        acc = jax.lax.dot_general(
            qx, qw, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * sx * sw
    raise ValueError(f"unknown precision {precision!r}")


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: [N, T, H, hd]; positions 0..T-1."""
    t, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = np.arange(t, dtype=np.float32)[:, None] * freqs[None]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _layer(x, w, sizes: W.Sizes, precision: str):
    n, t, d = x.shape
    h, kvh, hd = sizes.heads, sizes.kv_heads, sizes.head_dim
    a = _rms(x, w["ln1"], sizes.eps)
    q = _rope(_mm(a, w["wq"], precision).reshape(n, t, h, hd),
              sizes.rope_theta)
    k = _rope(_mm(a, w["wk"], precision).reshape(n, t, kvh, hd),
              sizes.rope_theta)
    v = _mm(a, w["wv"], precision).reshape(n, t, kvh, hd)
    k = jnp.repeat(k, h // kvh, axis=2)
    v = jnp.repeat(v, h // kvh, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=HI) / np.sqrt(hd)
    causal = np.tril(np.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("nhqk,nkhd->nqhd", p, v, precision=HI).reshape(n, t, d)
    x = x + _mm(o, w["wo"], precision)
    b = _rms(x, w["ln2"], sizes.eps)
    g = _mm(b, w["w1"], precision)
    u = _mm(b, w["w3"], precision)
    return x + _mm(jax.nn.silu(g) * u, w["w2"], precision)


def final_hidden(sizes: W.Sizes, seed: int, dtype: str, tokens: np.ndarray,
                 precision: str = "float32"):
    """Final normed hidden states [N, T, D] of token rows [N, T].

    Causal, so a row padded at its end has the same states at its real
    positions."""
    embed = W.embed_weights(sizes, seed, dtype)
    x = jnp.take(embed, jnp.asarray(tokens), axis=0)
    del embed
    for layer in range(sizes.layers):
        x = _layer(x, W.layer_weights(sizes, seed, layer, dtype), sizes,
                   precision)
    return _rms(x, W.final_norm_weights(sizes, seed, dtype), sizes.eps)


@functools.partial(jax.jit, static_argnames=("precision",))
def _logits(h, embed, precision: str):
    return _mm(h, embed.T, precision)


def logits(h, embed, precision: str = "float32"):
    """Logits over the padded vocabulary for hidden rows [R, D]."""
    return _logits(h, embed, precision)
