"""Seeded random weights of a decoder configuration.

The benchmark owns the weights: the program is handed a tree made here,
and the reference regenerates the same values from the seed, layer by
layer, without taking anything from the program.  Every leaf of every
layer has its own random stream, ``fold_in(fold_in(root, leaf), layer)``,
so a layer drawn alone equals that layer of the stacked tree.

Projections are N(0, 1/fan_in), the embedding N(0, 0.02**2) with the
padded rows (ids >= vocab_size, never served) at zero, norm scales
1 + N(0, 0.1**2).  Values are drawn in float32 and cast to the served
dtype; the reference upcasts that same value.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Sizes", "seed_words", "make_params", "layer_weights",
           "embed_weights", "final_norm_weights", "LAYER_LEAVES"]

#: leaf -> random stream id; never renumber (the ids key the streams)
_STREAM = {"embed": 0, "final_norm": 1, "ln1": 2, "wq": 3, "wk": 4,
           "wv": 5, "wo": 6, "ln2": 7, "w1": 8, "w3": 9, "w2": 10}
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w1", "w3", "w2")
MATMUL_LEAVES = ("wq", "wk", "wv", "wo", "w1", "w3", "w2")
EMBED_STD = 0.02
NORM_STD = 0.1


@dataclass(frozen=True)
class Sizes:
    """The sizes of a configuration file that the weights and the
    operation counts depend on."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    ff: int
    vocab: int
    eps: float
    rope_theta: float

    @classmethod
    def of(cls, config: dict) -> "Sizes":
        return cls(config["num_hidden_layers"], config["hidden_size"],
                   config["num_attention_heads"],
                   config["num_key_value_heads"],
                   config["intermediate_size"], config["vocab_size"],
                   config["rms_norm_eps"], config["rope_theta"])

    @property
    def head_dim(self) -> int:
        return self.d // self.heads

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // 256) * 256

    def leaf_shape(self, leaf: str):
        d, q, kv, f = (self.d, self.heads * self.head_dim,
                       self.kv_heads * self.head_dim, self.ff)
        return {"ln1": (d,), "ln2": (d,), "final_norm": (d,),
                "embed": (self.padded_vocab, d), "wq": (d, q), "wk": (d, kv),
                "wv": (d, kv), "wo": (q, d), "w1": (d, f), "w3": (d, f),
                "w2": (f, d)}[leaf]

    def layer_params(self) -> int:
        """Matmul parameters of one layer."""
        return sum(int(np.prod(self.leaf_shape(n))) for n in MATMUL_LEAVES)


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as two uint32 words, passed traced so a
    new seed compiles nothing."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def _leaf(words, leaf: str, layer, sizes: Sizes, dtype):
    root = jax.random.fold_in(jax.random.fold_in(jax.random.key(0),
                                                 words[0]), words[1])
    key = jax.random.fold_in(jax.random.fold_in(root, _STREAM[leaf]), layer)
    shape = sizes.leaf_shape(leaf)
    z = jax.random.normal(key, shape, jnp.float32)
    if leaf in ("ln1", "ln2", "final_norm"):
        return (1.0 + NORM_STD * z).astype(dtype)
    if leaf == "embed":
        rows = jnp.arange(shape[0])[:, None]
        return jnp.where(rows < sizes.vocab, EMBED_STD * z, 0.0).astype(dtype)
    return (z * np.float32(1.0 / np.sqrt(shape[0]))).astype(dtype)


def _layer(words, layer, sizes: Sizes, dtype):
    return {n: _leaf(words, n, layer, sizes, dtype) for n in LAYER_LEAVES}


@functools.lru_cache(maxsize=None)
def _make_params_fn(sizes: Sizes, dtype: str):
    dt = jnp.dtype(dtype)

    def make(words):
        per_layer = jax.vmap(lambda l: _layer(words, l, sizes, dt))(
            jnp.arange(sizes.layers, dtype=jnp.uint32))
        return {
            "embed": _leaf(words, "embed", 0, sizes, dt),
            "final_norm": {"scale": _leaf(words, "final_norm", 0, sizes, dt)},
            "blocks": {
                "ln1": {"scale": per_layer["ln1"]},
                "ln2": {"scale": per_layer["ln2"]},
                "attn": {n: per_layer[n] for n in ("wq", "wk", "wv", "wo")},
                "ffn": {n: per_layer[n] for n in ("w1", "w3", "w2")},
            },
        }

    return jax.jit(make)


def make_params(sizes: Sizes, seed: int, dtype: str):
    """The program's parameter tree, made on the device in one call."""
    return _make_params_fn(sizes, dtype)(seed_words(seed))


@functools.lru_cache(maxsize=None)
def _upcast_fn(sizes: Sizes, dtype: str, leaves: tuple):
    dt = jnp.dtype(dtype)
    return jax.jit(lambda words, l: {
        n: _leaf(words, n, l, sizes, dt).astype(jnp.float32)
        for n in leaves})


def layer_weights(sizes: Sizes, seed: int, layer: int, dtype: str) -> dict:
    """Layer ``layer``'s weights as served (``dtype``), upcast to float32."""
    return _upcast_fn(sizes, dtype, LAYER_LEAVES)(seed_words(seed),
                                                  jnp.uint32(layer))


def embed_weights(sizes: Sizes, seed: int, dtype: str):
    return _upcast_fn(sizes, dtype, ("embed",))(
        seed_words(seed), jnp.uint32(0))["embed"]


def final_norm_weights(sizes: Sizes, seed: int, dtype: str):
    return _upcast_fn(sizes, dtype, ("final_norm",))(
        seed_words(seed), jnp.uint32(0))["final_norm"]
