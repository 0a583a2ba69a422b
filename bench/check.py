"""Is what the timed path served correct?  Compared with the plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests that were served, drawn from the seed and holding the one
with the most served tokens, is run through the reference once: each
prompt followed by its served tokens.  At every served token the number
read is its *gap*: how far its logit lies below the reference's best
logit at that position.  Greedy decoding serves the program's best
token, so a sound program's gaps are rounding; a wrong token, or a
wrong step underneath, shows as a gap of the order of the logits'
spread.

Numbers read: ``gap_max``, the widest gap over the sample, and
``gap_mean``.  Those that the configuration file gives a limit are
compared; the others are reported only (``PERF.md`` says why).
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from bench import weights as W

__all__ = ["sample", "Readings", "judge", "MIN_TOKENS", "MAX_SEQS"]

#: served tokens the sample holds at least (when the window served them)
MIN_TOKENS = 256
MAX_SEQS = 8
_T_ALIGN = 128     # reference rows are padded to a multiple of this
_R_BLOCK = 256     # logits are computed this many positions at a time


def sample(served, seed: int, min_tokens: int = MIN_TOKENS,
           max_seqs: int = MAX_SEQS):
    """(prompt, served tokens) pairs: the longest served request, then
    others in an order drawn from the seed, up to ``min_tokens``."""
    cands = [s for s in served if s.tokens]
    if not cands:
        return []
    longest = max(cands, key=lambda s: (len(s.tokens), -s.rid))
    picked, total = [longest], len(longest.tokens)
    rng = np.random.default_rng([int(seed), 0x5A3])
    for i in rng.permutation(len(cands)):
        if total >= min_tokens or len(picked) >= max_seqs:
            break
        c = cands[int(i)]
        if c is not longest:
            picked.append(c)
            total += len(c.tokens)
    return [(list(c.request.prompt), list(c.tokens)) for c in picked]


def _rows(seqs):
    """Token rows (prompt + served[:-1], padded to fixed shapes so the
    reference compiles once per cell) and, per sequence, the first
    position whose logits predict a served token and the count."""
    lens = [len(p) + len(s) - 1 for p, s in seqs]
    tokens = np.zeros((MAX_SEQS, -(-max(lens) // _T_ALIGN) * _T_ALIGN),
                      np.int32)
    for i, (p, s) in enumerate(seqs):
        row = p + s[:-1]
        tokens[i, :len(row)] = row
    return tokens, [(len(p) - 1, len(s)) for p, s in seqs]


class Readings:
    """The reference's logits at every served position of a sample."""

    def __init__(self, ref, sizes: W.Sizes, seed: int, dtype: str, seqs):
        self.seqs = seqs
        self.tokens, self.spans = _rows(seqs)
        self.ref, self.sizes, self.seed, self.dtype = ref, sizes, seed, dtype
        self.embed = W.embed_weights(sizes, seed, dtype)
        self.logits = self._logits("float32")
        self.best = jnp.max(self.logits, axis=-1)

    def _logits(self, precision: str):
        """Logits [R, V] at the served positions, in sample order."""
        h = self.ref.final_hidden(self.sizes, self.seed, self.dtype,
                                  self.tokens, precision)
        rows = jnp.concatenate([h[i, a:a + n]
                                for i, (a, n) in enumerate(self.spans)])
        pad = -rows.shape[0] % _R_BLOCK
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        out = [self.ref.logits(rows[i:i + _R_BLOCK], self.embed, precision)
               for i in range(0, rows.shape[0], _R_BLOCK)]
        return jnp.concatenate(out)[:rows.shape[0] - pad]

    def gaps(self, picks) -> np.ndarray:
        """Gap of one picked token per served position (sample order)."""
        tok = jnp.asarray(np.concatenate([np.asarray(p) for p in picks]))
        got = jnp.take_along_axis(self.logits, tok[:, None], 1)[:, 0]
        return np.asarray(self.best - got)

    def served_gaps(self) -> np.ndarray:
        return self.gaps([s for _, s in self.seqs])

    def reference_control_picks(self, precision: str):
        """The tokens the reference computed at ``precision`` puts first."""
        return [np.asarray(jnp.argmax(self._logits(precision), axis=-1))]


def numbers(gaps: np.ndarray) -> dict:
    return {"gap_max": float(np.max(gaps)), "gap_mean": float(np.mean(gaps))}


def judge(nums: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}) over the limited numbers."""
    if not limits:
        raise ValueError("the configuration sets no correctness limit")
    out, ok = {}, True
    for name, lim in limits.items():
        value = nums[name]
        out[name] = {"value": value, "limit": lim}
        if not math.isfinite(value) or value > lim:
            ok = False
    return ok, out
