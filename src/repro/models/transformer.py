"""Decoder transformer block (dense or MoE FFN) + KV-cache decode step."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import moe as moe_mod
from repro.models.layers import (
    ParallelCtx,
    apply_norm,
    attention_params,
    decode_attention,
    dense,
    mlp,
    mlp_params,
    norm_params,
    rope,
)
from repro.models.params import P

__all__ = [
    "block_params",
    "block_apply",
    "block_decode",
    "block_decode_paged",
    "attn_cache_specs",
    "paged_attn_cache_specs",
    "cross_attention_block",
]


def block_params(cfg: ModelConfig, moe_layer: bool = False,
                 norm_kind: str = "rms", cross: bool = False) -> dict:
    p = {
        "ln1": norm_params(cfg, norm_kind),
        "attn": attention_params(cfg),
        "ln2": norm_params(cfg, norm_kind),
    }
    if cross:
        p["lnx"] = norm_params(cfg, norm_kind)
        p["xattn"] = attention_params(cfg, cross=True)
    p["ffn"] = moe_mod.moe_params(cfg) if moe_layer else mlp_params(cfg)
    return p


def _ffn(x, p, cfg, ctx, moe_layer, residual=None):
    if moe_layer:
        out = moe_mod.moe_ffn(x, p["ffn"], cfg, ctx)
        return out if residual is None else residual + out
    return mlp(x, p["ffn"], cfg, ctx, residual=residual)


def block_apply(x, p, cfg: ModelConfig, ctx: ParallelCtx, positions,
                moe_layer: bool = False, norm_kind: str = "rms",
                enc_out=None, enc_positions=None, causal: bool = True,
                return_kv: bool = False):
    """Full-sequence block. Returns (x, kv) where kv=(k, v) if requested.

    The block tail is fused: the residual adds ride the attention-out /
    MLP down-projection matmul epilogues, and — for rms-normed blocks
    without a cross-attention slot in between — ln2's normalization
    division is fused into the attention-out matmul's epilogue too
    (``rms_div(wo_out + residual)`` with the RAPID divider on the
    VMEM-resident output tile; only the cheap ``* scale`` stays outside).
    """
    from repro.models.layers import attention

    x = ctx.shard(x, "batch", "seq_act", None)
    # ln2's rms-div fuses into the attention-out matmul only when both
    # sites route to the same backend — a per-site "norm" override must
    # keep steering the normalization divide, not be silently absorbed
    # into the attn_proj matmul's execution path
    acfg = cfg.approx
    fuse_ln2 = (norm_kind == "rms" and enc_out is None
                and acfg.backend_for("norm") == acfg.backend_for("attn_proj"))
    h, k, v = attention(
        apply_norm(x, p["ln1"], cfg, norm_kind), p["attn"], cfg, ctx, positions,
        causal=causal, residual=x, tail_norm=fuse_ln2,
    )
    if fuse_ln2:
        y, ydiv = h
        ffn_in = (ydiv.astype(jnp.float32)
                  * p["ln2"]["scale"].astype(jnp.float32)).astype(y.dtype)
    else:
        y = h
        if enc_out is not None:
            hx, _, _ = attention(
                apply_norm(y, p["lnx"], cfg, norm_kind), p["xattn"], cfg, ctx,
                positions, kv_x=enc_out, kv_positions=enc_positions,
                causal=False, residual=y,
            )
            y = hx
        ffn_in = apply_norm(y, p["ln2"], cfg, norm_kind)
    x = _ffn(ffn_in, p, cfg, ctx, moe_layer, residual=y)
    return (x, (k, v)) if return_kv else (x, None)


# --------------------------------------------------------------------------
# decode
# --------------------------------------------------------------------------

def cache_len(cfg: ModelConfig, seq_len: int) -> int:
    return min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len


def attn_cache_specs(cfg: ModelConfig, batch: int, seq_len: int,
                     cross_len: int = 0) -> dict:
    """P-spec tree for one layer's attention cache."""
    C = cache_len(cfg, seq_len)
    KV, hd = cfg.n_kv_heads, cfg.hd
    dt = cfg.dtype
    cache_batch_ax = "batch" if batch > 1 else None
    # cache length always carries the "seq" logical axis: kv-head counts
    # (4..36) can never shard a 16-way axis, the 32k length always can
    seq_ax = "seq"
    p = {
        "k": P((batch, C, KV, hd), (cache_batch_ax, seq_ax, "kv", None),
               "zeros", dtype=dt),
        "v": P((batch, C, KV, hd), (cache_batch_ax, seq_ax, "kv", None),
               "zeros", dtype=dt),
    }
    if cross_len:
        p["ck"] = P((batch, cross_len, KV, hd), (cache_batch_ax, None, "kv", None),
                    "zeros", dtype=dt)
        p["cv"] = P((batch, cross_len, KV, hd), (cache_batch_ax, None, "kv", None),
                    "zeros", dtype=dt)
    return p


def paged_attn_cache_specs(cfg: ModelConfig, n_pages: int,
                           page_size: int) -> dict:
    """P-spec tree for one layer's block-paged KV pool.

    Unlike :func:`attn_cache_specs` there is no batch dim: slots own
    pages of the shared ``[n_pages, page_size, KV, hd]`` pool through a
    page table, so memory scales with live tokens, not slots x cache_n.
    """
    KV, hd = cfg.n_kv_heads, cfg.hd
    dt = cfg.dtype
    return {
        "k": P((n_pages, page_size, KV, hd), (None, None, "kv", None),
               "zeros", dtype=dt),
        "v": P((n_pages, page_size, KV, hd), (None, None, "kv", None),
               "zeros", dtype=dt),
    }


def block_decode_paged(x, p, cache, page_table, positions, valid,
                       kv_len, cfg: ModelConfig, ctx: ParallelCtx,
                       moe_layer: bool = False, norm_kind: str = "rms"):
    """Chunk decode against a block-paged KV pool (page-table writes).

    The paged generalization of :func:`block_decode`'s ring write: token
    ``i`` of slot ``b`` lands in pool page ``page_table[b, pos // PS]``
    at offset ``pos % PS``, and the slot's cache view is gathered back
    through the same table.  Handles both the continuous decode step
    (S=1, all slots) and a chunked-prefill step (S=chunk, one slot).

    x: [B, S, D]; cache: {"k","v"} pools [NP, PS, KV, hd]; page_table:
    [B, P] int32 pool indices; positions: [B, S] absolute token
    positions; valid: [B, S] bool (False tokens write to the scratch
    page and their outputs are ignored); kv_len: [B] int32 valid cache
    tokens per slot *after* this chunk's writes.
    """
    from repro.models.layers import chunk_cache_attention, decode_attention

    acfg = cfg.approx
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    PS = cache["k"].shape[1]
    Pp = page_table.shape[1]
    C = Pp * PS

    h = apply_norm(x, p["ln1"], cfg, norm_kind)
    q = dense(h, p["attn"]["wq"], acfg, "attn_proj").reshape(B, S, H, hd)
    k = dense(h, p["attn"]["wk"], acfg, "attn_proj").reshape(B, S, KV, hd)
    v = dense(h, p["attn"]["wv"], acfg, "attn_proj").reshape(B, S, KV, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    # the scopes name these ops in the compiled program's metadata, so a
    # device trace can put the paged KV path's time down to it
    with jax.named_scope("paged_kv"):
        # page-table indirection write; invalid tokens go to the scratch
        # page (0), whose contents are never addressed by any page table
        # audit: exact — integer page-index arithmetic, not datapath
        pidx = jnp.clip(positions // PS, 0, Pp - 1)           # [B, S]
        pid = jnp.take_along_axis(page_table, pidx, axis=1)   # [B, S]
        pid = jnp.where(valid, pid, 0).reshape(-1)
        poff = (positions % PS).reshape(-1)
        ck = cache["k"].at[pid, poff].set(
            k.reshape(B * S, KV, hd).astype(cache["k"].dtype))
        cv = cache["v"].at[pid, poff].set(
            v.reshape(B * S, KV, hd).astype(cache["v"].dtype))

        # gather the slot views back through the table: [B, P*PS, KV, hd]
        kg = ck[page_table].reshape(B, C, KV, hd)
        vg = cv[page_table].reshape(B, C, KV, hd)
        j = jnp.arange(C, dtype=jnp.int32)
        kv_pos = jnp.where(j[None, :] < kv_len[:, None], j[None, :],
                           jnp.iinfo(jnp.int32).max)          # [B, C]

    with jax.named_scope("attention"):
        if S == 1:
            # the hot path: same formulation as the dense decode step, so
            # a paged slot's logits are bit-identical to a lockstep slot's
            attn_out = decode_attention(
                q[:, 0], kg, vg, kv_pos, positions[:, 0],
                cfg.sliding_window, acfg, ctx)[:, None]
        else:
            attn_out = chunk_cache_attention(
                q, kg, vg, positions, kv_pos, cfg.sliding_window, acfg)
    x = dense(attn_out, p["attn"]["wo"], acfg, "attn_proj",
              residual=x)
    h2 = apply_norm(x, p["ln2"], cfg, norm_kind)
    x = _ffn(h2, p, cfg, ctx, moe_layer, residual=x)
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = ck, cv
    return x, new_cache


def block_decode(x, p, cache, slot_positions, pos, cfg: ModelConfig,
                 ctx: ParallelCtx, moe_layer: bool = False,
                 norm_kind: str = "rms", enc_positions=None,
                 seq_shard_axis: Optional[str] = None):
    """One-token decode. x: [B, D]; cache: {"k","v"[,ck,cv]}; pos scalar."""
    acfg = cfg.approx
    B, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    C = cache["k"].shape[1]

    h = apply_norm(x[:, None], p["ln1"], cfg, norm_kind)
    q = dense(h, p["attn"]["wq"], acfg, "attn_proj").reshape(B, H, hd)
    k = dense(h, p["attn"]["wk"], acfg, "attn_proj").reshape(B, KV, hd)
    v = dense(h, p["attn"]["wv"], acfg, "attn_proj").reshape(B, KV, hd)
    posv = jnp.full((B,), pos, jnp.int32)
    q = rope(q[:, None], posv[:, None], cfg.rope_theta)[:, 0]
    k = rope(k[:, None], posv[:, None], cfg.rope_theta)[:, 0]

    write = pos % C  # ring write for sliding-window caches
    ck = jax.lax.dynamic_update_slice(cache["k"], k[:, None].astype(cache["k"].dtype),
                                      (0, write, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v[:, None].astype(cache["v"].dtype),
                                      (0, write, 0, 0))
    attn_out = decode_attention(
        q, ck, cv, slot_positions, pos, cfg.sliding_window, acfg, ctx,
        seq_shard_axis,
    )
    # the residual adds ride the projection epilogues (fused block tail)
    x = dense(attn_out[:, None], p["attn"]["wo"], acfg, "attn_proj",
              residual=x[:, None])[:, 0]

    if "ck" in cache:  # cross attention (enc-dec decode)
        hx = apply_norm(x[:, None], p["lnx"], cfg, norm_kind)
        qx = dense(hx, p["xattn"]["wq"], acfg, "attn_proj").reshape(B, H, hd)
        Tc = cache["ck"].shape[1]
        xo = decode_attention(
            qx, cache["ck"], cache["cv"],
            jnp.broadcast_to(jnp.arange(Tc, dtype=jnp.int32), (B, Tc)),
            jnp.int32(2**30), 0, acfg, ctx, None,
        )
        x = dense(xo[:, None], p["xattn"]["wo"], acfg, "attn_proj",
                  residual=x[:, None])[:, 0]

    h2 = apply_norm(x[:, None], p["ln2"], cfg, norm_kind)
    x = _ffn(h2, p, cfg, ctx, moe_layer, residual=x[:, None])[:, 0]
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = ck, cv
    return x, new_cache


def cross_attention_block(*a, **kw):  # pragma: no cover - naming alias
    return block_apply(*a, **kw)
