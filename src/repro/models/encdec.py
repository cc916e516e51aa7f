"""Encoder-decoder backbone (seamless-m4t-medium [arXiv:2308.11596]).

Bidirectional encoder over (stub) audio-frame embeddings; causal decoder
with cross-attention over encoder memory.  LayerNorm (pre-LN), GELU FFN,
standard RoPE on self-attention; cross-attention is position-free (the
NLLB/seamless convention approximated — see DESIGN.md §Arch-applicability).

Decode path: self-attn KV cache + per-layer cross-KV computed once from the
encoder memory at prefill.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_activation
from repro.models.attention import attention, attn_params, decode_attention
from repro.models.config import ModelConfig
from repro.models.layers import apply_norm, dense_init, embed_init, norm_params
from repro.models.mlp import mlp, mlp_params

__all__ = [
    "init_params_encdec",
    "encode",
    "forward_encdec",
    "prefill_encdec",
    "decode_step_encdec",
    "init_cache_encdec",
]


def _enc_block_params(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": norm_params(cfg.norm, cfg.d_model, cfg.pdtype),
        "attn": attn_params(k1, cfg),
        "ln2": norm_params(cfg.norm, cfg.d_model, cfg.pdtype),
        "mlp": mlp_params(k2, cfg),
    }


def _dec_block_params(key, cfg: ModelConfig) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": norm_params(cfg.norm, cfg.d_model, cfg.pdtype),
        "attn": attn_params(k1, cfg),
        "ln_cross": norm_params(cfg.norm, cfg.d_model, cfg.pdtype),
        "cross_attn": attn_params(k2, cfg),
        "ln2": norm_params(cfg.norm, cfg.d_model, cfg.pdtype),
        "mlp": mlp_params(k3, cfg),
    }


def init_params_encdec(cfg: ModelConfig, key) -> dict:
    ke, kenc, kdec, kh = jax.random.split(key, 4)
    enc_keys = jax.random.split(kenc, cfg.n_enc_layers)
    dec_keys = jax.random.split(kdec, cfg.n_layers)
    return {
        "embed": {"table": embed_init(ke, cfg.vocab_size, cfg.d_model, cfg.pdtype)},
        "enc_layers": jax.vmap(lambda k: _enc_block_params(k, cfg))(enc_keys),
        "enc_norm": norm_params(cfg.norm, cfg.d_model, cfg.pdtype),
        "layers": jax.vmap(lambda k: _dec_block_params(k, cfg))(dec_keys),
        "final_norm": norm_params(cfg.norm, cfg.d_model, cfg.pdtype),
        "lm_head": {"w": dense_init(kh, cfg.d_model, cfg.vocab_size, cfg.pdtype)},
    }


def encode(cfg: ModelConfig, params, src_embeds):
    """src_embeds: (B, S_src, d) from the (stub) audio frontend."""
    x = src_embeds.astype(cfg.cdtype)
    B, S = x.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = shard_activation(x, "dp", None, None)

    def body(h, lp):
        hn = apply_norm(cfg.norm, lp["ln1"], h, cfg.norm_eps)
        h = h + attention(lp["attn"], cfg, hn, pos, causal=False)
        hn = apply_norm(cfg.norm, lp["ln2"], h, cfg.norm_eps)
        return h + mlp(lp["mlp"], cfg, hn), None

    if cfg.remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return apply_norm(cfg.norm, params["enc_norm"], x, cfg.norm_eps)


def _dec_block(lp, cfg, x, pos, memory):
    hn = apply_norm(cfg.norm, lp["ln1"], x, cfg.norm_eps)
    x = x + attention(lp["attn"], cfg, hn, pos, causal=True)
    hn = apply_norm(cfg.norm, lp["ln_cross"], x, cfg.norm_eps)
    x = x + attention(lp["cross_attn"], cfg, hn, pos, causal=False, kv_x=memory)
    hn = apply_norm(cfg.norm, lp["ln2"], x, cfg.norm_eps)
    return x + mlp(lp["mlp"], cfg, hn)


def forward_encdec(cfg: ModelConfig, params, src_embeds, tgt_tokens):
    """Training forward: encode once, teacher-forced decoder.  → logits."""
    memory = encode(cfg, params, src_embeds)
    x = jnp.take(params["embed"]["table"], tgt_tokens, axis=0).astype(cfg.cdtype)
    B, S = x.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def body(h, lp):
        return _dec_block(lp, cfg, h, pos, memory), None

    if cfg.remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"]["w"].astype(cfg.cdtype),
                        preferred_element_type=jnp.float32)
    return shard_activation(logits, "dp", None, "model"), jnp.float32(0.0)


def init_cache_encdec(cfg: ModelConfig, batch: int, max_len: int, src_len: int):
    L, hkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((L, batch, max_len, hkv, hd), cfg.cdtype),
        "v": jnp.zeros((L, batch, max_len, hkv, hd), cfg.cdtype),
        "cross_k": jnp.zeros((L, batch, src_len, hkv, hd), cfg.cdtype),
        "cross_v": jnp.zeros((L, batch, src_len, hkv, hd), cfg.cdtype),
    }


def prefill_encdec(cfg: ModelConfig, params, src_embeds, tgt_tokens,
                   max_len: Optional[int] = None):
    """Encode + decoder prefill.  Returns (last_logits, cache)."""
    memory = encode(cfg, params, src_embeds)
    x = jnp.take(params["embed"]["table"], tgt_tokens, axis=0).astype(cfg.cdtype)
    B, S = x.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def body(h, lp):
        hn = apply_norm(cfg.norm, lp["ln1"], h, cfg.norm_eps)
        a, (k, v) = attention(lp["attn"], cfg, hn, pos, causal=True, return_kv=True)
        h = h + a
        hn = apply_norm(cfg.norm, lp["ln_cross"], h, cfg.norm_eps)
        c, (ck, cv) = attention(lp["cross_attn"], cfg, hn, pos, causal=False,
                                kv_x=memory, return_kv=True)
        h = h + c
        hn = apply_norm(cfg.norm, lp["ln2"], h, cfg.norm_eps)
        return h + mlp(lp["mlp"], cfg, hn), {"k": k, "v": v, "cross_k": ck, "cross_v": cv}

    x, cache = jax.lax.scan(body, x, params["layers"])
    if max_len is not None and max_len > S:
        pad = max_len - S
        cache["k"] = jnp.pad(cache["k"], ((0, 0),) * 2 + ((0, pad),) + ((0, 0),) * 2)
        cache["v"] = jnp.pad(cache["v"], ((0, 0),) * 2 + ((0, pad),) + ((0, 0),) * 2)
    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    logits = jnp.einsum("bd,dv->bv", x[:, -1], params["lm_head"]["w"].astype(cfg.cdtype),
                        preferred_element_type=jnp.float32)
    return logits, cache


def decode_step_encdec(cfg: ModelConfig, params, token, cache, lengths):
    """One decoder token with cached self-KV and cross-KV."""
    x = jnp.take(params["embed"]["table"], token[:, None], axis=0).astype(cfg.cdtype)

    def body(h, inp):
        lp, ck, cv, xk, xv = inp
        hn = apply_norm(cfg.norm, lp["ln1"], h, cfg.norm_eps)
        a, nk, nv = decode_attention(lp["attn"], cfg, hn, ck, cv, lengths)
        h = h + a
        hn = apply_norm(cfg.norm, lp["ln_cross"], h, cfg.norm_eps)
        # cross-attention against fixed memory KV (no cache update)
        c = _cross_decode(lp["cross_attn"], cfg, hn, xk, xv)
        h = h + c
        hn = apply_norm(cfg.norm, lp["ln2"], h, cfg.norm_eps)
        return h + mlp(lp["mlp"], cfg, hn), (nk, nv)

    x, (new_k, new_v) = jax.lax.scan(
        body, x,
        (params["layers"], cache["k"], cache["v"], cache["cross_k"], cache["cross_v"]),
    )
    new_cache = dict(cache, k=new_k, v=new_v)
    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    logits = jnp.einsum("bd,dv->bv", x[:, 0], params["lm_head"]["w"].astype(cfg.cdtype),
                        preferred_element_type=jnp.float32)
    return logits, new_cache


def _cross_decode(p, cfg, x, xk, xv):
    """Single-query cross-attention over precomputed memory KV."""
    import math
    cd = cfg.cdtype
    b = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x.astype(cd), p["wq"].astype(cd),
                   preferred_element_type=jnp.float32).astype(cd)
    hkv = xk.shape[2]
    g = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(b, 1, hkv, g, cfg.hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        xk.astype(jnp.float32)) / math.sqrt(cfg.hd)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(cd), xv)
    out = out.reshape(b, 1, cfg.n_heads, cfg.hd)
    return jnp.einsum("bshd,hdm->bsm", out.astype(cd), p["wo"].astype(cd),
                      preferred_element_type=jnp.float32).astype(cd)
