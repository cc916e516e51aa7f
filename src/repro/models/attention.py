"""Grouped-query attention with RoPE variants, KV cache, sliding window.

Pure-jnp reference implementation used by training, prefill and decode.
The Pallas kernels in ``repro.kernels`` implement the same math
(``flash_attention`` for prefill, ``decode_attention`` for decode) and are
validated against this module; on TPU the serving/training step builders can
swap them in via ``repro.kernels.ops``.

Shapes:
  x          (B, S, d_model)
  q          (B, S, H, hd)      k/v (B, S, Hkv, hd)
  cache k/v  (B, S_max, Hkv, hd)
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_activation
from repro.models.config import ModelConfig
from repro.models.layers import (
    apply_mrope,
    apply_rope,
    apply_rope_half,
    dense_init,
)

__all__ = ["attn_params", "attention", "decode_attention", "init_kv_cache",
           "query_chunk"]

NEG_INF = -1e30


def attn_params(key, cfg: ModelConfig, cross: bool = False) -> dict:
    d, hd = cfg.d_model, cfg.hd
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": dense_init(kq, d, cfg.n_heads * hd, cfg.pdtype).reshape(d, cfg.n_heads, hd),
        "wk": dense_init(kk, d, cfg.n_kv_heads * hd, cfg.pdtype).reshape(d, cfg.n_kv_heads, hd),
        "wv": dense_init(kv, d, cfg.n_kv_heads * hd, cfg.pdtype).reshape(d, cfg.n_kv_heads, hd),
        "wo": dense_init(ko, cfg.n_heads * hd, d, cfg.pdtype).reshape(cfg.n_heads, hd, d),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads, hd), cfg.pdtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads, hd), cfg.pdtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads, hd), cfg.pdtype)
    return p


def _project_qkv(p, cfg: ModelConfig, x, kv_x=None):
    cd = cfg.cdtype
    kv_x = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x.astype(cd), p["wq"].astype(cd),
                   preferred_element_type=jnp.float32).astype(cd)
    k = jnp.einsum("bsd,dhk->bshk", kv_x.astype(cd), p["wk"].astype(cd),
                   preferred_element_type=jnp.float32).astype(cd)
    v = jnp.einsum("bsd,dhk->bshk", kv_x.astype(cd), p["wv"].astype(cd),
                   preferred_element_type=jnp.float32).astype(cd)
    if "bq" in p:
        q = q + p["bq"].astype(cd)
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    return q, k, v


def _rope(cfg: ModelConfig, q, k, positions):
    if cfg.rope == "standard":
        return apply_rope(q, k, positions, cfg.rope_theta)
    if cfg.rope == "half":
        return apply_rope_half(q, k, positions, cfg.rope_theta)
    if cfg.rope == "mrope":
        pos3 = positions if positions.ndim == 3 else jnp.broadcast_to(
            positions[None], (3,) + positions.shape
        )
        return apply_mrope(q, k, pos3, cfg.rope_theta, cfg.mrope_sections)
    if cfg.rope == "none":
        return q, k
    raise ValueError(cfg.rope)


def query_chunk(cfg: ModelConfig, s: int) -> int:
    """Query rows per chunk of full-sequence attention over ``s`` positions:
    ``cfg.attn_chunk`` when that splits them, else 0 (one unchunked block,
    whose scores are at most ``attn_chunk`` x ``s`` when chunking is on).
    The chunks run are ``ceil(s / query_chunk)``; the last is padded."""
    c = cfg.attn_chunk
    return c if c and s > c else 0


def _gqa_scores(q, k):
    """(B,S,H,hd) x (B,T,Hkv,hd) -> (B,Hkv,G,S,T) with G = H // Hkv."""
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, s, hkv, g, hd)
    return jnp.einsum(
        "bskgd,btkd->bkgst", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) / math.sqrt(hd)


def attention(
    p: dict,
    cfg: ModelConfig,
    x,
    positions,
    *,
    causal: bool = True,
    kv_x=None,
    kv_positions=None,
    return_kv: bool = False,
):
    """Full-sequence attention (training / prefill / encoder / cross).

    ``kv_x`` != None → cross-attention (no RoPE on cross, per seamless-m4t).
    Sliding-window mask applied when ``cfg.attn_window > 0`` and causal.
    """
    cd = cfg.cdtype
    q, k, v = _project_qkv(p, cfg, x, kv_x)
    if kv_x is None and cfg.rope != "none":
        q, k = _rope(cfg, q, k, positions)
    q = shard_activation(q, "dp", None, "model", None)
    k = shard_activation(k, "dp", None, "model", None)

    b, s, h, hd = q.shape
    hkv = k.shape[2]
    qpos = None
    if causal and kv_x is None:
        qpos = positions if positions.ndim == 2 else positions[0]

    C = query_chunk(cfg, s)
    if C:
        # Chunked (flash-style) scores: scan query blocks so the biggest
        # intermediate is (B,Hkv,G,C,S) instead of (B,Hkv,G,S,S).  Exact —
        # softmax rows are independent, so the query rows are padded up to
        # whole chunks and the padded rows' output is dropped; the keys
        # stay as they are.
        g = h // hkv
        n = -(-s // C)
        pad = n * C - s
        qg = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
        qc = jnp.moveaxis(qg.reshape(b, n, C, hkv, g, hd), 1, 0)
        if qpos is not None:
            pp = jnp.pad(qpos, ((0, 0), (0, pad))) if pad else qpos
            pc = jnp.moveaxis(pp.reshape(b, n, C), 1, 0)
        else:
            pc = jnp.zeros((n, b, C), jnp.int32)
        kpos = qpos

        def chunk_fn(_, inp):
            q_blk, p_blk = inp  # (b,C,hkv,g,hd), (b,C)
            sc = jnp.einsum(
                "bskgd,btkd->bkgst", q_blk.astype(jnp.float32),
                k.astype(jnp.float32)) / math.sqrt(hd)
            if qpos is not None:
                m = p_blk[:, None, None, :, None] >= kpos[:, None, None, None, :]
                if cfg.attn_window > 0:
                    m &= (p_blk[:, None, None, :, None]
                          - kpos[:, None, None, None, :]) < cfg.attn_window
                sc = jnp.where(m, sc, NEG_INF)
            pr = jax.nn.softmax(sc, axis=-1)
            o = jnp.einsum("bkgst,btkd->bskgd", pr.astype(cd), v)
            return _, o.reshape(b, q_blk.shape[1], h, hd)

        _, out_c = jax.lax.scan(chunk_fn, 0, (qc, pc))
        out = jnp.moveaxis(out_c, 0, 1).reshape(b, n * C, h, hd)
        if pad:
            out = out[:, :s]
    else:
        scores = _gqa_scores(q, k)  # (B,Hkv,G,S,T)
        if qpos is not None:
            kpos = qpos
            m = qpos[:, None, None, :, None] >= kpos[:, None, None, None, :]
            if cfg.attn_window > 0:
                m &= (
                    qpos[:, None, None, :, None] - kpos[:, None, None, None, :]
                    < cfg.attn_window
                )
            scores = jnp.where(m, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(cd), v)
        out = out.reshape(b, s, h, hd)
    y = jnp.einsum("bshd,hdm->bsm", out.astype(cd), p["wo"].astype(cd),
                   preferred_element_type=jnp.float32).astype(cd)
    y = shard_activation(y, "dp", None, None)
    if return_kv:
        return y, (k, v)
    return y


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, n_layers=None):
    """Stacked-over-layers KV cache (L, B, S, Hkv, hd)."""
    L = n_layers if n_layers is not None else cfg.n_layers
    shape = (L, batch, max_len, cfg.n_kv_heads, cfg.hd)
    return {
        "k": jnp.zeros(shape, cfg.cdtype),
        "v": jnp.zeros(shape, cfg.cdtype),
    }


def decode_attention(
    p: dict,
    cfg: ModelConfig,
    x,                      # (B, 1, d_model)
    cache_k,                # (B, S_max, Hkv, hd) — this layer's slice
    cache_v,
    lengths,                # (B,) int32: current context length per request
    *,
    window: Optional[int] = None,
):
    """One-token decode with KV-cache append.

    The new KV is written at ``lengths % S_max`` (a ring buffer when
    ``window`` is set — hymba's sliding-window layers — and a plain append
    otherwise).  Attention masks out slots ≥ length (or outside the window).
    Returns (y, new_cache_k, new_cache_v).
    """
    cd = cfg.cdtype
    b, one, d = x.shape
    s_max = cache_k.shape[1]
    q, k_new, v_new = _project_qkv(p, cfg, x)
    if cfg.rope != "none":
        rope_pos = lengths[:, None]  # (B,1) true positions
        if cfg.rope == "mrope":
            rope_pos = jnp.broadcast_to(rope_pos[None], (3, b, 1))
        q, k_new = _rope(cfg, q, k_new, rope_pos)

    slot = (lengths % s_max)[:, None] if window else jnp.minimum(lengths, s_max - 1)[:, None]
    # Scatter-update ONE slot per lane (O(B·Hkv·hd) traffic, in-place with
    # buffer donation).  The earlier one_hot read-modify-write streamed the
    # whole cache per step AND invited GSPMD to reshard it (a 2×34 GiB
    # all-gather appeared in the decode HLO) — see EXPERIMENTS.md §Perf.
    b_ix = jnp.arange(b)[:, None]
    cache_k = cache_k.at[b_ix, slot].set(k_new.astype(cache_k.dtype))
    cache_v = cache_v.at[b_ix, slot].set(v_new.astype(cache_v.dtype))

    scores = _gqa_scores(q, cache_k)  # (B,Hkv,G,1,S_max)
    idx = jnp.arange(s_max)
    if window:
        # ring buffer: valid slots are the last `window` positions
        valid = (idx[None, :] * 0 + 1).astype(bool)
        age = (slot[:, :1] - idx[None, :]) % s_max  # distance backwards
        valid = age < jnp.minimum(lengths + 1, window)[:, None]
    else:
        valid = idx[None, :] <= jnp.minimum(lengths, s_max - 1)[:, None]
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    hkv, g = cache_k.shape[2], cfg.n_heads // cfg.n_kv_heads
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(cd), cache_v)
    out = out.reshape(b, 1, cfg.n_heads, cfg.hd)
    y = jnp.einsum("bshd,hdm->bsm", out.astype(cd), p["wo"].astype(cd),
                   preferred_element_type=jnp.float32).astype(cd)
    return y, cache_k, cache_v
