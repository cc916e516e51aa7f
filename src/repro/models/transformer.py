"""Decoder-only LM covering the dense / MoE / SSM / hybrid / VLM families.

Scan-over-layers everywhere: layer parameters are stacked along a leading
layer axis and the depth loop is one ``lax.scan`` — constant-size HLO
regardless of depth (61-layer MoE dry-runs compile in seconds) and the
idiomatic TPU form.  MoE archs keep their ``first_dense_layers`` in a
separate (smaller) stack, matching DeepSeekMoE/Kimi-K2.

Entry points:
  init_params(cfg, key)                       → params pytree
  forward(cfg, params, tokens | embeds, pos)  → (logits, aux_loss)
  prefill(cfg, params, tokens, pos)           → (logits, cache)
  decode_step(cfg, params, token, cache, len) → (logits, cache)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.query import async_query, table_gather_spec
from repro.distributed.sharding import shard_activation
from repro.models.attention import (
    attention,
    attn_params,
    decode_attention,
    init_kv_cache,
)
from repro.models.config import ModelConfig
from repro.models.layers import apply_norm, dense_init, embed_init, norm_params
from repro.models.mlp import mlp, mlp_params
from repro.models.moe import moe, moe_params
from repro.models.ssm import (
    init_ssm_state,
    ssm_decode_step,
    ssm_forward,
    ssm_params,
)

__all__ = [
    "init_params",
    "forward",
    "prefill",
    "decode_step",
    "init_cache",
    "block_kind",
]


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def block_kind(cfg: ModelConfig, moe_stack: bool) -> str:
    if cfg.family == "ssm":
        return "ssm"
    if cfg.hybrid:
        return "hybrid"
    if cfg.is_moe and moe_stack:
        return "moe"
    return "dense"


def _block_params(key, cfg: ModelConfig, kind: str) -> dict:
    ks = jax.random.split(key, 8)
    p: dict = {}
    if kind != "ssm":
        p["ln1"] = norm_params(cfg.norm, cfg.d_model, cfg.pdtype)
        p["attn"] = attn_params(ks[0], cfg)
        p["ln2"] = norm_params(cfg.norm, cfg.d_model, cfg.pdtype)
        if kind == "moe":
            p["moe"] = moe_params(ks[1], cfg)
        else:
            p["mlp"] = mlp_params(ks[1], cfg)
    else:
        p["ln1"] = norm_params(cfg.norm, cfg.d_model, cfg.pdtype)
    if kind in ("ssm", "hybrid"):
        p["ssm"] = ssm_params(ks[2], cfg)
        if kind == "hybrid":
            p["ssm_branch_norm"] = norm_params("rmsnorm", cfg.d_model, cfg.pdtype)
            p["attn_branch_norm"] = norm_params("rmsnorm", cfg.d_model, cfg.pdtype)
    return p


def _block_forward(p, cfg: ModelConfig, kind: str, x, positions):
    """Full-sequence block (training / prefill w/o cache).  → (x, aux)."""
    aux = jnp.float32(0.0)
    if kind == "ssm":
        h = apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
        x = x + ssm_forward(p["ssm"], cfg, h)
        return x, aux
    h = apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
    if kind == "hybrid":
        # Hymba [arXiv:2411.13676]: attention and SSM heads in parallel on
        # the same input; per-branch RMSNorm, then mean.
        a = attention(p["attn"], cfg, h, positions, causal=True)
        s = ssm_forward(p["ssm"], cfg, h)
        a = apply_norm("rmsnorm", p["attn_branch_norm"], a)
        s = apply_norm("rmsnorm", p["ssm_branch_norm"], s)
        x = x + 0.5 * (a + s)
    else:
        x = x + attention(p["attn"], cfg, h, positions, causal=True)
    h2 = apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
    if kind == "moe":
        y, aux = moe(p["moe"], cfg, h2)
    else:
        y = mlp(p["mlp"], cfg, h2)
    return x + y, aux


def _block_prefill(p, cfg: ModelConfig, kind: str, x, positions):
    """→ (x, aux, (k, v) or None).  SSM state from prefill is produced by
    running ssm_forward with return_state."""
    aux = jnp.float32(0.0)
    kv = None
    ssm_state = None
    if kind == "ssm":
        h = apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
        y, ssm_state = ssm_forward(p["ssm"], cfg, h, return_state=True)
        return x + y, aux, kv, ssm_state
    h = apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
    if kind == "hybrid":
        a, kv = attention(p["attn"], cfg, h, positions, causal=True, return_kv=True)
        s, ssm_state = ssm_forward(p["ssm"], cfg, h, return_state=True)
        a = apply_norm("rmsnorm", p["attn_branch_norm"], a)
        s = apply_norm("rmsnorm", p["ssm_branch_norm"], s)
        x = x + 0.5 * (a + s)
    else:
        a, kv = attention(p["attn"], cfg, h, positions, causal=True, return_kv=True)
        x = x + a
    h2 = apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
    y, aux = (moe(p["moe"], cfg, h2) if kind == "moe" else (mlp(p["mlp"], cfg, h2), aux))
    return x + y, aux, kv, ssm_state


def _block_decode(p, cfg: ModelConfig, kind: str, x, cache_slice, lengths):
    """One-token decode.  cache_slice: per-layer dict of cache arrays."""
    new_cache = dict(cache_slice)
    if kind == "ssm":
        h = apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
        y, s, c = ssm_decode_step(p["ssm"], cfg, h, cache_slice["ssm"], cache_slice["conv"])
        new_cache["ssm"], new_cache["conv"] = s, c
        return x + y, new_cache
    h = apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
    window = cfg.attn_window if cfg.attn_window > 0 else None
    if kind == "hybrid":
        a, ck, cv = decode_attention(
            p["attn"], cfg, h, cache_slice["k"], cache_slice["v"], lengths, window=window
        )
        s, st, cc = ssm_decode_step(p["ssm"], cfg, h, cache_slice["ssm"], cache_slice["conv"])
        a = apply_norm("rmsnorm", p["attn_branch_norm"], a)
        s = apply_norm("rmsnorm", p["ssm_branch_norm"], s)
        x = x + 0.5 * (a + s)
        new_cache.update(k=ck, v=cv, ssm=st, conv=cc)
    else:
        a, ck, cv = decode_attention(
            p["attn"], cfg, h, cache_slice["k"], cache_slice["v"], lengths, window=window
        )
        x = x + a
        new_cache.update(k=ck, v=cv)
    h2 = apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
    y = moe(p["moe"], cfg, h2)[0] if kind == "moe" else mlp(p["mlp"], cfg, h2)
    return x + y, new_cache


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key) -> dict:
    ke, kl, kd, kh, kf = jax.random.split(key, 5)
    n_moe = cfg.n_layers - cfg.first_dense_layers if cfg.is_moe else 0
    n_main = n_moe if cfg.is_moe else cfg.n_layers
    main_kind = block_kind(cfg, moe_stack=True)

    def stack_init(k, n, kind):
        keys = jax.random.split(k, n)
        return jax.vmap(lambda kk: _block_params(kk, cfg, kind))(keys)

    p = {
        "embed": {"table": embed_init(ke, cfg.vocab_size, cfg.d_model, cfg.pdtype)},
        "layers": stack_init(kl, n_main, main_kind),
        "final_norm": norm_params(cfg.norm, cfg.d_model, cfg.pdtype),
    }
    if cfg.is_moe and cfg.first_dense_layers > 0:
        p["dense_layers"] = stack_init(kd, cfg.first_dense_layers, "dense")
    if not cfg.tie_embeddings:
        p["lm_head"] = {"w": dense_init(kh, cfg.d_model, cfg.vocab_size, cfg.pdtype)}
    return p


def _embed(cfg: ModelConfig, params, tokens):
    table = params["embed"]["table"]
    if cfg.query_embedding:
        # the paper's "query": a per-step table lookup, batchable by fission
        emb = async_query(table_gather_spec, table, tokens)
    else:
        emb = jnp.take(table, tokens, axis=0)
    return emb.astype(cfg.cdtype)


def _head(cfg: ModelConfig, params, x):
    w = (
        params["embed"]["table"].T
        if cfg.tie_embeddings
        else params["lm_head"]["w"]
    )
    logits = jnp.einsum(
        "bsd,dv->bsv", x.astype(cfg.cdtype), w.astype(cfg.cdtype),
        preferred_element_type=jnp.float32,
    )
    return shard_activation(logits, "dp", None, "model")


def _layer_stacks(cfg: ModelConfig, params):
    """[(stacked_params, kind, n_layers)] in execution order."""
    out = []
    if cfg.is_moe and cfg.first_dense_layers > 0:
        out.append((params["dense_layers"], "dense", cfg.first_dense_layers))
    n_main = cfg.n_layers - (cfg.first_dense_layers if cfg.is_moe else 0)
    out.append((params["layers"], block_kind(cfg, True), n_main))
    return out


def _run_stack(cfg, stacked, kind, x, positions, mode, cache=None, lengths=None):
    """Scan one layer stack.  mode: 'forward' | 'prefill' | 'decode'."""

    if mode == "forward":

        def body(h, lp):
            h, aux = _block_forward(lp, cfg, kind, h, positions)
            return h, aux

        if cfg.remat:
            body = jax.checkpoint(body)
        x, auxs = jax.lax.scan(body, x, stacked)
        return x, auxs.sum(), None

    if mode == "prefill":

        def body(h, lp):
            h, aux, kv, ssm_state = _block_prefill(lp, cfg, kind, h, positions)
            ys = {}
            if kv is not None:
                ys["k"], ys["v"] = kv
            if ssm_state is not None:
                ys.update(ssm_state)  # {"ssm": ..., "conv": ...}
            return h, (aux, ys)

        if cfg.remat:
            body = jax.checkpoint(body)
        x, (auxs, cache_out) = jax.lax.scan(body, x, stacked)
        return x, auxs.sum(), cache_out

    # decode
    def body(h, inp):
        lp, cache_slice = inp
        h, new_slice = _block_decode(lp, cfg, kind, h, cache_slice, lengths)
        return h, new_slice

    x, new_cache = jax.lax.scan(body, x, (stacked, cache))
    return x, jnp.float32(0.0), new_cache


def forward(cfg: ModelConfig, params, tokens=None, positions=None, embeds=None):
    """Training forward.  tokens (B,S) int32 or embeds (B,S,d) for stub
    frontends.  → (logits (B,S,V) fp32, aux_loss)."""
    x = _embed(cfg, params, tokens) if embeds is None else embeds.astype(cfg.cdtype)
    B, S = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = shard_activation(x, "dp", None, None)
    aux_total = jnp.float32(0.0)
    for stacked, kind, _n in _layer_stacks(cfg, params):
        x, aux, _ = _run_stack(cfg, stacked, kind, x, positions, "forward")
        aux_total = aux_total + aux
    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    return _head(cfg, params, x), aux_total


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Stacked decode cache for every stack, keyed by stack name."""
    caches = {}
    for name, kind, n in _stack_names(cfg):
        c: dict = {}
        if kind in ("dense", "moe", "hybrid"):
            kv_len = min(max_len, cfg.attn_window) if cfg.attn_window > 0 else max_len
            kv = init_kv_cache(cfg, batch, kv_len, n_layers=n)
            c["k"], c["v"] = kv["k"], kv["v"]
        if kind in ("ssm", "hybrid"):
            s = init_ssm_state(cfg, batch, n_layers=n)
            c["ssm"], c["conv"] = s["ssm"], s["conv"]
        caches[name] = c
    return caches


def _stack_names(cfg: ModelConfig):
    out = []
    if cfg.is_moe and cfg.first_dense_layers > 0:
        out.append(("dense_layers", "dense", cfg.first_dense_layers))
    n_main = cfg.n_layers - (cfg.first_dense_layers if cfg.is_moe else 0)
    out.append(("layers", block_kind(cfg, True), n_main))
    return out


def prefill(cfg: ModelConfig, params, tokens=None, positions=None, embeds=None,
            max_len: Optional[int] = None, return_all_logits: bool = False):
    """Full-sequence prefill.  → (logits (B,V) at the last position — or
    (B,S,V) with ``return_all_logits`` for right-padded serving batches —
    and the cache).

    ``max_len`` pads the KV cache to the decode capacity (serving); windowed
    caches are re-laid out as ring buffers of size ``cfg.attn_window``.
    Right-padded prompts are safe: causal masking keeps pad keys invisible
    to real queries, and decode overwrites pad KV slots before attending
    them (per-lane ``lengths`` gate validity).
    """
    x = _embed(cfg, params, tokens) if embeds is None else embeds.astype(cfg.cdtype)
    B, S = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = shard_activation(x, "dp", None, None)
    caches = {}
    for (name, kind, _n), (stacked, kind2, _n2) in zip(
        _stack_names(cfg), _layer_stacks(cfg, params)
    ):
        x, _aux, cache_out = _run_stack(cfg, stacked, kind, x, positions, "prefill")
        c = dict(cache_out or {})
        if kind in ("dense", "moe", "hybrid"):
            if cfg.attn_window > 0:
                # Ring-buffer re-layout: keep the last W tokens, placing
                # token p at slot p % W (what decode expects).
                W = cfg.attn_window
                if S >= W:
                    lk, lv = c["k"][:, :, -W:], c["v"][:, :, -W:]
                    shift = S % W
                    c["k"] = jnp.roll(lk, shift, axis=2)
                    c["v"] = jnp.roll(lv, shift, axis=2)
                else:  # S < W: slots p = p, pad tail
                    pad = W - S
                    c["k"] = jnp.pad(c["k"], ((0, 0),) * 2 + ((0, pad),) + ((0, 0),) * 2)
                    c["v"] = jnp.pad(c["v"], ((0, 0),) * 2 + ((0, pad),) + ((0, 0),) * 2)
            elif max_len is not None and max_len > S:
                pad = max_len - S
                c["k"] = jnp.pad(c["k"], ((0, 0),) * 2 + ((0, pad),) + ((0, 0),) * 2)
                c["v"] = jnp.pad(c["v"], ((0, 0),) * 2 + ((0, pad),) + ((0, 0),) * 2)
        caches[name] = c
    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    if return_all_logits:
        return _head(cfg, params, x), caches
    logits = _head(cfg, params, x[:, -1:])[:, 0]
    return logits, caches


def decode_step(cfg: ModelConfig, params, token, cache: dict, lengths):
    """token (B,) int32, lengths (B,) int32 → (logits (B,V), new_cache)."""
    x = _embed(cfg, params, token[:, None])
    new_caches = {}
    for (name, kind, _n), (stacked, _k2, _n2) in zip(
        _stack_names(cfg), _layer_stacks(cfg, params)
    ):
        x, _aux, new_c = _run_stack(
            cfg, stacked, kind, x, None, "decode", cache=cache[name], lengths=lengths
        )
        new_caches[name] = new_c
    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    logits = _head(cfg, params, x)[:, 0]
    return logits, new_caches
