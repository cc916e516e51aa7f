"""One-token decode reading KV directly from a paged physical pool.

Mirrors :func:`repro.models.transformer.decode_step` exactly — same embed,
norms, residuals, MLP/MoE blocks and head — while replacing the dense
per-lane KV cache ``(L, B, S_max, Hkv, hd)`` with shared physical page
arrays ``(L, P, page_size, Hkv, hd)`` addressed through per-request block
tables (the vLLM PagedAttention layout).  Attention goes through
``repro.kernels.registry.dispatch("paged_decode_attention", ...)``: the
Pallas kernel runs on TPU (or under interpret mode), the pure-jnp paged
reference everywhere else — the registry's one dispatch policy, so the
serving engine never re-implements the fallback dance.

Numerical contract: for lanes marked ``active``, the logits are the same
computation the dense path performs — the gather of a lane's pages in
logical order reproduces its dense cache rows exactly, and the masking
(``kpos < length + 1``) admits exactly the rows dense decode admits — so
greedy decode over paged KV is bit-identical at the token level.

Physical page ``P - 1`` is a **trash page**: inactive lanes' KV scatter
writes are routed there, so a fully-batched decode step can never corrupt
a page owned by an active request.  Block tables never reference it, and
the pool (``serving/paged_kv.py``) never allocates it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.models.attention import _project_qkv, _rope
from repro.models.config import ModelConfig
from repro.models.layers import apply_norm
from repro.models.mlp import mlp
from repro.models.moe import moe
from repro.models.transformer import (
    _embed,
    _head,
    _layer_stacks,
    _stack_names,
)

__all__ = ["supports_paged_decode", "paged_decode_step", "sample_tokens"]


def sample_tokens(logits, temps, seeds, lengths):
    """Per-lane next-token selection for the cross-template megabatch.

    One decode dispatch now covers every active lane regardless of
    template, so sampling parameters ride along per lane instead of per
    dispatch: ``temps``/``seeds`` are (B,) float32/int32.  Temperature-0
    lanes take the greedy argmax — bit-identical to the dense engine's
    ``jnp.argmax`` path.  Positive-temperature lanes draw from the
    temperature-scaled categorical under a counter-based per-lane key
    ``fold_in(fold_in(key0, seed), length)``: keyed on the request's own
    *position* (not a global step counter), so the draw at a given token
    index reproduces bit-identically across spill/restore, lane
    reassignment and batch composition changes.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw(seed, length, lg, t):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(0), seed), length)
        return jax.random.categorical(key, lg / jnp.maximum(t, 1e-6))

    sampled = jax.vmap(draw)(seeds, lengths, logits, temps).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def supports_paged_decode(cfg: ModelConfig) -> bool:
    """Whether this config's decode math can run over paged KV.

    Paged decode covers full-context dense/MoE attention stacks —
    sliding-window (ring-buffer) layers, SSM/hybrid state and
    encoder-decoder cross KV keep the dense per-lane layout.
    """
    if cfg.is_encoder_decoder or cfg.attn_window > 0:
        return False
    return all(kind in ("dense", "moe")
               for _name, kind, _n in _stack_names(cfg))


def _paged_attention(p, cfg: ModelConfig, x, k_pool, v_pool, layer,
                     block_tables, lengths, active, *, use_kernel: bool,
                     interpret: bool):
    """One-token GQA attention over pages; mirrors ``decode_attention``.

    ``x`` is (B, 1, d_model); ``k_pool``/``v_pool`` are the whole stacked
    (L, P, page_size, Hkv, hd) physical pools (slot ``P - 1`` is the trash
    page) and ``layer`` the int32 index of this layer in them;
    ``block_tables`` is (B, max_pages) int32; ``lengths`` (B,) int32;
    ``active`` (B,) bool.  The new token's KV is scattered into
    ``[layer, page, offset]`` of the page backing logical position
    ``min(length, s_max - 1)`` for active lanes (trash page otherwise),
    then attention reads positions ``[0, length]`` of ``layer`` through
    the registry's paged kernel/ref pair.  Returns ``(y, k_pool, v_pool)``:
    the pools are updated in place, never sliced per layer.
    """
    cd = cfg.cdtype
    b = x.shape[0]
    n_phys, ps = k_pool.shape[1], k_pool.shape[2]
    s_max = block_tables.shape[1] * ps
    q, k_new, v_new = _project_qkv(p, cfg, x)
    if cfg.rope != "none":
        rope_pos = lengths[:, None]  # (B, 1) true positions
        if cfg.rope == "mrope":
            rope_pos = jnp.broadcast_to(rope_pos[None], (3, b, 1))
        q, k_new = _rope(cfg, q, k_new, rope_pos)

    # Same write position as the dense path (min(lengths, s_max-1)),
    # translated to (physical page, in-page offset).  Inactive lanes write
    # the trash page so the batched scatter cannot clobber live pages.
    slot = jnp.minimum(lengths, s_max - 1)
    logical = slot // ps
    phys = jnp.where(active, block_tables[jnp.arange(b), logical], n_phys - 1)
    off = slot % ps
    k_pool = k_pool.at[layer, phys, off].set(k_new[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[layer, phys, off].set(v_new[:, 0].astype(v_pool.dtype))

    # Valid rows per lane: [0, length] inclusive of the token just written
    # (identical to the dense mask idx <= min(lengths, s_max-1)); inactive
    # lanes attend nothing and their output rows are discarded.
    att_len = jnp.where(active, jnp.minimum(lengths + 1, s_max), 0)
    out = registry.dispatch(
        "paged_decode_attention",
        (q[:, 0], k_pool, v_pool, block_tables, att_len),
        common={"layer": layer}, use_kernel=use_kernel, interpret=interpret)
    out = out.reshape(b, 1, cfg.n_heads, cfg.hd)
    y = jnp.einsum("bshd,hdm->bsm", out.astype(cd), p["wo"].astype(cd),
                   preferred_element_type=jnp.float32).astype(cd)
    return y, k_pool, v_pool


def _paged_block_decode(p, cfg: ModelConfig, kind: str, x, k_pool, v_pool,
                        layer, block_tables, lengths, active, *,
                        use_kernel: bool, interpret: bool):
    """One transformer block's decode step over paged KV (dense/moe only)."""
    h = apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
    a, k_pool, v_pool = _paged_attention(
        p["attn"], cfg, h, k_pool, v_pool, layer, block_tables, lengths,
        active, use_kernel=use_kernel, interpret=interpret)
    x = x + a
    h2 = apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
    y = moe(p["moe"], cfg, h2)[0] if kind == "moe" else mlp(p["mlp"], cfg, h2)
    return x + y, k_pool, v_pool


def paged_decode_step(cfg: ModelConfig, params, token, cache: dict,
                      block_tables, lengths, active, *,
                      use_kernel: bool = True, interpret: bool = False):
    """Batched one-token decode over paged KV.

    ``token``/``lengths`` are (B,) int32, ``active`` (B,) bool; ``cache``
    is ``{stack: {"k": (L, P, ps, Hkv, hd), "v": ...}}`` and
    ``block_tables`` (B, max_pages) int32 shared by every layer.  Returns
    ``(logits (B, V), new_cache)`` — the same contract as
    :func:`~repro.models.transformer.decode_step`, over pages.  Each
    stack's pools ride the layer scan's carry, so under a jit that donates
    ``cache`` the step writes its tokens into the pools in place.
    """
    x = _embed(cfg, params, token[:, None])
    new_caches = {}
    for (name, kind, n), (stacked, _k2, _n2) in zip(
        _stack_names(cfg), _layer_stacks(cfg, params)
    ):
        def body(carry, inp, kind=kind):
            h, kp, vp = carry
            lp, li = inp
            return _paged_block_decode(
                lp, cfg, kind, h, kp, vp, li, block_tables, lengths, active,
                use_kernel=use_kernel, interpret=interpret), None

        (x, kp, vp), _ = jax.lax.scan(
            body, (x, cache[name]["k"], cache[name]["v"]),
            (stacked, jnp.arange(n, dtype=jnp.int32)))
        new_caches[name] = {"k": kp, "v": vp}
    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    logits = _head(cfg, params, x)[:, 0]
    return logits, new_caches
