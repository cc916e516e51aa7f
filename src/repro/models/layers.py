"""Shared layers: norms, rotary-embedding variants, initializers.

All computation helpers are pure functions over explicit parameter pytrees
(dicts of jnp arrays) — no framework.  Norms and softmax run in fp32
regardless of the compute dtype (bf16-safe numerics).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "dense_init",
    "embed_init",
    "rmsnorm",
    "layernorm",
    "nonparam_ln",
    "apply_norm",
    "norm_params",
    "rope_freqs",
    "apply_rope",
    "apply_rope_half",
    "apply_mrope",
    "linear",
]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def dense_init(key, in_dim: int, out_dim: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim)) * scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype):
    return (jax.random.normal(key, (vocab, dim)) * 0.02).astype(dtype)


def linear(x, w, b=None, compute_dtype=None):
    """x @ w (+ b) with fp32 accumulation on the MXU."""
    cd = compute_dtype or x.dtype
    y = jnp.einsum(
        "...d,df->...f",
        x.astype(cd),
        w.astype(cd),
        preferred_element_type=jnp.float32,
    ).astype(cd)
    if b is not None:
        y = y + b.astype(cd)
    return y


# ---------------------------------------------------------------------------
# norms (fp32 internals)
# ---------------------------------------------------------------------------


def rmsnorm(x, weight, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    nrm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (nrm * weight.astype(jnp.float32)).astype(dt)


def layernorm(x, weight, bias, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def nonparam_ln(x, eps: float = 1e-5):
    """OLMo's non-parametric LayerNorm (no scale/bias) [arXiv:2402.00838]."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(dt)


def norm_params(kind: str, dim: int, dtype):
    if kind == "rmsnorm":
        return {"w": jnp.ones((dim,), dtype)}
    if kind == "layernorm":
        return {"w": jnp.ones((dim,), dtype), "b": jnp.zeros((dim,), dtype)}
    if kind == "nonparam_ln":
        return {}
    raise ValueError(kind)


def apply_norm(kind: str, p, x, eps: float = 1e-6):
    """The norm ``kind`` of ``x``; ``eps`` is RMSNorm's (the LayerNorms
    keep their own 1e-5)."""
    if kind == "rmsnorm":
        return rmsnorm(x, p["w"], eps)
    if kind == "layernorm":
        return layernorm(x, p["w"], p["b"])
    if kind == "nonparam_ln":
        return nonparam_ln(x)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# rotary position embeddings — three published variants
# ---------------------------------------------------------------------------


def rope_freqs(dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies for a rotary dim (must be even)."""
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def _rotate(x, cos, sin):
    # x: (..., rot_dim) pairs interleaved as [x0, x1] halves convention
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def apply_rope(q, k, positions, theta: float):
    """Standard RoPE [arXiv:2104.09864] over the full head dim.

    q: (B, S, H, D), k: (B, S, Hkv, D), positions: (B, S) int32.
    """
    d = q.shape[-1]
    inv = rope_freqs(d, theta)  # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * inv  # (B, S, d/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    return (
        _rotate(q.astype(jnp.float32), cos, sin).astype(q.dtype),
        _rotate(k.astype(jnp.float32), cos, sin).astype(k.dtype),
    )


def apply_rope_half(q, k, positions, theta: float):
    """ChatGLM's 2D RoPE: rotary on the first half of the head dim only
    [arXiv:2406.12793 / GLM lineage].

    The rotated half pairs channel i with i + d/4, as :func:`apply_rope`
    does over a whole head.  ChatGLM's own code rotates interleaved pairs
    (2i, 2i + 1) of that half instead; the two are the same function once
    the rotated channels of ``wq``, ``wk``, ``bq`` and ``bk`` are permuted
    (evens first, then odds), so weights drawn here, or converted with that
    permutation, serve ChatGLM exactly.
    """
    d = q.shape[-1]
    rot = d // 2
    q1, q2 = q[..., :rot], q[..., rot:]
    k1, k2 = k[..., :rot], k[..., rot:]
    q1r, k1r = apply_rope(q1, k1, positions, theta)
    return (
        jnp.concatenate([q1r, q2], axis=-1),
        jnp.concatenate([k1r, k2], axis=-1),
    )


def apply_mrope(q, k, positions3, theta: float, sections: Tuple[int, ...]):
    """Qwen2-VL M-RoPE [arXiv:2409.12191]: the rotary dim is partitioned into
    (temporal, height, width) sections, each rotated by its own position id.

    positions3: (3, B, S) int32 — for text tokens all three rows are equal,
    so M-RoPE degenerates to standard RoPE (as in the paper).
    """
    d = q.shape[-1]
    assert sum(sections) == d // 2, (sections, d)
    inv = rope_freqs(d, theta)  # (d/2,)
    # Build per-frequency position selector from the sections.
    sec_id = jnp.concatenate(
        [jnp.full((s,), i, jnp.int32) for i, s in enumerate(sections)]
    )  # (d/2,)
    # select positions3[sec_id[f]] per frequency f:
    # ang[b, s, f] = positions3[sec_id[f], b, s] * inv[f]
    p = positions3.astype(jnp.float32)  # (3, B, S)
    ang = jnp.einsum("kbs,fk->bsf", p, jax.nn.one_hot(sec_id, 3, dtype=jnp.float32))
    ang = ang * inv[None, None, :]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    return (
        _rotate(q.astype(jnp.float32), cos, sin).astype(q.dtype),
        _rotate(k.astype(jnp.float32), cos, sin).astype(k.dtype),
    )
