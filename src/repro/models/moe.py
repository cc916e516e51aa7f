"""Mixture-of-Experts layer — DeepSeekMoE-style fine-grained experts with
shared experts [arXiv:2401.06066], used by deepseek-moe-16b (64e top-6,
2 shared) and kimi-k2 (384e top-8, 1 shared) [arXiv:2501.kimi2].

TPU-native dispatch (GShard/Switch capacity model, scatter form), hardened
through three §Perf iterations (full log in EXPERIMENTS.md):

  B1  a combine that *gathers* eo[b, e_ix, c_ix] across the EP-sharded
      expert axis made GSPMD materialize a replicated (B,S,K,d) tensor and
      all-reduce 1.4 TB per site — replaced by an inverse-map scatter-add;
  B2  sharding constraints on the zero-filled scatter targets are folded
      away with the constant, so GSPMD still replicated the dispatch — the
      lesson: *constraint propagation cannot express masked-local scatter*;
  B3  the dispatch/expert/combine block therefore runs under an explicit
      ``shard_map`` over (dp × model): every device scatters only the
      tokens routed to ITS experts (out-of-range expert ids fall out of
      bounds and are dropped — locality for free), computes its expert FFNs,
      scatter-adds partial token outputs, and ONE ``psum`` over ``model``
      combines them.  Per layer the only collective is that (B_loc, S, d)
      all-reduce — the all-to-all-equivalent floor for capacity-style MoE.

Routing runs in fp32; the Switch-style load-balance aux loss is returned
for training.  Without an ambient mesh (smoke tests, single device) the
same local function runs over the full expert range (e_offset=0, psum
skipped) — one code path, two execution layouts.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_mesh, shard_activation
from repro.models.config import ModelConfig
from repro.models.layers import dense_init

__all__ = ["moe_params", "moe"]


def moe_params(key, cfg: ModelConfig) -> dict:
    d, ff, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    kr, ke, ks = jax.random.split(key, 3)

    def expert_stack(k, n):
        k1, k2, k3 = jax.random.split(k, 3)
        s = 1.0 / math.sqrt(d)
        return {
            "w_gate": (jax.random.normal(k1, (n, d, ff)) * s).astype(cfg.pdtype),
            "w_in": (jax.random.normal(k2, (n, d, ff)) * s).astype(cfg.pdtype),
            "w_out": (jax.random.normal(k3, (n, ff, d)) * (1.0 / math.sqrt(ff))).astype(cfg.pdtype),
        }

    p = {
        "router": {"w": dense_init(kr, d, E, jnp.float32)},
        "experts": expert_stack(ke, E),
    }
    if cfg.n_shared_experts > 0:
        p["shared"] = expert_stack(ks, cfg.n_shared_experts)
    return p


def _capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    c = int(math.ceil(tokens_per_group * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4, floor 4


def _route(router_w, cfg: ModelConfig, x, C):
    """fp32 routing → (expert_idx, gate_vals, pos).  Deterministic given x,
    so every model-shard computes identical assignments (no comm)."""
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    # position-in-expert: exclusive running count over the (S·K) stream
    flat_idx = expert_idx.reshape(B, S * K)
    onehot = jax.nn.one_hot(flat_idx, E, dtype=jnp.int32)
    pos_all = jnp.cumsum(onehot, axis=1) - onehot
    pos = jnp.take_along_axis(pos_all, flat_idx[..., None], axis=-1)[..., 0]
    pos = pos.reshape(B, S, K)
    gate_vals = gate_vals * (pos < C).astype(jnp.float32)
    return probs, expert_idx, gate_vals, pos


def _experts_local(weights, cfg, x, expert_idx, gate_vals, pos, C,
                   e_offset, E_loc):
    """Dispatch→FFN→combine for experts [e_offset, e_offset+E_loc).

    Locality trick: expert ids are shifted by -e_offset; ids outside
    [0, E_loc) (another shard's experts) go OUT OF BOUNDS and XLA's
    mode="drop" discards them — masked-local scatter with no mask tensor.
    Over-capacity positions (pos ≥ C) drop the same way.
    Returns the f32 partial (B,S,d); summing over shards = full MoE.
    """
    cd = cfg.cdtype
    B, S, d = x.shape
    K = cfg.top_k
    b_ix = jnp.arange(B)[:, None, None]
    e_loc = expert_idx - e_offset  # OOB for other shards' experts
    xk = jnp.broadcast_to(x[:, :, None, :], (B, S, K, d)).astype(cd)

    buf = jnp.zeros((B, E_loc, C, d), cd).at[b_ix, e_loc, pos].add(xk, mode="drop")

    w_gate, w_in, w_out = (weights[k].astype(cd) for k in ("w_gate", "w_in", "w_out"))
    g = jnp.einsum("becd,edf->becf", buf, w_gate, preferred_element_type=jnp.float32).astype(cd)
    h = jnp.einsum("becd,edf->becf", buf, w_in, preferred_element_type=jnp.float32).astype(cd)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(cd) * h
    eo = jnp.einsum("becf,efd->becd", h, w_out, preferred_element_type=jnp.float32)

    # inverse maps: which token fills each (e, c) slot, with which gate
    s_ids = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :, None], (B, S, K))
    token_of = jnp.zeros((B, E_loc, C), jnp.int32).at[b_ix, e_loc, pos].set(
        s_ids, mode="drop")
    gate_of = jnp.zeros((B, E_loc, C), jnp.float32).at[b_ix, e_loc, pos].set(
        gate_vals, mode="drop")
    weighted = eo.astype(jnp.float32) * gate_of[..., None]
    b_full = jnp.arange(B)[:, None, None]
    y = jnp.zeros((B, S, d), jnp.float32).at[b_full, token_of].add(weighted)
    return y


def moe(p: dict, cfg: ModelConfig, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) → (y, aux_loss)."""
    cd = cfg.cdtype
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = _capacity(cfg, S)

    # aux loss on the full (replicated-routing) probabilities
    probs, expert_idx, gate_vals, pos = _route(p["router"]["w"], cfg, x, C)
    assign1 = jax.nn.one_hot(expert_idx[..., 0], E, dtype=jnp.float32)
    frac = assign1.mean(axis=(0, 1))
    mprob = probs.mean(axis=(0, 1))
    aux = cfg.router_aux_coef * E * jnp.sum(frac * mprob)

    mesh = current_mesh()
    dp_axes = tuple(a for a in ("pod", "data") if mesh and a in mesh.axis_names)
    dp_size = 1
    if mesh:
        for a in dp_axes:
            dp_size *= mesh.shape[a]
    use_shard_map = (
        mesh is not None
        and "model" in mesh.axis_names
        and E % mesh.shape["model"] == 0
        and B % max(dp_size, 1) == 0
    )

    if use_shard_map:
        n_model = mesh.shape["model"]
        E_loc = E // n_model
        dp_spec = dp_axes if dp_axes else None

        def block(x_l, ei_l, gv_l, pos_l, wg, wi, wo):
            e_off = jax.lax.axis_index("model") * E_loc
            y_part = _experts_local(
                {"w_gate": wg, "w_in": wi, "w_out": wo}, cfg,
                x_l, ei_l, gv_l, pos_l, C, e_off, E_loc)
            return jax.lax.psum(y_part, "model")

        y = jax.shard_map(
            block, mesh=mesh,
            in_specs=(
                P(dp_spec, None, None),        # x
                P(dp_spec, None, None),        # expert_idx
                P(dp_spec, None, None),        # gates
                P(dp_spec, None, None),        # pos
                P("model", None, None),        # w_gate
                P("model", None, None),        # w_in
                P("model", None, None),        # w_out
            ),
            out_specs=P(dp_spec, None, None),
            check_vma=False,
        )(x, expert_idx, gate_vals, pos,
          p["experts"]["w_gate"], p["experts"]["w_in"], p["experts"]["w_out"])
    else:
        y = _experts_local(p["experts"], cfg, x, expert_idx, gate_vals, pos,
                           C, 0, E)
    y = y.astype(cd)

    # ---- shared experts (dense path over all tokens) -----------------------
    if "shared" in p:
        sw_g, sw_i, sw_o = (p["shared"][k].astype(cd) for k in ("w_gate", "w_in", "w_out"))
        sg = jnp.einsum("bsd,ndf->bsnf", x.astype(cd), sw_g, preferred_element_type=jnp.float32).astype(cd)
        sh = jnp.einsum("bsd,ndf->bsnf", x.astype(cd), sw_i, preferred_element_type=jnp.float32).astype(cd)
        sh = jax.nn.silu(sg.astype(jnp.float32)).astype(cd) * sh
        y = y + jnp.einsum("bsnf,nfd->bsd", sh, sw_o, preferred_element_type=jnp.float32).astype(cd)

    return shard_activation(y, "dp", None, None), aux
