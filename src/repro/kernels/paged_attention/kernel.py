"""Paged split-KV decode attention (vLLM-style PagedAttention on TPU).

Flash-decoding over a global page pool ``(L, P, page_size, Hkv, D)`` — the
engine's stacked layout, left as it is — instead of a dense per-request
cache.  The per-request block table arrives via scalar prefetch (SMEM)
alongside lengths and the layer index, and the K/V BlockSpec index_map
dereferences it:

    grid step (b, j)  →  page  (layer, block_tables[b, j])

so the Pallas pipeline DMAs exactly the pages the request owns, in table
order, with no host-side gather.  Scalar-prefetched operands are available
to index_maps *before* the grid runs — that is what lets the DMA schedule
itself be data-dependent (the whole point of paging: fragmentation-free
allocation without ever materializing a dense copy).  The layer index
lets a decode step hand the kernel the whole stacked pool, which the step
updates in place, instead of a slice of one layer.  A one-layer pool
``(P, page_size, Hkv, D)`` is the ``L = 1``, ``layer = 0`` case, by a
free reshape.

Block layout (page-major).  The grid is ``(B, pages)``; each step DMAs one
whole page of K and of V, block ``(1, page_size, Hkv, D)`` of the layer
(the layer axis is squeezed out of the block).  The TPU
compiler requires a block's last two dims to be multiples of (8, 128) or
equal to the array's, and ``(Hkv, D)`` equals the array's, so the block
compiles at any head count — a per-head block ``(1, ps, 1, D)`` does not.
The kernel walks the kv heads inside the step, one GQA group
``q[b, h]`` of shape ``(G, D)`` against the page's ``(ps, D)`` rows of
head ``h``, and keeps each head's online-softmax state in VMEM scratch
``(Hkv, G, ·)`` across the page axis.  The query block ``(1, Hkv, G, D)``
and the output block likewise equal the array's last two dims.

Tail masking: step ``j`` covers key positions [j*ps, (j+1)*ps);
``pl.when(k_start < length)`` skips pages past the request's length, so
padded table slots (conventionally page 0) are never read.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_attention_kernel"]

NEG_INF = -1e30


def _kernel(lengths_ref, tables_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, sm_scale, page_size, n_kv_heads):
    b = pl.program_id(0)
    pi = pl.program_id(1)
    npages = pl.num_programs(1)
    length = lengths_ref[b]

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_start = pi * page_size

    @pl.when(k_start < length)
    def _body():
        for h in range(n_kv_heads):
            q = q_ref[0, h].astype(jnp.float32)       # (G, D)
            k = k_ref[0, :, h, :].astype(jnp.float32)  # (ps, D)
            v = v_ref[0, :, h, :].astype(jnp.float32)  # (ps, D)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # (G, ps)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos < length, s, NEG_INF)
            m_prev = m_ref[h]                          # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, -1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(pi == npages - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_decode_attention_kernel(q, k_pages, v_pages, block_tables, lengths,
                                  layer=0, *, interpret: bool = False):
    """q: (B, Hq, D); k/v_pages: (L, P, ps, Hkv, D), or (P, ps, Hkv, D) for
    one layer; block_tables: (B, NP); ``layer``: int32 scalar, the layer
    of the pool to read.

    ``lengths``: (B,) int32 valid tokens (attends [0, lengths)); padded
    table entries must be valid page ids (they are skipped, not read).
    Returns (B, Hq, D) in q.dtype.
    """
    if k_pages.ndim == 4:
        k_pages, v_pages = k_pages[None], v_pages[None]
    b, hq, d = q.shape
    ps, hkv = k_pages.shape[2], k_pages.shape[3]
    npages = block_tables.shape[1]
    g = hq // hkv
    sm_scale = 1.0 / math.sqrt(d)

    qg = q.reshape(b, hkv, g, d)

    def kv_map(b_, pi, lens, tabs, lay):
        return (lay[0], tabs[b_, pi], 0, 0, 0)

    def q_map(b_, pi, lens, tabs, lay):
        return (b_, 0, 0, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale, page_size=ps,
                          n_kv_heads=hkv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, npages),
            in_specs=[
                pl.BlockSpec((1, hkv, g, d), q_map),
                pl.BlockSpec((None, 1, ps, hkv, d), kv_map),
                pl.BlockSpec((None, 1, ps, hkv, d), kv_map),
            ],
            out_specs=pl.BlockSpec((1, hkv, g, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((hkv, g, d), jnp.float32),
                pltpu.VMEM((hkv, g, 1), jnp.float32),
                pltpu.VMEM((hkv, g, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
        # the kernel's name in compiled programs and device traces, apart
        # from any other Pallas call in the same step
        name="paged_attention",
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), qg, k_pages, v_pages)
    return out.reshape(b, hq, d)
