"""Pure-jnp oracle for paged single-token decode attention.

The paged layout stores KV in fixed-size pages shared across requests; a
per-request block table maps logical page slot ``j`` to physical page
``block_tables[b, j]``.  The oracle materializes the dense per-request
cache by gathering pages and defers to the dense decode oracle — so paged
and dense attention agree bit-for-bit by construction on the masked range.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.decode_attention.ref import decode_ref

__all__ = ["paged_decode_ref"]


def paged_decode_ref(q, k_pages, v_pages, block_tables, lengths, layer=0):
    """q: (B, Hq, D); k/v_pages: (L, P, ps, Hkv, D), or (P, ps, Hkv, D)
    for one layer; block_tables: (B, NP) int32; ``layer``: the layer of a
    stacked pool to read.

    ``lengths``: (B,) valid tokens per request (attends slots
    [0, lengths)); table entries past ``ceil(length/ps)`` are padding and
    may hold any valid page id — masking keeps them unread.  Returns
    (B, Hq, D) in q.dtype.
    """
    if k_pages.ndim == 5:
        k_pages, v_pages = k_pages[layer], v_pages[layer]
    b, np_ = block_tables.shape
    ps, hkv, d = k_pages.shape[1:]
    kd = k_pages[block_tables].reshape(b, np_ * ps, hkv, d)
    vd = v_pages[block_tables].reshape(b, np_ * ps, hkv, d)
    return decode_ref(q, kd, vd, lengths.astype(jnp.int32))
