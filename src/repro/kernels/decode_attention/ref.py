"""Pure-jnp oracle for paged decode attention.

Gathers the block table back into a contiguous KV view and runs masked
single-token attention — the semantics the Pallas kernel must match.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def paged_decode_ref(q, k_pages, v_pages, block_tables, kv_len):
    """q (B, H, D); k/v_pages (P, KVH, page, D); block_tables (B, NB) int32
    page ids; kv_len (B,) valid tokens.  Returns (B, H, D)."""
    B, H, D = q.shape
    P, KVH, page, _ = k_pages.shape
    NB = block_tables.shape[1]
    G = H // KVH
    # gather pages -> (B, NB*page, KVH, D)
    k = k_pages[block_tables].transpose(0, 1, 3, 2, 4).reshape(B, NB * page, KVH, D)
    v = v_pages[block_tables].transpose(0, 1, 3, 2, 4).reshape(B, NB * page, KVH, D)
    T = NB * page
    qg = q.reshape(B, KVH, G, D).astype(F32)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg, k.astype(F32)) / (D**0.5)
    mask = jnp.arange(T)[None, :] < kv_len[:, None]  # (B, T)
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p, v.astype(F32))
    return out.reshape(B, H, D).astype(q.dtype)
