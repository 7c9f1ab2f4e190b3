"""jit wrapper: model layout (B, H, D) -> grouped kernel layout, GQA."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .decode_attention import paged_decode_kernel
from .ref import paged_decode_ref


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode(q, k_pages, v_pages, block_tables, kv_len, *, interpret: bool = False):
    """q (B, H, D); k/v_pages (P, KVH, page, D); block_tables (B, NB) int32;
    kv_len (B,) int32 -> (B, H, D)."""
    B, H, D = q.shape
    KVH = k_pages.shape[1]
    G = H // KVH
    qg = q.reshape(B, KVH, G, D)
    out = paged_decode_kernel(qg, k_pages, v_pages, block_tables, kv_len, interpret=interpret)
    return out.reshape(B, H, D)


__all__ = ["paged_decode", "paged_decode_ref"]
