"""Paged decode attention for TPU (Pallas) — the serving hot path fed by
the LSM store (DESIGN.md §3): KV blocks promoted from disk land in a paged
HBM pool; attention reads them through a block-table indirection.

TPU adaptation of GPU paged attention: instead of warp-level gather, the
page indirection lives in the BlockSpec ``index_map`` via scalar prefetch
(``pltpu.PrefetchScalarGridSpec``) — the block table is prefetched to SMEM
and each grid step DMAs exactly one (page x D) KV tile HBM->VMEM.  Pages
are laid out (P, KVH, page, D), so that tile is the block's last two dims
and meets the TPU's (8, 128) tiling for page % 8 == 0 and D % 128 == 0
(a (page, 1, D) slice of a (page, KVH, D) page does not).  Online
softmax state (m, l, acc) is carried in VMEM scratch across the sequential
page axis; tiles are (G x page) and (page x D), MXU-friendly for G or page
>= 8.  Pages past ``kv_len`` are masked; whole pages past the end are
skipped via ``pl.when`` (no DMA cost on TPU for skipped blocks is NOT
guaranteed — the win is the compute skip; block tables should be
right-sized by the pool allocator anyway).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref, acc, m, l, *, page, scale):
    b = pl.program_id(0)
    i = pl.program_id(2)
    ni = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        m[...] = jnp.full_like(m, NEG_INF)
        l[...] = jnp.zeros_like(l)
        acc[...] = jnp.zeros_like(acc)

    kv_len = lens_ref[b]
    base = i * page
    run = base < kv_len  # page intersects the valid prefix

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (page, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (G, page)
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < kv_len, s, NEG_INF)
        m_prev = m[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l[...] = l[...] * corr + p.sum(axis=1, keepdims=True)
        acc[...] = acc[...] * corr + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
        m[...] = m_new

    @pl.when(i == ni - 1)
    def _finalize():
        o_ref[0, 0] = (acc[...] / jnp.maximum(l[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_kernel(q, k_pages, v_pages, block_tables, kv_len, *, interpret: bool = False):
    """q (B, KVH, G, D); k/v_pages (P, KVH, page, D); block_tables (B, NB);
    kv_len (B,).  Returns (B, KVH, G, D)."""
    B, KVH, G, D = q.shape
    P, _, page, _ = k_pages.shape
    NB = block_tables.shape[1]
    grid = (B, KVH, NB)

    def q_map(b, h, i, tables, lens):
        return (b, h, 0, 0)

    def kv_map(b, h, i, tables, lens):
        return (tables[b, i], h, 0, 0)

    def o_map(b, h, i, tables, lens):
        return (b, h, 0, 0)

    kern = functools.partial(_kernel, page=page, scale=D**-0.5)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, D), q_map),
                pl.BlockSpec((1, 1, page, D), kv_map),
                pl.BlockSpec((1, 1, page, D), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, G, D), o_map),
            scratch_shapes=[
                pltpu.VMEM((G, D), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(block_tables, kv_len, q, k_pages, v_pages)
