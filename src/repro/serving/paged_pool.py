"""Paged KV pool: the device-side staging area between the LSM store and
the paged decode-attention kernel (DESIGN.md §3, "decode hot path").

Disk-resident KV blocks promoted by the cache hierarchy land in a paged
HBM pool; sequences reference pages through block tables consumed directly
by ``repro.kernels.decode_attention`` (scalar-prefetch indirection).  The
pool is a classic free-list allocator with per-sequence tables:

    alloc(seq_id, n_pages) / extend(seq_id) / free(seq_id)
    stage(seq_id, page_idx, k_block, v_block)      host -> pool page
    block_tables(batch_of_seq_ids) -> (B, NB) int32 (padded)

Pages are (KVH, page_size, Dh) per layer, the layout the kernel tiles;
the pool stores all layers of a page contiguously (L, KVH, page, Dh) so
one promotion stages one object from the store.  Eviction is the
hierarchy's concern — the pool refuses allocation when full (caller
demotes and retries), keeping the allocator deterministic and
thread-free like the rest of the runtime.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np


class PoolFullError(RuntimeError):
    pass


@dataclass
class PagedKVPool:
    n_pages: int
    page_size: int  # tokens per page
    n_layers: int
    n_kv_heads: int
    d_head: int
    dtype: np.dtype = np.dtype("float16")

    def __post_init__(self):
        shape = (self.n_pages, self.n_layers, self.n_kv_heads, self.page_size, self.d_head)
        self.k_pages = np.zeros(shape, self.dtype)
        self.v_pages = np.zeros(shape, self.dtype)
        self._free: List[int] = list(range(self.n_pages - 1, -1, -1))
        self._tables: Dict[int, List[int]] = {}
        self._lens: Dict[int, int] = {}

    # ------------------------------------------------------------ allocator
    @property
    def free_pages(self) -> int:
        return len(self._free)

    def alloc(self, seq_id: int, n_pages: int) -> List[int]:
        if n_pages > len(self._free):
            raise PoolFullError(f"need {n_pages}, free {len(self._free)}")
        if seq_id in self._tables:
            raise ValueError(f"seq {seq_id} already allocated")
        pages = [self._free.pop() for _ in range(n_pages)]
        self._tables[seq_id] = pages
        self._lens[seq_id] = 0
        return pages

    def extend(self, seq_id: int) -> int:
        if not self._free:
            raise PoolFullError("pool exhausted")
        p = self._free.pop()
        self._tables[seq_id].append(p)
        return p

    def free(self, seq_id: int) -> None:
        for p in self._tables.pop(seq_id):
            self._free.append(p)
        self._lens.pop(seq_id, None)

    # -------------------------------------------------------------- staging
    def stage_block(self, seq_id: int, token_offset: int, k: np.ndarray, v: np.ndarray) -> None:
        """Write a (L, n_tok, KVH, Dh) block at ``token_offset`` within the
        sequence (n_tok <= page_size; blocks never straddle pages when
        block_size == page_size, the default wiring)."""
        page_idx = token_offset // self.page_size
        within = token_offset % self.page_size
        n_tok = k.shape[1]
        assert within + n_tok <= self.page_size, "block straddles a page"
        page = self._tables[seq_id][page_idx]
        self.k_pages[page, :, :, within : within + n_tok] = k.transpose(0, 2, 1, 3)
        self.v_pages[page, :, :, within : within + n_tok] = v.transpose(0, 2, 1, 3)
        self._lens[seq_id] = max(self._lens[seq_id], token_offset + n_tok)

    def append_token(self, seq_id: int, k: np.ndarray, v: np.ndarray) -> None:
        """Decode step: append one token's (L, KVH, Dh) KV, extending the
        table when the tail page is full."""
        pos = self._lens[seq_id]
        if pos // self.page_size >= len(self._tables[seq_id]):
            self.extend(seq_id)
        self.stage_block(seq_id, pos, k[:, None], v[:, None])

    # ---------------------------------------------------------- kernel view
    def seq_len(self, seq_id: int) -> int:
        return self._lens[seq_id]

    def block_tables(self, seq_ids: Sequence[int]) -> np.ndarray:
        """(B, NB) int32 page-id table padded with page 0 (masked by kv_len
        in the kernel)."""
        nb = max(len(self._tables[s]) for s in seq_ids)
        out = np.zeros((len(seq_ids), nb), np.int32)
        for i, s in enumerate(seq_ids):
            t = self._tables[s]
            out[i, : len(t)] = t
        return out

    def kv_lens(self, seq_ids: Sequence[int]) -> np.ndarray:
        return np.asarray([self._lens[s] for s in seq_ids], np.int32)

    def layer_view(self, layer: int):
        """(P, KVH, page, Dh) views for one layer — the kernel's operands."""
        return self.k_pages[:, layer], self.v_pages[:, layer]
