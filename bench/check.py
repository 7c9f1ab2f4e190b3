"""The comparison that decides ``correct``.

The program hands the engine K/V blocks of 16 tokens in float16: row ``t``
is token ``t`` and the columns are ``(k|v, layer, kv-head, d_head)``.  The
reference gives K and V as ``(layers, tokens, kv-heads, d_head)``.  A
block is compared one layer and one of K/V at a time, by the relative
error ``|got - ref| / |ref|`` (Frobenius norms over the block's tokens and
heads), and the worst over all blocks is the number.  A block that should
be there and is not reads 1.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np


def kv_rows(K: np.ndarray, V: np.ndarray) -> np.ndarray:
    """K, V ``(L, S, KVH, Dh)`` -> rows ``(S, 2 * L * KVH * Dh)`` in the
    blocks' column order."""
    kv = np.concatenate([K, V])
    return kv.transpose(1, 0, 2, 3).reshape(kv.shape[1], -1)


def worst_block_error(got: Sequence[Optional[np.ndarray]], ref_rows: np.ndarray,
                      first_block: int, layers: int, block: int) -> float:
    """Worst relative error of blocks ``first_block, first_block + 1, ...``
    of a prompt against the reference rows."""
    worst = 0.0
    for i, blk in enumerate(got):
        lo = (first_block + i) * block
        ref = ref_rows[lo:lo + block].reshape(block, 2, layers, -1)
        if blk is None:
            worst = max(worst, 1.0)
            continue
        diff = blk.astype(np.float32).reshape(ref.shape) - ref
        err = np.linalg.norm(diff, axis=(0, 3)) / np.linalg.norm(ref, axis=(0, 3))
        worst = max(worst, float(err.max()))
    return worst


def relative_error(got: np.ndarray, ref: np.ndarray) -> float:
    got, ref = np.asarray(got, np.float32).ravel(), np.asarray(ref, np.float32).ravel()
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def int8_round_trip(block: np.ndarray) -> np.ndarray:
    """A block as an int8 codec with one absmax scale per column would
    return it: the control's stand-in for a store read."""
    x = block.astype(np.float32)
    s = np.maximum(np.abs(x).max(axis=0), 1e-30) / 127.0
    return (np.round(x / s) * s).astype(block.dtype)


def verdict(numbers: Dict[str, float], limits: Dict[str, Dict]) -> List[Dict]:
    """Each number beside its limit.  A limit is ``{"max": x}`` or
    ``{"min": x}``; a number without a limit, or a limit without its
    number, fails."""
    out = []
    for name in sorted(set(numbers) | set(limits)):
        value, lim = numbers.get(name), limits.get(name)
        if value is None or lim is None:
            ok = False
        elif "max" in lim:
            ok = value <= lim["max"]
        else:
            ok = value >= lim["min"]
        out.append({"name": name, "value": value,
                    "limit": None if lim is None else lim, "ok": bool(ok)})
    return out
