"""The comparison that decides ``correct``.

The program hands the engine blocks of 16 tokens in float16: row ``t`` is
token ``t`` and the columns are the family's block parts in turn, each
over every layer: ``(part, layer, width)`` (``bench/families/``).  The
reference gives the same rows in float32.  A block is compared one layer
and one part at a time, by the relative error ``|got - ref| / |ref|``
(Frobenius norms over the block's tokens and the part's width), and the
worst over all blocks is the number.  A block that should be there and is
not, or that has not the row's width, reads 1.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


def worst_block_error(got: Sequence[Optional[np.ndarray]], ref_rows: np.ndarray,
                      first_block: int, parts: Sequence[Tuple[str, int]], layers: int,
                      block: int) -> float:
    """Worst relative error of blocks ``first_block, first_block + 1, ...``
    of a prompt against the reference rows; ``parts`` is the family's
    ``block_parts``."""
    worst = 0.0
    for i, blk in enumerate(got):
        lo = (first_block + i) * block
        ref = ref_rows[lo:lo + block]
        if blk is None or blk.size != ref.size:
            worst = max(worst, 1.0)
            continue
        diff = blk.astype(np.float32).reshape(ref.shape) - ref
        col = 0
        for _, width in parts:
            cols = slice(col, col + layers * width)
            d = diff[:, cols].reshape(block, layers, width)
            r = ref[:, cols].reshape(block, layers, width)
            err = np.linalg.norm(d, axis=(0, 2)) / np.linalg.norm(r, axis=(0, 2))
            worst = max(worst, float(err.max()))
            col += layers * width
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
