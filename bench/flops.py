"""Operations a prefill requires, from its shapes.

A prompt of ``S`` tokens whose first ``reused`` tokens are cached requires:

- every layer's projections and MLP over the ``S - reused`` new tokens;
- causal attention of each new position ``p`` over the ``p + 1`` positions
  up to it (scores and values);
- the LM head on the last position only (the first token's logits).

Work a program does beyond that (the cached prefix again, logits at every
position) is not required and is not counted.  Norms, RoPE, softmax and
the residual adds are not matrix multiplications and are left out; they
are under 0.1% of the total at the cells' widths.  One multiply-add is two
operations.  ``cfg`` is a configuration file's dict (Hugging Face keys).
"""

from __future__ import annotations

from typing import Dict


def prefill_flops(cfg: Dict, prompt: int, reused: int) -> float:
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    KVH = cfg["num_key_value_heads"]
    Dh = cfg["head_dim"]
    F = cfg["intermediate_size"]
    L = cfg["num_hidden_layers"]
    V = cfg["vocab_size"]
    new = prompt - reused
    per_token = 2 * D * (H * Dh + 2 * KVH * Dh) + 2 * H * Dh * D + 2 * 3 * D * F
    # sum over new positions p in [reused, prompt) of (p + 1) keys
    keys = prompt * (prompt + 1) // 2 - reused * (reused + 1) // 2
    attention = 2 * 2 * H * Dh * keys
    head = 2 * D * V if new > 0 else 0
    return float(L * (new * per_token + attention) + head)
