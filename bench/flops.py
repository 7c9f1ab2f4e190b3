"""Operations a prefill requires, from its shapes: what every family's
``prefill_flops`` counts (``bench/families/``).

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


def causal_keys(prompt: int, reused: int) -> int:
    """Keys the new positions ``p`` in ``[reused, prompt)`` attend to: ``p + 1`` each."""
    return prompt * (prompt + 1) // 2 - reused * (reused + 1) // 2


def head(cfg: Dict, new: int) -> int:
    """The LM head on the last position, where there is a new one."""
    return 2 * cfg["hidden_size"] * cfg["vocab_size"] if new > 0 else 0
