"""The dense decoder with grouped-query attention: every configuration file
that names no ``bench_family``.

A family module tells the harness all it knows of one kind of model:

- ``model_config(cfg)``: the program's ``ModelConfig`` for a configuration
  file's dict (Hugging Face keys);
- ``rehearsal_config(cfg)``: the same file with every width cut to a smoke
  size, for the CPU rehearsal;
- ``block_parts(cfg)``: ``[(part, width per layer), ...]``, the columns of
  a block row in order: each part's layers in turn, ``(part, layer,
  width)``;
- ``prefill_flops(cfg, prompt, reused)``: the operations a prefill
  requires (``bench/flops.py`` says which);
- ``init_weights(cfg, seed)`` and ``forward(cfg, w, tokens, head_rows=None,
  fp8=False) -> (rows, logits)``: the plain reference, whose ``rows`` are
  ``(S, sum of widths x layers)`` float32 in the blocks' column order, and
  its float8 control;
- ``lower_reference(cfg, tokens, fp8, sharding)``: the reference's forward
  over ``tokens`` positions, lowered for arguments on ``sharding`` without
  making them (the one-chip fit test);
- optionally ``scope_costs(cfg, prompt, reused) -> {scope: (flops,
  bytes)}``: the work of each ``jax.named_scope`` of the prefill program
  that a metric reads the device time of (``Reduction.scope_s``).

This family names no scope.  Its reference is ``bench/reference.py``,
which imports nothing of the program; only ``model_config`` builds the
program's own config.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, reference

PROGRAM_EPS = 1e-6  # the program's RMSNorm epsilon
REHEARSAL_WIDTHS = dict(hidden_size=64, num_attention_heads=4, head_dim=16,
                        intermediate_size=128, vocab_size=256, num_hidden_layers=2)


def model_config(cfg: Dict):
    from repro.configs.base import ModelConfig

    run = cfg["as_run"]
    if run["rms_norm_eps"] != PROGRAM_EPS or run["rope_dims"] != cfg["head_dim"]:
        raise ValueError(f"{cfg['name']}: the program runs RMSNorm eps {PROGRAM_EPS} and RoPE "
                         "over the whole head; the configuration asks otherwise")
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], d_head=cfg["head_dim"], qk_norm=bool(run["qk_norm"]),
        qkv_bias=bool(run["qkv_bias"]), rope_theta=float(run["rope_theta"]))


def rehearsal_config(cfg: Dict) -> Dict:
    out = dict(cfg, **REHEARSAL_WIDTHS)
    out["num_key_value_heads"] = min(2, cfg["num_key_value_heads"])
    out["as_run"] = dict(cfg["as_run"], rope_dims=out["head_dim"])
    return out


def block_parts(cfg: Dict) -> List[Tuple[str, int]]:
    width = cfg["num_key_value_heads"] * cfg["head_dim"]
    return [("k", width), ("v", width)]


def prefill_flops(cfg: Dict, prompt: int, reused: int) -> float:
    """Per new token and layer the Q, K, V and O projections and the SwiGLU
    MLP; per key, scores and values over every head."""
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    KVH = cfg["num_key_value_heads"]
    Dh = cfg["head_dim"]
    F = cfg["intermediate_size"]
    L = cfg["num_hidden_layers"]
    new = prompt - reused
    per_token = 2 * D * (H * Dh + 2 * KVH * Dh) + 2 * H * Dh * D + 2 * 3 * D * F
    attention = 2 * 2 * H * Dh * flops.causal_keys(prompt, reused)
    return float(L * (new * per_token + attention) + flops.head(cfg, new))


def kv_rows(K: np.ndarray, V: np.ndarray) -> np.ndarray:
    """K, V ``(L, S, KVH, Dh)`` -> rows ``(S, 2 * L * KVH * Dh)`` in the
    blocks' column order."""
    kv = np.concatenate([K, V])
    return kv.transpose(1, 0, 2, 3).reshape(kv.shape[1], -1)


def init_weights(cfg: Dict, seed: int):
    return reference.init_weights(reference.RefConfig.from_file(cfg), seed)


def forward(cfg: Dict, w, tokens: Sequence[int], head_rows: Optional[Sequence[int]] = None,
            fp8: bool = False):
    K, V, logits = reference.forward(reference.RefConfig.from_file(cfg), w, tokens,
                                     head_rows or (), fp8)
    return kv_rows(K, V), logits


def lower_reference(cfg: Dict, tokens: int, fp8: bool, sharding):
    rc = reference.RefConfig.from_file(cfg)
    w = {n: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding)
         for n, s, _ in reference.leaf_specs(rc)}
    toks = jax.ShapeDtypeStruct((tokens,), jnp.int32, sharding=sharding)
    rows = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=sharding)
    return reference._forward.lower(rc, w, toks, rows, fp8)
