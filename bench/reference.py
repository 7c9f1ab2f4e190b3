"""Plain float32 reference of the served dense decoder, and its control.

It imports nothing of the program and takes nothing the program made.
Weights are made again from the seed by the initialisation the program
documents: leaf ``i`` of the parameter tree, in the tree's sorted-key
order, is ``normal(fold_in(key(seed), i), shape, float32) / sqrt(fan_in)``
in bfloat16 (``fan_in`` is the next-to-last axis); norms are ones and
biases zeros.  The forward pass then runs in float32 with the highest
matmul precision: RMSNorm, GQA with optional QK-norm and QKV bias, RoPE
over the whole head (split halves), causal softmax attention, SwiGLU, and
the LM head on the rows asked for.

The control is the same forward with every linear layer's operands
rounded to float8 (e4m3): weights with a scale per output column,
activations with a scale per token.  It stands for a program that serves
below the bfloat16 the configurations state.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
Q_CHUNK = 256  # query rows per attention chunk: bounds the score matrix
FP8_MAX = 448.0  # largest finite float8_e4m3fn


@dataclass(frozen=True)
class RefConfig:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    qk_norm: bool
    qkv_bias: bool
    rope_theta: float
    eps: float

    @classmethod
    def from_file(cls, cfg: Dict) -> "RefConfig":
        run = cfg["as_run"]
        if run["rope_dims"] != cfg["head_dim"]:
            raise NotImplementedError("the reference rotates the whole head")
        return cls(cfg["num_hidden_layers"], cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["head_dim"], cfg["intermediate_size"],
                   cfg["vocab_size"], bool(run["qk_norm"]), bool(run["qkv_bias"]),
                   float(run["rope_theta"]), float(run["rms_norm_eps"]))


def leaf_specs(rc: RefConfig) -> List[Tuple[str, Tuple[int, ...], str]]:
    """(name, shape, init) of every parameter, in initialisation order."""
    L, D, H, KVH, Dh, F, V = (rc.layers, rc.d_model, rc.heads, rc.kv_heads, rc.d_head,
                              rc.d_ff, rc.vocab)
    attn = []
    if rc.qkv_bias:
        attn += [("bk", (L, KVH * Dh), "zeros"), ("bq", (L, H * Dh), "zeros"),
                 ("bv", (L, KVH * Dh), "zeros")]
    if rc.qk_norm:
        attn += [("k_norm", (L, Dh), "ones"), ("q_norm", (L, Dh), "ones")]
    attn += [("wk", (L, D, KVH * Dh), "normal"), ("wo", (L, H * Dh, D), "normal"),
             ("wq", (L, D, H * Dh), "normal"), ("wv", (L, D, KVH * Dh), "normal")]
    return attn + [
        ("attn_norm", (L, D), "ones"),
        ("w_down", (L, F, D), "normal"), ("w_gate", (L, D, F), "normal"),
        ("w_up", (L, D, F), "normal"),
        ("mlp_norm", (L, D), "ones"),
        ("embed", (V, D), "normal"),
        ("final_norm", (D,), "ones"),
        ("lm_head", (D, V), "normal"),
    ]


@partial(jax.jit, static_argnums=0)
def _init(rc: RefConfig, key) -> Dict[str, jax.Array]:
    out = {}
    for i, (name, shape, init) in enumerate(leaf_specs(rc)):
        if init == "zeros":
            out[name] = jnp.zeros(shape, jnp.bfloat16)
        elif init == "ones":
            out[name] = jnp.ones(shape, jnp.bfloat16)
        else:
            fan_in = shape[-2] if len(shape) >= 2 else max(1, shape[-1])
            std = 1.0 / np.sqrt(fan_in)
            out[name] = (jax.random.normal(jax.random.fold_in(key, i), shape, F32)
                         * std).astype(jnp.bfloat16)
    return out


def init_weights(rc: RefConfig, seed: int) -> Dict[str, jax.Array]:
    return _init(rc, jax.random.key(seed))


def _fp8(x, axis: int):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _linear(x, w, fp8: bool):
    x, w = x.astype(F32), w.astype(F32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v):
    """Causal attention of q (S, H, Dh) over k/v (S, KVH, Dh), a chunk of
    query rows at a time."""
    S, H, Dh = q.shape
    KVH = k.shape[1]
    G = H // KVH
    n = -(-S // Q_CHUNK)
    qp = jnp.pad(q, ((0, n * Q_CHUNK - S), (0, 0), (0, 0))).reshape(n, Q_CHUNK, KVH, G, Dh)
    t = jnp.arange(S)

    def chunk(args):
        qc, c = args
        s = jnp.einsum("qkgd,tkd->kgqt", qc, k, precision=HIGHEST) / np.sqrt(Dh)
        rows = c * Q_CHUNK + jnp.arange(Q_CHUNK)
        s = jnp.where(t[None, None, None, :] <= rows[None, None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST)

    out = jax.lax.map(chunk, (qp, jnp.arange(n)))
    return out.reshape(n * Q_CHUNK, H, Dh)[:S]


@partial(jax.jit, static_argnums=(0, 4))
def _forward(rc: RefConfig, w, tokens, head_rows, fp8: bool):
    S = tokens.shape[0]
    H, KVH, Dh = rc.heads, rc.kv_heads, rc.d_head
    pos = jnp.arange(S)
    x = w["embed"][tokens].astype(F32)
    blocks = {n: w[n] for n, _, _ in leaf_specs(rc)
              if n not in ("embed", "final_norm", "lm_head")}

    def layer(x, p):
        h = _rms(x, p["attn_norm"], rc.eps)
        q, k, v = (_linear(h, p[n], fp8) for n in ("wq", "wk", "wv"))
        if rc.qkv_bias:
            q, k, v = q + p["bq"].astype(F32), k + p["bk"].astype(F32), v + p["bv"].astype(F32)
        q, k, v = q.reshape(S, H, Dh), k.reshape(S, KVH, Dh), v.reshape(S, KVH, Dh)
        if rc.qk_norm:
            q, k = _rms(q, p["q_norm"], rc.eps), _rms(k, p["k_norm"], rc.eps)
        q, k = _rope(q, pos, rc.rope_theta), _rope(k, pos, rc.rope_theta)
        x = x + _linear(_attention(q, k, v).reshape(S, H * Dh), p["wo"], fp8)
        h = _rms(x, p["mlp_norm"], rc.eps)
        g = jax.nn.silu(_linear(h, p["w_gate"], fp8)) * _linear(h, p["w_up"], fp8)
        return x + _linear(g, p["w_down"], fp8), (k, v)

    x, (K, V) = jax.lax.scan(layer, x, blocks)
    logits = _linear(_rms(x[head_rows], w["final_norm"], rc.eps), w["lm_head"], fp8)
    return K, V, logits


def forward(rc: RefConfig, w, tokens: Sequence[int], head_rows: Sequence[int] = (),
            fp8: bool = False):
    """K and V of every layer and position, ``(L, S, KVH, Dh)`` float32 on
    the host, and the logits of ``head_rows``."""
    toks = jnp.asarray(np.asarray(tokens, np.int32))
    rows = jnp.asarray(np.asarray(list(head_rows) or [len(tokens) - 1], np.int32))
    K, V, logits = _forward(rc, w, toks, rows, fp8)
    return np.asarray(K), np.asarray(V), np.asarray(logits)
