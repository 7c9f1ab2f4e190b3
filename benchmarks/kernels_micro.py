"""Kernel microbenchmarks.

This container has no TPU, so Pallas kernels are validated in interpret
mode (correctness vs ref.py — also covered by tests/) and their *TPU*
performance is reported as roofline terms: bytes moved at HBM per the
BlockSpec tiling vs the XLA-lowered oracle's HBM traffic (from hlocost on
the compiled oracle).  This quantifies exactly what each kernel buys.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlocost import analyze_text
from repro.launch.mesh import HBM_BW, PEAK_FLOPS_BF16

from . import common


def _oracle_traffic(fn, *avals) -> float:
    text = jax.jit(fn).lower(*avals).compile().as_text()
    return analyze_text(text).bytes


def flash_attention_case(B=4, S=2048, H=16, KVH=4, D=128):
    from repro.kernels.flash_attention.ref import attention_ref

    q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, S, KVH, D), jnp.bfloat16)
    oracle_bytes = _oracle_traffic(lambda q, k, v: attention_ref(q, k, v, causal=True), q, kv, kv)
    # kernel HBM traffic: Q, K, V in + O out (scores live in VMEM scratch)
    kernel_bytes = (B * S * H * D + 2 * B * S * KVH * D + B * S * H * D) * 2
    flops = 4.0 * B * H * D * S * (S + 1) / 2
    return {
        "oracle_hbm_bytes": oracle_bytes,
        "kernel_hbm_bytes": kernel_bytes,
        "traffic_reduction": oracle_bytes / kernel_bytes,
        "kernel_mem_s": kernel_bytes / HBM_BW,
        "compute_s": flops / PEAK_FLOPS_BF16,
        "bound": "compute" if flops / PEAK_FLOPS_BF16 > kernel_bytes / HBM_BW else "memory",
    }


def rwkv6_case(B=8, H=32, S=4096, N=64):
    from repro.kernels.rwkv6.ref import wkv_ref

    r = jax.ShapeDtypeStruct((B, H, S, N), jnp.float32)
    u = jax.ShapeDtypeStruct((H, N), jnp.float32)
    st = jax.ShapeDtypeStruct((B, H, N, N), jnp.float32)
    oracle_bytes = _oracle_traffic(wkv_ref, r, r, r, r, u, st)
    # kernel: r/k/v/w in + y out + state in/out once (stays in VMEM across chunks)
    kernel_bytes = (4 * B * H * S * N + B * H * S * N + 2 * B * H * N * N) * 4
    return {
        "oracle_hbm_bytes": oracle_bytes,
        "kernel_hbm_bytes": kernel_bytes,
        "traffic_reduction": oracle_bytes / kernel_bytes,
        "kernel_mem_s": kernel_bytes / HBM_BW,
    }


def kv_codec_case(T=256, C=8192):
    from repro.kernels.kv_codec.ref import quantize_ref

    x = jax.ShapeDtypeStruct((T, C), jnp.bfloat16)
    oracle_bytes = _oracle_traffic(quantize_ref, x)
    kernel_bytes = T * C * 2 + T * C * 1 + C * 4  # in bf16 + out int8 + scales
    return {
        "oracle_hbm_bytes": oracle_bytes,
        "kernel_hbm_bytes": kernel_bytes,
        "traffic_reduction": oracle_bytes / kernel_bytes,
    }


def mamba2_case(B=8, S=4096, H=32, P=64, N=64):
    from repro.kernels.mamba2.ref import ssd_ref

    x = jax.ShapeDtypeStruct((B, S, H, P), jnp.float32)
    bc = jax.ShapeDtypeStruct((B, S, N), jnp.float32)
    ad = jax.ShapeDtypeStruct((B, S, H), jnp.float32)
    st = jax.ShapeDtypeStruct((B, H, P, N), jnp.float32)
    oracle_bytes = _oracle_traffic(ssd_ref, x, bc, bc, ad, ad, st)
    # kernel: x/B/C/a/dt in + y out + state once (VMEM-resident across chunks)
    kernel_bytes = (2 * B * S * H * P + 2 * B * S * N + 2 * B * S * H + 2 * B * H * P * N) * 4
    return {
        "oracle_hbm_bytes": oracle_bytes,
        "kernel_hbm_bytes": kernel_bytes,
        "traffic_reduction": oracle_bytes / kernel_bytes,
        "kernel_mem_s": kernel_bytes / HBM_BW,
    }


def paged_decode_case(B=64, H=32, KVH=8, D=128, page=64, NB=512):
    from repro.kernels.decode_attention.ref import paged_decode_ref

    P = B * NB
    q = jax.ShapeDtypeStruct((B, H, D), jnp.bfloat16)
    pages = jax.ShapeDtypeStruct((P, KVH, page, D), jnp.bfloat16)
    tb = jax.ShapeDtypeStruct((B, NB), jnp.int32)
    ln = jax.ShapeDtypeStruct((B,), jnp.int32)
    oracle_bytes = _oracle_traffic(paged_decode_ref, q, pages, pages, tb, ln)
    # kernel reads each mapped page once; oracle gathers pages into a dense
    # copy first (2x the KV traffic) and round-trips f32 scores
    kernel_bytes = (B * H * D + 2 * B * NB * page * KVH * D + B * H * D) * 2
    return {
        "oracle_hbm_bytes": oracle_bytes,
        "kernel_hbm_bytes": kernel_bytes,
        "traffic_reduction": oracle_bytes / kernel_bytes,
        "kernel_mem_s": kernel_bytes / HBM_BW,
    }


def run(verbose=True):
    out = {
        "flash_attention": flash_attention_case(),
        "rwkv6_wkv": rwkv6_case(),
        "mamba2_ssd": mamba2_case(),
        "kv_codec": kv_codec_case(),
        "paged_decode": paged_decode_case(),
    }
    if verbose:
        for name, r in out.items():
            print(f"{name:16s} oracle {r['oracle_hbm_bytes']/1e9:8.2f}GB -> kernel "
                  f"{r['kernel_hbm_bytes']/1e9:8.2f}GB  ({r['traffic_reduction']:.1f}x less HBM traffic)")
    common.save_artifact("kernels_micro", out)
    return out


if __name__ == "__main__":
    run()
