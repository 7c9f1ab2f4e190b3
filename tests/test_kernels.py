"""Per-kernel interpret-mode validation: sweep shapes/dtypes, allclose vs
the pure-jnp ref.py oracle (assignment deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import paged_decode, paged_decode_ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.kv_codec import dequantize, dequantize_ref, quantize, quantize_ref
from repro.kernels.rwkv6 import wkv, wkv_ref

KEY = jax.random.key(42)


# ---------------------------------------------------------------- kv_codec
@pytest.mark.parametrize("shape", [(16, 256), (4, 8, 128), (32, 130), (3, 5, 96)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_kv_codec_matches_oracle(shape, dtype):
    x = jax.random.normal(jax.random.fold_in(KEY, sum(shape)), shape, dtype) * 4
    q, s = quantize(x, interpret=True)
    qr, sr = quantize_ref(x)
    # round-half boundaries may differ by one ULP between reduction orders
    diff = np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32))
    assert diff.max() <= 1 and (diff > 0).mean() < 0.01
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    d = dequantize(q, s, interpret=True)
    dr = dequantize_ref(qr, sr)
    # one-ULP q differences dequantize to at most one scale step
    np.testing.assert_allclose(
        np.asarray(d, np.float32), np.asarray(dr, np.float32),
        atol=float(np.max(np.asarray(sr))) + 1e-3,
    )


def test_kv_codec_matches_host_codec():
    from repro.core.codec import quantize_int8

    x = jax.random.normal(KEY, (24, 192), jnp.float32)
    q, _ = quantize(x, interpret=True)
    qh, _ = quantize_int8(np.asarray(x))
    diff = np.abs(np.asarray(q, np.int32) - qh.astype(np.int32))
    assert diff.max() <= 1 and (diff > 0).mean() < 0.01


def test_kv_codec_zero_channel_scale_one():
    x = jnp.zeros((8, 128), jnp.float32)
    q, s = quantize(x, interpret=True)
    np.testing.assert_array_equal(np.asarray(s), np.ones(128, np.float32))
    np.testing.assert_array_equal(np.asarray(q), np.zeros((8, 128), np.int8))


# ---------------------------------------------------- paged decode attention
@pytest.mark.parametrize(
    "B,H,KVH,D,page,NB,P",
    [(2, 8, 2, 64, 16, 4, 12), (3, 4, 4, 128, 8, 3, 10), (1, 16, 1, 64, 32, 2, 5)],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_matches_oracle(B, H, KVH, D, page, NB, P, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, B * 1000 + H), 5)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kp = jax.random.normal(ks[1], (P, KVH, page, D), dtype)
    vp = jax.random.normal(ks[2], (P, KVH, page, D), dtype)
    tables = jax.random.randint(ks[3], (B, NB), 0, P)
    kv_len = jax.random.randint(ks[4], (B,), 1, NB * page + 1)
    out = paged_decode(q, kp, vp, tables, kv_len, interpret=True)
    ref = paged_decode_ref(q, kp, vp, tables, kv_len)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


def test_paged_decode_single_valid_token():
    """kv_len=1: only the first slot of the first page participates."""
    B, H, KVH, D, page, NB, P = 1, 2, 1, 64, 8, 2, 4
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    kp = jax.random.normal(ks[1], (P, KVH, page, D), jnp.float32)
    vp = jax.random.normal(ks[2], (P, KVH, page, D), jnp.float32)
    tables = jnp.array([[2, 0]], jnp.int32)
    kv_len = jnp.array([1], jnp.int32)
    out = paged_decode(q, kp, vp, tables, kv_len, interpret=True)
    # attention over one token == that token's value (page 2, head 0, slot 0)
    np.testing.assert_allclose(
        np.asarray(out)[0, 0], np.asarray(vp)[2, 0, 0], rtol=1e-5, atol=1e-5
    )


# -------------------------------------------------------------------- rwkv6
@pytest.mark.parametrize("B,H,S,N,chunk", [(2, 3, 37, 16, 8), (1, 2, 64, 32, 32), (2, 4, 100, 64, 16)])
def test_rwkv6_kernel_matches_oracle(B, H, S, N, chunk):
    ks = jax.random.split(jax.random.fold_in(KEY, S * 10 + N), 6)
    r = jax.random.normal(ks[0], (B, H, S, N))
    k = jax.random.normal(ks[1], (B, H, S, N))
    v = jax.random.normal(ks[2], (B, H, S, N))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, H, S, N))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (H, N)) * 0.3
    s0 = jax.random.normal(ks[5], (B, H, N, N)) * 0.5
    y, sT = wkv(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    yr, sr = wkv_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sr), rtol=3e-4, atol=3e-4)


def test_rwkv6_kernel_state_chaining():
    """Running two halves with carried state == one full run."""
    B, H, S, N = 1, 2, 64, 16
    ks = jax.random.split(KEY, 6)
    r = jax.random.normal(ks[0], (B, H, S, N))
    k = jax.random.normal(ks[1], (B, H, S, N))
    v = jax.random.normal(ks[2], (B, H, S, N))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, H, S, N))) * 0.4 + 0.55
    u = jax.random.normal(ks[4], (H, N)) * 0.3
    s0 = jnp.zeros((B, H, N, N))
    y_full, s_full = wkv(r, k, v, w, u, s0, chunk=16, interpret=True)
    h = S // 2
    y1, s1 = wkv(r[:, :, :h], k[:, :, :h], v[:, :, :h], w[:, :, :h], u, s0, chunk=16, interpret=True)
    y2, s2 = wkv(r[:, :, h:], k[:, :, h:], v[:, :, h:], w[:, :, h:], u, s1, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y_full[:, :, :h]), np.asarray(y1), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y_full[:, :, h:]), np.asarray(y2), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_full), np.asarray(s2), rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------- mamba2
@pytest.mark.parametrize("B,S,H,P,N,chunk", [(2, 50, 3, 8, 16, 16), (1, 128, 2, 16, 8, 64)])
def test_mamba2_ssd_kernel_matches_oracle(B, S, H, P, N, chunk):
    from repro.kernels.mamba2 import ssd, ssd_ref

    ks = jax.random.split(jax.random.fold_in(KEY, S + P), 6)
    x = jax.random.normal(ks[0], (B, S, H, P))
    Bm = jax.random.normal(ks[1], (B, S, N))
    Cm = jax.random.normal(ks[2], (B, S, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    a = jnp.exp(-dt * jnp.exp(jax.random.normal(ks[4], (H,))[None, None] * 0.3))
    s0 = jax.random.normal(ks[5], (B, H, P, N)) * 0.5
    y, sT = ssd(x, Bm, Cm, a, dt, s0, chunk=chunk, interpret=True)
    yr, sr = ssd_ref(x, Bm, Cm, a, dt, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=4e-4, atol=4e-4)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sr), rtol=4e-4, atol=4e-4)


def test_mamba2_ssd_state_chaining():
    from repro.kernels.mamba2 import ssd

    B, S, H, P, N = 1, 64, 2, 8, 16
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    Bm = jax.random.normal(ks[1], (B, S, N))
    Cm = jax.random.normal(ks[2], (B, S, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    a = jnp.exp(-dt * 0.8)
    s0 = jnp.zeros((B, H, P, N))
    y_full, s_full = ssd(x, Bm, Cm, a, dt, s0, chunk=16, interpret=True)
    h = S // 2
    y1, s1 = ssd(x[:, :h], Bm[:, :h], Cm[:, :h], a[:, :h], dt[:, :h], s0, chunk=16, interpret=True)
    y2, s2 = ssd(x[:, h:], Bm[:, h:], Cm[:, h:], a[:, h:], dt[:, h:], s1, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y_full[:, :h]), np.asarray(y1), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y_full[:, h:]), np.asarray(y2), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_full), np.asarray(s2), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------- flash attention
@pytest.mark.parametrize("Sq,Skv,H,KVH,D", [(128, 128, 4, 2, 64), (64, 192, 8, 8, 128)])
def test_flash_attention_matches_oracle(Sq, Skv, H, KVH, D):
    ks = jax.random.split(jax.random.fold_in(KEY, Sq + Skv), 3)
    q = jax.random.normal(ks[0], (2, Sq, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (2, Skv, KVH, D), jnp.float32)
    v = jax.random.normal(ks[2], (2, Skv, KVH, D), jnp.float32)
    out = flash_attention(q, k, v, causal=True, q_offset=Skv - Sq, block_q=64, block_k=64, interpret=True)
    # ops takes model layout (B,S,H,D); the ref oracle takes kernel layout
    ref = attention_ref(
        jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
        causal=True, q_offset=Skv - Sq,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(jnp.moveaxis(ref, 1, 2)), rtol=2e-5, atol=2e-5
    )
