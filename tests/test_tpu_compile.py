"""Compiles for one TPU v5e chip that is described, not attached: the
served model's prefill and decode programs at qwen3-14b's published widths,
and the Pallas kernels of the main path at real widths.  Nothing runs; a
program the chip's compiler refuses (tiling, fast memory, device memory)
fails here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and every test
worker imports this file.  The persistent compile cache is off around
these compiles, since an entry written for a described chip cannot be read
back without one.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import api

V5E_HBM_BYTES = 16e9
BLOCK = 16
PROMPT = 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here, or its library is held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree)


def _qwen3(n_layers):
    return dataclasses.replace(get_config("qwen3-14b"), n_layers=n_layers)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes


def test_qwen3_prefill_compiles_at_published_widths(one_chip):
    cfg = _qwen3(2)
    params = _on(one_chip, api.param_specs(cfg))
    tokens = jax.ShapeDtypeStruct((1, PROMPT), jnp.int32, sharding=one_chip)
    cache = _on(one_chip, api.cache_specs(cfg, 1, PROMPT))
    compiled = jax.jit(api.prefill_fn(cfg)).lower(params, {"tokens": tokens}, cache, 0).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_qwen3_decode_step_compiles_at_published_widths(one_chip):
    cfg = _qwen3(2)
    params = _on(one_chip, api.param_specs(cfg))
    tokens = jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=one_chip)
    cache = _on(one_chip, api.cache_specs(cfg, 1, PROMPT + 8))
    compiled = jax.jit(api.decode_fn(cfg)).lower(params, tokens, cache, PROMPT).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_kv_codec_compiles_for_one_block(one_chip):
    from repro.kernels.kv_codec import dequantize, quantize

    # one 16-token block of the 40-layer model: (k|v, layer, kv-head, d_head)
    width = get_config("qwen3-14b").kv_bytes_per_token // 2
    assert width == 81920
    x = jax.ShapeDtypeStruct((BLOCK, width), jnp.bfloat16, sharding=one_chip)
    q = jax.ShapeDtypeStruct((BLOCK, width), jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((width,), jnp.float32, sharding=one_chip)
    for compiled in (quantize.lower(x).compile(), dequantize.lower(q, s).compile()):
        assert "tpu_custom_call" in compiled.as_text()


def test_decode_attention_compiles_at_qwen3_widths(one_chip):
    from repro.kernels.decode_attention import paged_decode

    cfg = get_config("qwen3-14b")
    B, NB, P = 8, PROMPT // BLOCK, 1024
    q = jax.ShapeDtypeStruct((B, cfg.n_heads, cfg.d_head), jnp.bfloat16, sharding=one_chip)
    pages = jax.ShapeDtypeStruct((P, cfg.n_kv_heads, BLOCK, cfg.d_head), jnp.bfloat16,
                                 sharding=one_chip)
    tables = jax.ShapeDtypeStruct((B, NB), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    compiled = paged_decode.lower(q, pages, pages, tables, lens).compile()
    assert "tpu_custom_call" in compiled.as_text()
