"""Each configuration's largest prefill, and the reference and control that
the check runs after the window, compile for one TPU v5e chip that is
described, not attached, and fit its 16 GB: a configuration that outgrows
the chip fails here, at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and every test
worker imports this file.  The persistent compile cache is off around
these compiles, since an entry written for a described chip cannot be read
back without one.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import generator, harness, spec
from repro.models import api

HBM_BYTES = spec.peaks("TPU v5 lite")["hbm_bytes"]


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


def _longest(name):
    cfg = spec.config(name)
    return max(max(generator.Traffic(spec.traffic(w["traffic"]), cfg["vocab_size"], 16, 0)
                   .lengths()) for w in spec.benchmark()["workloads"] if w["config"] == name)


def _bytes(compiled):
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes


@pytest.mark.parametrize("name", [c["name"] for c in spec.benchmark()["configs"]])
def test_largest_prefill_fits_one_chip(name, one_chip):
    cfg = spec.config(name)
    mcfg = spec.family(cfg).model_config(cfg)
    longest = _longest(name)
    on = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)
    toks = jax.ShapeDtypeStruct((1, longest), jnp.int32, sharding=one_chip)
    compiled = jax.jit(api.prefill_fn(mcfg)).lower(
        on(api.param_specs(mcfg)), {"tokens": toks}, on(api.cache_specs(mcfg, 1, longest)), 0
    ).compile()
    assert _bytes(compiled) < HBM_BYTES, f"{name}: {_bytes(compiled)} B for a {longest}-token prefill"


@pytest.mark.parametrize("fp8", [False, True], ids=["reference", "control"])
@pytest.mark.parametrize("name", [c["name"] for c in spec.benchmark()["configs"]])
def test_reference_of_the_longest_decode_fits_one_chip(name, fp8, one_chip):
    """The check runs the reference on the chip once the program is freed,
    over the longest prompt and its decoded tokens, as its family builds it."""
    cfg = spec.config(name)
    seq = _longest(name) + harness.DECODE_TOKENS
    compiled = spec.family(cfg).lower_reference(cfg, seq, fp8, one_chip).compile()
    assert _bytes(compiled) < HBM_BYTES, f"{name}: {_bytes(compiled)} B for {seq} tokens"
