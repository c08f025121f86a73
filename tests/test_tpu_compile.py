"""Ahead-of-time compiles of the serve path for a described TPU v5e.

No chip is attached: the TPU compiler installed with JAX compiles for a
``v5e:2x2`` topology that is only described, and refuses what the chip
would refuse (ops that do not lower, programs that do not fit).  Nothing
runs, so these tests say nothing about results or times.

The topology, the sharding and the shapes are built only inside the
fixtures below, never while a module is imported: only one process at a
time may load the TPU library, and under several pytest workers only the
worker given this file may load it.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.flow import CompileConfig, Flow, ServeConfig
from repro.nn import init_params, models


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Entries compiled for a described chip cannot be read back without
    one, so the persistent cache stays off around these compiles."""
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _design(builder):
    model, in_shape, in_quant = builder()
    params, _ = init_params(jax.random.PRNGKey(0), model, in_shape)
    design = Flow.compile(model, params, in_shape, in_quant, config=CompileConfig(jobs=1))
    return design, in_shape


def _aot_compile(design, in_shape, batch, sharding):
    x = jax.ShapeDtypeStruct((batch, *in_shape), jnp.int32, sharding=sharding)
    return jax.jit(design.forward_int).lower(x).compile()


def test_jet_tagger_compiles_at_largest_serve_bucket(one_chip, no_persistent_cache):
    design, in_shape = _design(models.jet_tagger)
    batch = ServeConfig().max_batch  # the largest default bucket
    compiled = _aot_compile(design, in_shape, batch, one_chip)
    assert compiled.memory_analysis() is not None
    out = compiled.out_info
    assert out.shape == (batch, *design.out_shape)
    assert out.dtype == jnp.int32


def test_svhn_cnn_conv_path_compiles(one_chip, no_persistent_cache):
    design, in_shape = _design(models.svhn_cnn)
    assert any(s.kind == "conv" for s in design.step_specs)
    compiled = _aot_compile(design, in_shape, 16, one_chip)
    assert compiled.out_info.shape == (16, *design.out_shape)
    # what the program asks of the device must fit one v5e's 16 GB
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
