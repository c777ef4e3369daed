"""Compiles for a described TPU v5e: the main path's kernel and train step
at real widths, with no chip attached.

The v5e:2x2 topology is described inside a module fixture (never at
import), so every test worker collects the same tests and only the one
that runs this file loads the TPU compiler.  The persistent compilation
cache is off around these compiles: an entry written for a described
chip cannot be read back without one.
"""

import functools

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.checksum.ops import lanesum32_lanes
from repro.models.registry import build
from repro.optim import OptimizerConfig
from repro.runtime.steps import abstract_train_state, make_train_step

HBM_BYTES = 16 * 2**30  # one v5e chip
EMBED = (151936, 1024)  # qwen1.5-0.5b's tied embedding, its largest leaf


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
def test_lanesum32_compiles_within_input_bytes(one_chip, dtype):
    x = jax.ShapeDtypeStruct(EMBED, dtype, sharding=one_chip)
    compiled = jax.jit(functools.partial(lanesum32_lanes, interpret=False)
                       ).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # a byte-level word view padded to 128 lanes once took 64x the input
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= x.size * x.dtype.itemsize


def test_qwen_train_step_fits_one_chip(one_chip):
    api = build(get_config("qwen1.5-0.5b"))
    opt = OptimizerConfig(state_dtype="float32")
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip)
    state = jax.tree.map(place, abstract_train_state(api, opt))
    batch = {k: place(jax.ShapeDtypeStruct((4, 1024), jnp.int32))
             for k in ("tokens", "labels")}
    compiled = jax.jit(make_train_step(api, opt), donate_argnums=(0,)
                       ).lower(state, batch).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 4.6e9  # the whole 464M-param state
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes <= HBM_BYTES
