"""Compiles for a described TPU v5e (2x2), without a chip attached.

The TPU compiler is installed with JAX, so these tests refuse what the chip's
compiler would refuse: a kernel block that breaks the (8, 128) tiling, a
collective that lowers to the wrong op, a step that does not fit in HBM.
Nothing runs, so they say nothing about results or times.

The topology is described only inside the module-scoped fixture below: the
TPU library may be loaded by one process at a time, and under several test
workers only the worker that runs this file should load it.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.collectives import all_reduce
from repro.data import make_batch
from repro.kernels import ops
from repro.models import get_config, init_model
from repro.optim import AdamWConfig
from repro.training import init_train_state, make_train_step

V5E_HBM_BYTES = 16 * 10**9
MODES = {
    "xla": {},
    "ring": {},
    "tree": {},
    "r2ccl": dict(degraded=1, lost_fraction=0.5, g=2),
    "recursive": dict(bandwidths=(1.0, 0.5, 1.0, 1.0), g=2),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache off around them.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def data_mesh(topo):
    return Mesh(np.array(topo.devices), ("data",),
                axis_types=(jax.sharding.AxisType.Auto,))


@pytest.mark.parametrize("mode", sorted(MODES))
def test_all_reduce_compiles_on_2x2(data_mesh, mode):
    """64 MiB of bf16 per chip; the explicit schedules lower to
    collective-permutes, never to XLA's own all-reduce."""
    per_chip = 64 * 2**20 // 2
    f = jax.shard_map(
        lambda v: all_reduce(v[0], "data", mode=mode, **MODES[mode])[None],
        mesh=data_mesh, in_specs=P("data", None), out_specs=P("data", None),
        check_vma=False)
    x = jax.ShapeDtypeStruct((4, per_chip), jnp.bfloat16,
                             sharding=NamedSharding(data_mesh, P("data", None)))
    text = jax.jit(f).lower(x).compile().as_text()
    if mode == "xla":
        assert "all-reduce" in text
    else:
        assert "collective-permute" in text
        assert "all-reduce" not in text


def test_chunk_combine_pallas_compiles(one_chip):
    """The fused stage-2 merge at a gradient-bucket size is a Mosaic
    kernel, not the interpreter."""
    data = jax.ShapeDtypeStruct((8, 2**20), jnp.float32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    compiled = ops.chunk_combine.lower(data, data, mask, mask,
                                       impl="pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_smollm_train_step_fits_one_chip(one_chip):
    """Full-width SmolLM-360M, batch 8 x 512, sync="xla": the whole step
    (f32 params, AdamW state, the new state and temporaries) fits in HBM."""
    cfg = get_config("smollm-360m")
    state = jax.eval_shape(lambda: init_train_state(
        init_model(jax.random.PRNGKey(0), cfg)[0]))
    batch = make_batch(cfg, seq_len=512, batch_size=8)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    step = jax.jit(make_train_step(cfg, AdamWConfig(), sync="xla"))
    compiled = step.lower(jax.tree_util.tree_map(on_chip, state),
                          jax.tree_util.tree_map(on_chip, batch)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES
