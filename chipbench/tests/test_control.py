"""The control of each comparison, at a size a test run holds: the float32
reference put in the program's place and computed one precision below what
the configuration states (float8 e4m3 matrix products for the bfloat16
model, bfloat16 sums for the float32 AllReduce) has to come out as not
correct under the cell's limits, while the program, on the same seeds,
comes out correct.  The model is SmolLM-360M at its published widths and
four layers, on short sequences; on the chip the same control runs at the
cells' own sizes (``tools/calibrate.py``)."""

from pathlib import Path

import numpy as np
import pytest

from chipbench.tests import tiny

SEEDS = (11, 2**33 + 12)
#: 8 checked requests of 24 served tokens: enough positions for the widest
#: gap to show, as the chip's 8 x 64 do
TRAFFIC = dict(tiny.TINY_TRAFFIC, serve=dict(
    tiny.TINY_TRAFFIC["serve"], prompt_len=32, max_new_tokens=24, check_requests=8,
    rate_per_s=8.0))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    tiny.make_root(root, tiny.WIDE_CONFIG, TRAFFIC)
    return root


def _context(root: Path, cell_name: str, seed: int, seconds: float = 1.0):
    import jax

    tiny._paths()
    from chipbench import bench

    cell = bench.load_cell(cell_name, root)
    return cell, bench.Context(cell=cell, seed=seed, devices=jax.devices()[:cell.chips],
                               log=lambda m: None, seconds=seconds)


def _fails(readings: dict, limits: dict) -> bool:
    return any(readings[k] > limits[k] for k in limits)


@pytest.mark.parametrize("seed", SEEDS)
def test_train_control_is_not_correct(root, seed):
    from chipbench import reference

    cell, ctx = _context(root, "smollm-4l.tiny.train.dp1", seed)
    driver = cell.module("drivers", "train")
    st = driver.setup(ctx)
    program = st.program
    ref = driver.reference_readings(ctx)
    control = driver.reference_readings(ctx, quant=reference.fp8)
    limits = cell.traffic["limits"]
    assert not _fails(reference.compare_train(program, ref), limits)
    assert _fails(reference.compare_train(control, ref), limits)


@pytest.mark.parametrize("seed", SEEDS)
def test_serve_control_is_not_correct(root, seed):
    from jax.sharding import SingleDeviceSharding
    from chipbench import reference

    cell, ctx = _context(root, "smollm-4l.tiny.serve.poisson", seed, seconds=2.0)
    driver = cell.module("drivers", "serve")
    st = driver.setup(ctx)
    driver.window(st, ctx, ctx.seconds)
    ids = sorted(st.served)[: cell.traffic["check_requests"]]
    served = np.stack([st.served[i] for i in ids])
    params = reference.make_params(cell.config, seed, SingleDeviceSharding(ctx.devices[0]))
    limit = cell.traffic["limits"]["logit_gap"]
    program = reference.served_gaps(params, st.prompts[ids], served, cell.config)
    control = reference.served_gaps(params, st.prompts[ids], served, cell.config,
                                    quant=reference.fp8)
    assert program.max() <= limit < control.max()


@pytest.mark.parametrize("seed", SEEDS)
def test_allreduce_control_is_not_correct(root, seed):
    import jax.numpy as jnp

    cell, _ = _context(root, "allreduce-2x2.tiny.allreduce.degraded-64MiB", seed)
    driver = cell.module("drivers", "allreduce")
    x = np.random.default_rng(seed).standard_normal((4, 2**16), np.float32)
    control = np.asarray(jnp.asarray(x, jnp.bfloat16).sum(0, dtype=jnp.bfloat16),
                         np.float32)
    exact_f32 = x.sum(0, dtype=np.float32)
    limit = cell.traffic["limits"]["sum_err_eps"]
    assert driver.sum_error(x, np.broadcast_to(exact_f32, x.shape)) <= limit
    assert driver.sum_error(x, np.broadcast_to(control, x.shape)) > limit
