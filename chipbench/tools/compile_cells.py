"""Compile every cell's programs for a described TPU v5e 2x2, with no chip.

  JAX_PLATFORMS=cpu python3 chipbench/tools/compile_cells.py [cell ...]

For each cell it compiles, at the cell's own sizes, the programs its window
runs and the reference it is checked against, and prints each program's
``memory_analysis()``: what the chip's compiler would refuse, and whether a
program fits one chip's memory, are known before any chip time is spent.
Nothing runs, so nothing here is a time.
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

GiB = 2**30


def _mem(name: str, compiled) -> dict:
    m = compiled.memory_analysis()
    row = {"program": name,
           "arguments_GiB": m.argument_size_in_bytes / GiB,
           "outputs_GiB": m.output_size_in_bytes / GiB,
           "temporaries_GiB": m.temp_size_in_bytes / GiB,
           "aliased_GiB": m.alias_size_in_bytes / GiB}
    row["total_GiB"] = (row["arguments_GiB"] + row["outputs_GiB"]
                        + row["temporaries_GiB"] - row["aliased_GiB"])
    return row


def compile_train(cell, devices) -> list[dict]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding
    from repro.training import init_train_state
    from chipbench import reference
    from chipbench.drivers import train
    from chipbench.programs import data_mesh

    cfg, tr = cell.config, cell.traffic
    mesh = data_mesh(devices)
    step, replicated, bspec = train.build_step(cfg, tr, mesh)
    shapes = reference.param_shapes(cfg)
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32), shapes,
        is_leaf=lambda s: isinstance(s, tuple) and all(isinstance(e, int) for e in s))
    state = jax.eval_shape(init_train_state, params)
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated), state)
    rows = tr["batch_per_chip"] * len(devices)
    tok = jax.ShapeDtypeStruct((rows, tr["seq_len"]), jnp.int32, sharding=bspec)
    t0 = time.perf_counter()
    out = [dict(_mem("train step", step.lower(state, {"tokens": tok, "labels": tok}).compile()),
                compile_s=time.perf_counter() - t0)]

    one = SingleDeviceSharding(devices[0])
    p1 = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), params)
    r = tr["reference_rows"]
    rt = jax.ShapeDtypeStruct((r, tr["seq_len"]), jnp.int32, sharding=one)
    fn = reference._grad_fn(cfg, None)
    t0 = time.perf_counter()
    out.append(dict(_mem(f"reference loss and gradient, {r} row(s)",
                         fn.lower(p1, rt, rt).compile()),
                    compile_s=time.perf_counter() - t0))
    return out


def compile_allreduce(cell, devices) -> list[dict]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from chipbench.drivers import allreduce

    tr = cell.traffic
    mesh = allreduce._mesh(devices)
    x = jax.ShapeDtypeStruct((len(devices), tr["bytes_per_rank"] // 4), jnp.float32,
                             sharding=NamedSharding(mesh, P("data", None)))
    t0 = time.perf_counter()
    c = allreduce.build(mesh, tr["schedule"]).lower(x).compile()
    permutes = c.as_text().count("collective-permute-start")
    return [dict(_mem("all_reduce", c), compile_s=time.perf_counter() - t0,
                 collective_permutes=permutes)]


def compile_serve(cell, devices) -> list[dict]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.models import init_caches
    from repro.serving.engine import make_decode_fn, make_prefill_fn
    from chipbench import reference
    from chipbench.programs import model_config

    cfg, tr = cell.config, cell.traffic
    mcfg = model_config(cfg)
    one = SingleDeviceSharding(devices[0])
    shapes = reference.param_shapes(cfg)
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one), shapes,
        is_leaf=lambda s: isinstance(s, tuple) and all(isinstance(e, int) for e in s))
    b = tr["max_batch"]
    ctx_len = tr["prompt_len"] + tr["max_new_tokens"]
    caches = jax.eval_shape(lambda: init_caches(mcfg, b, ctx_len, dtype=jnp.float32))
    caches = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), caches)
    toks = jax.ShapeDtypeStruct((b, tr["prompt_len"]), jnp.int32, sharding=one)
    last = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one)
    out = []
    for name, fn, args in (("prefill", make_prefill_fn(mcfg), ({"tokens": toks},)),
                           ("decode", make_decode_fn(mcfg), (last,))):
        t0 = time.perf_counter()
        c = fn.lower(params, *args, caches).compile()
        out.append(dict(_mem(f"{name}, batch {b}", c), compile_s=time.perf_counter() - t0))
    seq = jax.ShapeDtypeStruct((tr["check_requests"], ctx_len - 1), jnp.int32, sharding=one)
    srv = jax.ShapeDtypeStruct((tr["check_requests"], tr["max_new_tokens"]), jnp.int32,
                               sharding=one)
    for name, quant in (("reference", None), ("control (fp8)", reference.fp8)):
        t0 = time.perf_counter()
        c = reference._gap_fn(cfg, quant, tr["prompt_len"]).lower(params, seq, srv).compile()
        out.append(dict(_mem(f"{name} logit gaps, {tr['check_requests']} requests", c),
                        compile_s=time.perf_counter() - t0))
    return out


def main() -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import json
    import jax
    from jax.experimental import topologies
    from chipbench import bench

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    names = sys.argv[1:] or [w["name"] for w in spec["workloads"]]
    by_driver = {"train": compile_train, "allreduce": compile_allreduce,
                 "serve": compile_serve}
    for name in names:
        cell = bench.load_cell(name)
        for row in by_driver[cell.traffic["driver"]](cell, topo.devices[:cell.chips]):
            print(json.dumps(dict(cell=name, **row)), flush=True)


if __name__ == "__main__":
    main()
