"""Window driver for one AllReduce program, called back to back.

Entry: ``repro.core.collectives.all_reduce`` with the traffic's ``schedule``
inside ``jax.shard_map`` over a ``data`` mesh of all the cell's chips, jitted
and compiled in set-up.  The window cycles over ``inputs`` distinct inputs
made on the device from the seed, with at most two calls in flight.  It keeps
the answers of a few calls drawn from the seed, and of the last call; once
the window has closed, each is compared element by element, on every rank,
with the float64 sum of the inputs.

Traffic keys: ``bytes_per_rank``, ``inputs``, ``schedule`` (keyword arguments
of ``all_reduce``), ``sample_calls`` and ``limits``.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any

import numpy as np

from chipbench import flops, seeds
from chipbench.bench import Check, Window

#: sampled calls are drawn from the first this many of the window
SAMPLE_FROM = 64


@dataclasses.dataclass
class State:
    fn: Any
    inputs: list
    sample: set
    kept: dict = dataclasses.field(default_factory=dict)  # call -> (input, answer)


def build(mesh, schedule: dict):
    """The jitted (not yet compiled) AllReduce over ``mesh``'s ``data``."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.core.collectives import all_reduce

    return jax.jit(jax.shard_map(
        lambda v: all_reduce(v[0], "data", **schedule)[None],
        mesh=mesh, in_specs=P("data", None), out_specs=P("data", None),
        check_vma=False))


def make_inputs(seed: int, count: int, n: int, elems: int, sharding):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda k: list(jax.random.normal(k, (count, n, elems),
                                                    jnp.float32)),
                   out_shardings=sharding)(seeds.key(seed, "data"))


def _mesh(devices):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(devices), ("data",),
                axis_types=(jax.sharding.AxisType.Auto,))


def setup(ctx) -> State:
    from jax.sharding import NamedSharding, PartitionSpec as P

    tr = ctx.cell.traffic
    mesh = _mesh(ctx.devices)
    n = len(ctx.devices)
    elems = tr["bytes_per_rank"] // 4
    inputs = make_inputs(ctx.seed, tr["inputs"], n, elems,
                         NamedSharding(mesh, P("data", None)))
    t0 = time.perf_counter()
    fn = build(mesh, tr["schedule"]).lower(inputs[0]).compile()
    ctx.log(f"allreduce: compiled in {time.perf_counter() - t0:.2f} s")
    fn(inputs[0]).block_until_ready()
    draw = seeds.rng(ctx.seed, "sample")
    sample = set(int(i) for i in draw.choice(SAMPLE_FROM, tr["sample_calls"],
                                             replace=False))
    return State(fn=fn, inputs=inputs, sample=sample)


def window(st: State, ctx, seconds: float) -> Window:
    tr = ctx.cell.traffic
    n_in = len(st.inputs)
    calls, flight = 0, []
    t0 = time.perf_counter()
    while True:
        with ctx.span("call"):
            out = st.fn(st.inputs[calls % n_in])
        if calls in st.sample:
            st.kept[calls] = (calls % n_in, out)
        calls += 1
        flight.append(out)
        if len(flight) > 2:
            flight.pop(0).block_until_ready()
        if time.perf_counter() - t0 >= seconds:
            break
    out.block_until_ready()
    elapsed = time.perf_counter() - t0
    st.kept[calls - 1] = ((calls - 1) % n_in, out)
    n = len(ctx.devices)
    size = tr["bytes_per_rank"]
    return Window(seconds=elapsed, attempted=calls, failed=0,
                  end_to_end={"allreduce_busbw":
                              flops.busbw(size, elapsed / calls, n) / 1e9},
                  counts={"calls": calls, "bytes_per_rank": size, "ranks": n})


def sum_error(inputs: np.ndarray, got: np.ndarray) -> float:
    """Worst |answer - float64 sum| over every element of every rank, in
    units of float32 epsilon times the sum of the magnitudes added there."""
    x = inputs.astype(np.float64)
    want = x.sum(0)
    scale = np.finfo(np.float32).eps * np.abs(x).sum(0)
    return float((np.abs(got.astype(np.float64) - want[None]) / scale[None]).max())


def verify(st: State, ctx) -> list[Check]:
    st.fn = None
    worst = 0.0
    for call in sorted(st.kept):
        which, out = st.kept[call]
        got = np.asarray(out)
        worst = max(worst, sum_error(np.asarray(st.inputs[which]), got))
    st.kept.clear()
    st.inputs = None
    gc.collect()
    return [Check("sum_err_eps", worst, ctx.limit("sum_err_eps"))]
