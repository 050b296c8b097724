"""Window driver for data-parallel training.

Entry: the jitted step of ``repro.training.make_train_step`` with the
shardings that ``launch/train.py:train`` uses (state replicated, batch split
over ``data``), compiled once in set-up.  Set-up drives that same compiled
step from the seed through the first ``check_steps`` steps, on the window's
own batches, and keeps what the comparison needs: each step's loss, the
first gradient (read back from AdamW's first moment) and each leaf's change
after the last of them.  The window then goes on from that state, each step
ending in its loss, over batches made on the device in set-up.

Traffic keys: ``batch_per_chip``, ``seq_len``, ``batches`` (distinct batches
the window cycles through), ``sync``, ``comm`` (``CommConfig`` fields),
``optimizer``, ``check_steps``, ``reference_rows`` and ``limits``.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any

import numpy as np

from chipbench import reference
from chipbench.bench import Check, Window


@dataclasses.dataclass
class State:
    step: Any
    state: Any
    batches: list
    tokens_per_step: int
    program: dict                    # readings of the check steps


def _batches(cfg: dict, seed: int, n: int, rows: int, seq_len: int, sharding):
    import jax
    data = reference.make_tokens(cfg, seed, (n, rows, seq_len + 1))
    split = jax.jit(lambda d: [{"tokens": d[i, :, :-1], "labels": d[i, :, 1:]}
                               for i in range(n)], out_shardings=sharding)
    return split(data)


def build_step(ctx_cfg: dict, traffic: dict, mesh):
    """The jitted (not yet compiled) step and its shardings."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.planner import CommConfig
    from repro.optim import AdamWConfig
    from repro.training import make_train_step
    from chipbench.programs import model_config

    opt = traffic["optimizer"]
    step = make_train_step(
        model_config(ctx_cfg),
        AdamWConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                    weight_decay=opt["weight_decay"],
                    grad_clip_norm=opt["grad_clip_norm"]),
        sync=traffic["sync"], comm=CommConfig(**traffic["comm"]), mesh=mesh,
        data_axes=("data",), total_steps=opt["total_steps"],
        warmup_steps=opt["warmup_steps"])
    replicated = NamedSharding(mesh, P())
    bspec = NamedSharding(mesh, P(("data",)))
    return jax.jit(step, in_shardings=(replicated, bspec),
                   out_shardings=replicated), replicated, bspec


def setup(ctx) -> State:
    import jax
    from repro.training import init_train_state
    from chipbench.programs import data_mesh

    cfg, tr = ctx.cell.config, ctx.cell.traffic
    mesh = data_mesh(ctx.devices)
    step, replicated, bspec = build_step(cfg, tr, mesh)
    rows = tr["batch_per_chip"] * len(ctx.devices)
    params = reference.make_params(cfg, ctx.seed, replicated)
    state = jax.jit(init_train_state, out_shardings=replicated)(params)
    del params
    batches = _batches(cfg, ctx.seed, tr["batches"], rows, tr["seq_len"], bspec)
    t0 = time.perf_counter()
    step = step.lower(state, batches[0]).compile()
    ctx.log(f"train: step compiled in {time.perf_counter() - t0:.2f} s")

    # The steps the comparison follows, through the window's own call.
    b1 = tr["optimizer"]["b1"]
    clip = tr["optimizer"]["grad_clip_norm"]
    losses = []
    for i in range(tr["check_steps"]):
        state, metrics = step(state, batches[i])
        losses.append(float(metrics["loss"]))
        if i == 0:
            gnorm = float(metrics["grad_norm"])
            # AdamW's first moment after one step is (1 - b1) * clipped grad
            unclip = 1.0 / min(1.0, clip / max(gnorm, 1e-9)) / (1.0 - b1)
            grad_norms = {k: v * unclip for k, v in
                          reference.leaf_norms(state.opt_state["mu"]).items()}
    p0 = reference.make_params(cfg, ctx.seed, replicated)
    update_norms = reference.diff_norms(state.params, p0)
    del p0
    jax.block_until_ready(state)
    return State(step=step, state=state, batches=batches,
                 tokens_per_step=rows * tr["seq_len"],
                 program={"losses": losses, "grad_norms": grad_norms,
                          "update_norms": update_norms})


def window(st: State, ctx, seconds: float) -> Window:
    import jax

    n_b = len(st.batches)
    first = ctx.cell.traffic["check_steps"]
    state, steps = st.state, 0
    t0 = time.perf_counter()
    ends = []
    while True:
        with ctx.span("step"):
            state, metrics = st.step(state, st.batches[(first + steps) % n_b])
        steps += 1
        # Each step ends in its loss, as launch/train.py:train ends every
        # step: it keeps each loss, and decides between steps, on the host,
        # which compiled step (healthy or degraded) runs next.
        metrics["loss"].block_until_ready()
        ends.append(time.perf_counter())
        if ends[-1] - t0 >= seconds:
            break
    jax.block_until_ready(state)
    elapsed = time.perf_counter() - t0
    step_s = np.diff([t0] + ends)
    ctx.log(f"train: {steps} steps, median {np.median(step_s):.4f} s, slowest "
            f"{step_s.max():.4f} s (step {int(step_s.argmax())} of the window)")
    st.state = state
    tokens = steps * st.tokens_per_step
    return Window(seconds=elapsed, attempted=steps, failed=0,
                  end_to_end={"train_tokens_per_s": tokens / elapsed},
                  counts={"steps": steps, "tokens": tokens,
                          "seq_len": ctx.cell.traffic["seq_len"]})


def reference_batches(cfg: dict, traffic: dict, seed: int, chips: int, sharding):
    rows = traffic["batch_per_chip"] * chips
    data = reference.make_tokens(cfg, seed, (traffic["batches"], rows,
                                             traffic["seq_len"] + 1), sharding)
    return [(data[i, :, :-1], data[i, :, 1:]) for i in range(traffic["check_steps"])]


def reference_readings(ctx, **kw) -> dict:
    """The float32 reference's readings on the first chip, for the cell's
    whole batch (``kw``: a control's ``quant`` or a planted fault's
    ``keep_rows``)."""
    from jax.sharding import SingleDeviceSharding

    cfg, tr = ctx.cell.config, ctx.cell.traffic
    one = SingleDeviceSharding(ctx.devices[0])
    params = reference.make_params(cfg, ctx.seed, one)
    batches = reference_batches(cfg, tr, ctx.seed, ctx.cell.chips, one)
    opt = dict(tr["optimizer"])
    return reference.train_readings(params, batches, cfg, opt,
                                    rows=tr["reference_rows"], **kw)


def verify(st: State, ctx) -> list[Check]:
    st.state = st.batches = st.step = None
    gc.collect()
    t0 = time.perf_counter()
    ref = reference_readings(ctx)
    ctx.log(f"train: reference took {time.perf_counter() - t0:.2f} s")
    ctx.log(f"train: losses program {st.program['losses']} "
            f"reference {ref['losses']}")
    gaps = reference.compare_train(st.program, ref)
    return [Check(k, v, ctx.limit(k)) for k, v in gaps.items()]
