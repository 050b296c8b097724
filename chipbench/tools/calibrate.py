"""Readings that the limits of a cell's comparisons are set from.

  python3 chipbench/tools/calibrate.py <cell> --seeds 1,2,... [--control 1,2,3]
      [--seconds 4]

In one process, for each seed of ``--seeds``, it drives the cell's timed
path as a run does (set-up, for serving and the AllReduce a short window at
the cell's own load) and prints the numbers its comparison reads: the lower
readings.  For each seed of ``--control`` it also prints the control's
numbers (the float32 reference computed with float8 e4m3 matrix products
for a model, bfloat16 sums for the AllReduce) and those of the planted
faults the cell can have, each planted in the reference put in the
program's place:

  train       half of the batch left out.  A step that returns its state
              unchanged reads 1 on ``update_gap`` by construction and needs
              no run.
  serve       one served token altered; the second half of each checked
              batch answered with the first half's tokens.
  allreduce   the exchange left out (each rank keeps its input); half of
              the elements left unreduced; one element of the answer altered.

Each line of standard output is one JSON object.  Run it on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def emit(**row) -> None:
    print(json.dumps(row), flush=True)


def calibrate_train(cell, driver, ctx, control: bool) -> None:
    from chipbench import reference

    st = driver.setup(ctx)
    readings = st.program
    st.state = st.batches = st.step = None
    ref = driver.reference_readings(ctx)
    emit(cell=cell.name, seed=ctx.seed, kind="program",
         **reference.compare_train(readings, ref), losses=readings["losses"],
         ref_losses=ref["losses"])
    if not control:
        return
    rows = cell.traffic["batch_per_chip"] * cell.chips
    runs = {"control_fp8": dict(quant=reference.fp8),
            "fault_half_batch": dict(keep_rows=slice(0, rows // 2))}
    for kind, kw in runs.items():
        got = driver.reference_readings(ctx, **kw)
        emit(cell=cell.name, seed=ctx.seed, kind=kind,
             **reference.compare_train(got, ref))


def calibrate_allreduce(cell, driver, ctx, control: bool, seconds: float) -> None:
    import jax.numpy as jnp
    import numpy as np

    st = driver.setup(ctx)
    driver.window(st, ctx, seconds)
    worst, x = 0.0, None
    for which, out in st.kept.values():
        x = np.asarray(st.inputs[which])
        worst = max(worst, driver.sum_error(x, np.asarray(out)))
    emit(cell=cell.name, seed=ctx.seed, kind="program", sum_err_eps=worst)
    if not control:
        return
    total = x.sum(0, dtype=np.float32)
    bf16 = np.asarray(jnp.asarray(x, jnp.bfloat16).sum(0, dtype=jnp.bfloat16),
                      np.float32)
    half = x.copy()                       # the second half left unreduced
    half[:, : x.shape[1] // 2] = total[: x.shape[1] // 2]
    altered = np.broadcast_to(total, x.shape).copy()
    altered[0, 0] += 1.0
    for kind, got in {"control_bf16": np.broadcast_to(bf16, x.shape),
                      "fault_no_exchange": x,
                      "fault_half_unreduced": half,
                      "fault_altered_element": altered}.items():
        emit(cell=cell.name, seed=ctx.seed, kind=kind,
             sum_err_eps=driver.sum_error(x, got))


def calibrate_serve(cell, driver, ctx, control: bool, seconds: float, st=None):
    """Returns the warmed set-up, which the next seed reuses with its own
    weights and prompts (the same compiled programs)."""
    import numpy as np
    from jax.sharding import SingleDeviceSharding
    from chipbench import reference, seeds

    cfg = cell.config
    if st is None:
        st = driver.setup(ctx)
    else:
        driver.reseed(st, ctx, seconds, reference.make_params(
            cfg, ctx.seed, SingleDeviceSharding(ctx.devices[0])))
    win = driver.window(st, ctx, seconds)
    ids = driver.checked(st, ctx)
    served = np.stack([st.served[i] for i in ids])
    prompts = st.prompts[ids]
    params = st.engine.params
    gaps = reference.served_gaps(params, prompts, served, cfg)
    emit(cell=cell.name, seed=ctx.seed, kind="program", logit_gap=float(gaps.max()),
         requests=win.attempted, failed=win.failed,
         e2e=win.end_to_end)
    if not control:
        return st
    ctl = reference.served_gaps(params, prompts, served, cfg, quant=reference.fp8)
    emit(cell=cell.name, seed=ctx.seed, kind="control_fp8", logit_gap=float(ctl.max()))
    altered = served.copy()
    r, j = seeds.rng(ctx.seed, "sample").integers(0, [len(ids), served.shape[1]])
    altered[r, j] = (altered[r, j] + 1) % cfg["vocab_size"]
    half = served.copy()
    h = len(ids) // 2
    half[h:2 * h] = served[:h]
    for kind, toks in (("fault_altered_token", altered), ("fault_half_batch", half)):
        g = reference.served_gaps(params, prompts, toks, cfg)
        emit(cell=cell.name, seed=ctx.seed, kind=kind, logit_gap=float(g.max()))
    return st


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from chipbench import bench
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = bench.load_cell(args.cell)
    driver = cell.module("drivers", cell.traffic["driver"])
    devices = jax.devices()[:cell.chips]
    control = {int(s) for s in args.control.split(",") if s}
    log = lambda m: print(m, file=sys.stderr, flush=True)
    kept = None
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        ctx = bench.Context(cell=cell, seed=seed, devices=devices, log=log,
                            seconds=args.seconds)
        kind = cell.traffic["driver"]
        if kind == "train":
            calibrate_train(cell, driver, ctx, seed in control)
        elif kind == "allreduce":
            calibrate_allreduce(cell, driver, ctx, seed in control, args.seconds)
        else:
            kept = calibrate_serve(cell, driver, ctx, seed in control, args.seconds, kept)
        log(f"calibrate: seed {seed} took {time.perf_counter() - t0:.1f} s")


if __name__ == "__main__":
    main()
