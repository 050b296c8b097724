"""Sweep the offered rate of a serving cell, to find the rate it sustains.

  python3 chipbench/tools/sweep_serve.py <cell> --rates 8,10,12 [--seconds 20]
      [--seed 1]

One process: the cell's set-up once, then for each rate a window of
``--seconds`` at that rate (the cell's traffic otherwise), printing one JSON
line per rate with the tails, the batch sizes and how long the last request
waited for its first token.  Past the rate the system sustains, the queue
grows all through the window, and the last request's wait grows with the
window.  Run it on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from chipbench import bench
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = bench.load_cell(args.cell)
    driver = cell.module("drivers", cell.traffic["driver"])
    log = lambda m: print(m, file=sys.stderr, flush=True)
    ctx = bench.Context(cell=cell, seed=args.seed, devices=jax.devices()[:1],
                        log=log, seconds=args.seconds)
    st = driver.setup(ctx)
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic["rate_per_s"] = rate
        driver.reseed(st, ctx, args.seconds)
        win = driver.window(st, ctx, args.seconds)
        sizes = win.counts["batch_sizes"]
        print(json.dumps({
            "rate_per_s": rate, "requests": win.attempted, "failed": win.failed,
            "window_s": win.seconds, **win.end_to_end,
            "mean_batch": float(np.mean(sizes)) if sizes else 0.0,
            "batches": len(sizes),
            "served_per_s": (win.attempted - win.failed) / win.seconds}), flush=True)


if __name__ == "__main__":
    main()
