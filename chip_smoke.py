#!/usr/bin/env python3
"""Smoke run of the R²CCL training step and serving engine on a TPU.

  python3 chip_smoke.py                # one chip
  python3 chip_smoke.py --four-chips   # the four chips of one v5e 2x2 host

One chip: full-width SmolLM-360M from random weights (seed 0) trains for 5
steps at batch 8 x 512 with the failure-aware gradient sync; a NIC failure at
step 3 switches in the pre-built degraded step.  The same model then serves 4
requests (prompt 128, 16 new tokens) with a failure at decode step 5, which
the recovery control plane handles.

Four chips: ``all_reduce`` in every mode on a ``data=4`` mesh, each checked
against a float64 sum, and the full-width train step with ``sync="xla"``,
``"r2ccl"`` ring and ``"r2ccl"`` degraded, which must agree within the bf16
wire tolerance.  No one-chip phase runs.

Every phase goes through the entry points a user calls
(``repro.launch.train.train``, ``repro.launch.serve.serve``).  Any failed
check raises, and the script exits non-zero.  It exits non-zero before any
phase when JAX finds no TPU: it never falls back to the CPU.  It runs in one
process and starts no other.  The last line of standard output is one JSON
object naming the device.  Step times, TTFT and TPOT printed here are smoke
numbers, not benchmark results.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import math
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core.collectives import all_reduce  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import serve  # noqa: E402
from repro.launch.train import train  # noqa: E402
from repro.models import get_config, init_params  # noqa: E402

ARCH = "smollm-360m"
#: a random-init LM predicts about uniformly: first loss within this of ln(V)
LOSS0_TOL = 1.0
#: bf16 wire tolerance on losses and parameters (tests/test_multidevice.py)
PARITY_TOL = 5e-3
ALL_REDUCE_MODES = {
    "xla": {},
    "ring": {},
    "tree": {},
    "r2ccl": dict(degraded=1, lost_fraction=0.5, g=2),
    "recursive": dict(bandwidths=(1.0, 0.5, 1.0, 1.0), g=2),
}


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def _median(xs) -> float:
    return float(np.median(xs)) if len(xs) else float("nan")


def train_phase(cfg, *, steps: int = 5, seq_len: int = 512, batch: int = 8,
                fail_at_step: int = 3) -> float:
    """Train with r2ccl sync and a failure; returns compile seconds."""
    res = train(cfg, steps=steps, seq_len=seq_len, batch=batch, sync="r2ccl",
                comm_mode="ring", fail_at_step=fail_at_step, log_every=1,
                log=log)
    check(bool(np.isfinite(res.losses).all()), f"non-finite loss: {res.losses}")
    ln_v = math.log(cfg.vocab_size)
    check(abs(res.losses[0] - ln_v) < LOSS0_TOL,
          f"first loss {res.losses[0]} is not near ln(vocab) = {ln_v}")
    want = ["healthy"] * fail_at_step + ["degraded"] * (steps - fail_at_step)
    check(res.schedules == want, f"schedules {res.schedules} != {want}")
    init = init_params(jax.random.PRNGKey(0), cfg)     # train()'s weights
    moved = max(float(jnp.abs(a - b).max()) for a, b in zip(
        jax.tree_util.tree_leaves(res.state.params),
        jax.tree_util.tree_leaves(init)))
    check(moved > 0.0, "parameters did not change")
    steady = {name: _median([t for t, s in zip(res.step_s[1:], res.schedules[1:])
                             if s == name]) for name in res.compile_s}
    log(f"train: losses={res.losses} max|param change|={moved:.3e}")
    log("train: compile_s=" + json.dumps(res.compile_s)
        + " steady_step_s=" + json.dumps(steady) + " (smoke numbers)")
    return sum(res.compile_s.values())


def serve_phase(cfg, *, requests: int = 4, prompt_len: int = 128,
                max_new: int = 16, fail_at_step: int = 5) -> float:
    """Serve with r2ccl hot repair and a failure; returns warm-up seconds."""
    out = serve(cfg, requests=requests, prompt_len=prompt_len, max_new=max_new,
                strategy="r2ccl", fail_at_step=fail_at_step)
    check(len(out.results) == requests, f"{len(out.results)} results")
    for i, r in enumerate(out.results):
        check(len(r.tokens) == max_new, f"request {i}: {len(r.tokens)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in r.tokens),
              f"request {i}: token outside the vocabulary: {r.tokens}")
        check(r.failovers == 1, f"request {i}: {r.failovers} failovers")
    entries = out.engine.control_plane.ledger.entries
    check(len(entries) == 1, f"{len(entries)} recoveries in the ledger, want 1")
    r = out.results[0]
    log(f"serve: warmup_s={out.warmup_s:.3f} (prefill + decode compile and "
        f"one batch) recovery={entries[0].total * 1e3:.3f}ms (modeled)")
    log(f"serve: TTFT={r.ttft * 1e3:.3f}ms TPOT={r.tpot * 1e3:.3f}ms "
        "(smoke numbers, not a benchmark; TPOT includes the modeled hiccup)")
    return out.warmup_s


def all_reduce_phase(mesh, *, per_chip: int = 4 * 2**20, seed: int = 0) -> float:
    """Every all_reduce mode against a float64 sum; returns compile seconds."""
    n = mesh.shape["data"]
    x = np.random.default_rng(seed).standard_normal((n, per_chip), np.float32)
    want = x.astype(np.float64).sum(0)
    # n-1 float32 additions, each off by at most half an ulp of |partial sum|
    bound = n * np.finfo(np.float32).eps * np.abs(x).astype(np.float64).sum(0)
    xd = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    total = 0.0
    for mode, kw in ALL_REDUCE_MODES.items():
        f = jax.shard_map(
            lambda v, mode=mode, kw=kw: all_reduce(v[0], "data", mode=mode, **kw)[None],
            mesh=mesh, in_specs=P("data", None), out_specs=P("data", None),
            check_vma=False)
        t0 = time.perf_counter()
        compiled = jax.jit(f).lower(xd).compile()
        total += time.perf_counter() - t0
        got = np.asarray(compiled(xd), np.float64)
        err = np.abs(got - want)
        check(bool((err <= bound).all()),
              f"all_reduce {mode}: max error {err.max()} exceeds the f32 bound")
        log(f"all_reduce {mode}: {per_chip * 4 / 2**20:.4g} MiB f32 per chip, "
            f"max |got - float64 sum| = {err.max():.3e} (bound {bound.max():.3e})")
    return total


def train_parity_phase(cfg, *, steps: int = 3, seq_len: int = 512,
                       batch: int = 8) -> float:
    """xla vs r2ccl ring vs r2ccl degraded sync; returns compile seconds."""
    runs = {
        "xla": dict(sync="xla"),
        "r2ccl ring": dict(sync="r2ccl", comm_mode="ring"),
        "r2ccl degraded": dict(sync="r2ccl", fail_at_step=0, fail_node=1,
                               nics_per_node=2),
    }
    out, total = {}, 0.0
    for name, kw in runs.items():
        res = train(cfg, steps=steps, seq_len=seq_len, batch=batch,
                    log_every=1, log=log, **kw)
        check(bool(np.isfinite(res.losses).all()), f"{name}: loss {res.losses}")
        total += sum(res.compile_s.values())
        log(f"train {name}: schedules={res.schedules} compile_s="
            + json.dumps(res.compile_s)
            + f" steady_step_s={_median(res.step_s[1:]):.4f} (smoke numbers)")
        out[name] = (res.losses, jax.device_get(res.state.params))
        del res
    ref_losses, ref_params = out.pop("xla")
    for name, (losses, params) in out.items():
        dl = max(abs(a - b) for a, b in zip(ref_losses, losses))
        dp = max(float(np.abs(a - b).max()) for a, b in zip(
            jax.tree_util.tree_leaves(ref_params),
            jax.tree_util.tree_leaves(params)))
        log(f"train parity {name} vs xla: max |loss diff|={dl:.3e} "
            f"max |param diff|={dp:.3e} (tolerance {PARITY_TOL})")
        check(dl < PARITY_TOL and dp < PARITY_TOL, f"{name} diverges from xla")
    return total


def timed(name: str, phase, *args) -> float:
    """Run one phase and log its wall seconds, set-up included."""
    t0 = time.perf_counter()
    out = phase(*args)
    log(f"{name} phase: {time.perf_counter() - t0:.1f} s wall")
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip all_reduce and gradient-sync "
                         "parity phases (one v5e 2x2 host)")
    args = ap.parse_args()

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX's first device is a "
                 f"{dev.platform!r} device); this script runs only on a TPU")
    libtpu = importlib.metadata.version("libtpu")
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} libtpu={libtpu}")
    cache = Path(enable_compile_cache())
    before = sum(1 for _ in cache.glob("*")) if cache.is_dir() else 0
    log(f"compile cache: {cache} ({before} entries before this run)")

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    if args.four_chips:
        check(len(devices) == 4, f"--four-chips needs 4 chips, found {len(devices)}")
        mesh = jax.make_mesh((4,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        compile_s = (timed("all_reduce", all_reduce_phase, mesh)
                     + timed("train parity", train_parity_phase, cfg))
    else:
        compile_s = (timed("train", train_phase, cfg)
                     + timed("serve", serve_phase, cfg))
    log(f"compile seconds this run: {compile_s:.1f} (cache had {before} "
        f"entries); phases took {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
