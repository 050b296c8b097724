"""Training launcher.

Runs real training on the locally available devices (a CPU at smoke size,
one TPU chip or one TPU host).  ``train()`` is the entry point; ``main()``
is a thin command line over it.  ``dryrun.py`` is a different thing: it
only lowers and compiles the 256/512-chip meshes on host devices and runs
nothing.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --smoke --steps 50 --sync r2ccl --comm-mode ring \
      --fail-at-step 20 --fail-node 0 --fail-rail 0
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.detection import FailureDetector
from repro.core.failures import Failure, FailureState, FailureType
from repro.core.planner import CommConfig
from repro.core.topology import make_cluster
from repro.data import make_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import data_axis_names, make_host_mesh
from repro.models import get_config, get_smoke_config, init_params
from repro.optim import AdamWConfig
from repro.training import init_train_state, make_train_step, save_checkpoint


@dataclasses.dataclass
class TrainResult:
    losses: list[float]
    schedules: list[str]           # pre-built step that ran at each step
    compile_s: dict[str, float]    # lower + compile seconds per pre-built step
    step_s: list[float]            # per step, ending in the loss read
    state: Any                     # final TrainState


def train(
    cfg: ModelConfig,
    *,
    steps: int = 50,
    seq_len: int = 128,
    batch: int = 8,
    lr: float = 1e-3,
    sync: str = "xla",
    comm_mode: str = "ring",
    data_par: int = 0,
    fail_at_step: int | None = None,
    fail_node: int = 0,
    fail_rail: int = 0,
    nics_per_node: int = 8,
    log_every: int = 10,
    log: Callable[[str], None] = print,
) -> TrainResult:
    """Train ``cfg`` from random weights (``PRNGKey(0)``) on synthetic batches.

    ``data_par`` is the data-parallel degree (0 = all local devices).  With
    ``sync="r2ccl"`` and ``fail_at_step`` set, a NIC failure on
    ``fail_node`` is injected at that step and the pre-built degraded step
    takes over from it.
    """
    ndev = len(jax.devices())
    dp = data_par or ndev
    mesh = make_host_mesh(data=dp, model=max(ndev // dp, 1))
    baxes = data_axis_names(mesh)
    log(f"arch={cfg.name} devices={ndev} mesh={dict(mesh.shape)} sync={sync}")

    replicated = NamedSharding(mesh, P())
    bspec = NamedSharding(mesh, P(tuple(baxes)))
    params = init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    state = jax.device_put(init_train_state(params), replicated)
    del params
    log(f"params: {n_params:,}")

    def place(step: int) -> dict:
        b = make_batch(cfg, seq_len=seq_len, batch_size=batch, step=step)
        return {k: jax.device_put(jnp.asarray(v), bspec) for k, v in b.items()}

    # Pre-built steps, healthy and degraded: the analogue of the paper's
    # pre-established backup connections.  Both are compiled before the
    # first step, so nothing is planned or compiled on the failure path.
    # A step that can never run (healthy when the failure is at step 0) is
    # not built.
    comms = {}
    if fail_at_step != 0 or sync != "r2ccl":
        comms["healthy"] = CommConfig(mode=comm_mode if sync == "r2ccl" else "xla")
    if fail_at_step is not None and sync == "r2ccl":
        comms["degraded"] = CommConfig(
            mode="r2ccl", degraded_rank=fail_node,
            lost_fraction=max(1.0 / nics_per_node, 0.34),
            devices_per_node=nics_per_node)
    first = place(0)
    compiled, compile_s = {}, {}
    for name, comm in comms.items():
        t0 = time.perf_counter()
        compiled[name] = jax.jit(
            make_train_step(cfg, AdamWConfig(lr=lr), sync=sync, comm=comm,
                            mesh=mesh, data_axes=baxes),
            in_shardings=(replicated, bspec), out_shardings=replicated,
        ).lower(state, first).compile()
        compile_s[name] = time.perf_counter() - t0

    detector = FailureDetector(FailureState())
    cluster = make_cluster(max(mesh.shape.get("data", 1), 2), nics_per_node)
    active = "healthy" if "healthy" in compiled else "degraded"
    result = TrainResult([], [], compile_s, [], None)
    t_start = time.perf_counter()
    for step in range(steps):
        if fail_at_step is not None and step == fail_at_step:
            failure = Failure(FailureType.NIC_HARDWARE, fail_node, fail_rail,
                              at_time=time.perf_counter() - t_start)
            diag = detector.detect(failure, (fail_node, fail_rail),
                                   ((fail_node + 1) % cluster.num_nodes, fail_rail),
                                   aux=((fail_node + 2) % cluster.num_nodes, 0))
            log(f"step {step}: NIC failure injected -> located {diag.location.value} "
                f"in {diag.localize_latency*1e3:.2f}ms; "
                f"switching to degraded schedule" if "degraded" in compiled else
                f"step {step}: failure injected (xla sync cannot adapt)")
            if "degraded" in compiled:
                active = "degraded"
        b = first if step == 0 else place(step)
        t0 = time.perf_counter()
        state, metrics = compiled[active](state, b)
        loss = float(metrics["loss"])
        result.step_s.append(time.perf_counter() - t0)
        gnorm = float(metrics["grad_norm"])
        result.losses.append(loss)
        result.schedules.append(active)
        if step % log_every == 0 or step == steps - 1:
            log(f"step {step:4d} loss {loss:.4f} gnorm {gnorm:.3f} sched={active}")
    result.state = state
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--sync", default="xla", choices=["xla", "r2ccl"])
    ap.add_argument("--comm-mode", default="ring",
                    choices=["xla", "ring", "r2ccl", "recursive"])
    ap.add_argument("--data-par", type=int, default=0,
                    help="data-parallel degree (0 = all local devices)")
    ap.add_argument("--fail-at-step", type=int, default=None)
    ap.add_argument("--fail-node", type=int, default=0)
    ap.add_argument("--fail-rail", type=int, default=0)
    ap.add_argument("--nics-per-node", type=int, default=8)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    res = train(cfg, steps=args.steps, seq_len=args.seq_len, batch=args.batch,
                lr=args.lr, sync=args.sync, comm_mode=args.comm_mode,
                data_par=args.data_par, fail_at_step=args.fail_at_step,
                fail_node=args.fail_node, fail_rail=args.fail_rail,
                nics_per_node=args.nics_per_node, log_every=args.log_every)
    if args.checkpoint_dir:
        save_checkpoint(args.checkpoint_dir, res.state, args.steps)
        print(f"checkpoint saved to {args.checkpoint_dir}")
    print(json.dumps({"first_loss": res.losses[0], "last_loss": res.losses[-1],
                      "decreased": res.losses[-1] < res.losses[0]}))


if __name__ == "__main__":
    main()
