"""Training step with pluggable gradient synchronization.

Two sync modes (the framework's first-class R2CCL integration):

  * ``sync="xla"``   — plain ``jax.grad`` under pjit; XLA inserts its own
    all-reduce over the data axes (the baseline).
  * ``sync="r2ccl"`` — gradients are computed under ``shard_map`` *manual*
    over the data axes (model axes stay auto/SPMD) and synchronized by an
    explicit R2CCL collective program (ring / r2ccl-allreduce / recursive,
    per the ``CommConfig``).  Failure-aware schedules switch here without
    touching the model code — the paper's drop-in-replacement property.

Multi-pod meshes sync hierarchically: the configured schedule runs over the
intra-pod ``data`` axis, then an explicit ring combines over ``pod``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import spans
from repro.core.collectives import sync_gradients
from repro.core.planner import CommConfig
from repro.models import apply_model
from repro.optim import AdamWConfig, adamw_update, init_opt_state
from repro.optim.schedules import cosine_with_warmup
from . import losses


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jnp.ndarray


jax.tree_util.register_dataclass(
    TrainState, data_fields=["params", "opt_state", "step"], meta_fields=[]
)


def init_train_state(params) -> TrainState:
    return TrainState(params=params, opt_state=init_opt_state(params),
                      step=jnp.zeros((), jnp.int32))


def compute_loss(params, cfg: ModelConfig, batch) -> tuple[jnp.ndarray, dict]:
    logits, _, aux = apply_model(params, cfg, batch, mode="train")
    mtp_loss = jnp.zeros((), jnp.float32)
    if isinstance(aux, tuple):                 # MTP head active
        aux, mtp_logits = aux
        # position t's MTP target is token t+2 = labels[t+1]
        from repro.models.layers import cross_entropy
        mtp_loss = cross_entropy(mtp_logits[:, :-1], batch["labels"][:, 1:])
    loss = losses.task_loss(cfg, logits, batch)
    total = loss + aux + cfg.mtp_loss_weight * mtp_loss
    return total, {"loss": loss, "aux_loss": aux, "mtp_loss": mtp_loss}


def make_train_step(
    cfg: ModelConfig,
    opt: AdamWConfig,
    *,
    sync: str = "xla",                     # "xla" | "r2ccl"
    comm: CommConfig | None = None,
    mesh=None,
    data_axes: tuple[str, ...] = ("data",),
    total_steps: int = 10_000,
    warmup_steps: int = 100,
) -> Callable:
    """Builds ``train_step(state, batch) -> (state, metrics)``.

    ``comm.mode`` selects the gradient AllReduce schedule in r2ccl sync:
    "ring" (NCCL-equivalent explicit schedule), "r2ccl"
    (failure-aware decomposition for ``comm.degraded_rank``), "recursive"
    (multi-failure bandwidth spectrum), or "xla" (psum — for parity tests).
    """
    comm = comm or CommConfig()

    def loss_for_grad(params, batch):
        total, metrics = compute_loss(params, cfg, batch)
        return total, metrics

    @jax.named_scope(spans.OPTIMIZER)
    def apply_updates(state: TrainState, grads, metrics):
        lr_scale = cosine_with_warmup(state.step, warmup_steps=warmup_steps,
                                      total_steps=total_steps)
        new_params, new_opt, gnorm = adamw_update(
            opt, state.params, grads, state.opt_state, lr_scale=lr_scale)
        metrics = dict(metrics, grad_norm=gnorm,
                       lr=jnp.asarray(opt.lr) * lr_scale)
        return TrainState(new_params, new_opt, state.step + 1), metrics

    if sync == "xla":
        def train_step(state: TrainState, batch):
            (_, metrics), grads = jax.value_and_grad(
                loss_for_grad, has_aux=True)(state.params, batch)
            return apply_updates(state, grads, metrics)
        return train_step

    if sync != "r2ccl":
        raise ValueError(f"unknown sync mode {sync!r}")

    assert mesh is not None, "r2ccl sync needs the mesh for shard_map"
    manual = set(data_axes)
    batch_spec = P(tuple(data_axes))

    def sharded_grads(params, batch):
        (_, metrics), grads = jax.value_and_grad(
            loss_for_grad, has_aux=True)(params, batch)
        with jax.named_scope(spans.SYNC):
            # Wire dtype: ship gradients in bf16 (the XLA-native path fuses the
            # cast into its all-reduce; the explicit schedule must do the same
            # or pay 2x the ring bytes).
            wire_t = jnp.bfloat16 if comm.comm_dtype == "bfloat16" else jnp.float32
            orig_dtypes = jax.tree_util.tree_map(lambda g: g.dtype, grads)
            grads = jax.tree_util.tree_map(lambda g: g.astype(wire_t), grads)
            # Intra-pod sync with the configured (possibly failure-aware)
            # schedule; inter-pod combine with an explicit ring.
            grads = sync_gradients(grads, data_axes[-1], mean=True, **comm.kwargs())
            for ax in data_axes[:-1]:
                grads = sync_gradients(grads, ax, mode="ring" if comm.mode != "xla"
                                       else "xla", mean=True, g=comm.devices_per_node)
            grads = jax.tree_util.tree_map(
                lambda g, t: g.astype(t), grads, orig_dtypes)
            metrics = jax.tree_util.tree_map(
                lambda m: jax.lax.pmean(m, tuple(data_axes)), metrics)
            return grads, metrics

    def train_step(state: TrainState, batch):
        spec_batch = jax.tree_util.tree_map(lambda _: batch_spec, batch)
        grads, metrics = jax.shard_map(
            sharded_grads,
            mesh=mesh,
            in_specs=(P(), spec_batch),
            out_specs=(P(), P()),
            axis_names=manual,
            check_vma=False,
        )(state.params, batch)
        return apply_updates(state, grads, metrics)

    return train_step


def make_eval_step(cfg: ModelConfig):
    def eval_step(params, batch):
        total, metrics = compute_loss(params, cfg, batch)
        return dict(metrics, total_loss=total)
    return eval_step
