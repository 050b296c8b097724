"""JAX execution of collective schedules (the data plane).

Executes the schedule IR from ``core.schedule`` inside ``shard_map`` using
``lax.ppermute`` — one ppermute per schedule round, with per-rank chunk
selection done via static index maps.  This is the TPU-native analogue of
the paper's NCCL channel execution: a ring "channel" becomes a chunked
ppermute pipeline over the mesh axis, and switching schedules (ring vs
R2CCL-AllReduce vs recursive) is a compile-time decision made by the
planner from the failure state — the analogue of pre-established backup
connections: every failure class's program is built (and jit-cached) ahead
of time, so nothing is re-planned on the failure path.

Public entry points:
  * ``execute_schedule`` / ``execute_program`` — run an IR program on a flat
    array inside an active shard_map context;
  * ``all_reduce``      — dispatching wrapper (xla | ring | r2ccl | recursive);
  * ``sync_gradients``  — pytree gradient synchronization used by
    ``training.train_step`` with ``sync="r2ccl"``.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import spans
from .allreduce import build_r2ccl_all_reduce
from .recursive import build_recursive_all_reduce
from .schedule import (
    ChunkSchedule,
    CollectiveProgram,
    Segment,
    Step,
    build_ring_all_reduce,
    build_tree_all_reduce,
)


def _axis_size(axis_name: str) -> int:
    return lax.axis_size(axis_name)


def _dst_mask(step: Step, n: int) -> np.ndarray:
    m = np.zeros((n,), dtype=np.bool_)
    for _, d in step.perm:
        m[d] = True
    return m


def execute_schedule(x: jax.Array, sched: ChunkSchedule, axis_name: str) -> jax.Array:
    """Run one ChunkSchedule on a flat per-rank array ``x`` (inside shard_map).

    Returns the per-rank result (same shape as ``x``).
    """
    n = sched.n
    rank = lax.axis_index(axis_name)
    orig = x.shape[0]
    pad = (-orig) % sched.num_chunks
    with jax.named_scope(spans.PACK):
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
        chunks = x.reshape(sched.num_chunks, -1)

    for step in sched.steps:
        with jax.named_scope(spans.MERGE):
            dst_mask = jnp.asarray(_dst_mask(step, n))[rank]
        if step.whole_buffer:
            recv = lax.ppermute(chunks, axis_name, step.perm)
            with jax.named_scope(spans.MERGE):
                if step.accumulate:
                    # non-destinations receive zeros -> adding is a no-op
                    chunks = chunks + recv
                else:
                    chunks = jnp.where(dst_mask, recv, chunks)
        else:
            send_map = jnp.asarray(np.maximum(np.array(step.send_chunk), 0))
            recv_map = jnp.asarray(np.maximum(np.array(step.recv_chunk), 0))
            with jax.named_scope(spans.MERGE):
                payload = jnp.take(chunks, send_map[rank], axis=0)
            recv = lax.ppermute(payload, axis_name, step.perm)
            with jax.named_scope(spans.MERGE):
                ridx = recv_map[rank]
                cur = jnp.take(chunks, ridx, axis=0)
                new = cur + recv if step.accumulate else recv
                upd = jnp.where(dst_mask, new, cur)
                chunks = lax.dynamic_update_index_in_dim(chunks, upd, ridx, axis=0)

    with jax.named_scope(spans.PACK):
        out = chunks.reshape(-1)
        return out[:orig] if pad else out


def execute_program(x: jax.Array, prog: CollectiveProgram, axis_name: str) -> jax.Array:
    """Run a multi-segment program on a flat per-rank array."""
    total = x.shape[0]
    outs = []
    start = 0
    for i, seg in enumerate(prog.segments):
        end = total if i == len(prog.segments) - 1 else start + int(round(seg.frac * total))
        end = min(max(end, start), total)
        with jax.named_scope(spans.segment_scope(seg.schedule.name)):
            with jax.named_scope(spans.PACK):
                part = x[start:end]
            outs.append(execute_schedule(part, seg.schedule, axis_name))
        start = end
    with jax.named_scope(spans.PACK):
        return jnp.concatenate(outs) if len(outs) > 1 else outs[0]


# ---------------------------------------------------------------------------
# Program cache + dispatching all_reduce
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=256)
def _ring_program_cached(n: int) -> CollectiveProgram:
    return CollectiveProgram(
        "ring_all_reduce", n,
        [Segment(1.0, build_ring_all_reduce(list(range(n)), n))],
    )


@functools.lru_cache(maxsize=256)
def _tree_program_cached(n: int) -> CollectiveProgram:
    return CollectiveProgram(
        "tree_all_reduce", n,
        [Segment(1.0, build_tree_all_reduce(list(range(n)), n))],
    )


@functools.lru_cache(maxsize=256)
def _r2ccl_program_cached(n: int, degraded: int, x_pct: int, g: int) -> CollectiveProgram:
    prog, _ = build_r2ccl_all_reduce(
        list(range(n)), degraded, x=x_pct / 100.0, g=g)
    return prog


@functools.lru_cache(maxsize=64)
def _recursive_program_cached(bw_key: tuple[int, ...], g: int) -> CollectiveProgram:
    prog, _ = build_recursive_all_reduce([b / 100.0 for b in bw_key], g=g)
    return prog


def all_reduce(
    x: jax.Array,
    axis_name: str,
    *,
    mode: str = "xla",
    degraded: int | None = None,
    lost_fraction: float = 0.0,
    bandwidths: Sequence[float] | None = None,
    g: int = 8,
) -> jax.Array:
    """AllReduce over ``axis_name`` (must be a manual shard_map axis).

    mode:
      "xla"       — ``lax.psum`` (XLA's native collective; baseline);
      "ring"      — explicit chunked ring (the NCCL-equivalent schedule);
      "r2ccl"     — R2CCL-AllReduce for a single degraded node
                    (``degraded``, ``lost_fraction``);
      "recursive" — recursive decomposition over a ``bandwidths`` spectrum.

    Works on arrays of any shape (flattened internally).
    """
    n = _axis_size(axis_name)
    if mode == "xla" or n == 1:
        return lax.psum(x, axis_name)
    shape = x.shape
    flat = x.reshape(-1)
    if mode == "ring":
        prog = _ring_program_cached(n)
    elif mode == "tree":
        prog = _tree_program_cached(n)
    elif mode == "r2ccl":
        assert degraded is not None
        prog = _r2ccl_program_cached(n, degraded, int(round(lost_fraction * 100)), g)
    elif mode == "recursive":
        assert bandwidths is not None
        key = tuple(int(round(b * 100)) for b in bandwidths)
        prog = _recursive_program_cached(key, g)
    else:
        raise ValueError(f"unknown all_reduce mode {mode!r}")
    out = execute_program(flat, prog, axis_name)
    return out.reshape(shape)


def all_reduce_mean(x: jax.Array, axis_name: str, **kw) -> jax.Array:
    return all_reduce(x, axis_name, **kw) / _axis_size(axis_name)


def sync_gradients(grads, axis_name: str, *, mode: str = "ring",
                   degraded: int | None = None, lost_fraction: float = 0.0,
                   bandwidths: Sequence[float] | None = None, g: int = 8,
                   mean: bool = True):
    """Synchronize a gradient pytree across the data axis.

    Each leaf is flattened and run through the selected schedule.  With
    ``mode="xla"`` this is exactly ``psum``-mean; the other modes are the
    paper's explicit schedules — identical results (property-tested), but
    an explicit, failure-aware communication plan.
    """
    n = _axis_size(axis_name)

    def sync_leaf(leaf):
        out = all_reduce(leaf, axis_name, mode=mode, degraded=degraded,
                         lost_fraction=lost_fraction, bandwidths=bandwidths, g=g)
        return out / n if mean else out

    return jax.tree_util.tree_map(sync_leaf, grads)
