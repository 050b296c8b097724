"""Jit'd public wrappers for the Pallas kernels (padding, dtype, dispatch).

``impl`` selection:
  * "pallas"     — pl.pallas_call, TPU lowering (interpret=False);
  * "interpret"  — same kernel body executed in interpret mode (CPU CI);
  * "reference"  — the pure-jnp oracle from ``ref.py``;
  * ``None``     — the default: "pallas" on a TPU backend, "interpret" on
                   any other, so a kernel the TPU compiler refuses fails
                   loudly on the chip instead of running the interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .chunk_combine import LANES, chunk_combine_pallas
from .flash_attention import flash_attention_pallas
from .lru_scan import lru_scan_pallas


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "interpret"


def _round_up(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


def _pad_axis(x, axis, multiple):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "prefix_len", "logit_cap", "scale",
    "q_block", "kv_block", "impl"))
def flash_attention(
    q, k, v, *,
    causal: bool = True,
    window: int | None = None,
    prefix_len: int | None = None,
    logit_cap: float | None = None,
    scale: float | None = None,
    q_block: int = 128,
    kv_block: int = 128,
    impl: str | None = None,
):
    """(B,Tq,KVH,G,D) x (B,Tk,KVH,D)^2 -> (B,Tq,KVH,G,D)."""
    impl = impl or default_impl()
    if impl == "reference":
        return ref.reference_attention(
            q, k, v, causal=causal, window=window, prefix_len=prefix_len,
            logit_cap=logit_cap, scale=scale)
    Tq, Tk = q.shape[1], k.shape[1]
    qb = min(q_block, Tq) if Tq >= 8 else Tq
    kb = min(kv_block, Tk) if Tk >= 8 else Tk
    qp, tq = _pad_axis(q, 1, qb)
    kp, _ = _pad_axis(k, 1, kb)
    vp, _ = _pad_axis(v, 1, kb)
    out = flash_attention_pallas(
        qp, kp, vp, causal=causal, window=window, prefix_len=prefix_len,
        logit_cap=logit_cap, scale=scale, q_block=qb, kv_block=kb,
        interpret=(impl != "pallas"))
    return out[:, :tq]


@functools.partial(jax.jit, static_argnames=("tile", "impl"))
def chunk_combine(local, recv, seg_mask, accumulate, *, tile: int = 64 * 1024,
                  impl: str | None = None):
    """Fused R2CCL stage-2 merge; (C,M) buffers, (C,) masks.

    Each chunk is padded and viewed as 128-lane rows; one grid step merges
    about ``tile`` elements of one chunk, in a row block rounded to the
    dtype's sublane tile (8 rows of f32, 16 of bf16)."""
    impl = impl or default_impl()
    if impl == "reference":
        return ref.reference_chunk_combine(local, recv, seg_mask, accumulate)
    C, M = local.shape
    sublanes = 8 * max(1, 4 // local.dtype.itemsize)
    rows = -(-M // LANES)
    block_rows = min(_round_up(max(tile // LANES, 1), sublanes),
                     _round_up(rows, sublanes))
    R = _round_up(rows, block_rows)

    def lay(x):
        return jnp.pad(x, ((0, 0), (0, R * LANES - M))).reshape(C, R, LANES)

    out = chunk_combine_pallas(lay(local), lay(recv), seg_mask, accumulate,
                               block_rows=block_rows,
                               interpret=(impl != "pallas"))
    return out.reshape(C, R * LANES)[:, :M]


@functools.partial(jax.jit, static_argnames=("time_tile", "width_tile",
                                             "batch_tile", "impl"))
def lru_scan(a, x, *, time_tile: int = 128, width_tile: int = 128,
             batch_tile: int = 8, impl: str | None = None):
    """RG-LRU hidden states with h0=0; (B,T,W) -> (B,T,W) float32."""
    impl = impl or default_impl()
    if impl == "reference":
        h0 = jnp.zeros((a.shape[0], a.shape[2]), jnp.float32)
        return ref.reference_lru_scan(a, x, h0)
    B, T, W = a.shape
    tt = min(time_tile, T)
    wt = min(width_tile, W)
    bt = min(batch_tile, B)
    ap, t0 = _pad_axis(a, 1, tt)
    xp, _ = _pad_axis(x, 1, tt)
    ap, w0 = _pad_axis(ap, 2, wt)
    xp, _ = _pad_axis(xp, 2, wt)
    ap, b0 = _pad_axis(ap, 0, bt)
    xp, _ = _pad_axis(xp, 0, bt)
    # padded decay must be *1* with x=0 so the scan carry passes through
    if ap.shape != a.shape:
        mask_t = jnp.arange(ap.shape[1]) < t0
        ap = jnp.where(mask_t[None, :, None], ap, 1.0)
    out = lru_scan_pallas(ap, xp, time_tile=tt, width_tile=wt, batch_tile=bt,
                          interpret=(impl != "pallas"))
    return out[:b0, :t0, :w0]


@functools.partial(jax.jit, static_argnames=("time_tile", "impl"))
def wkv_scan(r, k, v, w, u, *, time_tile: int = 64, impl: str | None = None):
    """RWKV-6 WKV recurrence: r/k/w (BH,T,K), v (BH,T,V), u (BH,K)
    -> (BH,T,V) float32, S_0 = 0."""
    from .wkv_scan import wkv_scan_pallas
    impl = impl or default_impl()
    if impl == "reference":
        return ref.reference_wkv(r, k, v, w, u)
    T = r.shape[1]
    tt = min(time_tile, T)
    rp, t0 = _pad_axis(r, 1, tt)
    kp, _ = _pad_axis(k, 1, tt)
    vp, _ = _pad_axis(v, 1, tt)
    wp, _ = _pad_axis(w, 1, tt)
    out = wkv_scan_pallas(rp, kp, vp, wp, u, time_tile=tt,
                          interpret=(impl != "pallas"))
    return out[:, :t0]
