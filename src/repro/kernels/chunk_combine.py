"""Fused chunk-combine Pallas kernel — the stage-2 merge of R2CCL-AllReduce.

The paper implements "a customized broadcast kernel to support the specific
requirements of the R2CCL-AllReduce phase" (Section 7): after the partial
AllReduce, received chunks must be merged into the local buffer — some
accumulated (reduction edges), some overwritten (broadcast edges), some
untouched (chunks the local rank already owns).  Doing this with separate
select/add ops costs three HBM round-trips over the gradient buffer; the
fused kernel does one read of each operand and one write.

Layout: each chunk is viewed as ``(rows, 128)`` lane rows, so a data block
is ``(block_rows, 128)`` with ``block_rows`` a multiple of the sublane tile
— the (8, 128) tiling the TPU compiler requires.  Grid: (chunk, row
block).  The per-chunk control (segment membership, accumulate vs
overwrite) is scalar-prefetched into SMEM as two whole ``(C,)`` int32
arrays and read with the chunk's grid index.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _combine_kernel(seg_ref, acc_ref, local_ref, recv_ref, o_ref):
    c = pl.program_id(0)
    seg = seg_ref[c] != 0
    acc = acc_ref[c] != 0

    @pl.when(jnp.logical_not(seg))
    def _():
        o_ref[...] = local_ref[...]

    @pl.when(jnp.logical_and(seg, acc))
    def _():
        o_ref[...] = local_ref[...] + recv_ref[...]

    @pl.when(jnp.logical_and(seg, jnp.logical_not(acc)))
    def _():
        o_ref[...] = recv_ref[...]


def chunk_combine_pallas(
    local: jax.Array,               # (C, R, 128)
    recv: jax.Array,                # (C, R, 128)
    seg_mask: jax.Array,            # (C,) int32/bool
    accumulate: jax.Array,          # (C,) int32/bool
    *,
    block_rows: int,
    interpret: bool = False,
) -> jax.Array:
    C, R, lanes = local.shape
    assert lanes == LANES, f"last dim {lanes} must be {LANES}"
    assert R % block_rows == 0, f"R={R} must be a multiple of {block_rows}"
    block = pl.BlockSpec((None, block_rows, LANES), lambda c, r, *_: (c, r, 0))
    return pl.pallas_call(
        _combine_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(C, R // block_rows),
            in_specs=[block, block],
            out_specs=block,
        ),
        out_shape=jax.ShapeDtypeStruct(local.shape, local.dtype),
        interpret=interpret,
    )(seg_mask.astype(jnp.int32), accumulate.astype(jnp.int32), local, recv)
