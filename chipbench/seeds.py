"""Keys from a run's ``--seed``: every seed up to 64 bits, one stream each.

``jax.random.key`` keeps only the low 32 bits of a Python int, so two seeds
that differ above bit 31 would give the same inputs; both halves are folded
in here.
"""

from __future__ import annotations

import numpy as np

#: one stream per kind of input, so that weights and data never share draws
STREAMS = {"weights": 1, "data": 2, "sample": 4}


def key(seed: int, stream: str):
    import jax
    k = jax.random.key(0)
    k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, STREAMS[stream])


def rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, STREAMS[stream]])
