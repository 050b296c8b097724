"""Operations and bytes that the benchmarked work needs, from its shapes.

Model FLOPs follow PaLM (Chowdhery et al. 2022, Appendix B): a token costs
``2N`` FLOPs forward and ``6N`` forward and backward, where ``N`` counts the
parameters that enter a matrix multiplication for that token (every layer's
projections and the unembedding; a tied embedding counts once, as the
unembedding, since its lookup is no matrix multiplication), plus attention:
``4 L d_attn T`` forward and ``12 L d_attn T`` forward and backward, with
``d_attn = heads x head_dim`` and ``T`` the positions attended.  Recomputation
under rematerialisation is not counted.

AllReduce bytes follow the nccl-tests convention (``doc/PERFORMANCE.md``): a
ring moves ``2 (n-1)/n S`` bytes per rank over the wire, and busbw is the
algorithm bandwidth ``S / t`` times ``2 (n-1)/n``.  The least HBM traffic of
an AllReduce is reading the input and writing the result once: ``2 S``.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """The device kind has no entry in ``peaks.json``."""


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {path.name}; "
            f"known: {sorted(table)}")
    return table[device_kind]


def matmul_params(cfg: dict) -> int:
    """``N``: parameters that a token multiplies through (see module doc)."""
    d = cfg["hidden_size"]
    hd = cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    attn = d * q + 2 * d * kv + q * d
    mlp = 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (attn + mlp) + d * cfg["vocab_size"]


def _attn(cfg: dict, positions: float) -> float:
    d_attn = cfg["num_attention_heads"] * cfg["head_dim"]
    return 4.0 * cfg["num_hidden_layers"] * d_attn * positions


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 6.0 * matmul_params(cfg) + 3.0 * _attn(cfg, seq_len)


def prefill_flops(cfg: dict, batch: int, prompt_len: int) -> float:
    """A prefill needs the logits of the last position only."""
    unembed = cfg["hidden_size"] * cfg["vocab_size"]
    per_token = 2.0 * (matmul_params(cfg) - unembed) + _attn(cfg, prompt_len)
    return batch * (prompt_len * per_token + 2.0 * unembed)


def decode_flops(cfg: dict, batch: int, context: int) -> float:
    """One decode step of ``batch`` tokens, each attending ``context``
    positions (the prompt and the tokens decoded so far, itself included)."""
    return batch * (2.0 * matmul_params(cfg) + _attn(cfg, context))


def allreduce_wire_bytes(size: int, n: int) -> float:
    return 2.0 * (n - 1) / n * size


def allreduce_hbm_bytes(size: int) -> float:
    return 2.0 * size


def busbw(size: int, seconds_per_call: float, n: int) -> float:
    """nccl-tests bus bandwidth in bytes/s."""
    return size / seconds_per_call * 2.0 * (n - 1) / n
