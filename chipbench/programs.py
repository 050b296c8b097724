"""The system under test, as the drivers build it from a configuration file."""

from __future__ import annotations

import dataclasses

import numpy as np


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file: the registry
    entry named by ``program.registry`` with every size the file states."""
    from repro.models import get_config

    base = get_config(cfg["program"]["registry"])
    attn = dataclasses.replace(
        base.attention, num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=cfg["rope_theta"])
    return dataclasses.replace(
        base, num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        attention=attn, tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["precision"]["compute"])


def data_mesh(devices):
    """A ``data`` x ``model`` mesh over ``devices``, all on ``data``: one
    full replica per chip, as ``launch/train.py:train`` builds it."""
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(devices).reshape(len(devices), 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
