"""Model assembly: pattern-based block stacks for all 6 architecture types.

A model is ``embedding -> [pattern block groups] -> final norm -> unembed``
where the repeating pattern (e.g. ``("rglru","rglru","local_attn")`` for
RecurrentGemma, ``("local_attn","global_attn")`` for Gemma-2) is scanned
over ``num_groups`` repeats with stacked parameters — keeping the lowered
HLO one-pattern-group sized regardless of depth.  Remainder layers (depth
not divisible by the pattern) run unscanned.

Modes: ``train`` (full sequence, logits everywhere), ``prefill`` (build
caches, logits at last position), ``decode`` (one token + caches).
Caches are pytrees compatible with ``lax.scan`` slicing.

[vlm]/[audio] frontends are stubs per the task carve-out: the model
consumes precomputed patch/frame embeddings via a linear projector.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from . import layers as L
from . import mla as MLA
from . import moe as MOE
from . import rglru as RG
from . import rwkv6 as RW


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------

def _init_ffn(key, cfg: ModelConfig, layer_idx: int):
    """Dense MLP or MoE depending on config + first_k_dense."""
    use_moe = (cfg.moe is not None and cfg.moe.num_experts > 0
               and layer_idx >= (cfg.moe.first_k_dense if cfg.moe else 0))
    if use_moe:
        p, a = MOE.init_moe(key, cfg.d_model, cfg.moe.expert_d_ff or cfg.d_ff,
                            cfg.moe.num_experts, cfg.moe.num_shared_experts,
                            cfg.activation)
        return ("moe", p, a)
    p, a = L.init_mlp(key, cfg.d_model, cfg.d_ff, cfg.activation)
    return ("mlp", p, a)


def _init_layer(key, cfg: ModelConfig, kind: str, layer_idx: int):
    ks = jax.random.split(key, 4)
    norm_init, _ = L.make_norm(cfg.norm, cfg.d_model)
    params: dict[str, Any] = {"norm1": norm_init[0]}
    axes: dict[str, Any] = {"norm1": norm_init[1]}

    if kind in ("attn", "local_attn", "global_attn"):
        a = cfg.attention
        if a.kind == "mla":
            p, ax = MLA.init_mla(ks[0], cfg.d_model, a.num_heads,
                                 q_lora_rank=a.q_lora_rank,
                                 kv_lora_rank=a.kv_lora_rank,
                                 qk_nope_head_dim=a.qk_nope_head_dim,
                                 qk_rope_head_dim=a.qk_rope_head_dim,
                                 v_head_dim=a.v_head_dim)
        else:
            p, ax = L.init_gqa(ks[0], cfg.d_model, a.num_heads,
                               a.num_kv_heads, a.head_dim)
        params["attn"], axes["attn"] = p, ax
    elif kind == "rglru":
        w = cfg.rglru.lru_width or cfg.d_model
        p, ax = RG.init_rglru_block(ks[0], cfg.d_model, w, cfg.rglru.conv_width)
        params["rglru"], axes["rglru"] = p, ax
    elif kind == "rwkv":
        p, ax = RW.init_rwkv_block(ks[0], cfg.d_model, cfg.rwkv.head_size,
                                   cfg.rwkv.decay_lora, cfg.rwkv.tokenshift_lora)
        params["rwkv"], axes["rwkv"] = p, ax
        return params, axes          # rwkv block includes channel-mix
    else:
        raise ValueError(kind)

    n2, _ = L.make_norm(cfg.norm, cfg.d_model)
    params["norm2"], axes["norm2"] = n2
    ftype, fp, fa = _init_ffn(ks[1], cfg, layer_idx)
    params[ftype], axes[ftype] = fp, fa
    return params, axes


def _apply_layer(params, cfg: ModelConfig, kind: str, x, *, cache, mode,
                 prefix_len=None, window_override=None):
    """Returns (x_out, new_cache, aux_loss)."""
    _, norm_fn = L.make_norm(cfg.norm, cfg.d_model)
    aux = jnp.zeros((), jnp.float32)
    h = norm_fn(params["norm1"], x)

    if kind in ("attn", "local_attn", "global_attn"):
        a = cfg.attention
        if kind == "local_attn":
            window = a.sliding_window
        elif kind == "global_attn":
            window = window_override
        else:
            window = window_override or a.sliding_window
        if a.kind == "mla":
            y, new_cache = MLA.mla_attention(
                params["attn"], h, num_heads=a.num_heads,
                qk_nope_head_dim=a.qk_nope_head_dim,
                qk_rope_head_dim=a.qk_rope_head_dim,
                v_head_dim=a.v_head_dim, rope_theta=a.rope_theta,
                cache=cache, mode=mode)
        else:
            y, new_cache = L.gqa_attention(
                params["attn"], h, num_heads=a.num_heads,
                num_kv_heads=a.num_kv_heads, head_dim=a.head_dim,
                rope_theta=a.rope_theta, use_rope=a.use_rope,
                causal=a.causal, window=window, prefix_len=prefix_len,
                logit_cap=a.logit_softcap, cache=cache, mode=mode)
        x = x + y.astype(x.dtype)
    elif kind == "rglru":
        y, new_cache = RG.rglru_block(params["rglru"], h,
                                      conv_width=cfg.rglru.conv_width,
                                      state=cache, mode=mode)
        x = x + y.astype(x.dtype)
    elif kind == "rwkv":
        y, new_cache = RW.rwkv_block(params["rwkv"], h,
                                     head_size=cfg.rwkv.head_size,
                                     state=cache, mode=mode)
        return x + y.astype(x.dtype), new_cache, aux
    else:
        raise ValueError(kind)

    h2 = norm_fn(params["norm2"], x)
    if "moe" in params:
        y2, aux = MOE.moe_ffn(params["moe"], h2,
                              num_experts=cfg.moe.num_experts,
                              top_k=cfg.moe.top_k,
                              capacity_factor=cfg.moe.capacity_factor,
                              activation=cfg.activation,
                              router_aux_weight=cfg.moe.router_aux_weight,
                              expert_sharding=cfg.moe.expert_axis)
    else:
        y2 = L.mlp(params["mlp"], h2, cfg.activation)
    return x + y2.astype(x.dtype), new_cache, aux


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------

def _layer_cache(cfg: ModelConfig, kind: str, batch: int, context_len: int,
                 window_override=None, dtype=jnp.bfloat16):
    if kind in ("attn", "local_attn", "global_attn"):
        a = cfg.attention
        if a.kind == "mla":
            return MLA.init_mla_cache(batch, context_len, a.kv_lora_rank,
                                      a.qk_rope_head_dim, dtype)
        if kind == "local_attn" and a.sliding_window:
            size = min(a.sliding_window, context_len)
        elif window_override:
            size = min(window_override, context_len)
        else:
            size = context_len
        return L.init_kv_cache(batch, size, a.num_kv_heads, a.head_dim, dtype)
    if kind == "rglru":
        w = cfg.rglru.lru_width or cfg.d_model
        return RG.init_rglru_state(batch, w, cfg.rglru.conv_width, dtype)
    if kind == "rwkv":
        return RW.init_rwkv_state(batch, cfg.d_model, cfg.rwkv.head_size, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# model init / apply
# ---------------------------------------------------------------------------

def _pattern_split(cfg: ModelConfig) -> tuple[int, list[str], list[str]]:
    """(num_groups, pattern, remainder_kinds).

    Leading ``first_k_dense`` layers run unscanned (they have a different
    FFN), so the scan covers ``num_layers - first_k_dense``.
    """
    p = list(cfg.block_pattern)
    lead = cfg.moe.first_k_dense if (cfg.moe and cfg.moe.first_k_dense) else 0
    if not cfg.scan_layers:
        return 0, p, cfg.pattern_layers[lead:]
    effective = cfg.num_layers - lead
    n_groups = effective // len(p)
    remainder = cfg.pattern_layers[lead + n_groups * len(p):]
    return n_groups, p, remainder


def init_model(key, cfg: ModelConfig):
    """Returns (params, axes) pytrees."""
    keys = jax.random.split(key, 8)
    params: dict[str, Any] = {}
    axes: dict[str, Any] = {}

    emb_p, emb_a = L.init_embedding(keys[0], cfg.vocab_size, cfg.d_model,
                                    cfg.tie_embeddings)
    params["embed"], axes["embed"] = emb_p, emb_a

    if cfg.modality.kind in ("audio_frames", "vision_text"):
        params["frontend_proj"] = L.dense_init(
            keys[1], (cfg.modality.frontend_dim, cfg.d_model),
            cfg.modality.frontend_dim)
        axes["frontend_proj"] = (None, "embed")

    n_groups, pattern, remainder = _pattern_split(cfg)

    if n_groups > 0:
        def init_group(gkey):
            gks = jax.random.split(gkey, len(pattern))
            ps, as_ = [], []
            for i, kind in enumerate(pattern):
                # layer_idx for first_k_dense: use pattern position of group 0;
                # per-group idx handled by initializing group 0 separately if
                # first_k_dense is inside the scanned region (see below).
                p_, a_ = _init_layer(gks[i], cfg, kind, layer_idx=10**6)
                ps.append(p_)
                as_.append(a_)
            return tuple(ps), tuple(as_)

        gkeys = jax.random.split(keys[2], n_groups)
        sample_p, sample_a = init_group(gkeys[0])
        stacked = jax.vmap(lambda k: init_group(k)[0])(gkeys)
        params["blocks"] = stacked
        axes["blocks"] = jax.tree_util.tree_map(
            lambda ax: (None,) + tuple(ax), sample_a,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))

    # Leading dense layers (first_k_dense) and remainder layers run unscanned.
    lead = cfg.moe.first_k_dense if (cfg.moe and cfg.moe.first_k_dense) else 0
    if lead:
        lead_ps, lead_as = [], []
        lks = jax.random.split(keys[3], lead)
        for i in range(lead):
            p_, a_ = _init_layer(lks[i], cfg, cfg.pattern_layers[i], layer_idx=i)
            lead_ps.append(p_)
            lead_as.append(a_)
        params["lead"] = lead_ps
        axes["lead"] = lead_as

    if remainder:
        rks = jax.random.split(keys[4], len(remainder))
        rem_ps, rem_as = [], []
        for i, kind in enumerate(remainder):
            p_, a_ = _init_layer(rks[i], cfg, kind, layer_idx=10**6)
            rem_ps.append(p_)
            rem_as.append(a_)
        params["tail"] = rem_ps
        axes["tail"] = rem_as

    fn, _ = L.make_norm(cfg.norm, cfg.d_model)
    params["final_norm"], axes["final_norm"] = fn

    if cfg.mtp:
        # DeepSeek-V3 MTP module: project [h_t ; emb(t_{t+1})] -> d, one
        # extra block, shared unembedding.
        mk = jax.random.split(keys[5], 3)
        params["mtp_proj"] = L.dense_init(mk[0], (2 * cfg.d_model, cfg.d_model),
                                          2 * cfg.d_model)
        axes["mtp_proj"] = (None, "embed")
        p_, a_ = _init_layer(mk[1], cfg, cfg.block_pattern[-1], layer_idx=10**6)
        params["mtp_block"], axes["mtp_block"] = p_, a_
        n_, _ = L.make_norm(cfg.norm, cfg.d_model)
        params["mtp_norm"], axes["mtp_norm"] = n_
    return params, axes


def init_params(key, cfg: ModelConfig):
    """``init_model``'s params from one compiled program.  Run op by op,
    the init compiles one small program per random draw, which on a TPU
    costs about a minute for a 32-layer model."""
    return jax.jit(lambda k: init_model(k, cfg)[0])(key)


def init_caches(cfg: ModelConfig, batch: int, context_len: int,
                window_override=None, dtype=jnp.bfloat16):
    """Cache pytree matching the model structure (None in train mode)."""
    n_groups, pattern, remainder = _pattern_split(cfg)
    caches: dict[str, Any] = {}
    if n_groups > 0:
        def one(kind):
            c = _layer_cache(cfg, kind, batch, context_len, window_override, dtype)
            return jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape).copy(), c)
        caches["blocks"] = tuple(one(k) for k in pattern)
    lead = cfg.moe.first_k_dense if (cfg.moe and cfg.moe.first_k_dense) else 0
    if lead:
        caches["lead"] = [
            _layer_cache(cfg, cfg.pattern_layers[i], batch, context_len,
                         window_override, dtype) for i in range(lead)]
    if remainder:
        caches["tail"] = [
            _layer_cache(cfg, k, batch, context_len, window_override, dtype)
            for k in remainder]
    return caches


def apply_model(
    params,
    cfg: ModelConfig,
    batch: dict[str, jnp.ndarray],
    *,
    mode: str = "train",            # train | prefill | decode
    caches=None,
    window_override=None,
) -> tuple[jnp.ndarray, Any, jnp.ndarray]:
    """Forward pass.  ``batch`` keys by modality:

      text:          tokens (B,T)
      vision_text:   patches (B,P,frontend_dim) + tokens (B,T_text)
      audio_frames:  frames (B,T,frontend_dim)

    Returns (logits, new_caches, aux_loss).
    """
    prefix_len = None
    if cfg.modality.kind == "vision_text" and mode != "decode":
        patches = batch["patches"]
        x_img = patches @ params["frontend_proj"]
        x_txt = L.embed(params["embed"], batch["tokens"],
                        scale_by_dim=cfg.embedding_scale)
        x = jnp.concatenate([x_img.astype(x_txt.dtype), x_txt], axis=1)
        prefix_len = patches.shape[1]
    elif cfg.modality.kind == "audio_frames":
        x = batch["frames"] @ params["frontend_proj"]
    else:
        x = L.embed(params["embed"], batch["tokens"],
                    scale_by_dim=cfg.embedding_scale)
    x = x.astype(jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)

    n_groups, pattern, remainder = _pattern_split(cfg)
    total_aux = jnp.zeros((), jnp.float32)

    lead = cfg.moe.first_k_dense if (cfg.moe and cfg.moe.first_k_dense) else 0
    new_caches: dict[str, Any] = {}
    if lead:
        lead_caches = []
        for i in range(lead):
            c = caches["lead"][i] if caches else None
            x, c2, aux = _apply_layer(params["lead"][i], cfg,
                                      cfg.pattern_layers[i], x,
                                      cache=c, mode=mode,
                                      prefix_len=prefix_len,
                                      window_override=window_override)
            total_aux += aux
            lead_caches.append(c2)
        new_caches["lead"] = lead_caches

    if n_groups > 0:
        group_params = params["blocks"]
        group_caches = caches["blocks"] if caches else tuple(None for _ in pattern)

        def group_step(carry, scanned):
            x, aux_acc = carry
            gp, gc = scanned

            def body(x, aux_acc, gp, gc):
                new_gc = []
                for i, kind in enumerate(pattern):
                    c = gc[i] if gc is not None else None
                    x, c2, aux = _apply_layer(gp[i], cfg, kind, x, cache=c,
                                              mode=mode, prefix_len=prefix_len,
                                              window_override=window_override)
                    aux_acc = aux_acc + aux
                    new_gc.append(c2)
                return x, aux_acc, tuple(new_gc)

            if cfg.remat and mode == "train":
                x, aux_acc, new_gc = jax.checkpoint(
                    lambda x_, a_, p_: body(x_, a_, p_, gc))(x, aux_acc, gp)
            else:
                x, aux_acc, new_gc = body(x, aux_acc, gp, gc)
            out_caches = new_gc if mode != "train" else None
            return (x, aux_acc), out_caches

        scanned_caches = group_caches if mode != "train" else None
        if mode == "train":
            (x, total_aux), _ = lax.scan(
                lambda c, gp: group_step(c, (gp, None)),
                (x, total_aux), group_params)
        else:
            (x, total_aux), block_caches = lax.scan(
                group_step, (x, total_aux), (group_params, group_caches))
            new_caches["blocks"] = block_caches

    if remainder:
        tail_caches = []
        for i, kind in enumerate(remainder):
            c = caches["tail"][i] if caches else None
            x, c2, aux = _apply_layer(params["tail"][i], cfg, kind, x,
                                      cache=c, mode=mode,
                                      prefix_len=prefix_len,
                                      window_override=window_override)
            total_aux += aux
            tail_caches.append(c2)
        new_caches["tail"] = tail_caches

    _, norm_fn = L.make_norm(cfg.norm, cfg.d_model)
    xn = norm_fn(params["final_norm"], x)
    if mode == "prefill":
        xn = xn[:, -1:]                   # only the last position's logits
    cap = 30.0 if cfg.attention and cfg.attention.logit_softcap else None
    logits = L.unembed(params["embed"], xn, logit_cap=cap)

    # -- MTP auxiliary head (train only): predict token t+2 from
    #    [h_t ; emb(token_{t+1})] through one extra block -----------------
    if cfg.mtp and mode == "train" and cfg.modality.kind == "text":
        emb_next = L.embed(params["embed"], batch["tokens"],
                           scale_by_dim=cfg.embedding_scale).astype(xn.dtype)
        # align: position t pairs with the embedding of token t+1
        emb_shift = jnp.concatenate(
            [emb_next[:, 1:], jnp.zeros_like(emb_next[:, :1])], axis=1)
        h = jnp.concatenate([xn, emb_shift], axis=-1) @ params["mtp_proj"]
        h = h.astype(xn.dtype)
        h, _, mtp_aux = _apply_layer(params["mtp_block"], cfg,
                                     cfg.block_pattern[-1], h,
                                     cache=None, mode="train")
        total_aux += mtp_aux
        h = norm_fn(params["mtp_norm"], h)
        mtp_logits = L.unembed(params["embed"], h, logit_cap=cap)
        return logits, None, (total_aux, mtp_logits)

    return logits, (new_caches if mode != "train" else None), total_aux
