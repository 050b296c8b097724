"""Plain float32 reference of a Llama-style decoder, and the benchmark's weights.

It imports nothing of the program under test.  It reads the weights through
the key names of the program's parameter tree, which is how the program
stores a Llama decoder:

  embed.embedding (V, d)                       tied: also the unembedding
  blocks[0].norm1.scale, norm2.scale (L, d)    RMSNorm weight = 1 + scale
  blocks[0].attn.wq (L, d, H, D), wk / wv (L, d, KV, D), wo (L, H, D, d)
  blocks[0].mlp.wg / wu (L, d, F), wd (L, F, d)
  final_norm.scale (d,)

Every matrix product runs at ``Precision.HIGHEST`` in float32, attention is
the full causal softmax, and nothing is cached or fused.  ``quant``
replaces each operand of each matrix product by a lower-precision copy;
that is how the control of a comparison is computed (``fp8`` below).

Departures from the published SmolLM description, both the program's: the
RMSNorm weight is stored as ``scale`` and applied as ``1 + scale``, which is
a reparametrisation of Llama's ``weight``; and RMSNorm's epsilon is the
configuration file's ``rms_norm_eps``, which states the program's fixed 1e-6
(published: 1e-5) and lists it under ``reduced``.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import seeds

HI = lax.Precision.HIGHEST
Quant = Callable[[jax.Array], jax.Array] | None


@jax.custom_vjp
def fp8(x: jax.Array) -> jax.Array:
    """float8 e4m3 with one scale per tensor (largest magnitude -> 448),
    back in float32: the matrix-product inputs of an fp8 path.  Gradients
    pass through unchanged (the backward products see the rounded inputs
    and float32 cotangents)."""
    x = x.astype(jnp.float32)
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


fp8.defvjp(lambda x: (fp8(x), None), lambda _, g: (g,))


def _mm(eq: str, a, b, quant: Quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.einsum(eq, a, b, precision=HI, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# weights, made on the device from the seed in one program
# ---------------------------------------------------------------------------

def param_shapes(cfg: dict) -> dict:
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    n_l, h, kv = cfg["num_hidden_layers"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    return {
        "blocks": ({
            "attn": {"wk": (n_l, d, kv, hd), "wo": (n_l, h, hd, d),
                     "wq": (n_l, d, h, hd), "wv": (n_l, d, kv, hd)},
            "mlp": {"wd": (n_l, f, d), "wg": (n_l, d, f), "wu": (n_l, d, f)},
            "norm1": {"scale": (n_l, d)}, "norm2": {"scale": (n_l, d)},
        },),
        "embed": {"embedding": (v, d)},
        "final_norm": {"scale": (d,)},
    }


def _std(path: str, shape: tuple, cfg: dict) -> float:
    if "norm" in path:
        return 0.1                       # RMSNorm weights 1 + N(0, 0.1^2)
    if "embedding" in path:
        return 0.02                      # SmolLM's initializer_range
    fan_in = shape[1] if "wo" not in path else shape[1] * shape[2]
    return 1.0 / math.sqrt(fan_in)


def make_params(cfg: dict, seed: int, sharding=None):
    """Float32 weights from ``seed``, made by one jitted program."""
    shapes = param_shapes(cfg)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple) and all(
            isinstance(e, int) for e in s))
    names = [jax.tree_util.keystr(p) for p, _ in paths]
    dims = [s for _, s in paths]

    def build(key):
        keys = jax.random.split(key, len(dims))
        return treedef.unflatten([
            jax.random.normal(k, s, jnp.float32) * _std(n, s, cfg)
            for k, n, s in zip(keys, names, dims)])

    return jax.jit(build, out_shardings=sharding)(seeds.key(seed, "weights"))


def make_tokens(cfg: dict, seed: int, shape: tuple, sharding=None):
    """Token ids uniform over the vocabulary, from ``seed``: one program."""
    return jax.jit(lambda k: jax.random.randint(k, shape, 0, cfg["vocab_size"],
                                                jnp.int32),
                   out_shardings=sharding)(seeds.key(seed, "data"))


# ---------------------------------------------------------------------------
# forward pass
# ---------------------------------------------------------------------------

def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + scale)


def _rope(x, theta):
    """Rotary embedding, rotate-half convention; x (B, T, H, D)."""
    t, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs      # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]      # (T, 1, D/2)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(x, p, cfg: dict, quant: Quant):
    eps, hd = cfg["rms_norm_eps"], cfg["head_dim"]
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    h = _rmsnorm(x, p["norm1"]["scale"], eps)
    a = p["attn"]
    q = _rope(_mm("btd,dhk->bthk", h, a["wq"], quant), cfg["rope_theta"])
    k = _rope(_mm("btd,dhk->bthk", h, a["wk"], quant), cfg["rope_theta"])
    v = _mm("btd,dhk->bthk", h, a["wv"], quant)
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, k, quant) / math.sqrt(hd)
    t = x.shape[1]
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, quant)
    x = x + _mm("bthk,hkd->btd", o, a["wo"], quant)
    h = _rmsnorm(x, p["norm2"]["scale"], eps)
    m = p["mlp"]
    gate = jax.nn.silu(_mm("btd,df->btf", h, m["wg"], quant))
    up = _mm("btd,df->btf", h, m["wu"], quant)
    return x + _mm("btf,fd->btd", gate * up, m["wd"], quant)


def logits(params, tokens, cfg: dict, quant: Quant = None):
    """Float32 logits (B, T, V) for token ids (B, T)."""
    emb = params["embed"]["embedding"]
    x = jnp.take(emb, tokens, axis=0)
    layer = jax.checkpoint(lambda x, p: _layer(x, p, cfg, quant))
    x, _ = lax.scan(lambda x, p: (layer(x, p), None), x, params["blocks"][0])
    x = _rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return _mm("btd,vd->btv", x, emb, quant)


def _loss_sum(params, tokens, labels, cfg, quant):
    lg = logits(params, tokens, cfg, quant)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


# ---------------------------------------------------------------------------
# training: loss, gradient and AdamW, the batch in blocks of rows
# ---------------------------------------------------------------------------

def loss_and_grads(params, tokens, labels, cfg: dict, *, rows: int = 1,
                   quant: Quant = None):
    """Mean next-token cross entropy over all rows and its gradient, summed
    block by block of ``rows`` rows so that the activations fit."""
    fn = _grad_fn(cfg, quant)
    n_rows = tokens.shape[0]
    total, grads = 0.0, None
    for r in range(0, n_rows, rows):
        loss, g = fn(params, tokens[r:r + rows], labels[r:r + rows])
        total = total + loss
        grads = g if grads is None else _add(grads, g)
    count = tokens.shape[0] * tokens.shape[1]
    return total / count, _scale(grads, 1.0 / count)


_GRAD_FNS: dict = {}


def _grad_fn(cfg: dict, quant: Quant):
    key = (tuple(sorted((k, str(v)) for k, v in cfg.items())), quant)
    if key not in _GRAD_FNS:
        _GRAD_FNS[key] = jax.jit(jax.value_and_grad(
            lambda p, t, lab: _loss_sum(p, t, lab, cfg, quant)))
    return _GRAD_FNS[key]


_add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
_scale = jax.jit(lambda a, s: jax.tree_util.tree_map(lambda x: x * s, a))


def lr_scale(step: int, opt: dict) -> float:
    """Linear warm-up, then cosine decay to ``min_lr_ratio``."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    progress = min(max((step - opt["warmup_steps"]) /
                       max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    cos = 0.5 * (1.0 + math.cos(math.pi * progress))
    return warm * (opt["min_lr_ratio"] + (1.0 - opt["min_lr_ratio"]) * cos)


def _adamw(params, grads, mu, nu, count: int, lr: float, opt: dict):
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads)))
    clip = jnp.minimum(1.0, opt["grad_clip_norm"] / jnp.maximum(gnorm, 1e-9))
    b1, b2 = opt["b1"], opt["b2"]
    b1c, b2c = 1.0 - b1 ** count, 1.0 - b2 ** count

    def upd(p, g, m, v):
        g = g * clip
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        step = (m / b1c) / (jnp.sqrt(v / b2c) + opt["eps"])
        return p - lr * (step + opt["weight_decay"] * p), m, v

    flat, treedef = jax.tree_util.tree_flatten(params)
    out = [upd(p, g, m, v) for p, g, m, v in zip(
        flat, treedef.flatten_up_to(grads), treedef.flatten_up_to(mu),
        treedef.flatten_up_to(nu))]
    return tuple(treedef.unflatten([o[i] for o in out]) for i in range(3))


_adamw_jit = jax.jit(_adamw, static_argnums=(6,))


def leaf_norms(tree) -> dict[str, float]:
    """Float32 norm of each leaf, by its path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                                for x in xs])([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in zip(flat, norms)}


def diff_norms(a, b) -> dict[str, float]:
    return leaf_norms(jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))(a, b))


def train_readings(params, batches, cfg: dict, opt: dict, *, rows: int = 1,
                   quant: Quant = None, keep_rows=None) -> dict:
    """Follow ``len(batches)`` AdamW steps from ``params``.

    ``batches`` is a list of ``(tokens, labels)``.  Returns each step's loss,
    the first step's gradient norm by leaf (before clipping, as the optimizer
    is handed it) and each leaf's change after the last step.  ``keep_rows``
    keeps only those rows of every batch: a planted fault.
    """
    tm = jax.tree_util.tree_map
    p0 = params
    mu = tm(jnp.zeros_like, params)
    nu = tm(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for i, (tokens, labels) in enumerate(batches):
        if keep_rows is not None:
            tokens, labels = tokens[keep_rows], labels[keep_rows]
        loss, grads = loss_and_grads(params, tokens, labels, cfg, rows=rows,
                                     quant=quant)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = leaf_norms(grads)
        lr = opt["lr"] * lr_scale(i, opt)
        params, mu, nu = _adamw_jit(params, grads, mu, nu, i + 1, lr,
                                    _Hashable(opt))
        del grads
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": diff_norms(params, p0)}


class _Hashable(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def compare_train(got: dict, ref: dict, floor: float = 1e-3) -> dict[str, float]:
    """The three training numbers, each the worst over steps or leaves.

    ``loss_gap``: |loss - reference loss| / reference loss, worst step.
    ``grad_gap`` / ``update_gap``: |leaf norm - reference leaf norm| over the
    larger of the reference leaf's norm and the median leaf's, worst leaf.
    Leaves whose reference gradient is under ``floor`` times the median
    leaf's move by round-off alone under Adam and are left out of both.
    """
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"]))
    g_med = float(np.median(list(ref["grad_norms"].values())))
    leaves = [k for k, v in ref["grad_norms"].items() if v >= floor * g_med]

    def gap(key: str) -> float:
        med = float(np.median([ref[key][k] for k in leaves]))
        return max(abs(got[key][k] - ref[key][k]) / max(ref[key][k], med)
                   for k in leaves)

    return {"loss_gap": loss_gap, "grad_gap": gap("grad_norms"),
            "update_gap": gap("update_norms")}


# ---------------------------------------------------------------------------
# serving: the widest gap of a served token's logit below the best
# ---------------------------------------------------------------------------

def served_gaps(params, prompts: np.ndarray, served: np.ndarray, cfg: dict,
                *, quant: Quant = None, rows: int = 8) -> np.ndarray:
    """For each request and served position, the reference's best logit
    minus its logit of the served token (>= 0; 0 when they agree).

    With ``quant`` the served token is the one the lower precision puts
    first, and the gap is still read off the float32 reference: the
    control's reading.  ``prompts`` (R, P), ``served`` (R, N).
    """
    fn = _gap_fn(cfg, quant, prompts.shape[1])
    out = []
    for r in range(0, len(prompts), rows):
        seq = np.concatenate([prompts[r:r + rows], served[r:r + rows, :-1]], axis=1)
        out.append(np.asarray(fn(params, jnp.asarray(seq),
                                 jnp.asarray(served[r:r + rows]))))
    return np.concatenate(out)


_GAP_FNS: dict = {}


def _gap_fn(cfg: dict, quant: Quant, prompt_len: int):
    key = (tuple(sorted((k, str(v)) for k, v in cfg.items())), quant, prompt_len)
    if key not in _GAP_FNS:
        def fn(params, seq, served):
            ref = logits(params, seq, cfg)[:, prompt_len - 1:]
            if quant is not None:
                served = jnp.argmax(logits(params, seq, cfg, quant)[:, prompt_len - 1:], -1)
            picked = jnp.take_along_axis(ref, served[..., None], axis=-1)[..., 0]
            return jnp.max(ref, axis=-1) - picked
        _GAP_FNS[key] = jax.jit(fn)
    return _GAP_FNS[key]
