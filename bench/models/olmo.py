"""OLMo (arXiv:2402.00838): weights from a seed, the plain reference, work counts.

The configuration file's ``model`` section holds the published
``config.json`` keys (transformers format); everything here is computed
from them.  This module imports nothing of the program under test: the
program receives the weights through :func:`to_program`, which only
renames and nests the arrays into the program's parameter tree.

Architecture, as published: token embedding tied to the output head; per
layer a non-parametric LayerNorm (eps 1e-5), multi-head attention with
rotary position embedding (the half-split "rotate_half" form, base
``rope_theta``), no biases, a residual add, a second non-parametric
LayerNorm and a SwiGLU MLP (``silu(x W_gate) * (x W_up)`` then
``W_down``), a residual add; a final non-parametric LayerNorm, then the
logits against the embedding table.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.models.common import dot, fan_in_normal

EPS = 1e-5


def dims(model: dict) -> dict:
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    return {
        "d_model": d,
        "layers": model["num_hidden_layers"],
        "heads": h,
        "kv_heads": model.get("num_key_value_heads", h),
        "head_dim": d // h,
        "d_ff": model["intermediate_size"],
        "vocab": model["vocab_size"],
    }


def program_arch(model: dict) -> dict:
    """The program's ``ArchConfig`` fields, as this configuration sets them."""
    m = dims(model)
    return {
        "num_layers": m["layers"],
        "d_model": m["d_model"],
        "num_heads": m["heads"],
        "num_kv_heads": m["kv_heads"],
        "head_dim": m["head_dim"],
        "d_ff": m["d_ff"],
        "vocab_size": m["vocab"],
        "rope_theta": float(model["rope_theta"]),
        "tie_embeddings": bool(model["tie_word_embeddings"]),
        "norm": "layernorm_nonparam",
        "norm_eps": EPS,
        "block_pattern": ("attn",),
    }


def matmul_params(model: dict) -> int:
    """Weights one generated token multiplies through (layers and head)."""
    m = dims(model)
    d, h, kv, dh, f = m["d_model"], m["heads"], m["kv_heads"], m["head_dim"], m["d_ff"]
    per_layer = d * h * dh * 2 + d * kv * dh * 2 + 3 * d * f
    return m["layers"] * per_layer + m["vocab"] * d


def mixer_flops(model: dict, kv_lens) -> float:
    """Attention FLOPs (QK^T and PV) of tokens decoded at contexts
    ``kv_lens`` (one entry a token, each the rows it attends to)."""
    m = dims(model)
    return 4.0 * m["layers"] * m["heads"] * m["head_dim"] * float(np.sum(kv_lens))


def init_weights(key, model: dict) -> dict:
    """Seeded weights in bf16, the type they are served in."""
    m = dims(model)
    d, h, kv, dh, f, n = (m["d_model"], m["heads"], m["kv_heads"],
                          m["head_dim"], m["d_ff"], m["layers"])
    ks = jax.random.split(key, 8)
    bf = jnp.bfloat16
    return {
        "embed": (jax.random.normal(ks[0], (m["vocab"], d), jnp.float32) * 0.02).astype(bf),
        "layers": {
            "wq": fan_in_normal(ks[1], (n, d, h, dh), d, bf),
            "wk": fan_in_normal(ks[2], (n, d, kv, dh), d, bf),
            "wv": fan_in_normal(ks[3], (n, d, kv, dh), d, bf),
            "wo": fan_in_normal(ks[4], (n, h, dh, d), h * dh, bf),
            "w_gate": fan_in_normal(ks[5], (n, d, f), d, bf),
            "w_up": fan_in_normal(ks[6], (n, d, f), d, bf),
            "w_down": fan_in_normal(ks[7], (n, f, d), f, bf),
        },
    }


def to_program(w: dict) -> dict:
    """The program's parameter tree (``repro.models.transformer``), same arrays."""
    lw = w["layers"]
    return {
        "embed": {"embedding": w["embed"]},
        "blocks": {"block0": {
            "attn": {k: lw[k] for k in ("wq", "wk", "wv", "wo")},
            "mlp": {k: lw[k] for k in ("w_gate", "w_up", "w_down")},
            "norm_mixer": {},
            "norm_ffn": {},
        }},
        "final_norm": {},
        "head": {},
    }


def _layernorm(x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + EPS)


def _rope(x, theta):
    """x: (R, S, heads, dh); positions 0..S-1."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v):
    """Causal softmax attention of one sequence: q (S,H,dh), k/v (S,KV,dh)."""
    s, h, dh = q.shape
    rep = h // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(dh))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)


def hidden(w: dict, tokens, model: dict, *, control: bool = False):
    """Final normed hidden states, fp32: tokens (R, S) -> (R, S, d).

    Layer by layer (a scan over the stacked layers), each layer's weights
    upcast to fp32 as it is reached.  ``control`` computes every weight
    matmul in fp8 (see :func:`bench.models.common.dot`).
    """
    m = dims(model)
    r, s = tokens.shape
    d, h, kv, dh = m["d_model"], m["heads"], m["kv_heads"], m["head_dim"]
    theta = float(model["rope_theta"])
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(x, lw):
        lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
        hN = _layernorm(x)
        q = dot(hN, lw["wq"].reshape(d, h * dh), control).reshape(r, s, h, dh)
        k = dot(hN, lw["wk"].reshape(d, kv * dh), control).reshape(r, s, kv, dh)
        v = dot(hN, lw["wv"].reshape(d, kv * dh), control).reshape(r, s, kv, dh)
        q, k = _rope(q, theta), _rope(k, theta)
        ctx = lax.map(lambda qkv: _attention(*qkv), (q, k, v))
        x = x + dot(ctx.reshape(r, s, h * dh), lw["wo"].reshape(h * dh, d), control)
        hN = _layernorm(x)
        act = jax.nn.silu(dot(hN, lw["w_gate"], control)) * dot(hN, lw["w_up"], control)
        return x + dot(act, lw["w_down"], control), None

    with jax.default_matmul_precision("highest"):
        x, _ = lax.scan(layer, x, w["layers"])
    return _layernorm(x)


def logits(w: dict, x, model: dict, *, control: bool = False):
    """Logits of normed hidden states ``x`` (..., d) against the tied head."""
    with jax.default_matmul_precision("highest"):
        return dot(x, w["embed"].astype(jnp.float32).T, control)
