"""Mamba-2 (arXiv:2405.21060): weights from a seed, the plain reference, work counts.

The configuration file's ``model`` section holds the published
``config.json`` keys of a ``mamba_ssm`` MambaLMHeadModel; the mixer's own
sizes are the ``Mamba2`` layer's defaults, written out in the same
section (``d_state``, ``d_conv``, ``expand``, ``headdim``, ``ngroups``).
This module imports nothing of the program under test: the program
receives the weights through :func:`to_program`, which only renames and
nests the arrays into the program's parameter tree.

Architecture, as published: token embedding (vocabulary padded up to a
multiple of ``pad_vocab_size_multiple``) tied to the output head; per
layer an RMSNorm, then the Mamba-2 mixer — one input projection to
``[z, x, B, C, dt]``, a causal depthwise convolution (with bias) and
SiLU over ``x ⊕ B ⊕ C``, ``dt = softplus(dt + dt_bias)``, ``A =
-exp(A_log)``, the selective state recurrence ``h_t = exp(dt_t A) h_{t-1}
+ dt_t B_t x_t``, ``y_t = C_t h_t + D x_t``, a gated RMSNorm
``norm(y * silu(z))`` and the output projection — and a residual add; a
final RMSNorm, then the logits against the embedding table.  The
reference runs the recurrence step by step (a scan over time), which is
the definition; the program's chunked SSD and its decode step are two
other ways of computing it.  Departure: the published model keeps the
residual stream in fp32 (``residual_in_fp32``); the program keeps it in
bf16, and the reference (fp32 throughout) follows the publication.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.models.common import dot, fan_in_normal

EPS = 1e-5


def dims(model: dict) -> dict:
    d = model["d_model"]
    d_inner = model["expand"] * d
    heads = d_inner // model["headdim"]
    g, n = model["ngroups"], model["d_state"]
    mult = model["pad_vocab_size_multiple"]
    return {
        "d_model": d,
        "layers": model["n_layer"],
        "d_inner": d_inner,
        "ssm_heads": heads,
        "head_dim": model["headdim"],
        "d_state": n,
        "groups": g,
        "conv": model["d_conv"],
        "conv_dim": d_inner + 2 * g * n,
        "proj_dim": 2 * d_inner + 2 * g * n + heads,
        "vocab": mult * math.ceil(model["vocab_size"] / mult),
    }


def program_arch(model: dict) -> dict:
    """The program's ``ArchConfig`` fields, as this configuration sets them."""
    m = dims(model)
    return {
        "num_layers": m["layers"],
        "d_model": m["d_model"],
        "vocab_size": m["vocab"],
        "d_ff": 0,
        "block_pattern": ("mamba",),
        "tie_embeddings": bool(model["tie_embeddings"]),
        "norm": "rmsnorm",
        "norm_eps": EPS,
        "ssm": {
            "state_dim": m["d_state"],
            "head_dim": m["head_dim"],
            "expand": model["expand"],
            "conv_width": m["conv"],
            "chunk_size": model["chunk_size"],
            "num_groups": m["groups"],
        },
    }


def matmul_params(model: dict) -> int:
    """Weights one generated token multiplies through (layers and head)."""
    m = dims(model)
    per_layer = m["d_model"] * m["proj_dim"] + m["d_inner"] * m["d_model"]
    return m["layers"] * per_layer + m["vocab"] * m["d_model"]


def mixer_flops(model: dict, kv_lens) -> float:
    """State-space FLOPs of tokens decoded at contexts ``kv_lens`` (one
    entry a token; the work does not depend on the context): per layer the
    state update and read-out, 2 x heads x state x head_dim each, and the
    depthwise convolution."""
    m = dims(model)
    ssd = 4.0 * m["ssm_heads"] * m["d_state"] * m["head_dim"]
    conv = 2.0 * m["conv_dim"] * m["conv"]
    return m["layers"] * (ssd + conv) * float(np.size(kv_lens))


def init_weights(key, model: dict) -> dict:
    """Seeded weights in the types they are served in (bf16 matrices;
    fp32 norm scales and per-head constants), initialised as Mamba-2 does:
    ``A`` uniform in [1, 16], ``dt`` log-uniform in [0.001, 0.1] through
    the inverse softplus, ``D = 1``."""
    m = dims(model)
    n, d, di, h, c, w = (m["layers"], m["d_model"], m["d_inner"], m["ssm_heads"],
                         m["conv_dim"], m["conv"])
    ks = jax.random.split(key, 8)
    bf, f32 = jnp.bfloat16, jnp.float32
    bound = 1.0 / math.sqrt(w)
    dt = jnp.exp(jax.random.uniform(ks[5], (n, h), f32, math.log(1e-3), math.log(1e-1)))
    return {
        "embed": (jax.random.normal(ks[0], (m["vocab"], d), f32) * 0.02).astype(bf),
        "final_norm": jnp.ones((d,), f32),
        "layers": {
            "norm": jnp.ones((n, d), f32),
            "in_proj": fan_in_normal(ks[1], (n, d, m["proj_dim"]), d, bf),
            "conv_w": jax.random.uniform(ks[2], (n, w, c), f32, -bound, bound).astype(bf),
            "conv_b": jax.random.uniform(ks[3], (n, c), f32, -bound, bound).astype(bf),
            "A_log": jnp.log(jax.random.uniform(ks[4], (n, h), f32, 1.0, 16.0)),
            "D": jnp.ones((n, h), f32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "norm_scale": jnp.ones((n, di), f32),
            "out_proj": fan_in_normal(ks[6], (n, di, d), di, bf),
        },
    }


def to_program(w: dict) -> dict:
    """The program's parameter tree (``repro.models.transformer``), same arrays."""
    lw = w["layers"]
    return {
        "embed": {"embedding": w["embed"]},
        "blocks": {"block0": {
            "norm_mixer": {"scale": lw["norm"]},
            "mamba": {k: lw[k] for k in ("in_proj", "conv_w", "conv_b", "A_log",
                                         "D", "dt_bias", "norm_scale", "out_proj")},
        }},
        "final_norm": {"scale": w["final_norm"]},
        "head": {},
    }


def _rmsnorm(x, scale):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + EPS) * scale


def hidden(w: dict, tokens, model: dict, *, control: bool = False):
    """Final normed hidden states, fp32: tokens (R, S) -> (R, S, d).

    Layer by layer (a scan over the stacked layers, each upcast to fp32
    as it is reached), the recurrence a scan over time.  ``control``
    computes the projections in fp8 (see :func:`bench.models.common.dot`).
    """
    m = dims(model)
    r, s = tokens.shape
    di, h, p, n, g, cw = (m["d_inner"], m["ssm_heads"], m["head_dim"], m["d_state"],
                          m["groups"], m["conv"])
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(x, lw):
        lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
        proj = dot(_rmsnorm(x, lw["norm"]), lw["in_proj"], control)
        z, xbc, dt = proj[..., :di], proj[..., di:di + m["conv_dim"]], proj[..., -h:]
        padded = jnp.pad(xbc, ((0, 0), (cw - 1, 0), (0, 0)))
        conv = sum(padded[:, i:i + s] * lw["conv_w"][i] for i in range(cw)) + lw["conv_b"]
        xbc = jax.nn.silu(conv)
        xs = xbc[..., :di].reshape(r, s, h, p)
        bm = jnp.repeat(xbc[..., di:di + g * n].reshape(r, s, g, n), h // g, axis=2)
        cm = jnp.repeat(xbc[..., di + g * n:].reshape(r, s, g, n), h // g, axis=2)
        dt = jax.nn.softplus(dt + lw["dt_bias"])
        a = -jnp.exp(lw["A_log"])

        def step(state, t):
            xt, dtt, bt, ct = t  # (R,H,P) (R,H) (R,H,N) (R,H,N)
            state = (state * jnp.exp(dtt * a)[..., None, None]
                     + (dtt[..., None] * bt)[..., None] * xt[:, :, None, :])
            return state, jnp.einsum("rhn,rhnp->rhp", ct, state) + lw["D"][:, None] * xt

        seq = (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(dt, 1, 0),
               jnp.moveaxis(bm, 1, 0), jnp.moveaxis(cm, 1, 0))
        _, ys = lax.scan(step, jnp.zeros((r, h, n, p), jnp.float32), seq)
        y = jnp.moveaxis(ys, 0, 1).reshape(r, s, di)
        y = _rmsnorm(y * jax.nn.silu(z), lw["norm_scale"])
        return x + dot(y, lw["out_proj"], control), None

    with jax.default_matmul_precision("highest"):
        x, _ = lax.scan(layer, x, w["layers"])
    return _rmsnorm(x, w["final_norm"])


def logits(w: dict, x, model: dict, *, control: bool = False):
    """Logits of normed hidden states ``x`` (..., d) against the tied head."""
    with jax.default_matmul_precision("highest"):
        return dot(x, w["embed"].astype(jnp.float32).T, control)
