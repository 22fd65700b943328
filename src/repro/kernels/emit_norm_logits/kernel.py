"""Fused emit-epilogue Pallas kernel: final norm + LM-head matmul.

``make_decode_emit`` closes the decode feedback loop with final-norm →
logits → sample; unfused, the norm round-trips the (B, d) hidden state
through HBM (fp32 upcast, variance reduce, normalize) before the head
matmul reads it again.  This kernel tiles the vocab axis and recomputes
the (tiny, B×d) normalization per tile in VMEM, so each weight tile is
read once and the hidden state never materializes a normalized copy in
HBM.  The per-tile recompute is bitwise-stable: every logit is an
independent d-length dot, so vocab tiling cannot change its reduction
order.  The dot accumulates in fp32 (the MXU's accumulator) and rounds
once to the input dtype, as the unfused bf16 ``einsum`` in
``layers.logits`` does — outputs are bitwise equal to the unfused path
(ref.py) under the interpreter.

Supports both norms the configs use (rmsnorm and OLMo's non-parametric
layernorm) and both head layouts (untied ``(d, V)`` / tied embedding
``(V, d)``), mirroring ``layers.logits``'s einsum + fp32 cast exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _normalize(x_ref, scale_ref, *, norm: str, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (B, d)
    if norm == "rmsnorm":
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        xn = x * lax.rsqrt(var + eps) * scale_ref[...]
    else:  # layernorm_nonparam
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        xn = (x - mu) * lax.rsqrt(var + eps)
    return xn.astype(x_ref.dtype)


# The output block stays in the input dtype (as ``layers.logits``'s dot
# output does) and the fp32 upcast happens OUTSIDE the pallas_call: the
# store is the one rounding of the fp32 accumulator, so XLA's
# float-normalization cleanup cannot elide it.

def _head_dot(xn, w_ref, o_ref, tied):
    eq = "bd,vd->bv" if tied else "bd,dv->bv"
    acc = jnp.einsum(eq, xn, w_ref[...], preferred_element_type=jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def _emit_kernel_scaled(x_ref, scale_ref, w_ref, o_ref, *, norm, eps, tied):
    _head_dot(_normalize(x_ref, scale_ref, norm=norm, eps=eps), w_ref, o_ref, tied)


def _emit_kernel_plain(x_ref, w_ref, o_ref, *, norm, eps, tied):
    _head_dot(_normalize(x_ref, None, norm=norm, eps=eps), w_ref, o_ref, tied)


@functools.partial(
    jax.jit,
    static_argnames=("norm", "eps", "tied", "block_v", "interpret"),
)
def emit_norm_logits_pallas(
    x: jnp.ndarray,           # (B, d)
    w: jnp.ndarray,           # (d, V) untied | (V, d) tied
    scale: jnp.ndarray | None,  # (d,) fp32 (rmsnorm only)
    *,
    norm: str,
    eps: float = 1e-5,
    tied: bool = False,
    block_v: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    b, d = x.shape
    v = w.shape[0] if tied else w.shape[1]
    block_v = min(block_v, v)
    while block_v > 1 and v % block_v != 0:
        block_v //= 2
    grid = (v // block_v,)
    x_spec = pl.BlockSpec((b, d), lambda j: (0, 0))
    w_spec = (
        pl.BlockSpec((block_v, d), lambda j: (j, 0))
        if tied
        else pl.BlockSpec((d, block_v), lambda j: (0, j))
    )
    o_spec = pl.BlockSpec((b, block_v), lambda j: (0, j))
    out_shape = jax.ShapeDtypeStruct((b, v), x.dtype, vma=jax.typeof(x).vma)
    if norm == "rmsnorm":
        out = pl.pallas_call(
            functools.partial(
                _emit_kernel_scaled, norm=norm, eps=eps, tied=tied
            ),
            grid=grid,
            in_specs=[x_spec, pl.BlockSpec((d,), lambda j: (0,)), w_spec],
            out_specs=o_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(x, scale.astype(jnp.float32), w)
    else:
        out = pl.pallas_call(
            functools.partial(
                _emit_kernel_plain, norm=norm, eps=eps, tied=tied
            ),
            grid=grid,
            in_specs=[x_spec, w_spec],
            out_specs=o_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(x, w)
    return out.astype(jnp.float32)
