"""Arithmetic shared by the plain references.

The references compute in fp32 under ``default_matmul_precision("highest")``.
Their control computes each weight matmul in fp8 instead (the precision
below the bf16 the configurations serve in): both operands are scaled to
``float8_e4m3fn``'s range, rounded to it and multiplied back out, the
weight with one scale per tensor and the activation with one per row,
as an fp8 serving path would; the products accumulate in fp32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

FP8 = jnp.float8_e4m3fn
FP8_MAX = float(jnp.finfo(FP8).max)


def fan_in_normal(key, shape, fan_in: int, dtype):
    """Normal weights with variance ``1 / fan_in``, cast to ``dtype``."""
    x = jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(jnp.float32(fan_in))
    return x.astype(dtype)


def fp8_round(x, axis):
    """``x`` rounded to fp8 after scaling its absolute maximum over ``axis``
    to fp8's largest value, then scaled back (fp32 out)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def dot(a, w, control: bool = False):
    """``a @ w`` in fp32 at the highest precision; in fp8 under ``control``."""
    if control:
        a = fp8_round(a, -1)
        w = fp8_round(w, None)
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)
