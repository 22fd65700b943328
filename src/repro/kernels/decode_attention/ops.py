"""Jit'd public wrapper: model layout adaptation + interpret fallback.

Model code hands the decode query as ``(B, 1, H, dh)`` (the S==1 decode
step) and per-sequence ``kv_len`` as ``(B,)`` or ``(B, 1)``; the kernel
wants flat per-row operands.  The ``pallas_decode_attention`` name scope
is the structural marker ``roofline.hlo_parse.fused_region_present``
asserts on in compiled round HLO.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.decode_attention.kernel import (
    DEFAULT_BLOCK_S,
    decode_attention_pallas,
)

FUSION_SCOPE = "pallas_decode_attention"


def fused_decode_attention(
    q: jnp.ndarray,        # (B, 1, H, dh)
    k_new: jnp.ndarray,    # (B, KV, dh)
    v_new: jnp.ndarray,    # (B, KV, dh)
    k_cache: jnp.ndarray,  # (B, S, KV, dh)
    v_cache: jnp.ndarray,  # (B, S, KV, dh)
    *,
    pos: jnp.ndarray,      # (B,) int32 write positions
    kv_len: jnp.ndarray,   # (B,) or (B, 1) valid KV count after the write
    softmax_scale: float | None = None,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Drop-in for the slab-update + attention_dense decode path; returns
    the attention context ``(B, 1, H, dh)`` (ref.py to fp32 rounding)."""
    if interpret is None:
        interpret = default_interpret()
    b = q.shape[0]
    with jax.named_scope(FUSION_SCOPE):
        out = decode_attention_pallas(
            q[:, 0],
            k_new, v_new, k_cache, v_cache,
            jnp.asarray(pos).reshape(b),
            jnp.asarray(kv_len).reshape(b),
            softmax_scale=softmax_scale,
            block_s=block_s,
            interpret=interpret,
        )
    return out[:, None]
