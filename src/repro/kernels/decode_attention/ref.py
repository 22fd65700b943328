"""Pure-jnp oracle: today's unfused decode-attention path, verbatim.

This is the exact op sequence ``repro.models.transformer._self_attn``
runs on the decode (S==1) path: functionally update the K/V slab at each
sequence's write position (``.at[idx, pos].set`` — the HBM slab copy the
fused kernel removes), then dense attention over the updated slab with
the ``kv_len`` prefix mask.  The kernel is gated on matching this
function (bitwise in bf16 while the cache is one block, to one bf16 ulp
when it walks several); this function stays bitwise equal to the model
path by calling the same :func:`repro.models.layers.attention_dense`.
"""
from __future__ import annotations

import jax.numpy as jnp


def decode_attention_ref(
    q: jnp.ndarray,       # (B, 1, H, dh) — the one decode query
    k_new: jnp.ndarray,   # (B, KV, dh) — this step's K row (cache dtype)
    v_new: jnp.ndarray,   # (B, KV, dh)
    k_cache: jnp.ndarray, # (B, S, KV, dh) — the cache slab (pre-update)
    v_cache: jnp.ndarray, # (B, S, KV, dh)
    *,
    pos: jnp.ndarray,     # (B,) int32 per-sequence write position
    kv_len: jnp.ndarray,  # (B,) or (B,1) valid KV count after the write
    softmax_scale: float | None = None,
    interpret: bool | None = None,  # accepted for signature parity
) -> jnp.ndarray:
    from repro.models.layers import attention_dense

    b = q.shape[0]
    idx = jnp.arange(b)
    ck = k_cache.at[idx, pos].set(k_new)
    cv = v_cache.at[idx, pos].set(v_new)
    return attention_dense(
        q, ck, cv, causal=False,
        kv_len=jnp.asarray(kv_len).reshape(b, 1),
        softmax_scale=softmax_scale,
    )
