"""Fused decode-attention Pallas kernel: KV row scatter + single-row read.

One decode tick's attention against the cache is, unfused, three HLO
ops per layer: scatter K row into the slab, scatter V row, dense
attention over both updated slabs — the scatters materialize two full
``(B, S, KV, dh)`` copies in HBM whose only consumer is the very next
dot.  This kernel consumes the *pre-update* cache pages plus the new
rows and emits the attention output directly: the updated rows exist
only as VMEM values (``jnp.where`` against a row iota), never in HBM.
The caller still owns the durable row-level cache write
(:func:`repro.models.transformer.scatter_decode_rows` on the tick
carry) — that write is the row itself, not a slab.

The grid is ``(batch row, cache block)``: each program walks one
``(block_s, KV, dh)`` page of K and V, so VMEM holds a block, never a
whole ``max_len`` slab.  Softmax is online (running max, running sum and
an unnormalized fp32 accumulator in VMEM scratch, normalized after the
last block).  Blocks past a row's ``kv_len`` are neither computed nor
fetched: their block index is clamped to the last valid block, which
Pallas does not copy again.  Scores and the V reduction are lane
reductions on the VPU, not matmuls: with one query row per KV head the
MXU would run at 1/128 occupancy, and the op is bound by the cache read.

Numerics: fp32 throughout, as :func:`repro.models.layers.attention_dense`,
but the softmax normalizes after the V reduction instead of before it
and sums block by block, so the result matches the dense path to fp32
rounding (one bf16 ulp after the cast back), not bitwise.

``pos``/``kv_len`` ride scalar prefetch (SMEM): they gate the in-VMEM
row substitution and the mask, and clamp the cache block index.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Cache rows per grid step.  Two (block_s, KV, dh) bf16 pages double-
# buffered plus their fp32 working copies fit v5e's 16 MiB scoped VMEM
# at KV x dh = 16 x 128 (olmo-1b).
DEFAULT_BLOCK_S = 256


def _decode_attention_kernel(
    pos_ref, len_ref,            # scalar prefetch: (B,) int32 each
    q_ref,                       # (1, H, dh)
    kn_ref, vn_ref,              # (1, KV, dh) — this step's rows
    kc_ref, vc_ref,              # (1, block_s, KV, dh) — pre-update pages
    o_ref,                       # (1, H, dh)
    m_ref, l_ref, acc_ref,       # scratch: (G, KV, 1) x2, (G, KV, dh) fp32
    *,
    scale: float,
    block_s: int,
):
    bb = pl.program_id(0)
    j = pl.program_id(1)
    pos = pos_ref[bb]
    klen = len_ref[bb]
    g, kv, dh = acc_ref.shape

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j * block_s < klen)
    def _block():
        row = j * block_s + lax.broadcasted_iota(jnp.int32, (block_s, 1, 1), 0)
        # The "scatter" half: substitute the new row at ``pos`` in VMEM only.
        k = jnp.where(row == pos, kn_ref[0][None], kc_ref[0]).astype(jnp.float32)
        v = jnp.where(row == pos, vn_ref[0][None], vc_ref[0]).astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32).reshape(kv, g, dh)
        valid = row < klen  # (block_s, 1, 1)
        for gi in range(g):
            s = jnp.sum(k * q[:, gi][None], axis=-1, keepdims=True) * scale
            s = jnp.where(valid, s, -jnp.inf)  # (block_s, KV, 1)
            m_prev = m_ref[gi]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))  # (KV, 1)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[None])
            l_ref[gi] = alpha * l_ref[gi] + jnp.sum(p, axis=0)
            acc_ref[gi] = alpha * acc_ref[gi] + jnp.sum(p * v, axis=0)
            m_ref[gi] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        l = l_ref[...]
        # A row with no valid key (kv_len 0) yields zeros, as the dense
        # path's NaN scrub does.
        out = jnp.where(l > 0, acc_ref[...] / jnp.where(l > 0, l, 1.0), 0.0)
        out = out[0] if g == 1 else jnp.swapaxes(out, 0, 1).reshape(kv * g, dh)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("softmax_scale", "block_s", "interpret")
)
def decode_attention_pallas(
    q: jnp.ndarray,        # (B, H, dh)
    k_new: jnp.ndarray,    # (B, KV, dh)
    v_new: jnp.ndarray,    # (B, KV, dh)
    k_cache: jnp.ndarray,  # (B, S, KV, dh)
    v_cache: jnp.ndarray,  # (B, S, KV, dh)
    pos: jnp.ndarray,      # (B,) int32
    kv_len: jnp.ndarray,   # (B,) int32
    *,
    softmax_scale: float | None = None,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, dh = q.shape
    s, kv = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    scale = softmax_scale or dh**-0.5
    block_s = min(block_s, s)
    if s % block_s:
        raise ValueError(f"cache length {s} not a multiple of block_s={block_s}")

    def page(bb, j, pos_ref, len_ref):
        # Past the row's last valid block, repeat that block's index:
        # an unchanged block index is not fetched again.
        last = jnp.maximum(len_ref[bb] - 1, 0) // block_s
        return (bb, jnp.minimum(j, last), 0, 0)

    row_spec = lambda shape: pl.BlockSpec(shape, lambda bb, j, p_, l_: (bb, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, s // block_s),
        in_specs=[
            row_spec((1, h, dh)),
            row_spec((1, kv, dh)),
            row_spec((1, kv, dh)),
            pl.BlockSpec((1, block_s, kv, dh), page),
            pl.BlockSpec((1, block_s, kv, dh), page),
        ],
        out_specs=row_spec((1, h, dh)),
        scratch_shapes=[
            pltpu.VMEM((g, kv, 1), jnp.float32),
            pltpu.VMEM((g, kv, 1), jnp.float32),
            pltpu.VMEM((g, kv, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_attention_kernel, scale=scale, block_s=block_s),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (b, h, dh), q.dtype, vma=jax.typeof(q).vma
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(
        pos.astype(jnp.int32), kv_len.astype(jnp.int32),
        q, k_new, v_new, k_cache, v_cache,
    )
