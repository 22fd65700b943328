"""Composable decoder: dense / MoE / hybrid (Mamba interleave) / VLM cross-
attention / audio backbone — one implementation, driven by ArchConfig.

Layers are grouped into a repeating *period* = lcm(block pattern, MoE
interval, cross-attn interval); parameters are stacked over
``num_layers / period`` groups and the stack is scanned — compile time is
O(period), not O(num_layers), which is what makes the 100-layer configs
lowerable.

Step kinds:
  * ``forward``      — logits for full sequences (train / smoke).
  * ``prefill``      — forward + materialized KV/SSD caches + last logits.
  * ``decode_step``  — one token per sequence against preallocated caches.
  * decode *cells*   — the same decode math split into ``num_cells``
    contiguous layer-group pipeline cells (``split_decode_cells`` /
    ``make_decode_cell`` / ``make_decode_emit``): layer params ride the
    Stream's read-only ``const_state``, each cell's KV/SSD cache shard
    is its mutable state (updated by row-level scatters only); the
    serving engine runs them under ``Stream.feedback`` so the sampled
    token re-enters as the next item.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ArchConfig
from repro.kernels import get_impl, resolve_mode
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.params import ParamSpec

PyTree = Any


# ---------------------------------------------------------------------------
# Block plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    mixer: str  # "attn" | "mamba" | "cross_attn"
    ffn: str    # "dense" | "moe" | "none"


def effective_period(cfg: ArchConfig) -> int:
    period = cfg.pattern_period
    if cfg.moe is not None:
        period = math.lcm(period, cfg.moe.every_k_layers)
    if cfg.cross_attn_every > 0:
        period = math.lcm(period, cfg.cross_attn_every)
    return period


def block_plans(cfg: ArchConfig) -> list[BlockPlan]:
    period = effective_period(cfg)
    plans = []
    for i in range(period):
        mixer = cfg.block_pattern[i % cfg.pattern_period]
        if (
            cfg.cross_attn_every > 0
            and i % cfg.cross_attn_every == cfg.cross_attn_every - 1
        ):
            mixer = "cross_attn"
        if cfg.d_ff == 0 and cfg.moe is None:
            ffn = "none"
        elif cfg.moe is not None and (
            i % cfg.moe.every_k_layers == cfg.moe.every_k_layers - 1
        ):
            ffn = "moe"
        else:
            ffn = "dense"
        plans.append(BlockPlan(mixer, ffn))
    return plans


# ---------------------------------------------------------------------------
# Layout
# ---------------------------------------------------------------------------


def model_layout(cfg: ArchConfig) -> PyTree:
    period = effective_period(cfg)
    if cfg.num_layers % period != 0:
        raise ValueError(f"{cfg.num_layers=} not divisible by period {period}")
    groups = cfg.num_layers // period
    stacked = (groups,)
    plans = block_plans(cfg)

    blocks: dict[str, PyTree] = {}
    for i, plan in enumerate(plans):
        blk: dict[str, PyTree] = {}
        norm_layout, _ = L.make_norm(cfg.norm, cfg.d_model, stacked)
        blk["norm_mixer"] = norm_layout
        if plan.mixer in ("attn", "cross_attn"):
            blk["attn"] = L.attn_layout(cfg, stacked, cross=plan.mixer == "cross_attn")
            if plan.mixer == "cross_attn":
                blk["xattn_gate"] = {
                    "gate": ParamSpec(stacked + (1,), ("layers", None), init="zeros", dtype=jnp.float32)
                }
        else:
            blk["mamba"] = S.ssm_layout(cfg, cfg.ssm, stacked)
        if plan.ffn != "none":
            norm2, _ = L.make_norm(cfg.norm, cfg.d_model, stacked)
            blk["norm_ffn"] = norm2
            if plan.ffn == "moe":
                blk["moe"] = M.moe_layout(cfg, cfg.moe, stacked)
            else:
                blk["mlp"] = L.mlp_layout(cfg, stacked=stacked)
        blocks[f"block{i}"] = blk

    final_norm, _ = L.make_norm(cfg.norm, cfg.d_model, ())
    return {
        "embed": L.embed_layout(cfg),
        "blocks": blocks,
        "final_norm": final_norm,
        "head": L.head_layout(cfg),
    }


# ---------------------------------------------------------------------------
# Cache layout (decode)
# ---------------------------------------------------------------------------


def cache_layout(cfg: ArchConfig, batch: int, max_len: int) -> PyTree:
    """Abstract cache spec: dict mirroring blocks, leaves ShapeDtypeStruct.

    Attention: K/V (groups, B, Smax, KV, dh).  Mamba: conv + state.
    Cross-attention: precomputed vision K/V (groups, B, V, KV, dh).
    """
    groups = cfg.num_layers // effective_period(cfg)
    plans = block_plans(cfg)
    caches: dict[str, PyTree] = {}
    for i, plan in enumerate(plans):
        if plan.mixer == "attn":
            shape = (groups, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
            caches[f"block{i}"] = {
                "k": jax.ShapeDtypeStruct(shape, cfg.dtype),
                "v": jax.ShapeDtypeStruct(shape, cfg.dtype),
            }
        elif plan.mixer == "cross_attn":
            shape = (groups, batch, cfg.vision_tokens, cfg.num_kv_heads, cfg.head_dim)
            caches[f"block{i}"] = {
                "k": jax.ShapeDtypeStruct(shape, cfg.dtype),
                "v": jax.ShapeDtypeStruct(shape, cfg.dtype),
            }
        if plan.mixer == "mamba":
            d_inner, num_heads, conv_dim, _ = S.ssm_dims(cfg, cfg.ssm)
            caches[f"block{i}"] = {
                "conv": jax.ShapeDtypeStruct(
                    (groups, batch, cfg.ssm.conv_width - 1, conv_dim), cfg.dtype
                ),
                "state": jax.ShapeDtypeStruct(
                    (groups, batch, num_heads, cfg.ssm.state_dim, cfg.ssm.head_dim),
                    jnp.float32,
                ),
            }
    return caches


def cache_logical_axes(cfg: ArchConfig) -> PyTree:
    """Logical axes per cache leaf (for sharding rules)."""
    plans = block_plans(cfg)
    axes: dict[str, PyTree] = {}
    for i, plan in enumerate(plans):
        if plan.mixer == "attn":
            ax = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
            axes[f"block{i}"] = {"k": ax, "v": ax}
        elif plan.mixer == "cross_attn":
            ax = ("layers", "batch", None, "kv_heads", "head_dim")
            axes[f"block{i}"] = {"k": ax, "v": ax}
        if plan.mixer == "mamba":
            axes[f"block{i}"] = {
                "conv": ("layers", "batch", None, "ffn"),
                "state": ("layers", "batch", "heads", "state", None),
            }
    return axes


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> PyTree:
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache_layout(cfg, batch, max_len)
    )


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


# Block norms do NOT dispatch on the kernels knob: the XLA path's bf16
# numerics at a norm -> matmul boundary are fusion-dependent (the
# f32->bf16->f32 round-trip of the norm output is elided into some
# consumers, e.g. the SSM in-projection, but not others), so a
# materialized kernel output cannot be bitwise-stable against it.  The
# standalone rmsnorm kernel stays in the registry for callers that own
# their numerics end to end; the decode hot path gets its fusion wins
# from the decode-attention and emit-epilogue kernels, whose references
# are fusion-stable (all-f32 attention math / the emit's reshape-
# separated head matmul).
def _norm(cfg, params, x):
    if cfg.norm == "rmsnorm":
        return L.rmsnorm(params, x, cfg.norm_eps)
    return L.layernorm_nonparam(x, cfg.norm_eps)


def _self_attn(
    params, x, cfg, *, positions, cache=None, cache_pos=None, kv_len=None,
    attn_impl="dense", q_chunk=512, kv_chunk=1024, causal_skip=None,
    collect_rows=False, kernels="xla",
):
    """Self-attention; with cache: decode/chunked-prefill.

    Decode (S==1): ``cache_pos`` is (B,) per-sequence write positions.
    Chunked prefill (S>1): ``cache_pos`` is a scalar chunk offset; the
    chunk is written at [pos, pos+S) and attends causally to the cache.

    ``collect_rows`` (decode only): instead of the updated K/V slabs,
    return just the written rows ``{"k": (B, KV, dh), "v": ...}`` — the
    caller scatters them into its full cache buffer at row level, so no
    slab-sized value ever rides a scan ys or a carry write-back.
    Attention still reads the functionally-updated slab (its compute
    operand), so outputs are bitwise unchanged.

    ``kernels="pallas"`` (decode only): the fused scatter+read kernel
    replaces the functional slab update — the new K/V row is substituted
    into the cache pages inside the kernel (VMEM), so no updated slab is
    ever materialized in HBM.  It replicates the dense attention math
    bitwise, so it overrides ``attn_impl`` for the S==1 step.
    """
    q, k, v = L.attn_project_qkv(params, x, cfg, positions)
    new_cache = None
    if cache is not None:
        bsz, s = x.shape[:2]
        if s == 1 and kernels == "pallas":
            rows_k = k[:, 0].astype(cache["k"].dtype)
            rows_v = v[:, 0].astype(cache["v"].dtype)
            ctx = get_impl("decode_attention", "pallas")(
                q, rows_k, rows_v, cache["k"], cache["v"],
                pos=cache_pos, kv_len=kv_len,
            )
            if collect_rows:
                new_cache = {"k": rows_k, "v": rows_v}
            else:
                idx = jnp.arange(bsz)
                new_cache = {
                    "k": cache["k"].at[idx, cache_pos].set(rows_k),
                    "v": cache["v"].at[idx, cache_pos].set(rows_v),
                }
            return L.attn_out(params, ctx), new_cache, (k, v)
        if s == 1:
            idx = jnp.arange(bsz)
            ck = cache["k"].at[idx, cache_pos].set(k[:, 0])
            cv = cache["v"].at[idx, cache_pos].set(v[:, 0])
            causal, q_offset = False, 0
        else:  # chunked prefill: scalar offset
            zero = jnp.zeros((), cache_pos.dtype if hasattr(cache_pos, "dtype") else jnp.int32)
            start = (zero, cache_pos, zero, zero)
            ck = lax.dynamic_update_slice(cache["k"], k, start)
            cv = lax.dynamic_update_slice(cache["v"], v, start)
            causal, q_offset = True, cache_pos
        if collect_rows:
            if s != 1:
                raise ValueError("collect_rows is a decode-path (S==1) mode")
            new_cache = {
                "k": k[:, 0].astype(cache["k"].dtype),
                "v": v[:, 0].astype(cache["v"].dtype),
            }
        else:
            new_cache = {"k": ck, "v": cv}
        ctx = L.attention(
            q, ck, cv, impl=attn_impl, causal=causal, q_offset=q_offset,
            kv_len=kv_len, q_chunk=q_chunk, kv_chunk=kv_chunk,
            causal_skip=causal_skip,
        )
    else:
        ctx = L.attention(
            q, k, v, impl=attn_impl, causal=True,
            q_chunk=q_chunk, kv_chunk=kv_chunk, causal_skip=causal_skip,
        )
    return L.attn_out(params, ctx), new_cache, (k, v)


def _cross_attn(params, gate, x, cfg, *, vision_kv=None, vision_embeds=None,
                attn_impl="dense", q_chunk=512, kv_chunk=1024):
    """Cross-attention to vision tokens (gated, llama-3.2 style)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if "q_norm" in params:
        q = L.rmsnorm({"scale": params["q_norm"]}, q, cfg.norm_eps)
    if vision_kv is not None:
        k, v = vision_kv["k"], vision_kv["v"]
    else:
        k = jnp.einsum("bvd,dhk->bvhk", vision_embeds, params["wk"])
        v = jnp.einsum("bvd,dhk->bvhk", vision_embeds, params["wv"])
        if "k_norm" in params:
            k = L.rmsnorm({"scale": params["k_norm"]}, k, cfg.norm_eps)
    ctx = L.attention(
        q, k, v, impl=attn_impl, causal=False, q_chunk=q_chunk, kv_chunk=kv_chunk
    )
    out = L.attn_out(params, ctx)
    return jnp.tanh(gate["gate"]).astype(out.dtype) * out, {"k": k, "v": v}


def _apply_group(
    group_params,
    x,
    cfg,
    plans,
    *,
    positions,
    group_cache=None,
    cache_pos=None,
    kv_len=None,
    vision_embeds=None,
    collect_kv=False,
    attn_impl="dense",
    q_chunk=512,
    kv_chunk=1024,
    causal_skip=None,
    cache_rows=False,
    kernels="xla",
):
    """Apply one period group.  Returns (x, new_group_cache, aux_losses).

    ``cache_rows`` (decode only): attention blocks return just the K/V
    rows written this step (see ``_self_attn(collect_rows=True)``) and
    cross-attention blocks return nothing (their vision K/V never
    changes during decode); SSM blocks return their per-sequence state
    as usual — it is row-sized already.  The caller owns the row-level
    scatter into its full cache.
    """
    new_cache: dict[str, PyTree] = {}
    aux = {"moe_lb_loss": 0.0, "moe_z_loss": 0.0, "moe_drop_fraction": 0.0}
    num_moe = 0
    for i, plan in enumerate(plans):
        blk = group_params[f"block{i}"]
        h = _norm(cfg, blk.get("norm_mixer"), x)
        if plan.mixer == "attn":
            cache_i = None if group_cache is None else group_cache.get(f"block{i}")
            out, c_new, kv = _self_attn(
                blk["attn"], h, cfg,
                positions=positions, cache=cache_i, cache_pos=cache_pos,
                kv_len=kv_len, attn_impl=attn_impl,
                q_chunk=q_chunk, kv_chunk=kv_chunk, causal_skip=causal_skip,
                collect_rows=cache_rows, kernels=kernels,
            )
            if c_new is not None:
                new_cache[f"block{i}"] = c_new
            elif collect_kv:
                new_cache[f"block{i}"] = {"k": kv[0], "v": kv[1]}
        elif plan.mixer == "cross_attn":
            # Fresh vision embeds (prefill) take priority over cached K/V.
            vkv = None
            if vision_embeds is None and group_cache is not None:
                vkv = group_cache.get(f"block{i}")
            out, vkv_new = _cross_attn(
                blk["attn"], blk["xattn_gate"], h, cfg,
                vision_kv=vkv, vision_embeds=vision_embeds,
                attn_impl=attn_impl, q_chunk=q_chunk, kv_chunk=kv_chunk,
            )
            if (collect_kv or group_cache is not None) and not cache_rows:
                new_cache[f"block{i}"] = vkv_new
        else:  # mamba
            cache_i = None if group_cache is None else group_cache.get(f"block{i}")
            out, c_new = S.ssm_block(blk["mamba"], h, cfg, cfg.ssm, cache=cache_i)
            if group_cache is not None or collect_kv:
                new_cache[f"block{i}"] = c_new
        x = L.constrain_res(x + out)

        if plan.ffn != "none":
            h = _norm(cfg, blk.get("norm_ffn"), x)
            if plan.ffn == "moe":
                out, moe_aux = M.moe_apply(blk["moe"], h, cfg.moe)
                for key in ("moe_lb_loss", "moe_z_loss", "moe_drop_fraction"):
                    aux[key] = aux[key] + moe_aux[key]
                num_moe += 1
            else:
                out = L.mlp(blk["mlp"], h)
            x = L.constrain_res(x + out)
    if num_moe:
        aux = {k: v / num_moe for k, v in aux.items()}
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Model entry points
# ---------------------------------------------------------------------------


def _embed_input(params, cfg, tokens=None, embeds=None):
    if cfg.embeds_input:
        assert embeds is not None, "stubbed-frontend arch takes embeddings"
        return embeds.astype(cfg.dtype)
    return L.embed_lookup(params["embed"]["embedding"], tokens)


def forward(
    params,
    cfg: ArchConfig,
    *,
    tokens=None,
    embeds=None,
    vision_embeds=None,
    collect_kv=False,
    cache_pad_to=None,
    attn_impl="dense",
    q_chunk=512,
    kv_chunk=1024,
    causal_skip=None,
    remat=True,
    unroll=1,
):
    """Full-sequence forward.  Returns (logits, caches|None, aux)."""
    plans = block_plans(cfg)
    x = _embed_input(params, cfg, tokens, embeds)
    bsz, s, _ = x.shape
    positions = jnp.arange(s)[None, :]

    def group_fn(x, group_params):
        x, kv, aux = _apply_group(
            group_params, x, cfg, plans,
            positions=positions, vision_embeds=vision_embeds,
            collect_kv=collect_kv, attn_impl=attn_impl,
            q_chunk=q_chunk, kv_chunk=kv_chunk, causal_skip=causal_skip,
        )
        return x, (kv, aux)

    if remat:
        group_fn = jax.checkpoint(group_fn)
    x, (kvs, auxs) = lax.scan(group_fn, x, params["blocks"], unroll=unroll)
    aux = jax.tree.map(lambda a: jnp.mean(a), auxs)
    x = _norm(cfg, params.get("final_norm"), x)
    lg = L.logits(params.get("head"), params["embed"], x, cfg)

    caches = None
    if collect_kv:
        caches = kvs
        if cache_pad_to is not None:
            caches = jax.tree.map(
                partial(_pad_cache_seq, plans=plans, pad_to=cache_pad_to),
                caches,
            )
    return lg, caches, aux


def _pad_cache_seq(x, *, plans, pad_to):
    # pads K/V (groups,B,S,KV,dh) to (groups,B,pad_to,KV,dh); leaves others
    if x.ndim == 5 and x.shape[2] < pad_to:
        pad = [(0, 0)] * 5
        pad[2] = (0, pad_to - x.shape[2])
        return jnp.pad(x, pad)
    return x


def _emit_logits(params, cfg: ArchConfig, x, kernels: str = "xla"):
    """Final-norm -> logits for one decode position: (B, 1, d) -> (B, V).

    Under ``kernels="pallas"`` the two ops run as one fused epilogue
    (norm recomputed per vocab tile in VMEM — see
    repro.kernels.emit_norm_logits); in bf16 bitwise equal to the XLA
    path under the interpreter.
    """
    if kernels == "pallas":
        w = (
            params["embed"]["embedding"]
            if cfg.tie_embeddings
            else params["head"]["w"]
        )
        fn = params.get("final_norm")
        return get_impl("emit_norm_logits", "pallas")(
            x, w, norm=cfg.norm,
            scale=fn["scale"] if cfg.norm == "rmsnorm" else None,
            eps=cfg.norm_eps, tied=cfg.tie_embeddings,
        )
    xn = _norm(cfg, params.get("final_norm"), x)
    return L.logits(params.get("head"), params["embed"], xn, cfg)[:, 0, :]


def decode_step(
    params,
    caches,
    cfg: ArchConfig,
    *,
    tokens=None,
    embeds=None,
    lengths=None,
    attn_impl="dense",
    kv_chunk=1024,
    unroll=1,
    kernels=None,
):
    """One-token step.  tokens: (B,) int32 (or embeds (B,1,d)); lengths:
    (B,) current context length per sequence (cache write position).
    Returns (logits (B,V), new_caches).

    ``kernels`` (None inherits ``cfg.kernels``) selects the per-op
    implementations (see repro.kernels): ``"pallas"`` runs the fused
    decode-attention and emit-epilogue kernels (equal to the XLA path
    to fp32 rounding; interpret-emulated off-TPU)."""
    plans = block_plans(cfg)
    mode = resolve_mode(cfg.kernels if kernels is None else kernels)
    if cfg.embeds_input:
        x = embeds.astype(cfg.dtype)
        bsz = x.shape[0]
    else:
        x = L.embed_lookup(params["embed"]["embedding"], tokens)[:, None, :]
        bsz = tokens.shape[0]
    if lengths is None:
        lengths = jnp.zeros((bsz,), jnp.int32)
    positions = lengths[:, None]
    kv_len = (lengths + 1)[:, None]  # (B,1) valid kv after the write

    def group_fn(x, scan_in):
        group_params, group_cache = scan_in
        x, new_cache, aux = _apply_group(
            group_params, x, cfg, plans,
            positions=positions, group_cache=group_cache,
            cache_pos=lengths, kv_len=kv_len,
            attn_impl=attn_impl, kv_chunk=kv_chunk, q_chunk=1,
            kernels=mode,
        )
        return x, new_cache

    x, new_caches = lax.scan(group_fn, x, (params["blocks"], caches), unroll=unroll)
    return _emit_logits(params, cfg, x, mode), new_caches


def _cache_seq_len(caches):
    for blk in caches.values():
        if "k" in blk:
            return blk["k"].shape[2]
    return None


def prefill_step(
    params,
    caches,
    cfg: ArchConfig,
    *,
    tokens=None,
    embeds=None,
    pos=0,
    vision_embeds=None,
    attn_impl="chunked",
    q_chunk=512,
    kv_chunk=1024,
    unroll=1,
    logits_at: int | None = None,
    kernels=None,
):
    """Chunked streaming prefill: process a prompt chunk at offset ``pos``.

    The chunk sequence is a bounded stream whose carried value is the
    KV/SSD cache (the paper's construct on the sequence axis): chunk c's
    attention forces the cache future produced by chunk c-1.
    tokens: (B, C).  Returns (logits (B,V), new caches) — logits at the
    chunk's last position, or at index ``logits_at`` when given (static
    int or traced scalar; a ragged prompt tail padded to one masked
    chunk reads its logits at the last *real* position, and a traced
    index lets every tail length share one compiled prefill.  Pad
    queries only pollute pad rows, which the next decode's write
    position and kv_len mask retire).

    ``kernels`` (None inherits ``cfg.kernels``) is validated but prefill
    currently runs XLA in every mode: the chunk path's offset/ragged
    masking has no bitwise-stable tiled kernel, and prefill runs once
    per request, not per tick — the fused kernels target the decode
    loop (see ``decode_step`` / ``make_decode_cell``).
    """
    plans = block_plans(cfg)
    resolve_mode(cfg.kernels if kernels is None else kernels)
    if cfg.embeds_input:
        x = embeds.astype(cfg.dtype)
    else:
        x = L.embed_lookup(params["embed"]["embedding"], tokens)
    bsz, s, _ = x.shape
    static_pos = isinstance(pos, int)
    if not static_pos:
        pos = jnp.asarray(pos, jnp.int32)
    positions = (pos + jnp.arange(s))[None, :]
    # Whole-cache prefill (pos 0, chunk covers the buffer): no padding to
    # mask and a static zero offset — unlocks causal block skipping.
    full_cover = static_pos and pos == 0 and _cache_seq_len(caches) == s
    kv_len = None if full_cover else pos + s

    def group_fn(x, scan_in):
        group_params, group_cache = scan_in
        x, new_cache, _ = _apply_group(
            group_params, x, cfg, plans,
            positions=positions, group_cache=group_cache,
            cache_pos=pos, kv_len=kv_len, vision_embeds=vision_embeds,
            attn_impl=attn_impl, q_chunk=q_chunk, kv_chunk=kv_chunk,
        )
        return x, new_cache

    x, new_caches = lax.scan(group_fn, x, (params["blocks"], caches), unroll=unroll)
    x = _norm(cfg, params.get("final_norm"), x)
    if logits_at is None:
        xs_last = x[:, s - 1 : s, :]
    elif isinstance(logits_at, int):
        xs_last = x[:, logits_at : logits_at + 1, :]
    else:  # traced index: one compile serves every ragged-tail length
        xs_last = lax.dynamic_slice_in_dim(x, logits_at, 1, axis=1)
    lg = L.logits(params.get("head"), params["embed"], xs_last, cfg)
    return lg[:, 0, :], new_caches


# ---------------------------------------------------------------------------
# Decode as Stream cells (pipelined serving)
# ---------------------------------------------------------------------------
#
# The decode loop *is* a stream: cells = contiguous layer groups (each
# owning its params and its KV/SSD cache shard as mutable per-cell
# state), items = in-flight request microbatches.  The flowing item is a
# fixed-structure dict
#
#     {"x":   (Bm, 1, d)  hidden state (embed(tok) on entry),
#      "tok": (Bm,)       the token being decoded,
#      "pos": (Bm,)       per-slot context length (cache write position),
#      "active", "uid", "ngen", "budget": (Bm,) per-slot bookkeeping,
#      "mb":  ()          which microbatch of the batch this item is,
#      "step": ()         round-local decode step}
#
# and `make_decode_emit` closes the loop: final-norm -> logits -> sample
# -> re-embed, so the emitted item is exactly the next step's input — the
# shape `Stream.feedback` runs.  Inactive slots keep decoding with frozen
# pos/tok (identical to the sequential engine, which batches them too);
# their cache writes land at the frozen position < max_len and are
# overwritten at the next admission.
#
# Hot-path discipline (the const-state / row-scatter contract):
#   * layer params and the admission payload ride the Stream's
#     `const_state` — scan xs only, stage-sharded, never written back;
#   * the KV/SSD cache is the only mutable per-cell state, and a steady
#     decode tick touches it with row-level scatters only: attention
#     writes the one new (B, KV, dh) row per layer at its per-sequence
#     position, SSM blocks write their (row-sized) per-sequence state —
#     no microbatch slab is ever sliced out, carried through a scan ys,
#     or written back whole.


def _split_cells(tree, num_cells: int):
    def _split(leaf):
        groups = leaf.shape[0]
        if groups % num_cells != 0:
            raise ValueError(
                f"{groups} layer groups not divisible by num_cells={num_cells}"
            )
        return leaf.reshape((num_cells, groups // num_cells) + leaf.shape[1:])

    return jax.tree.map(_split, tree)


def split_decode_cells(params, caches, num_cells: int):
    """Slice params and caches into ``num_cells`` contiguous layer-group
    cells.  Leaves (groups, ...) become (num_cells, groups/num_cells,
    ...).

    Returns ``(const_state, state)`` — the Stream contract's read-only /
    mutable split:

    * ``const_state = {"blocks": ...}`` — each cell's layer-group params,
      threaded via ``Stream.through(..., const_state=...)``: delivered as
      scan xs (and stage-sharded by the Future engine, so weights are
      neither replicated per device nor gathered per tick), never
      written back.  The engine merges the per-round admission payload
      in as ``const_state["adm"]`` — it is read-only within a round too.
    * ``state = {"cache": ...}`` — the per-cell KV/SSD cache shard, the
      only thing the cells mutate.
    """
    return (
        {"blocks": _split_cells(params["blocks"], num_cells)},
        {"cache": _split_cells(caches, num_cells)},
    )


def merge_decode_caches(cell_states) -> PyTree:
    """Inverse of :func:`split_decode_cells` for the cache half."""
    return jax.tree.map(
        lambda l: l.reshape((-1,) + l.shape[2:]), cell_states["cache"]
    )


def stack_admission_payload(singles, slots, steps, mbs, num_cells: int):
    """Pack host-prefilled single-request caches into per-cell admission
    state.

    ``singles``: list of A caches from ``init_cache(cfg, 1, max_len)``
    after prefill (leaves (groups, 1, ...)).  Returns a pytree with
    leading axis ``num_cells`` holding, per cell, the slice of every
    admission's cache this cell owns plus the (slot, step, microbatch)
    the plan installs it at.  ``step == -1`` rows never fire (padding).
    """
    a_ = len(singles)

    def _cellify(*leaves):
        stacked = jnp.stack([l[:, 0] for l in leaves])  # (A, groups, ...)
        g = stacked.shape[1]
        per = g // num_cells
        stacked = stacked.reshape(
            (a_, num_cells, per) + stacked.shape[2:]
        )
        return jnp.swapaxes(stacked, 0, 1)  # (num_cells, A, gpc, ...)

    cache = jax.tree.map(lambda *ls: _cellify(*ls), *singles) if a_ else None
    meta = {
        "slot": jnp.broadcast_to(jnp.asarray(slots, jnp.int32), (num_cells, a_)),
        "step": jnp.broadcast_to(jnp.asarray(steps, jnp.int32), (num_cells, a_)),
        "mb": jnp.broadcast_to(jnp.asarray(mbs, jnp.int32), (num_cells, a_)),
    }
    return {"cache": cache, **meta} if a_ else meta


def scatter_decode_rows(cache, rows, plans, *, mb0, batch_idx, pos):
    """Row-level scatter of one decode step's cache writes.

    ``cache`` is a cell's full-batch cache shard (leaves ``(gpc, B,
    ...)``); ``rows`` the per-group rows the step produced
    (``_apply_group(cache_rows=True)`` stacked over the cell's group
    scan).  Attention K/V rows land at ``[:, batch_idx, pos]`` — one
    ``(KV, dh)`` row per sequence, an in-place scatter on the tick
    carry; SSM conv/state rows (whole per-sequence states) land as one
    contiguous ``dynamic_update_slice`` on the batch axis at ``mb0``.
    Cross-attention vision K/V never changes during decode and is left
    untouched.  Bytes written per tick: the rows themselves — the
    max_len-sized slab never moves.
    """
    out = dict(cache)
    for i, plan in enumerate(plans):
        key = f"block{i}"
        if key not in rows or key not in cache:
            continue
        if plan.mixer == "attn":
            out[key] = {
                "k": cache[key]["k"].at[:, batch_idx, pos].set(rows[key]["k"]),
                "v": cache[key]["v"].at[:, batch_idx, pos].set(rows[key]["v"]),
            }
        elif plan.mixer == "mamba":
            out[key] = jax.tree.map(
                lambda full, mb: lax.dynamic_update_slice_in_dim(
                    full, mb.astype(full.dtype), mb0, axis=1
                ),
                cache[key],
                rows[key],
            )
    return out


def make_decode_cell(
    cfg: ArchConfig,
    *,
    num_cells: int,
    microbatch: int,
    attn_impl: str = "dense",
    kv_chunk: int = 1024,
    admissions: int = 0,
    kernels: str = "xla",
):
    """One pipeline cell of the decode stream.

    ``cell_fn(const, state, item) -> (state', item')`` — the canonical
    const-state cell: ``const`` holds this cell's layer-group params
    (``const["blocks"]``, delivered by the evaluator as scan xs — no
    per-tick gather, no per-device replication) and, with ``admissions >
    0``, the in-plan admission buffer ``const["adm"]``: freshly
    prefilled whole-slot cache columns installed the moment this cell
    first sees item ``(step, mb)`` — continuous batching executed by
    the plan, not by host Python between steps.  ``state`` holds only
    the cell's cache shard, and a steady tick touches it exclusively
    through :func:`scatter_decode_rows` — the microbatch slab is read
    (the attention operand) but never sliced out/written back.

    ``kernels="pallas"`` goes one step further: the fused
    decode-attention kernel substitutes each layer's new K/V row into
    the cache pages in VMEM, so the steady tick also stops
    materializing the functionally-updated slab that the XLA path
    builds as the attention operand — row scatters become the only
    slab-touching writes left in the tick.  Outputs stay bitwise equal.
    """
    plans = block_plans(cfg)
    mode = resolve_mode(kernels)

    def cell_fn(const, state, item):
        cache = state["cache"]
        if admissions:
            adm = const["adm"]
            gates = [
                (adm["step"][a] == item["step"]) & (adm["mb"][a] == item["mb"])
                for a in range(admissions)
            ]
            any_hit = gates[0]
            for g in gates[1:]:
                any_hit = any_hit | g

            def _install_all(cache_in):
                out = cache_in
                for a in range(admissions):
                    slot = jnp.clip(adm["slot"][a], 0, None)

                    def _install(cfull, crow, _g=gates[a], _s=slot, _a=a):
                        cur = lax.dynamic_slice_in_dim(cfull, _s, 1, axis=1)
                        new = jnp.where(_g, crow[_a][:, None], cur)
                        return lax.dynamic_update_slice_in_dim(
                            cfull, new, _s, axis=1
                        )

                    out = jax.tree.map(_install, out, adm["cache"])
                return out

            # Admission ticks are rare (<= admit_per_round per round per
            # cell); everything else skips the install entirely.
            cache = lax.cond(any_hit, _install_all, lambda c: c, cache)
        mb0 = item["mb"] * microbatch
        batch_idx = mb0 + jnp.arange(microbatch)
        # Pure read: the attention operand.  The write path is the
        # row-level scatter below — nothing slab-sized rides the group
        # scan's ys or the state write-back.
        cache_mb = jax.tree.map(
            lambda c: lax.dynamic_slice_in_dim(c, mb0, microbatch, axis=1),
            cache,
        )
        lengths = item["pos"]
        positions = lengths[:, None]
        kv_len = (lengths + 1)[:, None]

        def group_fn(x, scan_in):
            group_params, group_cache = scan_in
            x, step_rows, _ = _apply_group(
                group_params, x, cfg, plans,
                positions=positions, group_cache=group_cache,
                cache_pos=lengths, kv_len=kv_len,
                attn_impl=attn_impl, kv_chunk=kv_chunk, q_chunk=1,
                cache_rows=True, kernels=mode,
            )
            return x, step_rows

        x, rows = lax.scan(group_fn, item["x"], (const["blocks"], cache_mb))
        cache = scatter_decode_rows(
            cache, rows, plans, mb0=mb0, batch_idx=batch_idx, pos=lengths
        )
        return {**state, "cache": cache}, {**item, "x": x}

    return cell_fn


def make_decode_emit(
    params,
    cfg: ArchConfig,
    *,
    sample_fn,
    eos_id: int,
    max_len: int,
    kernels: str = "xla",
):
    """The feedback emit closing the decode loop: final-norm -> logits ->
    sample -> re-embed.  ``sample_fn(logits, uid, ngen) -> (Bm,) int32``
    (the engine supplies it with temperature/seed closed over, so host
    and device sampling share one code path).  Retirement mirrors the
    sequential engine exactly: a slot freezes (pos/tok/ngen stop) once
    it has generated its budget, hit EOS, or reached the ``max_len``
    cache boundary — frozen slots keep flowing (batched decode does not
    shrink) but never advance, so no cache row at index >= max_len is
    ever written.

    ``kernels="pallas"`` fuses the norm -> logits head into the
    emit-epilogue kernel (repro.kernels.emit_norm_logits); the engine's
    conditional guard around the emit column is untouched, so the head
    matmul still only runs where the plan emits.
    """
    mode = resolve_mode(kernels)

    def emit(item):
        lg = _emit_logits(params, cfg, item["x"], mode)
        sampled = sample_fn(lg, item["uid"], item["ngen"])
        act = item["active"]
        tok = jnp.where(act, sampled, item["tok"])
        pos = jnp.where(act, item["pos"] + 1, item["pos"])
        ngen = jnp.where(act, item["ngen"] + 1, item["ngen"])
        done = (ngen >= item["budget"]) | (tok == eos_id) | (pos + 1 >= max_len)
        return {
            **item,
            "x": L.embed_lookup(params["embed"]["embedding"], tok)[:, None, :],
            "tok": tok,
            "pos": pos,
            "ngen": ngen,
            "active": act & ~done,
            "step": item["step"] + 1,
        }

    return emit
