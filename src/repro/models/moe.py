"""Mixture-of-Experts MLP with sort-based dispatch (expert parallel).

Dispatch is one-hot-free: assignments are ranked within their expert via a
single argsort (MegaBlocks-style grouping), scattered into a capacity-
bounded (E, C, d) buffer, processed with batched expert GEMMs, and
combined with a scatter-add.  Experts shard over the ``model`` mesh axis
(EP folded onto TP); token activations stay sharded over ``data``, so
GSPMD inserts the dispatch/combine exchanges.

The MoE dispatch chain (route → exchange → expert GEMM → combine) is
itself a stream of dependent cells; under the pipeline evaluator the
exchange of chunk b overlaps the GEMM of chunk b-1 (see
EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, MoEConfig
from repro.models.params import ParamSpec


def moe_layout(cfg: ArchConfig, moe: MoEConfig, stacked: tuple[int, ...] = ()):
    d, e, f = cfg.d_model, moe.num_experts, moe.d_ff_expert
    ax = ("layers",) * len(stacked)
    out = {
        "router": ParamSpec(
            stacked + (d, e), ax + ("embed", None), dtype=jnp.float32
        ),
        "w_gate": ParamSpec(
            stacked + (e, d, f), ax + ("experts", "mlp_in", None), dtype=cfg.dtype
        ),
        "w_up": ParamSpec(
            stacked + (e, d, f), ax + ("experts", "mlp_in", None), dtype=cfg.dtype
        ),
        "w_down": ParamSpec(
            stacked + (e, f, d), ax + ("experts", None, "mlp_in"), dtype=cfg.dtype
        ),
    }
    if moe.num_shared_experts:
        fs = f * moe.num_shared_experts
        out["shared"] = {
            "w_gate": ParamSpec(stacked + (d, fs), ax + ("embed", "ffn"), dtype=cfg.dtype),
            "w_up": ParamSpec(stacked + (d, fs), ax + ("embed", "ffn"), dtype=cfg.dtype),
            "w_down": ParamSpec(stacked + (fs, d), ax + ("ffn", "embed"), dtype=cfg.dtype),
        }
    return out


def _data_shards(t: int) -> int:
    """Number of batch shards the dispatch is blocked by.

    The dispatch scatter/gather is *blocked per data shard* (leading vmap
    dim sharded over (pod, data)) so every scatter stays shard-local —
    GSPMD partitions a batched scatter along its batch dim for free,
    whereas a flat cross-shard scatter triggers pathological resharding
    (observed: moonshot train_4k failed HLO verification at 256 chips).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return 1
    shards = 1
    for ax in ("pod", "data"):
        if ax in mesh.axis_names:
            shards *= mesh.shape[ax]
    while shards > 1 and t % shards != 0:
        shards //= 2
    return max(shards, 1)


def moe_apply(params, x, moe: MoEConfig, *, capacity: int | None = None):
    """x: (B, S, d) -> (y, aux).  Token-drop routing with capacity bound."""
    b, s, d = x.shape
    t = b * s
    e, k = moe.num_experts, moe.top_k
    xf = x.reshape(t, d)

    # --- route (fp32) -----------------------------------------------------
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = lax.top_k(probs, k)  # (t, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    # --- aux losses ---------------------------------------------------------
    # load-balance (Switch): E * sum_e fraction_e * prob_e
    assign_onehot_mean = jnp.zeros((e,), jnp.float32).at[expert_ids.reshape(-1)].add(
        1.0 / (t * k)
    )
    prob_mean = jnp.mean(probs, axis=0)
    lb_loss = e * jnp.sum(assign_onehot_mean * prob_mean)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    # --- dispatch: per-data-shard blocked, sort-based ranking ---------------
    ds = _data_shards(t)
    tl = t // ds  # tokens per shard block
    if capacity is None:
        capacity = int(np.ceil(tl * k / e * moe.capacity_factor))
        capacity = max(8, -(-capacity // 8) * 8)

    from repro.parallel.sharding import maybe_constrain

    def dispatch_block(xb, eids, gates):
        """xb: (tl, d); eids: (tl, k); gates: (tl, k) -> (y (tl,d))."""
        flat_e = eids.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(tl), k)
        flat_gate = gates.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        starts = jnp.searchsorted(sorted_e, jnp.arange(e))
        rank_sorted = jnp.arange(tl * k) - starts[sorted_e]
        rank = (
            jnp.zeros((tl * k,), jnp.int32)
            .at[order]
            .set(rank_sorted.astype(jnp.int32))
        )
        keep = rank < capacity
        dest = jnp.where(keep, flat_e * capacity + rank, e * capacity)
        buf = jnp.zeros((e * capacity, d), x.dtype)
        buf = buf.at[dest].set(xb[flat_tok], mode="drop")
        return buf.reshape(e, capacity, d), (dest, flat_tok, flat_gate, keep)

    xb = xf.reshape(ds, tl, d)
    eb = expert_ids.reshape(ds, tl, k)
    gb = gate_vals.reshape(ds, tl, k)
    buf, meta = jax.vmap(dispatch_block)(xb, eb, gb)
    # buf: (DS, E, C, d) — batch shards over (pod,data), experts over model.
    buf = maybe_constrain(buf, P(("pod", "data"), "model", None, None))

    # --- expert GEMMs (SwiGLU), expert-parallel over `model` -----------------
    gate = jnp.einsum("xecd,edf->xecf", buf, params["w_gate"])
    up = jnp.einsum("xecd,edf->xecf", buf, params["w_up"])
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    out_buf = jnp.einsum("xecf,efd->xecd", act, params["w_down"])
    out_buf = maybe_constrain(
        out_buf, P(("pod", "data"), "model", None, None)
    )

    # --- combine (shard-local gather + scatter-add) ---------------------------
    def combine_block(ob, meta):
        dest, flat_tok, flat_gate, keep = meta
        flat = ob.reshape(e * capacity, d)
        contrib = flat[jnp.minimum(dest, e * capacity - 1)]
        contrib = jnp.where(keep[:, None], contrib, 0) * flat_gate[
            :, None
        ].astype(x.dtype)
        return jnp.zeros((tl, d), x.dtype).at[flat_tok].add(contrib)

    y = jax.vmap(combine_block)(out_buf, meta).reshape(t, d)

    # --- shared experts --------------------------------------------------------
    if "shared" in params:
        sh = params["shared"]
        g = jnp.einsum("td,df->tf", xf, sh["w_gate"])
        u = jnp.einsum("td,df->tf", xf, sh["w_up"])
        y = y + jnp.einsum(
            "tf,fd->td",
            jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u,
            sh["w_down"],
        )

    aux = {
        "moe_lb_loss": lb_loss,
        "moe_z_loss": z_loss,
        "moe_drop_fraction": 1.0 - jnp.mean(meta[3].astype(jnp.float32)),
    }
    return y.reshape(b, s, d), aux
