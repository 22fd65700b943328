"""Logical-axis sharding rules (GSPMD/pjit layer).

Weights are 2-D sharded (FSDP over ``data`` × TP over ``model``) — ZeRO-3
style: optimizer state and gradients inherit the same sharding, which is
what lets the 398 B/400 B configs fit 16 GB/chip on the 256-chip pod.

Rule sets are plain dicts ``logical axis -> mesh axis (or tuple or None)``;
per-shape overrides (e.g. decode shards the KV-cache sequence dim over
``model``; long-context batch=1 shards it over ``data`` too) are expressed
as dict updates, not code.
"""
from __future__ import annotations

from typing import Any, Mapping

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import params as P_

PyTree = Any

# Base rules: training / prefill on the production mesh.
TRAIN_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "embed": "data",       # FSDP shard of the d_model dim of weights
    "mlp_in": "data",      # FSDP shard of non-model dims
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "ffn": "model",
    "vocab": "model",
    # Untied input-embedding table: FSDP the rows over `data`; the input
    # gather then costs one transient table replication (SPMD last-resort
    # replicate-then-gather — compiles everywhere; an embed-dim-sharded
    # table instead trips the CPU partitioner on the gather+reshard).
    # Baseline inefficiency, attacked in §Perf.
    "vocab_table": "data",
    "embed_table": None,
    "experts": "model",    # expert parallelism folded onto the TP axis
    "layers": None,
    "stage": "pod",        # pipeline stages (stream-future mode)
    "seq": None,
    "act_seq": "model",    # sequence-parallel activations between blocks
    "kv_seq": None,
    "conv": None,
    "state": None,
    "groups": None,
}

# Decode: KV cache sequence dim sharded over the TP axis (flash-decoding
# style split-K combine is left to GSPMD's partial softmax reductions).
# kv_heads must then stay unsharded — one mesh axis per spec position.
DECODE_RULES = dict(TRAIN_RULES, kv_seq="model", kv_heads=None, act_seq=None)

# Prefill: cache written across the whole sequence; shard it like decode.
PREFILL_RULES = dict(TRAIN_RULES, kv_seq="model", kv_heads=None)

# Long-context decode with global_batch=1: batch axes would idle, so the
# KV/state sequence shards over every axis (512k / 512 = 1k per chip).
LONG_DECODE_RULES = dict(
    DECODE_RULES, batch=None, kv_seq=("pod", "data", "model")
)


def spec_for(logical_axes: tuple[str | None, ...], rules: Mapping[str, Any]) -> P:
    parts = []
    for ax in logical_axes:
        if ax is None:
            parts.append(None)
        else:
            if ax not in rules:
                raise KeyError(f"no sharding rule for logical axis {ax!r}")
            parts.append(rules[ax])
    # Drop trailing Nones for tidiness.
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def prune_spec(spec: P, mesh: Mesh) -> P:
    """Remove mesh axes that don't exist in ``mesh`` (single-pod has no 'pod')."""
    parts = []
    for part in spec:
        if part is None:
            parts.append(None)
        elif isinstance(part, tuple):
            kept = tuple(a for a in part if a in mesh.axis_names)
            # normalize singleton tuples so equal specs compare equal
            parts.append(
                None if not kept else (kept[0] if len(kept) == 1 else kept)
            )
        else:
            parts.append(part if part in mesh.axis_names else None)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def fit_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Make a spec legal for ``shape`` on ``mesh``.

    * drops mesh axes whose product does not evenly divide the dim
      (e.g. 20 q-heads or a 50280-row tied vocab on model=16 — the dim
      stays replicated; a recorded inefficiency, see DESIGN §5), and
    * de-duplicates mesh axes across positions (first occurrence wins).
    """
    spec = prune_spec(spec, mesh)
    used: set[str] = set()
    parts = []
    for d, part in enumerate(list(spec) + [None] * (len(shape) - len(spec))):
        axes = () if part is None else (part if isinstance(part, tuple) else (part,))
        axes = tuple(a for a in axes if a not in used)
        # drop axes from the right until the product divides the dim
        while axes and shape[d] % int(
            np.prod([mesh.shape[a] for a in axes])
        ) != 0:
            axes = axes[:-1]
        used.update(axes)
        if not axes:
            parts.append(None)
        elif len(axes) == 1:
            parts.append(axes[0])
        else:
            parts.append(axes)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def param_pspecs(layout: PyTree, rules: Mapping[str, Any], mesh: Mesh) -> PyTree:
    return jax.tree.map(
        lambda s: fit_spec(spec_for(s.logical_axes, rules), s.shape, mesh),
        layout,
        is_leaf=P_.is_spec,
    )


def param_shardings(layout: PyTree, rules: Mapping[str, Any], mesh: Mesh) -> PyTree:
    return jax.tree.map(
        lambda s: NamedSharding(
            mesh, fit_spec(spec_for(s.logical_axes, rules), s.shape, mesh)
        ),
        layout,
        is_leaf=P_.is_spec,
    )


def maybe_constrain(x, spec: P):
    """with_sharding_constraint, a no-op when no mesh is in context.

    Lets model code carry sharding annotations that activate under the
    production mesh but stay inert in single-device smoke tests.  Inside a
    partial-manual shard_map region (the stream-future pipeline), manual
    axes are already local and must be dropped from the spec.
    """
    import os
    if os.environ.get("REPRO_NO_CONSTRAIN") == "1":
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    manual = frozenset(mesh.manual_axes)
    if manual:
        parts = []
        for part in spec:
            axes = () if part is None else (
                part if isinstance(part, tuple) else (part,)
            )
            axes = tuple(a for a in axes if a not in manual)
            parts.append(
                None if not axes else (axes[0] if len(axes) == 1 else axes)
            )
        spec = P(*parts)
    return jax.lax.with_sharding_constraint(x, prune_spec(spec, mesh))


def shard_activation(x, logical_axes, rules, mesh=None):
    """with_sharding_constraint by logical axes (no-op outside jit/mesh)."""
    spec = spec_for(logical_axes, rules)
    if mesh is not None:
        spec = prune_spec(spec, mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return maybe_constrain(x, spec)
