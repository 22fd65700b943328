import os
_SMALL = os.environ.get("PIPE_SMALL", "0") == "1"
if "dryrun" not in os.environ.get("_REPRO_DEVICES_SET", ""):
    count = "8" if _SMALL else "512"
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={count}"
    os.environ["_REPRO_DEVICES_SET"] = "dryrun"

"""Multi-pod STREAM-FUTURE mode: layer pipeline across the pod axis.

This is the paper's technique as the production cross-pod schedule
(DESIGN §4 mode (b)): stages = contiguous layer-group spans of a real
architecture, items = microbatches, tails = ppermute'd activations on the
inter-pod links; FSDP×TP sharding stays automatic *inside* each stage
(partial-manual shard_map).  jax.grad through the schedule yields the
backward pipeline (GPipe by autodiff), rematerialized per (cell, item).

The dry-run lowers + compiles the full train step of qwen3-32b at
train_4k on the 2×16×16 mesh with stages=2 over 'pod', and records the
same roofline artifacts as the baseline DP-over-pod mode for comparison.

    PYTHONPATH=src python -m repro.launch.pipeline_demo

"""
import json
import time
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES
from repro.configs.registry import get_config
from repro.core.pipeline import pipeline_apply
from repro.launch import specs as SP
from repro.launch.dryrun import ARTIFACT_DIR
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.models import layers as L
from repro.models.params import abstract_params
from repro.parallel import sharding as SH
from repro.roofline import analysis as RL
from repro.roofline import analytic as AN
from repro.roofline import hlo_parse as HP
from repro.train import optimizer as O
from repro.train.train_step import TrainConfig

NUM_MICRO = 8
ARCH = os.environ.get("PIPE_ARCH", "qwen3-32b")
ATTN = os.environ.get("PIPE_ATTN", "chunked")
SHAPE = "train_4k"
REMAT = os.environ.get("PIPE_REMAT", "1") == "1"
# Pipeline schedule knobs (see repro.core.schedules): gpipe (default),
# one_f_one_b, or interleaved with PIPE_INTERLEAVE groups per device.
# PIPE_STAGES is the number of *stage groups* of the model; it must be
# (pod axis size x PIPE_INTERLEAVE), so the interleaved demo over the
# 2-pod mesh is PIPE_SCHEDULE=interleaved PIPE_INTERLEAVE=2 PIPE_STAGES=4.
# PIPE_BACKWARD selects the backward execution: "autodiff" (jax.grad
# transposes the forward plan) or "planned" (the combined plan's B units
# run through the custom-VJP engine — true 1F1B, min(S, M) stash).
SCHEDULE = os.environ.get("PIPE_SCHEDULE", "gpipe")
INTERLEAVE = int(os.environ.get("PIPE_INTERLEAVE", "1"))
NUM_STAGES = int(os.environ.get("PIPE_STAGES", str(2 * INTERLEAVE)))
BACKWARD = os.environ.get("PIPE_BACKWARD", "autodiff")


def _train_config():
    return TrainConfig(
        num_microbatches=NUM_MICRO, remat=REMAT,
        pipeline_schedule=SCHEDULE, pipeline_interleave=INTERLEAVE,
        pipeline_backward=BACKWARD,
    )


def staged_blocks_abstract(cfg, rules, mesh):
    """Abstract block params reshaped (G, ...) -> (stages, G/S, ...) with the
    stage axis sharded over 'pod'."""
    layout = T.model_layout(cfg)
    a = abstract_params(layout)
    specs = SH.param_pspecs(layout, rules, mesh)

    def stage_leaf(struct, spec):
        groups = struct.shape[0]
        assert groups % NUM_STAGES == 0
        shape = (NUM_STAGES, groups // NUM_STAGES) + struct.shape[1:]
        pspec = jax.sharding.PartitionSpec("pod", *spec)
        pspec = SH.fit_spec(pspec, shape, mesh)
        return jax.ShapeDtypeStruct(
            shape, struct.dtype, sharding=jax.sharding.NamedSharding(mesh, pspec)
        )

    blocks = jax.tree.map(
        stage_leaf, a["blocks"], specs["blocks"],
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    rest = {}
    for key in ("embed", "final_norm", "head"):
        rest[key] = jax.tree.map(
            lambda s, sp: jax.ShapeDtypeStruct(
                s.shape, s.dtype,
                sharding=jax.sharding.NamedSharding(
                    mesh, SH.fit_spec(sp, s.shape, mesh)
                ),
            ),
            a[key], specs[key],
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )
    return {"blocks": blocks, **rest}


def make_pipelined_loss(cfg, mesh):
    plans = T.block_plans(cfg)
    pcfg = _train_config().pipeline_config(NUM_STAGES, axis_name="pod")

    def stage_fn(stage_params, x):
        positions = jnp.arange(x.shape[1])[None, :]

        def group_fn(x, group_params):
            x, _, _ = _group(group_params, x)
            return x, None

        def _group(group_params, x):
            return T._apply_group(
                group_params, x, cfg, plans, positions=positions,
                attn_impl=ATTN, q_chunk=512, kv_chunk=1024,
            )

        x, _ = jax.lax.scan(group_fn, x, stage_params)
        return x

    def loss_fn(params, batch):
        x = L.embed_lookup(params["embed"]["embedding"], batch["tokens"])
        x = pipeline_apply(stage_fn, params["blocks"], x, pcfg, mesh=mesh)
        x = T._norm(cfg, params.get("final_norm"), x)
        logits = L.logits(params["head"], params["embed"], x, cfg)
        lse = jax.nn.logsumexp(logits, axis=-1)
        vocab_iota = jnp.arange(logits.shape[-1], dtype=batch["labels"].dtype)
        gold = jnp.sum(
            jnp.where(vocab_iota == batch["labels"][..., None], logits, 0.0),
            axis=-1,
        )
        return jnp.mean(lse - gold)

    def train_step(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        # sgd-style apply keeps the demo focused on the pipeline schedule
        params = jax.tree.map(
            lambda p, g: (p - 1e-3 * g.astype(jnp.float32)).astype(p.dtype),
            params, grads,
        )
        return params, loss

    return train_step


def main():
    if _SMALL:
        mesh = jax.make_mesh(
            (2, 2, 2), ("pod", "data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 3,
        )
    else:
        mesh = make_production_mesh(multi_pod=True)
    cfg = get_config(ARCH)
    # XLA:CPU CHECK-fails ("Invalid binary instruction opcode copy",
    # hlo_instruction.cc:1558) partitioning bf16 cotangents inside a
    # partial-manual shard_map; bisected to bf16+grad+pipeline — f32
    # compiles.  Lower the demo in f32 and halve its byte metrics when
    # comparing against bf16 baselines (EXPERIMENTS §Perf).
    cfg = cfg.with_overrides(dtype=jnp.float32)
    shape = SHAPES[SHAPE]
    if _SMALL:
        import dataclasses
        shape = dataclasses.replace(shape, global_batch=16, seq_len=512)
    rules = dict(SH.TRAIN_RULES, batch="data")  # pod is the pipeline axis
    a_params = staged_blocks_abstract(cfg, rules, mesh)
    bs, ba = SP.batch_struct(cfg, shape)
    a_batch = SP.sharded(bs, ba, rules, mesh)

    step = make_pipelined_loss(cfg, mesh)
    t0 = time.perf_counter()
    with jax.sharding.set_mesh(mesh):
        lowered = jax.jit(step, donate_argnums=(0,)).lower(a_params, a_batch)
        compiled = lowered.compile()
    compile_s = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    hp = HP.analyze_hlo(compiled.as_text())
    analytic = AN.step_flops(cfg, shape, remat=True, causal_skip=True)
    import dataclasses
    pcfg = _train_config().pipeline_config(NUM_STAGES)
    autodiff_stash = dataclasses.replace(
        pcfg, backward="autodiff"
    ).peak_stash_items
    record = {
        "cell": f"{ARCH}×{SHAPE}×multipod-PIPELINE",
        "mode": f"stream-future pipeline: stages={NUM_STAGES} over 'pod', "
                f"microbatches={NUM_MICRO}, schedule={SCHEDULE}"
                f"x{INTERLEAVE}, backward={BACKWARD}, bubble="
                f"{pcfg.bubble_fraction:.3f}, "
                f"peak_stash={pcfg.peak_stash_items}/{NUM_MICRO}",
        "compile_seconds": compile_s,
        "memory_analysis": {
            "argument_size_gib": mem.argument_size_in_bytes / 2**30,
            "temp_size_gib": mem.temp_size_in_bytes / 2**30,
        },
        "hlo_analysis": {
            "hbm_traffic_gib": hp["hbm_traffic_bytes"] / 2**30,
            "collective_weighted_gib": hp["collective_weighted_bytes"] / 2**30,
            "collective_bytes_by_kind": hp["collective_bytes_by_kind"],
            "top_collectives": hp["top_collectives"][:6],
        },
        "analytic_flops": analytic["total"],
    }
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    with open(os.path.join(ARTIFACT_DIR, f"{ARCH}_{SHAPE}_pipeline.json"), "w") as f:
        json.dump(record, f, indent=2)
    print(json.dumps(record["hlo_analysis"]["collective_bytes_by_kind"], indent=1))
    print(f"pipeline dry-run compiled in {compile_s:.0f}s; "
          f"collective {hp['collective_weighted_bytes']/2**30:.0f} GiB, "
          f"hbm {hp['hbm_traffic_bytes']/2**30:.0f} GiB per device")
    print(f"schedule {SCHEDULE}x{INTERLEAVE} backward={BACKWARD}: "
          f"combined-plan stash bound {pcfg.peak_stash_items}/{NUM_MICRO} "
          f"microbatches per device "
          f"(autodiff keeps {autodiff_stash}/{NUM_MICRO} live; the bound "
          f"is proven by the plan's stash/release columns and realized "
          f"by a fused executor — see schedules.CombinedPlan)")


if __name__ == "__main__":
    main()
