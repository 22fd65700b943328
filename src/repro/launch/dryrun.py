import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first — jax locks the device count on first
init, and the production meshes need 512 host-platform placeholders.

Per cell this driver:
  1. builds the production mesh (16×16 or 2×16×16),
  2. builds abstract inputs (ShapeDtypeStruct + NamedSharding — no
     allocation; the 398 B configs never materialize),
  3. ``jax.jit(step).lower(...).compile()`` — sharding propagation, SPMD
     partitioning and scheduling all run for real; failures here are
     system bugs,
  4. records ``memory_analysis()`` (fits-on-chip proof),
     ``cost_analysis()`` (FLOPs/bytes) and HLO collective bytes
     (roofline terms) to ``experiments/dryrun/<cell>.json``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all  [--multi-pod-only]
"""
import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES
from repro.configs.registry import all_cells, get_config
from repro.launch import specs as SP
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.models.params import param_count
from repro.parallel import sharding as SH
from repro.roofline import analysis as RL
from repro.roofline import analytic as AN
from repro.roofline import hlo_parse as HP
from repro.train import optimizer as O
from repro.train.train_step import TrainConfig, make_train_step

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "../../../experiments/dryrun")


def cell_rules(shape_name: str):
    if shape_name == "long_500k":
        return SH.LONG_DECODE_RULES
    if SHAPES[shape_name].kind == "decode":
        return SH.DECODE_RULES
    if SHAPES[shape_name].kind == "prefill":
        return SH.PREFILL_RULES
    return SH.TRAIN_RULES


def train_configs_for(cfg):
    """Production microbatching/dtype policy per model size."""
    big = param_count(T.model_layout(cfg)) > 90e9
    # §Perf iteration 2: fewer/bigger microbatches — per-microbatch fixed
    # collectives (ZeRO-3 weight all-gathers, grad reductions) dominate the
    # collective term and scale linearly with the count.  Iteration 6:
    # microbatch count targets a fixed ~256k tokens per microbatch (the
    # paper's §7 chunk-size rule, applied via optimal_num_chunks logic):
    # a size-blind global count regressed the memory term on mid models
    # (qwen3 train 118→172 s at µb=2) while big models were already at
    # the target.  Divisibility walked down from the target.
    tokens = SHAPES["train_4k"].tokens
    num_micro = max(1, tokens // 262144)
    while SHAPES["train_4k"].global_batch % num_micro != 0:
        num_micro -= 1
    tcfg = TrainConfig(
        num_microbatches=num_micro,
        accum_dtype=jnp.bfloat16 if big else jnp.float32,
        attn_impl="chunked",
        remat=True,
        unroll=False,  # rolled scans; loop-aware HLO analysis scales bodies
        # §Perf iteration 6: causal block skipping stays ON for forward-only
        # paths (prefill: pure win) but OFF for training — the pair scan's
        # backward carry traffic outweighs the halved attention FLOPs on
        # memory-bound train cells (qwen3: mem 172.9 -> 118.5 s).
        causal_skip=False,
    )
    ocfg = O.AdamWConfig(
        moment_dtype=jnp.bfloat16 if big else jnp.float32
    )
    return tcfg, ocfg





def lower_cell(arch: str, shape_name: str, mesh, mesh_name: str):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rules = cell_rules(shape_name)
    tcfg, ocfg = train_configs_for(cfg)

    layout = T.model_layout(cfg)
    pspecs = SH.param_pspecs(layout, rules, mesh)

    def sh_of(tree):
        return jax.tree.map(lambda s: s.sharding, tree,
                            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    scale = 1
    with jax.sharding.set_mesh(mesh):
        if shape.kind == "train":
            step = make_train_step(cfg, tcfg, ocfg, param_pspecs=pspecs)
            a_params, a_opt = SP.abstract_model_state(cfg, ocfg, rules, mesh)
            batch_structs, batch_axes = SP.batch_struct(cfg, shape)
            a_batch = SP.sharded(batch_structs, batch_axes, rules, mesh)
            jitted = jax.jit(
                step, donate_argnums=(0, 1),
                out_shardings=(sh_of(a_params), sh_of(a_opt), None),
            )
            lowered = jitted.lower(a_params, a_opt, a_batch)
        elif shape.kind == "prefill":
            a_params, _ = SP.abstract_model_state(cfg, ocfg, rules, mesh)
            a_caches = SP.abstract_cache(cfg, shape, rules, mesh)
            a_in = SP.prefill_inputs(cfg, shape, rules, mesh)
            step = partial(
                T.prefill_step, cfg=cfg, attn_impl="chunked",
                q_chunk=512, kv_chunk=1024,
            )
            jitted = jax.jit(
                step, donate_argnums=(1,),
                out_shardings=(None, sh_of(a_caches)),
            )
            lowered = jitted.lower(a_params, a_caches, pos=0, **a_in)
        else:  # decode
            a_params, _ = SP.abstract_model_state(cfg, ocfg, rules, mesh)
            a_caches = SP.abstract_cache(cfg, shape, rules, mesh)
            a_in = SP.decode_inputs(cfg, shape, rules, mesh)
            # §Perf iteration 5: decode uses dense attention — q=1 scores
            # against the seq-sharded cache stay shard-local with tiny
            # (B,1,KV,G) stat reductions (flash-decoding via GSPMD); the
            # chunked kv scan's traced-offset slices forced fp32 all-
            # gathers of the whole cache (2×64 GiB/step on qwen3).
            step = partial(T.decode_step, cfg=cfg, attn_impl="dense")
            jitted = jax.jit(
                step, donate_argnums=(1,),
                out_shardings=(None, sh_of(a_caches)),
            )
            lowered = jitted.lower(a_params, a_caches, **a_in)

    return cfg, shape, lowered, scale, tcfg


def _analytic_state_gib(cfg, shape, tcfg, chips):
    """params + moments + grad accumulator + saved activation stack, per chip."""
    layout = T.model_layout(cfg)
    n = param_count(layout)
    bytes_total = n * 2            # bf16 params
    moment_b = 2 if tcfg.accum_dtype == jnp.bfloat16 else 4
    if shape.kind == "train":
        bytes_total += 2 * n * moment_b        # adam m, v
        accum_b = 2 if tcfg.accum_dtype == jnp.bfloat16 else 4
        bytes_total += n * accum_b             # grad accumulator
        groups = cfg.num_layers // max(1, T.effective_period(cfg))
        tokens_mb = shape.tokens // tcfg.num_microbatches
        bytes_total_act = groups * tokens_mb * cfg.d_model * 2  # saved stack
        return (bytes_total / chips + bytes_total_act / chips) / 2**30
    if shape.kind == "decode":
        # params + caches handled in args; just params here
        return (bytes_total / chips) / 2**30
    return (bytes_total / chips) / 2**30


def analyze(arch, shape_name, mesh_name, cfg, shape, lowered, scale, tcfg):
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    hp = HP.analyze_hlo(hlo)  # loop-aware collectives + HBM traffic

    chips = 512 if mesh_name == "multipod" else 256
    layout = T.model_layout(cfg)
    n_active = RL.active_param_count(cfg, layout)
    mflops = RL.model_flops(cfg, shape, n_active)
    # analytic count mirrors the lowering's causal-skip policy (iter. 6):
    # prefill auto-skips (forward-only); train lowers with skip off.
    skip = shape.kind == "prefill" or (
        shape.kind == "train" and bool(tcfg.causal_skip)
    )
    analytic = AN.step_flops(cfg, shape, remat=tcfg.remat, causal_skip=skip)
    raw_flops = float(cost.get("flops", 0.0))

    terms = RL.RooflineTerms(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        hlo_flops=raw_flops,
        hlo_bytes=hp["hbm_traffic_bytes"],
        collective_bytes=hp["collective_weighted_bytes"],
        model_flops=mflops,
        analytic_flops=analytic["total"],
    ).finalize()

    record = {
        "cell": f"{arch}×{shape_name}×{mesh_name}",
        "compile_seconds": None,
        "memory_analysis": {
            "argument_size_gib": mem.argument_size_in_bytes / 2**30,
            "output_size_gib": mem.output_size_in_bytes / 2**30,
            "temp_size_gib": mem.temp_size_in_bytes / 2**30,
            "peak_gib": (
                mem.argument_size_in_bytes
                + mem.temp_size_in_bytes
            ) / 2**30,
            # Decomposed estimate of the real per-chip residency (the CPU
            # backend's temp figure includes scatter-lowering key buffers
            # and fp32 cotangent copies a TPU lowering does not hold; see
            # EXPERIMENTS.md §Dry-run "memory methodology").
            "analytic_state_gib": _analytic_state_gib(cfg, shape, tcfg, chips),
        },
        "cost_analysis": {
            "flops_raw_hlo": raw_flops,
            "analytic_flops": analytic["total"],
            "analytic_breakdown": analytic["forward"],
            "xla_bytes_accessed_raw": float(cost.get("bytes accessed", 0.0)),
        },
        "hlo_analysis": {
            "hbm_traffic_gib": hp["hbm_traffic_bytes"] / 2**30,
            "collective_weighted_gib": hp["collective_weighted_bytes"] / 2**30,
            "collective_bytes_by_kind": hp["collective_bytes_by_kind"],
            "collective_counts_static": hp["collective_counts_static"],
            "collective_counts_dynamic": hp["collective_counts_dynamic"],
            "num_loops": hp["num_loops"],
            "top_collectives": hp["top_collectives"],
        },
        "roofline": terms.to_json(),
        "params_total": param_count(layout),
        "params_active": n_active,
    }
    return record, compiled


def run_cell(arch, shape_name, multi_pod: bool, save=True, verbose=True):
    mesh_name = "multipod" if multi_pod else "pod"
    t0 = time.perf_counter()
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg, shape, lowered, scale, tcfg = lower_cell(arch, shape_name, mesh, mesh_name)
    record, compiled = analyze(
        arch, shape_name, mesh_name, cfg, shape, lowered, scale, tcfg
    )
    record["compile_seconds"] = time.perf_counter() - t0
    if verbose:
        r = record["roofline"]
        print(
            f"{arch:28s} {shape_name:12s} {mesh_name:8s} "
            f"peak {record['memory_analysis']['peak_gib']:7.2f} GiB  "
            f"compute {r['compute_s']*1e3:9.3f} ms  "
            f"memory {r['memory_s']*1e3:9.3f} ms  "
            f"collective {r['collective_s']*1e3:9.3f} ms  "
            f"-> {r['bottleneck']}"
        )
    if save:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        fname = f"{arch}_{shape_name}_{mesh_name}.json".replace("/", "_")
        with open(os.path.join(ARTIFACT_DIR, fname), "w") as f:
            json.dump(record, f, indent=2)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    args = ap.parse_args()

    if args.all:
        cells = all_cells()
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape)]

    meshes = [False, True]
    if args.single_pod_only:
        meshes = [False]
    if args.multi_pod_only:
        meshes = [True]
    if args.multi_pod:
        meshes = [True]

    failures = []
    for arch, shape_name in cells:
        for multi_pod in meshes:
            try:
                run_cell(arch, shape_name, multi_pod)
            except Exception as e:  # noqa: BLE001 — report and continue
                failures.append((arch, shape_name, multi_pod, repr(e)))
                print(f"FAIL {arch} {shape_name} multipod={multi_pod}: {e}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} failures:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nAll dry-run cells compiled.")


if __name__ == "__main__":
    main()
