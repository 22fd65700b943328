"""End-to-end training driver.

Wires every substrate layer together: config registry → mesh → sharded
params/optimizer → step-keyed data pipeline with prefetch (future tails) →
jitted train step (microbatch stream) → resilient loop (heartbeats,
straggler detection, async checkpoints, restart-on-failure).

CPU-scale example (the quickstart path, ~25M params):
    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
        --steps 100 --global-batch 8 --seq-len 256
Production shapes lower through the same code path (see dryrun.py).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCH_IDS, get_config, smoke_config
from repro.data.pipeline import DataConfig, PrefetchIterator, make_source
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as T
from repro.models.params import init_params, param_count
from repro.parallel import sharding as SH
from repro.train.checkpoint import Checkpointer
from repro.train.fault import FaultConfig, ResilientLoop
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.train_step import TrainConfig, make_train_step


def build(args):
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
        cfg = cfg.with_overrides(
            d_model=args.d_model or 256,
            num_layers=args.layers or cfg.num_layers,
            d_ff=4 * (args.d_model or 256) if cfg.d_ff else 0,
            vocab_size=1024,
        )
    tcfg = TrainConfig(
        num_microbatches=args.microbatches,
        attn_impl=args.attn_impl,
        remat=True,
        pipeline_schedule=args.pipeline_schedule,
        pipeline_backward=args.pipeline_backward,
        kernels=args.kernels,
    )
    ocfg = AdamWConfig(
        learning_rate=args.lr, warmup_steps=args.warmup,
        total_steps=args.steps,
    )
    return cfg, tcfg, ocfg


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="olmo-1b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--attn-impl", default="dense",
                    choices=["dense", "chunked", "pallas"])
    ap.add_argument("--pipeline-schedule", default="gpipe",
                    choices=["gpipe", "one_f_one_b", "interleaved"],
                    help="layer-pipeline tick schedule (multi-pod mode)")
    ap.add_argument("--pipeline-backward", default="autodiff",
                    choices=["autodiff", "planned"],
                    help="backward execution: jax.grad transpose of the "
                         "forward plan, or the combined plan's B units "
                         "through the custom-VJP engine (true 1F1B)")
    ap.add_argument("--kernels", choices=["xla", "pallas", "auto"],
                    default="xla",
                    help="kernel dispatch (repro.kernels). Training "
                         "requires xla (Pallas kernels have no VJPs); "
                         "pallas fails fast with a clear error, auto "
                         "resolves to xla")
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg, tcfg, ocfg = build(args)
    layout = T.model_layout(cfg)
    print(f"arch={cfg.name} params={param_count(layout)/1e6:.1f}M "
          f"devices={jax.device_count()}")
    if tcfg.num_microbatches > 1:
        # Surface the schedule's memory bound (4-stage reference split —
        # this CPU driver itself runs unpipelined; the multi-pod driver
        # is launch.pipeline_demo): the combined plan's stash bound vs
        # what autodiff keeps live.  Plan-level: the bound a fused
        # executor realizes; the two-phase custom-VJP realization holds
        # V*M at the autodiff phase boundary (see CombinedPlan).
        pcfg = tcfg.pipeline_config(num_stages=4)
        auto = dataclasses.replace(pcfg, backward="autodiff").peak_stash_items
        print(f"pipeline: schedule={tcfg.pipeline_schedule} "
              f"backward={tcfg.pipeline_backward} -> combined-plan stash "
              f"bound {pcfg.peak_stash_items}/{tcfg.num_microbatches} "
              f"microbatches per device at a 4-stage split (autodiff "
              f"keeps {auto}/{tcfg.num_microbatches} live)")

    rng = jax.random.PRNGKey(args.seed)
    params = init_params(rng, layout)
    opt_state = init_opt_state(params, ocfg)

    # data: step-keyed, prefetched
    dcfg = DataConfig(
        seq_len=args.seq_len, global_batch=args.global_batch,
        seed=args.seed, vocab_size=cfg.vocab_size,
    )
    source = make_source(dcfg)

    def batch_fn(step):
        b = source.batch(step)
        return {k: jnp.asarray(v) for k, v in b.items()}

    step_fn = jax.jit(make_train_step(cfg, tcfg, ocfg), donate_argnums=(0, 1))

    ckpt = Checkpointer(args.checkpoint_dir)
    start_step = 0
    if args.resume and ckpt.latest_step() is not None:
        state, start_step = ckpt.restore(
            {"params": params, "opt_state": opt_state}
        )
        params, opt_state = state["params"], state["opt_state"]
        print(f"resumed from step {start_step}")

    loop = ResilientLoop(
        step_fn, ckpt,
        FaultConfig(checkpoint_every=args.checkpoint_every,
                    heartbeat_path=args.checkpoint_dir + "/heartbeat"),
    )
    loop.install_signal_handlers()

    t0 = time.perf_counter()
    params, opt_state, step, history = loop.run(
        params, opt_state, batch_fn, args.steps, start_step=start_step
    )
    wall = time.perf_counter() - t0
    for h in history[:: args.log_every]:
        print(f"step {h['step']:5d}  loss {h['loss']:.4f}  "
              f"gnorm {h['grad_norm']:.3f}  lr {h['learning_rate']:.2e}")
    if history:
        print(f"final loss {history[-1]['loss']:.4f}  "
              f"({wall/max(1,len(history)):.2f}s/step, "
              f"restarts={loop.stats['restarts']}, "
              f"stragglers={loop.stats['stragglers']})")
    return history


if __name__ == "__main__":
    main()
