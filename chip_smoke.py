"""Serve olmo-1b at its published widths on TPU, end to end.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # pipelined over four chips

One chip: ``StreamEngine`` with no mesh (``LazyEvaluator``, fused Pallas
decode kernels) serves 16 seeded requests; the sequential ``Engine`` on
the XLA path serves the same requests first, as the reference.  Four
chips: only the pipelined phase — ``StreamEngine`` under
``FutureEvaluator`` on a four-chip mesh, gpipe with 4 cells and then
interleaved (V=2) with 8 cells — against the same reference on chip 0.

Weights are random, made from ``--seed`` with ``init_params``: no
published checkpoint is in the repository.  The check is greedy-token
agreement with the reference.  With random weights the top logits are
dense, and a bf16 rounding difference between two correct paths flips
near-ties, so a divergence is judged against exact arithmetic: at a
request's first differing step the logits are recomputed, teacher-forced
on the shared prefix with ``transformer.decode_step``, by the reference
(XLA) path, by the engine's kernel path, and by the XLA path in fp32.
The divergence is accounted for when the kernel path is no further from
the fp32 logits than ``ERR_RATIO`` times the XLA path's own bf16 error,
and the two tokens' fp32 logits lie within the sum of both paths'
errors of each other: a tie that bf16 rounding can flip.  Any other
divergence fails the run.

This is a smoke run, not a benchmark: times and rates printed here come
from one unrepeated run, compilation excluded where stated.  The last
line of standard output is one JSON object; the run exits nonzero, and
prints no such line, when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import DecodePipelineConfig  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.kernels import resolve_mode  # noqa: E402
from repro.kernels.decode_attention.ops import FUSION_SCOPE as ATTN_SCOPE  # noqa: E402
from repro.kernels.emit_norm_logits.ops import FUSION_SCOPE as EMIT_SCOPE  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.params import init_params, param_count  # noqa: E402
from repro.roofline.hlo_parse import tpu_kernel_present  # noqa: E402
from repro.serve.engine import Engine, ServeConfig, StreamEngine  # noqa: E402

ARCH = "olmo-1b"
REQUESTS = 16
PROMPT_LENS = (128, 1024)
MAX_NEW = 32
SCFG = dict(max_batch=8, max_len=2048, prefill_chunk=256, max_new_tokens=MAX_NEW)
# How much further from the fp32 logits than the XLA bf16 path the
# kernel path may be (both errors are maxima over the vocabulary).
ERR_RATIO = 2.0


def log(msg: str) -> None:
    print(msg, flush=True)


def make_requests(seed: int, vocab: int):
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, size=REQUESTS)
    return [rng.integers(1, vocab, size=int(n)).astype(np.int32) for n in lens]


def serve(engine, prompts, label: str):
    """Submit every prompt and drain.  The first step compiles the
    prefill and decode programs; its time is reported on its own."""
    reqs = [engine.submit(p, MAX_NEW) for p in prompts]
    t0 = time.perf_counter()
    engine.step()
    jax.block_until_ready(jax.tree.leaves(
        engine.cell_states if hasattr(engine, "cell_states") else engine.cache))
    first = time.perf_counter() - t0
    before = sum(len(r.out_tokens) for r in reqs)
    t1 = time.perf_counter()
    engine.run_until_drained()
    wall = time.perf_counter() - t1
    tokens = sum(len(r.out_tokens) for r in reqs)
    log(f"{label}: first step (compile + run) {first:.3f} s; then "
        f"{tokens - before} tokens in {wall:.3f} s wall "
        f"({(tokens - before) / wall:.1f} tokens/s; smoke run, one "
        "unrepeated pass, not a benchmark)")
    return [list(r.out_tokens) for r in reqs]


def teacher_forcer(params, cfg, scfg, kernels):
    """``f(prompt, forced)``: the logits after ``prompt`` and the
    ``forced`` tokens, through the engines' own chunked prefill and
    ``decode_step`` at batch 1 on the ``kernels`` path."""
    ck = scfg.prefill_chunk
    prefill = jax.jit(partial(T.prefill_step, cfg=cfg, attn_impl=scfg.attn_impl))
    decode = jax.jit(partial(T.decode_step, cfg=cfg, attn_impl=scfg.attn_impl,
                             kernels=kernels))

    def logits_after(prompt, forced):
        cache = T.init_cache(cfg, 1, scfg.max_len)
        full = (len(prompt) // ck) * ck
        for c in range(0, full, ck):
            logits, cache = prefill(params, cache,
                                    tokens=jnp.asarray(prompt[None, c:c + ck]), pos=c)
        if len(prompt) > full:
            tail = np.zeros((1, min(ck, scfg.max_len - full)), np.int32)
            tail[0, :len(prompt) - full] = prompt[full:]
            logits, cache = prefill(
                params, cache, tokens=jnp.asarray(tail), pos=full,
                logits_at=jnp.asarray(len(prompt) - full - 1, jnp.int32))
        for pos, tok in enumerate(forced, start=len(prompt)):
            logits, cache = decode(params, cache, tokens=jnp.asarray([tok], jnp.int32),
                                   lengths=jnp.asarray([pos], jnp.int32))
        return np.asarray(logits[0], np.float32)

    return logits_after


def compare(params, cfg, scfg, prompts, ref_tokens, got_tokens, label: str) -> bool:
    """Token agreement; each divergence must be a tie (see module doc)."""
    agree = total = 0
    ok = True
    forcers = None
    for r, (prompt, a, b) in enumerate(zip(prompts, ref_tokens, got_tokens)):
        total += len(a)
        step = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
        if step is None:
            if len(a) != len(b):
                log(f"{label}: request {r} length {len(b)} != reference {len(a)}")
                ok = False
            agree += len(a)
            continue
        agree += step
        if forcers is None:
            exact = jax.tree.map(lambda x: x.astype(jnp.float32), params)
            forcers = (
                teacher_forcer(params, cfg, scfg, "xla"),
                teacher_forcer(params, cfg, scfg, resolve_mode(cfg.kernels)),
                teacher_forcer(exact, cfg.with_overrides(dtype=jnp.float32),
                               scfg, "xla"),
            )
        ref_lg, got_lg, exact_lg = (f(prompt, a[:step]) for f in forcers)
        err_ref = float(np.abs(ref_lg - exact_lg).max())
        err_got = float(np.abs(got_lg - exact_lg).max())
        gap = float(abs(exact_lg[a[step]] - exact_lg[b[step]]))
        tie = err_got <= ERR_RATIO * err_ref and gap <= err_ref + err_got
        ok = ok and tie
        log(f"{label}: request {r} first differs at step {step}: reference "
            f"token {a[step]} vs {b[step]}; max|logit - fp32| xla {err_ref:.6g}, "
            f"kernels {err_got:.6g}; fp32 gap of the two tokens {gap:.6g} -> "
            f"{'tie, accounted for' if tie else 'NOT accounted for'}")
    log(f"{label}: token agreement {agree}/{total} before the first "
        f"divergence of each request")
    return ok


def round_hlo(engine) -> str:
    """Compiled text of the engine's round program (no requests queued)."""
    adm, _ = engine._plan_admissions(engine.pcfg.round_steps)
    init_items, overlay, adm_payload = engine._build_round_inputs(adm)
    return engine._round.lower(
        {**engine.cell_consts, "adm": adm_payload},
        engine.cell_states, init_items, overlay,
    ).compile().as_text()


def report_kernels(engine, label: str) -> bool:
    t0 = time.perf_counter()
    text = round_hlo(engine)
    attn = tpu_kernel_present(text, ATTN_SCOPE)
    emit = tpu_kernel_present(text, EMIT_SCOPE)
    log(f"{label}: round compile {time.perf_counter() - t0:.3f} s; "
        f"tpu_custom_call decode_attention={attn} emit_norm_logits={emit}")
    return attn and emit


def peak_memory(devices) -> list[int]:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in devices]


def run_reference(params, cfg, scfg, prompts):
    ref = Engine(params, cfg.with_overrides(kernels="xla"), scfg)
    tokens = serve(ref, prompts, "reference Engine (xla)")
    del ref
    gc.collect()
    return tokens


def one_chip(params, cfg, scfg, prompts) -> bool:
    ref_tokens = run_reference(params, cfg, scfg, prompts)
    pcfg = DecodePipelineConfig(num_cells=4, round_steps=8)
    eng = StreamEngine(params, cfg, scfg, pcfg)
    log(f"StreamEngine: evaluator={eng.evaluator.name} kernels={eng.kernels} "
        f"cells={pcfg.num_cells} microbatches={pcfg.microbatches} "
        f"round_steps={pcfg.round_steps}")
    got = serve(eng, prompts, "StreamEngine (lazy)")
    ok = report_kernels(eng, "StreamEngine (lazy)")
    log(f"peak_bytes_in_use after serving: {peak_memory(jax.devices()[:1])}")
    del eng
    gc.collect()
    return compare(params, cfg, scfg, prompts, ref_tokens, got, "lazy") and ok


def four_chips(params, cfg, scfg, prompts) -> bool:
    devices = jax.devices()
    if len(devices) != 4:
        raise SystemExit(f"--chips 4 needs four devices, found {len(devices)}")
    ref_tokens = run_reference(params, cfg, scfg, prompts)
    mesh = jax.make_mesh((4,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))
    ok = True
    outputs = {}
    for schedule, v, cells in (("gpipe", 1, 4), ("interleaved", 2, 8)):
        pcfg = DecodePipelineConfig(num_cells=cells, schedule=schedule,
                                    interleave=v, round_steps=8)
        eng = StreamEngine(params, cfg, scfg, pcfg, mesh=mesh)
        label = f"StreamEngine (future, {schedule}, V={v}, {cells} cells)"
        outputs[schedule] = serve(eng, prompts, label)
        ok = report_kernels(eng, label) and ok
        leaf = jax.tree.leaves(eng.cell_consts)[0]
        log(f"{label}: cell_consts leaf {leaf.shape} sharding {leaf.sharding}; "
            f"cache leaf sharding {jax.tree.leaves(eng.cell_states)[0].sharding}")
        log(f"{label}: peak_bytes_in_use per device {peak_memory(devices)}")
        del eng
        gc.collect()
    for schedule, got in outputs.items():
        ok = compare(params, cfg, scfg, prompts, ref_tokens, got, schedule) and ok
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    cache_dir = use_compile_cache()
    log(f"jax {jax.__version__}; compile cache {cache_dir}")
    for d in devices:
        log(f"device {d.id}: platform={d.platform} kind={d.device_kind}")

    cfg = get_config(ARCH)
    scfg = ServeConfig(seed=args.seed, **SCFG)
    t0 = time.perf_counter()
    layout = T.model_layout(cfg)
    params = jax.block_until_ready(
        jax.jit(lambda key: init_params(key, layout))(jax.random.PRNGKey(args.seed))
    )
    log(f"{ARCH}: {param_count(layout) / 1e9:.3f}B params, "
        f"layers={cfg.num_layers} d_model={cfg.d_model} heads={cfg.num_heads}"
        f"x{cfg.head_dim} kv_heads={cfg.num_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} dtype={jnp.dtype(cfg.dtype).name}; "
        f"init {time.perf_counter() - t0:.3f} s")
    prompts = make_requests(args.seed, cfg.vocab_size)
    log(f"{len(prompts)} requests, prompt lengths {[len(p) for p in prompts]}, "
        f"max_new_tokens {MAX_NEW}; {SCFG}")

    run = one_chip if args.chips == 1 else four_chips
    ok = run(params, cfg, scfg, prompts)
    print(json.dumps({"ok": bool(ok), "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
