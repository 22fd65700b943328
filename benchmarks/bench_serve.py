"""Serving throughput: Stream-shaped pipelined decode, schedule by schedule.

One subprocess with 2 virtual devices (this container has 2 cores — D=2
is genuine parallelism, matching bench_pipeline's layout choice) runs
the same continuous-batching workload through four engines back to back:

* ``stream_lazy`` — **the layer-sequential baseline**: ``StreamEngine``
  under ``LazyEvaluator`` — the identical ``Stream.feedback`` round
  program (same cells, same in-plan admissions, same emit) with layers
  evaluated sequentially on one device.  This is the paper's Lazy side.
* ``stream_gpipe`` / ``stream_interleaved`` — the same program under
  ``FutureEvaluator`` with the layer-group cells sharded over both
  devices.  The monad substitution is the *only* change; the measured
  gap (gpipe ~1.4x lazy on this container) is the pipelining win —
  per-layer-group latency hidden behind the ring hand-off.
* ``sequential`` — the monolithic reference ``Engine`` (one jitted
  ``decode_step`` per decode step).  On this 2-core container it stays
  fastest in absolute terms because XLA's *intra-op* threading already
  gives the single-device program both cores at near-perfect efficiency
  — device-level pipelining has no spare cores to recruit here, so its
  win shows against the layer-sequential schedule of the same program,
  not against intra-op parallelism.  On a real multi-chip pod the
  sequential engine cannot use the other chips at all; the stream
  schedules are the scaling path (the per-tick overheads measured here
  are CPU-emulation artifacts — on TPU the hand-off is an async
  collective-permute the issue-early/force-late ring overlaps).

Measured per (engine, batch): tokens/sec over a drain of 2x-oversubscribed
requests (so admissions churn mid-flight) and TTFT — a single request on
an idle engine, submit until its first token is caller-visible: one
chunked prefill, plus (stream engines only) their first round, since
control returns to the caller at round boundaries.

**Prefill-tail microbench** (the ``prefill_tail_*`` rows): a prompt of
``2*chunk - 1`` tokens exercises the worst ragged tail.  The padded-tail
path (one masked prefill call, logits read at the last real position) vs
the old per-token path (chunk-1 B=1 decode calls).  Representative run
on this container (chunk=16, smoke model): padded ~40 ms vs per-token
~490 ms — a ~12x TTFT win for short ragged prompts, since tail cost
used to scale with ``plen % chunk``.

**Chaos cells** (the ``chaos_*`` rows): the same drain under the
:class:`repro.serve.supervisor.ServeSupervisor` — once clean, once with
an injected mid-drain fault.  Recorded per cell: ``requests_lost``
(gated == 0), ``bitwise_equal`` to the clean run (gated True), and
``recovery_overhead_seconds`` (the snapshot/restore/replay cost).

``run`` returns records persisted to ``BENCH_serve.json`` — the serving
perf trajectory ``benchmarks/run.py --check`` gates on (tokens/sec may
not regress; chaos cells must keep zero loss; see run.py).
"""
from __future__ import annotations

import json

from benchmarks._util import csv_row, run_with_devices

# (label, schedule, devices, interleave, kernels); stream_lazy is the
# layer-sequential baseline the pipelined schedules are gated against.
# stream_lazy_pallas runs the same round program with the fused
# decode-attention + emit kernels — on CPU the Pallas interpreter
# emulates them (a while loop per grid point), so its tokens/sec is a
# correctness-under-load cell, not the fusion win; the roofline
# prediction recorded next to it is what the fusion buys on real HBM.
ENGINES = [
    ("sequential", "-", 1, 1, "xla"),
    ("stream_lazy", "lazy", 1, 1, "xla"),
    ("stream_lazy_pallas", "lazy", 1, 1, "pallas"),
    ("stream_gpipe", "gpipe", 2, 1, "xla"),
    ("stream_interleaved", "interleaved", 2, 2, "xla"),
]

# Container-class roofline constants for the predicted-tick record
# (directional: the achieved/predicted ratio is tracked, not the
# absolute).  ~2 CPU cores of f32 FMA and dual-channel DDR-class
# bandwidth; on TPU the same prediction uses the chip's specs.
CPU_PEAK_FLOPS = 5e10
CPU_HBM_BPS = 2e10

SCRIPT = """
import json, time, jax, jax.numpy as jnp, numpy as np
from repro.configs.base import DecodePipelineConfig
from repro.configs.registry import get_config, smoke_config
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serve.engine import Engine, ServeConfig, StreamEngine

BATCH, REQUESTS, MAX_NEW, PLEN, CHUNK = {batch}, {requests}, {max_new}, {plen}, {chunk}
DIM, LAYERS, ROUND, MICRO = {dim}, {layers}, {round_steps}, {micro}
cfg = smoke_config(get_config("olmo-1b")).with_overrides(num_layers=LAYERS)
if DIM:
    cfg = cfg.with_overrides(d_model=DIM, d_ff=2 * DIM, num_heads=8,
                             head_dim=DIM // 8, num_kv_heads=2,
                             vocab_size=2048)
params = init_params(jax.random.PRNGKey(0), T.model_layout(cfg))
mesh = jax.make_mesh((2,), ("pod",), devices=jax.devices()[:2])
scfg = ServeConfig(max_batch=BATCH, max_len=64, prefill_chunk=CHUNK,
                   max_new_tokens=MAX_NEW)

def build(label, schedule, devices, interleave, kernels):
    if label == "sequential":
        return Engine(params, cfg, scfg)
    pcfg = DecodePipelineConfig(
        num_cells=LAYERS, microbatches=MICRO,
        schedule=schedule if schedule != "lazy" else "gpipe",
        interleave=interleave, round_steps=ROUND, admit_per_round=4,
        kernels=kernels)
    m = None if schedule == "lazy" else mesh
    return StreamEngine(params, cfg, scfg, pcfg, mesh=m)

def workload(rng):
    return [rng.integers(1, cfg.vocab_size, size=PLEN) for _ in range(REQUESTS)]

results = {{}}
engines = {{label: build(label, s, d, v, kern)
           for label, s, d, v, kern in {engines!r}}}
# warmup: compile every engine's hot path on a small drain
for label, eng in engines.items():
    for p in workload(np.random.default_rng(1))[: BATCH]:
        eng.submit(p, 4)
    eng.run_until_drained()
# TTFT: one request on an idle engine, submit until its first token is
# visible to the caller.  For every engine the token is produced by the
# chunked prefill inside the first step(); the stream engines' number
# additionally includes their first round — that is their true
# caller-observed latency (control only returns at round boundaries).
for label, eng in engines.items():
    vals = []
    for _ in range(3):
        t0 = time.perf_counter()
        r = eng.submit(np.random.default_rng(3).integers(1, cfg.vocab_size, size=PLEN))
        while not r.out_tokens:
            eng.step()
        vals.append(time.perf_counter() - t0)
        eng.run_until_drained()
    results.setdefault(label, {{}})["ttft"] = min(vals)
# paired timing: interleave repeats across engines so drift hits all equally
for rep in range(3):
    for label, eng in engines.items():
        rng = np.random.default_rng(7)
        t0 = time.perf_counter()
        reqs = [eng.submit(p) for p in workload(rng)]
        eng.run_until_drained()
        wall = time.perf_counter() - t0
        total = sum(len(r.out_tokens) for r in reqs)
        results[label].setdefault("runs", []).append((wall, total))
for label in engines:
    walls, totals = zip(*results[label]["runs"])
    print("ENGINE", label, min(walls), results[label]["ttft"], totals[0])

# prefill ragged-tail microbench: padded masked chunk vs per-token decode
eng = engines["sequential"]
prompt = np.arange(1, 2 * CHUNK, dtype=np.int32)  # 2*CHUNK - 1: worst tail
from repro.serve.engine import Request
def padded():
    r = Request(uid=10**6, prompt=prompt, max_new_tokens=4)
    return eng._prefill_single(r)
def per_token():
    single = T.init_cache(cfg, 1, scfg.max_len)
    lg, single = eng._prefill(params, single, tokens=jnp.asarray(prompt[None, :CHUNK]), pos=0)
    for t in range(CHUNK, len(prompt)):
        lg, single = _dec(params, single, jnp.asarray(prompt[None, t]), jnp.full((1,), t, jnp.int32))
    return jax.block_until_ready(lg)
_dec = jax.jit(lambda p, c, t, l: T.decode_step(p, c, cfg=cfg, tokens=t, lengths=l, attn_impl=scfg.attn_impl))
padded(); per_token()  # compile
times_p, times_t = [], []
for _ in range(3):
    t0 = time.perf_counter(); padded(); times_p.append(time.perf_counter() - t0)
    t0 = time.perf_counter(); per_token(); times_t.append(time.perf_counter() - t0)
print("TAIL", min(times_p), min(times_t))

# chaos cells: the same drain under the ServeSupervisor, once clean and
# once with a mid-drain injected fault.  The delta is the cost of
# snapshot+restore+replay; the gated invariants are zero lost requests
# and bitwise-equal tokens (benchmarks/run.py --check pins both).
from repro.serve.supervisor import ServeSupervisor, chaos_injector
for label in ("sequential", "stream_lazy"):
    eng = engines[label]
    sup = ServeSupervisor(eng)
    pristine = sup.snapshot()
    t0 = time.perf_counter()
    reqs = [sup.submit(p) for p in workload(np.random.default_rng(7))]
    sup.run_until_drained()
    clean_wall = time.perf_counter() - t0
    golden = [r.out_tokens for r in reqs]
    sup2 = ServeSupervisor(
        eng, fail_injector=chaos_injector("raise", sup.stats["rounds"] // 2))
    sup2.restore(pristine)
    t0 = time.perf_counter()
    reqs2 = [sup2.submit(p) for p in workload(np.random.default_rng(7))]
    sup2.run_until_drained()
    chaos_wall = time.perf_counter() - t0
    print("CHAOS", label, clean_wall, chaos_wall,
          sup2.stats["requests_lost"], sup2.stats["restarts"],
          [r.out_tokens for r in reqs2] == golden)
"""


def _predicted_ticks(dim: int, layers: int, batch: int) -> dict:
    """Roofline decode-tick predictions for the bench model, per kernel
    mode — recorded so BENCH_serve.json carries achieved-vs-predicted.
    Returns {} when repro isn't importable (standalone benchmark run)."""
    try:
        from repro.configs.registry import get_config, smoke_config
        from repro.roofline.analytic import predicted_tick_seconds
    except ImportError:
        return {}
    cfg = smoke_config(get_config("olmo-1b")).with_overrides(num_layers=layers)
    if dim:
        cfg = cfg.with_overrides(d_model=dim, d_ff=2 * dim, num_heads=8,
                                 head_dim=dim // 8, num_kv_heads=2,
                                 vocab_size=2048)
    return {
        mode: predicted_tick_seconds(
            cfg, batch=batch, kv_len=64,
            peak_flops_per_second=CPU_PEAK_FLOPS,
            hbm_bytes_per_second=CPU_HBM_BPS, mode=mode,
        )["total"]
        for mode in ("xla", "pallas")
    }


def run(quick: bool = True):
    rows, records = [], []
    # dim=0 keeps the smoke model's 64-dim blocks — the regime where the
    # round program's per-tick costs are amortized and the monad
    # substitution's pipelining win is measurable on 2 CPU cores.
    dim, layers = (0, 8) if quick else (384, 8)
    batches = (8, 16) if quick else (8, 16)
    for batch in batches:
        out = run_with_devices(
            SCRIPT.format(
                batch=batch,
                requests=2 * batch,
                max_new=24 if quick else 32,
                plen=16,
                chunk=16,
                dim=dim,
                layers=layers,
                round_steps=16,
                micro=2,
                engines=ENGINES,
            ),
            2,
            timeout=3000,
        )
        tail = None
        per_engine = {}
        chaos = {}
        for line in out.strip().splitlines():
            parts = line.split()
            if parts[0] == "ENGINE":
                per_engine[parts[1]] = (
                    float(parts[2]), float(parts[3]), int(parts[4])
                )
            elif parts[0] == "TAIL":
                tail = (float(parts[1]), float(parts[2]))
            elif parts[0] == "CHAOS":
                chaos[parts[1]] = (
                    float(parts[2]), float(parts[3]),
                    int(parts[4]), int(parts[5]), parts[6] == "True",
                )
        lazy_tps = None
        if "stream_lazy" in per_engine:
            w, _, tot = per_engine["stream_lazy"]
            lazy_tps = tot / w
        predicted = _predicted_ticks(dim, layers, batch)
        for label, schedule, ndev, interleave, kern in ENGINES:
            wall, ttft, total = per_engine[label]
            tps = total / wall
            # one "tick" = one decode step across the full batch; the
            # drain produces total tokens over batch-wide steps
            achieved_tick = wall * batch / total
            pred = predicted.get(kern)
            vs = (
                f",vs_lazy={tps / lazy_tps:.2f}x"
                if lazy_tps and label.startswith("stream_") and label != "stream_lazy"
                else ""
            )
            if pred:
                vs += f",roofline_tick_ms={pred*1e3:.2f}"
            rows.append(
                csv_row(
                    f"serve_{label}_b{batch}",
                    wall,
                    f"tok_per_s={tps:.1f},ttft_ms={ttft*1e3:.1f},"
                    f"devices={ndev},kernels={kern}"
                    + (f",V={interleave}" if interleave > 1 else "")
                    + vs,
                )
            )
            records.append(
                {
                    "engine": label,
                    "schedule": schedule,
                    "devices": ndev,
                    "interleave": interleave,
                    "kernels": kern,
                    "batch": batch,
                    "requests": 2 * batch,
                    "max_new": 24 if quick else 32,
                    "prompt_len": 16,
                    "dim": dim,
                    "layers": layers,
                    "round_steps": 16,
                    "layer_sequential_baseline": label == "stream_lazy",
                    "tokens_per_sec": tps,
                    "ttft_seconds": ttft,
                    "speedup_vs_layer_sequential": (
                        tps / lazy_tps if lazy_tps else None
                    ),
                    "wall_seconds": wall,
                    "achieved_tick_seconds": achieved_tick,
                    "predicted_tick_seconds": pred,
                    "tick_vs_roofline": (
                        achieved_tick / pred if pred else None
                    ),
                }
            )
        for label, (cw, xw, lost, restarts, bitwise) in chaos.items():
            # supervised-recovery cells: no tokens_per_sec on purpose —
            # the gate on these is zero-loss + bitwise, not throughput.
            rows.append(
                csv_row(
                    f"serve_chaos_{label}_b{batch}",
                    xw,
                    f"clean_s={cw:.2f},requests_lost={lost},"
                    f"restarts={restarts},bitwise={bitwise},"
                    f"overhead_ms={(xw - cw)*1e3:.0f}",
                )
            )
            records.append(
                {
                    "engine": f"chaos_{label}",
                    "schedule": "-",
                    "devices": 1,
                    "interleave": 1,
                    "kernels": "xla",
                    "batch": batch,
                    "requests": 2 * batch,
                    "dim": dim,
                    "layers": layers,
                    "requests_lost": lost,
                    "restarts": restarts,
                    "bitwise_equal": bitwise,
                    "clean_wall_seconds": cw,
                    "chaos_wall_seconds": xw,
                    "recovery_overhead_seconds": xw - cw,
                }
            )
        if tail is not None:
            rows.append(
                csv_row(
                    f"serve_prefill_tail_b{batch}",
                    tail[0],
                    f"padded_ms={tail[0]*1e3:.1f},"
                    f"per_token_ms={tail[1]*1e3:.1f},"
                    f"speedup={tail[1]/tail[0]:.1f}x",
                )
            )
            records.append(
                {
                    "engine": "prefill_tail",
                    "schedule": "-",
                    "devices": 1,
                    "interleave": 1,
                    "batch": batch,
                    "dim": dim,
                    "padded_seconds": tail[0],
                    "per_token_seconds": tail[1],
                }
            )
    run.records = records  # picked up by benchmarks.run for BENCH_serve.json
    return rows


if __name__ == "__main__":
    for row in run(quick=True):
        print(row)
