"""Pipeline schedules: analytic model vs measured step time.

Fixes the *model* at 4 pipeline stages (16 cells) and M microbatches,
and lets each schedule realize those stages on its natural device
layout — gpipe / 1F1B span one device per stage (D=4, V=1); the
interleaved schedule assigns each of D=2 devices V=2 non-contiguous
stage groups.  That is the schedule's actual production trade: fewer
pipeline devices each owning interleaved chunks, cutting the per-device
bubble `h(D-1)/(V*M + h(D-1))` and matching device count to real
parallel lanes (this container has 2 cores, so 4 virtual devices
oversubscribe 2x while D=2 is genuine parallelism).

All layouts for a given M are timed back-to-back inside one subprocess,
interleaved across repeats, so machine drift hits every schedule
equally — unpaired measurements minutes apart would drown the bubble
effect in noise.  Work sizes are chosen so per-cell compute dominates
the ring rendezvous (~ms on CPU): the paper's Section 7 condition,
measured.  The modeled bubble/ticks come from the schedule-aware
chunking model (`schedule_ticks` / `schedule_bubble_fraction`); `run`
returns records that `benchmarks/run.py` persists to
BENCH_pipeline.json as the perf trajectory baseline.
"""
from __future__ import annotations

from benchmarks._util import csv_row, run_with_devices
from repro.core.chunking import schedule_bubble_fraction, schedule_ticks

# (schedule, devices, interleave): always devices * interleave == 4
# virtual stages of the same 16-cell model.
SWEEP = [
    ("gpipe", 4, 1),
    ("one_f_one_b", 4, 1),
    ("interleaved", 2, 2),
]

SCRIPT = """
import time, jax, jax.numpy as jnp
from repro.core import StreamProgram, FutureEvaluator, evaluate
M, D, ROWS = {micro}, {dim}, {rows}
CELLS = 16  # 4 virtual stages x 4 cells, identical for every layout
W = jax.random.normal(jax.random.PRNGKey(0), (CELLS, D, D)) / D**0.5
prog = StreamProgram(lambda w, x: (w, jnp.tanh(x @ w)), W, CELLS,
                     mutable_state=False)
items = jax.random.normal(jax.random.PRNGKey(1), (M, ROWS // M, D))
runs = {{}}
for name, ndev, v in {sweep!r}:
    mesh = jax.make_mesh((ndev,), ("pod",), devices=jax.devices()[:ndev])
    ev = FutureEvaluator(mesh, "pod", schedule=name, interleave=v)
    fn = jax.jit(lambda items, ev=ev: evaluate(prog, items, ev)[1])
    jax.block_until_ready(fn(items))  # compile
    runs[name] = fn
best = {{name: 1e9 for name, _, _ in {sweep!r}}}
for _ in range(7):  # interleave repeats across schedules: paired timing
    for name, fn in runs.items():
        t0 = time.perf_counter()
        jax.block_until_ready(fn(items))
        best[name] = min(best[name], time.perf_counter() - t0)
for name, t in best.items():
    print(name, t)
"""


def run(quick: bool = True):
    rows_csv, records = [], []
    dim, rows = (256, 4096) if quick else (512, 8192)
    for micro in (1, 2, 4, 8, 16):
        out = run_with_devices(
            SCRIPT.format(micro=micro, dim=dim, rows=rows, sweep=SWEEP), 4
        )
        timings = dict(
            line.split() for line in out.strip().splitlines()[-len(SWEEP):]
        )
        for schedule, ndev, interleave in SWEEP:
            t = float(timings[schedule])
            frac = schedule_bubble_fraction(schedule, ndev, micro, interleave)
            ticks = schedule_ticks(schedule, ndev, micro, interleave)
            rows_csv.append(
                csv_row(
                    f"pipeline_{schedule}_m{micro}",
                    t,
                    f"bubble={frac:.3f},ticks={ticks},devices={ndev}"
                    + (f",V={interleave}" if interleave > 1 else ""),
                )
            )
            records.append(
                {
                    "schedule": schedule,
                    "devices": ndev,
                    "interleave": interleave,
                    "virtual_stages": ndev * interleave,
                    "num_microbatches": micro,
                    "dim": dim,
                    "rows": rows,
                    "measured_seconds": t,
                    "modeled_bubble": frac,
                    "modeled_ticks": ticks,
                }
            )
    run.records = records  # picked up by benchmarks.run for BENCH_pipeline.json
    return rows_csv


if __name__ == "__main__":
    for row in run(quick=True):
        print(row)
