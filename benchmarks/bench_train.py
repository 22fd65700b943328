"""Training step time: autodiff vs planned backward, per schedule x M.

The tentpole claim of the planned backward is *scheduling*, not raw
speed: the combined plan (repro.core.schedules.build_combined_plan)
makes the backward first-class tick work and bounds 1F1B's stash at
min(S, M) — but the two paths must also stay in the same wall-clock
ballpark, and neither may silently regress.  This suite times one
jitted ``value_and_grad`` step of the same 16-cell model under every
(schedule, backward, M) cell, paired inside one subprocess exactly like
bench_pipeline (machine drift hits every cell equally; see that
module's docstring for the pairing rationale).

``benchmarks/run.py --suite train`` persists the records to
BENCH_train.json; ``--check`` then diffs a fresh sweep against it and
fails on >tolerance wall-clock regression per cell — the planned
backward is gated the day it lands.  Each record also carries the
plan-level peak-stash counts (planned vs autodiff) so the memory story
is in the artifact, not just the test suite.
"""
from __future__ import annotations

from benchmarks._util import csv_row, run_with_devices
from repro.core.chunking import schedule_peak_items

# (schedule, devices, interleave): the two true-1F1B-relevant layouts.
SWEEP = [
    ("gpipe", 4, 1),
    ("one_f_one_b", 4, 1),
]
BACKWARDS = ("autodiff", "planned")

SCRIPT = """
import time, jax, jax.numpy as jnp
from repro.core import StreamProgram, FutureEvaluator, evaluate
M, D, ROWS = {micro}, {dim}, {rows}
CELLS = 16
W = jax.random.normal(jax.random.PRNGKey(0), (CELLS, D, D)) / D**0.5
items = jax.random.normal(jax.random.PRNGKey(1), (M, ROWS // M, D))
def loss(W, items, ev):
    prog = StreamProgram(lambda w, x: (w, jnp.tanh(x @ w)), W, CELLS,
                         mutable_state=False, remat=True)
    return jnp.sum(evaluate(prog, items, ev)[1] ** 2)
runs = {{}}
for name, ndev, v in {sweep!r}:
    mesh = jax.make_mesh((ndev,), ("pod",), devices=jax.devices()[:ndev])
    for bwd in {backwards!r}:
        ev = FutureEvaluator(mesh, "pod", schedule=name, interleave=v,
                             backward=bwd)
        fn = jax.jit(jax.value_and_grad(
            lambda W, ev=ev: loss(W, items, ev)))
        jax.block_until_ready(fn(W))  # compile
        runs[(name, bwd)] = fn
best = {{k: 1e9 for k in runs}}
for _ in range(5):  # interleave repeats across cells: paired timing
    for k, fn in runs.items():
        t0 = time.perf_counter()
        jax.block_until_ready(fn(W))
        best[k] = min(best[k], time.perf_counter() - t0)
for (name, bwd), t in best.items():
    print(name, bwd, t)
"""


def run(quick: bool = True):
    rows_csv, records = [], []
    dim, rows = (128, 2048) if quick else (256, 4096)
    for micro in (4, 8):
        out = run_with_devices(
            SCRIPT.format(
                micro=micro, dim=dim, rows=rows, sweep=SWEEP,
                backwards=BACKWARDS,
            ),
            4,
        )
        timings = {}
        for line in out.strip().splitlines()[-len(SWEEP) * len(BACKWARDS):]:
            name, bwd, t = line.split()
            timings[(name, bwd)] = float(t)
        for schedule, ndev, interleave in SWEEP:
            for bwd in BACKWARDS:
                t = timings[(schedule, bwd)]
                peak = schedule_peak_items(
                    schedule, ndev, micro, interleave, backward=bwd
                )
                rows_csv.append(
                    csv_row(
                        f"train_{schedule}_{bwd}_m{micro}",
                        t,
                        f"peak_stash={peak}/{micro},devices={ndev}",
                    )
                )
                records.append(
                    {
                        "schedule": schedule,
                        "backward": bwd,
                        "devices": ndev,
                        "interleave": interleave,
                        "num_microbatches": micro,
                        "dim": dim,
                        "rows": rows,
                        "measured_seconds": t,
                        "peak_stash_items": peak,
                    }
                )
    run.records = records  # picked up by benchmarks.run for BENCH_train.json
    return rows_csv


if __name__ == "__main__":
    for row in run(quick=True):
        print(row)
