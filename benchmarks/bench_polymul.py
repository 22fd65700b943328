"""Paper Table 1 / Figure 4: sparse polynomial multiplication.

Rows: stream / stream_big (Lazy, Future×1, Future×2) and the
parallel-collections control list / list_big (times_dense).  Coefficient
footprint via limb count; ``stream_big`` multiplies by 100000000001 as in
the paper.  quick mode uses (1+x+y+z)^6; --paper-scale uses ^20 ×(^20+1)
(the Fateman case the paper cites).
"""
from __future__ import annotations

import jax

from benchmarks._util import csv_row, run_with_devices, timed
from repro.algorithms import polynomial as poly

PAR_SCRIPT = """
import time, jax
from repro.algorithms import polynomial as poly
from repro.core.stream import FutureEvaluator
power, limbs, big, tpc, xch, acc = {power}, {limbs}, {big}, {tpc}, {xch}, {acc}
cap = {cap}
x = poly.fateman_poly(power, cap, limbs, big_factor=big)
y = poly.fateman_poly(power, cap, limbs, big_factor=big)
mesh = jax.make_mesh((jax.device_count(),), ("pod",),
                        axis_types=(jax.sharding.AxisType.Auto,))
ev = FutureEvaluator(mesh, "pod")
fn = jax.jit(lambda x, y: poly.times(x, y, evaluator=ev, num_x_chunks=xch,
                                     terms_per_cell=tpc, acc_capacity=acc))
out = fn(x, y); jax.block_until_ready(out.coeffs)
t0 = time.perf_counter()
out = fn(x, y); jax.block_until_ready(out.coeffs)
print(time.perf_counter() - t0)
"""


def _sizes(power: int, tpc: int, xch: int):
    n_terms = (power + 3) * (power + 2) * (power + 1) // 6
    quantum = tpc * max(2, xch)
    cap = -(-n_terms // quantum) * quantum
    p2 = 2 * power
    acc = 1 << ((p2 + 3) * (p2 + 2) * (p2 + 1) // 6 - 1).bit_length()
    return cap, acc


def run(quick: bool = True, paper_scale: bool = False):
    rows = []
    power = 20 if paper_scale else (6 if quick else 10)
    tpc, xch = 8, 4
    cap, acc = _sizes(power, tpc, xch)
    for name, limbs, big in (("stream", 4, 1), ("stream_big", 12, 100000000001)):
        x = poly.fateman_poly(power, cap, limbs, big_factor=big)
        y = poly.fateman_poly(power, cap, limbs, big_factor=big)
        fn = jax.jit(
            lambda x, y: poly.times(
                x, y, num_x_chunks=xch, terms_per_cell=tpc, acc_capacity=acc
            )
        )
        t_seq, out = timed(fn, x, y, repeats=3)
        if quick:  # correctness only at small scale (oracle is O(n^2) python)
            assert poly.to_dict(out) == poly.reference_product(
                poly.to_dict(x), poly.to_dict(y)
            )
        rows.append(csv_row(f"{name}_seq", t_seq, f"power={power},limbs={limbs}"))
        for nd in (1, 2):
            stdout = run_with_devices(
                PAR_SCRIPT.format(power=power, limbs=limbs, big=big,
                                  tpc=tpc, xch=xch, acc=acc, cap=cap),
                nd,
            )
            rows.append(csv_row(
                f"{name}_par{nd}", float(stdout.strip().splitlines()[-1]),
                f"power={power},limbs={limbs}",
            ))
        # the paper's `list` control: data-parallel dense outer product
        fn_d = jax.jit(lambda x, y: poly.times_dense(x, y, capacity=acc))
        t_dense, _ = timed(fn_d, x, y, repeats=3)
        list_name = "list" if name == "stream" else "list_big"
        rows.append(csv_row(f"{list_name}", t_dense, f"power={power},limbs={limbs}"))
    return rows


if __name__ == "__main__":
    import sys

    for row in run(quick=True, paper_scale="--paper-scale" in sys.argv):
        print(row)
