"""Paper §6: sparse polynomial multiplication as a stream computation.

Reproduces the paper's experiment shape: ``stream`` (small coefficients)
vs ``stream_big`` (coefficients × 100000000001) under the Lazy and Future
evaluators, plus the data-parallel ``list`` control.

Run (2 virtual devices ≈ the paper's hyperthreaded Atom):
    XLA_FLAGS=--xla_force_host_platform_device_count=2 \
        PYTHONPATH=src python examples/polynomial_multiplication.py --power 6
"""
import argparse
import time

import jax
import numpy as np

from repro.algorithms import polynomial as poly
from repro.core import FutureEvaluator


def timed(fn, *args, repeats=1, **kwargs):
    out = fn(*args, **kwargs)  # compile
    jax.block_until_ready(out.coeffs)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args, **kwargs)
        jax.block_until_ready(out.coeffs)
    return out, (time.perf_counter() - t0) / repeats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--power", type=int, default=6, help="k in (1+x+y+z)^k")
    ap.add_argument("--terms-per-cell", type=int, default=8)
    ap.add_argument("--x-chunks", type=int, default=4)
    args = ap.parse_args()

    n_terms = (args.power + 3) * (args.power + 2) * (args.power + 1) // 6
    # capacity must be divisible by terms_per_cell × device_count (cells)
    # and by x_chunks (items).
    quantum = args.terms_per_cell * max(jax.device_count(), args.x_chunks)
    cap = -(-n_terms // quantum) * quantum
    p2 = args.power * 2
    acc_cap = 1 << ((p2 + 3) * (p2 + 2) * (p2 + 1) // 6 - 1).bit_length()
    print(f"(1+x+y+z)^{args.power}: {n_terms} terms (cap {cap}) -> product capacity {acc_cap}")

    for tag, limbs, big in (("stream", 4, 1), ("stream_big", 12, 100000000001)):
        x = poly.fateman_poly(args.power, cap, limbs, big_factor=big)
        y = poly.fateman_poly(args.power, cap, limbs, big_factor=big)
        ref = poly.reference_product(poly.to_dict(x), poly.to_dict(y))

        jit_times = jax.jit(
            lambda x, y: poly.times(
                x, y,
                num_x_chunks=args.x_chunks,
                terms_per_cell=args.terms_per_cell,
                acc_capacity=acc_cap,
            )
        )
        out, seq = timed(jit_times, x, y)
        assert poly.to_dict(out) == ref, "stream/lazy result mismatch"

        if jax.device_count() >= 2:
            mesh = jax.make_mesh(
                (jax.device_count(),), ("pod",),
                axis_types=(jax.sharding.AxisType.Auto,),
            )
            fut = FutureEvaluator(mesh, "pod")
            jit_par = jax.jit(
                lambda x, y: poly.times(
                    x, y, evaluator=fut,
                    num_x_chunks=args.x_chunks,
                    terms_per_cell=args.terms_per_cell,
                    acc_capacity=acc_cap,
                )
            )
            outp, par = timed(jit_par, x, y)
            assert poly.to_dict(outp) == ref, "stream/future result mismatch"
        else:
            par = float("nan")

        jit_dense = jax.jit(lambda x, y: poly.times_dense(x, y, capacity=acc_cap))
        outd, dense = timed(jit_dense, x, y)
        assert poly.to_dict(outd) == ref, "list result mismatch"

        print(
            f"{tag:12s} seq(Lazy) {seq*1e3:8.1f} ms   "
            f"par(Future,{jax.device_count()}dev) {par*1e3:8.1f} ms   "
            f"list(dense) {dense*1e3:8.1f} ms"
        )


if __name__ == "__main__":
    main()
