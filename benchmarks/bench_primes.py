"""Paper Table 1 / Figure 3: the prime sieve, seq vs par(1) vs par(2).

``primes`` and ``primes_x3`` follow the paper (limits 20000 / 60000);
``quick`` mode shrinks the limits so the full harness stays snappy on one
core.  seq = Lazy monad in-process; par(N) = Future monad in a fresh
process with N virtual devices (the paper's 'available processors').
"""
from __future__ import annotations

import jax

from benchmarks._util import csv_row, run_with_devices, timed
from repro.algorithms import sieve

PAR_SCRIPT = """
import time, numpy as np, jax, jax.numpy as jnp
from repro.algorithms import sieve
from repro.core.stream import FutureEvaluator
limit, block, ppc, cells = {limit}, {block}, {ppc}, {cells}
mesh = jax.make_mesh((jax.device_count(),), ("pod",),
                        axis_types=(jax.sharding.AxisType.Auto,))
ev = FutureEvaluator(mesh, "pod")
run = jax.jit(lambda items_unused: 0)  # warm placeholder
p, c = sieve.run_sieve(limit, block_size=block, primes_per_cell=ppc,
                       num_cells=cells, evaluator=ev)  # compile
jax.block_until_ready(p)
t0 = time.perf_counter()
p, c = sieve.run_sieve(limit, block_size=block, primes_per_cell=ppc,
                       num_cells=cells, evaluator=ev)
jax.block_until_ready(p)
print(time.perf_counter() - t0)
ref = sieve.reference_primes(limit)
pn = np.asarray(p)
assert int(c) == len(ref) and np.array_equal(pn[pn>0], ref), "wrong primes"
"""


def _cells(limit: int, ppc: int, devices: int) -> int:
    bound = int(sieve._pi_upper_bound(limit))
    cells = -(-bound // ppc)
    return -(-cells // devices) * devices  # divisible by device count


def run(quick: bool = True):
    rows = []
    cases = [("primes", 2000 if quick else 20000),
             ("primes_x3", 6000 if quick else 60000)]
    block, ppc = 256, 16
    for name, limit in cases:
        cells = _cells(limit, ppc, 2)
        seq_fn = lambda: sieve.run_sieve(
            limit, block_size=block, primes_per_cell=ppc, num_cells=cells
        )[0]
        t_seq, primes = timed(seq_fn, repeats=3)
        import numpy as np

        ref = sieve.reference_primes(limit)
        pn = np.asarray(primes)
        assert np.array_equal(pn[pn > 0], ref)
        rows.append(csv_row(f"{name}_seq", t_seq, f"limit={limit}"))
        for nd in (1, 2):
            out = run_with_devices(
                PAR_SCRIPT.format(limit=limit, block=block, ppc=ppc, cells=cells),
                nd,
            )
            t_par = float(out.strip().splitlines()[-1])
            rows.append(csv_row(f"{name}_par{nd}", t_par, f"limit={limit}"))
    return rows


if __name__ == "__main__":
    for row in run(quick=True):
        print(row)
