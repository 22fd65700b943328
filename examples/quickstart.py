"""Quickstart: the Stream combinator algebra with a substitutable monad.

Builds stream programs with the algebra — ``source . map . through .
zip . collect`` — and runs them under the Lazy monad (sequential) and,
if more than one JAX device is available, under the Future monad
(pipelined across devices), demonstrating the paper's monad
substitution: the program text does not change, only the evaluator.

Run:
    PYTHONPATH=src python examples/quickstart.py
    # pipelined across 4 virtual devices:
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    FutureEvaluator,
    LazyEvaluator,
    Stream,
    bubble_fraction,
    optimal_num_chunks,
)
from repro.algorithms import sieve


def main():
    # --- 1. A stream program, written with combinators ---------------------
    # Cell s multiplies the flowing item by a per-cell weight and bumps a
    # per-cell counter (mutable state, like the sieve's claimed primes).
    def cell_fn(state, item):
        weight, count = state
        return (weight, count + 1), jnp.tanh(item * weight)

    num_cells, num_items = 8, 16
    states = (jnp.linspace(0.5, 1.5, num_cells), jnp.zeros(num_cells, jnp.int32))
    items = jnp.linspace(-1.0, 1.0, num_items * 4).reshape(num_items, 4)

    program = (
        Stream.source(items)
        .map(lambda x: x * 2.0)          # stateless: fused at lowering
        .through(cell_fn, states)        # the chain of dependent cells
    )

    lazy = program.collect(LazyEvaluator())
    print("lazy:   outs[0] =", np.asarray(lazy.items[0]))

    if jax.device_count() >= 2 and num_cells % jax.device_count() == 0:
        mesh = jax.make_mesh(
            (jax.device_count(),), ("pod",),
            axis_types=(jax.sharding.AxisType.Auto,),
        )
        fut = program.collect(FutureEvaluator(mesh, "pod"))
        print("future: outs[0] =", np.asarray(fut.items[0]))
        print("lazy == future:", bool(jnp.all(lazy.items == fut.items)))
        print(
            f"bubble fraction (S={jax.device_count()}, M={num_items}):",
            bubble_fraction(jax.device_count(), num_items),
        )

        # --- 1b. Multi-source: zip a second stream in ----------------------
        # Each source gets its own feed carousel; neither is replicated.
        other = jnp.linspace(0.0, 1.0, num_items * 4).reshape(num_items, 4)
        zipped = (
            Stream.source(items)
            .zip(Stream.source(other), lambda a, b: a + 0.25 * b)
            .through(cell_fn, states)
        )
        zl = zipped.collect(LazyEvaluator())
        zf = zipped.collect(FutureEvaluator(mesh, "pod"))
        print("zip: lazy == future:", bool(jnp.all(zl.items == zf.items)))
    else:
        print("(single device: set XLA_FLAGS=--xla_force_host_platform_"
              "device_count=4 to see the Future evaluator)")

    # --- 1c. Feedback: a self-feeding stream (the serving decode shape) ----
    # Item b re-enters as emit(item b - lag): this is a decode loop —
    # the emitted token is the next step's input, per-cell state is the
    # KV cache, and `lag` in-flight items keep a pipeline busy.
    lag = 4
    fb = (
        Stream.feedback(items[:lag], num_items=12, emit=lambda x: x * 0.5 + 0.1)
        .through(cell_fn, states)
    )
    fb_lazy = fb.collect(LazyEvaluator())
    print("feedback: outs[-1] =", np.asarray(fb_lazy.items[-1]))

    # --- 2. The paper's §7 chunking rule -----------------------------------
    print(
        "optimal #chunks for work=1s, 4 stages, 1ms overhead:",
        optimal_num_chunks(1.0, 4, 1e-3),
    )

    # --- 3. The paper's prime sieve (§5): source . mask . through ----------
    primes, count = sieve.run_sieve(200, block_size=64, primes_per_cell=4)
    primes = np.asarray(primes)
    print(f"primes < 200 ({int(count)}):", primes[primes > 0])

    # --- 4. Stream-shaped serving: decode as a feedback program ------------
    # The serving engine is the same construct at production scale: the
    # transformer's layer groups are the cells (each owning its KV-cache
    # shard as per-cell state), in-flight request microbatches are the
    # items, and the emit (logits -> sample -> re-embed) closes the
    # loop.  StreamEngine runs it under LazyEvaluator here; give it a
    # mesh and it pipelines across devices (gpipe / interleaved),
    # bit-identically.
    from repro.configs.base import DecodePipelineConfig
    from repro.configs.registry import get_config, smoke_config
    from repro.models import transformer as T
    from repro.models.params import init_params
    from repro.serve.engine import ServeConfig, StreamEngine

    cfg = smoke_config(get_config("olmo-1b")).with_overrides(num_layers=4)
    params = init_params(jax.random.PRNGKey(0), T.model_layout(cfg))
    eng = StreamEngine(
        params, cfg,
        ServeConfig(max_batch=4, max_len=64, prefill_chunk=8, max_new_tokens=6),
        DecodePipelineConfig(num_cells=4, microbatches=2, round_steps=4),
        mesh=None,  # pass a 1-axis mesh to pipeline the cells across it
    )
    reqs = [eng.submit(np.array([5, 9, 2, 7])), eng.submit(np.array([3, 1]))]
    eng.run_until_drained()
    for r in reqs:
        print(f"served req {r.uid}: {r.out_tokens}")


if __name__ == "__main__":
    main()
