"""Served tokens against the reference by prompt length: whole prefill
chunks against a ragged tail.

    python bench/study/prefill_tail.py --workload mamba2-1.3b.reason --seeds 1 2 3 [--root DIR]

For each seed: weights from the seed, the cell's engine, and one request
at a time for each prompt length in ``--lengths`` (default: one and two
chunks, and one and two chunks plus 1 and minus 1 token); prints the
widest logit gap of its served tokens below the fp32 reference.  Runs on
the CPU too (``--cpu``) at the widths of the root's configuration.
"""
import argparse
import json
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--lengths", type=int, nargs="*")
    ap.add_argument("--new", type=int, default=32)
    ap.add_argument("--root", default=str(CHECKOUT / "bench"))
    args = ap.parse_args(argv)
    import jax
    import numpy as np

    from bench.harness import cell, check, spec

    c = spec.Cell(args.workload, Path(args.root))
    serving = c.config["serving"]
    ck = serving["prefill_chunk"]
    lengths = args.lengths or [ck, 2 * ck, ck + 1, 2 * ck - 1, 2 * ck + 1]
    for seed in args.seeds:
        w = cell.weights(c, seed)
        engine = cell.build(c, w, jax.devices()[:1])
        rng = np.random.default_rng(seed)
        gaps = {}
        for n in lengths:
            prompt = rng.integers(1, int(c.config["model"]["vocab_size"]), size=n, dtype=np.int32)
            req = engine.submit(prompt, args.new)
            while not req.done:
                engine.step()
            inputs, targets, mask = check.teacher_forced([req.out_tokens], [prompt],
                                                         serving["max_len"], rows=1)
            gaps[n] = check.logit_gaps(c.family.__name__, c.config["model"], w,
                                       inputs, targets, mask)["served"]
        print(json.dumps({"seed": seed, "gap_by_prompt_length": gaps}), flush=True)
        del engine, w
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
