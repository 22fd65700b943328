"""Find an open-loop cell's knee: the highest offered rate whose backlog
does not grow over a window.

    python bench/study/sweep.py --root <dir>/bench --workload olmo-1b.docqa \
        --seconds 30 --rates 0.3 0.45 0.6 0.75 0.9 1.05

``--root`` names a benchmark root (a ``BENCHMARK.json`` beside it) whose
cell uses the open-loop mix; the rate comes from ``--rates``, so the cell
needs no ``rate_per_s`` yet.  One process, one engine: each rate runs the cell's mix at that rate for
``--seconds`` (after the mix's ``warm_s``), then drains.  For each rate it
prints the queue at the window's end, the requests completed per second,
tokens/s and the TTFT tail.  The cell's ``rate_per_s`` is then set at
four fifths of the knee.
"""
import argparse
import json
import sys
from pathlib import Path

import numpy as np

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--root", default=str(CHECKOUT / "bench"))
    args = ap.parse_args(argv)
    import jax

    from bench.harness import cell, serve, spec
    from bench.harness.traffic import Traffic

    c = spec.Cell(args.workload, Path(args.root))
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT / ".jax_cache"))
    engine = cell.build(c, cell.weights(c, args.seed), jax.devices()[:c.chips])
    serving = c.config["serving"]
    vocab = int(c.config["model"]["vocab_size"])
    session = serve.Session(engine)
    serve.warm_up(session, vocab=vocab, prefill_chunk=serving["prefill_chunk"],
                  admit_per_round=engine.pcfg.admit_per_round)
    for rate in args.rates:
        traffic = Traffic(c.mix, args.seed, vocab=vocab, max_batch=serving["max_batch"],
                          rate_per_s=rate)
        session = serve.Session(engine)
        seen = {}
        win = serve.run_open(session, traffic, args.seconds,
                             on_close=lambda: seen.update(queue=len(engine.queue)))
        done = [r for r in win.records if r.done_t is not None and win.t0 < r.done_t <= win.t1]
        m = serve.client_metrics(win)
        ttft = [r.first_t - r.due for r in win.attempted if r.first_t is not None]
        print(json.dumps({
            "rate_per_s": rate, "window_s": win.seconds, "due": len(win.attempted),
            "completed_per_s": len(done) / win.seconds, "queue_at_end": seen["queue"],
            "tokens_per_s": m["tokens_per_s"], "token_gap_p95_ms": m["token_gap_p95_ms"],
            "ttft_p50_ms": 1e3 * float(np.median(ttft)), "ttft_p95_ms": m.get("ttft_p95_ms"),
        }), flush=True)
        while not session.idle():
            session.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
