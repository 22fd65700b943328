"""Where a traced window's time goes on the host, by the engine's own spans.

    python bench/study/host_spans.py --workload olmo-1b.reason --seed 1

One traced run of the cell, as ``bench/run.py --trace 1`` makes it, with
its trace kept long enough to read it again.  Prints the run's result
line, then one line of readings: the device's idle seconds in the window
by the innermost ``serve.*`` span open at the middle of each idle gap
(``bench.*`` where the engine had none open); each span's mean
milliseconds per round; and the round period (window over rounds)
against the mean round execution and the mean of the engine's own host
time per round (``serve.step`` less ``serve.wait``), with what is left.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]


def readings(t) -> dict:
    from bench.harness import trace

    lo, hi = t.window
    ours = [s for s in t.host if s.name.startswith(("serve.", "bench."))]
    idle = dict(trace.Trace(t.ops, t.modules, ours, t.window).breakdown(top=len(ours))["idle_gaps"])
    rounds = sum(s.name == "serve.dispatch" and lo <= s.start < hi for s in t.host)
    if not rounds:
        return {"rounds": 0, "idle_s": idle}
    per_round = collections.Counter()
    for s in t.host:
        if s.name.startswith("serve.") and lo <= s.start < hi:
            per_round[s.name] += (s.end - s.start) / 1e6 / rounds
    executions = [m.end - m.start for ms in t.executions("jit__round").values() for m in ms]
    period = t.window_s * 1e3 / rounds
    round_mean = float(np.mean(executions)) / 1e6
    host_mean = per_round["serve.step"] - per_round["serve.wait"]
    return {"rounds": rounds, "window_s": t.window_s, "period_ms": period,
            "round_exec_ms_mean": round_mean, "host_own_ms_mean": host_mean,
            "remainder_ms": period - round_mean - host_mean,
            "wait_beyond_round_ms": per_round["serve.wait"] - round_mean,
            "span_ms_per_round": dict(per_round), "idle_s": idle}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    from bench.harness import cell, trace

    kept = []
    load = trace.load

    def keep(directory):
        kept.append(load(directory))
        return kept[-1]

    trace.load = keep
    result = cell.run(args.workload, args.seed, args.seconds, True, t_start=T_START)
    print(json.dumps(result), flush=True)
    print(json.dumps({"seed": args.seed, **readings(kept[0])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
