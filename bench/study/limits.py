"""Readings for a cell's ``logit_gap_limit``: the program's gap and the fp8
control's, seed after seed, in one process.

    python bench/study/limits.py --workload olmo-1b.reason --seconds 51 --seeds 1 2 3

Each seed is one whole run of the cell, as ``bench/run.py`` makes it,
with the fp8 control compared in the program's place on the same sample:
the run's ``correct`` is the control's, and has to come out false.  The
program's own gap is read beside it.  The lower reading is the largest
program gap over the seeds, the upper the smallest control gap.  The
benchmark's own runs never run the control.  Exits nonzero if the
control came out correct on any seed.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--root", default=str(CHECKOUT / "bench"))
    args = ap.parse_args(argv)
    from bench.harness import cell

    served, control, control_correct = [], [], []
    for seed in args.seeds:
        r = cell.run(args.workload, seed, args.seconds, False, t_start=time.perf_counter(),
                     root=Path(args.root), control=True)
        served.append(r.get("program_gap"))
        control.append(r["check"]["logit_gap"]["value"])
        control_correct.append(r["correct"])
        print(json.dumps({"seed": seed, **r}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds, "served": served,
                      "control": control, "control_correct": control_correct,
                      "lower": max(g for g in served if g is not None),
                      "upper": min(g for g in control if g is not None)}))
    return 1 if any(control_correct) else 0


if __name__ == "__main__":
    sys.exit(main())
