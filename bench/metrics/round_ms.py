"""round_ms: device time of one decode round, the median over the window.

A round is one execution of the StreamEngine's round program (``_round``:
``round_steps`` decode steps of every microbatch through the Stream
evaluator), read from the trace's program executions.  Moves
``token_gap_p95_ms``: a client receives tokens once per round.
"""
import numpy as np


def read(r):
    durations = [m.end - m.start for ms in r.trace.executions("jit__round").values() for m in ms]
    return float(np.median(durations)) / 1e6 if durations else None
