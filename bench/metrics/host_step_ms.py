"""host_step_ms: the engine's own host time per round, the median over the window.

Read from the program's spans: each ``serve.step`` (one
``StreamEngine.step``) that begins in the window, less its ``serve.wait``
child, the time the host sat blocked reading the round back.  What is
left runs serially with the round program: deadlines and admission
planning, prefill, building the round's inputs, dispatch, the token walk.
A program without these spans gives nothing.  Moves ``tokens_per_s``.
"""
import numpy as np


def read(r):
    lo, hi = r.trace.window
    host = r.trace.host
    steps = [s for s in host if s.name == "serve.step" and lo <= s.start < hi]
    waits = [s for s in host if s.name == "serve.wait"]
    own = []
    for step in steps:
        waited = sum(w.end - w.start for w in waits
                     if step.start <= w.start and w.end <= step.end)
        own.append(step.end - step.start - waited)
    return float(np.median(own)) / 1e6 if own else None
