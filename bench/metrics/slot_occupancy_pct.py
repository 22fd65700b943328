"""slot_occupancy_pct: the share of decode slot-steps that produced a real token.

Read from the metadata the program sets on each ``serve.step`` span (one
``StreamEngine.step``) that begins in the window: 100 x the tokens the
round's walk appended over its slot-steps (``max_batch`` x
``round_steps`` when a round ran), summed over the rounds.  A slot idles
when its request retired mid-round and nothing was admitted in its
place.  A program without these spans gives nothing.  Moves
``tokens_per_s``.
"""
from bench.harness import host_meta


def read(r):
    lo, hi = r.trace.window
    steps = [args for s, args in host_meta.spans("serve.step") if lo <= s.start < hi]
    slot_steps = sum(a.get("slot_steps", 0) for a in steps)
    if not slot_steps:
        return None
    return 100.0 * sum(a.get("tokens", 0) for a in steps) / slot_steps
