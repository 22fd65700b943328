"""The comparison that decides ``correct``: served tokens against the plain reference.

Once the window has closed, a sample of the requests it served tokens to
(drawn from the seed, always with the one that was served the most) is run
through the configuration's plain reference (fp32, highest matmul
precision), teacher-forced on each prompt followed by the tokens the
program served.  At each served token the reference's best logit is
compared with its logit for the served token; the number compared is the
widest of those gaps.  A served token the reference also puts first has
a gap of 0; greedy bf16 serving departs from it only at near-ties, by
about its own rounding error.

The control reads the same gap for the token that the reference computed
in fp8 (``control=True``) puts first, at the same positions.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

SAMPLE = 8          # requests compared per run
BLOCK = 256         # positions per logits block


def sample(records, seed: int, k: int = SAMPLE) -> list:
    """Up to ``k`` of ``records``, finished or still running: the one
    served the most tokens, and the rest drawn from the seed.  A request
    of a decode-heavy mix can outlive the window; the tokens it was served
    are compared all the same."""
    done = sorted((r for r in records if r.tokens and not r.failed), key=lambda r: r.index)
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    picked = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False) if rest else []
    return [longest] + [rest[i] for i in sorted(picked)]


def teacher_forced(samples, prompts, length: int, rows: int = SAMPLE):
    """Inputs (prompt then served tokens), targets and a mask of the
    positions whose next token was served, each (rows, length); rows past
    the sample are padding, masked out."""
    r = rows
    inputs = np.zeros((r, length), np.int32)
    targets = np.zeros((r, length), np.int32)
    mask = np.zeros((r, length), bool)
    for i, (prompt, served) in enumerate(zip(prompts, samples)):
        seq = np.concatenate([prompt, np.asarray(served, np.int32)])
        n = len(seq) - 1
        inputs[i, :n] = seq[:n]
        targets[i, :n] = seq[1:]
        mask[i, len(prompt) - 1:n] = True
    return inputs, targets, mask


@partial(jax.jit, static_argnames=("family", "model_items", "control"))
def _readout(w, inputs, targets, *, family, model_items, control):
    import importlib

    fam = importlib.import_module(family)
    model = dict(model_items)
    h = fam.hidden(w, inputs, model)
    hc = fam.hidden(w, inputs, model, control=True) if control else h
    r, s, d = h.shape
    blk = BLOCK if s % BLOCK == 0 else s

    def blocks(a):
        return jnp.moveaxis(a.reshape((r, s // blk, blk) + a.shape[2:]), 1, 0)

    def one(args):
        hb, hcb, tb = args
        lg = fam.logits(w, hb, model)
        best = lg.max(-1)
        at_target = jnp.take_along_axis(lg, tb[..., None], -1)[..., 0]
        if not control:
            return best, at_target, at_target
        top = fam.logits(w, hcb, model, control=True).argmax(-1)
        return best, at_target, jnp.take_along_axis(lg, top[..., None], -1)[..., 0]

    out = jax.lax.map(one, (blocks(h), blocks(hc), blocks(targets)))
    return tuple(jnp.moveaxis(o, 0, 1).reshape(r, s) for o in out)


def logit_gaps(family_module: str, model: dict, weights, inputs, targets, mask,
               *, control: bool = False) -> dict:
    """Widest gap below the reference's best logit: of the served tokens,
    and (``control``) of the fp8 reference's first choices."""
    items = tuple(sorted((k, v) for k, v in model.items()
                         if isinstance(v, (int, float, str, bool))))
    best, served, ctrl = (np.asarray(a) for a in _readout(
        weights, jnp.asarray(inputs), jnp.asarray(targets),
        family=family_module, model_items=items, control=control))
    out = {"served": float(np.max((best - served)[mask], initial=0.0)),
           "tokens": int(mask.sum())}
    if control:
        out["control"] = float(np.max((best - ctrl)[mask], initial=0.0))
    return out
