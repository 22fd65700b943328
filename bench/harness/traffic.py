"""One generator for every traffic mix: requests from a mix's data file and a seed.

A mix file (``bench/traffic/<mix>.json``) holds parameters only:

``loop``
    ``"closed"``: ``clients`` callers (a number, or ``"max_batch"``: one
    per slot of the configuration), each sending its next request when the
    previous one completes.  ``"open"``: requests due on a schedule of
    Poisson arrivals at the cell's ``rate_per_s``, whether or not earlier
    ones have finished.
``prompt_tokens``, ``output_tokens``
    Lognormal lengths: ``median``, ``sigma`` (of the log), clipped to
    ``[min, max]``.  The clips keep prompt plus output within
    ``max_total_tokens`` (the cache holds ``max_total_tokens + 1`` rows).
``start``
    ``"staggered"`` (closed loop): the first request of each client
    enters as if it had been running for a while — a share of its output
    is already in its prompt — so the window opens with every slot busy
    at a spread of ages instead of in lockstep.
``warm_s``
    Open loop: seconds of arrivals before the window opens, so that it
    opens on a running system.
``pool``
    How many (prompt, output) pairs the mix is built from.

Every seed gets the same pool of lengths and gaps: they are lognormal and
exponential quantiles at evenly spaced probabilities, and the seed only
orders them (and draws the token ids).  So two seeds differ in the order
of the same work, not in its amount.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    """One request of the mix: ``prompt`` token ids and ``max_new`` tokens."""
    index: int
    prompt: np.ndarray
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_pool(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of the clipped lognormal."""
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    lengths = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(lengths, spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(rate_per_s: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at evenly spaced quantiles of Exp(rate)."""
    return -np.log1p(-_quantiles(n)) / rate_per_s


class Traffic:
    """The requests of one mix under one seed.

    ``vocab`` bounds the token ids drawn for prompts (ids 1..vocab-1).
    ``clients`` resolves ``"max_batch"``; ``rate_per_s`` is the cell's
    offered rate (open loop only).
    """

    def __init__(self, mix: dict, seed: int, *, vocab: int, max_batch: int,
                 rate_per_s: float | None = None):
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.loop = mix["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"loop must be 'closed' or 'open', not {self.loop!r}")
        n = int(mix["pool"])
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 0]))
        prompts = rng.permutation(lognormal_pool(mix["prompt_tokens"], n))
        outputs = rng.permutation(lognormal_pool(mix["output_tokens"], n))
        if mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] > mix["max_total_tokens"]:
            raise ValueError("the length clips exceed max_total_tokens")
        self.prompt_lens = prompts
        self.output_lens = outputs
        self.pool = n
        if self.loop == "closed":
            clients = mix["clients"]
            self.clients = max_batch if clients == "max_batch" else int(clients)
            ages = rng.permutation(_quantiles(self.clients))
            self.start_age = ages if mix.get("start") == "staggered" else np.zeros(self.clients)
        else:
            if not rate_per_s:
                raise ValueError("an open-loop mix needs the cell's rate_per_s")
            self.rate_per_s = float(rate_per_s)
            gaps = rng.permutation(exponential_gaps(self.rate_per_s, n))
            self.arrivals = np.concatenate([[0.0], np.cumsum(gaps)])
            self.warm_s = float(mix.get("warm_s", 0.0))

    def request(self, i: int) -> Request:
        """The i-th request of the mix (pool entries repeat after ``pool``)."""
        j = i % self.pool
        plen, out = int(self.prompt_lens[j]), int(self.output_lens[j])
        if self.loop == "closed" and i < self.clients and self.start_age[i] > 0:
            # A request already running for a share of its output: those
            # tokens sit in its prompt, the rest is still to come.
            done = int(math.floor(self.start_age[i] * out))
            plen, out = plen + done, out - done
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 1, i]))
        prompt = rng.integers(1, self.vocab, size=plen, dtype=np.int32)
        return Request(index=i, prompt=prompt, max_new=out)

    def due(self, i: int) -> float:
        """Open loop: when request ``i`` is due, in seconds after the first
        arrival of the schedule (``warm_s`` before the window opens)."""
        whole, part = divmod(i, self.pool)
        return float(whole * self.arrivals[-1] + self.arrivals[part])
