"""The traffic generator: deterministic per seed, lengths as each mix declares."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.harness.traffic import Traffic, exponential_gaps, lognormal_pool

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def make(name, seed):
    return Traffic(mix(name), seed, vocab=50304, max_batch=24, rate_per_s=8.0)


@pytest.mark.parametrize("name", ["reason", "docqa"])
def test_same_seed_same_requests(name):
    a, b = make(name, 2**31 + 17), make(name, 2**31 + 17)
    for i in (0, 1, 5, 300, 5000):
        ra, rb = a.request(i), b.request(i)
        assert ra.max_new == rb.max_new
        assert np.array_equal(ra.prompt, rb.prompt)
        if name == "docqa":
            assert a.due(i) == b.due(i)


@pytest.mark.parametrize("name", ["reason", "docqa"])
def test_seeds_reorder_one_pool(name):
    """Two seeds give the same lengths in another order, and other tokens."""
    a, b = make(name, 1), make(name, 2)
    assert sorted(a.prompt_lens) == sorted(b.prompt_lens)
    assert sorted(a.output_lens) == sorted(b.output_lens)
    assert not np.array_equal(a.prompt_lens, b.prompt_lens)
    assert not np.array_equal(a.request(50).prompt[:16], b.request(50).prompt[:16])


@pytest.mark.parametrize("name", ["reason", "docqa"])
def test_declared_clips_and_medians(name):
    m = mix(name)
    t = make(name, 7)
    for key, lens in (("prompt_tokens", t.prompt_lens), ("output_tokens", None)):
        spec = m[key]
        pool = lognormal_pool(spec, m["pool"])
        assert pool.min() >= spec["min"] and pool.max() <= spec["max"]
        assert abs(np.median(pool) - spec["median"]) <= 1
    assert np.all(t.prompt_lens + t.output_lens <= m["max_total_tokens"])
    assert np.all(t.output_lens >= 1)


def test_reason_starts_staggered():
    """Each client's first request carries a share of its output in its
    prompt; the shares are spread evenly over (0, 1)."""
    t = make("reason", 11)
    assert t.clients == 24
    firsts = [t.request(i) for i in range(t.clients)]
    whole = [(int(t.prompt_lens[i]), int(t.output_lens[i])) for i in range(t.clients)]
    for r, (p, o) in zip(firsts, whole):
        assert len(r.prompt) + r.max_new == p + o
        assert r.max_new >= 1
    assert sorted(np.round(t.start_age * t.clients * 2).astype(int)) == list(range(1, 48, 2))


def test_open_loop_rate():
    gaps = exponential_gaps(8.0, 4096)
    assert abs(gaps.mean() - 1 / 8.0) < 0.01 / 8.0
    t = make("docqa", 3)
    assert t.due(0) == 0.0
    assert t.due(4096) == pytest.approx(t.arrivals[-1])
    assert np.all(np.diff([t.due(i) for i in range(100)]) >= 0)
    assert t.warm_s == mix("docqa")["warm_s"]


def test_open_loop_needs_a_rate():
    with pytest.raises(ValueError):
        Traffic(mix("docqa"), 1, vocab=100, max_batch=8)
