"""The serving engine's own spans and counters, and the readers built on them.

A tiny ``StreamEngine`` serves a few requests under the profiler inside a
``bench.window`` span; the trace is read back through ``trace.load`` and
``host_meta.spans``, the readers the chip's trace goes through.  The
metric readers are also checked by hand on constructed spans."""
import dataclasses
import types

import jax
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from bench.harness import host_meta, spec, trace
from bench.harness.trace import WINDOW, Span, Trace
from repro.configs.base import DecodePipelineConfig
from repro.configs.registry import get_config, smoke_config
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serve.engine import ServeConfig, StreamEngine

PARENT = {
    "serve.step": WINDOW,
    "serve.admit": "serve.step",
    "serve.prefill": "serve.admit",
    "serve.inputs": "serve.step",
    "serve.dispatch": "serve.step",
    "serve.wait": "serve.step",
    "serve.walk": "serve.step",
}
ROUND = ("serve.inputs", "serve.dispatch", "serve.wait", "serve.walk")
ROUND_STEPS, MAX_BATCH = 3, 4
MS = 1_000_000  # ns


def metric(name):
    return spec.Cell("olmo-1b.reason").reader(name)


def _parent(span, spans):
    """The innermost of ``spans`` that holds ``span``."""
    holders = [p for p in spans if p is not span and p.start <= span.start and span.end <= p.end]
    return min(holders, key=lambda p: p.end - p.start) if holders else None


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    sc = smoke_config(get_config("olmo-1b")).with_overrides(num_layers=4)
    params = init_params(jax.random.PRNGKey(0), T.model_layout(sc))
    scfg = ServeConfig(max_batch=MAX_BATCH, max_len=64, prefill_chunk=4, max_new_tokens=6)
    eng = StreamEngine(params, sc, scfg, DecodePipelineConfig(
        num_cells=2, microbatches=2, round_steps=ROUND_STEPS, admit_per_round=2))
    for n in (4, 5):  # both prefill programs and the round, outside the trace
        eng.submit(np.arange(1, n + 1), 2)
    eng.run_until_drained()
    before = dataclasses.replace(eng.counters)
    prompts = [np.array([5, 9, 2, 7, 11]), np.array([3, 1, 4]), np.array([2] * 6),
               np.array([8, 8]), np.array([1, 2, 3, 4]), np.array([7])]
    budgets = [6, 3, 5, 1, 6, 4]
    path = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=opts)
    with TraceAnnotation(WINDOW):
        reqs = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
        eng.run_until_drained()
    jax.profiler.stop_trace()
    return eng, before, reqs, trace.load(path), path


def test_spans_nest_as_the_engine_calls(served):
    eng, before, reqs, t, path = served
    ours = [s for s in t.host if s.name in PARENT or s.name == WINDOW]
    assert {s.name for s in ours} == set(PARENT) | {WINDOW}
    for s in ours:
        if s.name != WINDOW:
            assert _parent(s, ours).name == PARENT[s.name], s
    steps = host_meta.spans("serve.step", path)
    assert [s for s, _ in steps] == [s for s in ours if s.name == "serve.step"]
    assert [a["step_num"] for _, a in steps] == sorted(a["step_num"] for _, a in steps)
    for step, args in steps:
        inside = [s.name for s in ours if _parent(s, ours) == step]
        assert inside.count("serve.admit") == 1
        ran = args["slot_steps"] > 0
        assert all(inside.count(name) == ran for name in ROUND), inside


def test_prefill_spans_name_their_request(served):
    eng, before, reqs, t, path = served
    prefills = [a for _, a in host_meta.spans("serve.prefill", path)]
    assert sorted(a["uid"] for a in prefills) == [r.uid for r in reqs]
    length = {r.uid: len(r.prompt) for r in reqs}
    for a in prefills:
        assert a["prompt_len"] == length[a["uid"]]
        assert a["queue_ms"] >= 0
    assert len(prefills) == eng.counters.prefills - before.prefills


def test_step_metadata_is_the_counters_delta(served):
    eng, before, reqs, t, path = served
    steps = [a for _, a in host_meta.spans("serve.step", path)]
    tokens = sum(a["tokens"] for a in steps)
    slot_steps = sum(a["slot_steps"] for a in steps)
    rounds = eng.counters.rounds - before.rounds
    assert tokens == eng.counters.tokens - before.tokens
    # Each request's first token comes from its prefill, the rest from the walk.
    assert tokens == sum(len(r.out_tokens) - 1 for r in reqs)
    assert slot_steps == eng.counters.slot_steps - before.slot_steps == rounds * ROUND_STEPS * MAX_BATCH
    assert rounds == sum(a["slot_steps"] > 0 for a in steps) > 0


def test_readers_on_the_recorded_trace(served, monkeypatch):
    """Both readers read the trace the engine wrote; with none of its
    spans, as from a program without them, they give nothing."""
    eng, before, reqs, t, path = served
    monkeypatch.setattr(host_meta, "TRACE_DIR", path)
    r = types.SimpleNamespace(trace=t)
    steps = [a for _, a in host_meta.spans("serve.step", path)]
    assert metric("slot_occupancy_pct")(r) == pytest.approx(
        100 * sum(a["tokens"] for a in steps) / sum(a["slot_steps"] for a in steps))
    assert 0 < metric("host_step_ms")(r) < 1e3 * t.window_s
    bare = types.SimpleNamespace(trace=Trace(t.ops, t.modules, [s for s in t.host if s.name == WINDOW], t.window))
    assert metric("host_step_ms")(bare) is None
    monkeypatch.setattr(host_meta, "TRACE_DIR", path / "none")
    assert metric("slot_occupancy_pct")(r) is None


def test_host_step_ms_by_hand():
    """A round's own host time is its step span less the wait inside it;
    a step that ran no round has no wait; a step that begins after the
    window does not count."""
    host = [
        Span(0, 1000 * MS, WINDOW),
        Span(0, 100 * MS, "serve.step"), Span(30 * MS, 80 * MS, "serve.wait"),
        Span(100 * MS, 300 * MS, "serve.step"), Span(150 * MS, 160 * MS, "serve.wait"),
        Span(300 * MS, 340 * MS, "serve.step"),
        Span(1000 * MS, 1500 * MS, "serve.step"), Span(1100 * MS, 1200 * MS, "serve.wait"),
    ]
    r = types.SimpleNamespace(trace=Trace({}, {}, host, (0, 1000 * MS)))
    # own times 50, 190 and 40 ms
    assert metric("host_step_ms")(r) == pytest.approx(50.0)
    r.trace.host = host[:1]
    assert metric("host_step_ms")(r) is None


def test_slot_occupancy_pct_by_hand(monkeypatch):
    def step(start, tokens, slot_steps):
        return (Span(start * MS, (start + 10) * MS, "serve.step"),
                {"step_num": start, "tokens": tokens, "slot_steps": slot_steps})

    steps = [step(0, 90, 96), step(20, 96, 96), step(40, 0, 0), step(1000, 0, 96)]
    r = types.SimpleNamespace(trace=Trace({}, {}, [], (0, 1000 * MS)))
    for given, want in [(steps, 100 * 186 / 192), (steps[2:], None), ([], None)]:
        monkeypatch.setattr(host_meta, "spans", lambda name, directory=None, given=given: given)
        got = metric("slot_occupancy_pct")(r)
        assert got == (pytest.approx(want) if want else None)
