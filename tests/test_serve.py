"""Serving engines: continuous batching correctness.

Single-device: the sequential reference ``Engine`` against a full
forward, retirement edge cases, per-request RNG, and the
``StreamEngine`` (LazyEvaluator — the same Stream.feedback round
program, layer-sequential).  The pipelined FutureEvaluator bit-identity
gate runs in test_serve_pipeline.py (multidevice marker).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import DecodePipelineConfig
from repro.configs.registry import get_config, smoke_config
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serve.engine import Engine, Request, ServeConfig, StreamEngine


@pytest.fixture(scope="module")
def small_model():
    rng = jax.random.PRNGKey(0)
    sc = smoke_config(get_config("olmo-1b"))
    params = init_params(rng, T.model_layout(sc))
    return sc, params


@pytest.fixture(scope="module")
def cell_model():
    """4 layer groups so the decode chain splits into cells."""
    rng = jax.random.PRNGKey(0)
    sc = smoke_config(get_config("olmo-1b")).with_overrides(num_layers=4)
    params = init_params(rng, T.model_layout(sc))
    return sc, params


def greedy_ref(params, sc, prompt, n_new):
    toks = list(prompt)
    for _ in range(n_new):
        lg, _, _ = T.forward(params, sc, tokens=jnp.asarray([toks]), attn_impl="dense")
        toks.append(int(jnp.argmax(lg[0, -1])))
    return toks[len(prompt):]


class TestEngine:
    def test_greedy_matches_full_forward(self, small_model):
        sc, params = small_model
        eng = Engine(params, sc, ServeConfig(
            max_batch=3, max_len=64, prefill_chunk=4, max_new_tokens=5))
        prompts = [np.array([5, 9, 2, 7, 11]), np.array([3, 1, 4]), np.array([2] * 6)]
        reqs = [eng.submit(p) for p in prompts]
        done = eng.run_until_drained()
        assert len(done) == 3
        for req, p in zip(reqs, prompts):
            assert req.out_tokens == greedy_ref(params, sc, p, 5)

    def test_more_requests_than_slots(self, small_model):
        sc, params = small_model
        eng = Engine(params, sc, ServeConfig(
            max_batch=2, max_len=64, prefill_chunk=4, max_new_tokens=3))
        prompts = [np.array([i + 1, i + 2, i + 3]) for i in range(5)]
        reqs = [eng.submit(p) for p in prompts]
        eng.run_until_drained()
        for req, p in zip(reqs, prompts):
            assert req.done
            assert req.out_tokens == greedy_ref(params, sc, p, 3)

    def test_staggered_arrivals(self, small_model):
        """Requests admitted mid-decode must not disturb running slots."""
        sc, params = small_model
        eng = Engine(params, sc, ServeConfig(
            max_batch=2, max_len=64, prefill_chunk=4, max_new_tokens=6))
        r1 = eng.submit(np.array([5, 9, 2]))
        eng.step(); eng.step()
        r2 = eng.submit(np.array([7, 7]))
        eng.run_until_drained()
        assert r1.out_tokens == greedy_ref(params, sc, np.array([5, 9, 2]), 6)
        assert r2.out_tokens == greedy_ref(params, sc, np.array([7, 7]), 6)

    def test_request_isolation(self, small_model):
        """A request's output must not depend on its batch-mates."""
        sc, params = small_model
        solo = Engine(params, sc, ServeConfig(
            max_batch=1, max_len=64, prefill_chunk=4, max_new_tokens=4))
        rs = solo.submit(np.array([9, 4, 1]))
        solo.run_until_drained()
        batched = Engine(params, sc, ServeConfig(
            max_batch=4, max_len=64, prefill_chunk=4, max_new_tokens=4))
        rb = batched.submit(np.array([9, 4, 1]))
        for other in ([3, 3, 3], [8], [2, 6, 4, 4, 2]):
            batched.submit(np.array(other))
        batched.run_until_drained()
        assert rs.out_tokens == rb.out_tokens


class TestRetirementEdges:
    def _first_token(self, params, sc, prompt):
        lg, _, _ = T.forward(params, sc, tokens=jnp.asarray([prompt]),
                             attn_impl="dense")
        return int(jnp.argmax(lg[0, -1]))

    def test_max_new_tokens_one(self, small_model):
        """A budget of 1 completes on the prefill-sampled token alone."""
        sc, params = small_model
        eng = Engine(params, sc, ServeConfig(
            max_batch=2, max_len=64, prefill_chunk=4, max_new_tokens=1))
        req = eng.submit(np.array([5, 9, 2]))
        done = eng.run_until_drained()
        assert req.done and req in done
        assert len(req.out_tokens) == 1
        assert req.out_tokens == greedy_ref(params, sc, np.array([5, 9, 2]), 1)
        # its slot was never occupied
        assert all(r is None for r in eng.active)

    def test_eos_on_prefill_token(self, small_model):
        """EOS hit by the first (prefill-sampled) token retires at once."""
        sc, params = small_model
        prompt = np.array([5, 9, 2, 7])
        eos = self._first_token(params, sc, prompt)
        eng = Engine(params, sc, ServeConfig(
            max_batch=2, max_len=64, prefill_chunk=4, max_new_tokens=8,
            eos_id=eos))
        req = eng.submit(prompt)
        other = eng.submit(np.array([3, 1]))
        eng.run_until_drained()
        assert req.done and req.out_tokens == [eos]
        assert other.done  # the freed slot kept serving

    def test_max_len_boundary_no_oob_cache_write(self, small_model):
        """No cache row at index >= max_len is ever written: lengths
        stays < max_len and the boundary slot retires exactly there."""
        sc, params = small_model
        max_len = 16
        eng = Engine(params, sc, ServeConfig(
            max_batch=2, max_len=max_len, prefill_chunk=4,
            max_new_tokens=64))
        near = eng.submit(np.arange(1, max_len - 2, dtype=np.int32))  # plen=13
        long_lived = eng.submit(np.array([2, 3]))
        steps = 0
        while (eng.queue or any(r is not None for r in eng.active)) and steps < 80:
            eng.step()
            steps += 1
            assert int(eng.lengths.max()) <= max_len - 1
        assert near.done
        # retired at the boundary: plen + generated == max_len - 1 context
        # rows used, never one past the cache
        assert len(near.out_tokens) < 64
        assert long_lived.done

    def test_prompt_at_max_len_rejected(self, small_model):
        sc, params = small_model
        eng = Engine(params, sc, ServeConfig(max_batch=1, max_len=8))
        with pytest.raises(ValueError, match="max_len"):
            eng.submit(np.arange(8, dtype=np.int32))

    def test_ragged_tail_near_cache_end(self, small_model):
        """max_len not a multiple of prefill_chunk: the padded tail
        chunk must clamp to the cache end — an unclamped chunk's
        dynamic_update_slice would shift backwards and silently corrupt
        earlier prompt rows."""
        sc, params = small_model
        eng = Engine(params, sc, ServeConfig(
            max_batch=1, max_len=20, prefill_chunk=16, max_new_tokens=2))
        prompt = np.arange(1, 18, dtype=np.int32)  # plen=17: tail at 16..19
        req = eng.submit(prompt)
        eng.run_until_drained()
        assert req.out_tokens == greedy_ref(params, sc, prompt, 2)


class TestPerRequestRNG:
    def test_sampling_independent_of_admission_order(self, small_model):
        """Temperature sampling derives from (seed, uid, token index):
        the same request samples identically solo or batched, early or
        late in the queue."""
        sc, params = small_model
        mk = lambda b: Engine(params, sc, ServeConfig(
            max_batch=b, max_len=64, prefill_chunk=4, max_new_tokens=5,
            temperature=0.8, seed=3))
        solo = mk(1)
        r_solo = solo.submit(np.array([9, 4, 1]))
        solo.run_until_drained()
        # same uid (0) in a crowded engine, admitted alongside others
        crowded = mk(2)
        r_crowd = crowded.submit(np.array([9, 4, 1]))
        for other in ([3, 3, 3], [8], [2, 6, 4]):
            crowded.submit(np.array(other))
        crowded.run_until_drained()
        assert r_solo.out_tokens == r_crowd.out_tokens

    def test_retry_reproducible(self, small_model):
        sc, params = small_model
        outs = []
        for _ in range(2):
            eng = Engine(params, sc, ServeConfig(
                max_batch=2, max_len=64, prefill_chunk=4, max_new_tokens=6,
                temperature=1.1, seed=7))
            r = eng.submit(np.array([5, 9, 2]))
            eng.run_until_drained()
            outs.append(r.out_tokens)
        assert outs[0] == outs[1]


class TestStreamEngineLazy:
    """The Stream.feedback round program (LazyEvaluator) must match the
    sequential engine token for token — same retirement, same mid-flight
    admissions, same sampling."""

    def _workload(self):
        prompts = [np.array([5, 9, 2, 7, 11]), np.array([3, 1, 4]),
                   np.array([2] * 6), np.array([8, 8]),
                   np.array([1, 2, 3, 4]), np.array([7])]
        budgets = [6, 3, 5, 1, 6, 4]
        return prompts, budgets

    @pytest.mark.parametrize("microbatches,round_steps", [(2, 4), (4, 3)])
    def test_matches_sequential(self, cell_model, microbatches, round_steps):
        sc, params = cell_model
        scfg = ServeConfig(max_batch=4, max_len=64, prefill_chunk=4,
                           max_new_tokens=6)
        prompts, budgets = self._workload()
        ref = Engine(params, sc, scfg)
        reqs_a = [ref.submit(p, b) for p, b in zip(prompts, budgets)]
        ref.run_until_drained()
        pcfg = DecodePipelineConfig(
            num_cells=4, microbatches=microbatches,
            round_steps=round_steps, admit_per_round=3)
        eng = StreamEngine(params, sc, scfg, pcfg)
        reqs_b = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
        done = eng.run_until_drained()
        assert len(done) == len(prompts)
        for ra, rb in zip(reqs_a, reqs_b):
            assert rb.done
            assert ra.out_tokens == rb.out_tokens
        # Both engines count the same work: one prefill and its first
        # token per request, every later token in a decode slot-step.
        decoded = sum(len(r.out_tokens) - 1 for r in reqs_b)
        for e, per_round in ((ref, scfg.max_batch),
                             (eng, round_steps * scfg.max_batch)):
            c = e.counters
            assert c.prefills == len(prompts) and c.tokens == decoded
            assert c.slot_steps == c.rounds * per_round >= decoded

    def test_temperature_matches_sequential(self, cell_model):
        sc, params = cell_model
        scfg = ServeConfig(max_batch=2, max_len=64, prefill_chunk=4,
                           max_new_tokens=5, temperature=0.9, seed=11)
        prompts = [np.array([5, 9, 2]), np.array([4, 4]), np.array([1, 2, 3])]
        ref = Engine(params, sc, scfg)
        reqs_a = [ref.submit(p) for p in prompts]
        ref.run_until_drained()
        eng = StreamEngine(params, sc, scfg, DecodePipelineConfig(
            num_cells=2, microbatches=2, round_steps=3, admit_per_round=2))
        reqs_b = [eng.submit(p) for p in prompts]
        eng.run_until_drained()
        for ra, rb in zip(reqs_a, reqs_b):
            assert ra.out_tokens == rb.out_tokens

    def test_no_oob_cache_write_at_boundary(self, cell_model):
        sc, params = cell_model
        max_len = 16
        scfg = ServeConfig(max_batch=2, max_len=max_len, prefill_chunk=4,
                           max_new_tokens=64)
        eng = StreamEngine(params, sc, scfg, DecodePipelineConfig(
            num_cells=2, microbatches=2, round_steps=4, admit_per_round=2))
        near = eng.submit(np.arange(1, max_len - 2, dtype=np.int32))
        eng.submit(np.array([2, 3]))
        rounds = 0
        while (eng.queue or any(r is not None for r in eng.active)) and rounds < 40:
            eng.step()
            rounds += 1
            assert int(eng.lengths.max()) <= max_len - 1
        assert near.done


class TestStreamEnginePallas:
    """``kernels="pallas"`` (interpret-emulated on CPU) must be bitwise
    token-identical to the xla sequential engine: the fused decode
    attention replaces the per-layer slab update + dense read, and the
    fused emit epilogue replaces final-norm + logits.  The arch axis
    covers layernorm+tied (olmo), rmsnorm+untied hybrid attn/ssm
    (jamba), and attention-free rmsnorm+tied (mamba2 — emit fusion
    only)."""

    ARCHS = ["olmo-1b", "jamba-1.5-large-398b", "mamba2-1.3b"]

    def _run_pair(self, arch, temperature=0.0):
        sc = smoke_config(get_config(arch))
        params = init_params(jax.random.PRNGKey(0), T.model_layout(sc))
        scfg = ServeConfig(max_batch=4, max_len=32, prefill_chunk=4,
                           max_new_tokens=5, temperature=temperature, seed=3)
        prompts = [np.array([5, 9, 2, 7]), np.array([3, 1]),
                   np.array([2] * 5), np.array([8, 8, 4]), np.array([6])]
        budgets = [5, 3, 4, 5, 2]
        ref = Engine(params, sc, scfg)
        reqs_a = [ref.submit(p, b) for p, b in zip(prompts, budgets)]
        ref.run_until_drained()
        pcfg = DecodePipelineConfig(num_cells=2, microbatches=2,
                                    round_steps=3, admit_per_round=2,
                                    kernels="pallas")
        eng = StreamEngine(params, sc, scfg, pcfg)
        assert eng.kernels == "pallas"
        reqs_b = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
        eng.run_until_drained()
        for ra, rb in zip(reqs_a, reqs_b):
            assert rb.done
            assert ra.out_tokens == rb.out_tokens

    @pytest.mark.parametrize("arch", ARCHS)
    def test_greedy_bitwise_vs_xla_sequential(self, arch):
        self._run_pair(arch)

    def test_temperature_bitwise_vs_xla_sequential(self):
        self._run_pair("olmo-1b", temperature=0.9)

    def test_arch_knob_inherited_when_pipeline_unset(self, cell_model):
        """DecodePipelineConfig.kernels=None defers to ArchConfig.kernels."""
        sc, params = cell_model
        scfg = ServeConfig(max_batch=2, max_len=32, prefill_chunk=4,
                           max_new_tokens=3)
        eng = StreamEngine(
            params, sc.with_overrides(kernels="pallas"), scfg,
            DecodePipelineConfig(num_cells=2, microbatches=2,
                                 round_steps=2, admit_per_round=1))
        assert eng.kernels == "pallas"
        r = eng.submit(np.array([5, 9, 2]))
        eng.run_until_drained()
        assert r.done and len(r.out_tokens) == 3


class TestServeBenchGate:
    """The BENCH_serve.json regression gate is throughput-directional."""

    def _rec(self, engine="stream_gpipe", batch=8, tok_s=100.0):
        return {
            "engine": engine, "schedule": "gpipe", "devices": 2,
            "interleave": 1, "batch": batch, "dim": 256, "max_new": 24,
            "tokens_per_sec": tok_s,
        }

    def test_within_tolerance_passes(self):
        from benchmarks.run import check_serve_regressions

        base = [self._rec(tok_s=100.0)]
        fresh = [self._rec(tok_s=95.0)]
        assert check_serve_regressions(base, fresh, 0.10) == []

    def test_throughput_drop_detected(self):
        from benchmarks.run import check_serve_regressions

        base = [self._rec(tok_s=100.0), self._rec(batch=16, tok_s=200.0)]
        fresh = [self._rec(tok_s=80.0), self._rec(batch=16, tok_s=195.0)]
        out = check_serve_regressions(base, fresh, 0.10)
        assert len(out) == 1 and out[0]["batch"] == 8

    def test_faster_never_flags(self):
        from benchmarks.run import check_serve_regressions

        base = [self._rec(tok_s=100.0)]
        fresh = [self._rec(tok_s=150.0)]
        assert check_serve_regressions(base, fresh, 0.10) == []

    def test_kernels_axis_distinct_cells(self):
        """pallas cells never gate against xla cells; records written
        before the kernels axis existed keep gating the xla cells."""
        from benchmarks.run import check_serve_regressions

        legacy = [self._rec(tok_s=100.0)]  # pre-axis baseline: no key
        pallas = [dict(self._rec(tok_s=10.0), kernels="pallas")]
        assert check_serve_regressions(legacy, pallas, 0.10) == []
        xla = [dict(self._rec(tok_s=80.0), kernels="xla")]
        out = check_serve_regressions(legacy, xla, 0.10)
        assert len(out) == 1 and out[0]["batch"] == 8

    def _chaos_rec(self, lost=0, bitwise=True):
        return {
            "engine": "chaos_sequential", "schedule": "-", "devices": 1,
            "interleave": 1, "batch": 8, "dim": 0,
            "requests_lost": lost, "bitwise_equal": bitwise,
            "recovery_overhead_seconds": 0.1,
        }

    def test_chaos_zero_loss_passes(self):
        from benchmarks.run import check_serve_regressions

        assert check_serve_regressions([], [self._chaos_rec()], 0.10) == []

    def test_chaos_lost_request_flags_without_baseline(self):
        """The chaos invariant is absolute — it fires on the fresh run
        alone, with no matching baseline cell required."""
        from benchmarks.run import check_serve_regressions

        out = check_serve_regressions([], [self._chaos_rec(lost=2)], 0.10)
        assert len(out) == 1 and out[0]["requests_lost"] == 2

    def test_chaos_bitwise_mismatch_flags(self):
        from benchmarks.run import check_serve_regressions

        out = check_serve_regressions(
            [], [self._chaos_rec(bitwise=False)], 0.10)
        assert len(out) == 1 and out[0]["bitwise_equal"] is False

    def test_chaos_cells_skip_throughput_gate(self):
        """Chaos cells carry no tokens_per_sec, so they never trip the
        throughput comparator even when a baseline chaos cell exists."""
        from benchmarks.run import check_serve_regressions

        assert check_serve_regressions(
            [self._chaos_rec()], [self._chaos_rec()], 0.10) == []
