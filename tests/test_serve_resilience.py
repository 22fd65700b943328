"""Resilient serving: the chaos battery and request-lifecycle guards.

The acceptance bar (ISSUE 10): a fault injected at *every* round index —
mid-round exception, NaN-poisoned cache, SIGTERM — loses zero accepted
requests and the recovered serve's tokens are bitwise-equal to the
fault-free run, for the sequential ``Engine`` and the ``StreamEngine``
(xla and pallas-interpret here; gpipe/interleaved on 4 devices in the
multidevice battery below).  Bitwise replay is the paper's determinism
carried into the failure path: failure is a value, recovery re-runs the
same pure flow.

Runtime discipline: each battery builds ONE engine (one jit compile),
takes a pristine supervisor snapshot at birth, uses the fault-free run
as both golden and warmup, and replays every chaos scenario from the
pristine snapshot — restore resets the uid counter, so resubmitted
workloads are bitwise-identical without recompiling.
"""
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import DecodePipelineConfig
from repro.configs.registry import get_config, smoke_config
from repro.models import transformer as T
from repro.models.params import init_params
from repro.resilience import (
    Heartbeat,
    InjectedFault,
    OneShotInjector,
    RestartBudget,
    RestartPolicy,
    StragglerTracker,
)
from repro.resilience.injection import call_injector
from repro.serve.engine import (
    DrainTimeoutError,
    Engine,
    QueueFullError,
    ServeConfig,
    StreamEngine,
)
from repro.serve.supervisor import (
    DrainingError,
    NumericsFault,
    ServeSupervisor,
    SupervisorConfig,
    WatchdogTimeout,
    chaos_injector,
    poison_cache,
)

PROMPTS = [
    np.array([5, 9, 2, 7]),
    np.array([3, 1]),
    np.array([2] * 5),
    np.array([8, 8, 4]),
]
BUDGETS = [4, 2, 3, 4]

SCFG = dict(max_batch=2, max_len=64, prefill_chunk=4, max_new_tokens=4)


@pytest.fixture(scope="module")
def cell_model():
    rng = jax.random.PRNGKey(0)
    sc = smoke_config(get_config("olmo-1b")).with_overrides(num_layers=4)
    params = init_params(rng, T.model_layout(sc))
    return sc, params


def _submit_all(sup):
    return [sup.submit(p, b) for p, b in zip(PROMPTS, BUDGETS)]


def _rig(engine):
    """(pristine snapshot, golden tokens, clean round count) for ``engine``.

    The fault-free supervised run doubles as jit warmup; the pristine
    snapshot (taken before any submit) is the reset lever every chaos
    scenario replays from.
    """
    sup = ServeSupervisor(engine)
    pristine = sup.snapshot()
    reqs = _submit_all(sup)
    sup.run_until_drained()
    golden = [r.out_tokens for r in reqs]
    assert all(r.done for r in reqs)
    return pristine, golden, sup.stats["rounds"]


@pytest.fixture(scope="module")
def seq_rig(cell_model):
    sc, params = cell_model
    eng = Engine(params, sc, ServeConfig(**SCFG))
    pristine, golden, rounds = _rig(eng)
    return eng, pristine, golden, rounds


@pytest.fixture(scope="module")
def stream_rig(cell_model):
    sc, params = cell_model
    eng = StreamEngine(
        params, sc, ServeConfig(**SCFG),
        DecodePipelineConfig(num_cells=2, microbatches=2, round_steps=3,
                             admit_per_round=2),
    )
    pristine, golden, rounds = _rig(eng)
    return eng, pristine, golden, rounds


@pytest.fixture(scope="module")
def pallas_rig(cell_model):
    sc, params = cell_model
    eng = StreamEngine(
        params, sc, ServeConfig(**SCFG),
        DecodePipelineConfig(num_cells=2, microbatches=2, round_steps=3,
                             admit_per_round=2, kernels="pallas"),
    )
    assert eng.kernels == "pallas"
    pristine, golden, rounds = _rig(eng)
    return eng, pristine, golden, rounds


def _chaos_run(rig, kind, k, cfg=None, **inj_kw):
    """Replay the golden workload with a ``kind`` fault at round ``k``."""
    eng, pristine, golden, _ = rig
    sup = ServeSupervisor(
        eng, cfg or SupervisorConfig(),
        fail_injector=chaos_injector(kind, k, **inj_kw),
    )
    sup.restore(pristine)
    reqs = _submit_all(sup)
    if kind == "sigterm":
        prev = signal.getsignal(signal.SIGTERM)
        sup.install_signal_handlers()
        try:
            sup.run_until_drained()
        finally:
            signal.signal(signal.SIGTERM, prev)
        assert sup.draining
    else:
        sup.run_until_drained()
    assert sup.stats["requests_lost"] == 0, (kind, k, sup.stats)
    assert [r.out_tokens for r in reqs] == golden, (kind, k)
    return sup


class TestChaosEngine:
    """Sequential Engine under the supervisor: every fault class at
    every round index recovers bitwise with zero requests lost."""

    def test_raise_every_round(self, seq_rig):
        rounds = seq_rig[3]
        for k in range(rounds):
            sup = _chaos_run(seq_rig, "raise", k)
            assert sup.stats["faults"] == 1 and sup.stats["restarts"] == 1

    def test_nan_poison_every_round(self, seq_rig):
        rounds = seq_rig[3]
        detected = 0
        for k in range(rounds):
            sup = _chaos_run(seq_rig, "nan", k)
            # A round that re-prefills every slot can fully overwrite the
            # poison — then there is nothing to detect and the run is
            # clean.  Whenever poison survives the round it must be
            # caught, restored, and replayed (never silently served).
            assert sup.stats["faults"] == sup.stats["restarts"] <= 1
            if sup.stats["faults"]:
                detected += 1
                assert any(
                    "NumericsFault" in e.get("error", "") for e in sup.events
                ), k
        assert detected >= rounds - 1

    def test_sigterm_every_round_drains_gracefully(self, seq_rig):
        rounds = seq_rig[3]
        for k in range(rounds):
            sup = _chaos_run(seq_rig, "sigterm", k)
            # SIGTERM is not a fault: admission closes, accepted work runs
            # to completion, and the drain event is recorded.
            assert sup.stats["faults"] == 0
            assert {"event": "drained"} in sup.events

    def test_wedge_trips_watchdog_and_replays(self, seq_rig):
        sup = _chaos_run(
            seq_rig, "wedge", 1,
            cfg=SupervisorConfig(deadline_s=0.3), wedge_seconds=0.6,
        )
        assert sup.stats["restarts"] == 1
        assert any(
            "WatchdogTimeout" in e.get("error", "") for e in sup.events
        )


class TestChaosStream:
    """StreamEngine (LazyEvaluator round program) under the supervisor:
    cell_states (the sharded KV slabs) snapshot/restore bitwise."""

    def test_stream_matches_sequential_golden(self, seq_rig, stream_rig):
        # cross-engine pin: the stream rig's fault-free tokens are the
        # sequential engine's, so chaos equality below is transitive.
        assert stream_rig[2] == seq_rig[2]

    def test_raise_every_round(self, stream_rig):
        for k in range(stream_rig[3]):
            sup = _chaos_run(stream_rig, "raise", k)
            assert sup.stats["restarts"] == 1

    def test_nan_poison_every_round(self, stream_rig):
        for k in range(stream_rig[3]):
            _chaos_run(stream_rig, "nan", k)

    def test_sigterm_every_round(self, stream_rig):
        for k in range(stream_rig[3]):
            sup = _chaos_run(stream_rig, "sigterm", k)
            assert sup.stats["faults"] == 0


class TestChaosPallas:
    """kernels="pallas" (interpret-emulated on CPU): the fused round
    program recovers bitwise too — fault tolerance is kernel-agnostic."""

    def test_pallas_matches_sequential_golden(self, seq_rig, pallas_rig):
        assert pallas_rig[2] == seq_rig[2]

    def test_raise_every_round(self, pallas_rig):
        for k in range(pallas_rig[3]):
            _chaos_run(pallas_rig, "raise", k)

    def test_nan_poison_recovers(self, pallas_rig):
        _chaos_run(pallas_rig, "nan", 1)


class TestSupervisorEdge:
    def test_budget_exhaustion_counts_lost_and_reraises(self, cell_model):
        sc, params = cell_model
        eng = Engine(params, sc, ServeConfig(**SCFG))
        def always_fail(step, engine):
            raise InjectedFault("persistent failure")
        sup = ServeSupervisor(
            eng, SupervisorConfig(max_restarts=2), fail_injector=always_fail
        )
        reqs = _submit_all(sup)
        with pytest.raises(InjectedFault):
            sup.run_until_drained()
        assert sup.stats["requests_lost"] == len(reqs)
        assert sup.stats["restarts"] == 2 and sup.stats["faults"] == 3
        gave_up = [e for e in sup.events if e["event"] == "gave_up"]
        assert gave_up and gave_up[0]["requests_lost"] == sorted(
            r.uid for r in reqs
        )

    def test_pristine_restore_is_bitwise_repeatable(self, seq_rig):
        eng, pristine, golden, _ = seq_rig
        for _ in range(2):
            sup = ServeSupervisor(eng)
            sup.restore(pristine)
            reqs = _submit_all(sup)
            sup.run_until_drained()
            assert [r.out_tokens for r in reqs] == golden

    def test_submit_after_drain_requested_rejected(self, seq_rig):
        eng, pristine, _, _ = seq_rig
        sup = ServeSupervisor(eng)
        sup.restore(pristine)
        sup.request_drain()
        with pytest.raises(DrainingError):
            sup.submit(np.array([1, 2]))

    def test_numerics_check_detects_poison(self, seq_rig):
        eng, pristine, _, _ = seq_rig
        sup = ServeSupervisor(eng)
        sup.restore(pristine)
        poison_cache(eng)
        with pytest.raises(NumericsFault):
            sup._check_numerics()
        sup.restore(pristine)
        sup._check_numerics()  # clean after restore

    def test_run_until_drained_counts_truncation_as_lost(self, seq_rig):
        eng, pristine, _, _ = seq_rig
        sup = ServeSupervisor(eng)
        sup.restore(pristine)
        _submit_all(sup)
        with pytest.raises(DrainTimeoutError) as ei:
            sup.run_until_drained(max_steps=1)
        assert sup.stats["requests_lost"] == len(ei.value.undrained) > 0
        sup2 = ServeSupervisor(eng)
        sup2.restore(pristine)  # leave the shared rig engine clean


class TestRequestLifecycle:
    """Engine-level robustness: bounded queue, deadlines, cancellation,
    loud drain truncation."""

    def test_bounded_queue_sheds_load(self, cell_model):
        sc, params = cell_model
        eng = Engine(params, sc, ServeConfig(
            max_batch=1, max_len=64, prefill_chunk=4, max_queue=2))
        eng.submit(np.array([1, 2]))
        eng.submit(np.array([3, 4]))
        with pytest.raises(QueueFullError):
            eng.submit(np.array([5, 6]))
        assert eng.counters.shed == 1
        assert len(eng.queue) == 2  # the shed request was never accepted

    def test_deadline_expires_queued_request(self, cell_model, seq_rig):
        sc, params = cell_model
        golden = seq_rig[2]
        eng = Engine(params, sc, ServeConfig(**SCFG))
        keep = [eng.submit(p, b) for p, b in zip(PROMPTS, BUDGETS)]
        dead = eng.submit(np.array([7, 7, 7]), 4, deadline_s=0.0)
        done = eng.run_until_drained()
        assert dead.done and dead.status == "expired" and dead in done
        assert dead.out_tokens == []
        assert eng.counters.expired == 1
        # survivors are untouched by the expiry
        assert [r.out_tokens for r in keep] == golden
        assert all(r.status == "ok" for r in keep)

    def test_deadline_expires_active_request(self, cell_model):
        sc, params = cell_model
        eng = Engine(params, sc, ServeConfig(
            max_batch=2, max_len=64, prefill_chunk=4, max_new_tokens=50))
        req = eng.submit(np.array([5, 9, 2]), deadline_s=0.15)
        eng.step()
        assert not req.done and any(r is req for r in eng.active)
        time.sleep(0.2)
        done = eng.step()
        assert req in done and req.status == "expired"
        assert len(req.out_tokens) > 0  # partial output is kept
        assert eng.counters.expired == 1
        assert all(r is not req for r in eng.active)

    def test_cancel_queued_and_active(self, cell_model):
        sc, params = cell_model
        eng = Engine(params, sc, ServeConfig(
            max_batch=1, max_len=64, prefill_chunk=4, max_new_tokens=6))
        ra = eng.submit(np.array([5, 9, 2]))
        rq = eng.submit(np.array([3, 1]))
        eng.step(); eng.step()
        assert eng.cancel(rq.uid)      # still queued
        assert eng.cancel(ra.uid)      # active in a slot
        assert not eng.cancel(9999)    # unknown uid
        assert ra.status == rq.status == "cancelled"
        assert ra.done and rq.done
        assert eng.counters.cancelled == 2
        # the freed slot is reusable
        rest = eng.submit(np.array([2, 2]))
        eng.run_until_drained()
        assert rest.done and rest.status == "ok"

    def test_drain_truncation_raises_with_uids(self, cell_model):
        sc, params = cell_model
        eng = Engine(params, sc, ServeConfig(
            max_batch=2, max_len=64, prefill_chunk=4, max_new_tokens=50))
        req = eng.submit(np.array([5, 9, 2]))
        with pytest.raises(DrainTimeoutError) as ei:
            eng.run_until_drained(max_steps=2)
        assert ei.value.undrained == [req.uid]

    def test_stream_drain_truncation_raises(self, stream_rig):
        eng, pristine, _, _ = stream_rig
        sup = ServeSupervisor(eng)
        sup.restore(pristine)
        eng.submit(PROMPTS[0], 50)
        with pytest.raises(DrainTimeoutError):
            eng.run_until_drained(max_steps=1)
        sup.restore(pristine)  # leave the shared rig engine clean


class TestDegradedMode:
    """There is no degraded mode: a pallas failure raises.  The engine
    never swaps the xla path in behind the caller's back, so a kernel
    that cannot run on the device is seen, not served around."""

    def test_init_probe_failure_degrades(self, cell_model, monkeypatch):
        """A fused kernel that cannot be dispatched fails the first round."""
        sc, params = cell_model
        import repro.kernels as K
        import repro.models.transformer as TT
        real = K.get_impl
        def broken(op, mode="auto"):
            if mode == "pallas":
                raise RuntimeError("simulated pallas import failure")
            return real(op, mode)
        monkeypatch.setattr(K, "get_impl", broken)
        monkeypatch.setattr(TT, "get_impl", broken)
        eng = StreamEngine(
            params, sc, ServeConfig(**SCFG),
            DecodePipelineConfig(num_cells=2, microbatches=2,
                                 round_steps=3, admit_per_round=2,
                                 kernels="pallas"),
        )
        for p, b in zip(PROMPTS, BUDGETS):
            eng.submit(p, b)
        with pytest.raises(RuntimeError, match="simulated pallas import"):
            eng.run_until_drained()
        assert eng.kernels == "pallas"
        assert not hasattr(eng, "degraded")

    def test_midflight_round_failure_degrades_and_replays(self, cell_model):
        """A round that fails mid-serve raises to the caller (the
        supervisor's replay is the recovery path, not a kernel swap)."""
        sc, params = cell_model
        eng = StreamEngine(
            params, sc, ServeConfig(**SCFG),
            DecodePipelineConfig(num_cells=2, microbatches=2, round_steps=3,
                                 admit_per_round=2, kernels="pallas"),
        )

        def exploding_round(*a, **k):
            raise RuntimeError("simulated pallas lowering crash")

        eng._round = exploding_round
        reqs = [eng.submit(p, b) for p, b in zip(PROMPTS, BUDGETS)]
        with pytest.raises(RuntimeError, match="simulated pallas lowering"):
            eng.run_until_drained()
        assert eng.kernels == "pallas"
        assert not all(r.done for r in reqs)


class TestResiliencePrimitives:
    def test_one_shot_injector_fires_once(self):
        hits = []
        inj = OneShotInjector(2, hits.append)
        for step in range(5):
            inj(step, f"t{step}")
        inj(2, "again")
        assert hits == ["t2"]

    def test_call_injector_arity(self):
        seen = []
        call_injector(lambda s: seen.append(("one", s)), 3, "eng")
        call_injector(lambda s, t: seen.append(("two", s, t)), 4, "eng")
        call_injector(None, 5)
        assert seen == [("one", 3), ("two", 4, "eng")]

    def test_restart_budget_and_backoff(self):
        b = RestartBudget(RestartPolicy(
            max_restarts=2, backoff_seconds=0.01, backoff_factor=2.0))
        assert b.admit() and b.next_delay() == pytest.approx(0.01)
        assert b.admit() and b.next_delay() == pytest.approx(0.02)
        assert b.exhausted and not b.admit()
        assert RestartBudget(RestartPolicy()).next_delay() == 0.0

    def test_heartbeat_roundtrip_and_staleness(self, tmp_path):
        path = str(tmp_path / "hb")
        assert Heartbeat.is_stale(path, 1.0)  # no file yet
        hb = Heartbeat(path)
        hb.beat(7)
        step, t = Heartbeat.read(path)
        assert step == 7
        assert not Heartbeat.is_stale(path, 60.0)
        assert Heartbeat.is_stale(path, 5.0, now=t + 10.0)
        Heartbeat(None).beat(0)  # disabled: no-op

    def test_straggler_tracker_flags_deviation(self):
        flagged = []
        t = StragglerTracker(factor=2.0, ema=0.9,
                             on_straggler=lambda s, r: flagged.append((s, r)))
        assert not t.observe(0, 1.0)   # seeds
        assert not t.observe(1, 1.1)
        assert t.observe(2, 5.0)
        assert flagged and flagged[0][0] == 2 and flagged[0][1] > 2.0
        assert t.count == 1

    def test_chaos_injector_rejects_unknown_kind(self):
        with pytest.raises(ValueError, match="chaos kind"):
            chaos_injector("meteor", 0)


# -- pipelined chaos battery (FutureEvaluator, 4 devices) --------------------

PIPELINE_SCRIPT = r"""
import os, signal
# Strict bf16 rounding at every op: XLA otherwise keeps fused
# intermediates in fp32 where its fusion decisions allow, and those
# differ between the pipelined and the sequential program.
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4"
                           " --xla_allow_excess_precision=false")
import jax, numpy as np
from repro.configs.base import DecodePipelineConfig
from repro.configs.registry import get_config, smoke_config
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serve.engine import Engine, ServeConfig, StreamEngine
from repro.serve.supervisor import ServeSupervisor, chaos_injector

sc = smoke_config(get_config("olmo-1b")).with_overrides(num_layers=8)
params = init_params(jax.random.PRNGKey(0), T.model_layout(sc))
mesh = jax.make_mesh((4,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))

scfg = ServeConfig(max_batch=8, max_len=64, prefill_chunk=4, max_new_tokens=6)
rng = np.random.default_rng(7)
prompts = [rng.integers(1, sc.vocab_size, size=int(rng.integers(1, 9)))
           for _ in range(10)]
budgets = [int(b) for b in rng.integers(1, 7, size=10)]

ref = Engine(params, sc, scfg)
gr = [ref.submit(p, b) for p, b in zip(prompts, budgets)]
ref.run_until_drained()
golden = [r.out_tokens for r in gr]

for sched, v, cells, m in [("gpipe", 1, 8, 8), ("interleaved", 2, 8, 4)]:
    eng = StreamEngine(params, sc, scfg, DecodePipelineConfig(
        num_cells=cells, microbatches=m, schedule=sched, interleave=v,
        round_steps=4, admit_per_round=4), mesh=mesh)
    sup0 = ServeSupervisor(eng)
    pristine = sup0.snapshot()
    rc = [sup0.submit(p, b) for p, b in zip(prompts, budgets)]
    sup0.run_until_drained()
    rounds = sup0.stats["rounds"]
    ok = [r.out_tokens for r in rc] == golden
    scenarios = ([("raise", k) for k in range(rounds)]
                 + [("nan", min(1, rounds - 1)), ("sigterm", 0)])
    for kind, k in scenarios:
        sup = ServeSupervisor(eng, fail_injector=chaos_injector(kind, k))
        sup.restore(pristine)
        rs = [sup.submit(p, b) for p, b in zip(prompts, budgets)]
        if kind == "sigterm":
            prev = signal.getsignal(signal.SIGTERM)
            sup.install_signal_handlers()
            try:
                sup.run_until_drained()
            finally:
                signal.signal(signal.SIGTERM, prev)
        else:
            sup.run_until_drained()
        ok = (ok and sup.stats["requests_lost"] == 0
              and [r.out_tokens for r in rs] == golden)
        if not ok:
            print(f"# first failure: {sched} {kind}@{k} {sup.stats}")
            break
    print(f"CHAOS_{sched.upper()}", ok)
"""


@pytest.mark.multidevice
class TestChaosPipelined:
    """FutureEvaluator on 4 devices: every fault class recovers bitwise
    under gpipe and interleaved schedules (subprocess — forced host
    device count must be set before jax initialises)."""

    @pytest.fixture(scope="class")
    def report(self):
        import os
        import subprocess
        import sys
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(
            os.path.dirname(__file__), "..", "src")
        proc = subprocess.run(
            [sys.executable, "-c", PIPELINE_SCRIPT],
            capture_output=True, text=True, env=env, timeout=1500,
            stdin=subprocess.DEVNULL,
        )
        assert proc.returncode == 0, proc.stderr[-3000:]
        return dict(
            line.split(None, 1)
            for line in proc.stdout.strip().splitlines()
            if not line.startswith("#")
        )

    def test_gpipe_chaos_zero_loss_bitwise(self, report):
        assert report["CHAOS_GPIPE"].startswith("True")

    def test_interleaved_chaos_zero_loss_bitwise(self, report):
        assert report["CHAOS_INTERLEAVED"].startswith("True")
