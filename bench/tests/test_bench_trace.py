"""The trace reduction: busy union, idle share, idle attribution, kernel time."""
import jax
import jax.numpy as jnp
import pytest
from jax.profiler import TraceAnnotation

from bench.harness import trace
from bench.harness.trace import Span, Trace


def test_union_and_idle_share_by_hand():
    ops = {"d0": [Span(10, 30, "a"), Span(20, 40, "b"), Span(60, 70, "c"), Span(95, 120, "d")]}
    t = Trace(ops, {}, [], (0, 100))
    # busy: [10,40] + [60,70] + [95,100] = 30 + 10 + 5
    assert t.busy_share("d0") == pytest.approx(0.45)
    assert t.busy_s() == pytest.approx(45e-9)
    assert trace._gaps(ops["d0"], 0, 100) == [(0, 10), (40, 60), (70, 95)]


def test_idle_attributed_to_the_innermost_host_span():
    ops = {"d0": [Span(0, 10, "op"), Span(50, 60, "op")]}
    host = [Span(0, 100, trace.WINDOW), Span(5, 45, "bench.step"), Span(20, 30, "PjitFunction(x)")]
    t = Trace(ops, {}, host, (0, 100))
    gaps = dict(t.breakdown()["idle_gaps"])
    # (10, 50): middle 30 is at the end of the pjit span, inside bench.step
    assert gaps["bench.step"] == pytest.approx(40e-9)
    # (60, 100): middle 80, only the window is open
    assert gaps[trace.WINDOW] == pytest.approx(40e-9)


def test_kernel_time_by_instruction_name_and_executions():
    """Event names as the TPU trace gives them (a chip run's, shortened):
    the kernel is its custom call's instruction, not an operation that
    merely reads its output; an enclosing while is not a leaf."""
    ops = {"d0": [
        Span(0, 60, "%while.82 = (s32[], bf16[4,4,12,2048,16,128]{5,4,3,2,1,0}) while(%tuple.1)"),
        Span(0, 10, "%decode_attention_pallas.1 = bf16[8,16,128]{2,1,0} custom-call(s32[8] %pos.1)"),
        Span(10, 12, "%broadcast_in_dim.1 = bf16[8,1,2048]{2,0,1} reshape(bf16[8,16,128] %decode_attention_pallas.1)"),
        Span(12, 30, "%copy.196 = bf16[4,4,12,2048,16,128]{5,4,3,2,1,0} copy(bf16[4,4,12,2048,16,128] %x)"),
        Span(95, 105, "%decode_attention_pallas.2 = bf16[8,16,128]{2,1,0} custom-call(s32[8] %pos.2)"),
    ]}
    mods = {"d0": [Span(0, 50, "jit__round(123)"), Span(60, 70, "jit_prefill_step(9)"),
                   Span(120, 130, "jit__round(123)")]}
    t = Trace(ops, mods, [], (0, 100))
    assert t.kernel_s("decode_attention_pallas") == pytest.approx(15e-9)
    assert t.kernel_calls("decode_attention_pallas") == 2
    assert len(t.executions("jit__round")["d0"]) == 1
    top = dict((k, v) for k, v in t.breakdown()["device_ops"])
    assert top["%copy.196 bf16[4,4,12,2048,16,128]"] == pytest.approx(18e-9)
    assert not any(k.startswith("%while") for k in top)


def test_reduction_of_a_trace_recorded_here(tmp_path):
    """A short CPU trace through the same reader the chip's trace goes
    through: the window span is found, the device's operations fall
    inside it, and the busy share is a share."""

    @jax.jit
    def f(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with TraceAnnotation(trace.WINDOW):
        for _ in range(3):
            with TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = trace.load(tmp_path)
    assert t.window_s > 0
    assert t.ops, "no device operation found"
    dev = next(iter(t.ops))
    assert 0.0 < t.busy_share(dev) <= 1.0
    assert 0.0 < t.busy_s() <= t.window_s
    assert any("jit_f" in m.name for m in t.executions("jit_f")[dev])
    assert any(name == "bench.step" for name, _ in t.breakdown()["idle_gaps"])
