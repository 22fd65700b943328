"""The comparison that decides ``correct``, driven through whole runs of
the tiny cells on the CPU: sound runs pass; the fp8 control and each
fault the serving cells can have, planted in the program underneath the
timed path, come out not correct."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench.harness import cell
from bench.tests.conftest import TINY_LIMIT


def run(root, name="tiny-olmo.reason", seed=4000000007, seconds=1.5, **kw):
    return cell.run(name, seed, seconds, False, t_start=time.perf_counter(), root=root,
                    require_tpu=False, cache=False, **kw)


@pytest.mark.parametrize("name", ["tiny-olmo.reason", "tiny-olmo.docqa"])
def test_sound_run_is_correct(tiny_root, name):
    r = run(tiny_root, name)
    assert r["correct"], r["check"]
    assert list(r)[-1] == "check"
    assert r["check"]["logit_gap"]["limit"] == TINY_LIMIT
    assert r["metrics"]["tokens_per_s"]["value"] > 0
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("seed", [11, 2**31 + 5, 7 * 10**9])
def test_fp8_control_is_not_correct(tiny_root, seed):
    """The reference computed in fp8, put where the served tokens were,
    makes the run not correct; the program's own tokens, on the same
    sample, stay under the limit."""
    r = run(tiny_root, seed=seed, control=True)
    assert not r["correct"]
    assert r["check"]["logit_gap"]["value"] > TINY_LIMIT
    assert r["program_gap"] <= TINY_LIMIT


def _state_unchanged(monkeypatch):
    from repro.models import transformer as T

    monkeypatch.setattr(T, "scatter_decode_rows", lambda cache, *a, **k: cache)


def _half_batch_left_out(monkeypatch):
    from repro.models import transformer as T

    make = T.make_decode_cell

    def make_half(*a, **k):
        fn = make(*a, **k)

        def cell_fn(const, state, item):
            state2, out = fn(const, state, item)
            half = item["x"].shape[0] // 2
            return state2, {**out, "x": out["x"].at[half:].set(item["x"][half:])}

        return cell_fn

    monkeypatch.setattr(T, "make_decode_cell", make_half)


def _token_altered(monkeypatch):
    from repro.serve import engine

    sample = engine.sample_token

    def altered(logits, *a, **k):
        tok = sample(logits, *a, **k)
        return (tok + 1) % jnp.shape(logits)[-1]

    monkeypatch.setattr(engine, "sample_token", altered)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch_left_out, _token_altered],
                         ids=["state_unchanged", "half_batch_left_out", "token_altered"])
def test_fault_is_not_correct(tiny_root, monkeypatch, fault):
    fault(monkeypatch)
    jax.clear_caches()
    r = run(tiny_root)
    assert not r["correct"]
    assert r["check"]["logit_gap"]["value"] > TINY_LIMIT


def test_traced_run_reports_per_layer_metrics(tiny_root, cpu_peaks):
    r = cell.run("tiny-olmo.reason", 5, 1.5, True, t_start=time.perf_counter(), root=tiny_root,
                 require_tpu=False, cache=False)
    assert r["correct"]
    assert {"round_ms", "decode_mfu", "device_idle_pct"} <= set(r["metrics"])
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
    assert r["breakdown"]["device_ops"] and r["breakdown"]["idle_gaps"]
