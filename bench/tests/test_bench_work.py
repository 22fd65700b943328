"""Work counts and the per-layer readers, against numbers worked out by hand.

The counts take shapes and each decoded row's live context only: no
allocated slab and no implementation switch enter them."""
import json
import types
from pathlib import Path

import numpy as np
import pytest

from bench import peaks
from bench.harness import spec
from bench.models import mamba2, olmo

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
OLMO = json.loads((CONFIGS / "olmo-1b.json").read_text())["model"]
MAMBA2 = json.loads((CONFIGS / "mamba2-1.3b.json").read_text())["model"]
V5E = peaks.peaks_for("TPU v5 lite")


def test_peaks_table():
    assert V5E["bf16_flops"] == 197e12 and V5E["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_olmo_matmul_params():
    # 16 x (q,k,v,o 4 x 2048^2 + SwiGLU 3 x 2048 x 8192) + head 50304 x 2048
    assert olmo.matmul_params(OLMO) == 16 * (4 * 2048**2 + 3 * 2048 * 8192) + 50304 * 2048
    assert olmo.matmul_params(OLMO) == 1_176_764_416


def test_mamba2_matmul_params_and_vocab():
    d = mamba2.dims(MAMBA2)
    assert d["vocab"] == 50288 and d["proj_dim"] == 8512 and d["ssm_heads"] == 64
    assert mamba2.matmul_params(MAMBA2) == 48 * (2048 * 8512 + 4096 * 2048) + 50288 * 2048
    assert mamba2.matmul_params(MAMBA2) == 1_342_406_656


def test_mixer_flops():
    kv = np.array([1, 1024, 2047])
    assert olmo.mixer_flops(OLMO, kv) == 4 * 16 * 16 * 128 * (1 + 1024 + 2047)
    per = 48 * (4 * 64 * 128 * 64 + 2 * 4352 * 4)
    assert mamba2.mixer_flops(MAMBA2, kv) == 3 * per


class FakeTrace:
    def __init__(self, window_s, scope_seconds, rounds):
        self.window = (0, int(window_s * 1e9))
        self.window_s = window_s
        self._scope = scope_seconds
        self._rounds = rounds

    def kernel_s(self, kernel):
        return self._scope.get(kernel, 0.0)

    def executions(self, part):
        return {"dev0": [types.SimpleNamespace(start=0, end=int(20e6), name="jit__round")] * self._rounds}


def reading(model, family, kv, *, window_s=1.0, scopes=None, rounds=10):
    return types.SimpleNamespace(
        model=model, family=family, dims=family.dims(model), chips=1, peaks=V5E,
        decoded_kv_lens=np.asarray(kv), trace=FakeTrace(window_s, scopes or {}, rounds),
        round_steps=8, memory_peak_bytes=14e9)


def metric(name):
    return spec.Cell("olmo-1b.reason").reader(name)


def test_decode_mfu_by_hand():
    kv = np.full(1000, 1024)
    r = reading(OLMO, olmo, kv, window_s=2.0)
    flops = 1000 * (2 * 1_176_764_416 + 4 * 16 * 16 * 128 * 1024)
    assert metric("decode_mfu")(r) == pytest.approx(100 * flops / (2.0 * 197e12))
    assert metric("decode_mfu")(reading(OLMO, olmo, [])) is None


def test_decode_attention_roofline_counts_the_live_prefix():
    KERNEL = "decode_attention_pallas"
    kv = np.array([100, 2000])
    r = reading(OLMO, olmo, kv, scopes={KERNEL: 1e-3})
    nbytes = 2 * 16 * (2 * 16 * 128 * 2100 + 2 * 16 * 128 * 2)
    assert metric("decode_attention_roofline")(r) == pytest.approx(100 * nbytes / 819e9 / 1e-3)
    # Twice the context, twice the bytes: the count follows kv_len, not a slab.
    r2 = reading(OLMO, olmo, kv * 2, scopes={KERNEL: 1e-3})
    assert metric("decode_attention_roofline")(r2) > 1.9 * metric("decode_attention_roofline")(r)
    assert metric("decode_attention_roofline")(reading(OLMO, olmo, kv)) is None
    assert metric("decode_attention_roofline")(reading(MAMBA2, mamba2, kv, scopes={KERNEL: 1.0})) is None


@pytest.mark.parametrize("family,model,vocab", [(olmo, OLMO, 50304), (mamba2, MAMBA2, 50288)])
def test_emit_roofline_by_hand(family, model, vocab):
    """The head is read once per decode step (10 rounds of 8 steps), each
    decoded token reads its input row and writes its fp32 logits."""
    KERNEL = "emit_norm_logits_pallas"
    tokens = 10 * 8 * 24
    r = reading(model, family, np.full(tokens, 500), scopes={KERNEL: 0.05}, rounds=10)
    nbytes = 80 * 2 * vocab * 2048 + tokens * (2 * 2048 + 4 * vocab)
    assert metric("emit_norm_logits_roofline")(r) == pytest.approx(100 * nbytes / 819e9 / 0.05)
    # Half the tokens in the same steps: the head's bytes stay, the rows' halve.
    half = reading(model, family, np.full(tokens // 2, 500), scopes={KERNEL: 0.05}, rounds=10)
    nbytes_half = 80 * 2 * vocab * 2048 + tokens // 2 * (2 * 2048 + 4 * vocab)
    assert metric("emit_norm_logits_roofline")(half) == pytest.approx(100 * nbytes_half / 819e9 / 0.05)
    assert metric("emit_norm_logits_roofline")(reading(model, family, [], scopes={KERNEL: 0.05})) is None


def test_memory_and_round_readers():
    r = reading(OLMO, olmo, [5], rounds=3)
    assert metric("peak_hbm_gb")(r) == pytest.approx(14.0)
    assert metric("round_ms")(r) == pytest.approx(20.0)
