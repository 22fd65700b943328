"""A small benchmark root for the tests: the same files the chip runs, at
widths the CPU holds, with the program's real code underneath."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]

TINY_OLMO = {
    "name": "tiny-olmo", "source": "test", "arch": "olmo-1b", "reference": "olmo",
    "model": {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 4,
              "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 512,
              "rope_theta": 10000.0, "tie_word_embeddings": True},
    "serving": {"max_batch": 8, "max_len": 64, "prefill_chunk": 16},
}
TINY_REASON = {
    "loop": "closed", "clients": "max_batch",
    "prompt_tokens": {"median": 14, "sigma": 0.5, "min": 4, "max": 30},
    "output_tokens": {"median": 20, "sigma": 0.3, "min": 8, "max": 32},
    "max_total_tokens": 63, "start": "staggered", "pool": 256,
}
TINY_DOCQA = {
    "loop": "open",
    "prompt_tokens": {"median": 24, "sigma": 0.4, "min": 8, "max": 48},
    "output_tokens": {"median": 6, "sigma": 0.5, "min": 2, "max": 15},
    "max_total_tokens": 63, "warm_s": 0.5, "pool": 256,
}
# The limit of the tiny cells, between the program's gap on the CPU
# (at most about 0.003 over seeds) and the fp8 control's (0.08 and up).
TINY_LIMIT = 0.03


def make_root(tmp: Path) -> Path:
    """A checkout-shaped directory: BENCHMARK.json and bench/ with the
    tiny configuration and mixes, and the real metric readers."""
    root = tmp / "bench"
    for d in ("configs", "traffic", "workloads"):
        (root / d).mkdir(parents=True)
    shutil.copytree(BENCH / "metrics", root / "metrics")
    (root / "configs" / "tiny-olmo.json").write_text(json.dumps(TINY_OLMO))
    (root / "traffic" / "reason.json").write_text(json.dumps(TINY_REASON))
    (root / "traffic" / "docqa.json").write_text(json.dumps(TINY_DOCQA))
    (root / "workloads" / "tiny-olmo.reason.json").write_text(
        json.dumps({"logit_gap_limit": TINY_LIMIT}))
    (root / "workloads" / "tiny-olmo.docqa.json").write_text(
        json.dumps({"logit_gap_limit": TINY_LIMIT, "rate_per_s": 20.0}))
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench = dict(real)
    bench["configs"] = [{"name": "tiny-olmo", "source": "test",
                         "file": "bench/configs/tiny-olmo.json", "reduced": [], "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny-olmo.reason", "config": "tiny-olmo", "traffic": "reason", "chips": 1, "why": "test"},
        {"name": "tiny-olmo.docqa", "config": "tiny-olmo", "traffic": "docqa", "chips": 1, "why": "test"},
    ]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [w.replace("olmo-1b", "tiny-olmo") for w in metric["workloads"]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    return make_root(tmp_path)


@pytest.fixture
def cpu_peaks(monkeypatch):
    """Stand-in peaks for the CPU, so that a traced run here can be read
    end to end; the numbers it gives are not a device's."""
    from bench import peaks

    monkeypatch.setitem(peaks.PEAKS, "cpu", {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
