"""The harness finds a cell's configuration, mix and metrics by name, so
that a new one is a new file and a new entry, with no edit elsewhere."""
import json
from pathlib import Path

import pytest

from bench.harness import spec

BENCH = Path(__file__).resolve().parents[1]
REAL = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [w["name"] for w in REAL["workloads"]])
def test_every_cell_resolves(name):
    cell = spec.Cell(name)
    assert cell.config["name"] == cell.entry["config"]
    assert cell.mix["loop"] in ("closed", "open")
    assert "logit_gap_limit" in cell.params
    assert cell.family.__name__ == f"bench.models.{cell.config['reference']}"
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]))
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "tokens_per_s"}


def test_contract_shape():
    assert REAL["command"] == ["python3", "bench/run.py"] and REAL["paths"] == ["bench"]
    for c in REAL["configs"]:
        assert (BENCH.parent / c["file"]).exists()
    names = [m["name"] for m in REAL["end_to_end"] + REAL["per_layer"]]
    assert len(names) == len(set(names))
    for m in REAL["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
        assert m["moves"] in {e["name"] for e in REAL["end_to_end"]}


def test_new_parts_are_new_files_only(tiny_root):
    """Adding a configuration, a mix, a cell and a metric touches no file
    that was there: the harness finds them by name."""
    before = {p: p.read_bytes() for p in tiny_root.rglob("*") if p.is_file()}
    cfg = json.loads((tiny_root / "configs" / "tiny-olmo.json").read_text())
    cfg["name"] = "tiny-olmo-wide"
    (tiny_root / "configs" / "tiny-olmo-wide.json").write_text(json.dumps(cfg))
    mix = json.loads((tiny_root / "traffic" / "reason.json").read_text())
    (tiny_root / "traffic" / "chat.json").write_text(json.dumps(mix))
    (tiny_root / "workloads" / "tiny-olmo-wide.chat.json").write_text(json.dumps({"logit_gap_limit": 1.0}))
    (tiny_root / "metrics" / "answer.py").write_text("def read(r):\n    return 42.0\n")
    bench_file = tiny_root.parent / "BENCHMARK.json"
    bench = json.loads(bench_file.read_text())
    bench["configs"].append({"name": "tiny-olmo-wide", "source": "test",
                             "file": "bench/configs/tiny-olmo-wide.json", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-olmo-wide.chat", "config": "tiny-olmo-wide",
                               "traffic": "chat", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "answer", "unit": "%", "better": "higher", "source": "device_trace",
                               "layer": "device", "moves": "tokens_per_s", "workloads": ["tiny-olmo-wide.chat"]})
    bench_file.write_text(json.dumps(bench))
    cell = spec.Cell("tiny-olmo-wide.chat", tiny_root)
    assert cell.config["name"] == "tiny-olmo-wide"
    assert cell.params == {"logit_gap_limit": 1.0}
    assert "answer" in [m["name"] for m in cell.per_layer]
    assert cell.reader("answer")(None) == 42.0
    assert "answer" not in [m["name"] for m in spec.Cell("tiny-olmo.reason", tiny_root).per_layer]
    after = {p: p.read_bytes() for p in before}
    changed = [p for p in before if before[p] != after[p]]
    assert changed == [], changed


def test_unknown_cell():
    with pytest.raises(KeyError):
        spec.Cell("no-such-model.reason")
