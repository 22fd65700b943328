"""The Stream-shaped serving gate: pipelined decode bit-identity.

One subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=4
runs a mixed prefill/decode workload (more requests than slots, ragged
prompt lengths, mixed budgets — so slots retire and admit mid-flight)
through the sequential reference ``Engine`` and through ``StreamEngine``
under ``FutureEvaluator`` on 4 devices for both gpipe and interleaved
(V=2) schedules.  Greedy outputs must match token for token — the
paper's monad substitution applied to serving: same program text, Lazy
swapped for Future, results bit-identical.
"""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.multidevice

SCRIPT = r"""
import os
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

sc = smoke_config(get_config("olmo-1b")).with_overrides(num_layers=8)
params = init_params(jax.random.PRNGKey(0), T.model_layout(sc))
mesh = jax.make_mesh((4,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))

scfg = ServeConfig(max_batch=8, max_len=64, prefill_chunk=4, max_new_tokens=6)
rng = np.random.default_rng(7)
prompts = [rng.integers(1, sc.vocab_size, size=int(rng.integers(1, 9)))
           for _ in range(14)]
budgets = [int(b) for b in rng.integers(1, 8, size=14)]

ref = Engine(params, sc, scfg)
reqs_ref = [ref.submit(p, b) for p, b in zip(prompts, budgets)]
ref.run_until_drained()

for sched, v, cells, m in [("gpipe", 1, 8, 8), ("interleaved", 2, 8, 4)]:
    pcfg = DecodePipelineConfig(num_cells=cells, microbatches=m,
                                schedule=sched, interleave=v,
                                round_steps=4, admit_per_round=4)
    eng = StreamEngine(params, sc, scfg, pcfg, mesh=mesh)
    reqs = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
    done = eng.run_until_drained()
    ok = len(done) == len(prompts) and all(
        rb.done and ra.out_tokens == rb.out_tokens
        for ra, rb in zip(reqs_ref, reqs)
    )
    print(f"SERVE_{sched.upper()}", ok)

# temperature sampling: per-request RNG identical under the pipeline
scfg_t = ServeConfig(max_batch=8, max_len=64, prefill_chunk=4,
                     max_new_tokens=5, temperature=0.9, seed=11)
ref_t = Engine(params, sc, scfg_t)
rt_ref = [ref_t.submit(p, b) for p, b in zip(prompts[:10], budgets[:10])]
ref_t.run_until_drained()
eng_t = StreamEngine(params, sc, scfg_t, DecodePipelineConfig(
    num_cells=8, microbatches=8, schedule="gpipe", round_steps=4,
    admit_per_round=4), mesh=mesh)
rt = [eng_t.submit(p, b) for p, b in zip(prompts[:10], budgets[:10])]
eng_t.run_until_drained()
print("SERVE_TEMPERATURE", all(
    a.out_tokens == b.out_tokens for a, b in zip(rt_ref, rt)))

# emit split: the round program's only logits-width matmul must live
# behind the plan-keyed emit conditional (region isolation in the SPMD
# module), and the plan's emit column must be zero on every non-final
# device — together: no non-final device's executed tick body contains
# the LM head.
from repro.roofline.hlo_parse import head_matmul_conditional_only


def round_text(eng, pcfg):
    adm, _ = eng._plan_admissions(pcfg.round_steps)
    ii, ov, ap = eng._build_round_inputs(adm)
    return eng._round.lower(
        {**eng.cell_consts, "adm": ap}, eng.cell_states, ii, ov
    ).compile().as_text()


texts = {}
for sched, v, cells, m in [("gpipe", 1, 8, 8), ("interleaved", 2, 8, 4)]:
    pcfg_h = DecodePipelineConfig(num_cells=cells, microbatches=m,
                                  schedule=sched, interleave=v,
                                  round_steps=4, admit_per_round=4)
    eng_h = StreamEngine(params, sc, scfg, pcfg_h, mesh=mesh)
    txt = round_text(eng_h, pcfg_h)
    texts[sched] = txt
    guarded = head_matmul_conditional_only(txt, sc.vocab_size)
    plan = eng_h.evaluator.plan_for(
        pcfg_h.round_steps * m, (0, 0), feedback_lag=m)
    last_only = bool((plan.emit[:, :3] == 0).all()) and int(plan.emit.sum()) > 0
    print(f"EMIT_SPLIT_{sched.upper()}", guarded and last_only)

# Pallas decode cells: same pipelined battery with the fused
# decode-attention + emit kernels (interpret-emulated on CPU) — tokens
# must stay bit-identical to the sequential xla reference.
pcfg_p = DecodePipelineConfig(num_cells=8, microbatches=8, schedule="gpipe",
                              round_steps=4, admit_per_round=4,
                              kernels="pallas")
eng_p = StreamEngine(params, sc, scfg, pcfg_p, mesh=mesh)
reqs_p = [eng_p.submit(p, b) for p, b in zip(prompts, budgets)]
done_p = eng_p.run_until_drained()
print("SERVE_GPIPE_PALLAS", len(done_p) == len(prompts) and all(
    rb.done and ra.out_tokens == rb.out_tokens
    for ra, rb in zip(reqs_ref, reqs_p)))

# Structural pins on the compiled round HLO (positive + negative
# controls): the fused-kernel name scopes appear only in the pallas
# module; the pallas steady tick carries at most half the xla module's
# slab-sized cache writes (the per-layer K/V slab materializations are
# gone — what remains is admission row traffic); and the LM head stays
# conditional-guarded with the fused emit in place.
from repro.kernels.decode_attention.ops import FUSION_SCOPE as ATTN_SCOPE
from repro.kernels.emit_norm_logits.ops import FUSION_SCOPE as EMIT_SCOPE
from repro.roofline.hlo_parse import fused_region_present, slab_scatter_counts

txt_xla = texts["gpipe"]
txt_pallas = round_text(eng_p, pcfg_p)
print("HLO_MARKER_PALLAS", fused_region_present(txt_pallas, ATTN_SCOPE)
      and fused_region_present(txt_pallas, EMIT_SCOPE))
print("HLO_MARKER_XLA_ABSENT", not fused_region_present(txt_xla, ATTN_SCOPE)
      and not fused_region_present(txt_xla, EMIT_SCOPE))
mb = scfg.max_batch // pcfg_p.microbatches
slab = (mb * scfg.max_len * sc.num_kv_heads * sc.head_dim
        * jax.numpy.dtype(sc.dtype).itemsize)
tot_x, ung_x = slab_scatter_counts(txt_xla, slab)
tot_p, ung_p = slab_scatter_counts(txt_pallas, slab)
# The group body's K+V slab materializations (one static pair — the
# layer scan counts its body once) must be gone; the writes both modes
# share are admission-buffer row traffic, which stays.
print("HLO_SLAB_SCATTER", tot_x > 0 and tot_p <= tot_x - 2
      and ung_p <= ung_x, f"xla={tot_x}/{ung_x} pallas={tot_p}/{ung_p}")
print("HLO_HEAD_GUARD_PALLAS",
      head_matmul_conditional_only(txt_pallas, sc.vocab_size))
"""


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, env=env, timeout=1500,
        stdin=subprocess.DEVNULL,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return dict(
        line.split(None, 1) for line in proc.stdout.strip().splitlines()
    )


def test_pipelined_gpipe_bit_identical(report):
    assert report["SERVE_GPIPE"].startswith("True")


def test_pipelined_interleaved_bit_identical(report):
    assert report["SERVE_INTERLEAVED"].startswith("True")


def test_pipelined_temperature_sampling_identical(report):
    assert report["SERVE_TEMPERATURE"].startswith("True")


def test_emit_split_head_matmul_last_stage_only_gpipe(report):
    # acceptance: the LM head is conditional-guarded in the compiled
    # round HLO and the plan's emit column fires only on device D-1
    assert report["EMIT_SPLIT_GPIPE"].startswith("True")


def test_emit_split_head_matmul_last_stage_only_interleaved(report):
    assert report["EMIT_SPLIT_INTERLEAVED"].startswith("True")


def test_pipelined_pallas_bit_identical(report):
    # kernels="pallas" through the 4-device FutureEvaluator: fused decode
    # attention + emit epilogue, tokens identical to the xla reference
    assert report["SERVE_GPIPE_PALLAS"].startswith("True")


def test_fusion_markers_present_in_pallas_hlo_only(report):
    # positive control: both kernel name scopes in the pallas module...
    assert report["HLO_MARKER_PALLAS"].startswith("True")
    # ...negative control: neither in the xla module
    assert report["HLO_MARKER_XLA_ABSENT"].startswith("True")


def test_pallas_round_drops_steady_tick_slab_writes(report):
    # the layer-scan body's K/V slab materializations are gone from the
    # pallas round; remaining slab-sized writes are admission traffic
    # both modes share
    assert report["HLO_SLAB_SCATTER"].startswith("True")


def test_head_matmul_stays_guarded_under_pallas(report):
    assert report["HLO_HEAD_GUARD_PALLAS"].startswith("True")
