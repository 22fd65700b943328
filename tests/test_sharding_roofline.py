"""Sharding rule resolution, fit_spec properties, HLO parsing, analytic flops."""
import pytest

from _hypothesis_stub import hypothesis, st  # skips @given tests offline
import numpy as np
from jax.sharding import PartitionSpec as P

import jax

from repro.parallel import sharding as SH
from repro.roofline import analytic as AN
from repro.roofline.hlo_parse import analyze_hlo, loop_multipliers, parse_module, shape_bytes


class FakeMesh:
    """Duck-typed mesh: only .axis_names and .shape are consulted."""

    def __init__(self, shape: dict):
        self.axis_names = tuple(shape)
        self.shape = shape


MESH = FakeMesh({"data": 16, "model": 16})
MESH3 = FakeMesh({"pod": 2, "data": 16, "model": 16})


class TestFitSpec:
    @hypothesis.given(
        st.lists(st.sampled_from([None, "data", "model", ("data", "model")]),
                 min_size=1, max_size=4),
        st.lists(st.sampled_from([1, 8, 16, 20, 24, 64, 256, 50280]),
                 min_size=1, max_size=4),
    )
    @hypothesis.settings(max_examples=60, deadline=None)
    def test_always_legal(self, parts, dims):
        n = min(len(parts), len(dims))
        spec, shape = P(*parts[:n]), tuple(dims[:n])
        out = SH.fit_spec(spec, shape, MESH)
        used = []
        for d, part in enumerate(out):
            if part is None:
                continue
            axes = part if isinstance(part, tuple) else (part,)
            prod = int(np.prod([MESH.shape[a] for a in axes]))
            assert shape[d] % prod == 0  # divisibility
            used.extend(axes)
        assert len(used) == len(set(used))  # no duplicate mesh axes

    def test_dedup_keeps_first(self):
        out = SH.fit_spec(P("model", "model"), (32, 32), MESH)
        assert out == P("model")

    def test_indivisible_heads_replicated(self):
        out = SH.fit_spec(P(None, "data", "model"), (48, 1536, 24), MESH)
        assert out == P(None, "data")

    def test_tuple_axis_partial_drop(self):
        # 32 % (2*16) == 0 keeps both; 16 % 32 != 0 drops from the right
        assert SH.fit_spec(P(("pod", "data")), (32,), MESH3) == P(("pod", "data"))
        # normalized singleton: P("pod"), not P(("pod",))
        assert SH.fit_spec(P(("pod", "data")), (2,), MESH3) == P("pod")

    def test_prune_removes_missing_axes(self):
        assert SH.prune_spec(P(("pod", "data"), "model"), MESH) == P("data", "model")

    def test_rules_have_no_conflicts_per_ruleset(self):
        from repro.models.transformer import cache_logical_axes
        from repro.configs.registry import ARCH_IDS, get_config

        for rules in (SH.DECODE_RULES, SH.PREFILL_RULES, SH.LONG_DECODE_RULES):
            for arch in ARCH_IDS:
                axes = cache_logical_axes(get_config(arch))
                for leaf_axes in jax.tree.leaves(
                    axes, is_leaf=lambda x: isinstance(x, tuple)
                ):
                    spec = SH.spec_for(leaf_axes, rules)
                    SH.fit_spec(spec, (48, 256, 512, 16, 128)[: len(leaf_axes)], MESH3)


SAMPLE_HLO = """
HloModule test

%body (p: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %p = (s32[], f32[8,128]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,128] get-tuple-element(%p), index=1
  %ar = f32[8,128] all-reduce(%x), to_apply=%sum
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,128]) tuple(%ni, %ar)
}

%cond (p: (s32[], f32[8,128])) -> pred[] {
  %p = (s32[], f32[8,128]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(10)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,128]) -> f32[8,128] {
  %a = f32[8,128] parameter(0)
  %ag = f32[16,128] all-gather(%a), dimensions={0}
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8,128]) tuple(%z, %a)
  %w = (s32[], f32[8,128]) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %out = f32[8,128] get-tuple-element(%w), index=1
}
"""


class TestHloParse:
    def test_shape_bytes(self):
        assert shape_bytes("f32[8,128]") == 8 * 128 * 4
        assert shape_bytes("bf16[2,3]{1,0}") == 12
        assert shape_bytes("(s32[], f32[4,4])") == 4 + 64

    def test_loop_multiplier_applied(self):
        out = analyze_hlo(SAMPLE_HLO)
        # all-reduce inside 10-trip loop: 10 × 4096B × 2 (ring factor)
        assert out["collective_bytes_by_kind"]["all-reduce"] == 10 * 8 * 128 * 4
        assert out["collective_bytes_by_kind"]["all-gather"] == 16 * 128 * 4
        assert out["collective_counts_dynamic"]["all-reduce"] == 10
        assert out["collective_counts_static"]["all-reduce"] == 1

    def test_multipliers(self):
        comps, entry = parse_module(SAMPLE_HLO)
        mult = loop_multipliers(comps, entry)
        assert mult[entry] == 1.0
        assert mult["body"] == 10.0

    def test_real_compiled_module_parses(self):
        import jax.numpy as jnp

        def f(x):
            def step(c, _):
                return c * 2.0, None
            out, _ = jax.lax.scan(step, x, None, length=7)
            return out

        hlo = jax.jit(f).lower(jnp.ones((4, 4))).compile().as_text()
        out = analyze_hlo(hlo)
        assert out["num_loops"] >= 0  # parses without error


class TestConditionalGuard:
    """The emit-split checker must be *sound*: an unconditional head
    matmul may never count as guarded — including when XLA fuses it
    (fusion bodies are referenced via ``calls=``, which the unguarded
    BFS must traverse)."""

    V = 2048

    def _w(self):
        import jax.numpy as jnp

        return jnp.zeros((64, self.V), jnp.float32)

    def test_unconditional_fused_head_is_flagged(self):
        import jax.numpy as jnp

        from repro.roofline.hlo_parse import head_matmul_conditional_only

        w = self._w()
        # + bias so the dot fuses on CPU: the checker must still see it
        f = jax.jit(lambda x: jnp.tanh(x @ w + 1.0))
        hlo = f.lower(jnp.zeros((4, 64), jnp.float32)).compile().as_text()
        assert "calls=" in hlo  # the fusion edge this test pins
        assert head_matmul_conditional_only(hlo, self.V) is False

    def test_cond_guarded_head_passes(self):
        import jax.numpy as jnp
        from jax import lax

        from repro.roofline.hlo_parse import head_matmul_conditional_only

        w = self._w()
        g = jax.jit(
            lambda p, x: lax.cond(
                p > 0,
                lambda y: jnp.tanh(y @ w + 1.0),
                lambda y: jnp.zeros((4, self.V)),
                x,
            )
        )
        hlo = g.lower(
            jnp.int32(0), jnp.zeros((4, 64), jnp.float32)
        ).compile().as_text()
        assert head_matmul_conditional_only(hlo, self.V) is True

    def test_no_head_at_all_is_not_a_pass(self):
        import jax.numpy as jnp

        from repro.roofline.hlo_parse import head_matmul_conditional_only

        f = jax.jit(lambda x: x * 2.0)
        hlo = f.lower(jnp.zeros((4, 64), jnp.float32)).compile().as_text()
        # total == 0 must fail: "no matmul found" is a broken probe,
        # not a guarded one
        assert head_matmul_conditional_only(hlo, self.V) is False


class TestAnalyticFlops:
    def test_dense_matches_hand_count(self):
        from repro.configs.base import ArchConfig, ShapeCell

        cfg = ArchConfig(
            name="tiny", family="dense", num_layers=2, d_model=64,
            num_heads=4, num_kv_heads=4, d_ff=128, vocab_size=256, head_dim=16,
        )
        shape = ShapeCell("t", seq_len=32, global_batch=2, kind="prefill")
        f = AN.forward_flops(cfg, shape.tokens, 2, 32)
        t = shape.tokens
        # qkv+o proj: 2*t*d*(h+2kv)*dh + 2*t*h*dh*d
        proj = 2 * t * 64 * (4 + 8) * 16 + 2 * t * 4 * 16 * 64
        attn = 2 * 2 * t * 32 * 4 * 16
        ffn = 2 * 3 * t * 64 * 128
        head = 2 * t * 64 * 256
        assert f["proj"] == proj * 2
        assert f["attn"] == attn * 2
        assert f["ffn"] == ffn * 2
        assert f["head"] == head

    def test_train_multiplier(self):
        from repro.configs.base import SHAPES
        from repro.configs.registry import get_config

        cfg = get_config("olmo-1b")
        tr = AN.step_flops(cfg, SHAPES["train_4k"], remat=True)["total"]
        no_remat = AN.step_flops(cfg, SHAPES["train_4k"], remat=False)["total"]
        assert tr > no_remat

    def test_moe_counts_active_only(self):
        from repro.configs.base import SHAPES
        from repro.configs.registry import get_config

        cfg = get_config("llama4-maverick-400b-a17b")
        f = AN.step_flops(cfg, SHAPES["prefill_32k"])["total"]
        # active ~17B params at 1M tokens: 2ND = 3.5e16; full 400B would be 8e17.
        assert f < 3e17

    def test_decode_flops_scale_with_batch_not_seq(self):
        from repro.configs.base import SHAPES
        from repro.configs.registry import get_config

        cfg = get_config("qwen3-32b")
        dec = AN.step_flops(cfg, SHAPES["decode_32k"])["total"]
        pre = AN.step_flops(cfg, SHAPES["prefill_32k"])["total"]
        assert dec < pre / 100
