"""Fused decode-path kernels vs pure-jnp oracles, interpret mode.

The serving hot path dispatches two fused Pallas ops (see
``repro.kernels``): ``decode_attention`` (KV row scatter + single-row
attention read, no updated slab materialized in HBM) and
``emit_norm_logits`` (final-norm + logits head).  The refs are verbatim
the unfused model ops.  In bf16 the emit kernel and a single-block
decode attention are bitwise equal to them; a decode attention that
walks several cache blocks is held to one bf16 ulp (see
``_assert_matches``).  Also covers the dispatch registry and the
training-path rejection.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import KERNEL_MODES, get_impl, resolve_mode
from repro.kernels.decode_attention.ops import fused_decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.emit_norm_logits.ops import emit_norm_logits
from repro.kernels.emit_norm_logits.ref import emit_norm_logits_ref


def _bitwise(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    return bool((a == b).all())


def _assert_matches(out, ref, dtype, *, blocks=1):
    """bf16 (the serving dtype), one cache block: bitwise — the fp32
    intermediates round through the same bf16 cast.  Several blocks: the
    online softmax sums block by block and normalizes after the V
    reduction, so the fp32 value moves by rounding and the bf16 output
    by at most one ulp (2**-7 relative).  fp32: a few ULPs — XLA's CPU
    gemm/softmax reduction blocking differs between the batched ref and
    the kernel's per-row math for some shapes, so exact fp32 bit
    equality would be shape-dependent."""
    if dtype == jnp.bfloat16 and blocks == 1:
        assert _bitwise(out, ref)
    elif dtype == jnp.bfloat16:
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=2**-7, atol=2**-7)
    else:
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6)


def _decode_case(rng, b, s, h, kv, dh, dtype, pos):
    q = jnp.asarray(rng.normal(size=(b, 1, h, dh)), dtype)
    k_new = jnp.asarray(rng.normal(size=(b, kv, dh)), dtype)
    v_new = jnp.asarray(rng.normal(size=(b, kv, dh)), dtype)
    k_cache = jnp.asarray(rng.normal(size=(b, s, kv, dh)), dtype)
    v_cache = jnp.asarray(rng.normal(size=(b, s, kv, dh)), dtype)
    pos = jnp.asarray(pos, jnp.int32)
    kv_len = pos + 1
    return q, k_new, v_new, k_cache, v_cache, pos, kv_len


# (dtype, block_s): block_s None is one block over the whole cache; a
# smaller block_s walks several blocks (the max_len 2048 serving shape).
BLOCK_CASES = [
    (jnp.float32, None), (jnp.bfloat16, None),
    (jnp.float32, 4), (jnp.bfloat16, 4),
]


class TestDecodeAttentionKernel:
    @pytest.mark.parametrize("dtype,block_s", BLOCK_CASES, ids=str)
    def test_ragged_positions_bitwise(self, dtype, block_s):
        """Every row at a different depth — the steady decode tick."""
        rng = np.random.default_rng(0)
        b, s, h, kv, dh = 4, 16, 4, 2, 16
        pos = np.array([0, 5, 11, 15])  # includes fresh row and boundary
        q, kn, vn, kc, vc, pos, kvl = _decode_case(rng, b, s, h, kv, dh, dtype, pos)
        out = fused_decode_attention(
            q, kn, vn, kc, vc, pos=pos, kv_len=kvl, interpret=True,
            **({} if block_s is None else {"block_s": block_s}))
        ref = decode_attention_ref(q, kn, vn, kc, vc, pos=pos, kv_len=kvl)
        assert out.shape == ref.shape == (b, 1, h, dh)
        _assert_matches(out, ref, dtype, blocks=s // (block_s or s))

    @pytest.mark.parametrize("dtype,block_s", BLOCK_CASES, ids=str)
    def test_max_len_boundary(self, dtype, block_s):
        """All rows writing the last cache slot (pos == max_len - 1)."""
        rng = np.random.default_rng(1)
        b, s, h, kv, dh = 3, 8, 2, 2, 8
        q, kn, vn, kc, vc, pos, kvl = _decode_case(
            rng, b, s, h, kv, dh, dtype, np.full(3, s - 1))
        out = fused_decode_attention(
            q, kn, vn, kc, vc, pos=pos, kv_len=kvl, interpret=True,
            **({} if block_s is None else {"block_s": block_s}))
        ref = decode_attention_ref(q, kn, vn, kc, vc, pos=pos, kv_len=kvl)
        _assert_matches(out, ref, dtype, blocks=s // (block_s or s))

    @pytest.mark.parametrize("dtype,block_s", BLOCK_CASES, ids=str)
    def test_admission_rows(self, dtype, block_s):
        """Mid-round admissions: freshly prefilled rows (pos=0, garbage
        cache beyond the valid prefix) mixed with deep rows — the mask
        must come from kv_len, never from cache contents."""
        rng = np.random.default_rng(2)
        b, s, h, kv, dh = 4, 12, 4, 4, 16
        q, kn, vn, kc, vc, pos, kvl = _decode_case(
            rng, b, s, h, kv, dh, dtype, np.array([0, 9, 0, 3]))
        # poison the invalid region of the fresh rows
        kc = kc.at[0, 1:].set(jnp.asarray(1e4, dtype))
        vc = vc.at[0, 1:].set(jnp.asarray(1e4, dtype))
        out = fused_decode_attention(
            q, kn, vn, kc, vc, pos=pos, kv_len=kvl, interpret=True,
            **({} if block_s is None else {"block_s": block_s}))
        ref = decode_attention_ref(q, kn, vn, kc, vc, pos=pos, kv_len=kvl)
        _assert_matches(out, ref, dtype, blocks=s // (block_s or s))
        assert bool(jnp.isfinite(out.astype(jnp.float32)).all())

    def test_under_jit_matches_eager_ref(self):
        """The engine calls the kernel from inside a jitted round body."""
        rng = np.random.default_rng(3)
        b, s, h, kv, dh = 2, 8, 2, 1, 8
        q, kn, vn, kc, vc, pos, kvl = _decode_case(
            rng, b, s, h, kv, dh, jnp.bfloat16, np.array([2, 7]))
        out = jax.jit(
            lambda *a: fused_decode_attention(
                *a[:5], pos=a[5], kv_len=a[6], interpret=True)
        )(q, kn, vn, kc, vc, pos, kvl)
        ref = decode_attention_ref(q, kn, vn, kc, vc, pos=pos, kv_len=kvl)
        assert _bitwise(out, ref)


EMIT_CASES = [
    # norm, tied, dtype
    ("rmsnorm", False, jnp.float32),
    ("rmsnorm", False, jnp.bfloat16),
    ("rmsnorm", True, jnp.bfloat16),
    ("layernorm_nonparam", True, jnp.float32),
    ("layernorm_nonparam", True, jnp.bfloat16),
    ("layernorm_nonparam", False, jnp.bfloat16),
]


class TestEmitNormLogitsKernel:
    @pytest.mark.parametrize("norm,tied,dtype", EMIT_CASES, ids=str)
    def test_bitwise_vs_ref(self, norm, tied, dtype):
        rng = np.random.default_rng(4)
        b, d, v = 3, 32, 96  # v not a multiple of 512: block_v walks down
        x = jnp.asarray(rng.normal(size=(b, 1, d)), dtype)
        w = jnp.asarray(
            rng.normal(size=(v, d) if tied else (d, v)) * 0.1, dtype)
        scale = (jnp.asarray(rng.normal(size=(d,)) * 0.2 + 1.0, dtype)
                 if norm == "rmsnorm" else None)
        out = emit_norm_logits(
            x, w, norm=norm, scale=scale, tied=tied, interpret=True)
        ref = emit_norm_logits_ref(x, w, norm=norm, scale=scale, tied=tied)
        assert out.dtype == jnp.float32 and out.shape == (b, v)
        # bf16 bitwise; fp32 to a few ULPs: with the kernel's explicit
        # fp32 accumulation XLA's CPU backend fuses the rmsnorm scale into
        # the interpreted fp32 dot differently from the unfused einsum.
        _assert_matches(out, ref, dtype)

    def test_bitwise_vs_jitted_ref_bf16(self):
        """The hard case: under jit, XLA elides the f32->bf16->f32
        round-trip only for directly-chained dot->convert.  The kernel
        stores its output block in the input dtype and upcasts outside
        the pallas call, so it matches the ref both eager and jitted."""
        rng = np.random.default_rng(5)
        b, d, v = 2, 64, 128
        x = jnp.asarray(rng.normal(size=(b, 1, d)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(d, v)) * 0.1, jnp.bfloat16)
        scale = jnp.asarray(rng.normal(size=(d,)) * 0.2 + 1.0, jnp.bfloat16)
        kw = dict(norm="rmsnorm", scale=scale, tied=False)
        out = jax.jit(
            lambda a, b_: emit_norm_logits(a, b_, interpret=True, **kw)
        )(x, w)
        ref_eager = emit_norm_logits_ref(x, w, **kw)
        ref_jit = jax.jit(lambda a, b_: emit_norm_logits_ref(a, b_, **kw))(x, w)
        assert _bitwise(out, ref_eager)
        assert _bitwise(out, ref_jit)

    def test_bad_norm_rejected(self):
        x = jnp.zeros((1, 1, 8)); w = jnp.zeros((8, 16))
        with pytest.raises(ValueError):
            emit_norm_logits(x, w, norm="batchnorm")


class TestKernelRegistry:
    def test_resolve_mode(self):
        assert resolve_mode(None) == "xla"
        assert resolve_mode("xla") == "xla"
        assert resolve_mode("pallas") == "pallas"
        assert resolve_mode("auto") in ("xla", "pallas")
        with pytest.raises(ValueError):
            resolve_mode("cuda")

    def test_get_impl_dispatch(self):
        assert get_impl("decode_attention", "xla") is decode_attention_ref
        assert get_impl("decode_attention", "pallas") is fused_decode_attention
        assert get_impl("emit_norm_logits", "xla") is emit_norm_logits_ref
        assert get_impl("emit_norm_logits", "pallas") is emit_norm_logits
        with pytest.raises(ValueError):
            get_impl("decode_attention", "cuda")
        with pytest.raises(ValueError):
            get_impl("conv3d", "xla")

    def test_legacy_ops_exported(self):
        from repro.kernels.flash_attention.ops import flash_attention
        from repro.kernels.rmsnorm.ops import rmsnorm
        from repro.kernels.ssd.ops import ssd_chunked_pallas

        assert get_impl("attention", "pallas") is flash_attention
        assert get_impl("rmsnorm", "pallas") is rmsnorm
        assert get_impl("ssd", "pallas") is ssd_chunked_pallas
        for op in ("attention", "rmsnorm", "ssd"):
            assert callable(get_impl(op, "xla"))

    def test_train_step_rejects_pallas(self):
        from repro.configs.registry import get_config, smoke_config
        from repro.train.optimizer import AdamWConfig
        from repro.train.train_step import TrainConfig, make_train_step

        cfg = smoke_config(get_config("olmo-1b"))
        ocfg = AdamWConfig(learning_rate=1e-3, warmup_steps=1, total_steps=2)
        with pytest.raises(ValueError, match="no VJPs"):
            make_train_step(cfg, TrainConfig(kernels="pallas"), ocfg)
        with pytest.raises(ValueError, match="planned"):
            make_train_step(
                cfg,
                TrainConfig(kernels="pallas", pipeline_backward="planned"),
                ocfg,
            )
        # auto resolves to xla off-TPU: accepted
        make_train_step(cfg, TrainConfig(kernels="auto"), ocfg)
