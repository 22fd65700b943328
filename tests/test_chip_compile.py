"""The serving kernels compile for a TPU v5e chip at olmo-1b widths.

Ahead-of-time compiles for a described, unattached ``v5e:2x2`` chip:
Mosaic refuses here what the Pallas interpreter accepts (unaligned
blocks, non-fp32 matmul accumulators, more VMEM than a kernel may use),
so these catch a kernel that cannot run on the chip without spending
chip time.  Nothing runs; a compile is not a measurement.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and each test
worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.ops import fused_decode_attention
from repro.kernels.emit_norm_logits.ops import emit_norm_logits

# olmo-1b (configs/olmo_1b.py) served at max_batch 8, max_len 2048.
BATCH, MAX_LEN, HEADS, KV_HEADS, HEAD_DIM = 8, 2048, 16, 16, 128
D_MODEL, VOCAB = 2048, 50304


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_decode_attention_compiles_for_v5e(one_chip):
    bf16 = jnp.bfloat16
    args = (
        _spec((BATCH, 1, HEADS, HEAD_DIM), bf16, one_chip),
        _spec((BATCH, KV_HEADS, HEAD_DIM), bf16, one_chip),
        _spec((BATCH, KV_HEADS, HEAD_DIM), bf16, one_chip),
        _spec((BATCH, MAX_LEN, KV_HEADS, HEAD_DIM), bf16, one_chip),
        _spec((BATCH, MAX_LEN, KV_HEADS, HEAD_DIM), bf16, one_chip),
        _spec((BATCH,), jnp.int32, one_chip),
        _spec((BATCH,), jnp.int32, one_chip),
    )
    fn = jax.jit(
        lambda q, kn, vn, kc, vc, pos, kvl: fused_decode_attention(
            q, kn, vn, kc, vc, pos=pos, kv_len=kvl, interpret=False
        )
    )
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_emit_norm_logits_compiles_for_v5e(one_chip):
    bf16 = jnp.bfloat16
    fn = jax.jit(
        lambda x, w: emit_norm_logits(
            x, w, norm="layernorm_nonparam", tied=True, interpret=False
        )
    )
    compiled = fn.lower(
        _spec((BATCH, 1, D_MODEL), bf16, one_chip),
        _spec((VOCAB, D_MODEL), bf16, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
