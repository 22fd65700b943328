"""The serving kernels compile for a TPU v5e chip at olmo-1b widths,
and the serving round keeps its KV cache in place.

Ahead-of-time compiles for a described, unattached ``v5e:2x2`` chip:
Mosaic refuses here what the Pallas interpreter accepts (unaligned
blocks, non-fp32 matmul accumulators, more VMEM than a kernel may use),
so these catch a kernel that cannot run on the chip without spending
chip time; and the chip's compiler, not the CPU's, decides what the
round's loops copy.  Nothing runs; a compile is not a measurement.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and each test
worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels
from repro.configs.base import DecodePipelineConfig
from repro.configs.registry import get_config
from repro.kernels.decode_attention.ops import fused_decode_attention
from repro.kernels.emit_norm_logits.ops import emit_norm_logits
from repro.models import transformer as T
from repro.models.params import init_params
from repro.roofline.hlo_parse import loop_state_copies
from repro.serve.engine import ServeConfig, StreamEngine

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


def test_lazy_round_moves_no_cache_shard_inside_its_loops(one_chip, monkeypatch):
    """The one-chip serving round (``StreamEngine`` under
    ``LazyEvaluator``, fused kernels as on the chip) at small widths:
    4 cells, 4 microbatches, ``max_len`` 256.  Inside its loops no copy,
    dynamic-slice or dynamic-update-slice moves as much as one cell's K
    (or V) shard: the cells write cache rows in place.  The split into
    per-cell buffers and the restack run once a round, outside the
    loops, and the donated cache still aliases the round's output."""
    monkeypatch.setattr(repro.kernels, "on_tpu", lambda: True)
    cfg = get_config("olmo-1b").with_overrides(
        num_layers=8, d_model=256, num_heads=2, num_kv_heads=2, d_ff=512,
        vocab_size=512,
    )
    params = init_params(jax.random.PRNGKey(0), T.model_layout(cfg))
    engine = StreamEngine(
        params, cfg, ServeConfig(max_batch=8, max_len=256, prefill_chunk=128),
        DecodePipelineConfig(num_cells=4, microbatches=4),
    )
    init_items, overlay, adm = engine._build_round_inputs([])
    args = ({**engine.cell_consts, "adm": adm}, engine.cell_states,
            init_items, overlay)
    specs = jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip), args
    )
    # Donated as on the chip (the CPU-built engine skips donation).
    compiled = (
        jax.jit(engine._round.__wrapped__, donate_argnums=(1,))
        .lower(*specs).compile()
    )
    text = compiled.as_text()
    assert " while(" in text
    k = engine.cell_states["cache"]["block0"]["k"]
    shard = k.nbytes // k.shape[0]
    moves = [
        f"{comp}: {ins.name} {ins.opcode} {ins.shape}"
        for comp, ins in loop_state_copies(text, shard)
    ]
    assert not moves, moves
    cache_bytes = sum(l.nbytes for l in jax.tree.leaves(engine.cell_states))
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
