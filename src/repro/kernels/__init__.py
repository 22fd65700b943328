"""Pallas kernel library + the per-op dispatch registry.

Each op lives in its own package (``kernel.py`` = the Pallas body,
``ops.py`` = the jit'd layout-adapting wrapper, ``ref.py`` = the pure-jnp
oracle the kernel is tested bitwise/tolerance against):

* ``flash_attention``  — tiled online-softmax attention (prefill/train).
* ``rmsnorm``          — fused reduce+rsqrt+scale, one VMEM pass.
* ``ssd``              — Mamba-2 SSD intra-chunk kernel.
* ``decode_attention`` — the serving hot path: fuses the per-tick KV row
  scatter with the single-row attention read, so no updated slab is ever
  materialized in HBM (the row lands in VMEM only).
* ``emit_norm_logits`` — decode-emit epilogue: final norm + LM-head
  matmul in one pass over vocab tiles.

Model code selects implementations through :func:`get_impl` driven by the
``kernels`` config knob (``"xla" | "pallas" | "auto"``) instead of
hard-coding XLA.  ``"auto"`` (the default) resolves to ``"pallas"`` on
TPU and ``"xla"`` elsewhere; an explicit ``"pallas"`` off-TPU runs the
kernels under the Pallas interpreter (kernel logic without Mosaic) —
that is what keeps the tier-1 parity batteries runnable on CPU.
Interpret mode exists for those tests only: on a TPU the kernels always
compile through Mosaic, and a kernel that fails there raises.
"""
from __future__ import annotations

import jax

KERNEL_MODES = ("xla", "pallas", "auto")

# op -> (module path, wrapper attr) for the pallas side; the xla side is
# the op's pure-jnp reference (same call signature).
_PALLAS_IMPLS = {
    "attention": ("repro.kernels.flash_attention.ops", "flash_attention"),
    "rmsnorm": ("repro.kernels.rmsnorm.ops", "rmsnorm"),
    "ssd": ("repro.kernels.ssd.ops", "ssd_chunked_pallas"),
    "decode_attention": (
        "repro.kernels.decode_attention.ops", "fused_decode_attention"
    ),
    "emit_norm_logits": (
        "repro.kernels.emit_norm_logits.ops", "emit_norm_logits"
    ),
}
_XLA_IMPLS = {
    "attention": ("repro.kernels.flash_attention.ref", "attention_ref"),
    "rmsnorm": ("repro.kernels.rmsnorm.ref", "rmsnorm_ref"),
    "ssd": ("repro.kernels.ssd.ref", "ssd_ref"),
    "decode_attention": (
        "repro.kernels.decode_attention.ref", "decode_attention_ref"
    ),
    "emit_norm_logits": (
        "repro.kernels.emit_norm_logits.ref", "emit_norm_logits_ref"
    ),
}

OPS = tuple(_PALLAS_IMPLS)


def on_tpu() -> bool:
    """Single source of the backend autodetect every ops.py used to copy."""
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Pallas interpret-mode default: emulate the kernel off-TPU."""
    return not on_tpu()


def resolve_mode(mode: str | None) -> str:
    """Validate the ``kernels`` knob and collapse ``auto`` to a backend."""
    if mode is None:
        mode = "auto"
    if mode not in KERNEL_MODES:
        raise ValueError(
            f"kernels={mode!r}; expected one of {KERNEL_MODES}"
        )
    if mode == "auto":
        return "pallas" if on_tpu() else "xla"
    return mode


def get_impl(op: str, mode: str = "auto"):
    """The implementation of ``op`` under the ``kernels`` mode.

    ``"pallas"`` returns the kernel's jit'd wrapper (interpret-mode
    off-TPU), ``"xla"`` the pure-jnp reference with the same signature.
    Imports lazily so importing the package never pulls Pallas in.
    """
    table = {"pallas": _PALLAS_IMPLS, "xla": _XLA_IMPLS}[resolve_mode(mode)]
    if op not in table:
        raise ValueError(f"unknown kernel op {op!r}; have {OPS}")
    module_path, attr = table[op]
    import importlib

    return getattr(importlib.import_module(module_path), attr)
