"""Published peaks of each accelerator the benchmark may run on.

Keyed by ``jax.Device.device_kind``.  A device that is not listed is an
error, never a default: a roofline share against a guessed peak is not a
measurement.

TPU v5e (reported by JAX as "TPU v5 lite"): Google Cloud documentation,
"TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; raises for an unknown device."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
