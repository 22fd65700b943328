"""decode_attention_roofline: the fused decode-attention kernel's share of its roofline.

Work is counted from shapes and each decoded row's live context
(``kv_len``, the rows it attends to), per layer: FLOPs 4 x heads x
head_dim x kv_len (QK^T and PV); bytes (bf16) of the K and V prefix
(kv_len rows each, the new row among them), q and the output.  No
allocated slab and no implementation term enter the count.  The least
time is the larger of FLOPs over peak FLOP/s and bytes over peak
bandwidth; the share is that over the kernel's summed device time.
Moves ``tokens_per_s``.
"""
from repro.kernels.decode_attention.kernel import decode_attention_pallas

KERNEL = decode_attention_pallas.__name__  # the kernel's custom call in the trace

BYTES = 2  # bf16 cache, q and output


def read(r):
    d = r.dims
    kernel_s = r.trace.kernel_s(KERNEL)
    if "heads" not in d or not kernel_s or not len(r.decoded_kv_lens):
        return None
    kv = float(r.decoded_kv_lens.sum())
    n = len(r.decoded_kv_lens)
    flops = 4.0 * d["layers"] * d["heads"] * d["head_dim"] * kv
    nbytes = BYTES * d["layers"] * (2 * d["kv_heads"] * d["head_dim"] * kv
                                    + 2 * d["heads"] * d["head_dim"] * n)
    least = max(flops / r.peaks["bf16_flops"], nbytes / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
