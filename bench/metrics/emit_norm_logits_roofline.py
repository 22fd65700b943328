"""emit_norm_logits_roofline: the fused final-norm + logits kernel's share of its roofline.

Work is what decoding has to do, whatever program does it: each decode
step reads the head weights (bf16, vocab x d_model) once, and each token
decoded in the window reads its normed input row (bf16) and writes one
fp32 row of logits; FLOPs 2 x d_model x vocab per decoded token.  Decode
steps are the rounds executed in the window times ``round_steps``; decoded
tokens come from the host's record of deliveries.  How the program splits
a step (microbatches, re-reads of the head) does not enter the count.  The
least time is the larger of FLOPs over peak FLOP/s and bytes over peak
bandwidth; the share is that over the kernel's summed device time.  Moves
``tokens_per_s``.
"""
from repro.kernels.emit_norm_logits.kernel import emit_norm_logits_pallas

KERNEL = emit_norm_logits_pallas.__name__  # the kernel's custom call in the trace


def read(r):
    kernel_s = r.trace.kernel_s(KERNEL)
    rounds = sum(len(ms) for ms in r.trace.executions("jit__round").values())
    tokens = len(r.decoded_kv_lens)
    if not kernel_s or not rounds or not tokens:
        return None
    d, v = r.dims["d_model"], r.dims["vocab"]
    steps = rounds * r.round_steps
    flops = tokens * 2.0 * d * v
    nbytes = steps * 2.0 * v * d + tokens * (2.0 * d + 4.0 * v)
    least = max(flops / r.peaks["bf16_flops"], nbytes / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
