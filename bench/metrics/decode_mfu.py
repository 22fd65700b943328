"""decode_mfu: model FLOPs of the tokens decoded in the window over the chips' peak.

FLOPs of a decoded token: 2 x the weights its matmuls touch (layers and
the output head) plus the mixer's own work at the token's context
(attention over the live rows, or the state update), from the
configuration's reference module.  Divided by window x chips x the bf16
peak of the device.  Moves ``tokens_per_s``.
"""


def read(r):
    n = len(r.decoded_kv_lens)
    if not n:
        return None
    flops = 2.0 * r.family.matmul_params(r.model) * n + r.family.mixer_flops(r.model, r.decoded_kv_lens)
    return 100.0 * flops / (r.trace.window_s * r.chips * r.peaks["bf16_flops"])
