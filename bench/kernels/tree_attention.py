"""``kernels/tree_attention.py``: the intra-tree half of verify attention.

A call: q (B, KV, R, hd), staged k and v (B, KV, T, hd), the mask tiled to
(B, R, T); results acc (B, KV, R, hd), m and l (B, KV, R, 1). R = rep * T
rows per KV head, hd padded to 128. Operations: scores and mix,
2 * 2 * B * KV * R * T * hd. Bytes: every operand read once and every
result written once.
"""
from harness import hlo

TRACE_NAME = "tree_attention_partial"
PEAK = "bf16_flops_s"


def cost(results, operands):
    (_, (B, KV, R, hd)), (_, (_, _, T, _)) = operands[0], operands[1]
    return (4.0 * B * KV * R * T * hd,
            float(hlo.nbytes(operands) + hlo.nbytes(results)))
