"""``kernels/int8_matmul.py``: the W8A8 matmul of the cascade's int8 level.

A call: x (M, K) int8, w (K, N) int8, row scales (M, 1) and column scales
(1, N) float32; result (M, N) float32 (M, K, N padded to 128). Operations:
2 * M * K * N int8 multiply-adds. Bytes: every operand read once and the
result written once.
"""
from harness import hlo

TRACE_NAME = "int8_matmul"
PEAK = "int8_ops_s"


def cost(results, operands):
    (_, (M, K)), (_, (_, N)) = operands[0], operands[1]
    return (2.0 * M * K * N,
            float(hlo.nbytes(operands) + hlo.nbytes(results)))
