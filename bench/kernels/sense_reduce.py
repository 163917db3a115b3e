"""HBM bytes of one ``sense_reduce`` call (``kernels/fused.py``).

Operands: the scalar-prefetched references f32[MAX_REFS] and the Vth of N
same-plan operands f32[N, R, C]; result u32[R, C/32].  The grid visits each
(N, 8, k*4096) Vth block and each (8, k*128) output block once, so the call
reads every operand byte once and writes every result byte once.
"""
from bench.kernels import operand_bytes


def bytes_moved(operands, results) -> int:
    return operand_bytes(operands) + operand_bytes(results)
