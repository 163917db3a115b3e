"""HBM bytes of one ``mlc_sense`` call (``kernels/mlc_sense.py``).

Operands: the scalar-prefetched references f32[MAX_REFS] and the Vth
f32[R, C]; result u32[R, C/32].  The grid visits each (8, 4096) Vth block
and each (8, 128) output block once, so the call reads every operand byte
once and writes every result byte once: 4 B per cell in, 1/8 B per cell out.
"""
from bench.kernels import operand_bytes


def bytes_moved(operands, results) -> int:
    return operand_bytes(operands) + operand_bytes(results)
