"""Per-kernel HBM byte functions: ``bench/kernels/<kernel>.py`` holds
``bytes_moved(operands, results)``, where each argument is a list of
``(dtype, shape)`` taken from the kernel call in the trace."""
from __future__ import annotations

import math

ITEMSIZE = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s16": 2,
            "u16": 2, "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8,
            "u64": 8}


def operand_bytes(arrays) -> int:
    """Bytes of ``(dtype, shape)`` arrays, dtypes in HLO spelling."""
    return sum(ITEMSIZE[dtype] * math.prod(shape) for dtype, shape in arrays)
