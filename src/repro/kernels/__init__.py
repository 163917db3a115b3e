"""Pallas TPU kernels for the MCFlash hot paths.

- ``mlc_sense``: fused threshold sense + lane-major bit-pack (lsb/msb/sbr/parity).
- ``bitops``: packed multi-operand AND/OR/XOR chains.
- ``popcount``: per-row popcount reduce.
- ``ops``: public jit wrappers (compiled on a TPU, interpreted on the CPU).
- ``ref``: pure-jnp oracles + the packing convention.
"""
from repro.kernels import ops, ref
from repro.kernels.ops import (bitwise_reduce, mlc_sense, pack_bits,
                               popcount_rows, sense_plan, unpack_bits)

__all__ = ["ops", "ref", "mlc_sense", "sense_plan", "bitwise_reduce",
           "popcount_rows", "pack_bits", "unpack_bits"]
