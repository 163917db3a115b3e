"""Public jit'd wrappers around the Pallas kernels.

The kernels run compiled on a TPU and in interpret mode on the CPU backend
(tests); :func:`resolve_interpret` refuses every other pairing, so a process
that finds the chip never falls back to the interpreter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import bitops as _bitops
from repro.kernels import fused as _fused
from repro.kernels import mlc_sense as _mlc
from repro.kernels import popcount as _pop
from repro.kernels import ref as kernel_ref

LANES = kernel_ref.LANES
WORD_BITS = kernel_ref.WORD_BITS
TILE_COLS = kernel_ref.TILE_COLS
ROW_TILE = _mlc.ROW_TILE
MAX_REFS = kernel_ref.MAX_REFS
pad_refs = _mlc.pad_refs


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Interpret mode for the current JAX backend: True on the CPU, False on
    a TPU.  ``None`` picks it; an explicit value that disagrees (or any other
    backend) raises instead of running the kernels somewhere unintended."""
    backend = jax.default_backend()
    want = {"cpu": True, "tpu": False}.get(backend)
    if want is None or interpret not in (None, want):
        raise RuntimeError(
            f"Pallas kernels run compiled on a TPU and interpreted on the CPU "
            f"only; got backend {backend!r} with interpret={interpret!r}")
    return want


def pad_rows(x: jnp.ndarray, multiple: int = ROW_TILE) -> tuple[jnp.ndarray, int]:
    """Pad axis 0 to a multiple; returns (padded, original_rows)."""
    r = x.shape[0]
    pad = (-r) % multiple
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    return x, r


def mlc_sense(vth: jnp.ndarray, refs, *, kind: str, invert: bool = False,
              n_refs: int = 0, interpret: bool | None = None) -> jnp.ndarray:
    """Fused sense+pack: (R, C) Vth -> (R, C//32) packed uint32."""
    interpret = resolve_interpret(interpret)
    padded, r = pad_rows(vth)
    out = _mlc.mlc_sense(padded, jnp.asarray(refs, jnp.float32),
                         kind=kind, invert=invert, n_refs=n_refs,
                         interpret=interpret)
    return out[:r]


def sense_plan(vth: jnp.ndarray, plan, *, interpret: bool | None = None) -> jnp.ndarray:
    """Run a repro.core.mcflash.ReadPlan through the Pallas sense kernel."""
    refs, kind, sense_invert, n_refs = _plan_parts(plan)
    return mlc_sense(vth, refs, kind=kind, invert=sense_invert,
                     n_refs=n_refs, interpret=interpret)


def _plan_parts(plan) -> tuple[tuple, str, bool, int]:
    # refs go through unpadded: the kernels pad to MAX_REFS via pad_refs
    return tuple(plan.refs), plan.kind, plan.uses_inverse, len(plan.refs)


def sense_reduce_plan(vth: jnp.ndarray, plan, *, op: str, invert: bool = False,
                      interpret: bool | None = None) -> jnp.ndarray:
    """Fused megakernel: (N, R, C) same-plan Vth -> (R, C//32) packed
    op-reduction, without round-tripping per-operand partials through HBM."""
    interpret = resolve_interpret(interpret)
    refs, kind, sense_invert, n_refs = _plan_parts(plan)
    n, r, c = vth.shape
    pad_r = (-r) % ROW_TILE
    if pad_r:
        vth = jnp.pad(vth, ((0, 0), (0, pad_r), (0, 0)))
    out = _fused.sense_reduce(vth, jnp.asarray(refs, jnp.float32), kind=kind,
                              sense_invert=sense_invert, op=op, invert=invert,
                              n_refs=n_refs, interpret=interpret)
    return out[:r]


def sense_reduce_popcount_plan(vth: jnp.ndarray, plan, mask: jnp.ndarray, *,
                               op: str, invert: bool = False,
                               interpret: bool | None = None) -> jnp.ndarray:
    """Fused megakernel + masked popcount: (N, R, C) Vth -> (R,) int32."""
    interpret = resolve_interpret(interpret)
    refs, kind, sense_invert, n_refs = _plan_parts(plan)
    n, r, c = vth.shape
    pad_r = (-r) % ROW_TILE
    if pad_r:
        vth = jnp.pad(vth, ((0, 0), (0, pad_r), (0, 0)))
        mask = jnp.pad(mask, ((0, pad_r), (0, 0)))   # zero mask counts nothing
    out = _fused.sense_reduce_popcount(vth, jnp.asarray(refs, jnp.float32),
                                       mask, kind=kind,
                                       sense_invert=sense_invert, op=op,
                                       invert=invert, n_refs=n_refs,
                                       interpret=interpret)
    return out[:r]


def bitwise_reduce(stack: jnp.ndarray, *, op: str, invert: bool = False,
                   interpret: bool | None = None) -> jnp.ndarray:
    """(N, R, W) packed uint32 -> (R, W) op-reduction over operands."""
    interpret = resolve_interpret(interpret)
    n, r, w = stack.shape
    pad_r = (-r) % _bitops.ROW_TILE
    pad_w = (-w) % _bitops.COL_TILE
    if pad_r or pad_w:
        stack = jnp.pad(stack, ((0, 0), (0, pad_r), (0, pad_w)))
    out = _bitops.bitwise_reduce(stack, op=op, invert=invert, interpret=interpret)
    return out[:r, :w]


def popcount_rows(words: jnp.ndarray, *, interpret: bool | None = None) -> jnp.ndarray:
    """(R, W) packed uint32 -> (R,) int32 popcounts."""
    interpret = resolve_interpret(interpret)
    padded, r = pad_rows(words)
    pad_w = (-padded.shape[1]) % _pop.COL_TILE      # zero words count nothing
    if pad_w:
        padded = jnp.pad(padded, ((0, 0), (0, pad_w)))
    return _pop.popcount_rows(padded, interpret=interpret)[:r]


pack_bits = kernel_ref.pack_bits
unpack_bits = kernel_ref.unpack_bits
