"""Fused sense→reduce(→popcount) Pallas megakernels.

A k-operand MCFlash chain used to run as one sense kernel per operand pair
plus a separate ``bitwise_reduce`` — every partial made a round trip through
HBM.  These kernels fuse the whole chain: the (P, R, C) Vth gather of all P
pair pages streams tile-by-tile into VMEM, each operand tile is sensed with
the (shared) read references, and the epilogue threads the sensed bits
straight into the reduce accumulator — packing (and optionally masked
popcounting) before anything leaves the chip.  HBM traffic drops from
``P reads + P writes + P reads + 1 write`` per tile to ``P reads + 1 write``
(or ``P reads + 128 lanes`` for the popcount form).

All P operands must share one read plan (same references / kind / inverse
flag) — exactly the homogeneous same-op chains the compiled executor groups;
heterogeneous graphs fall back to grouped senses + ``bitwise_reduce``.

Read references stay scalar-prefetched *data* (SMEM), so one compiled kernel
per (P, kind, op) shape serves every reference voltage — mirroring how the
real chip switches ops purely via SET_FEATURE register writes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mlc_sense import (LANES, ROW_TILE, TILE_COLS, WORD_BITS,
                                    _sense_bits, pack_tile, pad_refs)
from repro.kernels.popcount import _popcount

#: VMEM ceiling the automatic column-tile widening respects on compiled
#: backends (operand tiles resident per fused pass)
COL_TILE_VMEM_BUDGET_BYTES = 4 * 1024 * 1024


def _auto_col_tiles(n: int, c: int, interpret: bool) -> int:
    """Column tiles (of TILE_COLS) streamed per grid step.

    Per-grid-step dispatch overhead dominates these kernels — in interpret
    mode (the CPU default) each step replays the whole Python kernel body,
    and wider blocks amortize it dramatically (~9x on the quick-benchmark
    shapes).  Interpret mode therefore takes the whole row width in ONE
    step; compiled backends take the widest divisor of the width whose
    operand block (``n x ROW_TILE x k*TILE_COLS`` float32) still fits the
    VMEM budget.
    """
    t = c // TILE_COLS
    if interpret:
        return t
    k_max = max(1, COL_TILE_VMEM_BUDGET_BYTES
                // max(1, n * ROW_TILE * TILE_COLS * 4))
    for k in range(min(t, k_max), 0, -1):
        if t % k == 0:
            return k
    return 1


def _combine(acc: jnp.ndarray, nxt: jnp.ndarray, op: str) -> jnp.ndarray:
    if op == "and":
        return acc & nxt
    if op == "or":
        return acc | nxt
    if op == "xor":
        return acc ^ nxt
    raise ValueError(op)


def _sense_reduce_acc(refs_ref, vth_ref, *, n: int, kind: str,
                      sense_invert: bool, op: str, invert: bool,
                      n_refs: int) -> jnp.ndarray:
    """Shared body: sense all n operand tiles, fold into one bool accumulator."""
    acc = _sense_bits(refs_ref, vth_ref[0], kind, sense_invert, n_refs)
    for k in range(1, n):                       # static unroll over operands
        acc = _combine(acc, _sense_bits(refs_ref, vth_ref[k], kind,
                                        sense_invert, n_refs), op)
    return jnp.logical_not(acc) if invert else acc


def _sense_reduce_kernel(refs_ref, vth_ref, out_ref, *, n, kind,
                         sense_invert, op, invert, n_refs):
    out_ref[...] = pack_tile(_sense_reduce_acc(
        refs_ref, vth_ref, n=n, kind=kind, sense_invert=sense_invert,
        op=op, invert=invert, n_refs=n_refs))


def _sense_reduce_popcount_kernel(refs_ref, vth_ref, mask_ref, out_ref, *, n,
                                  kind, sense_invert, op, invert, n_refs):
    j = pl.program_id(1)
    words = pack_tile(_sense_reduce_acc(
        refs_ref, vth_ref, n=n, kind=kind, sense_invert=sense_invert,
        op=op, invert=invert, n_refs=n_refs)) & mask_ref[...]
    pcw = _popcount(words)                      # (ROW_TILE, k*LANES)
    rows, cols = pcw.shape
    # fold the k column stripes of a wide block into one LANES-wide slab
    pc = jnp.sum(pcw.reshape(rows, cols // LANES, LANES), axis=1,
                 dtype=jnp.int32)              # (ROW_TILE, LANES)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = pc

    @pl.when(j > 0)
    def _acc():
        out_ref[...] += pc


def _check_shapes(vth: jnp.ndarray) -> tuple[int, int, int]:
    n, r, c = vth.shape
    assert n >= 1, "need at least one operand"
    assert r % ROW_TILE == 0, f"rows {r} must be a multiple of {ROW_TILE}"
    assert c % TILE_COLS == 0, f"cols {c} must be a multiple of {TILE_COLS}"
    return n, r, c


@functools.partial(jax.jit, static_argnames=("kind", "sense_invert", "op",
                                             "invert", "n_refs", "interpret",
                                             "col_tiles"))
def sense_reduce(vth: jnp.ndarray, refs: jnp.ndarray, *, kind: str,
                 sense_invert: bool, op: str, invert: bool = False,
                 n_refs: int = 0, interpret: bool = True,
                 col_tiles: "int | None" = None) -> jnp.ndarray:
    """Fused chain: (N, R, C) Vth -> (R, C//32) packed op-reduction.

    Each of the N operands is sensed with the same ``refs``/``kind`` (and
    per-sense inverse-read when ``sense_invert``), folded with ``op``, with
    an optional final inversion — all inside one kernel.  ``n_refs`` is
    required (and used) only by kind='parity'.  ``col_tiles`` widens each
    grid step to that many TILE_COLS column stripes (must divide
    ``C // TILE_COLS``); ``None`` auto-tunes via :func:`_auto_col_tiles`.
    """
    n, r, c = _check_shapes(vth)
    if col_tiles is None:
        col_tiles = _auto_col_tiles(n, c, interpret)
    assert (c // TILE_COLS) % col_tiles == 0, (c, col_tiles)
    refs = pad_refs(refs)
    grid = (r // ROW_TILE, c // (col_tiles * TILE_COLS))
    return pl.pallas_call(
        functools.partial(_sense_reduce_kernel, n=n, kind=kind,
                          sense_invert=sense_invert, op=op, invert=invert,
                          n_refs=n_refs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((n, ROW_TILE, col_tiles * TILE_COLS),
                             lambda i, j, refs: (0, i, j)),
            ],
            out_specs=pl.BlockSpec((ROW_TILE, col_tiles * LANES),
                                   lambda i, j, refs: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((r, c // WORD_BITS), jnp.uint32),
        interpret=interpret,
    )(refs, vth)


@functools.partial(jax.jit, static_argnames=("kind", "sense_invert", "op",
                                             "invert", "n_refs", "interpret",
                                             "col_tiles"))
def sense_reduce_popcount(vth: jnp.ndarray, refs: jnp.ndarray,
                          mask: jnp.ndarray, *, kind: str, sense_invert: bool,
                          op: str, invert: bool = False, n_refs: int = 0,
                          interpret: bool = True,
                          col_tiles: "int | None" = None) -> jnp.ndarray:
    """Fused chain + popcount: (N, R, C) Vth -> (R,) int32 bit counts.

    ``mask`` is (R, C//32) packed uint32 ANDed into the reduced words before
    counting (zeroes the page-padding tail, which inverse-read ops would
    otherwise count as ones).  Only the counts leave the kernel — the packed
    result never round-trips through HBM.  ``col_tiles`` widens the column
    blocks exactly as in :func:`sense_reduce` (the kernel folds each wide
    block's stripes into the same LANES-wide accumulator slab).
    """
    n, r, c = _check_shapes(vth)
    assert mask.shape == (r, c // WORD_BITS), mask.shape
    if col_tiles is None:
        col_tiles = _auto_col_tiles(n, c, interpret)
    assert (c // TILE_COLS) % col_tiles == 0, (c, col_tiles)
    refs = pad_refs(refs)
    grid = (r // ROW_TILE, c // (col_tiles * TILE_COLS))
    lanes = pl.pallas_call(
        functools.partial(_sense_reduce_popcount_kernel, n=n, kind=kind,
                          sense_invert=sense_invert, op=op, invert=invert,
                          n_refs=n_refs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((n, ROW_TILE, col_tiles * TILE_COLS),
                             lambda i, j, refs: (0, i, j)),
                pl.BlockSpec((ROW_TILE, col_tiles * LANES),
                             lambda i, j, refs: (i, j)),
            ],
            out_specs=pl.BlockSpec((ROW_TILE, LANES), lambda i, j, refs: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((r, LANES), jnp.int32),
        interpret=interpret,
    )(refs, vth, mask)
    return jnp.sum(lanes, axis=-1, dtype=jnp.int32)
