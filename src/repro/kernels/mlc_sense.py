"""Fused MLC sense + bit-pack Pallas kernel — the MCFlash hot loop.

NAND senses a 16 kB wordline into the page buffer in one shot; the TPU
analogue streams (8, 4096) Vth tiles HBM->VMEM, applies the (shifted)
reference comparisons of the selected read kind, and emits lane-major packed
uint32 words (see repro.kernels.ref for the packing convention).  Fusing the
compare/XNOR/pack keeps bytes moved at the roofline floor:
4 B/cell in + 1/8 B/cell out.

Read references are *data* (scalar-prefetched to SMEM), so switching between
AND/OR/XNOR/NOT re-uses one compiled kernel per read kind — mirroring how the
real chip switches ops purely via SET_FEATURE register writes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
WORD_BITS = 32
TILE_COLS = LANES * WORD_BITS  # 4096
ROW_TILE = 8                   # sublane-aligned row tile
MAX_REFS = 8                   # widest reference stack (TLC XOR3 needs 7)


def _sense_bits(refs_ref, v: jnp.ndarray, kind: str, invert: bool,
                n_refs: int) -> jnp.ndarray:
    """Apply the read kind's reference comparisons to one Vth tile."""
    if kind == "lsb":
        bits = v < refs_ref[0]
    elif kind == "msb":
        bits = (v < refs_ref[0]) | (v > refs_ref[1])
    elif kind == "sbr":
        neg = (v < refs_ref[0]) | (v > refs_ref[1])
        pos = (v < refs_ref[2]) | (v > refs_ref[3])
        bits = jnp.logical_not(neg ^ pos)
    elif kind == "parity":
        # Generalized multi-reference read (TLC / 8-state encodings): the
        # references sit at the valleys where the target band pattern flips,
        # so bit = 1 iff an even number of references lie below the cell.
        assert 1 <= n_refs <= MAX_REFS, n_refs
        odd = v > refs_ref[0]
        for i in range(1, n_refs):              # static unroll over refs
            odd = odd ^ (v > refs_ref[i])
        bits = jnp.logical_not(odd)
    else:
        raise ValueError(kind)
    return jnp.logical_not(bits) if invert else bits


def pack_tile(bits: jnp.ndarray) -> jnp.ndarray:
    """(rows, k*TILE_COLS) bool -> (rows, k*LANES) lane-major uint32.

    Each TILE_COLS-wide stripe packs independently (so a k-stripe block
    packs exactly like k adjacent one-stripe blocks); the reduction runs
    over the 32 sublane groups and the lanes stay 128 wide.  Mosaic has no
    reduction over unsigned integers, so the shifted bits sum in int32 —
    they are disjoint, so the sum is their OR, bit 31 included — and the
    words are bitcast to uint32.
    """
    rows, cols = bits.shape
    k = cols // TILE_COLS
    b = bits.astype(jnp.int32).reshape(rows, k, WORD_BITS, LANES)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.int32)[None, None, :, None]
    words = jnp.sum(b << shifts, axis=2, dtype=jnp.int32)
    return lax.bitcast_convert_type(words, jnp.uint32).reshape(rows, k * LANES)


def _sense_kernel(refs_ref, vth_ref, out_ref, *, kind: str, invert: bool,
                  n_refs: int):
    out_ref[...] = pack_tile(_sense_bits(refs_ref, vth_ref[...], kind, invert,
                                         n_refs))


def pad_refs(refs: jnp.ndarray) -> jnp.ndarray:
    """Zero-pad a reference vector to the fixed (MAX_REFS,) SMEM slot."""
    refs = jnp.asarray(refs, jnp.float32).reshape(-1)
    assert refs.shape[0] <= MAX_REFS, refs.shape
    return jnp.pad(refs, (0, MAX_REFS - refs.shape[0]))


@functools.partial(jax.jit, static_argnames=("kind", "invert", "n_refs",
                                             "interpret"))
def mlc_sense(vth: jnp.ndarray, refs: jnp.ndarray, *, kind: str,
              invert: bool = False, n_refs: int = 0,
              interpret: bool = True) -> jnp.ndarray:
    """Sense a (R, C) Vth array into packed (R, C//32) uint32 bits.

    R % 8 == 0 and C % 4096 == 0 (use repro.kernels.ops.pad_rows otherwise).
    ``n_refs`` is required (and used) only by kind='parity'.
    """
    r, c = vth.shape
    assert r % ROW_TILE == 0, f"rows {r} must be a multiple of {ROW_TILE}"
    assert c % TILE_COLS == 0, f"cols {c} must be a multiple of {TILE_COLS}"
    refs = pad_refs(refs)
    grid = (r // ROW_TILE, c // TILE_COLS)
    return pl.pallas_call(
        functools.partial(_sense_kernel, kind=kind, invert=invert,
                          n_refs=n_refs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # index maps receive the scalar-prefetch operand as a trailing arg
                pl.BlockSpec((ROW_TILE, TILE_COLS), lambda i, j, refs: (i, j)),
            ],
            out_specs=pl.BlockSpec((ROW_TILE, LANES), lambda i, j, refs: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((r, c // WORD_BITS), jnp.uint32),
        interpret=interpret,
    )(refs, vth)
