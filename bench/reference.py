"""The plain reference and the comparison that decides ``correct``.

The reference evaluates a concrete expression (see ``generator.py``) with
NumPy over the host bits the benchmark generated from the seed; it imports
nothing of the program.  Served answers are packed uint32 words in the
lane-major layout of the program's result (word ``w`` of a 4096-cell tile
holds bit ``k`` of cell ``k*128 + w``), page-padded, or counts.
"""
from __future__ import annotations

from typing import Callable, Dict, Iterable, Tuple

import numpy as np

#: limits of the numbers compared: each answer must be exact, none missing,
#: and the run must have compared at least one answer
LIMITS = {"wrong_bits": ("max", 0), "missing_answers": ("max", 0),
          "answers_compared": ("min", 1)}

_REDUCE = {"and": np.bitwise_and, "or": np.bitwise_or, "xor": np.bitwise_xor}


def evaluate(expr, bits_of: Callable[[str], np.ndarray]) -> np.ndarray:
    """(n_bits,) uint8 value of a concrete expression."""
    if isinstance(expr, str):
        return bits_of(expr)
    op, *args = expr
    vals = [evaluate(a, bits_of) for a in args]
    out = vals[0].copy()
    for v in vals[1:]:
        _REDUCE[op](out, v, out=out)
    return out


def lane_major_bits(words: np.ndarray) -> np.ndarray:
    """One uint8 per cell of lane-major packed uint32 words."""
    w = np.asarray(words, np.uint32).reshape(-1, 128)
    shifts = np.arange(32, dtype=np.uint32)[None, :, None]
    return ((w[:, None, :] >> shifts) & 1).astype(np.uint8).reshape(-1)


def wrong_bits(got, popcount: bool, want) -> int:
    """Bits by which one served answer departs from the reference (``want``:
    a count, or the (n_bits,) uint8 bitmap): the count difference for a
    count; for a bitmap, cells that differ, cells missing, and padding cells
    that are not zero."""
    if popcount:
        return abs(int(np.asarray(got).reshape(-1)[0]) - int(want))
    if np.asarray(got).size % 128:
        return int(want.size)                 # not whole tiles: unreadable
    bits = lane_major_bits(got)
    n = min(bits.size, want.size)
    return (int(np.count_nonzero(bits[:n] != want[:n]))
            + (want.size - n) + int(np.count_nonzero(bits[n:])))


def compare(answers: Iterable[Tuple[tuple, bool, object]], missing: int,
            bits_of: Callable[[str], np.ndarray]) -> Dict[str, int]:
    """Compare served ``(expression, popcount, answer)`` triples with the
    reference; returns the numbers compared and ``wrong_answers``."""
    memo: Dict[tuple, object] = {}
    total = wrong = compared = 0
    for expr, popcount, got in answers:
        want = memo.get((expr, popcount))
        if want is None:
            want = evaluate(expr, bits_of)
            if popcount:
                want = int(np.count_nonzero(want))
            memo[(expr, popcount)] = want
        d = wrong_bits(got, popcount, want)
        total += d
        wrong += d > 0
        compared += 1
    return {"wrong_bits": total, "missing_answers": int(missing),
            "answers_compared": compared, "wrong_answers": wrong}


def verdict(numbers: Dict[str, int]) -> Tuple[bool, Dict[str, dict]]:
    """``(correct, {name: {"value", "max"|"min"}})`` for the numbers
    compared, each beside its limit."""
    shown, ok = {}, True
    for name, (kind, limit) in LIMITS.items():
        value = numbers[name]
        shown[name] = {"value": value, kind: limit}
        ok &= value <= limit if kind == "max" else value >= limit
    return ok, shown
