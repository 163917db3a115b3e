"""Paper Fig 6: RBER vs retention duration x P/E cycles, per op."""
from __future__ import annotations

import time

from benchmarks.common import emit
from repro.compile_cache import enable_compile_cache
from repro.core import rber, vth_model

OPS = ("xnor", "or", "and", "not")
RETENTION_H = (0.0, 100.0, 1000.0)
PE = (1000, 5000, 10000)


def main(quick: bool = True) -> None:
    chip = vth_model.get_chip_model()
    pages = 8 if quick else 48
    for op in OPS:
        t0 = time.perf_counter()
        cells = []
        grid = []
        for pe in PE:
            row = []
            for ret in RETENTION_H:
                r = rber.measure_rber(op, chip, pages=pages, n_pe=pe,
                                      retention_hours=ret, seed=31)
                row.append(r.rber_pct)
                cells.append(f"pe{pe//1000}k_t{int(ret)}h={r.rber_pct:.5f}%")
            grid.append(row)
        us = (time.perf_counter() - t0) * 1e6
        emit(f"fig6_{op}", us, ";".join(cells))
        # monotonicity along both axes (allowing zero plateaus)
        for row in grid:
            assert row[0] <= row[-1] + 1e-12, (op, row)
        for j in range(len(RETENTION_H)):
            assert grid[0][j] <= grid[-1][j] + 1e-12, (op, j)


if __name__ == "__main__":
    enable_compile_cache()
    main()
