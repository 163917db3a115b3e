"""Paper Table 2: RBER per part number, fresh vs cycled (N_PE = 1.5k)."""
from __future__ import annotations

import time

from benchmarks.common import emit
from repro.compile_cache import enable_compile_cache
from repro.core import rber, vth_model

PAPER_CYCLED = {  # part -> (AND, OR, XNOR, NOT) % at 1.5k P/E
    "MT29F256G08EBHAFJ4": (0.00025, 0.000931, 0.00134, 0.00047),
    "MT29F512G08EEHAFJ4": (0.00019, 0.000846, 0.00124, 0.00032),
    "MT29F1T08EELEEJ4": (0.00012, 0.000763, 0.00108, 0.00069),
    "MT29F1T08EELKEJ4": (0.00009, 0.000821, 0.00119, 0.00057),
    "MT29F4T08GMLCEJ4": (0.00021, 0.000672, 0.00203, 0.00078),
}
OPS = ("and", "or", "xnor", "not")


def main(quick: bool = True) -> None:
    fresh_pages = 8 if quick else 64
    cycled_pages = 48 if quick else 256
    for part, paper in PAPER_CYCLED.items():
        chip = vth_model.get_chip_model(part)
        t0 = time.perf_counter()
        fresh = [rber.measure_rber(op, chip, pages=fresh_pages, seed=21).rber_pct
                 for op in OPS]
        cyc = [rber.measure_rber(op, chip, pages=cycled_pages, n_pe=1500,
                                 seed=22).rber_pct for op in OPS]
        us = (time.perf_counter() - t0) * 1e6
        derived = ";".join(
            f"{op}:fresh={f:.5f}%:cyc={c:.5f}%:paper={p:.5f}%"
            for op, f, c, p in zip(OPS, fresh, cyc, paper))
        emit(f"table2_{part}", us, derived)
        assert all(f == 0.0 for f in fresh), (part, fresh)


if __name__ == "__main__":
    enable_compile_cache()
    main()
