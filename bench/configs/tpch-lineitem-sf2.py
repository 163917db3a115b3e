"""TPC-H ``lineitem`` behind a bitmap index for query Q6, and Q6's plain
row-level reference.

The rows follow dbgen's rules for the columns Q6 reads (see the
configuration's ``assumed``).  The index holds equality bitmaps of the ship
year, the discount and the quantity, and a bit-sliced index of the extended
price, so that Q6 is

    SUM(price * disc) = sum over d, i of d * 2^i * popcount(filter & disc<d> & price<i>)

over 10^4 (prices in cents, discounts in hundredths).  ``q6_revenue`` is the
SQL in NumPy over the generated columns; it imports nothing of the program.
"""
from __future__ import annotations

from fractions import Fraction
from typing import Dict, Iterable, Tuple

import numpy as np

#: dbgen's order-date window: STARTDATE .. ENDDATE - 151 days
ORDER_FIRST, ORDER_LAST = np.datetime64("1992-01-01"), np.datetime64("1998-08-02")
#: ship years the window reaches (order date + 1..121 days)
YEARS = range(1992, 1999)
DISCOUNTS = range(0, 11)            # hundredths
QUANTITIES = range(1, 51)
PRICE_BITS = 24                     # 50 x 209,900 cents < 2^24


def columns(cfg: dict, seed: int) -> Dict[str, np.ndarray]:
    """The ``lineitem`` columns Q6 reads, ``cfg["rows"]`` rows from ``seed``:
    ``l_shipdate`` (datetime64[D]), ``l_quantity``, ``l_discount``
    (hundredths) and ``l_extendedprice`` (cents)."""
    n = int(cfg["rows"])
    sf = int(cfg["published"]["scale_factor"])
    rng = np.random.default_rng(seed)
    span = int((ORDER_LAST - ORDER_FIRST) // np.timedelta64(1, "D")) + 1
    days = rng.integers(0, span, n) + rng.integers(1, 122, n)
    shipdate = ORDER_FIRST + days.astype("timedelta64[D]")
    quantity = rng.integers(1, 51, n)
    discount = rng.integers(0, 11, n)
    partkey = rng.integers(1, 200_000 * sf + 1, n)
    retailprice = 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1_000)
    return {"l_shipdate": shipdate, "l_quantity": quantity,
            "l_discount": discount, "l_extendedprice": quantity * retailprice}


def index(rows: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """The bitmap index of ``rows``: one (rows,) uint8 bitmap per name, in
    the order they are paired."""
    year = rows["l_shipdate"].astype("datetime64[Y]").astype(np.int64) + 1970
    price = rows["l_extendedprice"]
    bits = {f"y{y}": year == y for y in YEARS}
    bits.update({f"disc{d}": rows["l_discount"] == d for d in DISCOUNTS})
    bits.update({f"qty{q}": rows["l_quantity"] == q for q in QUANTITIES})
    bits.update({f"price{i}": (price >> i) & 1 == 1 for i in range(PRICE_BITS)})
    return {name: b.view(np.uint8) for name, b in bits.items()}


def make_vectors(cfg: dict, seed: int) -> tuple:
    """``(pairs, bits)``: the index of the rows from ``seed``, stored as
    ``write_pair`` pairs of consecutive bitmaps in index order."""
    bits = index(columns(cfg, seed))
    names = list(bits)
    return [(names[k], names[k + 1]) for k in range(0, len(names), 2)], bits


def q6_revenue(rows: Dict[str, np.ndarray], year: int, disc: int,
               qty: int) -> Fraction:
    """TPC-H Q6 over ``rows``, exactly::

        SELECT SUM(l_extendedprice * l_discount) FROM lineitem
        WHERE l_shipdate >= DATE '<year>-01-01'
          AND l_shipdate < DATE '<year>-01-01' + INTERVAL '1' YEAR
          AND l_discount BETWEEN <disc> - 0.01 AND <disc> + 0.01
          AND l_quantity < <qty>

    with ``disc`` in hundredths; the revenue in currency units."""
    ship = rows["l_shipdate"]
    keep = ((ship >= np.datetime64(f"{year}-01-01"))
            & (ship < np.datetime64(f"{year + 1}-01-01"))
            & (rows["l_discount"] >= disc - 1)
            & (rows["l_discount"] <= disc + 1)
            & (rows["l_quantity"] < qty))
    total = np.sum(rows["l_extendedprice"][keep] * rows["l_discount"][keep],
                   dtype=np.int64)
    return Fraction(int(total), 10**4)


def revenue(counts: Iterable[Tuple[int, int, int]]) -> Fraction:
    """The client's assembly of Q6's revenue from served popcounts:
    ``counts`` holds ``(discount, price bit, count)``."""
    return Fraction(sum(d * (count << i) for d, i, count in counts), 10**4)
