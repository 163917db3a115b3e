"""Vectors of the user-activity bitmap index: one bitmap per day, one bit per
user, stored as co-located pairs of consecutive days."""
from __future__ import annotations

import numpy as np


def make_vectors(cfg: dict, seed: int) -> tuple:
    """``(pairs, bits)`` for ``cfg`` from ``seed``: ``bits["d<i>"]`` is day
    ``i``'s (users,) uint8 activity bitmap and ``pairs`` lists the
    ``write_pair`` placement, ``(d0, d1), (d2, d3), ...``.

    A user with propensity ``p`` (in 1/256 steps) is active on a day with
    probability ``p``, so a user active one day is likelier active the next
    and AND chains over weeks keep a nonzero count."""
    users, days = int(cfg["users"]), int(cfg["days"])
    rng = np.random.default_rng(seed)
    propensity = rng.integers(0, 256, users, dtype=np.uint8)
    active = rng.integers(0, 256, (days, users), dtype=np.uint8) < propensity
    names = [f"d{d}" for d in range(days)]
    bits = dict(zip(names, active.view(np.uint8)))
    return [(names[d], names[d + 1]) for d in range(0, days, 2)], bits
