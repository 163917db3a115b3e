"""Vectors of bulk image encryption: each image's 24 bitplanes as one vector,
co-located with its own keystream."""
from __future__ import annotations

import numpy as np


def make_vectors(cfg: dict, seed: int) -> tuple:
    """``(pairs, bits)`` for ``cfg`` from ``seed``: ``bits["img<i>"]`` holds
    image ``i`` plane-major (channel, then bit 0..7, then pixel in row-major
    order) and ``bits["key<i>"]`` its keystream; ``pairs`` places each image
    on shared wordlines with its key."""
    n, h, w = int(cfg["images"]), int(cfg["height"]), int(cfg["width"])
    ch, depth = int(cfg["channels"]), int(cfg["bits_per_channel"])
    assert depth == 8, "pixels are stored as bytes"
    rng = np.random.default_rng(seed)
    pixels = rng.integers(0, 256, (n, ch, h * w, 1), dtype=np.uint8)
    planes = np.unpackbits(pixels, axis=-1, bitorder="little")  # (n, ch, hw, 8)
    images = planes.transpose(0, 1, 3, 2).reshape(n, ch * depth * h * w)
    n_bits = images.shape[1]
    key_bytes = rng.integers(0, 256, (n, -(-n_bits // 8)), dtype=np.uint8)
    keys = np.unpackbits(key_bytes, axis=-1)[:, :n_bits]
    bits, pairs = {}, []
    for i in range(n):
        bits[f"img{i}"], bits[f"key{i}"] = images[i], keys[i]
        pairs.append((f"img{i}", f"key{i}"))
    return pairs, bits
