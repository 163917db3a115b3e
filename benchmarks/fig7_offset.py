"""Paper Fig 7: RBER vs read-offset voltage for bitwise OR, fresh vs cycled.

Reproduces the three regimes: ~25% RBER at V_OFF = 0 (all L1 cells misread),
a zero-RBER window once the offset crosses the L1 distribution, and rising
RBER when the shifted reference enters L2.  The window closes on heavily
cycled blocks (Fig 7c).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.compile_cache import enable_compile_cache
from repro.core import mcflash, sensing, vth_model


def or_rber_at_offset(chip, v_off: float, n_pe: float, seed: int,
                      n_bits: int = 1 << 20) -> float:
    key = jax.random.PRNGKey(seed)
    lsb = jax.random.bernoulli(key, 0.5, (n_bits,)).astype(jnp.uint8)
    msb = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5,
                               (n_bits,)).astype(jnp.uint8)
    vth, _ = vth_model.program_page(jax.random.fold_in(key, 2), lsb, msb,
                                    chip, n_pe=n_pe)
    # OR = MSB read with VREF0 shifted up from default by v_off
    v0 = chip.vref_default[0] + v_off
    got = sensing.msb_read(vth, v0, chip.vref_default[2])
    want = mcflash.expected_result("or", lsb, msb)
    return 100.0 * float(jnp.mean((got != want).astype(jnp.float32)))


def main(quick: bool = True) -> None:
    chip = vth_model.get_chip_model()
    offsets = [0.0, 0.4, 0.9, 1.4, 1.8, 2.2, 2.6, 3.0]
    for label, n_pe in (("fresh", 0), ("cycled10k", 10000)):
        t0 = time.perf_counter()
        curve = [or_rber_at_offset(chip, off, n_pe, seed=41) for off in offsets]
        us = (time.perf_counter() - t0) * 1e6
        emit(f"fig7_{label}", us,
             ";".join(f"voff{off:.1f}={r:.4f}%" for off, r in zip(offsets, curve)))
        assert 20.0 < curve[0] < 30.0, curve        # ~25% at V_OFF = 0
        assert curve[-1] > 1.0, curve               # ref inside L2
        if label == "fresh":
            assert min(curve) == 0.0                 # zero-RBER window exists
        else:
            assert min(curve) > 0.0                  # window closed at 10k P/E


if __name__ == "__main__":
    enable_compile_cache()
    main()
