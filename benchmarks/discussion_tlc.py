"""Paper §7 (Discussion): TLC 3-operand ops + reduced-MLC robust mode."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.compile_cache import enable_compile_cache
from repro.core import tlc
from repro.flash import TimingModel


def main(quick: bool = True) -> None:
    chip = tlc.TLCChipModel()
    key = jax.random.PRNGKey(0)
    n = (1 << 18) if quick else (1 << 21)
    a = jax.random.bernoulli(key, 0.5, (n,)).astype(jnp.uint8)
    b = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, (n,)).astype(jnp.uint8)
    c = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.5, (n,)).astype(jnp.uint8)

    t0 = time.perf_counter()
    states = tlc.encode_tlc(a, b, c)
    for pe, label in ((0, "fresh"), (10_000, "10k")):
        vth = tlc.program_tlc(jax.random.fold_in(key, 3), states, chip, n_pe=pe)
        and_err = int(jnp.sum(tlc.and3_read(vth, chip) != (a & b & c)))
        or_err = int(jnp.sum(tlc.or3_read(vth, chip) != (a | b | c)))
        emit(f"tlc_and3_{label}", (time.perf_counter() - t0) * 1e6,
             f"rber={100*and_err/n:.5f}%;or3_rber={100*or_err/n:.5f}%;cells={n}")
        if pe == 0:
            assert and_err == 0 and or_err == 0

    # reduced-MLC robustness at 10k P/E
    red = tlc.encode_reduced(a, b)
    vth = tlc.program_tlc(jax.random.fold_in(key, 4), red, chip, n_pe=10_000)
    err = int(jnp.sum(tlc.reduced_and_read(vth, chip) != (a & b))) \
        + int(jnp.sum(tlc.reduced_or_read(vth, chip) != (a | b)))
    vthn = tlc.program_tlc(jax.random.fold_in(key, 5), states, chip, n_pe=10_000)
    nat = int(jnp.sum(tlc.and3_read(vthn, chip) != (a & b & c))) \
        + int(jnp.sum(tlc.or3_read(vthn, chip) != (a | b | c)))
    emit("tlc_reduced_vs_native_10k", 0.0,
         f"reduced_rber={100*err/(2*n):.5f}%;native_rber={100*nat/(2*n):.5f}%;"
         f"improvement={nat/max(err,1):.0f}x")

    # latency advantage: 3-operand AND in ONE sensing phase
    t = TimingModel()
    and3_us = t.t_fixed_us + t.t_sense_us
    mlc_chain_us = 2 * t.read_latency_us("and")
    emit("tlc_and3_latency", and3_us,
         f"vs_mlc_2op_chain={mlc_chain_us:.0f}us;speedup={mlc_chain_us/and3_us:.1f}x")


if __name__ == "__main__":
    enable_compile_cache()
    main()
