"""Endurance sweep: the reliability layer's wear-degradation story (§8).

Sweeps injected P/E wear over the paper's endurance points (1k / 5k / 10k
cycles) on native TLC and drives a small op-DAG suite through the full
detect -> retry -> recalibrate -> migrate ladder at each point, asserting
ZERO post-recovery bit errors against a host oracle:

- **1k P/E** — drift (~0.10V) stays inside the TLC read margin: factory
  references read clean, zero incidents, recovery is never invoked.
- **5k P/E** — drift (~0.27V) exceeds the half-gap: the bounded read-retry
  ladder recovers (third offset + margin confirmation), no recalibration.
- **10k P/E** — the ladder runs dry; a full reference sweep recalibrates
  (sticky trim ~-0.4V), the worn blocks cross the residual-RBER threshold
  and migrate to reduced-MLC, after which reads are error-free at the trim.

A recovery-disabled negative control at 10k P/E must FAIL (nonzero bit
errors) — proving the zero-error results come from the recovery ladder,
not from a toothless fault model.  Per-point RBER/retry/migration counts
land in ``BENCH_endurance.json`` (the CI ``endurance-smoke`` artifact).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.common import emit, write_json
from repro.api import ComputeSession
from repro.compile_cache import enable_compile_cache
from repro.flash.geometry import SSDConfig

PE_POINTS = (1_000, 5_000, 10_000)


def _suite(sess, bits):
    """The randomized-DAG acceptance suite: every op family over two
    co-located pairs.  Returns total bit errors vs the host oracle."""
    a, b = sess.vector("a"), sess.vector("b")
    c, d = sess.vector("c"), sess.vector("d")
    ba, bb, bc, bd = bits
    cases = (
        (a ^ b, ba ^ bb),
        (a & b, ba & bb),
        ((a & b) ^ (c | d), (ba & bb) ^ (bc | bd)),
        ((a | b) & ~(c & d), (ba | bb) & (1 - (bc & bd))),
    )
    errors = 0
    for expr, want in cases:
        got = np.asarray(sess.materialize(expr, unpacked=True))
        errors += int(np.count_nonzero(got != want.astype(np.uint8)))
    return errors


def _session(cfg, pe, seed=0, recovery=None):
    rng = np.random.default_rng(7)
    n = cfg.page_bits
    sess = ComputeSession(config=cfg, backend="pallas", encoding="tlc",
                          faults={"pe": pe, "seed": seed}, recovery=recovery)
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(4)]
    sess.write_pair("a", bits[0], "b", bits[1])
    sess.write_pair("c", bits[2], "d", bits[3])
    return sess, bits


def main(quick: bool = True, faults: bool = True) -> None:
    t0 = time.perf_counter()
    cfg = SSDConfig(page_kb=1) if quick else SSDConfig(page_kb=2)
    if not faults:
        # clean baseline: no fault model installed, no reliability manager
        sess, bits = None, None
        sess = ComputeSession(config=cfg, backend="pallas", encoding="tlc")
        rng = np.random.default_rng(7)
        n = cfg.page_bits
        bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(4)]
        sess.write_pair("a", bits[0], "b", bits[1])
        sess.write_pair("c", bits[2], "d", bits[3])
        errors = _suite(sess, bits)
        emit("endurance_baseline", sess.ledger.makespan_us(),
             f"errors={errors};faults=0")
        assert errors == 0, errors
        write_json("BENCH_endurance.json")
        return

    for pe in PE_POINTS:
        sess, bits = _session(cfg, pe)
        errors = _suite(sess, bits)
        rel = sess.stats()["reliability"]
        cats = sess.ledger.category_us
        encodings = sorted({m.encoding for m in sess.ftl.vectors.values()})
        trim = rel["ref_trim"].get("tlc")
        emit(f"endurance_pe{pe}", sess.ledger.makespan_us(),
             f"errors={errors};mismatches={rel['mismatches']};"
             f"retries={rel['retries']};recals={rel['recalibrations']};"
             f"migrations={rel['migrations']};retired={rel['retired_blocks']};"
             f"max_rber_pct={rel['wear']['max_rber_pct']:.3f};"
             f"trim={'none' if trim is None else f'{trim:.2f}V'};"
             f"encodings={'|'.join(encodings)};"
             f"recovery_us={cats.get('recovery', 0.0):.1f};"
             f"migration_us={cats.get('migration', 0.0):.1f}")
        assert errors == 0, (pe, errors)
        if pe <= 1_000:
            assert rel["mismatches"] == 0, rel        # inside factory margin
        if pe >= 5_000:
            assert rel["retries"] >= 1, rel           # the ladder earned it
        if pe >= 10_000:
            assert rel["recalibrations"] >= 1, rel
            assert rel["migrations"] >= 1 and rel["retired_blocks"] >= 1, rel
            assert "reduced-mlc" in encodings, encodings
            assert cats.get("recovery", 0.0) > 0, cats
            assert cats.get("migration", 0.0) > 0, cats

    # negative control: the same 10k workload without detection/recovery
    # must demonstrably fail
    ctrl, bits = _session(cfg, 10_000, recovery="off")
    ctrl_errors = _suite(ctrl, bits)
    emit("endurance_control_no_recovery", ctrl.ledger.makespan_us(),
         f"errors={ctrl_errors};recovery=off")
    assert ctrl_errors > 0, "10k P/E without recovery should show bit errors"

    emit("endurance_total", (time.perf_counter() - t0) * 1e6,
         f"quick={int(quick)};pe_points={len(PE_POINTS)}")
    write_json("BENCH_endurance.json")


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", default=True)
    ap.add_argument("--full", dest="quick", action="store_false")
    ap.add_argument("--faults", action="store_true",
                    help="inject seeded P/E wear and sweep the recovery "
                         "ladder (without it only the clean baseline runs)")
    args = ap.parse_args()
    main(quick=args.quick, faults=args.faults)
