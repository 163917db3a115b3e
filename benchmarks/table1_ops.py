"""Paper Table 1: read-offset plans for every bitwise op + bit-exactness.

Runs through the :class:`repro.api.ComputeSession` layer: operands are
registered once, every op materializes as an in-flash sense via the cached
read plan (re-planned at most once per (op, chip)), and repeat timings are
pure cache hits.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from benchmarks.common import emit, timeit, write_json
from repro.api import ComputeSession
from repro.compile_cache import enable_compile_cache
from repro.core import encoding


def main(quick: bool = True, trace: "str | None" = None,
         faults: "str | None" = None) -> None:
    t0 = time.perf_counter()
    sess = ComputeSession(backend="pallas", seed=0, trace=bool(trace),
                          faults=faults)
    pages = 2 if quick else 8
    n = pages * sess.device.config.page_bits
    rng = np.random.default_rng(0)
    lsb = (rng.random(n) < 0.5).astype(np.uint8)
    msb = (rng.random(n) < 0.5).astype(np.uint8)
    a, b = sess.write_pair("a", lsb, "b", msb)
    nv = sess.write("n", msb, role="msb")      # NOT operand: MSB page over zero LSB

    exprs = {
        "and": a & b, "or": a | b, "xnor": a.xnor(b),
        "nand": ~(a & b), "nor": ~(a | b), "xor": a ^ b,
        "not": ~nv,
    }
    for op in encoding.ALL_OPS:
        expr = exprs[op]
        got = np.asarray(sess.materialize(expr, unpacked=True))
        if op == "not":
            want = np.asarray(encoding.logical_op("not", msb))
        else:
            want = np.asarray(encoding.logical_op(op, lsb, msb))
        errors = int(np.sum(got != want))
        us = timeit(lambda expr=expr: jax.block_until_ready(
                        sess.materialize(expr)),
                    iters=3 if quick else 10)
        plan = sess.plan(op)
        emit(f"table1_{op}", us,
             f"phases={plan.sensing_phases};errors={errors};"
             f"plan={plan.describe().replace(',', ';')}")
        assert errors == 0, (op, errors)
    stats = sess.stats()["plan_cache"]
    emit("table1_plan_cache", 0.0,
         f"hits={stats['hits']};misses={stats['misses']};entries={stats['entries']}")
    assert stats["misses"] <= len(encoding.ALL_OPS), stats
    ex = sess.stats()["executor"]
    emit("table1_exec_cache", 0.0,
         f"hits={ex['hits']};misses={ex['misses']};traces={ex['traces']};"
         f"evictions={ex['evictions']}")
    # repeat timings replayed cached executables: one trace per DAG shape
    # (recovery re-senses compile extra shifted plans, so only assert clean)
    if faults is None:
        assert ex["traces"] == ex["misses"], ex
    led = sess.ledger
    emit("table1_die_parallel", led.die_step_us,
         f"serial_us={led.serial_us():.1f};"
         f"max_parallel_dies={led.max_parallel_dies};"
         f"arena_shards={sess.device.arena.n_shards}")
    assert led.die_step_us <= led.serial_us()

    # TLC 3-operand fast paths (§7): a&b&c / a|b|c over one co-located
    # wordline triple are ONE sense group each (AND3 = 1 phase, OR3 = 2)
    tsess = ComputeSession(backend="pallas", seed=0, encoding="tlc",
                           faults=faults)
    csb = (rng.random(n) < 0.5).astype(np.uint8)
    ta, tb, tc = tsess.write_triple("a", lsb, "b", msb, "c", csb)
    for op, expr, want in (("and3", ta & tb & tc, lsb & msb & csb),
                           ("or3", ta | tb | tc, lsb | msb | csb)):
        got = np.asarray(tsess.materialize(expr, unpacked=True))
        errors = int(np.sum(got != want))
        batches0 = tsess.sense_batches
        iters = 3 if quick else 10
        us = timeit(lambda expr=expr: jax.block_until_ready(
                        tsess.materialize(expr)),
                    iters=iters)
        per_call = (tsess.sense_batches - batches0) / (iters + 1)  # +warmup
        plan = tsess.device.plans.get_encoded(
            op[:-1], ("lsb", "csb", "msb"), tsess.device.tlc_chip, "tlc")
        emit(f"table1_tlc_{op}", us,
             f"phases={plan.sensing_phases};errors={errors};"
             f"sense_groups_per_call={per_call:g};"
             f"plan={plan.describe().replace(',', ';')}")
        assert errors == 0, (op, errors)
        if faults is None:       # retries legitimately add sense groups
            assert per_call == 1, per_call             # ONE sense group

    if faults is not None:
        # --faults: bit-exactness above already held THROUGH the recovery
        # ladder; surface what it cost
        for label, s in (("mlc", sess), ("tlc", tsess)):
            rel = s.stats()["reliability"]
            if rel is None:
                continue
            emit(f"table1_reliability_{label}",
                 s.ledger.category_us.get("recovery", 0.0),
                 f"spec={faults};mismatches={rel['mismatches']};"
                 f"retries={rel['retries']};recals={rel['recalibrations']};"
                 f"migrations={rel['migrations']}")

    # verifier overhead: a fresh session per mode (always fault-free — the
    # <3% budget measures the verifier alone) lowers the same mixed DAG
    # cold, then repeats it.  The verifier's accumulated wall clock (its own
    # perf counter, so jit-compile noise can't leak in) must stay under 3%
    # of the cold materialize, and the repeat must memo-hit by signature —
    # zero additional plans verified.
    modes = {}
    for mode in ("off", "on"):
        vsess = ComputeSession(backend="pallas", seed=0, verify=mode)
        va, vb = vsess.write_pair("a", lsb, "b", msb)
        vc, vd = vsess.write_pair("c", lsb, "d", msb)
        vexpr = (va & vb) ^ (vc | vd)
        t0v = time.perf_counter()
        jax.block_until_ready(vsess.materialize(vexpr))
        cold_us = (time.perf_counter() - t0v) * 1e6
        jax.block_until_ready(vsess.materialize(vexpr))      # memo-hit path
        st = vsess.stats()
        modes[mode] = (cold_us, st["plans_verified"],
                       st["verify_cache_hits"], st["verify"]["time_us"])
    cold_us, verified, memo_hits, verify_us = modes["on"]
    pct = 100.0 * verify_us / max(cold_us, 1e-9)
    emit("table1_verify_overhead", verify_us,
         f"pct_of_cold={pct:.3f};cold_us={cold_us:.1f};"
         f"plans_verified={verified};memo_hits={memo_hits};"
         f"off_plans_verified={modes['off'][1]}")
    assert modes["off"][1] == 0 and modes["off"][3] == 0.0, modes["off"]
    assert verified == 1 and memo_hits >= 1, modes["on"]     # repeat is free
    assert pct < 3.0, (verify_us, cold_us)

    if trace:
        # device-timeline audit: the exported Chrome trace's longest virtual
        # lane must equal the ledger's makespan (by construction — fail loud
        # here so CI catches any drift between the two models)
        tr, led = sess.trace, sess.ledger
        assert abs(tr.makespan_us() - led.makespan_us()) <= \
            1e-6 * max(1.0, led.makespan_us()), \
            (tr.makespan_us(), led.makespan_us())
        path = tr.export(trace)
        emit("table1_trace", tr.makespan_us(),
             f"path={path};device_spans={len(tr.device_spans)};"
             f"wall_spans={len(tr.wall_spans)};"
             f"ledger_makespan_us={led.makespan_us():.2f}")
        print(tr.report(led))
    emit("table1_total", (time.perf_counter() - t0) * 1e6, f"quick={int(quick)}")
    write_json("BENCH_kernels.json")


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", default=True)
    ap.add_argument("--full", dest="quick", action="store_false")
    ap.add_argument("--trace", nargs="?", const="trace_table1.json",
                    default=None, metavar="OUT_JSON",
                    help="export the device-timeline Chrome trace "
                         "(open in chrome://tracing or ui.perfetto.dev)")
    ap.add_argument("--faults", nargs="?", const="pe=5000", default=None,
                    metavar="SPEC",
                    help="inject seeded wear (e.g. pe=5000,seed=3) and run "
                         "every bit-exactness check through the recovery "
                         "ladder")
    args = ap.parse_args()
    main(quick=args.quick, trace=args.trace, faults=args.faults)
