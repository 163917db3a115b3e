"""Backend micro-benchmarks (interpret mode on CPU — correctness-shaped, the
TPU numbers come from the §Roofline analysis of the lowered kernels).

Times the :class:`repro.api.Backend` primitives — fused sense+pack, packed
multi-operand reduce, popcount, and the fused sense→reduce(→popcount)
megakernels — on both the Pallas backend and the pure-jnp sim backend, plus
the compiled-executor end-to-end path (16-operand chain materialize through
the cached executable).  Results land in ``BENCH_kernels.json`` so the perf
trajectory is tracked across PRs.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from benchmarks.common import emit, timeit, write_json
from repro.api import ComputeSession, PallasBackend, PlanCache, SimBackend
from repro.compile_cache import enable_compile_cache
from repro.core.vth_model import get_chip_model
from repro.flash.geometry import SSDConfig


def _bench_backends(quick: bool) -> None:
    rng = np.random.default_rng(0)
    rows = 8 if quick else 64
    vth = np.asarray(rng.normal(2.0, 2.0, (rows, 131072)), np.float32)
    plans = PlanCache()
    chip = get_chip_model()
    stack = rng.integers(0, 2**32, (8, rows, 4096), dtype=np.uint64).astype(np.uint32)
    vth_chain = np.asarray(rng.normal(2.0, 2.0, (8, rows, 131072)), np.float32)
    mask = np.full((rows, 4096), 0xFFFFFFFF, np.uint32)
    words = stack[0]

    for backend in (PallasBackend(), SimBackend()):
        for op, kind in (("and", "lsb"), ("or", "msb"), ("xnor", "sbr")):
            plan = plans.get(op, chip)
            us = timeit(lambda backend=backend, plan=plan: jax.block_until_ready(
                backend.sense(vth, plan)))
            emit(f"kernel_{backend.name}_sense_{kind}", us,
                 f"megacells_per_s={vth.size / us:.0f};pages={rows}")
        us = timeit(lambda backend=backend: jax.block_until_ready(
            backend.reduce(stack, "and")))
        emit(f"kernel_{backend.name}_reduce8", us,
             f"gbits_per_s={stack.size * 32 / us / 1e3:.1f}")
        us = timeit(lambda backend=backend: jax.block_until_ready(
            backend.popcount(words)))
        emit(f"kernel_{backend.name}_popcount", us,
             f"gbits_per_s={words.size * 32 / us / 1e3:.1f}")
        # fused megakernels: 8-operand chain, sense epilogue -> reduce (-> count)
        plan = plans.get("and", chip)
        us = timeit(lambda backend=backend, plan=plan: jax.block_until_ready(
            backend.sense_reduce(vth_chain, plan, op="and")))
        emit(f"kernel_{backend.name}_sense_reduce8", us,
             f"megacells_per_s={vth_chain.size / us:.0f}")
        us = timeit(lambda backend=backend, plan=plan: jax.block_until_ready(
            backend.sense_reduce_popcount(vth_chain, plan, mask, op="and")))
        emit(f"kernel_{backend.name}_sense_reduce_popcount8", us,
             f"megacells_per_s={vth_chain.size / us:.0f}")
    emit("kernel_plan_cache", 0.0,
         f"hits={plans.hits};misses={plans.misses}")


def _bench_executor(quick: bool, trace: "str | None" = None) -> None:
    """End-to-end compiled-executor path: 16-operand AND chain materialize."""
    rng = np.random.default_rng(1)
    sess = ComputeSession(config=SSDConfig(page_kb=2 if quick else 16),
                          backend="pallas", trace=bool(trace))
    n = sess.device.config.page_bits
    vecs = []
    for i in range(0, 16, 2):
        a, b = sess.write_pair(f"k{i}", (rng.random(n) < 0.5).astype(np.uint8),
                               f"k{i+1}", (rng.random(n) < 0.5).astype(np.uint8))
        vecs += [a, b]
    expr = sess.chain("and", vecs)
    us = timeit(lambda: jax.block_until_ready(sess.materialize(expr)),
                iters=5 if quick else 20)
    stats = sess.stats()
    emit("executor_chain16_materialize", us,
         f"bits={n};sense_batches={stats['sense_batches']};"
         f"megakernels={stats['megakernel_calls']};"
         f"exec_cache_hits={stats['executor']['hits']};"
         f"traces={stats['executor']['traces']}")
    us = timeit(lambda: sess.popcount(expr), iters=5 if quick else 20)
    emit("executor_chain16_popcount", us, f"bits={n}")
    # die topology: the 8 round-robined pairs sense in parallel across dies,
    # so the schedule's die-parallel time sits below the serial single-die sum
    led = sess.ledger
    speedup = led.serial_us() / max(led.die_step_us, 1e-9)
    emit("executor_chain16_die_parallel", led.die_step_us,
         f"serial_us={led.serial_us():.1f};die_parallel_speedup={speedup:.2f};"
         f"concurrent_dies={stats['max_concurrent_dies']};"
         f"waves={stats['sense_waves']};shards={stats['arena_shards']}")
    assert led.die_step_us <= led.serial_us()
    if trace:
        tr = sess.trace
        assert abs(tr.makespan_us() - led.makespan_us()) < 1e-6
        emit("executor_chain16_trace", tr.makespan_us(),
             f"path={tr.export(trace)}")
        print(tr.report(led))


def _overlap_session(mode: str, quick: bool, trace: bool = False) -> tuple:
    """Fresh device + session + the mixed-op multi-wave DAG the overlap
    benchmark times.  chain16 fuses into ONE wave (all pair senses share a
    plan), so pipelining has nothing to overlap there; this DAG cycles the
    pair ops through and/xor/or over two dies — 3 plans x 2 dies = 6 sense
    groups packed into 3 waves of 2 die-parallel groups — and OR-folds the
    pair results in the controller (mixed plans block fusion)."""
    rng = np.random.default_rng(7)
    sess = ComputeSession(config=SSDConfig(page_kb=2 if quick else 16),
                          backend="pallas", overlap=mode, drain_depth=2,
                          trace=trace)
    n = sess.device.config.page_bits
    ops = ("and", "xor", "or")
    pairs = []
    for i in range(8):
        a, b = sess.write_pair(f"o{i}a", (rng.random(n) < 0.5).astype(np.uint8),
                               f"o{i}b", (rng.random(n) < 0.5).astype(np.uint8),
                               die=i % 2)
        pairs.append(a._binary(ops[i % 3], b))
    expr = sess.chain("or", pairs)
    return sess, expr


def _bench_overlap(quick: bool, trace: "str | None" = None) -> None:
    """Double-buffered host pipelining: the same multi-wave DAG accounted
    under the ledger's "overlap" mode (channel/host steps concurrent with
    later waves' die work) vs the "sync" non-overlapped baseline.  The
    makespans are deterministic simulated time, so one materialize each
    suffices — the emitted value is the overlapped makespan."""
    sess_ov, expr_ov = _overlap_session("overlap", quick, trace=bool(trace))
    h = sess_ov.materialize_async(expr_ov)
    sess_ov.drain()
    assert h.done
    ov = sess_ov.ledger

    sess_sy, expr_sy = _overlap_session("sync", quick)
    sess_sy.materialize(expr_sy)
    sy = sess_sy.ledger

    waves = sess_ov.sense_waves
    assert waves >= 3, f"overlap DAG must span >=3 waves, got {waves}"
    assert ov.overlapped_channel_us > 0, "no channel/die overlap booked"
    assert ov.makespan_us() < sy.makespan_us(), (
        f"pipelined makespan {ov.makespan_us():.1f}us must beat "
        f"non-overlapped {sy.makespan_us():.1f}us")
    emit("executor_chain16_overlap", ov.makespan_us(),
         f"sync_us={sy.makespan_us():.1f};"
         f"speedup={sy.makespan_us() / ov.makespan_us():.3f};"
         f"overlapped_channel_us={ov.overlapped_channel_us:.1f};"
         f"waves={waves};drain_submits={sess_ov.host_drain_submits}")
    if trace:
        tr = sess_ov.trace
        path = trace.rsplit(".", 1)[0] + "_overlap.json"
        if tr is not None:
            emit("executor_overlap_trace", tr.makespan_us(),
                 f"path={tr.export(path)}")


def main(quick: bool = True, trace: "str | None" = None) -> None:
    t0 = time.perf_counter()
    _bench_backends(quick)
    _bench_executor(quick, trace=trace)
    _bench_overlap(quick, trace=trace)
    emit("kernel_throughput_total", (time.perf_counter() - t0) * 1e6,
         f"quick={int(quick)}")
    write_json("BENCH_kernels.json")


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", default=True,
                    help="small shapes (default; CI smoke mode)")
    ap.add_argument("--full", dest="quick", action="store_false")
    ap.add_argument("--trace", nargs="?", const="trace_kernels.json",
                    default=None, metavar="OUT_JSON",
                    help="export the chain16 executor run's Chrome trace")
    args = ap.parse_args()
    main(quick=args.quick, trace=args.trace)
