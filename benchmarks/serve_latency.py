"""Serving-engine latency benchmark: concurrent bitmap queries through the
:class:`repro.serve.QueryEngine` with cross-request wave coalescing.

An arrival loop submits a mixed predicate workload (pair AND/XOR/OR,
3-operand chains, popcount aggregates) over shared column bitmaps, the
engine forms SLO-bounded batches, and every request's admit->result latency
is read back from the *exported trace's* request-lifecycle spans — the same
per-request p99 breakdown the README documents.  Embedded assertions gate
the structural win: the batch schedule must dispatch FEWER sense waves than
the sum of the same requests' solo plans (``waves_shared`` /
``coalesced_sense_groups`` must be live), and every result is checked
bit-exact against a NumPy oracle.

Results land in ``BENCH_serve.json``; CI gates ``serve_p99_us`` against
``benchmarks/baselines/serve_quick.json`` (generous tolerance — wall-clock
medians on shared runners are noisy).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.common import emit, write_json
from repro.api import ComputeSession
from repro.compile_cache import enable_compile_cache
from repro.flash.geometry import SSDConfig
from repro.serve import QueryEngine, SLOConfig


def _workload(sess: ComputeSession, rng: np.random.Generator, n_cols: int,
              n_requests: int):
    """Shared column bitmaps + a mixed predicate stream over them.

    Returns (exprs, popcounts, oracles): one lazy DAG per request plus the
    NumPy truth its packed result must match."""
    n = sess.device.config.page_bits - 160     # exercise the tail mask
    bits, vecs = {}, {}
    for i in range(n_cols // 2):
        a, b = f"col{2 * i}", f"col{2 * i + 1}"
        bits[a] = (rng.random(n) < 0.5).astype(np.uint8)
        bits[b] = (rng.random(n) < 0.5).astype(np.uint8)
        va, vb = sess.write_pair(a, bits[a], b, bits[b],
                                 die=i % sess.device.config.dies)
        vecs[a], vecs[b] = va, vb

    def pick(k: int):
        names = list(rng.choice(sorted(vecs), size=k, replace=False))
        return names

    exprs, pcs, oracles = [], [], []
    ops = {"and": np.bitwise_and, "or": np.bitwise_or,
           "xor": np.bitwise_xor}
    for i in range(n_requests):
        kind = i % 4
        if kind in (0, 1):                     # pair predicate
            op = ("and", "xor")[kind]
            a, b = pick(2)
            exprs.append(vecs[a]._binary(op, vecs[b]))
            oracles.append(ops[op](bits[a], bits[b]))
        elif kind == 2:                        # 3-operand chain
            a, b, c = pick(3)
            exprs.append(sess.chain("or", [vecs[a], vecs[b], vecs[c]]))
            oracles.append(bits[a] | bits[b] | bits[c])
        else:                                  # popcount aggregate
            a, b = pick(2)
            exprs.append(vecs[a] & vecs[b])
            oracles.append(bits[a] & bits[b])
        pcs.append(kind == 3)
    return exprs, pcs, oracles


def _check(ticket, oracle: np.ndarray) -> None:
    if ticket.popcount:
        got = ticket.result()
        assert got == int(oracle.sum()), (ticket.rid, got, int(oracle.sum()))
        return
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    words = np.asarray(ticket.result())
    n = oracle.size
    unpacked = np.asarray(
        kops.unpack_bits(jnp.asarray(words).reshape(1, -1))[0][:n])
    assert np.array_equal(unpacked, oracle), f"rid {ticket.rid} mismatch"


def main(quick: bool = True, trace: "str | None" = None,
         backend: str = "pallas") -> None:
    t0 = time.perf_counter()
    rng = np.random.default_rng(11)
    sess = ComputeSession(config=SSDConfig(page_kb=1 if quick else 4),
                          backend=backend, trace=True)
    n_requests = 24 if quick else 96
    exprs, pcs, oracles = _workload(sess, rng, n_cols=16,
                                    n_requests=n_requests)

    # the coalescing yardstick: waves each request's SOLO plan would take
    solo_waves = sum(len(sess.lower(e).waves) for e in exprs)

    slo = SLOConfig(max_batch_requests=8, max_wait_batches=3,
                    max_delay_us=5_000.0)
    # warmup pass: populate the executable cache so the gated latencies
    # measure steady-state serving (cached-executable replay), not jit
    # compiles; the measured run below starts from a clean trace/ledger
    warm = QueryEngine(sess, slo)
    warm.drain([warm.submit(e, popcount=pc) for e, pc in zip(exprs, pcs)])
    sess.reset_stats()
    sess.trace.clear()

    t0 = time.perf_counter()
    eng = QueryEngine(sess, slo)
    tickets = []
    for expr, pc in zip(exprs, pcs):
        tickets.append(eng.submit(expr, popcount=pc))
        eng.poll()
    eng.drain(tickets)
    total_us = (time.perf_counter() - t0) * 1e6

    for ticket, oracle in zip(tickets, oracles):
        _check(ticket, oracle)

    st = eng.stats()
    assert st["requests_completed"] == n_requests, st
    assert st["coalesced_sense_groups"] >= 1, \
        f"no cross-request sense coalescing happened: {st}"
    assert st["waves_shared"] >= 1, f"no shared waves dispatched: {st}"
    assert st["sense_waves"] < solo_waves, (
        f"batching dispatched {st['sense_waves']} waves, not fewer than the "
        f"{solo_waves} the same requests take solo — coalescing is dead")

    # per-request latency comes from the trace's request-lifecycle spans —
    # the exact p99 readout the README documents
    lat = sorted(s.dur_us for s in sess.trace.wall_spans
                 if s.category == "serve")
    assert len(lat) == n_requests, (len(lat), n_requests)
    p50 = lat[len(lat) // 2]
    p99 = lat[min(len(lat) - 1, int(len(lat) * 0.99))]
    emit("serve_p50_us", p50, f"requests={n_requests};backend={backend}")
    emit("serve_p99_us", p99,
         f"requests={n_requests};batches={st['batches_dispatched']};"
         f"waves={st['sense_waves']};solo_waves={solo_waves}")
    emit("serve_coalescing", st["sense_waves"],
         f"solo_waves={solo_waves};waves_shared={st['waves_shared']};"
         f"coalesced_groups={st['coalesced_sense_groups']};"
         f"wave_reduction={solo_waves / max(st['sense_waves'], 1):.2f}x")
    emit("serve_throughput", total_us,
         f"requests_per_s={n_requests / (total_us / 1e6):.0f};"
         f"drain_submits={st['host_drain_submits']}")
    if trace:
        emit("serve_trace", sess.trace.makespan_us(),
             f"path={sess.trace.export(trace)}")
    write_json("BENCH_serve.json")


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", default=True,
                    help="small shapes (default; CI smoke mode)")
    ap.add_argument("--full", dest="quick", action="store_false")
    ap.add_argument("--backend", default="pallas",
                    choices=("pallas", "sim"))
    ap.add_argument("--trace", nargs="?", const="trace_serve.json",
                    default=None, metavar="OUT_JSON",
                    help="export the serving run's Chrome trace")
    args = ap.parse_args()
    main(quick=args.quick, trace=args.trace, backend=args.backend)
