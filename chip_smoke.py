"""Smoke run of the bitmap-query serving path on a TPU.

The deployment is a user-activity bitmap index, the workload of the In-DRAM
Bulk Bitwise Execution Engine (Ambit) bitmap-index study: one bitmap per day,
one bit per user, set when that user was active that day.  The run drives
the system's real entry points in one process:

- a :class:`repro.api.ComputeSession` on the default ``SSDConfig`` (16 KiB
  pages, 16 channels x 8 dies, MLC) loads 64 daily bitmaps of 2^24 users as
  32 co-located ``write_pair`` calls (4096 wordlines of float32 Vth, about
  2 GiB of arena in device memory), data made from ``--seed``;
- a :class:`repro.serve.QueryEngine` serves 17 requests in batches of 8:
  weekly "active every day" AND chains with on-device popcount, "active any
  day" OR chains, week-over-week XORs, materialised NANDs and fused chains
  over whole pairs;
- a second, small TLC session serves ``a & b & c`` and ``a ^ b ^ c`` over one
  co-located triple, so the multi-reference parity sense runs too.

Every result is compared bit for bit with a plain NumPy reference computed
on the host bits.  The printed times are set-up and first-pass times
(compiles included), not speed measurements.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # die shards over four chips, compared
                                      # with the one-device path

The last line of standard output is one JSON object naming the device.  Where
JAX finds no TPU the script exits nonzero before any work and prints no such
line; any failed check raises, so the exit code is nonzero then too.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

DAYS = 64
USERS = 1 << 24
TLC_USERS = 1 << 21


def check(cond: bool, what: str) -> None:
    """Fail the run (nonzero exit, no result line) when ``cond`` is false."""
    if not cond:
        raise RuntimeError(f"check failed: {what}")


# -- data and reference --------------------------------------------------------
def make_days(n_users: int, n_days: int, seed: int) -> np.ndarray:
    """(n_days, n_users) uint8 activity bits.  Each user has an activity
    propensity, so a user active one day is likelier active on the next and
    weekly AND chains keep a nonzero count."""
    rng = np.random.default_rng(seed)
    propensity = rng.random(n_users, dtype=np.float32)
    days = np.empty((n_days, n_users), np.uint8)
    for d in range(n_days):
        days[d] = rng.random(n_users, dtype=np.float32) < propensity
    return days


def lane_major_bits(words, n_cells: int) -> np.ndarray:
    """Unpack lane-major packed uint32 words (word ``w`` of a 4096-cell tile
    holds bit ``k`` of cell ``k*128 + w``) into one uint8 bit per cell."""
    w = np.asarray(words, np.uint32).reshape(-1, 128)
    shifts = np.arange(32, dtype=np.uint32)[None, :, None]
    bits = ((w[:, None, :] >> shifts) & 1).astype(np.uint8).reshape(-1)
    check(bits.size >= n_cells, "result shorter than the bitmap")
    return bits


def week(w: int) -> list:
    return [f"d{d}" for d in range(7 * w, 7 * w + 7)]


def requests(days: np.ndarray) -> list:
    """The served mix: (label, builder(session) -> expr, popcount, reference
    bits or count).  Day ``d`` is stored as vector ``d<d>``."""
    def all_of(names):
        return np.bitwise_and.reduce(days[[int(n[1:]) for n in names]])

    def any_of(names):
        return np.bitwise_or.reduce(days[[int(n[1:]) for n in names]])

    out = []
    for w in range(4):                       # weekly "active every day"
        out.append((f"count(every day of week {w})",
                    lambda s, w=w: s.chain("and", week(w)), True,
                    int(all_of(week(w)).sum())))
    for w in range(4, 8):                    # weekly "active any day"
        out.append((f"any day of week {w}",
                    lambda s, w=w: s.chain("or", week(w)), False,
                    any_of(week(w))))
    for w in (0, 4):                         # week-over-week change
        out.append((f"every day of week {w} XOR week {w + 1}",
                    lambda s, w=w: (s.chain("and", week(w))
                                    ^ s.chain("and", week(w + 1))), False,
                    all_of(week(w)) ^ all_of(week(w + 1))))
    for d in (20, 40):                       # NAND of a co-located pair
        out.append((f"NAND(d{d}, d{d + 1})",
                    lambda s, d=d: ~(s[f"d{d}"] & s[f"d{d + 1}"]), False,
                    1 - (days[d] & days[d + 1])))
    out.append(("count(any day of week 8)",
                lambda s: s.chain("or", week(8)), True,
                int(any_of(week(8)).sum())))
    weekdays = week(2)[:5]
    out.append(("every weekday of week 2",
                lambda s: s.chain("and", weekdays), False, all_of(weekdays)))
    out.append(("d10 XOR d11", lambda s: s["d10"] ^ s["d11"], False,
                days[10] ^ days[11]))
    span = [f"d{d}" for d in range(48, 56)]  # four whole pairs: one megakernel
    out.append(("every day of days 48-55",
                lambda s: s.chain("and", span), False, all_of(span)))
    # last, so that it forms a batch of its own: a lone fused popcount root
    # folds the count into the megakernel
    month = [n for w in range(4) for n in week(w)]
    out.append(("count(every day of weeks 0-3)",
                lambda s: s.chain("and", month), True, int(all_of(month).sum())))
    return out


# -- system under test -----------------------------------------------------------
def load(sess, days: np.ndarray) -> None:
    """Write day bitmaps ``2i, 2i+1`` as one co-located pair each."""
    for d in range(0, days.shape[0], 2):
        sess.write_pair(f"d{d}", days[d], f"d{d + 1}", days[d + 1])


def serve(sess, reqs: list, max_batch: int = 8) -> tuple:
    """Submit every request to a fresh QueryEngine and resolve them all;
    returns the results in request order and the engine's counters."""
    from repro.serve import QueryEngine, SLOConfig

    eng = QueryEngine(sess, SLOConfig(max_batch_requests=max_batch,
                                      max_delay_us=1e9))
    tickets = []
    for _, build, popcount, _ in reqs:
        tickets.append(eng.submit(build(sess), popcount=popcount))
        eng.poll()
    out = eng.drain(tickets)
    st = eng.stats()
    check(st["requests_completed"] == len(reqs),
          f"{st['requests_completed']} of {len(reqs)} requests completed")
    return out, st


def compare(reqs: list, results: list, n_users: int) -> None:
    """Bit-exact comparison of every served result with its reference."""
    for (label, _, popcount, want), got in zip(reqs, results):
        if popcount:
            check(int(got) == want, f"{label}: count {got} != {want}")
            continue
        bits = lane_major_bits(got, n_users)
        check(np.array_equal(bits[:n_users], want), f"{label}: bits differ")
        check(not bits[n_users:].any(), f"{label}: page padding not zero")


def arena_bytes(sess) -> int:
    arena = sess.device.arena
    return arena.used * arena.page_bits * np.dtype(np.float32).itemsize


def device_memory(dev) -> str:
    stats = dev.memory_stats() or {}
    return (f"bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def run_mlc(n_users: int, seed: int, config=None, device=None) -> dict:
    """Load, serve twice (the second pass replays cached executables) and
    check the MLC bitmap deployment; returns counters for the report."""
    from repro.api import ComputeSession

    t0 = time.perf_counter()
    days = make_days(n_users, DAYS, seed)
    reqs = requests(days)
    if device is None:
        sess = ComputeSession(config=config, seed=seed)
    else:
        sess = ComputeSession(device=device)
    load(sess, days)
    setup_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    results, st = serve(sess, reqs)
    first_s = time.perf_counter() - t1
    compare(reqs, results, n_users)
    misses_first = sess.executor.stats()["misses"]
    t2 = time.perf_counter()
    again, _ = serve(sess, reqs)
    second_s = time.perf_counter() - t2
    compare(reqs, again, n_users)
    ex = sess.executor.stats()
    return {"session": sess, "requests": reqs, "results": results,
            "setup_s": setup_s, "first_pass_with_compiles_s": first_s,
            "second_pass_s": second_s, "requests_completed": 2 * len(reqs),
            "batches": st["batches_dispatched"], "waves": sess.sense_waves,
            "megakernel_calls": sess.megakernel_calls,
            "executable_misses_first_pass": misses_first,
            "executable_misses_second_pass": ex["misses"] - misses_first,
            "arena_bytes": arena_bytes(sess)}


def run_tlc(n_users: int, seed: int, config=None) -> dict:
    """One co-located TLC triple: 3-operand AND and XOR (parity sense)."""
    from repro.api import ComputeSession

    rng = np.random.default_rng(seed + 1)
    a, b, c = (rng.random((3, n_users)) < 0.5).astype(np.uint8)
    sess = ComputeSession(config=config, seed=seed, encoding="tlc")
    sess.write_triple("a", a, "b", b, "c", c)
    reqs = [("a & b & c", lambda s: s["a"] & s["b"] & s["c"], False, a & b & c),
            ("a ^ b ^ c", lambda s: s["a"] ^ s["b"] ^ s["c"], False, a ^ b ^ c),
            ("count(a & b & c)", lambda s: s["a"] & s["b"] & s["c"], True,
             int((a & b & c).sum()))]
    results, _ = serve(sess, reqs)
    compare(reqs, results, n_users)
    return {"session": sess, "requests_completed": len(reqs)}


def report(name: str, r: dict) -> None:
    keep = {k: v for k, v in r.items()
            if k not in ("session", "requests", "results")}
    print(f"{name}: {json.dumps(keep)}")


# -- entry point -----------------------------------------------------------------
def one_chip(n_users: int, seed: int, config=None) -> list:
    """The MLC deployment and the TLC triple on the default device; returns
    the sessions it served through."""
    import jax

    r = run_mlc(n_users, seed, config=config)
    report("mlc", r)
    print(f"arena GiB: {r['arena_bytes'] / 2**30}")
    print(f"device memory: {device_memory(jax.devices()[0])}")
    t = run_tlc(min(n_users, TLC_USERS), seed, config=config)
    report("tlc", t)
    print(f"bit-exact: {r['requests_completed'] + t['requests_completed']} "
          "requests against the NumPy reference")
    return [r["session"], t["session"]]


def four_chips(n_users: int, seed: int, config=None) -> list:
    """The MLC deployment with die shards pinned round-robin to four
    devices, against the same data on the one-device path."""
    import jax

    from repro.flash.device import FlashDevice

    devices = jax.devices()
    check(len(devices) == 4, f"the four-chip path needs 4 devices, "
          f"found {len(devices)}")
    placed = run_mlc(n_users, seed, device=FlashDevice(
        config=config, seed=seed, shard_devices="auto"))
    sess = placed["session"]
    arena = sess.device.arena
    homes = {d.id for die in arena.shard_stats()
             for d in arena.shard(die).buf.devices()}
    check(len(homes) == 4, f"arena shards live on devices {sorted(homes)}")
    check(sess.placed_unit_dispatches > 0, "no wave unit ran placed")
    report("placed", placed)
    print(f"arena shards on devices {sorted(homes)}")
    single = run_mlc(n_users, seed,
                     device=FlashDevice(config=config, seed=seed))
    check(single["session"].device.arena.devices is None,
          "the reference path is not the unplaced one")
    report("single-device", single)
    for (label, _, popcount, _), a, b in zip(placed["requests"],
                                             placed["results"],
                                             single["results"]):
        same = (int(a) == int(b) if popcount
                else np.array_equal(np.asarray(a), np.asarray(b)))
        check(same, f"{label}: placed and single-device results differ")
    for d in devices:
        print(f"device {d.id} memory: {device_memory(d)}")
    print(f"bit-exact: placed == single-device == NumPy on "
          f"{len(placed['requests'])} requests")
    return [sess, single["session"]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the die-sharded multi-chip path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"no TPU: JAX backend is {jax.default_backend()!r}",
              file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    sessions = (four_chips if args.chips == 4 else one_chip)(USERS, args.seed)
    check(all(s.backend.interpret is False for s in sessions),
          "a session's Pallas backend resolved to interpret mode")
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
