"""Quickstart: MCFlash in 60 seconds — through the compute-session API.

Opens a :class:`repro.api.ComputeSession` on a simulated COTS 3D NAND chip,
registers two random operand vectors as aligned shared pages, records lazy
bitwise expressions, and materializes every Table-1 op in-flash (shifted
reads / SBR through the Pallas sensing kernels), verifying bit-exactness.
Then prints the plan cache behaviour, the Fig-9 system-level timelines, and
the traced device timeline of everything this script just executed.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.api import ComputeSession
from repro.compile_cache import enable_compile_cache
from repro.core import encoding, rber
from repro.flash import (TimingModel, isc_time_us, mcflash_time_us,
                         osc_time_us)

enable_compile_cache()

sess = ComputeSession(backend="pallas", seed=0, trace=True)
chip = sess.chip
print(f"chip: {chip.part_number} ({chip.description})\n")

print("== Table-1 read plans (compiled once per op through the plan cache) ==")
for line in sess.describe_plans():
    print("  " + line)

print("\n== lazy in-flash ops on one 16 kB wordline pair ==")
rng = np.random.default_rng(0)
n = sess.device.config.page_bits
a_bits = (rng.random(n) < 0.5).astype(np.uint8)
b_bits = (rng.random(n) < 0.5).astype(np.uint8)
a, b = sess.write_pair("a", a_bits, "b", b_bits)

exprs = {
    "and": a & b,
    "or": a | b,
    "xnor": a.xnor(b),
    "xor": a ^ b,
    "nand": ~(a & b),           # rewrites to one inverse-read sense
}
for op, expr in exprs.items():
    got = np.asarray(sess.materialize(expr, unpacked=True))
    want = np.asarray(encoding.logical_op(op, a_bits, b_bits))
    ok = bool(np.array_equal(got, want))
    us = sess.ledger.die_busy_us[0]
    print(f"  {op.upper():5s}: bit-exact={ok}  (cumulative die time {us:.0f} us)")

s = sess.stats()
print(f"\nplan cache: {s['plan_cache']}  "
      f"(every repeat op was a cache hit — re-planned at most once per op)")
print(f"in-flash senses: {s['in_flash_senses']}, "
      f"fused controller combines: {s['fused_reduce_calls']}")

print("\n== RBER vs endurance (paper Table 2 / Fig 6) ==")
for n_pe in (0, 1500, 10000):
    r = rber.measure_rber("xnor", chip, pages=8, n_pe=n_pe, seed=1)
    print(f"  XNOR @ {n_pe:>6d} P/E: RBER = {r.rber_pct:.5f}%")

print("\n== Fig 9 system timelines (2 x 8 MB operands) ==")
t = TimingModel()
print(f"  OSC                 {osc_time_us(t):7.0f} us   (paper 2063)")
print(f"  ISC                 {isc_time_us(t):7.0f} us   (paper 1495)")
print(f"  MCFlash (aligned)   {mcflash_time_us(t):7.0f} us   (paper 1087)")
print(f"  MCFlash (realign)   {mcflash_time_us(t, aligned=False):7.0f} us   (paper 1807)")

# every program/sense/DMA above was recorded as a span on its die/channel
# lane; `sess.trace.export("trace.json")` writes the Perfetto-loadable JSON
print("\n== traced device timeline of this session ==")
print(sess.trace.report(sess.ledger))
