"""In-flash bitmap-index query (paper §6.2) wired into the data pipeline.

Daily user-activity bitmaps live in flash as aligned pairs; the
"active every day" query is recorded as a lazy AND chain over
:class:`repro.api.BitVector` handles and materialized as in-flash senses
plus ONE fused packed combine; the bit-count offloads to the popcount
kernel — exactly the paper's workload, then reused as the framework's
training-data filter (repro.data.bitmap_pipeline).

    PYTHONPATH=src python examples/bitmap_index.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.data import BitmapFilter
from repro.flash import bitmap_index, speedup_table

enable_compile_cache()

rng = np.random.default_rng(11)
n_users = 131072                      # one page worth of users
days = 8

bf = BitmapFilter(n_users)
daily = [(rng.random(n_users) < 0.9).astype(np.uint8) for _ in range(days)]
for d in range(0, days, 2):
    bf.add_pair(f"day{d}", daily[d], f"day{d+1}", daily[d + 1])

pairs = [(f"day{d}", f"day{d+1}") for d in range(0, days, 2)]
mask = bf.select(pairs)
count = bf.count(pairs)
want = np.logical_and.reduce(daily)
np.testing.assert_array_equal(mask, want.astype(bool))
assert count == int(want.sum())
print(f"active-every-day users (in-flash AND over {days} days): "
      f"{count} / {n_users}  — matches host oracle")

stats = bf.session.stats()
print(f"flash commands issued: {stats['ledger']['commands']}; "
      f"die-parallel time {bf.device.ledger.makespan_us():.0f} us (serial {bf.device.ledger.serial_us():.0f} us); "
      f"senses {stats['in_flash_senses']}, fused combines {stats['fused_reduce_calls']}, "
      f"plan cache {stats['plan_cache']}")

# the paper's full-scale projection (800M users, 1-12 months)
for months in (1, 6, 12):
    s = speedup_table(bitmap_index(months))["speedup_vs"]
    print(f"{months:>2d} months: OSC {s['osc']:6.1f}x  ISC {s['isc']:6.1f}x  "
          f"ParaBit {s['parabit']:5.2f}x  FC {s['flashcosmos']:4.2f}x")
