"""Bitmap-query serving demo: concurrent predicate requests through the
:class:`repro.serve.QueryEngine` with cross-request wave coalescing.

Eight analytics-style predicates over shared column bitmaps arrive one at a
time; the engine admits each immediately (returning a ticket), forms
SLO-bounded batches, and lowers every batch in ONE pass so senses shared
across requests dispatch as shared waves — fewer waves than the same
requests would take served one at a time.  Results stream back per-request
through rid-tagged drain handles, and the exported Chrome trace carries a
request-lifecycle span per query (the per-request p99 input).

    PYTHONPATH=src python examples/serve_bitmap.py
"""
import numpy as np

from repro.api import ComputeSession
from repro.compile_cache import enable_compile_cache
from repro.flash.geometry import SSDConfig
from repro.serve import QueryEngine, SLOConfig

enable_compile_cache()

rng = np.random.default_rng(7)
sess = ComputeSession(config=SSDConfig(page_kb=1), backend="pallas",
                      trace=True)
n = sess.device.config.page_bits

# shared column bitmaps: region / tier / activity flags, striped over dies
cols = {}
names = ["us", "eu", "paid", "trial", "active", "churned"]
for i in range(0, len(names), 2):
    a, b = names[i], names[i + 1]
    cols[a] = (rng.random(n) < 0.5).astype(np.uint8)
    cols[b] = (rng.random(n) < 0.5).astype(np.uint8)
    va, vb = sess.write_pair(a, cols[a], b, cols[b],
                             die=(i // 2) % sess.device.config.dies)
    cols[a + "_v"], cols[b + "_v"] = va, vb

v = lambda name: cols[name + "_v"]
queries = [
    ("us AND paid", v("us") & v("paid"), False),
    ("eu AND active", v("eu") & v("active"), False),
    ("paid XOR trial", v("paid") ^ v("trial"), False),
    ("us OR eu", v("us") | v("eu"), False),
    ("count(us AND paid)", v("us") & v("paid"), True),        # shares senses
    ("count(active)", v("active") & v("active"), True),
    ("eu AND churned", v("eu") & v("churned"), False),
    ("count(eu AND active)", v("eu") & v("active"), True),    # shares senses
]

# how many waves these queries would cost served one at a time
solo_waves = sum(len(sess.lower(expr).waves) for _, expr, _ in queries)

eng = QueryEngine(sess, SLOConfig(max_batch_requests=4, max_delay_us=1e6))
tickets = []
for label, expr, popcount in queries:
    tickets.append((label, eng.submit(expr, popcount=popcount)))
    eng.poll()                        # dispatches once a full batch forms
eng.drain()

for label, ticket in tickets:
    res = ticket.result()
    shown = f"{res} bits set" if ticket.popcount else \
        f"{int(np.asarray(res).size)} packed words (batch {ticket.batch})"
    print(f"  rid {ticket.rid}: {label:<22s} -> {shown}")

st = eng.stats()
print(f"\n{st['requests_completed']} requests in "
      f"{st['batches_dispatched']} coalesced batches: "
      f"{st['sense_waves']} waves dispatched vs {solo_waves} solo "
      f"(waves_shared={st['waves_shared']}, "
      f"coalesced_sense_groups={st['coalesced_sense_groups']})")
assert st["sense_waves"] < solo_waves, "coalescing should beat solo serving"
path = sess.trace.export("trace_serve_example.json")
print(f"per-request lifecycle spans exported to {path} "
      "(load in chrome://tracing or ui.perfetto.dev)")
