"""repro.obs: device-timeline tracer (lane model, Chrome export, text
report), typed metrics registry behind ``sess.stats()`` (keys unchanged),
and the trace-makespan-equals-ledger-makespan invariant across backends,
die counts, and encodings."""
import json

import numpy as np
import pytest

from benchmarks.check_trace import check_trace
from repro.api import ComputeSession, ExecutableCache, PlanCache
from repro.flash.geometry import SSDConfig
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry, Tracer,
                       traced)

SMALL = SSDConfig(page_kb=1)


def _rand_bits(rng, n):
    return (rng.random(n) < 0.5).astype(np.uint8)


def _traced_session(config=SMALL, backend="pallas", **kw):
    return ComputeSession(config=config, backend=backend, seed=0, trace=True,
                          **kw)


def _run_some_ops(sess, pairs=2, rng_seed=0):
    rng = np.random.default_rng(rng_seed)
    n = sess.device.config.page_bits
    vecs = []
    for i in range(pairs):
        a, b = sess.write_pair(f"a{i}", _rand_bits(rng, n),
                               f"b{i}", _rand_bits(rng, n))
        vecs += [a, b]
    expr = sess.chain("and", vecs)
    sess.materialize(expr)
    return vecs


# -- metrics registry ---------------------------------------------------------

def test_counter_gauge_histogram():
    c = Counter("c", "a counter")
    c.inc()
    c.add(4)
    assert c.value == 5
    with pytest.raises(AssertionError):
        c.add(-1)

    g = Gauge("g", "a gauge")
    g.set(3.0)
    g.set_max(2.0)
    assert g.value == 3.0
    g.set_max(7.0)
    assert g.value == 7.0

    h = Histogram("h", "a histogram")
    assert h.mean == 0.0
    for v in (1.0, 3.0, 8.0):
        h.observe(v)
    assert h.count == 3 and h.total == 12.0
    assert h.summary() == {"count": 3, "sum": 12.0, "mean": 4.0,
                           "min": 1.0, "max": 8.0}


def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("hits", "cache hits")
    assert reg.counter("hits") is c        # get-or-create returns same object
    with pytest.raises(TypeError):
        reg.gauge("hits")                  # same name, different kind
    reg.gauge("depth").set(2.0)
    reg.histogram("sizes").observe(5.0)
    assert {m.name for m in reg} == {"hits", "depth", "sizes"}
    assert "hits" in reg and "nope" not in reg and len(reg) == 3
    c.add(3)
    assert reg.value("hits") == 3 and reg["hits"] is c
    d = reg.as_dict()
    assert d["hits"] == 3 and d["depth"] == 2.0 and d["sizes"]["count"] == 1
    reg.reset()
    assert reg.value("hits") == 0 and reg.value("depth") == 0
    assert reg.histogram("sizes").count == 0


# -- tracer unit behaviour ----------------------------------------------------

def test_tracer_die_step_offsets_and_lanes():
    tr = Tracer()
    tr.die_step(0.0, {0: 10.0, 1: 4.0}, "sense", "wave 0")
    tr.die_step(10.0, {1: 6.0}, "sense", "wave 1")
    tr.channel_step(0.0, {0: 2.0})
    tr.host_step(0.0, 1.5)
    lanes = tr.lanes()
    assert set(lanes) == {"die 0", "die 1", "channel 0", "host-link"}
    # concurrent dies in one step share the step's start offset
    assert [s.start_us for s in lanes["die 0"]] == [0.0]
    assert [(s.start_us, s.end_us) for s in lanes["die 1"]] == [(0.0, 4.0),
                                                               (10.0, 16.0)]
    assert [s.args["step"] for s in lanes["die 1"]] == [0, 1]
    assert tr.makespan_us() == 16.0
    assert tr.lane_end_us()["channel 0"] == 2.0
    tr.clear()
    assert tr.makespan_us() == 0.0 and not tr.device_spans


def test_tracer_max_spans_drops_not_grows():
    tr = Tracer(max_spans=3)
    for i in range(5):
        tr.die_step(float(i), {0: 1.0}, "sense")
    assert len(tr.device_spans) == 3 and tr.dropped == 2


def test_traced_nullcontext_when_off():
    with traced(None, "lower", "lower"):
        pass                               # no tracer -> profiler span only
    tr = Tracer()
    with traced(tr, "lower", "lower", waves=2):
        pass
    assert [s.name for s in tr.wall_spans] == ["lower"]
    assert tr.wall_spans[0].args == {"waves": 2}


def test_attached_tracer_records_host_spans_as_before():
    """The profiler sink leaves the tracer's wall spans as they were: the
    executor's lowering, executable build on a miss and wave dispatch keep
    their names, categories and arguments."""
    sess = _traced_session()
    _run_some_ops(sess)
    spans = {s.name: s for s in sess.trace.wall_spans}
    assert {"lower", "build-executable", "dispatch-waves"} <= set(spans)
    assert spans["lower"].category == "lower"
    assert spans["lower"].args == {"roots": 1}
    assert spans["build-executable"].category == "compile"
    assert spans["dispatch-waves"].category == "dispatch"
    assert spans["build-executable"].args == spans["dispatch-waves"].args
    assert spans["dispatch-waves"].args["waves"] >= 1


def test_traced_feeds_the_profiler_with_or_without_a_tracer(profiled):
    tr = Tracer()

    def spans():
        with traced(None, "verify"):
            pass
        with traced(tr, "lower", "lower", roots=2):
            pass

    got = profiled(spans)
    assert len(got["repro.verify"]) == len(got["repro.lower"]) == 1
    assert [(s.name, s.args) for s in tr.wall_spans] == [("lower",
                                                          {"roots": 2})]


# -- stats() back-compat over the registry ------------------------------------

def test_session_stats_keys_unchanged_and_attr_reads():
    sess = _traced_session()
    _run_some_ops(sess)
    s = sess.stats()
    assert set(s) == {"backend", "encoding", "arena_rows_by_encoding",
                      "plan_cache", "executor", "fused_reduce_calls",
                      "in_flash_senses", "sense_items", "sense_batches",
                      "sense_waves", "max_concurrent_dies",
                      "megakernel_calls", "tiled_megakernel_splits",
                      "arena_shards", "ledger",
                      "plans_verified", "verify_cache_hits", "verify",
                      "faults", "reliability",
                      "placed_unit_dispatches", "arena_gather_programs",
                      "arena_gathered_stacks", "realignments",
                      "copyback_wordlines", "arena_rows_reclaimed",
                      "host_drain",
                      "coalesced_sense_groups", "waves_shared",
                      "tail_mask_cache"}
    # pre-registry attribute reads still work and are plain ints
    for name in ("fused_reduce_calls", "in_flash_senses", "sense_items",
                 "sense_batches", "sense_waves", "megakernel_calls",
                 "tiled_megakernel_splits", "max_concurrent_dies"):
        assert type(getattr(sess, name)) is int
        assert s[name] == getattr(sess, name)
    assert s["in_flash_senses"] >= 1 and s["sense_batches"] >= 1
    # counters live in the typed registry underneath
    assert sess.metrics.value("in_flash_senses") == s["in_flash_senses"]


def test_cache_stats_shapes_unchanged():
    from repro.core.vth_model import get_chip_model
    plans = PlanCache()
    plans.get("and", get_chip_model())
    plans.get("and", get_chip_model())
    assert plans.stats() == {"hits": 1, "misses": 1, "entries": 1}
    cache = ExecutableCache(capacity=2)
    for k in ("a", "b", "c"):
        cache.get(k, lambda k=k: k)
    cache.get("c", lambda: "c")
    assert cache.stats() == {"hits": 1, "misses": 3, "entries": 2,
                             "evictions": 1, "capacity": 2}


def test_reset_stats_and_ledger_reset():
    sess = _traced_session()
    _run_some_ops(sess)
    assert sess.ledger.makespan_us() > 0 and sess.in_flash_senses > 0
    spans_before = len(sess.trace.device_spans)
    sess.reset_stats()
    assert sess.in_flash_senses == 0 and sess.sense_batches == 0
    assert sess.stats()["ledger"]["makespan_us"] == 0.0
    assert sess.ledger.serial_us() == 0.0 and sess.ledger.commands == 0
    # tracer spans survive a stats reset (cleared separately)
    assert len(sess.trace.device_spans) == spans_before
    sess.trace.clear()
    _run_some_ops(sess, rng_seed=1)        # session still fully usable
    assert sess.in_flash_senses > 0
    assert abs(sess.trace.makespan_us() - sess.ledger.makespan_us()) < 1e-6


def test_ledger_summary_reconstructs_makespan():
    sess = _traced_session()
    _run_some_ops(sess)
    summ = sess.ledger.summary()
    for key in ("makespan_us", "die_parallel_us", "channel_step_us",
                "host_busy_us", "serial_us", "die_steps", "energy_uj",
                "commands", "max_parallel_dies", "category_us"):
        assert key in summ, key
    assert summ["makespan_us"] == max(summ["die_parallel_us"],
                                      summ["channel_step_us"],
                                      summ["host_busy_us"])
    assert summ["die_steps"] > 0


# -- exported Chrome trace ----------------------------------------------------

def test_chrome_export_schema_and_lane_invariants(tmp_path):
    sess = _traced_session()
    _run_some_ops(sess, pairs=3)
    path = str(tmp_path / "trace.json")
    assert sess.trace.export(path) == path
    # the CI gate's checker: schema + per-lane non-overlap + makespan match
    stats = check_trace(path)
    assert stats["spans"] > 0 and stats["lanes"] >= 2
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    metas = [e for e in events if e["ph"] == "M"]
    assert {"device (virtual us)", "host (wall clock)"} <= {
        e["args"]["name"] for e in metas if e["name"] == "process_name"}
    xs = [e for e in events if e["ph"] == "X"]
    assert all({"name", "cat", "ts", "dur", "pid", "tid"} <= set(e)
               for e in xs)
    assert doc["otherData"]["makespan_us"] == pytest.approx(
        sess.ledger.makespan_us())
    # wall-clock process saw the host phases
    wall_names = {e["name"] for e in xs if e["pid"] == 2}
    assert "lower" in wall_names and "dispatch-waves" in wall_names
    assert any(e["ph"] == "i" for e in events)     # cache hit/miss instants


def test_die_lane_spans_never_overlap():
    sess = _traced_session()
    _run_some_ops(sess, pairs=4)
    for lane, spans in sess.trace.lanes().items():
        for a, b in zip(spans, spans[1:]):
            assert b.start_us >= a.end_us - 1e-9, (lane, a, b)


# -- the timeline == makespan invariant, across the whole config axis ---------

@pytest.mark.parametrize("encoding", ["mlc", "tlc", "reduced-mlc"])
@pytest.mark.parametrize("dies", [1, 2, 4])
@pytest.mark.parametrize("backend", ["sim", "pallas"])
def test_trace_makespan_equals_ledger(backend, dies, encoding):
    cfg = SSDConfig(page_kb=1, channels=1, dies_per_channel=dies)
    sess = ComputeSession(config=cfg, backend=backend, seed=0,
                          encoding=encoding, trace=True)
    rng = np.random.default_rng(dies)
    n = sess.device.config.page_bits
    bits = [_rand_bits(rng, n) for _ in range(4)]
    a, b = sess.write_pair("a", bits[0], "b", bits[1])
    c, d = sess.write_pair("c", bits[2], "d", bits[3])
    got = np.asarray(sess.materialize((a & b) | (c & d), unpacked=True))
    want = (bits[0] & bits[1]) | (bits[2] & bits[3])
    assert np.array_equal(got, want)
    led = sess.ledger
    tol = 1e-6 * max(1.0, led.makespan_us())
    assert abs(sess.trace.makespan_us() - led.makespan_us()) <= tol
    # each lane family ends exactly at its ledger scalar
    ends = sess.trace.lane_end_us()
    die_end = max(v for k, v in ends.items() if k.startswith("die "))
    assert die_end == pytest.approx(led.die_step_us)
    if led.channel_step_us > 0:
        ch_end = max(v for k, v in ends.items() if k.startswith("channel "))
        assert ch_end == pytest.approx(led.channel_step_us)
    if led.host_busy_us > 0:
        assert ends["host-link"] == pytest.approx(led.host_busy_us)


def test_cross_die_chain16_timeline_end_to_end(tmp_path):
    """Acceptance: a 16-operand chain over 4 dies — die spans from different
    dies overlap inside one wave, channel spans pipeline on their own
    timeline, and the longest lane equals the ledger makespan."""
    cfg = SSDConfig(page_kb=1, channels=2, dies_per_channel=2)
    sess = ComputeSession(config=cfg, backend="pallas", seed=0, trace=True)
    rng = np.random.default_rng(7)
    n = sess.device.config.page_bits
    vecs, oracle = [], np.ones(n, np.uint8)
    for i in range(8):
        ba, bb = _rand_bits(rng, n), _rand_bits(rng, n)
        a, b = sess.write_pair(f"p{i}a", ba, f"p{i}b", bb)
        vecs += [a, b]
        oracle &= ba & bb
    got = np.asarray(sess.materialize(sess.chain("and", vecs), unpacked=True))
    assert np.array_equal(got, oracle)
    led, tr = sess.ledger, sess.trace
    assert sess.stats()["max_concurrent_dies"] > 1
    # die spans of one wave start together and overlap across die lanes
    waves = {}
    for s in tr.device_spans:
        if s.lane.startswith("die ") and s.name.startswith("wave "):
            waves.setdefault(s.args["step"], []).append(s)
    multi = [spans for spans in waves.values()
             if len({s.lane for s in spans}) > 1]
    assert multi, "no wave dispatched >1 die concurrently"
    for spans in multi:
        starts = {s.start_us for s in spans}
        assert len(starts) == 1            # concurrent: same step offset
        assert max(s.dur_us for s in spans) > 0
    # channel DMA pipelines on its own timeline, not serialized after dies
    ends = tr.lane_end_us()
    ch_end = max(v for k, v in ends.items() if k.startswith("channel "))
    assert ch_end == pytest.approx(led.channel_step_us)
    assert ch_end < led.die_step_us        # transfer hides under sensing
    # the headline invariant, end to end through the exported file as well
    tol = 1e-6 * max(1.0, led.makespan_us())
    assert abs(tr.makespan_us() - led.makespan_us()) <= tol
    path = str(tmp_path / "chain16.json")
    tr.export(path)
    assert check_trace(path)["device_end_us"] == pytest.approx(
        led.makespan_us())


# -- text report --------------------------------------------------------------

def test_timeline_report_contents():
    sess = _traced_session()
    _run_some_ops(sess)
    text = sess.trace.report(sess.ledger)
    assert "makespan" in text
    assert "die 0" in text and "host-link" in text
    assert "per category" in text and "per wave" in text
    assert "wave 0:" in text               # executor wave labels survive
