"""Serving engine: cross-request wave coalescing (batch vs serial bit-exact
across backends/encodings), SLO scheduling (anti-starvation, delay/depth
bounds), rid-tagged trace attribution, DrainHandle readiness probing, the
tail-mask LRU bound, and the LM engine's decode-call-count regression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ComputeSession
from repro.api.hostio import DrainHandle
from repro.api.session import TAIL_MASK_CACHE_CAP
from repro.core import tlc
from repro.flash.geometry import SSDConfig
from repro.serve import QueryEngine, SLOConfig


def _session(backend="pallas", encoding=tlc.MLC, trace=False):
    return ComputeSession(config=SSDConfig(page_kb=1), backend=backend,
                          encoding=encoding, trace=trace)


def _workload(sess, rng, n_requests=8, tag=""):
    """Mixed predicate stream over shared pairs striped across dies.

    Returns (exprs, popcounts, oracles): one DAG per request + numpy truth."""
    n = sess.device.config.page_bits - 96      # exercises the tail mask
    dies = sess.device.config.dies
    bits, vecs = {}, []
    for i in range(4):
        a, b = f"{tag}a{i}", f"{tag}b{i}"
        bits[a] = rng.integers(0, 2, n, dtype=np.uint8)
        bits[b] = rng.integers(0, 2, n, dtype=np.uint8)
        va, vb = sess.write_pair(a, bits[a], b, bits[b], die=i % dies)
        vecs.append((va, vb, bits[a], bits[b]))
    exprs, pcs, oracles = [], [], []
    for i in range(n_requests):
        va, vb, ba, bb = vecs[i % 4]
        kind = i % 4
        if kind == 0:
            exprs.append(va & vb); oracles.append(ba & bb)
        elif kind == 1:
            exprs.append(va ^ vb); oracles.append(ba ^ bb)
        elif kind == 2:
            vc = vecs[(i + 1) % 4][0]
            bc = vecs[(i + 1) % 4][2]
            exprs.append(sess.chain("or", [va, vb, vc]))
            oracles.append(ba | bb | bc)
        else:                                   # popcount aggregate
            exprs.append(va & vb); oracles.append(ba & bb)
        pcs.append(kind == 3)
    return exprs, pcs, oracles


def _resolve(ticket, oracle):
    if ticket.popcount:
        assert ticket.result() == int(oracle.sum()), ticket.rid
        return
    from repro.kernels import ops as kops
    words = np.asarray(ticket.result())
    got = np.asarray(kops.unpack_bits(
        jnp.asarray(words).reshape(1, -1))[0][:oracle.size])
    np.testing.assert_array_equal(got, oracle, err_msg=f"rid {ticket.rid}")


# ------------------------ coalescing correctness ----------------------------

@pytest.mark.parametrize("backend", ["pallas", "sim"])
@pytest.mark.parametrize("encoding", list(tlc.ENCODINGS))
def test_batched_serving_bit_exact_and_coalesces(backend, encoding):
    """N interleaved requests (mixed ops, mixed dies, popcounts) through the
    engine must equal the serial path bit-for-bit AND dispatch fewer waves
    than the same requests' solo plans."""
    sess = _session(backend, encoding)
    rng = np.random.default_rng(5)
    exprs, pcs, oracles = _workload(sess, rng, n_requests=8)
    solo_waves = sum(len(sess.lower(e).waves) for e in exprs)

    # one batch holds all 8 requests: i and i+4 are structurally identical
    # DAGs, so the shared lowering MUST dedupe their senses across requests
    eng = QueryEngine(sess, SLOConfig(max_batch_requests=8,
                                      max_delay_us=1e9))
    tickets = [eng.submit(e, popcount=pc) for e, pc in zip(exprs, pcs)]
    eng.drain()
    for t, oracle in zip(tickets, oracles):
        _resolve(t, oracle)

    st = eng.stats()
    assert st["requests_admitted"] == st["requests_completed"] == 8
    assert st["coalesced_sense_groups"] >= 1, st
    assert st["waves_shared"] >= 1, st
    assert st["sense_waves"] < solo_waves, (st, solo_waves)


def test_cross_request_cse_dedupes_shared_subdag():
    """Two requests sharing the sub-DAG (a & b) lower once: the shared sense
    group carries both rids and the batch beats the solo wave count."""
    sess = _session("sim")
    rng = np.random.default_rng(1)
    n = sess.device.config.page_bits
    arrs = [rng.integers(0, 2, n, dtype=np.uint8) for _ in range(4)]
    va, vb = sess.write_pair("a", arrs[0], "b", arrs[1])
    vc, vd = sess.write_pair("c", arrs[2], "d", arrs[3])
    shared = va & vb
    e1, e2 = shared | vc, shared ^ vd

    # structural check on the shared lowering: the (a & b) sense lowers
    # ONCE and its group carries both owning rids
    plan = sess.lower_batch([e1, e2], rids=[0, 1])
    assert any(g.rids == (0, 1) for g in plan.groups), \
        [g.rids for g in plan.groups]
    solo_items = sum(len(g.items) for e in (e1, e2)
                     for g in sess.lower(e).groups)
    batch_items = sum(len(g.items) for g in plan.groups)
    assert batch_items < solo_items, (batch_items, solo_items)

    eng = QueryEngine(sess)
    t1, t2 = eng.submit(e1), eng.submit(e2)
    eng.drain()
    _resolve(t1, (arrs[0] & arrs[1]) | arrs[2])
    _resolve(t2, (arrs[0] & arrs[1]) ^ arrs[3])
    st = eng.stats()
    assert st["batches_dispatched"] == 1
    assert st["coalesced_sense_groups"] >= 1, st


def test_result_before_dispatch_self_dispatches():
    """ticket.result() on an undispatched request pumps the engine itself —
    no explicit step()/drain() needed."""
    sess = _session("sim")
    rng = np.random.default_rng(2)
    exprs, pcs, oracles = _workload(sess, rng, n_requests=2)
    eng = QueryEngine(sess)
    t = eng.submit(exprs[0])
    assert not t.dispatched
    _resolve(t, oracles[0])
    assert t.dispatched and t.done


# --------------------------- SLO scheduling ---------------------------------

def test_aged_out_request_preempts_priority_order():
    """Pathological arrival order: a zero-priority request vs an endless
    high-priority stream.  With aging disabled it would starve forever;
    max_wait_batches forces it into a batch."""
    sess = _session("sim")
    rng = np.random.default_rng(3)
    exprs, _, oracles = _workload(sess, rng, n_requests=8)
    slo = SLOConfig(max_batch_requests=2, max_wait_batches=2,
                    max_delay_us=1e9, aging_weight=0.0)
    eng = QueryEngine(sess, slo)
    low = eng.submit(exprs[0], priority=0.0)
    batches = []
    for i in range(1, 7, 2):                   # keep 2 high-prio queued
        eng.submit(exprs[i], priority=10.0)
        eng.submit(exprs[i + 1], priority=10.0)
        eng.step()
        batches.append(low.dispatched)
    # starved for max_wait_batches formations, then force-shipped
    assert batches == [False, False, True]
    assert eng.stats()["preempted_dispatches"] >= 1
    eng.drain()
    _resolve(low, oracles[0])


def test_delay_bound_forces_partial_batch():
    """poll() must not hold a lone request past max_delay_us."""
    sess = _session("sim")
    rng = np.random.default_rng(4)
    exprs, _, oracles = _workload(sess, rng, n_requests=1)
    eng = QueryEngine(sess, SLOConfig(max_batch_requests=8,
                                      max_delay_us=0.0))
    t = eng.submit(exprs[0])
    assert eng.poll() == 1                     # partial batch shipped
    assert eng.stats()["delay_bound_dispatches"] == 1
    _resolve(t, oracles[0])


def test_queue_depth_bound_auto_dispatches():
    sess = _session("sim")
    rng = np.random.default_rng(6)
    exprs, _, oracles = _workload(sess, rng, n_requests=2)
    eng = QueryEngine(sess, SLOConfig(max_batch_requests=2, max_wait_batches=1,
                                      max_delay_us=1e9, max_queue_depth=2))
    t0 = eng.submit(exprs[0])
    assert not t0.dispatched
    t1 = eng.submit(exprs[1])                  # hits the depth bound
    assert t0.dispatched and t1.dispatched
    _resolve(t0, oracles[0])
    _resolve(t1, oracles[1])


def test_answers_stream_back_batch_by_batch():
    """A queue of 4 full batches: poll dispatches 2 and holds the rest
    while their answers are untaken; each batch's first result() dispatches
    the next batch before it blocks, so every batch but the last goes out
    while the one before it is collected, and every answer is exact."""
    sess = _session("sim")
    rng = np.random.default_rng(8)
    exprs, pcs, oracles = _workload(sess, rng, n_requests=8)
    eng = QueryEngine(sess, SLOConfig(max_batch_requests=2,
                                      max_delay_us=0.0))
    tickets = [eng.submit(e, popcount=pc) for e, pc in zip(exprs, pcs)]
    while eng.poll():
        pass
    assert [t.dispatched for t in tickets] == [True] * 4 + [False] * 4
    dispatched = []
    for t, oracle in zip(tickets, oracles):
        _resolve(t, oracle)
        dispatched.append(sum(u.dispatched for u in tickets))
    assert dispatched == [4, 4, 6, 6, 8, 8, 8, 8]
    assert eng.stats()["batches_dispatched"] == 4
    assert eng.poll() == 0 and not eng._untaken


def test_slo_config_validation():
    with pytest.raises(ValueError, match="max_batch_requests"):
        SLOConfig(max_batch_requests=0)
    with pytest.raises(ValueError, match="max_wait_batches"):
        SLOConfig(max_wait_batches=0)
    with pytest.raises(ValueError, match="max_queue_depth"):
        SLOConfig(max_batch_requests=8, max_queue_depth=4)
    with pytest.raises(ValueError, match="lookahead_batches"):
        SLOConfig(lookahead_batches=0)


# ------------------------- trace attribution --------------------------------

def test_serve_trace_carries_rids_and_passes_check_trace(tmp_path):
    from benchmarks.check_trace import check_trace
    sess = _session("sim", trace=True)
    rng = np.random.default_rng(7)
    exprs, pcs, oracles = _workload(sess, rng, n_requests=6)
    eng = QueryEngine(sess, SLOConfig(max_batch_requests=3,
                                      max_delay_us=1e9))
    tickets = [eng.submit(e, popcount=pc) for e, pc in zip(exprs, pcs)]
    eng.drain(tickets)
    assert sess.trace.meta.get("serve_requests") is True
    # every wave-tagged device span names its owning requests
    waves = [s for s in sess.trace.device_spans
             if s.args and s.args.get("wave") is not None]
    assert waves and all(s.args.get("rids") for s in waves)
    # one request-lifecycle wall span per completed request
    path = sess.trace.export(str(tmp_path / "trace.json"))
    stats = check_trace(path)
    assert stats["serve_request_spans"] == 6


# ----------------------- drain/decode correctness ---------------------------

class _FakeDeviceArray:
    """Device-array stand-in: async-copy hook + toggleable readiness."""

    def __init__(self, data):
        self._data = np.asarray(data)
        self.ready = False
        self.async_copies = 0

    def copy_to_host_async(self):
        self.async_copies += 1

    def is_ready(self):
        return self.ready

    def __array__(self, dtype=None):
        return self._data if dtype is None else self._data.astype(dtype)

    @property
    def size(self):
        return self._data.size

    @property
    def dtype(self):
        return self._data.dtype


def test_drain_handle_done_probes_readiness():
    arr = _FakeDeviceArray(np.arange(4, dtype=np.uint32))
    h = DrainHandle(arr, 16)
    assert arr.async_copies == 1               # DMA started at submit
    assert not h.done                          # transfer still in flight
    arr.ready = True
    assert h.done                              # is_ready() flipped
    np.testing.assert_array_equal(h.result(), np.arange(4, dtype=np.uint32))
    assert h.done                              # memoized result stays done

    # numpy payloads are host-resident from the start
    assert DrainHandle(np.zeros(2, np.uint32), 8).done

    class _Broken(_FakeDeviceArray):
        def is_ready(self):
            raise RuntimeError("device error")

    # a failing readiness probe surfaces, it is not reported as "not ready"
    with pytest.raises(RuntimeError, match="device error"):
        DrainHandle(_Broken(np.zeros(2, np.uint32)), 8).done

    # real jax arrays report done once committed
    dev = DrainHandle(jnp.arange(4, dtype=jnp.uint32), 16)
    jax.block_until_ready(dev._array)
    assert dev.done


def test_tail_mask_cache_is_lru_bounded():
    sess = _session("sim")
    words = 128                                # packer tile: 4096-bit rows
    for i in range(TAIL_MASK_CACHE_CAP + 5):
        sess.tail_mask(i + 1, words)
    cache = sess.stats()["tail_mask_cache"]
    assert cache == {"size": TAIL_MASK_CACHE_CAP,
                     "cap": TAIL_MASK_CACHE_CAP, "evictions": 5}
    # recency: touching the oldest key protects it from the next eviction
    oldest = next(iter(sess._tail_masks))
    sess.tail_mask(oldest[0], words)
    sess.tail_mask(999, words)                 # evicts one more — not oldest
    assert oldest in sess._tail_masks
    assert sess.stats()["tail_mask_cache"]["evictions"] == 6


def test_lm_engine_decode_call_count():
    """generate() must run exactly max_new_tokens - 1 decode steps — the
    dead-final-decode regression guard (it used to pay one extra jitted
    step whose logits nobody consumed)."""
    from repro.configs.base import BlockCfg, ModelConfig
    from repro.serve import Engine, ServeConfig

    cfg = ModelConfig(name="t", family="dense", d_model=32, n_heads=2,
                      n_kv_heads=2, head_dim=16, d_ff=64, vocab=128,
                      pattern=(BlockCfg("attn"),), repeats=2)
    eng = Engine.from_seed(cfg, seed=0, serve_cfg=ServeConfig(max_seq=32))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 1, cfg.vocab)
    out = eng.generate(prompts, max_new_tokens=5)
    assert out.shape == (2, 8 + 5)
    assert eng.decode_calls == 4               # not 5: no dead final step
    eng.generate(prompts, max_new_tokens=1)    # degenerate: no decode at all
    assert eng.decode_calls == 4


#: the profiler spans of one ``QueryEngine.step`` (``repro.drain.wait``
#: nests inside ``repro.drain.block`` there)
STEP_SPANS = ("repro.lower", "repro.verify", "repro.account",
              "repro.executable", "repro.gather", "repro.launch",
              "repro.drain.submit", "repro.drain.block", "repro.drain.wait")


def test_step_spans_on_the_profiler_clock(profiled):
    """One batch wider than the drain depth, traced by ``jax.profiler``:
    every span of the step appears, inside the step's own span, and the
    drain's backpressure shows as ``repro.drain.block``."""
    sess = _session("sim")
    exprs, pcs, oracles = _workload(sess, np.random.default_rng(5),
                                    n_requests=4)
    assert len(exprs) > sess.host_queue.depth
    eng = QueryEngine(sess, SLOConfig(max_batch_requests=4))
    tickets = [eng.submit(e, popcount=pc) for e, pc in zip(exprs, pcs)]
    spans = profiled(eng.step)
    (step,) = spans["repro.serve.step"]
    for name in STEP_SPANS:
        assert name in spans, (name, sorted(spans))
        assert all(step[0] <= a <= b <= step[1] for a, b in spans[name]), \
            name
    # two transfers in flight, so the third and fourth result block
    assert len(spans["repro.drain.block"]) == 2
    for t, oracle in zip(tickets, oracles):
        _resolve(t, oracle)


def test_queue_wait_counts_each_dispatched_request():
    """``queue_wait_us`` observes admission to dispatch once per request."""
    sess = _session("sim")
    exprs, pcs, oracles = _workload(sess, np.random.default_rng(6))
    eng = QueryEngine(sess, SLOConfig(max_batch_requests=3))
    tickets = [eng.submit(e, popcount=pc) for e, pc in zip(exprs, pcs)]
    assert eng.stats()["queue_waits"] == 0
    eng.step()
    st = eng.stats()
    assert st["queue_waits"] == 3 and st["queue_wait_us_sum"] > 0
    eng.drain()
    st = eng.stats()
    assert st["queue_waits"] == len(exprs) == st["requests_admitted"]
    # later batches waited through the earlier ones
    assert st["queue_wait_us_sum"] / st["queue_waits"] > \
        eng.metrics["queue_wait_us"].min
    for t, oracle in zip(tickets, oracles):
        _resolve(t, oracle)
