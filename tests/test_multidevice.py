"""Multi-device wave dispatch + overlapped host pipelining.

Two halves:

- **Ledger-mode unit tests** (no devices needed): the three inter-resource
  timing models ("independent" / "sync" / "overlap"), drain-depth
  backpressure, the overlap-consistency invariant, reset symmetry.
- **Placed-dispatch tests** (skipped below 4 JAX devices — run under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4``): per-die wave
  units land on their shard's pinned device, results stay bit-exact against
  the single-device path across all three encodings and both backends, and
  placed/unplaced compilations never share an executable-cache entry.
"""
import jax
import numpy as np
import pytest

from repro.api import ComputeSession, HostDrainQueue, LEDGER_MODES, Ledger
from repro.core import tlc
from repro.verify import PlanInvariantError, check_overlap_consistency

needs_4_devices = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs 4 host devices (run under XLA_FLAGS="
           "--xla_force_host_platform_device_count=4)")


# ------------------------- ledger timing modes ------------------------------

def _book_waves(led: Ledger, n_waves: int = 3, die_us: float = 100.0,
                ch_us: float = 40.0) -> None:
    """n_waves of (die step, channel step) plus one host drain."""
    led.begin_epoch()
    for w in range(n_waves):
        led.add_die_batch({0: die_us, 1: die_us}, wave=w)
        led.add_channel_batch({0: ch_us}, wave=w)
    led.add_host(10.0)


def test_ledger_mode_validation():
    led = Ledger()
    assert led.mode == "independent"
    with pytest.raises(ValueError, match="unknown ledger mode"):
        led.set_mode("pipelined")
    for mode in LEDGER_MODES:
        led.set_mode(mode)
        assert led.mode == mode


def test_independent_mode_preserves_historical_makespan():
    led = Ledger()
    _book_waves(led)
    # free-running timelines: end offsets == busy sums, no step log
    assert led.die_end_us == led.die_step_us == 300.0
    assert led.channel_end_us == led.channel_step_us == 120.0
    assert led.makespan_us() == 300.0
    assert led.step_log == []
    assert led.overlapped_channel_us == 0.0


def test_sync_mode_serializes_everything():
    led = Ledger(mode="sync")
    _book_waves(led)
    # every step waits for everything booked before it
    assert led.makespan_us() == pytest.approx(3 * (100 + 40) + 10)
    assert len(led.step_log) == 7


def test_overlap_mode_hides_channel_time_behind_later_waves():
    sync, ov = Ledger(mode="sync"), Ledger(mode="overlap")
    _book_waves(sync)
    _book_waves(ov)
    # wave k's transfer streams while wave k+1 senses: only the LAST wave's
    # channel step (and the host drain) extend past the die frontier
    assert ov.makespan_us() == pytest.approx(3 * 100 + 40 + 10)
    assert ov.makespan_us() < sync.makespan_us()
    assert ov.overlapped_channel_us == pytest.approx(2 * 40)
    assert ov.overlapped_steps == 2
    # both audits pass: transfers overlap only later waves' die work
    check_overlap_consistency(sync)
    check_overlap_consistency(ov)


def test_overlap_drain_depth_backpressure():
    deep = Ledger(mode="overlap", drain_depth=4)
    _book_waves(deep, n_waves=4, die_us=10.0, ch_us=100.0)
    shallow = Ledger(mode="overlap", drain_depth=1)
    _book_waves(shallow, n_waves=4, die_us=10.0, ch_us=100.0)
    # slow transfers + depth-1 queue: each die step stalls on the previous
    # transfer draining, so the shallow pipeline finishes strictly later
    assert shallow.makespan_us() > deep.makespan_us()
    check_overlap_consistency(shallow)
    check_overlap_consistency(deep)


def test_overlap_consistency_rejects_corrupt_log():
    led = Ledger(mode="overlap")
    _book_waves(led)
    # forge a transfer that starts while its own wave's producer still runs
    led.step_log.append(("channel", led.step_epoch, 0, 50.0, 90.0))
    with pytest.raises(PlanInvariantError, match="overlap-consistency"):
        check_overlap_consistency(led)
    led.step_log.pop()
    # forge an EARLIER wave's die step running inside a later channel step
    led.step_log.append(("die", led.step_epoch, 0, 250.0, 260.0))
    with pytest.raises(PlanInvariantError, match="overlap-consistency"):
        check_overlap_consistency(led)


def test_ledger_reset_restores_fresh_state():
    led = Ledger(mode="overlap", drain_depth=3)
    _book_waves(led)
    assert led.step_log and led.makespan_us() > 0
    led.reset()
    fresh = Ledger(mode="overlap", drain_depth=3)
    assert led.summary() == fresh.summary()
    assert led.step_log == [] and led._channel_ends == []
    assert led.step_epoch == 0
    # mode/drain_depth survive the reset (configuration, not accounting)
    assert led.mode == "overlap" and led.drain_depth == 3


def test_session_reset_clears_overlap_and_placement_counters():
    sess = ComputeSession(backend="sim", overlap=True, drain_depth=2)
    rng = np.random.default_rng(0)
    a = rng.integers(0, 2, 1000, dtype=np.uint8)
    b = rng.integers(0, 2, 1000, dtype=np.uint8)
    va, vb = sess.write_pair("a", a, "b", b)
    h = sess.materialize_async(va & vb)
    sess.drain()
    assert h.done
    assert sess.host_drain_submits == 1
    assert sess.ledger.mode == "overlap"
    assert sess.ledger.step_log
    sess.reset_stats()
    # symmetric reset: every new counter/offset back to zero
    assert sess.host_drain_submits == 0
    assert sess.host_drain_blocks == 0
    assert sess.placed_unit_dispatches == 0
    assert len(sess.host_queue) == 0
    led = sess.ledger
    assert (led.die_end_us, led.channel_end_us, led.host_end_us) == (0, 0, 0)
    assert led.overlapped_channel_us == 0.0 and led.overlapped_steps == 0
    assert led.step_log == [] and led.step_epoch == 0
    assert led.summary() == Ledger(mode="overlap", drain_depth=2).summary()


def test_session_overlap_knob_maps_modes():
    for knob, mode in ((True, "overlap"), ("overlap", "overlap"),
                       ("sync", "sync"), (False, "independent")):
        sess = ComputeSession(backend="sim", overlap=knob)
        assert sess.ledger.mode == mode
    with pytest.raises(ValueError, match="overlap must be"):
        ComputeSession(backend="sim", overlap="both")


def test_host_drain_queue_backpressure_blocks_oldest():
    blocks = []
    q = HostDrainQueue(depth=2, on_block=lambda: blocks.append(1))
    handles = [q.submit(np.arange(8, dtype=np.uint32)) for _ in range(5)]
    # 5 submits through a depth-2 queue force 3 oldest-first resolutions
    assert len(blocks) == 3
    # numpy payloads are host-resident from the start, so every handle
    # reports done (readiness probes bytes, not queue position)
    assert [h.done for h in handles] == [True] * 5
    resolved = q.drain()
    assert [h.done for h in handles] == [True] * 5
    assert resolved == handles[3:]
    np.testing.assert_array_equal(handles[0].result(),
                                  np.arange(8, dtype=np.uint32))


# --------------------- placed multi-device dispatch -------------------------

_OPS = ("and", "xor", "or")


def _random_dag(sess, rng, n_pairs: int, n_bits: int, tag: str):
    """Mixed-op pair DAG across 2 dies + an or-fold root (multi-wave: mixed
    plans block fusion), plus the matching numpy reference."""
    expr = ref = None
    for i in range(n_pairs):
        a = rng.integers(0, 2, n_bits, dtype=np.uint8)
        b = rng.integers(0, 2, n_bits, dtype=np.uint8)
        va, vb = sess.write_pair(f"{tag}a{i}", a, f"{tag}b{i}", b, die=i % 2)
        op = _OPS[i % len(_OPS)]
        pair = va._binary(op, vb)
        pr = {"and": a & b, "xor": a ^ b, "or": a | b}[op]
        expr = pair if expr is None else expr._binary("or", pair)
        ref = pr if ref is None else ref | pr
    return expr, ref


@needs_4_devices
@pytest.mark.parametrize("backend", ["pallas", "sim"])
@pytest.mark.parametrize("encoding", list(tlc.ENCODINGS))
def test_placed_dispatch_bit_exact_vs_single_device(backend, encoding):
    from repro.flash.device import FlashDevice
    n_bits, n_pairs = 3000, 6
    placed = ComputeSession(FlashDevice(shard_devices="auto"),
                            backend=backend, encoding=encoding, overlap=True)
    seeds = np.random.default_rng(3)
    expr_p, ref = _random_dag(placed, seeds, n_pairs, n_bits, "p")
    out_p = np.asarray(placed.materialize(expr_p, unpacked=True))
    np.testing.assert_array_equal(out_p, ref)
    assert placed.placed_unit_dispatches > 0
    # same DAG on an unmapped (single default device) session
    plain = ComputeSession(backend=backend, encoding=encoding)
    seeds = np.random.default_rng(3)
    expr_u, _ = _random_dag(plain, seeds, n_pairs, n_bits, "u")
    out_u = np.asarray(plain.materialize(expr_u, unpacked=True))
    np.testing.assert_array_equal(out_p, out_u)
    assert plain.placed_unit_dispatches == 0


@needs_4_devices
def test_shards_pin_distinct_devices_and_gathers_stay_local():
    from repro.flash.device import FlashDevice
    dev = FlashDevice(shard_devices="auto")
    arena = dev.arena
    pinned = {arena.device_of(d) for d in range(4)}
    assert len(pinned) == 4
    assert arena.compute_device() == arena.device_of(0)
    sess = ComputeSession(dev, backend="pallas")
    rng = np.random.default_rng(5)
    for die in range(4):
        a = rng.integers(0, 2, 1000, dtype=np.uint8)
        b = rng.integers(0, 2, 1000, dtype=np.uint8)
        sess.write_pair(f"d{die}a", a, f"d{die}b", b, die=die)
        wls = dev.ftl.vectors[f"d{die}a"].pages
        local = dev.vth_stack(wls, place=False)
        (got,) = local.devices()
        assert got == arena.device_of(die)
        funneled = dev.vth_stack(wls)          # default still funnels
        (got,) = funneled.devices()
        assert got == arena.compute_device()


@needs_4_devices
def test_executable_cache_disjoint_placed_vs_unplaced():
    from repro.flash.device import FlashDevice

    def run(sess, tag):
        expr, ref = _random_dag(sess, np.random.default_rng(7), 4, 2000, tag)
        out = np.asarray(sess.materialize(expr, unpacked=True))
        np.testing.assert_array_equal(out, ref)
        return sess

    placed = run(ComputeSession(FlashDevice(shard_devices="auto"),
                                backend="pallas"), "x")
    plain = run(ComputeSession(backend="pallas"), "x")
    placed_keys = set(placed.device.executables._entries)
    plain_keys = set(plain.device.executables._entries)
    # the layout component keeps the key spaces disjoint: a placed runner
    # must never serve unplaced inputs (or vice versa)
    assert placed_keys and plain_keys
    assert not placed_keys & plain_keys
    for key in placed_keys:
        assert key[-1] is not None
    for key in plain_keys:
        assert key[-1] is None
    # repeat materialize replays the cached placed runner without rebuilding
    misses0, traces0 = placed.executor.cache.misses, placed.executor.traces
    run(placed, "y")                 # same DAG shape, new names
    assert placed.executor.cache.misses == misses0
    assert placed.executor.traces == traces0
    assert placed.executor.cache.hits > 0


@needs_4_devices
def test_overlap_makespan_beats_sync_on_multiwave_dag():
    from repro.flash.device import FlashDevice

    def makespan(mode):
        sess = ComputeSession(FlashDevice(shard_devices="auto"),
                              backend="pallas", overlap=mode, drain_depth=2)
        expr, _ = _random_dag(sess, np.random.default_rng(11), 8, 2000, "m")
        h = sess.materialize_async(expr)
        sess.drain()
        assert h.done
        assert sess.sense_waves >= 3
        return sess.ledger

    ov, sy = makespan("overlap"), makespan("sync")
    assert ov.makespan_us() <= sy.makespan_us()
    assert ov.makespan_us() < sy.makespan_us()      # strict on >=3 waves
    assert ov.overlapped_channel_us > 0


@pytest.mark.parametrize("backend", ["pallas", "sim"])
def test_placed_units_gather_on_their_shards_device(backend, monkeypatch):
    """Mapped shards keep the per-unit gathers: every die-local unit's stack
    lands on its shard's pinned device, no batch gather program runs, and
    the answers stay exact.  Four shard devices where the host has them,
    else the one device four times (the same placed path)."""
    from repro.flash import arena
    from repro.flash.device import FlashDevice
    from repro.flash.geometry import SSDConfig
    devices = (jax.devices() * 4)[:4]
    dev = FlashDevice(config=SSDConfig(page_kb=1), shard_devices=devices)
    sess = ComputeSession(dev, backend=backend)
    expr, ref = _random_dag(sess, np.random.default_rng(9), 4, 3000, "g")
    units = []

    def no_batch_program(*args, **kw):
        raise AssertionError("batch gather program on the placed path")

    monkeypatch.setattr(arena, "_gather_parts", no_batch_program)
    stack = dev.vth_stack

    def spied(wls, *, place=True):
        out = stack(wls, place=place)
        units.append((wls, place, out))
        return out

    monkeypatch.setattr(dev, "vth_stack", spied)
    out = np.asarray(sess.materialize(expr, unpacked=True))
    np.testing.assert_array_equal(out, ref)
    assert sess.arena_gather_programs == sess.arena_gathered_stacks == 0
    assert sess.placed_unit_dispatches == len(units) > 1
    for wls, place, got in units:
        (die,) = {dev.die_of_plane(wl[0]) for wl in wls}
        assert place is False
        assert got.devices() == {dev.arena.device_of(die)}
