"""Copyback realignment on the serving path: one device program per
realignment, bit-identical to a page read per source and a program per
destination wordline, placed on the less-loaded operand's die, and arena
rows of retired placements freed as the next batch's lowering begins
(partners kept, dispatched batches undisturbed)."""
import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ComputeSession
from repro.flash import device as device_mod
from repro.flash.device import FlashDevice
from repro.flash.ftl import FTL
from repro.flash.geometry import SSDConfig
from repro.kernels import ops as kops
from repro.serve import QueryEngine
from repro.verify import PlanInvariantError
from repro.verify.plan_check import check_plan

SMALL = SSDConfig(page_kb=1)           # 8192-bit pages keep interpret mode fast


def _bits(rng, n, k):
    return [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(k)]


def _rows(dev, wls):
    return np.asarray(dev.vth_stack(wls))


@pytest.mark.parametrize("backend,faults,worn_plane", [
    ("sim", None, None), ("pallas", None, None),
    ("sim", {"pe": 3000, "seed": 1}, None), ("sim", None, 1)])
@pytest.mark.parametrize("encoding,k", [("mlc", 2), ("tlc", 3),
                                        ("reduced-mlc", 2)])
def test_batched_copyback_is_bit_identical_to_per_wordline_path(
        rng, backend, faults, worn_plane, encoding, k):
    """The one-program copyback writes the same Vth rows, stores the same
    operands, leaves the device key where per-wordline page reads and
    programs leave it, and books the same serial ledger totals, also where
    one destination block is worn (a P/E count of its own).  With a fault
    model (per-row perturbation) it reads and programs in batches, with
    the same result."""
    from repro.api.backends import get_backend
    from repro.core.tlc import ROLES_OF
    from repro.reliability import FaultConfig, FaultModel

    n = 3 * SMALL.page_bits
    bits = _bits(rng, n, k)
    names = [f"v{i}" for i in range(k)]
    devs = []
    for _ in range(2):
        dev = FlashDevice(config=SMALL, seed=5)
        dev.set_default_backend(get_backend(backend))
        if faults is not None:
            dev.faults = FaultModel(FaultConfig(**faults))
        if worn_plane is not None:
            dev.pe_counts[(worn_plane, 0)] = 3000
        ftl = FTL(dev)
        for i, (name, b) in enumerate(zip(names, bits)):
            ftl.write_scattered(name, jnp.asarray(b), die=i % 2,
                                encoding=encoding)
        devs.append((dev, ftl))
    (dev, ftl), (ref, ref_ftl) = devs
    metas = [ref_ftl.vectors[name] for name in names]
    ledger0 = dev.ledger.serial_us(), dev.ledger.energy_uj
    if encoding == "mlc":
        ftl.align(*names)
    else:
        ftl.align_group(names)
    dst = ftl.vectors[names[0]].pages

    die = ref_ftl._realign_die(metas)
    want = ref_ftl._placement(len(metas[0].pages), die)
    assert want == dst and ftl.die_of(names[0]) == die
    if worn_plane is not None:          # fresh and worn destination blocks
        assert len({dev.pe_counts.get(wl[:2], 0) for wl in dst}) == 2
    for j, wl in enumerate(want):
        pages = [ref.page_read(m.pages[j], m.role, packed=False,
                               encoding=encoding) for m in metas]
        pages += [jnp.zeros_like(pages[0])] * (len(ROLES_OF[encoding]) - k)
        by_role = dict(zip(ROLES_OF[encoding], pages))
        ref.program_shared(wl, by_role["lsb"], by_role["msb"],
                           csb_bits=by_role.get("csb"), encoding=encoding)

    np.testing.assert_array_equal(_rows(dev, dst), _rows(ref, want))
    np.testing.assert_array_equal(np.asarray(dev._key), np.asarray(ref._key))
    for wl in dst:
        for got, exp in zip(dev.stored_operands(wl),
                            ref.stored_operands(wl)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    assert dev.ledger.serial_us() == pytest.approx(ref.ledger.serial_us())
    assert dev.ledger.energy_uj == pytest.approx(ref.ledger.energy_uj)
    assert dev.ledger.serial_us() > ledger0[0]
    if faults is not None or worn_plane is not None:
        return                  # worn rows read back with raw bit errors
    for name, b in zip(names, bits):          # the moved data reads back
        got = dev.page_read_batch(ftl.vectors[name].pages,
                                  ftl.vectors[name].role, encoding=encoding)
        np.testing.assert_array_equal(
            np.asarray(kops.unpack_bits(got.reshape(1, -1))[0][:n]), b)


def test_copyback_is_one_program_per_realignment(rng, monkeypatch):
    """A realignment dispatches ONE copyback program and is counted."""
    calls = []
    program = device_mod._copyback_rows

    def spy(bufs, idx, key, **kw):
        calls.append((len(bufs), idx.shape))
        return program(bufs, idx, key, **kw)

    monkeypatch.setattr(device_mod, "_copyback_rows", spy)
    sess = ComputeSession(config=SMALL, backend="sim")
    n = 5 * SMALL.page_bits
    a_bits, b_bits = _bits(rng, n, 2)
    a = sess.write("a", a_bits, die=0)
    b = sess.write("b", b_bits, die=1)
    assert sess.popcount(a & b) == int(np.sum(a_bits & b_bits))
    assert calls == [(3, (15,))]          # a's, b's and the destination's
    st = sess.stats()
    assert st["realignments"] == 1 and st["copyback_wordlines"] == 5
    assert sess.popcount(a & b) == int(np.sum(a_bits & b_bits))
    assert sess.stats()["realignments"] == 1     # aligned now: no copyback


def _q6_session(rng, n):
    """A Q6-shaped index (years, discounts, quantities, price slices as
    consecutive pairs) and the Q6 deck's queries."""
    names = ([f"y{y}" for y in range(1992, 1999)]
             + [f"disc{d}" for d in range(11)]
             + [f"qty{q}" for q in range(1, 51)]
             + [f"price{i}" for i in range(24)])
    bits = dict(zip(names, _bits(rng, n, len(names))))
    sess = ComputeSession(config=SMALL, backend="sim")
    for a, b in zip(names[::2], names[1::2]):
        sess.write_pair(a, bits[a], b, bits[b])
    return sess, bits


def _q6_query(sess, y, d, qn, year_first=True):
    """Q6's 72 ANDs, the ship year before the discount as in Q6's WHERE
    clause (or after it)."""
    qty = sess.chain("or", [f"qty{q}" for q in range(1, qn + 1)])
    out = []
    for delta in (-1, 0, 1):
        for i in range(24):
            first = [sess[f"y{y}"], sess[f"disc{d + delta}"]]
            if not year_first:
                first.reverse()
            out.append((d + delta, i, sess.chain(
                "and", first + [qty, sess[f"price{i}"]])))
    return out


@pytest.mark.parametrize("year_first", [True, False])
def test_q6_deck_keeps_the_arena_bounded_and_reclaims(rng, year_first):
    """A cycle of the Q6 deck in order, then two shuffled cycles, at 20
    pages per bitmap: every count exact, about three realignments per
    query, arena use at most twice its set-up use, retired rows freed and
    reused, and after the first cycle no shard's capacity changes, in
    either order of the year and discount operands."""
    n = 20 * SMALL.page_bits - 100
    sess, bits = _q6_session(rng, n)
    arena = sess.device.arena
    setup_used = arena.used
    eng = QueryEngine(sess)
    deck = list(itertools.product((1994, 1996), (3, 5, 7, 9), (23, 24)))
    queries = 0
    for cycle in range(3):
        if cycle == 1:
            capacity = {d: s["capacity"]
                        for d, s in arena.shard_stats().items()}
        order = (range(len(deck)) if cycle == 0 else
                 np.random.default_rng(cycle).permutation(len(deck)))
        for k in order:
            y, d, qn = deck[k]
            reqs = _q6_query(sess, y, d, qn, year_first)
            tickets = [eng.submit(e, popcount=True) for _, _, e in reqs]
            got = eng.drain(tickets)
            sel = bits[f"y{y}"] & np.bitwise_or.reduce(
                [bits[f"qty{q}"] for q in range(1, qn + 1)])
            for (disc, i, _), count in zip(reqs, got):
                want = sel & bits[f"disc{disc}"] & bits[f"price{i}"]
                assert count == int(want.sum())
            queries += 1
            assert arena.used <= 2 * setup_used
            if cycle:
                assert {d: s["capacity"] for d, s
                        in arena.shard_stats().items()} == capacity
    st = sess.stats()
    assert 2 * queries <= st["realignments"] <= 3 * queries
    assert st["copyback_wordlines"] == 20 * st["realignments"]
    assert st["arena_rows_reclaimed"] > 0


def test_realigned_pair_goes_to_a_die_with_room(rng):
    """A merged pair goes to the first operand's die where its shard has
    room, and where neither has, to the die with the fewest rows among
    those with room: no shard grows."""
    sess = ComputeSession(config=SMALL, backend="sim")
    arena = sess.device.arena
    small_bits = _bits(rng, 2 * SMALL.page_bits, 2)
    p = sess.write("p", small_bits[0], die=2)
    q = sess.write("q", small_bits[1], die=3)
    assert sess.popcount(q & p) == int(np.sum(small_bits[1] & small_bits[0]))
    assert sess.ftl.die_of("p") == sess.ftl.die_of("q") == 3
    n = arena.init_slots * SMALL.page_bits           # fills a fresh shard
    y_bits, d_bits = _bits(rng, n, 2)
    y = sess.write("y", y_bits, die=0)
    d = sess.write("d", d_bits, die=1)
    assert not arena.fits(0, arena.init_slots)
    grows = arena.grows
    assert sess.popcount(y & d) == int(np.sum(y_bits & d_bits))
    # die 2 holds no rows since p moved off it: its freed rows take the pair
    assert sess.ftl.die_of("y") == sess.ftl.die_of("d") == 2
    assert arena.grows == grows


def test_dispatched_batch_is_exact_while_later_batches_reuse_its_rows(rng):
    """A batch dispatched and not yet read back, whose pairs later batches
    realign away: the retired rows are freed as the next lowering begins
    and a copyback reuses them before any shard grows, and the first
    batch's answer is still exact (it holds the arena buffers it read)."""
    from repro.kernels import ref as kernel_ref

    n = 2 * SMALL.page_bits
    sess = ComputeSession(config=SMALL, backend="sim", drain_depth=8)
    a_bits, b_bits, c_bits, d_bits = _bits(rng, n, 4)
    a, b = sess.write_pair("a", a_bits, "b", b_bits, die=0)
    c, d = sess.write_pair("c", c_bits, "d", d_bits, die=0)
    dev = sess.device
    old = sess.ftl.vectors["a"].pages + sess.ftl.vectors["c"].pages
    old_slots = {dev._slot_of[wl] for wl in old}
    (first,) = sess.materialize_batch_async([a & b])
    assert sess.popcount(a & c) == int(np.sum(a_bits & c_bits))   # a, c move
    assert sess.popcount(b & d) == int(np.sum(b_bits & d_bits))   # b, d move
    assert all(wl in dev._slot_of for wl in old)    # retired, not yet freed
    capacity = dev.arena.capacity
    assert sess.popcount(d & a) == int(np.sum(d_bits & a_bits))   # copyback
    assert sess.stats()["arena_rows_reclaimed"] == len(old)
    assert all(wl not in dev._slot_of for wl in old)
    placed = {dev._slot_of[wl] for wl in sess.ftl.vectors["a"].pages}
    assert placed <= old_slots                            # freed rows reused
    assert dev.arena.capacity == capacity
    want = kernel_ref.pack_bits(jnp.asarray(a_bits & b_bits).reshape(1, -1))
    np.testing.assert_array_equal(first.result(), np.asarray(want[0]))


def test_row_with_a_live_partner_is_never_freed(rng):
    """Moving one vector of a pair leaves the pair's rows allocated while
    its partner lives there, however many batches drain; once the partner
    moves too they are freed."""
    n = 2 * SMALL.page_bits
    sess = ComputeSession(config=SMALL, backend="sim")
    a_bits, b_bits, c_bits, d_bits = _bits(rng, n, 4)
    a, b = sess.write_pair("a", a_bits, "b", b_bits, die=0)
    c, d = sess.write_pair("c", c_bits, "d", d_bits, die=1)
    dev = sess.device
    pair = list(sess.ftl.vectors["b"].pages)
    assert sess.popcount(a & c) == int(np.sum(a_bits & c_bits))   # a moves
    for _ in range(4):                                     # batches drain
        assert sess.popcount(b) == int(np.sum(b_bits))   # a leaf read
        assert sess.popcount(a & c) == int(np.sum(a_bits & c_bits))
    assert sess.ftl.vectors["b"].pages == pair
    assert all(wl in dev._slot_of for wl in pair)
    assert sess.stats()["arena_rows_reclaimed"] == 0
    assert sess.popcount(b & d) == int(np.sum(b_bits & d_bits))   # b moves
    sess.popcount(c & d)                       # a later batch frees the rows
    assert all(wl not in dev._slot_of for wl in pair)


def test_batches_that_never_realign_keep_no_reclaim_state(rng):
    """Pair-aligned traffic retires nothing: no stale rows are tracked."""
    n = SMALL.page_bits
    sess = ComputeSession(config=SMALL, backend="sim")
    vecs = [sess.write_pair(f"a{i}", x, f"b{i}", y)
            for i, (x, y) in enumerate(zip(_bits(rng, n, 3), _bits(rng, n, 3)))]
    eng = QueryEngine(sess)
    for _ in range(6):
        tickets = [eng.submit(x & y, popcount=True) for x, y in vecs]
        eng.drain(tickets)
    dev = sess.device
    assert not dev._stale
    st = sess.stats()
    assert st["realignments"] == st["arena_rows_reclaimed"] == 0


def test_slot_hazard_check_sees_freed_rows(rng):
    """A plan that senses a wordline whose row was freed as its lowering
    began fails the slot-hazard invariant."""
    n = SMALL.page_bits
    sess = ComputeSession(config=SMALL, backend="sim")
    a_bits, b_bits = _bits(rng, n, 2)
    a, b = sess.write_pair("a", a_bits, "b", b_bits)
    plan = sess.lower(a & b)
    plan.freed = (plan.groups[0].wls[0],)
    with pytest.raises(PlanInvariantError, match="slot-hazard") as exc:
        check_plan(plan, sess.plan_context())
    assert "freed at the pre-dispatch barrier" in str(exc.value)
    plan.freed = ()
    check_plan(plan, sess.plan_context())
