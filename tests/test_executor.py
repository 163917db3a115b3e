"""Compiled DAG executor: sim/pallas parity on randomized DAGs, executable
caching (0 retraces), per-die sense batching, the topology-aware wave
scheduler, fused megakernels (incl. VMEM-budget tiling), the die-sharded
Vth arena, and wave-batched ledger accounting."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ComputeSession, ExecutableCache, PlanCache
from repro.core.vth_model import get_chip_model
from repro.flash.arena import ShardedVthArena, VthArena
from repro.flash.geometry import SSDConfig
from repro.kernels import ops as kops
from repro.kernels import ref as kernel_ref
from repro.testing.hypothesis_compat import given, settings, st

SMALL = SSDConfig(page_kb=1)           # 8192-bit pages keep interpret mode fast

_OPS = {"and": np.bitwise_and, "or": np.bitwise_or, "xor": np.bitwise_xor}


def _session(backend, seed=0):
    return ComputeSession(config=SMALL, backend=backend, seed=seed)


def _random_expr(rng, vecs, bits, depth=0):
    """Random expression tree + its numpy oracle value."""
    if depth >= 3 or rng.random() < 0.35:
        i = int(rng.integers(0, len(vecs)))
        return vecs[i], bits[i]
    roll = rng.random()
    if roll < 0.15:
        e, o = _random_expr(rng, vecs, bits, depth + 1)
        return ~e, 1 - o
    op = ("and", "or", "xor")[int(rng.integers(0, 3))]
    k = int(rng.integers(2, 5))
    parts = [_random_expr(rng, vecs, bits, depth + 1) for _ in range(k)]
    expr, oracle = parts[0]
    for e, o in parts[1:]:
        expr = getattr(expr, f"__{op}__")(e)
        oracle = _OPS[op](oracle, o)
    return expr, oracle


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_randomized_dags_backend_parity(seed):
    """Random DAGs produce identical packed words on sim and pallas, both
    matching the host oracle (materialize + popcount)."""
    rng = np.random.default_rng(seed)
    n = SMALL.page_bits
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(6)]
    expr_rng_seed = int(rng.integers(0, 2**31))
    results = {}
    for backend in ("sim", "pallas"):
        sess = _session(backend, seed=seed % 7)
        vecs = []
        for i in range(0, 6, 2):
            a, b = sess.write_pair(f"v{i}", bits[i], f"v{i+1}", bits[i + 1])
            vecs += [a, b]
        expr, oracle = _random_expr(np.random.default_rng(expr_rng_seed),
                                    vecs, bits)
        packed = np.asarray(sess.materialize(expr))
        got = np.asarray(kops.unpack_bits(jnp.asarray(packed).reshape(1, -1))[0][:n])
        np.testing.assert_array_equal(got, oracle)
        assert sess.popcount(expr) == int(np.sum(oracle))
        results[backend] = packed
    np.testing.assert_array_equal(results["sim"], results["pallas"])


@pytest.mark.parametrize("n_leaves", [2, 4, 5, 9, 16])
def test_chain_issues_grouped_senses_and_one_combine(rng, n_leaves):
    """An N-leaf associative chain lowers to exactly ceil(N/2) logical senses
    — one per-die batched kernel call per (plan, die) bucket, all dispatched
    in ONE schedule wave — plus at most one fused combine."""
    sess = _session("pallas")
    n = SMALL.page_bits
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(n_leaves)]
    vecs = []
    for i in range(0, n_leaves - 1, 2):
        a, b = sess.write_pair(f"v{i}", bits[i], f"v{i+1}", bits[i + 1])
        vecs += [a, b]
    if n_leaves % 2:
        vecs.append(sess.write(f"v{n_leaves-1}", bits[-1]))
    expr = sess.chain("and", vecs)
    got = np.asarray(sess.materialize(expr, unpacked=True))
    np.testing.assert_array_equal(got, np.bitwise_and.reduce(bits))
    assert sess.sense_items == -(-n_leaves // 2)           # ceil(N/2)
    assert sess.in_flash_senses == n_leaves // 2           # pair senses only
    # every operand pair round-robins onto its own die, so all its senses
    # dispatch concurrently: one wave, ceil(N/2) concurrent dies
    assert sess.sense_waves == 1
    assert sess.max_concurrent_dies == -(-n_leaves // 2)
    if n_leaves % 2 == 0 and n_leaves > 2:
        # homogeneous chain: ONE fused sense->reduce megakernel call
        assert sess.sense_batches == 1
        assert sess.megakernel_calls == 1
    else:
        # odd chains add a leaf read partial, blocking fusion: one per-die
        # batched sense per pair + one per the leftover read
        assert sess.sense_batches == -(-n_leaves // 2)
    assert sess.fused_reduce_calls == (1 if n_leaves > 2 else 0)


def test_repeated_materialize_hits_cached_executable(rng):
    """Second materialize of the same DAG shape: executable-cache hit, zero
    retraces, and no extra read-plan compilation."""
    sess = _session("pallas")
    n = SMALL.page_bits
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(4)]
    a, b = sess.write_pair("a", bits[0], "b", bits[1])
    c, d = sess.write_pair("c", bits[2], "d", bits[3])
    expr = (a & b) ^ (c & d)
    want = (bits[0] & bits[1]) ^ (bits[2] & bits[3])
    for _ in range(3):
        got = np.asarray(sess.materialize(expr, unpacked=True))
        np.testing.assert_array_equal(got, want)
    stats = sess.executor.stats()
    assert stats["misses"] == 1
    assert stats["hits"] == 2
    assert stats["traces"] == 1                            # 0 retraces
    # same SHAPE with different leaves reuses the executable too
    e, f = sess.write_pair("e", bits[1], "f", bits[2])
    got = np.asarray(sess.materialize((a & b) ^ (e & f), unpacked=True))
    np.testing.assert_array_equal(got, (bits[0] & bits[1]) ^ (bits[1] & bits[2]))
    assert sess.executor.stats() == {**stats, "hits": 3}
    # arena shard growth must NOT retrace cached executables (gathers run
    # outside the jitted program, so input shapes depend only on the plan
    # signature).  Pin one die so ITS shard fills and grows.
    grows0 = sess.device.arena.grows
    i = 0
    while sess.device.arena.grows == grows0:
        sess.write_pair(f"g{i}", bits[0], f"h{i}", bits[1], die=0)
        i += 1
    got = np.asarray(sess.materialize(expr, unpacked=True))
    np.testing.assert_array_equal(got, want)
    assert sess.executor.stats()["traces"] == 1


def test_whole_graph_same_plan_senses_batch_once(rng):
    """Same-plan senses in DIFFERENT combine nodes run as one batched kernel
    call: (a&b) ^ (c&d) -> one AND group + one XOR combine."""
    sess = _session("pallas")
    n = SMALL.page_bits
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(4)]
    a, b = sess.write_pair("a", bits[0], "b", bits[1])
    c, d = sess.write_pair("c", bits[2], "d", bits[3])
    sess.materialize((a & b) ^ (c & d))
    assert sess.in_flash_senses == 2
    assert sess.sense_batches == 1                         # one AND group
    assert sess.fused_reduce_calls == 1                    # one XOR combine


def test_popcount_ledger_accounts_count_not_page(rng):
    """On-controller popcount ships 4 bytes to the host, not the packed
    vector; materialize(to_host=True) still accounts the full transfer."""
    sess = _session("pallas")
    n = SMALL.page_bits
    a_bits, b_bits = ((rng.random(n) < 0.5).astype(np.uint8) for _ in range(2))
    a, b = sess.write_pair("a", a_bits, "b", b_bits)
    host_bw = sess.device.config.host_bw_gbps * 1e3        # bytes/us
    before = sess.ledger.host_busy_us
    assert sess.popcount(a & b) == int(np.sum(a_bits & b_bits))
    assert sess.ledger.host_busy_us - before == pytest.approx(4 / host_bw)
    before = sess.ledger.host_busy_us
    packed = sess.materialize(a & b)
    words = int(packed.shape[-1])
    assert sess.ledger.host_busy_us - before == pytest.approx(4 * words / host_bw)


def test_popcount_fuses_into_root_megakernel(rng):
    """A homogeneous chain popcount runs as ONE sense->reduce->popcount
    megakernel — and stays exact on partial pages (mask in-kernel)."""
    for n in (SMALL.page_bits, 1000):
        sess = _session("pallas")
        bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(4)]
        a, b = sess.write_pair(f"a{n}", bits[0], f"b{n}", bits[1])
        c, d = sess.write_pair(f"c{n}", bits[2], f"d{n}", bits[3])
        expr = ~(a & b & c & d)                            # inverse-read: pad -> 1s
        want = int(np.sum(1 - np.bitwise_and.reduce(bits)))
        assert sess.popcount(expr) == want
        assert sess.megakernel_calls == 1
        assert sess.sense_batches == 1


@pytest.mark.parametrize("op,invert", [("and", False), ("or", False),
                                       ("xor", True)])
def test_fused_kernel_matches_reference(rng, op, invert):
    """kernels.fused sense_reduce(+popcount) == composed pure-jnp oracles."""
    plans = PlanCache()
    chip = get_chip_model()
    plan = plans.get(op if not invert else "xor", chip)
    vth = jnp.asarray(rng.normal(2.0, 2.0, (3, 2, 4096)), jnp.float32)
    mask = jnp.asarray(
        rng.integers(0, 2**32, (2, 128), dtype=np.uint64).astype(np.uint32))
    got = kops.sense_reduce_plan(vth, plan, op=op, invert=invert)
    refs = jnp.asarray(list(plan.refs) + [0.0] * (4 - len(plan.refs)),
                       jnp.float32)
    want = kernel_ref.sense_reduce(vth, refs, plan.kind, plan.uses_inverse,
                                   op, invert)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got_pc = kops.sense_reduce_popcount_plan(vth, plan, mask, op=op,
                                             invert=invert)
    want_pc = kernel_ref.sense_reduce_popcount(vth, refs, mask, plan.kind,
                                               plan.uses_inverse, op, invert)
    np.testing.assert_array_equal(np.asarray(got_pc), np.asarray(want_pc))


def test_vth_arena_alloc_free_grow():
    arena = VthArena(page_bits=256, init_slots=2)
    s0 = arena.alloc(2)
    assert arena.used == 2 and arena.grows == 0
    s1 = arena.alloc(3)                                    # forces a grow
    assert arena.grows == 1 and arena.capacity >= 5
    rows = np.arange(5 * 256, dtype=np.float32).reshape(5, 256)
    arena.write(s0 + s1, rows)
    np.testing.assert_array_equal(np.asarray(arena.gather(s0 + s1)), rows)
    arena.free(s0)
    assert arena.used == 3
    s2 = arena.alloc(2)                                    # recycles freed slots
    assert set(s2) == set(s0) and arena.grows == 1
    # non-contiguous gather keeps row identity
    np.testing.assert_array_equal(np.asarray(arena.gather([s1[2], s1[0]])),
                                  rows[[4, 2]])


def test_sharded_arena_per_die_alloc_free_grow():
    """Shards create lazily, alloc/free/grow stay die-local, and cross-die
    gathers preserve request order."""
    arena = ShardedVthArena(page_bits=256, n_dies=4, init_slots=2)
    assert arena.n_shards == 0                             # nothing eager
    r0 = arena.alloc(0, 2)
    r2 = arena.alloc(2, 1)
    assert arena.n_shards == 2 and arena.used == 3
    assert all(d == 0 for d, _ in r0) and r2[0][0] == 2
    # growing die 0 must not touch die 2's shard
    r0 += arena.alloc(0, 2)
    assert arena.shard(0).grows == 1 and arena.shard(2).grows == 0
    rows = np.arange(5 * 256, dtype=np.float32).reshape(5, 256)
    arena.write(r0 + r2, rows)
    np.testing.assert_array_equal(np.asarray(arena.gather(r0 + r2)), rows)
    # cross-die gather in scrambled order keeps row identity
    perm = [r2[0], r0[3], r0[0]]
    np.testing.assert_array_equal(np.asarray(arena.gather(perm)),
                                  rows[[4, 3, 0]])
    arena.free(r0[:2])
    assert arena.used == 3
    again = arena.alloc(0, 2)                              # recycles die 0 slots
    assert set(again) == set(r0[:2]) and arena.shard(0).grows == 1


def test_sharded_arena_optional_jax_device_mapping():
    """devices= pins shards onto JAX devices round-robin (single-host: all
    shards land on the one device, data stays bit-exact)."""
    import jax
    arena = ShardedVthArena(page_bits=256, n_dies=2, devices="auto")
    refs = arena.alloc(0, 1) + arena.alloc(1, 1)
    rows = np.arange(2 * 256, dtype=np.float32).reshape(2, 256)
    arena.write(refs, rows)
    np.testing.assert_array_equal(np.asarray(arena.gather(refs)), rows)
    assert arena.shard_devices() == [jax.devices()[0], jax.devices()[1 % len(jax.devices())]]


def test_die_affinity_placement(rng):
    """Co-pages of one vector always share a die; independent vectors
    round-robin across dies; die= pins placement; align preserves die."""
    sess = _session("sim")
    dev = sess.device
    n = 3 * SMALL.page_bits                                # multi-page vectors
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(4)]
    a, b = sess.write_pair("a", bits[0], "b", bits[1])
    c, d = sess.write_pair("c", bits[2], "d", bits[3])
    meta_a, meta_c = sess.ftl.vectors["a"], sess.ftl.vectors["c"]
    # all pages of one vector (and its co-paged partner) live on ONE die
    assert {dev.die_of_plane(p) for p, _, _ in meta_a.pages} == {meta_a.die}
    assert sess.ftl.vectors["b"].pages == meta_a.pages
    # independent vectors round-robin onto distinct dies
    assert meta_c.die != meta_a.die
    # pinning
    e = sess.write("e", bits[0], die=3)
    f = sess.write("f", bits[1], die=1)
    assert sess.ftl.die_of("e") == 3 and sess.ftl.die_of("f") == 1
    # realignment merges onto A's home die
    got = np.asarray(sess.materialize(e & f, unpacked=True))
    np.testing.assert_array_equal(got, bits[0] & bits[1])
    assert sess.ftl.die_of("e") == sess.ftl.die_of("f") == 3


@pytest.mark.parametrize("dies", [1, 2, 4])
def test_randomized_dags_parity_under_sharded_dies(dies):
    """Sim/pallas parity on random DAGs holds for 1-, 2- and 4-die arenas
    (die-parallel makespan never exceeds the serial sum)."""
    cfg = SSDConfig(page_kb=1, channels=1, dies_per_channel=dies)
    n = cfg.page_bits
    for seed in (11, 23):
        rng = np.random.default_rng(seed)
        bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(6)]
        expr_seed = int(rng.integers(0, 2**31))
        results = {}
        for backend in ("sim", "pallas"):
            sess = ComputeSession(config=cfg, backend=backend, seed=seed)
            vecs = []
            for i in range(0, 6, 2):
                a, b = sess.write_pair(f"v{i}", bits[i], f"v{i+1}", bits[i + 1])
                vecs += [a, b]
            expr, oracle = _random_expr(np.random.default_rng(expr_seed),
                                        vecs, bits)
            packed = np.asarray(sess.materialize(expr))
            got = np.asarray(kops.unpack_bits(
                jnp.asarray(packed).reshape(1, -1))[0][:n])
            np.testing.assert_array_equal(got, oracle)
            assert sess.device.arena.n_shards <= dies
            assert sess.ledger.die_step_us <= sess.ledger.serial_us() + 1e-9
            results[backend] = packed
        np.testing.assert_array_equal(results["sim"], results["pallas"])


def test_die_parallel_dispatch_beats_serial_sum(rng):
    """A DAG whose operands spread across dies dispatches >1 concurrent
    per-die sense group, and the ledger's die-parallel makespan lands
    strictly below the serial sum."""
    sess = _session("pallas")
    n = SMALL.page_bits
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(8)]
    vecs = []
    for i in range(0, 8, 2):
        a, b = sess.write_pair(f"v{i}", bits[i], f"v{i+1}", bits[i + 1])
        vecs += [a, b]
    # heterogeneous plans block fusion -> four per-die sense groups
    expr = ((vecs[0] & vecs[1]) | (vecs[2] & vecs[3])) ^ \
           ((vecs[4] | vecs[5]) & (vecs[6] | vecs[7]))
    oracle = ((bits[0] & bits[1]) | (bits[2] & bits[3])) ^ \
             ((bits[4] | bits[5]) & (bits[6] | bits[7]))
    sense0 = sess.ledger.die_step_us
    got = np.asarray(sess.materialize(expr, unpacked=True))
    np.testing.assert_array_equal(got, oracle)
    assert sess.max_concurrent_dies > 1                    # concurrent groups
    assert sess.sense_waves == 1                           # all dies disjoint
    led = sess.ledger
    assert led.max_parallel_dies > 1
    assert led.die_step_us < led.serial_us()               # strictly below
    assert led.makespan_us() < led.serial_us()             # sense-dominated
    # the whole 4-group wave booked as ONE parallel step: its step time is
    # the max per-die busy time, not the 4-group sum
    assert led.die_step_us - sense0 < sum(led.die_busy_us.values()) / 2


def test_same_die_groups_serialize_combines_interleave(rng):
    """Groups contending for one die serialize into waves; a combine whose
    inputs are ready attaches to the earliest wave instead of post-order."""
    sess = _session("pallas")
    n = SMALL.page_bits
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(6)]
    a, b = sess.write_pair("a", bits[0], "b", bits[1], die=0)
    c, d = sess.write_pair("c", bits[2], "d", bits[3], die=0)   # same die!
    e, f = sess.write_pair("e", bits[4], "f", bits[5], die=1)
    # AND and OR need different read plans -> two groups on die 0 (2 waves);
    # the XOR pair on die 1 rides wave 0 concurrently
    expr = ((a & b) ^ (e ^ f)) ^ (c | d)
    oracle = ((bits[0] & bits[1]) ^ (bits[4] ^ bits[5])) ^ (bits[2] | bits[3])
    got = np.asarray(sess.materialize(expr, unpacked=True))
    np.testing.assert_array_equal(got, oracle)
    assert sess.sense_waves == 2                           # die-0 contention
    assert sess.max_concurrent_dies == 2                   # die 1 overlaps
    # ledger booked one parallel step per wave
    assert sess.ledger.die_steps >= 2


def test_executable_cache_lru_eviction():
    built = []
    cache = ExecutableCache(capacity=2)
    for key in ("k1", "k2", "k1", "k3"):                   # k3 evicts k2 (LRU)
        cache.get(key, lambda k=key: built.append(k) or k)
    assert built == ["k1", "k2", "k3"]
    assert cache.evictions == 1 and len(cache) == 2
    assert "k2" not in cache and "k1" in cache and "k3" in cache
    cache.get("k2", lambda: built.append("k2b") or "k2b")  # rebuild = miss
    assert cache.stats() == {"hits": 1, "misses": 4, "entries": 2,
                             "evictions": 2, "capacity": 2}


def test_executable_cache_shared_across_sessions(rng):
    """Sessions on one device share compiled executables (same chip +
    backend key), like the device-level PlanCache."""
    sess1 = _session("pallas")
    n = SMALL.page_bits
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(4)]
    a, b = sess1.write_pair("a", bits[0], "b", bits[1])
    c, d = sess1.write_pair("c", bits[2], "d", bits[3])
    sess1.materialize((a & b) ^ (c & d))
    assert sess1.executor.stats()["misses"] == 1
    # second session on the SAME device: identical DAG shape replays the
    # cached executable — no new build, no new trace
    sess2 = ComputeSession(ftl=sess1.ftl, backend="pallas")
    assert sess2.device.executables is sess1.device.executables
    a2, b2 = sess2.vector("a"), sess2.vector("b")
    c2, d2 = sess2.vector("c"), sess2.vector("d")
    got = np.asarray(sess2.materialize((a2 & b2) ^ (c2 & d2), unpacked=True))
    np.testing.assert_array_equal(got, (bits[0] & bits[1]) ^ (bits[2] & bits[3]))
    stats = sess2.executor.stats()
    assert stats["hits"] >= 1 and stats["misses"] == 1     # shared counters
    assert sess2.executor.traces == 0                      # never traced


def test_vmem_budget_splits_oversized_megakernel(rng):
    """A fused chain whose operand stack exceeds the VMEM budget splits into
    tiled sense_reduce passes — bit-exact, with the split made observable."""
    from repro.api.executor import OPERAND_TILE_BYTES

    n = SMALL.page_bits
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(8)]
    want = np.bitwise_and.reduce(bits)
    for budget, min_calls in ((3 * OPERAND_TILE_BYTES, 2), (None, 1)):
        sess = ComputeSession(config=SMALL, backend="pallas",
                              vmem_budget_bytes=budget)
        vecs = []
        for i in range(0, 8, 2):
            a, b = sess.write_pair(f"v{i}", bits[i], f"v{i+1}", bits[i + 1])
            vecs += [a, b]
        expr = sess.chain("and", vecs)
        got = np.asarray(sess.materialize(expr, unpacked=True))
        np.testing.assert_array_equal(got, want)
        if budget is None:
            assert sess.tiled_megakernel_splits == 0
            assert sess.megakernel_calls == min_calls
        else:
            assert sess.executor.max_fused_operands == 3
            assert sess.tiled_megakernel_splits == 1
            assert sess.megakernel_calls == min_calls      # ceil(4 ops / 3)
        # popcount stays exact through the split path too
        assert sess.popcount(expr) == int(np.sum(want))


def test_device_senses_read_from_arena(rng):
    """Device reads after erase + rewrite hit the right arena rows."""
    from repro.flash.device import FlashDevice
    dev = FlashDevice(config=SMALL, seed=3)
    n = SMALL.page_bits
    wl_a, wl_b = (0, 0, 0), (1, 0, 0)
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(4)]
    dev.program_shared(wl_a, jnp.asarray(bits[0]), jnp.asarray(bits[1]))
    dev.program_shared(wl_b, jnp.asarray(bits[2]), jnp.asarray(bits[3]))
    got = np.asarray(dev.mcflash_read(wl_a, "and", packed=False))
    np.testing.assert_array_equal(got, bits[0] & bits[1])
    dev.erase_block(0, 0)                                  # frees wl_a's slot
    dev.program_shared(wl_a, jnp.asarray(bits[3]), jnp.asarray(bits[0]))
    got = np.asarray(dev.mcflash_read_batch([wl_a, wl_b], "or"))
    want = [bits[3] | bits[0], bits[2] | bits[3]]
    for row, w in zip(got, want):
        np.testing.assert_array_equal(
            np.asarray(kops.unpack_bits(row.reshape(1, -1))[0]), w)


def test_batched_ledger_matches_per_page_accounting(rng):
    """add_die_batch/dma batch entries book the same serial totals the
    per-page loops used to — but ONE batched call is one *parallel* step,
    so its die-parallel makespan is the max, not the sum."""
    from repro.api import Ledger
    led_a, led_b = Ledger(), Ledger()
    per_die = {0: 100.0, 1: 40.0}
    led_a.add_die_batch(per_die, uj=6.0, commands=3)
    for die, us in ((0, 60.0), (0, 40.0), (1, 40.0)):
        led_b.add_die(die, us, 2.0)
    # serial accounting identical either way
    assert led_a.die_busy_us == led_b.die_busy_us
    assert led_a.serial_us() == led_b.serial_us() == 140.0
    assert (led_a.energy_uj, led_a.commands) == (led_b.energy_uj, led_b.commands)
    assert led_a.summary()["category_us"] == led_b.summary()["category_us"]
    # parallel-step model: the batch overlaps dies 0 and 1 (one step, max);
    # the per-entry calls serialize (three steps, summed)
    assert led_a.makespan_us() == 100.0
    assert led_b.makespan_us() == 140.0
    assert led_a.makespan_us() <= led_a.serial_us()
    assert led_a.max_parallel_dies == 2
    led_a.add_channel_batch({0: 10.0, 2: 5.0})
    led_b.add_channel(0, 10.0)
    led_b.add_channel(2, 5.0)
    assert led_a.channel_busy_us == led_b.channel_busy_us
    assert led_a.channel_step_us == 10.0                   # parallel channels
    assert led_b.channel_step_us == 15.0                   # serialized calls


def test_sim_executor_never_enters_pallas(rng, monkeypatch):
    """The executor on backend='sim' stays pure-jnp even on the fused
    megakernel and popcount paths."""
    import jax.experimental.pallas as pl

    def _boom(*a, **kw):
        raise AssertionError("Pallas kernel invoked on the sim backend")

    monkeypatch.setattr(pl, "pallas_call", _boom)
    sess = _session("sim")
    n = SMALL.page_bits
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(4)]
    a, b = sess.write_pair("a", bits[0], "b", bits[1])
    c, d = sess.write_pair("c", bits[2], "d", bits[3])
    expr = a & b & c & d
    got = np.asarray(sess.materialize(expr, unpacked=True))
    np.testing.assert_array_equal(got, np.bitwise_and.reduce(bits))
    assert sess.megakernel_calls == 1
    assert sess.popcount(expr) == int(np.sum(np.bitwise_and.reduce(bits)))


# ---------------------- one arena gather program per batch ------------------

def _per_row_stacks(dev, wls_lists):
    """Reference gather, independent of the batch program: one shard take
    per row, stacked in request order."""
    return [np.stack([np.asarray(dev.arena.shard(d).gather([s]))[0]
                      for d, s in (dev._slot_of[wl] for wl in wls)])
            for wls in wls_lists]


def _spy_gather(monkeypatch, dev):
    """Spy on the batch gather: the layout of every gather program, and per
    ``vth_stack_many`` call (the executor's ``repro.gather`` phase) the
    wordline lists, the stacks returned, and the programs dispatched."""
    from repro.flash import arena
    layouts, calls = [], []
    program, stack_many = arena._gather_parts, dev.vth_stack_many

    def counted(*args, **kw):
        layouts.append(kw["layout"])
        return program(*args, **kw)

    def spied(wls_lists):
        n0 = len(layouts)
        out = stack_many(wls_lists)
        calls.append((wls_lists, out, len(layouts) - n0))
        return out

    monkeypatch.setattr(arena, "_gather_parts", counted)
    monkeypatch.setattr(dev, "vth_stack_many", spied)
    return layouts, calls


def _batch_case(case, sess, bits):
    """Vectors on pinned dies and the batch of one case, with its oracles."""
    vecs = []
    for i, die in zip(range(0, 6, 2), (3, 1, 2)):  # fused operands out of
        vecs += sess.write_pair(f"v{i}", bits[i], f"v{i+1}",  # die order
                                bits[i + 1], die=die)
    if case == "one_group":
        return [vecs[0] & vecs[1]], [bits[0] & bits[1]]
    if case == "fused_cross_die":
        return ([vecs[0] & vecs[1] & vecs[2] & vecs[3] & vecs[4] & vecs[5],
                 vecs[2] ^ vecs[3]],
                [np.bitwise_and.reduce(bits), bits[2] ^ bits[3]])
    rng = np.random.default_rng(int(case.split("_")[-1]))
    exprs, oracles = [], []
    for _ in range(3):
        e, o = _random_expr(rng, vecs, bits)
        exprs.append(e)
        oracles.append(o)
    return exprs, oracles


@pytest.mark.parametrize("case", ["multi_group_5", "multi_group_17",
                                  "one_group", "fused_cross_die"])
def test_batch_gathers_every_stack_in_one_program(case, monkeypatch):
    """An unplaced batch gathers all its operand stacks with ONE program:
    the stacks equal a per-row reference gather value for value, and the
    answers equal the oracle and the sim backend's bit for bit."""
    rng = np.random.default_rng(1)
    bits = [(rng.random(SMALL.page_bits * 2) < 0.5).astype(np.uint8)
            for _ in range(6)]
    answers = {}
    for backend in ("sim", "pallas"):
        sess = _session(backend, seed=4)
        dev = sess.device
        exprs, oracles = _batch_case(case, sess, bits)
        layouts, calls = _spy_gather(monkeypatch, dev)
        outs = sess.materialize_batch(exprs)
        ((stacks, got, programs),) = calls
        assert programs == 1
        assert sess.arena_gather_programs == 1
        assert sess.arena_gathered_stacks == len(stacks)
        assert sess.stats()["arena_gather_programs"] == 1
        if case == "one_group":
            assert len(stacks) == 1
        elif case == "fused_cross_die":     # the program restores the
            assert any(permuted for _, permuted in layouts[-1])  # order
        else:
            assert len(stacks) > 1
        for g, want in zip(got, _per_row_stacks(dev, stacks), strict=True):
            np.testing.assert_array_equal(np.asarray(g), want)
        words = []
        for out, oracle in zip(outs, oracles, strict=True):
            got_bits = np.asarray(kops.unpack_bits(
                jnp.asarray(out).reshape(1, -1))[0][:oracle.size])
            np.testing.assert_array_equal(got_bits, oracle)
            words.append(np.asarray(out))
        answers[backend] = words
    for s, p in zip(answers["sim"], answers["pallas"], strict=True):
        np.testing.assert_array_equal(s, p)


def test_batch_gather_compiles_once_across_dies_and_survives_growth(rng):
    """One plan signature on other dies of equal shard capacity reuses the
    compiled gather; growing the shard between two dispatches of one plan
    recompiles only the gather (never the executable) and stays exact."""
    from repro.flash import arena
    sess = _session("pallas")
    n = SMALL.page_bits
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(6)]
    a, b = sess.write_pair("a", bits[0], "b", bits[1], die=0)
    c, d = sess.write_pair("c", bits[2], "d", bits[3], die=1)
    assert sess.lower(a & b).signature("x") == sess.lower(c & d).signature("x")
    got = sess.materialize_batch([a & b])[0]
    size, traces = arena._gather_parts._cache_size(), sess.executor.traces
    got2 = sess.materialize_batch([c & d])[0]
    assert arena._gather_parts._cache_size() == size
    assert sess.executor.traces == traces
    for out, want in ((got, bits[0] & bits[1]), (got2, bits[2] & bits[3])):
        np.testing.assert_array_equal(
            np.asarray(kops.unpack_bits(out.reshape(1, -1))[0][:n]), want)
    shard = sess.device.arena.shard(0)
    cap = shard.capacity
    for i in range(cap):                # fill die 0's shard past capacity
        sess.write_pair(f"g{i}a", bits[4], f"g{i}b", bits[5], die=0)
    assert shard.capacity > cap
    again = sess.materialize_batch([a & b])[0]
    np.testing.assert_array_equal(
        np.asarray(kops.unpack_bits(again.reshape(1, -1))[0][:n]),
        bits[0] & bits[1])
    assert sess.executor.traces == traces
