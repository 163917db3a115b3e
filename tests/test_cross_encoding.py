"""End-to-end TLC / 8-state encoding path (paper §7) through the sharded
arena + compiled executor: randomized cross-encoding parity (sim vs pallas
vs jnp oracle at dies in {1,2,4}), the 3-operand single-sense-group fast
path, per-encoding executable-cache disjointness, worn-block endurance
(reduced-MLC zero RBER where native TLC fails), and encoding-aware FTL
placement."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ComputeSession
from repro.core import tlc
from repro.flash.geometry import SSDConfig
from repro.kernels import ops as kops
from repro.testing.hypothesis_compat import given, settings, st

ENCODINGS = ("mlc", "tlc", "reduced-mlc")

_OPS = {"and": np.bitwise_and, "or": np.bitwise_or, "xor": np.bitwise_xor}


def _config(dies: int) -> SSDConfig:
    return SSDConfig(page_kb=1, channels=1, dies_per_channel=dies)


def _write_six(sess, bits):
    """Register six operands under the session's encoding: TLC co-locates
    two wordline triples, the 2-page encodings three pairs."""
    vecs = []
    if sess.encoding == tlc.TLC:
        for i in range(0, 6, 3):
            vecs += list(sess.write_triple(
                f"v{i}", bits[i], f"v{i+1}", bits[i + 1],
                f"v{i+2}", bits[i + 2]))
    else:
        for i in range(0, 6, 2):
            vecs += list(sess.write_pair(f"v{i}", bits[i],
                                         f"v{i+1}", bits[i + 1]))
    return vecs


def _random_expr(rng, vecs, bits, depth=0):
    """Random expression tree + its numpy oracle value."""
    if depth >= 3 or rng.random() < 0.35:
        i = int(rng.integers(0, len(vecs)))
        return vecs[i], bits[i]
    if rng.random() < 0.15:
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


@pytest.mark.parametrize("encoding", ENCODINGS)
@pytest.mark.parametrize("dies", [1, 2, 4])
def test_randomized_cross_encoding_parity(encoding, dies):
    """Random DAGs materialize bit-exactly vs the jnp oracle on BOTH
    backends for every encoding x die count, sim and pallas agree on the
    packed words, and the die-parallel makespan never exceeds the serial
    sum.  (The property is nested so the hypothesis_compat ``given`` shim —
    which hides the wrapped signature — composes with parametrize.)"""
    cfg = _config(dies)
    n = cfg.page_bits

    @settings(max_examples=2, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def run(seed):
        rng = np.random.default_rng(seed)
        bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(6)]
        expr_seed = int(rng.integers(0, 2**31))
        results = {}
        for backend in ("sim", "pallas"):
            sess = ComputeSession(config=cfg, backend=backend, seed=seed % 5,
                                  encoding=encoding)
            vecs = _write_six(sess, bits)
            expr, oracle = _random_expr(np.random.default_rng(expr_seed),
                                        vecs, bits)
            packed = np.asarray(sess.materialize(expr))
            got = np.asarray(kops.unpack_bits(
                jnp.asarray(packed).reshape(1, -1))[0][:n])
            np.testing.assert_array_equal(got, oracle)
            assert sess.popcount(expr) == int(np.sum(oracle))
            assert sess.ledger.makespan_us() <= sess.ledger.serial_us() + 1e-9
            assert sess.device.arena.n_shards <= dies
            results[backend] = packed
        np.testing.assert_array_equal(results["sim"], results["pallas"])

    run()


@pytest.mark.parametrize("backend", ["sim", "pallas"])
@pytest.mark.parametrize("dies", [1, 2, 4])
def test_tlc_and3_lowers_to_one_sense_group(backend, dies, rng):
    """The acceptance path: a&b&c over a co-located TLC triple is ONE sense
    group (one single-reference parity sense — no pair senses, no combine),
    bit-exact on both backends at every die count."""
    cfg = _config(dies)
    n = cfg.page_bits
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(3)]
    sess = ComputeSession(config=cfg, backend=backend, seed=0, encoding="tlc")
    a, b, c = sess.write_triple("a", bits[0], "b", bits[1], "c", bits[2])
    for op, red in (("and", np.bitwise_and), ("or", np.bitwise_or)):
        expr = sess.chain(op, [a, b, c])
        got = np.asarray(sess.materialize(expr, unpacked=True))
        np.testing.assert_array_equal(got, red.reduce(bits))
    # inverted 3-operand ops fold into ONE inverse-read sense, no combine
    got = np.asarray(sess.materialize(~(a & b & c), unpacked=True))
    np.testing.assert_array_equal(got, 1 - np.bitwise_and.reduce(bits))
    # three materializes, ONE sense item / batched kernel call / wave each
    assert sess.in_flash_senses == 3
    assert sess.sense_items == 3
    assert sess.sense_batches == 3
    assert sess.sense_waves == 3
    assert sess.fused_reduce_calls == 0
    # commutative role canonicalization: (c&b&a) replays (a&b&c)'s plan,
    # batching into the same group shape — and the same executable
    misses = sess.executor.stats()["misses"]
    got = np.asarray(sess.materialize(c & b & a, unpacked=True))
    np.testing.assert_array_equal(got, np.bitwise_and.reduce(bits))
    assert sess.executor.stats()["misses"] == misses
    # AND3 = 1 sensing phase, OR3 = 2 (§7), at MLC 2-operand latency
    and3 = sess.device.plans.get_encoded("and", ("lsb", "csb", "msb"),
                                         sess.device.tlc_chip, "tlc")
    or3 = sess.device.plans.get_encoded("or", ("lsb", "csb", "msb"),
                                        sess.device.tlc_chip, "tlc")
    assert and3.sensing_phases == 1 and len(and3.refs) == 1
    assert or3.sensing_phases == 2 and len(or3.refs) == 2


def test_tlc_executable_cache_keys_disjoint_from_mlc(rng):
    """The same DAG shape under MLC and TLC encodings never shares an
    executable (signatures embed the encoded plans); a second TLC
    materialize of the same shape is a pure cache hit with 0 retraces."""
    cfg = _config(2)
    n = cfg.page_bits
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(4)]
    mlc = ComputeSession(config=cfg, backend="pallas", seed=0)
    a, b = mlc.write_pair("a", bits[0], "b", bits[1])
    np.testing.assert_array_equal(
        np.asarray(mlc.materialize(a & b, unpacked=True)), bits[0] & bits[1])
    stats = mlc.executor.stats()
    assert (stats["misses"], stats["hits"]) == (1, 0)
    # a TLC session on the SAME device: same DAG shape, different encoding
    sess = ComputeSession(ftl=mlc.ftl, backend="pallas", encoding="tlc")
    assert sess.device.executables is mlc.device.executables
    c, d = sess.write_pair("c", bits[2], "d", bits[3])
    np.testing.assert_array_equal(
        np.asarray(sess.materialize(c & d, unpacked=True)), bits[2] & bits[3])
    stats = sess.executor.stats()
    assert (stats["misses"], stats["hits"]) == (2, 0)   # no cross-encoding hit
    # the plan cache is disjoint too: Table-1 AND vs the encoded TLC AND
    mlc_plan = mlc.plan("and")
    tlc_plan = sess.device.plans.get_encoded("and", ("lsb", "csb"),
                                             sess.device.tlc_chip, "tlc")
    assert mlc_plan != tlc_plan and mlc_plan.refs != tlc_plan.refs
    # second TLC materialize of the same shape: hit, zero retraces
    traces = sess.executor.traces
    np.testing.assert_array_equal(
        np.asarray(sess.materialize(c & d, unpacked=True)), bits[2] & bits[3])
    stats = sess.executor.stats()
    assert (stats["misses"], stats["hits"]) == (2, 1)
    assert stats["traces"] == traces                    # 0 retraces


def test_reduced_mlc_zero_rber_on_worn_blocks_where_tlc_fails():
    """§7 headline: on worn blocks (10k P/E drift) the reduced-MLC mode's
    widened margins deliver ZERO raw bit errors through the full compiled
    pipeline while native TLC's narrow valleys do not.  Deterministic: the
    device PRNG seed and write order are fixed."""
    cfg = SSDConfig(page_kb=1, channels=1, dies_per_channel=2,
                    planes_per_die=2)
    n = cfg.page_bits
    rng = np.random.default_rng(42)
    a_b, b_b, c_b = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(3)]

    def worn_session(encoding):
        sess = ComputeSession(config=cfg, backend="sim", seed=1,
                              encoding=encoding)
        for plane in range(cfg.planes):
            for block in range(4):
                sess.device.pe_counts[(plane, block)] = 10_000
        return sess

    red = worn_session("reduced-mlc")
    a, b = red.write_pair("a", a_b, "b", b_b)
    red_err = sum(
        int(np.sum(np.asarray(red.materialize(expr, unpacked=True)) != want))
        for expr, want in ((a & b, a_b & b_b), (a | b, a_b | b_b)))

    nat = worn_session("tlc")
    x, y, z = nat.write_triple("a", a_b, "b", b_b, "c", c_b)
    tlc_err = sum(
        int(np.sum(np.asarray(nat.materialize(expr, unpacked=True)) != want))
        for expr, want in ((x & y & z, a_b & b_b & c_b),
                           (x | y | z, a_b | b_b | c_b)))
    assert red_err == 0, f"reduced-MLC must be error-free, got {red_err}"
    assert tlc_err > 0, "native TLC should fail on worn blocks"


def test_mixed_encoding_dag_combines_on_controller(rng):
    """Leaves written under different encodings cannot share a wordline:
    the executor falls back to per-encoding reads + a controller combine,
    still bit-exact."""
    cfg = _config(2)
    n = cfg.page_bits
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(3)]
    mlc = ComputeSession(config=cfg, backend="pallas", seed=0)
    a, b = mlc.write_pair("a", bits[0], "b", bits[1])
    sess = ComputeSession(ftl=mlc.ftl, backend="pallas", encoding="tlc")
    t = sess.write("t", bits[2])
    expr = (sess.vector("a") & sess.vector("b")) ^ t
    got = np.asarray(sess.materialize(expr, unpacked=True))
    np.testing.assert_array_equal(got, (bits[0] & bits[1]) ^ bits[2])


def test_tlc_triple_die_affinity_and_arena_tagging(rng):
    """A TLC triple's three roles share one wordline set on ONE home die;
    the arena rows are tagged with their encoding; scattered triples
    realign onto the first operand's die."""
    cfg = _config(4)
    n = 2 * cfg.page_bits
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(6)]
    sess = ComputeSession(config=cfg, backend="sim", seed=0, encoding="tlc")
    a, b, c = sess.write_triple("a", bits[0], "b", bits[1], "c", bits[2])
    metas = [sess.ftl.vectors[nm] for nm in "abc"]
    assert [m.role for m in metas] == ["lsb", "csb", "msb"]
    assert metas[1].pages == metas[0].pages == metas[2].pages
    dev = sess.device
    assert {dev.die_of_plane(p) for m in metas for p, _, _ in m.pages} \
        == {metas[0].die}
    assert sess.ftl.group_of("a") == ("a", "b", "c")
    assert dev.arena.used_by_encoding() == {"tlc": len(metas[0].pages)}
    assert all(dev.encoding_of(wl) == "tlc" for wl in metas[0].pages)
    # scattered vectors on different dies realign onto d's home die
    d = sess.write("d", bits[3], die=1)
    e = sess.write("e", bits[4], die=2)
    f = sess.write("f", bits[5], die=3)
    got = np.asarray(sess.materialize(d & e & f, unpacked=True))
    np.testing.assert_array_equal(got, bits[3] & bits[4] & bits[5])
    assert sess.ftl.die_of("d") == sess.ftl.die_of("e") \
        == sess.ftl.die_of("f") == 1
    assert sess.ftl.group_of("d") == ("d", "e", "f")


def test_rewriting_one_triple_member_keeps_the_rest_colocated(rng):
    """Rewriting one member of a TLC triple drops only that member from the
    co-location group — the remaining pair still senses in one group off
    the old wordlines."""
    cfg = _config(2)
    n = cfg.page_bits
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(4)]
    sess = ComputeSession(config=cfg, backend="sim", seed=0, encoding="tlc")
    a, b, c = sess.write_triple("a", bits[0], "b", bits[1], "c", bits[2])
    sess.write("a", bits[3])                        # a leaves the group
    assert sess.ftl.group_of("a") == ()
    assert sess.ftl.group_of("b") == ("b", "c")
    got = np.asarray(sess.materialize(sess.vector("b") & sess.vector("c"),
                                      unpacked=True))
    np.testing.assert_array_equal(got, bits[1] & bits[2])
    assert sess.in_flash_senses == 1 and sess.sense_batches == 1
    got = np.asarray(sess.materialize(sess.vector("a"), unpacked=True))
    np.testing.assert_array_equal(got, bits[3])
