"""Wear-aware reliability layer: fault injection, detection, recovery.

Covers the escalation ladder end to end under the seeded Cai-style fault
model: checkword sampling (cross-checked against the packing kernels),
fault-model determinism/replayability, deterministic ladder recovery at
5k P/E, the full retry -> recalibrate -> migrate escalation at 10k P/E
(zero post-recovery bit errors vs a numpy oracle, with the negative
control demonstrably failing), sticky reference trims, the typed error
taxonomy, retention aging, and sim/pallas bit-identity across all three
encodings while recovery is active.

The fault model is common-mode with *bounded* noise, so every outcome
asserted here (which ladder attempt succeeds, which sweep offset is
clean) is computable from the Vth margins — deterministic, not flaky.
"""
import dataclasses

import numpy as np
import pytest

from repro.api import ComputeSession
from repro.flash.geometry import SSDConfig
from repro.kernels import ops as kops
from repro.reliability import (BlockRetiredError, FaultConfig, FaultModel,
                               RetryExhaustedError, RetryPolicy,
                               SenseMismatchError, checkwords)
from repro.reliability.faults import STUCK_VTH
from repro.testing.hypothesis_compat import given, settings, st

SMALL = SSDConfig(page_kb=1)
ENCODINGS = ("mlc", "tlc", "reduced-mlc")


def _bits(rng, n):
    return (rng.random(n) < 0.5).astype(np.uint8)


def _faulted_pair(pe, seed=9, encoding="tlc", config=SMALL, backend="sim",
                  recovery=None, rng_seed=21):
    rng = np.random.default_rng(rng_seed)
    n = config.page_bits
    sess = ComputeSession(config=config, backend=backend, encoding=encoding,
                          faults={"pe": pe, "seed": seed}, recovery=recovery)
    ba, bb = _bits(rng, n), _bits(rng, n)
    a, b = sess.write_pair("a", ba, "b", bb)
    return sess, (a, b), (ba, bb)


def _errors(sess, expr, oracle):
    got = np.asarray(sess.materialize(expr, unpacked=True))
    return int(np.count_nonzero(got != oracle))


# ---------------------------------------------------------------------------
# checkwords: sampling layout + DAG composition


def test_sample_packed_matches_pack_bits_layout():
    """sample_packed mirrors the lane-major layout of kops.pack_bits —
    sampling the packed words equals sampling the unpacked bits, including
    multi-page vectors and the page-padded tail."""
    rng = np.random.default_rng(0)
    page_bits = SMALL.page_bits
    for pages in (1, 3):
        n = pages * page_bits
        bits = _bits(rng, n)
        packed = np.concatenate([
            np.asarray(kops.pack_bits(
                bits[p * page_bits:(p + 1) * page_bits].reshape(1, -1)))[0]
            for p in range(pages)])
        pos = checkwords.sample_positions(n)
        assert len(pos) == checkwords.DEFAULT_SAMPLES
        np.testing.assert_array_equal(
            checkwords.sample_packed(packed, pos, page_bits),
            checkwords.checkword(bits, pos))
    # positions are shared per (n_bits, n_samples): leaves compose
    assert checkwords.sample_positions(page_bits) is \
        checkwords.sample_positions(page_bits)


def test_expected_samples_composes_through_dag():
    """Evaluating stored leaf checkwords through the op DAG predicts the
    result's samples exactly (bitwise ops are positionwise)."""
    class Leaf:
        def __init__(self, name):
            self.name = name

    class Op:
        name = None

        def __init__(self, op, *args):
            self.op, self.args = op, args

    rng = np.random.default_rng(1)
    n = 4096
    xs = {k: _bits(rng, n) for k in "abc"}
    pos = checkwords.sample_positions(n, 64)
    leaves = {k: checkwords.checkword(v, pos) for k, v in xs.items()}
    node = Op("xor", Op("and", Leaf("a"), Leaf("b")),
              Op("nor", Leaf("b"), Leaf("c")))
    want = (xs["a"] & xs["b"]) ^ (1 - (xs["b"] | xs["c"]))
    np.testing.assert_array_equal(
        checkwords.expected_samples(node, leaves),
        checkwords.checkword(want, pos))


# ---------------------------------------------------------------------------
# fault model: seeded, replayable, typed tails


def test_fault_model_deterministic_replay():
    import jax.numpy as jnp
    vth = jnp.linspace(0.0, 5.0, 512)
    cfg = FaultConfig(pe=10_000, seed=3)
    one = FaultModel(cfg).perturb(vth, plane=0, block=1, wl=2)
    two = FaultModel(cfg).perturb(vth, plane=0, block=1, wl=2)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(two))
    other_seed = FaultModel(FaultConfig(pe=10_000, seed=4)).perturb(
        vth, plane=0, block=1, wl=2)
    assert np.any(np.asarray(one) != np.asarray(other_seed))
    other_wl = FaultModel(cfg).perturb(vth, plane=0, block=1, wl=3)
    assert np.any(np.asarray(one) != np.asarray(other_wl))
    # common-mode bounded drift: mean shift down, spread bounded
    delta = np.asarray(one) - np.asarray(vth)
    s = FaultModel(cfg).wear()
    assert np.all(delta <= -cfg.mean_shift_v * s + cfg.spread_v * s + 1e-6)
    assert np.all(delta >= -cfg.mean_shift_v * s - cfg.spread_v * s - 1e-6)

    stuck = FaultModel(FaultConfig(pe=0, seed=3, stuck_bit_pct=10.0)).perturb(
        vth, plane=0, block=1, wl=2)
    assert np.count_nonzero(np.asarray(stuck) == STUCK_VTH) > 0

    dead = FaultModel(FaultConfig(pe=0, dead_blocks=((0, 1),)))
    assert dead.is_dead(0, 1) and not dead.is_dead(0, 2)
    garbage = np.asarray(dead.perturb(vth, plane=0, block=1, wl=0))
    assert garbage.min() < 0.0 and garbage.max() > 5.0


def test_fault_spec_parsing():
    assert ComputeSession(config=SMALL, backend="sim").device.faults is None
    assert FaultConfig.parse(None) is None and FaultConfig.parse("off") is None
    assert FaultConfig.parse(5000).pe == 5000
    assert FaultConfig.parse("pe=5000,seed=3").seed == 3
    with pytest.raises(ValueError):
        FaultConfig.parse("bogus_knob=1")
    sess = ComputeSession(config=SMALL, backend="sim", faults=5000)
    assert sess.device.faults is not None
    assert sess.stats()["faults"]["pe"] == 5000
    assert sess.reliability is not None          # auto-enabled with faults
    assert sess.stats()["reliability"]["policy"]["max_attempts"] == 6


def test_fault_env_spec(monkeypatch):
    monkeypatch.setenv("REPRO_FAULTS", "pe=2000,seed=7")
    sess = ComputeSession(config=SMALL, backend="sim")
    assert sess.device.faults.cfg.pe == 2000
    assert sess.reliability is not None
    monkeypatch.delenv("REPRO_FAULTS")


# ---------------------------------------------------------------------------
# ladder recovery at 5k P/E: deterministic attempt count, zero errors


def test_ladder_offsets_alternate_around_trim():
    p = RetryPolicy()
    assert p.ladder_offsets() == pytest.approx(
        (-0.08, 0.08, -0.16, 0.16, -0.24, 0.24))
    assert p.ladder_offsets(-0.4)[0] == pytest.approx(-0.4)   # sticky trim
    assert len(p.ladder_offsets(-0.4)) == p.max_attempts
    with pytest.raises(ValueError):
        RetryPolicy(escalation=("retry", "pray"))


def test_ladder_recovers_tlc_xor_at_5k():
    """At 5k P/E the common-mode drift (~0.27V) exceeds the TLC half-gap
    (0.20V) at factory references; the ladder's third offset (-0.16V)
    samples clean and the margin-confirmation probe one step deeper
    (-0.24V) confirms and is accepted: exactly 4 counted retries, no
    recalibration, no migration, zero bit errors."""
    sess, (a, b), (ba, bb) = _faulted_pair(pe=5000)
    assert _errors(sess, a ^ b, ba ^ bb) == 0
    rel = sess.stats()["reliability"]
    assert rel["checks"] == 1 and rel["mismatches"] == 1
    assert rel["retries"] == 4
    assert rel["recalibrations"] == 0 and rel["migrations"] == 0
    assert rel["ref_trim"] == {}                 # ladder alone learns no trim
    mgr = sess.reliability
    assert mgr.incidents[0]["offset"] == pytest.approx(-0.24)
    # recovery re-senses booked real die/channel time
    assert sess.ledger.category_us["recovery"] > 0
    assert sess.ledger.makespan_us() > 0
    # a healthy ladder incident decays the blocks' residual toward zero
    assert rel["wear"]["max_rber_pct"] == 0.0


def test_popcount_checks_words_under_reliability():
    sess, (a, b), (ba, bb) = _faulted_pair(pe=5000)
    assert sess.popcount(a ^ b) == int(np.count_nonzero(ba ^ bb))
    assert sess.stats()["reliability"]["retries"] == 4


# ---------------------------------------------------------------------------
# full escalation at 10k P/E: recalibrate, then migrate to reduced-MLC


def test_escalation_recalibrates_and_migrates_at_10k():
    """At 10k P/E the ladder runs dry (drift ~0.38V, deepest offset
    -0.24V), recalibration centers the trim in the widest clean window
    (-0.4V), the worn block's residual RBER crosses the migration
    threshold, and the pair relocates to reduced-MLC — after which the
    result (and every follow-on op) is bit-error-free."""
    sess, (a, b), (ba, bb) = _faulted_pair(pe=10_000)
    assert _errors(sess, a ^ b, ba ^ bb) == 0
    rel = sess.stats()["reliability"]
    assert rel["retries"] == 6                   # the full ladder, dry
    assert rel["recalibrations"] == 1
    assert rel["migrations"] == 1 and rel["retired_blocks"] == 1
    assert rel["ref_trim"]["tlc"] == pytest.approx(-0.4)
    assert rel["wear"]["retired_blocks"] == 1
    assert rel["wear"]["max_rber_pct"] >= sess.reliability.policy.migrate_rber_pct
    # the pair now lives on fresh blocks under the wide-margin encoding
    assert sess.ftl.vectors["a"].encoding == "reduced-mlc"
    assert sess.ftl.vectors["b"].encoding == "reduced-mlc"
    # recovery and migration both booked as real, separately-categorized work
    cats = sess.ledger.category_us
    assert cats["recovery"] > 0 and cats["migration"] > 0
    assert sess.ledger.makespan_us() > 0
    # follow-on ops on the migrated vectors read clean at factory refs,
    # with no new incidents
    for expr, want in ((a & b, ba & bb), (a | b, ba | bb), (a ^ b, ba ^ bb)):
        assert _errors(sess, expr, want) == 0
    after = sess.stats()["reliability"]
    assert after["mismatches"] == rel["mismatches"]
    assert after["retries"] == rel["retries"]


def test_recovery_off_is_a_failing_negative_control():
    """The same 10k workload with recovery="off" demonstrably fails —
    proving the zero-error result above comes from the recovery ladder,
    not from the fault model being toothless."""
    sess, (a, b), (ba, bb) = _faulted_pair(pe=10_000, recovery="off")
    assert sess.reliability is None
    assert _errors(sess, a ^ b, ba ^ bb) > 0
    assert sess.stats()["reliability"] is None


def test_sticky_trim_shortcuts_the_next_incident():
    """A learned trim is attempt 1 of the next ladder: after recalibration
    stored -0.4V for TLC, a fresh worn pair recovers in exactly ONE retry
    (no new recalibration) — and reset_stats() clears counters but keeps
    the trim (it is device calibration, not a statistic)."""
    sess, (a, b), (ba, bb) = _faulted_pair(pe=10_000)
    sess.reliability.ref_trim["tlc"] = -0.4      # as recalibration learns
    sess.reset_stats()
    assert sess.reliability.ref_trim == {"tlc": -0.4}
    assert _errors(sess, a ^ b, ba ^ bb) == 0
    rel = sess.stats()["reliability"]
    assert rel["retries"] == 1 and rel["recalibrations"] == 0
    assert sess.reliability.incidents[0]["offset"] == pytest.approx(-0.4)


# ---------------------------------------------------------------------------
# typed taxonomy: each disabled escalation stage maps to its error


def test_taxonomy_sense_mismatch_when_retry_disabled():
    sess, (a, b), _ = _faulted_pair(pe=10_000,
                                    recovery={"escalation": ()})
    with pytest.raises(SenseMismatchError, match="retry ladder is disabled"):
        sess.materialize(a ^ b)
    rel = sess.stats()["reliability"]
    assert rel["mismatches"] == 1 and rel["retries"] == 0


def test_taxonomy_retry_exhausted_without_recalibration():
    sess, (a, b), _ = _faulted_pair(pe=10_000,
                                    recovery={"escalation": ("retry",)})
    with pytest.raises(RetryExhaustedError, match="6 attempts") as exc:
        sess.materialize(a ^ b)
    assert not exc.value.recalibrated
    assert sess.stats()["reliability"]["retries"] == 6


def test_taxonomy_block_retired_on_stuck_bits():
    """Stuck-at cells are pinned above every reference — no offset reads
    them back, migration cannot relocate the data intact, and the incident
    surfaces as unrecoverable data loss."""
    sess, (a, b), _ = _faulted_pair(pe=0, seed=5)
    sess.device.faults = FaultModel(FaultConfig(pe=0, seed=5,
                                                stuck_bit_pct=2.0))
    rng = np.random.default_rng(3)
    n = SMALL.page_bits
    c, d = sess.write_pair("c", _bits(rng, n), "d", _bits(rng, n))
    with pytest.raises(BlockRetiredError, match="unrecoverable data"):
        sess.materialize(c ^ d)
    rel = sess.stats()["reliability"]
    assert rel["recalibrations"] == 1            # the whole ladder ran first
    assert rel["retired_blocks"] >= 1


# ---------------------------------------------------------------------------
# retention aging compounds with wear; the ladder absorbs it


def test_retention_aging_recovers_clean():
    sess, (a, b), (ba, bb) = _faulted_pair(pe=5000, encoding="mlc")
    assert _errors(sess, a ^ b, ba ^ bb) == 0
    before = sess.stats()["reliability"]["retries"]
    sess.device.age(5000.0)                      # ~0.15V further downshift
    assert _errors(sess, a ^ b, ba ^ bb) == 0
    assert sess.stats()["reliability"]["retries"] >= before


# ---------------------------------------------------------------------------
# cross-encoding + cross-backend: recovery is bit-identical sim vs pallas


@pytest.mark.parametrize("encoding", ENCODINGS)
def test_sim_pallas_bit_identical_under_faults(encoding):
    """Same seeds, same faults: the recovered result is bit-identical on
    the sim and pallas backends for every encoding, and error-free."""
    results = {}
    for backend in ("sim", "pallas"):
        sess, (a, b), (ba, bb) = _faulted_pair(pe=5000, encoding=encoding,
                                               backend=backend)
        got = np.asarray(sess.materialize((a & b) | (a ^ b)))
        results[backend] = (got, sess.stats()["reliability"]["retries"])
        un = np.asarray(sess.materialize((a & b) | (a ^ b), unpacked=True))
        np.testing.assert_array_equal(un, (ba & bb) | (ba ^ bb))
    np.testing.assert_array_equal(results["sim"][0], results["pallas"][0])
    assert results["sim"][1] == results["pallas"][1]


@settings(max_examples=2, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_randomized_dags_error_free_at_10k(seed):
    """Acceptance: randomized op DAGs over native-TLC pairs at 10k P/E
    materialize with zero post-recovery bit errors (retry -> recalibrate
    -> migrate), verified against a numpy oracle."""
    rng = np.random.default_rng(seed)
    n = SMALL.page_bits
    sess = ComputeSession(config=SMALL, backend="sim", encoding="tlc",
                          faults={"pe": 10_000, "seed": int(seed) % 997})
    bits = [_bits(rng, n) for _ in range(4)]
    a, b = sess.write_pair("a", bits[0], "b", bits[1])
    c, d = sess.write_pair("c", bits[2], "d", bits[3])
    ops = {"and": (lambda x, y: x & y), "or": (lambda x, y: x | y),
           "xor": (lambda x, y: x ^ y)}
    names = list(ops)
    o1, o2, o3 = (names[int(rng.integers(3))] for _ in range(3))
    expr = ops[o3](ops[o1](a, b), ops[o2](c, d))
    want = ops[o3](ops[o1](bits[0], bits[1]), ops[o2](bits[2], bits[3]))
    assert _errors(sess, expr, want) == 0
    rel = sess.stats()["reliability"]
    assert rel["mismatches"] >= 1 and rel["retries"] >= 1


def test_mixed_encoding_dag_recovers_with_common_mode_trim():
    """TLC and reduced-MLC leaves in ONE DAG at 10k P/E: the drift is
    common-mode, so the single recalibrated offset that rescues the TLC
    leaves keeps the wide-margin reduced-MLC leaves clean too."""
    rng = np.random.default_rng(31)
    n = SMALL.page_bits
    tlc = ComputeSession(config=SMALL, backend="sim", encoding="tlc",
                         faults={"pe": 10_000, "seed": 11})
    red = ComputeSession(ftl=tlc.ftl, backend="sim", encoding="reduced-mlc")
    bits = [_bits(rng, n) for _ in range(4)]
    a, b = tlc.write_pair("a", bits[0], "b", bits[1])
    red.write_pair("c", bits[2], "d", bits[3])
    c, d = tlc.vector("c"), tlc.vector("d")
    want = (bits[0] ^ bits[1]) & (bits[2] | bits[3])
    assert _errors(tlc, (a ^ b) & (c | d), want) == 0
    rel = tlc.stats()["reliability"]
    assert rel["recalibrations"] >= 1


# ---------------------------------------------------------------------------
# stats plumbing


def test_reliability_stats_and_reset():
    sess, (a, b), (ba, bb) = _faulted_pair(pe=5000)
    assert _errors(sess, a ^ b, ba ^ bb) == 0
    rel = sess.stats()["reliability"]
    assert rel["incidents"] == 1
    assert rel["policy"] == dataclasses.asdict(RetryPolicy())
    hist = sess.metrics.histogram("incident_rber_pct")
    assert hist.count == 1 and hist.max > 0
    sess.reset_stats()
    rel = sess.stats()["reliability"]
    assert rel["incidents"] == 0 and rel["retries"] == 0
    assert rel["checks"] == 0
