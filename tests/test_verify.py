"""Adversarial self-tests for the static ExecPlan verifier (repro.verify).

The checker is itself checked: a mutation suite takes valid plans lowered
from the quick-benchmark corpus (every encoding x die count), applies seeded
schedule corruptions, and asserts the verifier rejects EVERY mutant with its
*intended* invariant — plus golden error-message tests, verifier-session
integration (memoization, stats, verify="off"), the Ledger.reset makespan
regression, and the signature/wave-layout distinctness guarantee.
"""
import copy
import dataclasses

import numpy as np
import pytest

from repro.api import ComputeSession
from repro.api.executor import (OPERAND_TILE_BYTES, ProgramStep,
                                schedule_programs_into_idle_waves)
from repro.flash.geometry import SSDConfig
from repro.testing.hypothesis_compat import given, settings, st
from repro.verify import PlanInvariantError, check_plan, render_plan
from repro.verify.corpus import iter_corpus

ENCODINGS = ("mlc", "tlc", "reduced-mlc")
SMALL = SSDConfig(page_kb=1)


def _ctx(sess):
    return sess.plan_context()


# ---------------------------------------------------------------------------
# mutation classes — each returns a corrupted deep copy targeting ONE
# invariant, or None when the plan has no applicable site

def _sense_wave_of(plan, wl):
    for wi, wave in enumerate(plan.waves):
        for gi in wave.groups:
            if wl in plan.groups[gi].wls:
                return wi
        for si in wave.fused:
            if wl in plan.steps[si].fused.wls:
                return wi
    return None


def mutate_unbook_wave(plan, ctx, rng):
    """Drop a booked sense group from its wave -> ledger-conservation."""
    if not plan.groups:
        return None
    m = copy.deepcopy(plan)
    for wave in m.waves:
        if wave.groups:
            wave.groups.pop(rng.integers(0, len(wave.groups)))
            return m
    return None


def mutate_merge_same_die_wave(plan, ctx, rng):
    """Merge two same-die groups into one wave -> wave-die-disjoint."""
    m = copy.deepcopy(plan)
    first_wave_of_die = {}
    for wi, wave in enumerate(m.waves):
        for gi in list(wave.groups):
            for die in m.groups[gi].dies:
                w0 = first_wave_of_die.setdefault(die, wi)
                if w0 < wi:
                    wave.groups.remove(gi)
                    m.waves[w0].groups.append(gi)
                    return m
    return None


def mutate_drop_program_barrier(plan, ctx, rng):
    """Move a lowering-time program into the wave that senses the same
    wordline -> slot-hazard."""
    m = copy.deepcopy(plan)
    for pr in m.programs:
        for wl in pr.wls:
            wi = _sense_wave_of(m, wl)
            if wi is not None:
                pr.wave = wi
                return m
    return None


def mutate_move_combine_early(plan, ctx, rng):
    """Hoist a combine above its producers -> schedule-topology."""
    m = copy.deepcopy(plan)
    produced_late = set()          # pids produced by wave >= 1 units
    for wi, wave in enumerate(m.waves):
        if wi == 0:
            continue
        for gi in wave.groups:
            produced_late.update(it.pid for it in m.groups[gi].items)
        for si in wave.fused:
            produced_late.add(m.steps[si].out)
        for ci in wave.combines:
            produced_late.add(m.steps[ci].out)
    for wi, wave in enumerate(m.waves):
        if wi == 0:
            continue
        for ci in list(wave.combines):
            if any(a in produced_late and m.steps[ci].out != a
                   for a in m.steps[ci].args):
                wave.combines.remove(ci)
                m.waves[0].combines.insert(0, ci)
                return m
    return None


def mutate_inflate_fused_past_vmem(plan, ctx, rng):
    """Inflate a fused chain's declared tile split past the VMEM budget
    -> vmem-budget."""
    m = copy.deepcopy(plan)
    budget = max(ctx.vmem_budget_bytes, ctx.operand_tile_bytes)
    for st in m.steps:
        if st.fused is not None:
            st.fused.pass_operands = budget // ctx.operand_tile_bytes + 1
            return m
    return None


def mutate_cross_plan_group(plan, ctx, rng):
    """Slip a sense with a different ReadPlan into a batched group
    -> encoding-consistency."""
    m = copy.deepcopy(plan)
    for g in m.groups:
        if g.items:
            it = g.items[0]
            it.plan = dataclasses.replace(it.plan, op=it.plan.op + "-alien")
            return m
    return None


def mutate_ref_overflow(plan, ctx, rng):
    """Blow a group's reference stack past MAX_REFS (kept internally
    consistent so no earlier invariant fires) -> ref-bounds."""
    m = copy.deepcopy(plan)
    refs = tuple(0.1 * (i + 1) for i in range(ctx.max_refs + 1))
    for g in m.groups:
        fat = dataclasses.replace(g.plan, refs=refs,
                                  sensing_phases=len(refs))
        g.plan = fat
        for it in g.items:
            it.plan = fat
        return m
    return None


def mutate_schedule_program_into_busy_wave(plan, ctx, rng):
    """Slot a migration copyback into a wave whose die is already sensing
    (a *different* wordline, so slot-hazard stays silent)
    -> migration-barrier."""
    m = copy.deepcopy(plan)
    for wi, wave in enumerate(m.waves):
        if not wave.groups:
            continue
        plane, blk, wl = m.groups[wave.groups[0]].wls[0]
        m.programs.append(ProgramStep(
            label="copyback mutant", wls=[(plane, blk, wl + 10_000)],
            dies=(ctx.die_of_plane(plane),), wave=wi))
        return m
    return None


MUTATIONS = (
    ("unbook_wave", "ledger-conservation", mutate_unbook_wave),
    ("merge_same_die_wave", "wave-die-disjoint", mutate_merge_same_die_wave),
    ("drop_program_barrier", "slot-hazard", mutate_drop_program_barrier),
    ("move_combine_early", "schedule-topology", mutate_move_combine_early),
    ("inflate_fused_past_vmem", "vmem-budget",
     mutate_inflate_fused_past_vmem),
    ("cross_plan_group", "encoding-consistency", mutate_cross_plan_group),
    ("ref_overflow", "ref-bounds", mutate_ref_overflow),
    ("schedule_program_into_busy_wave", "migration-barrier",
     mutate_schedule_program_into_busy_wave),
)


@pytest.mark.parametrize("encoding", ENCODINGS)
@pytest.mark.parametrize("dies", [1, 2, 4])
def test_mutation_suite(encoding, dies):
    """Every seeded schedule corruption is rejected with its intended
    invariant, the unmutated corpus verifies clean, and every mutation
    class finds at least one applicable plan per configuration."""

    @settings(max_examples=2, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def run(seed):
        rng = np.random.default_rng(seed)
        plans = []
        for label, sess, expr in iter_corpus(encoding, dies, seed % 1000):
            plans.append((label, sess, sess.lower(expr)))   # verifies clean
        applied = {name: 0 for name, _, _ in MUTATIONS}
        for name, invariant, mutate in MUTATIONS:
            for label, sess, plan in plans:
                mutant = mutate(plan, _ctx(sess), rng)
                if mutant is None:
                    continue
                applied[name] += 1
                with pytest.raises(PlanInvariantError) as exc:
                    check_plan(mutant, _ctx(sess))
                assert exc.value.invariant == invariant, (
                    f"{name} on {label}: expected {invariant}, "
                    f"got {exc.value.invariant}: {exc.value}")
                # the original plan still verifies clean after mutation
                # (deep copy did not alias)
                check_plan(plan, _ctx(sess))
        missing = [n for n, c in applied.items() if c == 0]
        assert not missing, f"mutations never applicable: {missing}"

    run()


# ---------------------------------------------------------------------------
# golden error messages (satellite: wave index + die + invariant named)

def _contended_session(dies=2):
    rng = np.random.default_rng(7)
    cfg = SSDConfig(page_kb=1, channels=1, dies_per_channel=dies)
    n = cfg.page_bits
    sess = ComputeSession(config=cfg, backend="sim", verify="on")
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(4)]
    p, q = sess.write_pair("p", bits[0], "q", bits[1], die=0)
    r, s = sess.write_pair("r", bits[2], "s", bits[3], die=0)
    return sess, (p & q) ^ (r | s)


def test_golden_message_wave_die_disjoint():
    sess, expr = _contended_session()
    plan = sess.lower(expr)
    rng = np.random.default_rng(0)
    mutant = mutate_merge_same_die_wave(plan, _ctx(sess), rng)
    with pytest.raises(PlanInvariantError) as exc:
        check_plan(mutant, _ctx(sess))
    msg = str(exc.value)
    assert "wave-die-disjoint" in msg
    assert "wave 0" in msg
    assert "die 0" in msg
    assert exc.value.wave == 0 and exc.value.die == 0
    assert ">>wave 0" in exc.value.excerpt          # rendered excerpt


def test_golden_message_schedule_topology():
    sess, expr = _contended_session()
    plan = sess.lower(expr)
    mutant = mutate_move_combine_early(plan, _ctx(sess),
                                       np.random.default_rng(0))
    assert mutant is not None
    with pytest.raises(PlanInvariantError) as exc:
        check_plan(mutant, _ctx(sess))
    msg = str(exc.value)
    assert "schedule-topology" in msg and "wave 0" in msg
    assert "combine[" in msg


def test_golden_message_slot_hazard():
    rng = np.random.default_rng(3)
    n = SMALL.page_bits
    sess = ComputeSession(config=SSDConfig(page_kb=1), backend="sim")
    a = sess.write("a", (rng.random(n) < 0.5).astype(np.uint8))
    b = sess.write("b", (rng.random(n) < 0.5).astype(np.uint8))
    plan = sess.lower(a & b)            # scattered pair -> realign program
    assert plan.programs and plan.programs[0].wave == -1
    mutant = mutate_drop_program_barrier(plan, _ctx(sess), rng)
    assert mutant is not None
    with pytest.raises(PlanInvariantError) as exc:
        check_plan(mutant, _ctx(sess))
    msg = str(exc.value)
    assert "slot-hazard" in msg and "wave 0" in msg and "die" in msg
    assert "program[0]" in msg


def test_golden_message_vmem_budget():
    rng = np.random.default_rng(4)
    n = SMALL.page_bits
    sess = ComputeSession(config=SSDConfig(page_kb=1), backend="sim")
    vecs = []
    for i in range(0, 4, 2):
        bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(2)]
        a, b = sess.write_pair(f"v{i}", bits[0], f"v{i+1}", bits[1])
        vecs += [a, b]
    plan = sess.lower(sess.chain("and", vecs))
    mutant = mutate_inflate_fused_past_vmem(plan, _ctx(sess), rng)
    assert mutant is not None
    with pytest.raises(PlanInvariantError) as exc:
        check_plan(mutant, _ctx(sess))
    msg = str(exc.value)
    assert "vmem-budget" in msg and "VMEM" in msg and "fused[" in msg


def test_golden_message_ledger_conservation():
    sess, expr = _contended_session()
    plan = sess.lower(expr)
    mutant = mutate_unbook_wave(plan, _ctx(sess), np.random.default_rng(0))
    with pytest.raises(PlanInvariantError) as exc:
        check_plan(mutant, _ctx(sess))
    msg = str(exc.value)
    assert "ledger-conservation" in msg
    assert "group[" in msg and " B " in msg          # byte figure named


def test_golden_message_ref_bounds_and_encoding():
    sess, expr = _contended_session()
    plan = sess.lower(expr)
    ctx = _ctx(sess)
    over = mutate_ref_overflow(plan, ctx, np.random.default_rng(0))
    with pytest.raises(PlanInvariantError) as exc:
        check_plan(over, ctx)
    assert exc.value.invariant == "ref-bounds"
    assert str(ctx.max_refs) in str(exc.value)
    mixed = mutate_cross_plan_group(plan, ctx, np.random.default_rng(0))
    with pytest.raises(PlanInvariantError) as exc:
        check_plan(mixed, ctx)
    assert exc.value.invariant == "encoding-consistency"
    assert "group[0]" in str(exc.value)


def test_golden_message_migration_barrier():
    """The migration-safety invariant names the copyback, the clashing
    wave/die, and the policy it enforces — and rejects out-of-range waves."""
    sess, expr = _contended_session()
    plan = sess.lower(expr)
    ctx = _ctx(sess)
    mutant = mutate_schedule_program_into_busy_wave(
        plan, ctx, np.random.default_rng(0))
    assert mutant is not None
    with pytest.raises(PlanInvariantError) as exc:
        check_plan(mutant, ctx)
    assert exc.value.invariant == "migration-barrier"
    msg = str(exc.value)
    assert "copyback program (copyback mutant) programs die 0 in wave 0" in msg
    assert "migration copybacks must fill idle die slots only" in msg
    assert "program barrier against in-flight senses" in msg
    assert exc.value.wave == 0 and exc.value.die == 0
    assert exc.value.unit.startswith("program[")

    oob = copy.deepcopy(plan)
    oob.programs.append(ProgramStep(label="copyback oob", wls=[(0, 0, 0)],
                                    dies=(0,), wave=len(plan.waves)))
    with pytest.raises(PlanInvariantError) as exc:
        check_plan(oob, ctx)
    assert exc.value.invariant == "migration-barrier"
    assert (f"scheduled into wave {len(plan.waves)}" in str(exc.value)
            and f"only {len(plan.waves)} wave(s)" in str(exc.value))


def test_schedule_programs_into_idle_waves_passes_verifier():
    """The reliability layer's copyback scheduler only fills idle die
    slots: a die-1 copyback overlaps a die-0-only wave (and the checked
    invariant passes), while a die-0 copyback finds no idle slot and
    falls back to the exempt pre-dispatch barrier wave -1."""
    sess, expr = _contended_session(dies=2)      # all senses live on die 0
    plan = sess.lower(expr)
    ctx = _ctx(sess)
    plane1 = sess.device.config.planes_per_die   # first plane of die 1
    idle = ProgramStep(label="copyback idle", wls=[(plane1, 0, 0)], dies=(1,))
    contended = ProgramStep(label="copyback busy", wls=[(0, 0, 99)], dies=(0,))
    schedule_programs_into_idle_waves(plan, [idle, contended])
    assert idle.wave == 0                        # overlaps the sense wave
    assert contended.wave == -1                  # no idle slot: barrier wave
    assert idle in plan.programs and contended in plan.programs
    check_plan(plan, ctx)                        # placement is hazard-free


def test_render_plan_windows_to_highlight():
    sess, expr = _contended_session()
    plan = sess.lower(expr)
    text = render_plan(plan, highlight=0)
    assert ">>wave 0" in text and f"root=p{plan.root}" in text


# ---------------------------------------------------------------------------
# session integration: modes, memoization, stats

def test_verify_modes_and_memoization():
    rng = np.random.default_rng(11)
    n = SMALL.page_bits
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(2)]
    sess = ComputeSession(config=SSDConfig(page_kb=1), backend="sim",
                          verify="on")
    a, b = sess.write_pair("a", bits[0], "b", bits[1])
    sess.materialize(a & b)
    assert sess.stats()["plans_verified"] == 1
    assert sess.stats()["verify_cache_hits"] == 0
    sess.materialize(a & b)              # same signature: memoized
    assert sess.stats()["plans_verified"] == 1
    assert sess.stats()["verify_cache_hits"] == 1
    sess.materialize(a | b)              # new signature: verified
    assert sess.stats()["plans_verified"] == 2

    off = ComputeSession(device=sess.device, backend="sim", verify="off")
    off.materialize(off["a"] & off["b"])
    assert off.stats()["plans_verified"] == 0

    paranoid = ComputeSession(device=sess.device, backend="sim",
                              verify="paranoid")
    paranoid.materialize(paranoid["a"] & paranoid["b"])
    paranoid.materialize(paranoid["a"] & paranoid["b"])
    assert paranoid.stats()["plans_verified"] == 2     # never memo-skips
    assert paranoid.stats()["verify_cache_hits"] == 0

    with pytest.raises(ValueError):
        ComputeSession(config=SSDConfig(page_kb=1), verify="sometimes")


def test_verify_env_default(monkeypatch):
    monkeypatch.setenv("REPRO_VERIFY", "paranoid")
    sess = ComputeSession(config=SSDConfig(page_kb=1), backend="sim")
    assert sess.verifier.mode == "paranoid"
    monkeypatch.delenv("REPRO_VERIFY")
    sess = ComputeSession(config=SSDConfig(page_kb=1), backend="sim")
    assert sess.verifier.mode == "on"


def test_reset_stats_clears_verifier_counters():
    rng = np.random.default_rng(12)
    n = SMALL.page_bits
    sess = ComputeSession(config=SSDConfig(page_kb=1), backend="sim")
    a, b = sess.write_pair("a", (rng.random(n) < 0.5).astype(np.uint8),
                           "b", (rng.random(n) < 0.5).astype(np.uint8))
    sess.materialize(a & b)
    sess.materialize(a & b)
    assert sess.stats()["plans_verified"] == 1
    sess.reset_stats()
    assert sess.stats()["plans_verified"] == 0
    assert sess.stats()["verify_cache_hits"] == 0
    sess.materialize(a & b)              # memo survives reset (still valid)
    assert sess.stats()["verify_cache_hits"] == 1


# ---------------------------------------------------------------------------
# satellite: Ledger.reset() makespan regression

def test_ledger_reset_clears_makespan_state():
    rng = np.random.default_rng(13)
    cfg = SSDConfig(page_kb=1, channels=1, dies_per_channel=2)
    n = cfg.page_bits
    sess = ComputeSession(config=cfg, backend="sim")
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(4)]
    a, b = sess.write_pair("a", bits[0], "b", bits[1], die=0)
    c, d = sess.write_pair("c", bits[2], "d", bits[3], die=1)
    sess.materialize((a & b) ^ (c | d))
    led = sess.ledger
    assert led.makespan_us() > 0
    assert led.max_parallel_dies >= 1
    sess.reset_stats()
    assert led.makespan_us() == 0
    assert led.die_step_us == 0 and led.channel_step_us == 0
    assert led.host_busy_us == 0 and led.die_steps == 0
    assert led.max_parallel_dies == 0
    assert led.serial_us() == 0 and led.commands == 0
    # and the model re-accumulates from zero, not from stale step state
    sess.materialize((a & b) ^ (c | d))
    assert led.makespan_us() > 0


def test_ledger_reset_no_double_count_on_recovery_resense():
    """Satellite regression: retry re-senses booked *after* a
    ``reset_stats()`` must account only their own recovery steps — never
    re-book the original wave's channel/die step.  Bookings are immediate
    and stateless, so repeated reset+materialize cycles of a deterministic
    faulted workload produce bit-identical ledgers."""
    rng = np.random.default_rng(21)
    cfg = SSDConfig(page_kb=1)
    n = cfg.page_bits
    sess = ComputeSession(config=cfg, backend="sim", encoding="tlc",
                          faults={"pe": 5000, "seed": 9})
    a, b = sess.write_pair("a", (rng.random(n) < 0.5).astype(np.uint8),
                           "b", (rng.random(n) < 0.5).astype(np.uint8))
    expr = a ^ b
    sess.materialize(expr)                        # ladder retries fire
    led = sess.ledger
    assert led.category_us.get("recovery", 0.0) > 0
    sess.reset_stats()
    assert led.category_us == {}
    assert led.die_step_us == 0 and led.channel_step_us == 0

    sess.materialize(expr)
    first = (dict(led.category_us), led.die_step_us, led.channel_step_us,
             led.makespan_us(), led.commands)
    assert first[0].get("recovery", 0.0) > 0      # re-senses re-book afresh
    assert first[0].get("sense", 0.0) > 0         # alongside the primary wave
    sess.reset_stats()
    sess.materialize(expr)
    second = (dict(led.category_us), led.die_step_us, led.channel_step_us,
              led.makespan_us(), led.commands)
    assert second == first                        # no carryover, no double-count
    # recovery work is real work: the makespan includes it
    assert first[3] > first[0]["sense"]


# ---------------------------------------------------------------------------
# satellite: signature embeds the wave layout

def test_signature_distinguishes_wave_structure():
    """Identical DAG shape, different wave structure -> different
    signatures (the executable iterates the wave layout, so sharing one
    cache entry would replay the wrong schedule)."""
    rng = np.random.default_rng(14)
    n = SMALL.page_bits
    bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(4)]

    def lower(die_r):
        cfg = SSDConfig(page_kb=1, channels=1, dies_per_channel=2)
        sess = ComputeSession(config=cfg, backend="sim")
        p, q = sess.write_pair("p", bits[0], "q", bits[1], die=0)
        r, s = sess.write_pair("r", bits[2], "s", bits[3], die=die_r)
        return sess.lower((p & q) ^ (r | s))

    spread = lower(die_r=1)     # die-disjoint: one wave
    packed = lower(die_r=0)     # die-contended: two waves
    assert len(spread.waves) != len(packed.waves)
    assert spread.signature("sim") != packed.signature("sim")

    # and a hand-merged wave layout alone (same groups/steps) changes it
    merged = copy.deepcopy(packed)
    merged.waves[0].groups += merged.waves[1].groups
    merged.waves[1].groups = []
    assert merged.signature("sim") != packed.signature("sim")


def test_fused_spec_declares_tile_split():
    budget = 3 * OPERAND_TILE_BYTES
    rng = np.random.default_rng(15)
    n = SMALL.page_bits
    sess = ComputeSession(config=SSDConfig(page_kb=1), backend="sim",
                          vmem_budget_bytes=budget)
    vecs = []
    for i in range(0, 8, 2):
        bits = [(rng.random(n) < 0.5).astype(np.uint8) for _ in range(2)]
        a, b = sess.write_pair(f"v{i}", bits[0], f"v{i+1}", bits[1])
        vecs += [a, b]
    plan = sess.lower(sess.chain("and", vecs))
    fused = [st.fused for st in plan.steps if st.fused is not None]
    assert fused and fused[0].n_operands == 4
    assert fused[0].pass_operands == 3        # clamped to the budget
