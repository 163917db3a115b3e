"""Functional simulated NAND flash device.

Per-wordline Vth lives in a die-sharded device-resident
:class:`~repro.flash.arena.ShardedVthArena` — one lazily-created
``(slots, page_bits)`` shard per die, addressed by ``(die, slot)`` refs —
so a batched sense is a row gather from device storage instead of a
host-side ``jnp.stack`` over a dict of arrays, and the compiled executor
gathers every operand stack of a batch in one program.  Read plans
execute through a pluggable backend (Pallas sense kernels by default), P/E
cycles are tracked per block, and the unified :class:`repro.api.Ledger`
(time + energy) is threaded through every command so that application
workloads derive their latency/energy from the *actual simulated command
stream* rather than hand-waved constants.

Read plans compile once per (op, chip) through the device's
:class:`repro.api.PlanCache`, and compiled-DAG executables are shared
across sessions through the device's :class:`repro.api.ExecutableCache`
(``device.executables``).  Multi-page ops dispatch through
:meth:`mcflash_read_batch`, which senses all pages of a batch in one fused
kernel call, accounts a single SET_FEATURE switch, and books the whole
batch's die/channel busy time through the batched ledger entry points — no
O(pages) Python accounting loops on the hot path.  The cost of any command
batch is also exposed *without* booking (:meth:`mcflash_cost` /
:meth:`page_read_cost` / :meth:`dma_cost`) so the executor can merge a
whole schedule wave of per-die groups into ONE parallel ledger step.
"""
from __future__ import annotations

import functools
import itertools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.ledger import Ledger
from repro.api.plan_cache import ExecutableCache, PlanCache
from repro.core import mcflash, tlc, vth_model
from repro.core.mcflash import ReadPlan
from repro.core.tlc import PAGES_PER_WL, TLCChipModel
from repro.core.vth_model import ChipModel
from repro.flash.arena import ShardedVthArena, SlotRef
from repro.flash.energy import EnergyModel
from repro.flash.geometry import SSDConfig
from repro.flash.timing import TimingModel

WordlineKey = Tuple[int, int, int]  # (plane, block, wordline)

#: ledger/timing op label for a standard page read of each role
PAGE_READ_OP = {"lsb": "and", "csb": "or", "msb": "or"}


@functools.partial(jax.jit, static_argnames=("encoding", "chip", "n_pe",
                                             "retention_hours"))
def _program_vth(key: jax.Array, pages: Tuple[jnp.ndarray, ...], *,
                 encoding: str, chip, n_pe: float,
                 retention_hours: float) -> jnp.ndarray:
    """Vth of one wordline programmed with its shared ``pages`` (role
    order) under ``encoding``: the 4-state MLC chip, or the 8-state chip
    for TLC and reduced MLC.  One jitted program, so a batch of wordlines
    under ``vmap`` samples exactly the same values (:func:`_copyback_rows`)."""
    if encoding == tlc.MLC:
        lsb, msb = pages
        return vth_model.program_page(key, lsb, msb, chip, n_pe=n_pe,
                                      retention_hours=retention_hours)[0]
    return tlc.program_tlc(key, tlc.encode_states(encoding, pages), chip,
                           n_pe=n_pe)


@functools.partial(jax.jit, static_argnames=(
    "plans", "backend", "encoding", "chip", "n_pe"))
def _copyback_rows(bufs: Tuple[jnp.ndarray, ...], idx: jnp.ndarray,
                   key: jax.Array, *, plans: Tuple[ReadPlan, ...], backend,
                   encoding: str, chip, n_pe: Tuple[Tuple[float, int], ...]):
    """A copyback realignment of ``n`` wordlines as ONE program.

    ``bufs`` holds the shard buffer of each of the ``K`` operands and then
    the destination's (the same buffer repeated where dies coincide, so the
    compile key does not depend on which do).  Operand ``k`` reads its
    pages from rows ``idx[k*n:(k+1)*n]`` of ``bufs[k]`` with its page-read
    plan ``plans[k]``; wordline ``j`` then programs page ``j`` of every
    operand, in role order (roles past the operands hold zeros), into row
    ``idx[K*n + j]`` of ``bufs[K]``, with the ``j``-th key drawn from
    ``key`` as :meth:`FlashDevice._next_key` draws it, at the P/E count of
    its block: ``n_pe`` gives ``(count, wordlines)`` runs in ``idx`` order.
    Returns the updated destination buffer, the key after the draws and the
    pages (uint8, role order): the rows a page read per source wordline and
    one :meth:`FlashDevice.program_shared` per destination wordline give."""
    from repro.kernels import ops as kops

    n = idx.shape[0] // (len(plans) + 1)
    pages = []
    for k, plan in enumerate(plans):
        vth = jnp.take(bufs[k], idx[k * n:(k + 1) * n], axis=0, mode="clip")
        pages.append(kops.unpack_bits(backend.sense(vth, plan)))
    pages += [jnp.zeros_like(pages[0])] * (PAGES_PER_WL[encoding] - len(pages))

    def draw(k, _):
        k, sub = jax.random.split(k)
        return k, sub

    key, subs = jax.lax.scan(draw, key, None, length=n)
    vths, at = [], 0
    for pe, count in n_pe:
        program = functools.partial(_program_vth, encoding=encoding, chip=chip,
                                    n_pe=pe, retention_hours=0.0)
        vths.append(jax.vmap(lambda sub, *p: program(sub, p))(
            subs[at:at + count], *(p[at:at + count] for p in pages)))
        at += count
    vth = vths[0] if len(vths) == 1 else jnp.concatenate(vths)
    out = bufs[-1].at[idx[len(plans) * n:]].set(vth)
    return out, key, tuple(pages)


class _Rows(NamedTuple):
    """Row ``row`` of a batch of pages in role order: the stored operands of
    a wordline that a copyback programmed, kept without slicing."""
    pages: Tuple[jnp.ndarray, ...]
    row: int


class FlashDevice:
    """One simulated multi-plane NAND chip set (the §6 SSD's raw layer)."""

    def __init__(self, chip: ChipModel | None = None,
                 config: SSDConfig | None = None,
                 timing: TimingModel | None = None,
                 energy: EnergyModel | None = None,
                 seed: int = 0, shard_devices=None,
                 tlc_chip: TLCChipModel | None = None,
                 exec_cache_capacity: Optional[int] = ExecutableCache.DEFAULT_CAPACITY):
        self.chip = chip or vth_model.get_chip_model()
        # 8-state chip model backing TLC and reduced-MLC wordlines (§7)
        self.tlc_chip = tlc_chip or TLCChipModel()
        self.config = config or SSDConfig()
        self.timing = timing or TimingModel()
        self.energy = energy or EnergyModel()
        self._page_bits = self.config.page_bits
        # One Vth shard per die; `shard_devices` ("auto" or a device list)
        # optionally pins shards to JAX devices round-robin.
        self.arena = ShardedVthArena(self._page_bits,
                                     n_dies=self.config.dies,
                                     devices=shard_devices)
        self._slot_of: Dict[WordlineKey, SlotRef] = {}
        # stored page bits per wordline, role order (2 for MLC/reduced, 3 TLC)
        self._operands: Dict[WordlineKey, "Tuple[jnp.ndarray, ...] | _Rows"] = {}
        #: retired wordlines: their rows are freed as the next batch's
        #: lowering begins (:meth:`begin_batch`)
        self._stale: List[WordlineKey] = []
        self._encoding_of: Dict[WordlineKey, str] = {}
        self.pe_counts: Dict[Tuple[int, int], int] = {}
        self.ledger = Ledger()
        self.plans = PlanCache()
        # Compiled-DAG executables: shared by every session on this device
        # (keys embed backend + plan signature), LRU-bounded.
        self.executables = ExecutableCache(capacity=exec_cache_capacity)
        from repro.api.backends import PallasBackend   # layers on kernels only
        self._default_backend = PallasBackend()
        self._key = jax.random.PRNGKey(seed)
        self.ftl = None                # first-bound FTL registers itself here
        #: optional :class:`repro.reliability.FaultModel` — when installed
        #: (``ComputeSession(faults=...)`` / ``REPRO_FAULTS``) every program
        #: perturbs its Vth rows per the seeded wear model
        self.faults = None
        #: when set (by the executor's lowering pass) every shared-page
        #: program appends ``(label, wls)`` here, so placement writes show
        #: up on the lowered plan for static hazard checking
        self.program_log: "list | None" = None

    def set_default_backend(self, backend) -> None:
        """Backend used when a command doesn't pass one explicitly (sessions
        install their backend here so e.g. copyback realignment reads follow
        the session's sim/Pallas choice)."""
        self._default_backend = backend

    # -- geometry helpers ---------------------------------------------------
    def die_of_plane(self, plane: int) -> int:
        return plane // self.config.planes_per_die

    # retained alias (older callers)
    _die_of_plane = die_of_plane

    def _channel_of_plane(self, plane: int) -> int:
        return self.die_of_plane(plane) // self.config.dies_per_channel

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    # -- arena access (the compiled executor's input surface) ----------------
    def vth_stack(self, wls: List[WordlineKey], *,
                  place: bool = True) -> jnp.ndarray:
        """(N, page_bits) Vth of a wordline batch (see
        :meth:`ShardedVthArena.gather`).  ``place=False`` leaves a die-local
        gather on its shard's pinned device (device-placed wave dispatch);
        the default funnels onto the primary compute device."""
        return self.arena.gather([self._slot_of[wl] for wl in wls],
                                 place=place)

    def vth_stack_many(self, wls_lists: List[List[WordlineKey]]
                       ) -> Tuple[jnp.ndarray, ...]:
        """One (N_i, page_bits) Vth stack per wordline batch, in order — ONE
        gather program for them all on unmapped shards (the unplaced
        executor's whole batch); mapped shards funnel each stack onto the
        primary compute device as :meth:`vth_stack` does."""
        slot_of = self._slot_of
        return self.arena.gather_many(
            [[slot_of[wl] for wl in wls] for wls in wls_lists])

    # -- commands -----------------------------------------------------------
    def program_shared_batch(self, wls: List[WordlineKey],
                             lsb_pages: List[jnp.ndarray],
                             msb_pages: List[jnp.ndarray],
                             retention_hours: float = 0.0, *,
                             csb_pages: "List[jnp.ndarray] | None" = None,
                             encoding: str = tlc.MLC,
                             kind: str = "program") -> None:
        """Program the shared pages of a wordline batch under one encoding.

        MLC programs (LSB, MSB) through the 4-state chip model; TLC programs
        (LSB, CSB, MSB) and reduced-MLC programs (LSB, MSB) on the widely
        spaced {L0, L2, L5, L7} states, both through the 8-state chip.  Vth
        generation stays per-page (independent RNG streams, one jitted
        :func:`_program_vth` each), but the arena write is ONE scatter and
        the ledger entry ONE batched call, labelled ``kind``.
        """
        assert encoding in tlc.ENCODINGS, encoding
        if encoding == tlc.TLC:
            assert csb_pages is not None and len(csb_pages) == len(wls), \
                "TLC wordlines carry three shared pages (lsb, csb, msb)"
        else:
            assert csb_pages is None, f"{encoding} wordlines have no CSB page"
        assert len(wls) == len(lsb_pages) == len(msb_pages)
        if not wls:
            return
        if encoding != tlc.MLC:
            # retention drift is modeled for the MLC chip only; the §7
            # experiments sweep P/E cycling
            assert retention_hours == 0.0, \
                "retention drift is not modeled for 8-state encodings"
        chip = self.chip if encoding == tlc.MLC else self.tlc_chip
        vths = []
        for i, wl in enumerate(wls):
            lsb_bits, msb_bits = lsb_pages[i], msb_pages[i]
            assert lsb_bits.shape == (self._page_bits,), lsb_bits.shape
            plane, block, _ = wl
            n_pe = self.pe_counts.get((plane, block), 0)
            pages = ((lsb_bits, csb_pages[i], msb_bits)
                     if encoding == tlc.TLC else (lsb_bits, msb_bits))
            vth = _program_vth(self._next_key(), pages, encoding=encoding,
                               chip=chip, n_pe=float(n_pe),
                               retention_hours=float(retention_hours))
            if self.faults is not None:
                vth = self.faults.perturb(vth, plane=plane, block=block,
                                          wl=wl[2], n_pe=n_pe)
            vths.append(vth)
            self._operands[wl] = tuple(p.astype(jnp.uint8) for p in pages)
            self._encoding_of[wl] = encoding
        slots = []
        for wl in wls:
            slot = self._slot_of.get(wl)
            if slot is None:
                # die-affinity allocation: the row lives on its plane's die shard
                (slot,) = self.arena.alloc(self.die_of_plane(wl[0]), 1,
                                           encoding=encoding)
                self._slot_of[wl] = slot
            elif self.arena.encoding_of(slot) != encoding:
                # reprogram under a different encoding reuses the slot
                self.arena.retag(slot, encoding)
            slots.append(slot)
        self.arena.write(slots, jnp.stack(vths))
        self._book_program(wls, encoding, kind)

    def _book_program(self, wls: List[WordlineKey], encoding: str,
                      kind: str) -> None:
        """Ledger (and plan program log) entry of a shared-page program of
        ``wls``: one page's worth of ISPP per shared page."""
        n_pages = PAGES_PER_WL[encoding]
        per_die: Dict[int, float] = {}
        for wl in wls:
            die = self.die_of_plane(wl[0])
            per_die[die] = per_die.get(die, 0.0) + n_pages * self.timing.t_prog_us
        label = f"{kind} {encoding}x{len(wls)}p"
        self.ledger.add_die_batch(
            per_die,
            n_pages * self.energy.e_prog_uj_kb * self.config.page_kb * len(wls),
            commands=len(wls), category="program", label=label)
        if self.program_log is not None:
            self.program_log.append((label, list(wls)))

    def program_shared(self, wl: WordlineKey, lsb_bits: jnp.ndarray,
                       msb_bits: jnp.ndarray, retention_hours: float = 0.0,
                       *, csb_bits: "jnp.ndarray | None" = None,
                       encoding: str = tlc.MLC) -> None:
        """Program the shared pages of one wordline (16 kB each)."""
        self.program_shared_batch(
            [wl], [lsb_bits], [msb_bits], retention_hours=retention_hours,
            csb_pages=None if csb_bits is None else [csb_bits],
            encoding=encoding)

    # -- command cost models (no booking) ------------------------------------
    def _per_die_us(self, wls: List[WordlineKey], us: float) -> Dict[int, float]:
        per_die: Dict[int, float] = {}
        for wl in wls:
            die = self.die_of_plane(wl[0])
            per_die[die] = per_die.get(die, 0.0) + us
        return per_die

    def mcflash_cost(self, wls: List[WordlineKey], op: str,
                     switch_op: bool = True,
                     phases: Optional[int] = None) -> Tuple[Dict[int, float], float]:
        """(per-die busy us, energy uj) of a batched MCFlash sense: per-page
        read latency aggregated per die, ONE SET_FEATURE for the whole batch.
        ``phases`` overrides the MLC Table-1 phase count (encoded plans)."""
        per_die = self._per_die_us(
            wls, self.timing.op_latency_us(op, switch_op=False, phases=phases))
        if switch_op and wls:
            first = self.die_of_plane(wls[0][0])
            per_die[first] += self.timing.t_setfeature_us
        uj = (self.energy.read_energy_uj_kb(op, phases)
              * self.config.page_kb * len(wls))
        return per_die, uj

    def page_read_cost(self, wls: List[WordlineKey], which: str = "lsb",
                       phases: Optional[int] = None) -> Tuple[Dict[int, float], float]:
        """(per-die busy us, energy uj) of a batched default-reference read."""
        op = PAGE_READ_OP[which]
        per_die = self._per_die_us(wls, self.timing.read_latency_us(op, phases))
        uj = (self.energy.read_energy_uj_kb(op, phases)
              * self.config.page_kb * len(wls))
        return per_die, uj

    def dma_cost(self, wls: List[WordlineKey]) -> Dict[int, float]:
        """Per-channel busy us of NAND -> controller page transfers."""
        us = self.config.page_bytes / (self.config.channel_bw_gbps * 1e3)
        per_ch: Dict[int, float] = {}
        for wl in wls:
            ch = self._channel_of_plane(wl[0])
            per_ch[ch] = per_ch.get(ch, 0.0) + us
        return per_ch

    # -- batched ledger accounting ------------------------------------------
    def account_mcflash_batch(self, wls: List[WordlineKey], op: str,
                              switch_op: bool = True,
                              phases: Optional[int] = None) -> None:
        """Book die busy time + energy for a batched MCFlash sense."""
        if not wls:
            return
        per_die, uj = self.mcflash_cost(wls, op, switch_op=switch_op,
                                        phases=phases)
        self.ledger.add_die_batch(per_die, uj, commands=len(wls))

    def account_page_read_batch(self, wls: List[WordlineKey],
                                which: str = "lsb",
                                phases: Optional[int] = None) -> None:
        """Book die busy time + energy for a batched default-reference read."""
        if not wls:
            return
        per_die, uj = self.page_read_cost(wls, which, phases)
        self.ledger.add_die_batch(per_die, uj, commands=len(wls))

    def mcflash_read_batch(self, wls: List[WordlineKey], op: str, *,
                           plan: ReadPlan | None = None, backend=None,
                           switch_op: bool = True) -> jnp.ndarray:
        """Execute one MCFlash op over a batch of programmed wordlines.

        All pages sense through **one** backend call ((N, page_bits) Vth
        gather -> (N, words) packed results); the SET_FEATURE offset switch
        is accounted once for the whole batch — the multi-plane dispatch
        path the paper's §6 layout assumes.
        """
        assert wls, "empty wordline batch"
        if plan is None:
            plan = self.plans.get(op, self.chip)
        self.account_mcflash_batch(wls, op, switch_op=switch_op,
                                   phases=plan.sensing_phases)
        if backend is None:
            backend = self._default_backend
        return backend.sense(self.vth_stack(wls), plan)

    def mcflash_read(self, wl: WordlineKey, op: str, packed: bool = True,
                     switch_op: bool = True, *, plan: ReadPlan | None = None,
                     backend=None) -> jnp.ndarray:
        """Execute an MCFlash bitwise op on a single programmed wordline."""
        from repro.kernels import ops as kops
        packed_bits = self.mcflash_read_batch([wl], op, plan=plan,
                                              backend=backend,
                                              switch_op=switch_op)
        return packed_bits[0] if packed else kops.unpack_bits(packed_bits)[0]

    def page_read_plan(self, which: str = "lsb",
                       encoding: str = tlc.MLC) -> ReadPlan:
        """Default-reference read plan for one shared-page role."""
        if encoding != tlc.MLC:
            return self.plans.get_encoded("read", (which,), self.tlc_chip,
                                          encoding)
        assert which in ("lsb", "msb"), \
            f"MLC wordlines have no {which!r} page (missing encoding=?)"
        v0, v1, v2 = self.chip.vref_default
        if which == "lsb":
            return ReadPlan("page_lsb", "lsb", (v1,), 1)
        return ReadPlan("page_msb", "msb", (v0, v2), 2)

    def page_read_batch(self, wls: List[WordlineKey], which: str = "lsb", *,
                        backend=None, encoding: str = tlc.MLC) -> jnp.ndarray:
        """Standard (default-reference) read of a batch of pages in one
        fused sense call -> (N, words) packed."""
        assert wls, "empty wordline batch"
        plan = self.page_read_plan(which, encoding)
        self.account_page_read_batch(wls, which, phases=plan.sensing_phases)
        return (backend or self._default_backend).sense(self.vth_stack(wls), plan)

    def page_read(self, wl: WordlineKey, which: str = "lsb",
                  packed: bool = True, *, backend=None,
                  encoding: str = tlc.MLC) -> jnp.ndarray:
        """Standard (default-reference) page read."""
        from repro.kernels import ops as kops
        out = self.page_read_batch([wl], which, backend=backend,
                                   encoding=encoding)
        return out[0] if packed else kops.unpack_bits(out)[0]

    def copyback(self, srcs: Sequence[List[WordlineKey]],
                 roles: Sequence[str], dst: List[WordlineKey],
                 encoding: str = tlc.MLC) -> None:
        """Copyback realignment (Fig 9e): read role ``roles[k]`` of every
        source wordline ``srcs[k][j]`` and program those pages together, in
        the encoding's role order, onto the fresh wordline ``dst[j]``.

        ONE jitted program (:func:`_copyback_rows`) for all the wordlines:
        the same page reads, the same Vth sampling and the same keys as a
        page read per source and a :meth:`program_shared` per destination
        wordline, so the rows are bit-identical to that, at the P/E count
        of each destination block; the ledger books those reads and that
        program.  With a fault model installed (its perturbation is per
        row) or shards on several JAX devices, the pages are read in one
        sense per operand and programmed by :meth:`program_shared_batch`
        instead: the benchmark's control (worn, faulty blocks) runs that
        path, not :func:`_copyback_rows`.
        """
        from repro.kernels import ops as kops

        n = len(dst)
        assert n and all(len(s) == n for s in srcs), (n, [len(s) for s in srcs])
        if self.faults is not None or self.arena.devices:
            rows = [kops.unpack_bits(self.page_read_batch(s, r,
                                                          encoding=encoding))
                    for s, r in zip(srcs, roles)]
            zeros = jnp.zeros_like(rows[0])
            by_role = dict(zip(tlc.ROLES_OF[encoding], rows))
            pages = {role: list(by_role.get(role, zeros))
                     for role in tlc.ROLES_OF[encoding]}
            self.program_shared_batch(dst, pages["lsb"], pages["msb"],
                                      csb_pages=pages.get("csb"),
                                      encoding=encoding, kind="copyback")
            return
        plans = tuple(self.page_read_plan(r, encoding) for r in roles)
        for s, r, plan in zip(srcs, roles, plans):
            self.account_page_read_batch(s, r, phases=plan.sensing_phases)
        die = self.die_of_plane(dst[0][0])
        assert all(self.die_of_plane(p) == die for p, _, _ in dst), dst
        assert not any(wl in self._slot_of for wl in dst), \
            "copyback programs fresh wordlines"
        refs = self.arena.alloc(die, n, encoding=encoding)
        dies = [self.die_of_plane(s[0][0]) for s in srcs] + [die]
        idx = ([self._slot_of[wl][1] for s in srcs for wl in s]
               + [slot for _, slot in refs])
        buf, self._key, pages = _copyback_rows(
            tuple(self.arena.shard(d).buf for d in dies),
            np.asarray(idx, np.int32), self._key, plans=plans,
            backend=self._default_backend, encoding=encoding,
            chip=self.chip if encoding == tlc.MLC else self.tlc_chip,
            n_pe=tuple((float(pe), len(list(run))) for pe, run in
                       itertools.groupby(self.pe_counts.get((p, b), 0)
                                         for p, b, _ in dst)))
        self.arena.shard(die).install(buf)
        for j, (wl, ref) in enumerate(zip(dst, refs)):
            self._slot_of[wl] = ref
            self._operands[wl] = _Rows(pages, j)
            self._encoding_of[wl] = encoding
        self._book_program(dst, encoding, "copyback")

    # -- reclamation of retired rows -----------------------------------------
    def retire(self, wls: List[WordlineKey]) -> None:
        """No live placement uses ``wls`` any more (their vectors moved or
        were rewritten).  Their rows are freed as the next batch's lowering
        begins (:meth:`begin_batch`), not at once: the lowering under way
        may have planned a sense of them."""
        self._stale.extend(wls)

    def begin_batch(self) -> List[WordlineKey]:
        """A batch's lowering begins: frees the rows of every wordline
        retired before it, so the lowering's copybacks reuse them; returns
        those wordlines.  A batch dispatched earlier is not disturbed: arena
        updates write new buffers (nothing is donated), and its gather
        already holds the old ones."""
        freed: List[WordlineKey] = []
        refs: List[SlotRef] = []
        for wl in self._stale:
            ref = self._slot_of.pop(wl, None)
            if ref is None:              # its block was erased meanwhile
                continue
            refs.append(ref)
            freed.append(wl)
            self._operands.pop(wl, None)
            self._encoding_of.pop(wl, None)
        self._stale.clear()
        self.arena.free(refs)
        return freed

    def erase_block(self, plane: int, block: int) -> None:
        self.pe_counts[(plane, block)] = self.pe_counts.get((plane, block), 0) + 1
        stale = [k for k in self._slot_of if k[0] == plane and k[1] == block]
        self.arena.free([self._slot_of.pop(wl) for wl in stale])
        for wl in stale:
            self._operands.pop(wl, None)
            self._encoding_of.pop(wl, None)
        # block erase ~ 3.5 ms, energy ~ 2x page program
        self.ledger.add_die(self.die_of_plane(plane), 3500.0,
                            2 * self.energy.e_prog_uj_kb * self.config.page_kb,
                            category="erase",
                            label=f"erase p{plane}b{block}")

    def dma_to_controller(self, wl: WordlineKey) -> None:
        """Account a page transfer NAND -> controller on the wordline's channel."""
        self.dma_to_controller_batch([wl])

    def dma_to_controller_batch(self, wls: List[WordlineKey]) -> None:
        """Account NAND -> controller transfers for a whole page batch in one
        ledger call (per-channel busy time aggregated host-side)."""
        if not wls:
            return
        self.ledger.add_channel_batch(self.dma_cost(wls))

    def ext_to_host(self, n_bytes: int) -> None:
        self.ledger.add_host(n_bytes / (self.config.host_bw_gbps * 1e3),
                             label=f"to-host {n_bytes}B")

    def age(self, hours: float) -> None:
        """Advance simulated retention time: every already-programmed arena
        row drifts down by the fault model's uniform retention term (future
        programs age from the new baseline).  No-op without a fault model."""
        if self.faults is None or hours <= 0:
            return
        delta = self.faults.age_delta(hours)
        refs = list(self._slot_of.values())
        if refs and delta != 0.0:
            self.arena.write(refs, self.arena.gather(refs) + delta)

    # -- oracles for verification -------------------------------------------
    def stored_operands(self, wl: WordlineKey) -> Tuple[jnp.ndarray, ...]:
        """Stored page bits in role order (2 pages for MLC/reduced, 3 TLC)."""
        pages = self._operands[wl]
        if isinstance(pages, _Rows):
            return tuple(p[pages.row] for p in pages.pages)
        return pages

    def encoding_of(self, wl: WordlineKey) -> str:
        """Row encoding of a programmed wordline."""
        return self._encoding_of[wl]

    def expected(self, wl: WordlineKey, op: str) -> jnp.ndarray:
        pages = self.stored_operands(wl)
        assert len(pages) == 2, \
            "expected() models 2-operand wordlines; 3-page TLC wordlines " \
            "need a 3-operand oracle (see tests/test_cross_encoding.py)"
        lsb, msb = pages
        return mcflash.expected_result(op, lsb, msb)
