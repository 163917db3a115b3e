"""Flash translation layer: allocation, wear leveling, operand alignment.

The FTL is where MCFlash integrates into an SSD (paper §5.1): shared-page
operand placement is a *placement policy*, and the bitwise op is dispatched
as a read with a per-op SET_FEATURE offset set.  This module provides:

- wear-levelled block allocation (least-P/E free block per plane),
- **die-affinity placement** (§6 layout): every vector gets a *home die*
  (round-robin across dies unless pinned with ``die=``) and stripes its
  pages across that die's planes only — so a vector's co-pages always share
  a die (one shard gather per sense group) while *independent* vectors
  spread across dies, which is what lets the compiled executor dispatch
  their sense groups concurrently on different dies,
- **encoding-aware co-location** (§7): each vector carries the row encoding
  it was programmed under.  MLC / reduced-MLC wordlines co-locate operand
  *pairs* on the shared LSB/MSB pages; TLC wordlines co-locate operand
  *triples* on LSB/CSB/MSB, which is what gives the executor its 3-operand
  single-sense fast paths,
- aligned operand-group writes (operands assigned shared-page roles in
  canonical order on the same wordlines),
- runtime copyback realignment for scattered operands, one device program
  per realignment (a realigned group goes to an operand's home die where
  its shard has room, else to the least-loaded die with room; NOT-ready
  derived placements inherit the source vector's home die),
- reclamation: a placement no vector lives on any more is retired to the
  device, which frees its arena rows once no batch can still read them.

Vector-level *compute* lives in :class:`repro.api.ComputeSession`; the
historical ``mcflash_compute`` / ``mcflash_chain`` entry points remain as
thin shims that forward to a session bound to this FTL, so existing callers
keep working while new code talks to the session layer directly.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import tlc
from repro.core.rber import WearTracker
from repro.core.tlc import PAGES_PER_WL, ROLES_OF
from repro.flash.device import FlashDevice, WordlineKey
from repro.obs.trace import traced
from repro.reliability import checkwords


@dataclasses.dataclass
class VectorMeta:
    name: str
    n_bits: int
    pages: List[WordlineKey]          # striped page placement
    role: str                          # 'lsb' | 'csb' | 'msb' (shared page)
    #: the co-located page holds zeros (scattered writes) — required for
    #: in-flash NOT; losing a pairing does NOT zero the stale co-page.
    zero_co_page: bool = False
    #: home die: all pages stripe across this die's planes (die affinity)
    die: int = 0
    #: row encoding the vector was programmed under (mlc | tlc | reduced-mlc)
    encoding: str = tlc.MLC
    #: sampled-parity checkword (reliability layer): the vector's bit values
    #: at the shared deterministic sample positions, recorded at write time
    check: Optional[np.ndarray] = None


class FTL:
    def __init__(self, device: FlashDevice):
        self.device = device
        if getattr(device, "ftl", None) is None:
            device.ftl = self          # first FTL owns the device's allocator
        self.cfg = device.config
        self._next_wl: Dict[int, Tuple[int, int]] = {}   # plane -> (block, wl)
        #: per-block P/E + observed-RBER health (reliability layer); retired
        #: blocks are skipped by the allocator
        self.wear = WearTracker()
        self.vectors: Dict[str, VectorMeta] = {}
        #: name -> ordered tuple of ALL names co-located on its wordlines
        #: (pairs under MLC/reduced-MLC, up to triples under TLC)
        self._group_of: Dict[str, Tuple[str, ...]] = {}
        #: live vectors per placement, keyed by its first wordline (the
        #: members of a co-located group share one placement)
        self._users: Dict[WordlineKey, int] = {}
        self._next_die = 0                               # round-robin home die
        self._session = None

    @property
    def _tracer(self):
        """Tracer attached to the device ledger (None when tracing is off) —
        placement work (copyback realignment, NOT-ready derived placements)
        shows up as 'ftl' wall spans bracketing its device-lane spans."""
        return self.device.ledger.tracer

    @property
    def session(self):
        """Lazily-created :class:`repro.api.ComputeSession` bound to this FTL."""
        if self._session is None:
            from repro.api.session import ComputeSession
            self._session = ComputeSession(ftl=self)
        return self._session

    # -- allocation ----------------------------------------------------------
    def allocate_wordline(self, plane: int) -> WordlineKey:
        block, wl = self._next_wl.get(plane, (0, 0))
        while self.wear.is_retired((plane, block)):      # skip retired blocks
            block, wl = block + 1, 0
        key = (plane, block, wl)
        wl += 1
        if wl >= self.cfg.pages_per_block // 2:          # wordlines per block
            block, wl = block + 1, 0
        self._next_wl[plane] = (block, wl)
        return key

    def vectors_in_block(self, plane: int, block: int) -> List[str]:
        """Registered vectors with at least one page in (plane, block)."""
        return [m.name for m in self.vectors.values()
                if any(p == plane and b == block for p, b, _ in m.pages)]

    def retire_block(self, plane: int, block: int) -> None:
        """Mark a block bad: the allocator skips it from now on (resident
        data stays readable until its vectors are relocated/rewritten)."""
        self.wear.retire((plane, block))

    # -- placement -----------------------------------------------------------
    def _home_die(self, die: "int | None" = None) -> int:
        """Pick (or validate) a vector's home die — round-robin by default so
        independent vectors spread across dies for die-parallel dispatch."""
        if die is None:
            die = self._next_die % self.cfg.dies
            self._next_die += 1
        assert 0 <= die < self.cfg.dies, (die, self.cfg.dies)
        return die

    def _placement(self, n_pages: int, die: int) -> List[WordlineKey]:
        """Allocate ``n_pages`` wordlines striped across ``die``'s planes."""
        ppd = self.cfg.planes_per_die
        return [self.allocate_wordline(die * ppd + (i % ppd))
                for i in range(n_pages)]

    def _realign_die(self, metas: Sequence[VectorMeta]) -> int:
        """Home die of a realigned group: the first operand's home die whose
        arena shard has room for the group's rows; else the die with the
        fewest rows among those with room (or no shard yet); else, with no
        room anywhere, the operands' die with the fewest rows.

        Realignment then grows no shard while the SSD has room.  A vector
        ANDed with one partner after another (a year bitmap with each
        discount in turn) would otherwise pile the merged placements onto
        one die, whose shard grows, and recompiles every program that
        reads it, at a point that depends on the order of the queries."""
        arena, n = self.device.arena, len(metas[0].pages)
        dies = list(dict.fromkeys(m.die for m in metas))
        for die in dies:
            if arena.fits(die, n):
                return die
        roomy = [d for d in range(self.cfg.dies) if arena.fits(d, n)]
        return min(roomy or dies, key=arena.used_on)

    def die_of(self, name: str) -> int:
        """Home die of a registered vector."""
        return self.vectors[name].die

    def encoding_of(self, name: str) -> str:
        """Row encoding of a registered vector."""
        return self.vectors[name].encoding

    def partner_of(self, name: str) -> "str | None":
        """The one co-located partner of an MLC-style pair (None when the
        vector is scattered or lives in a larger TLC group)."""
        group = self._group_of.get(name, ())
        if len(group) != 2:
            return None
        return group[0] if group[1] == name else group[1]

    def group_of(self, name: str) -> Tuple[str, ...]:
        """All names co-located on ``name``'s wordlines (empty if scattered)."""
        return self._group_of.get(name, ())

    @staticmethod
    def derived_not_name(name: str) -> str:
        """Name of the NOT-ready derived placement the session may cache."""
        return f"__not__{name}"

    def _register(self, meta: VectorMeta) -> None:
        """Make ``meta`` the live placement of its vector."""
        self.vectors[meta.name] = meta
        if meta.pages:
            key = meta.pages[0]
            self._users[key] = self._users.get(key, 0) + 1

    def _drop(self, name: str) -> None:
        """Forget a vector's placement; the last vector to leave a placement
        retires its wordlines (:meth:`FlashDevice.retire`): their rows are
        freed as the next batch's lowering begins.  A placement whose
        partner still lives there stays."""
        meta = self.vectors.pop(name, None)
        if meta is None or not meta.pages:
            return
        key = meta.pages[0]
        left = self._users[key] - 1
        if left:
            self._users[key] = left
        else:
            del self._users[key]
            self.device.retire(meta.pages)

    def _invalidate(self, name: str) -> None:
        """Before a vector is rewritten or moved: drop it from its
        co-location group (remaining members still share THEIR wordlines),
        drop any derived placement built from its old contents, and drop
        its placement (:meth:`_drop`, which retires the wordlines once no
        vector lives there)."""
        group = self._group_of.pop(name, None)
        if group is not None:
            rest = tuple(n for n in group if n != name)
            for n in rest:
                if len(rest) >= 2:
                    self._group_of[n] = rest
                else:
                    self._group_of.pop(n, None)
        self._drop(self.derived_not_name(name))
        self._drop(name)

    def _checkword(self, bits, n_bits: int) -> np.ndarray:
        """Sampled-parity checkword of a vector being written (positions are
        deterministic and shared per n_bits, so leaf checkwords compose
        through op DAGs — see :mod:`repro.reliability.checkwords`)."""
        n_samples = checkwords.DEFAULT_SAMPLES
        mgr = getattr(self._session, "reliability", None) \
            if self._session is not None else None
        if mgr is not None:
            n_samples = mgr.policy.check_samples
        pos = checkwords.sample_positions(n_bits, n_samples)
        return checkwords.checkword(np.asarray(bits), pos)

    def _paginate(self, bits: jnp.ndarray) -> List[jnp.ndarray]:
        pb = self.cfg.page_bits
        n = int(bits.shape[0])
        pad = (-n) % pb
        if pad:
            bits = jnp.pad(bits, (0, pad))
        return [bits[i * pb:(i + 1) * pb] for i in range(bits.shape[0] // pb)]

    def _program_roles(self, placement: List[WordlineKey],
                       pages_by_role: Dict[str, List[jnp.ndarray]],
                       encoding: str) -> None:
        """Program a wordline batch from a role->pages mapping (missing roles
        are zero-filled), under one row encoding."""
        n = len(placement)
        zeros = None
        pages = {}
        for role in ROLES_OF[encoding]:
            got = pages_by_role.get(role)
            if got is None:
                if zeros is None:
                    some = next(iter(pages_by_role.values()))
                    zeros = [jnp.zeros_like(p) for p in some]
                got = zeros
            assert len(got) == n
            pages[role] = got
        self.device.program_shared_batch(
            placement, pages["lsb"], pages["msb"],
            csb_pages=pages.get("csb"), encoding=encoding)

    def write_group_aligned(self, names: Sequence[str],
                            bits: Sequence[jnp.ndarray],
                            die: "int | None" = None,
                            encoding: str = tlc.MLC) -> None:
        """Write k operands co-located on shared wordlines (k=2 pairs for
        MLC / reduced-MLC, k in {2,3} for TLC), striped across one home
        die's planes (``die=None`` round-robins across dies).  Operands take
        the encoding's shared-page roles in canonical order; a TLC pair
        leaves a zero MSB page."""
        names, bits = list(names), list(bits)
        roles = ROLES_OF[encoding]
        assert 2 <= len(names) <= len(roles), \
            f"{encoding} wordlines co-locate 2..{len(roles)} operands"
        assert len(set(names)) == len(names), names
        paged = [self._paginate(b) for b in bits]
        assert len({len(p) for p in paged}) == 1, \
            "aligned operands must match in size"
        for n in names:
            self._invalidate(n)
        die = self._home_die(die)
        placement = self._placement(len(paged[0]), die)
        self._program_roles(placement,
                            dict(zip(roles, paged)), encoding)
        for name, b, role in zip(names, bits, roles):
            self._register(VectorMeta(name, int(b.shape[0]), placement, role,
                                      die=die, encoding=encoding,
                                      check=self._checkword(
                                          b, int(b.shape[0]))))
            self._group_of[name] = tuple(names)

    def write_pair_aligned(self, name_a: str, bits_a: jnp.ndarray,
                           name_b: str, bits_b: jnp.ndarray,
                           die: "int | None" = None,
                           encoding: str = tlc.MLC) -> None:
        """Write operands A,B co-located on shared wordlines (A takes the
        first shared-page role, B the second)."""
        self.write_group_aligned([name_a, name_b], [bits_a, bits_b],
                                 die=die, encoding=encoding)

    def write_scattered(self, name: str, bits: jnp.ndarray, role: str = "lsb",
                        die: "int | None" = None,
                        encoding: str = tlc.MLC) -> None:
        """Write a single vector without co-located partners (needs
        realignment before MCFlash compute) — all other shared pages zero."""
        assert role in ROLES_OF[encoding], (role, encoding)
        self._invalidate(name)
        pages = self._paginate(bits)
        die = self._home_die(die)
        placement = self._placement(len(pages), die)
        self._program_roles(placement, {role: pages}, encoding)
        self._register(VectorMeta(name, int(bits.shape[0]), placement, role,
                                  zero_co_page=True, die=die,
                                  encoding=encoding,
                                  check=self._checkword(
                                      bits, int(bits.shape[0]))))

    def align(self, name_a: str, name_b: str) -> str:
        """Copyback-realign two MLC vectors onto shared wordlines; returns
        the name of the merged pair (A becomes LSB, B becomes MSB).  The
        merged pair lives on A's home die, or B's, where its shard has room
        (:meth:`_realign_die`).

        The copy is ONE device program for all the wordlines
        (:meth:`FlashDevice.copyback`), a ``repro.ftl`` span.  Both old
        placements are dropped: one that no vector lives on any more is
        retired, and its arena rows are freed as the next batch's lowering
        begins (:meth:`FlashDevice.begin_batch`); one whose partner still
        lives there stays until the partner moves."""
        ma, mb = self.vectors[name_a], self.vectors[name_b]
        assert ma.encoding == mb.encoding == tlc.MLC, \
            "align() is the MLC copyback path; use align_group for " \
            "encoded vectors"
        assert len(ma.pages) == len(mb.pages)
        die = self._realign_die([ma, mb])
        self._invalidate(name_a)
        self._invalidate(name_b)
        placement = self._placement(len(ma.pages), die)
        with traced(self._tracer, "ftl", f"copyback-align[{name_a},{name_b}]",
                    pages=len(placement)):
            self.device.copyback([ma.pages, mb.pages], [ma.role, mb.role],
                                 placement)
        # the copyback preserves data, so the checkwords carry over
        self._register(VectorMeta(name_a, ma.n_bits, placement, "lsb",
                                  die=die, check=ma.check))
        self._register(VectorMeta(name_b, mb.n_bits, placement, "msb",
                                  die=die, check=mb.check))
        self._group_of[name_a] = self._group_of[name_b] = (name_a, name_b)
        return name_a

    def align_group(self, names: Sequence[str]) -> None:
        """Copyback-realign k same-encoding vectors onto shared wordlines
        (the generalized multi-level-encoding realignment): the group
        reprograms together on the home die :meth:`_realign_die` picks,
        taking shared-page roles in canonical order, in one device program
        (:meth:`FlashDevice.copyback`); old placements retire as in
        :meth:`align`.  MLC pairs take :meth:`align`."""
        metas = [self.vectors[n] for n in names]
        enc = metas[0].encoding
        assert all(m.encoding == enc for m in metas), \
            f"cannot co-locate mixed encodings: {[m.encoding for m in metas]}"
        assert len({len(m.pages) for m in metas}) == 1, \
            "aligned operands must match in size"
        if enc == tlc.MLC and len(names) == 2:
            self.align(names[0], names[1])
            return
        # Under fault injection a factory-reference readout here would copy
        # corrupted bits into the new placement AND recompute matching
        # checkwords — silent, undetectable data loss.  With the reliability
        # layer active, each vector reads back through the checked/retried
        # path instead.
        mgr = getattr(self._session, "reliability", None) \
            if self._session is not None else None
        die = self._realign_die(metas)
        with traced(self._tracer, "ftl",
                    f"align-group[{','.join(names)}]", encoding=enc):
            if mgr is not None:
                bits = [jnp.asarray(mgr.read_vector_checked(m)) for m in metas]
                self.write_group_aligned(list(names), bits, die=die,
                                         encoding=enc)
                return
            for n in names:
                self._invalidate(n)
            placement = self._placement(len(metas[0].pages), die)
            self.device.copyback([m.pages for m in metas],
                                 [m.role for m in metas], placement, enc)
        for m, role in zip(metas, ROLES_OF[enc]):
            self._register(VectorMeta(m.name, m.n_bits, placement, role,
                                      die=die, encoding=enc,
                                      check=m.check))
            self._group_of[m.name] = tuple(names)

    # -- executor lowering helpers --------------------------------------------
    def group_for_sense(self, names: List[str]) -> Tuple[List[Tuple[str, ...]], "str | None"]:
        """Group same-encoding operand names for shared-wordline senses.

        Already-co-located partners group first (no realignment cost); the
        rest group greedily up to the encoding's wordline capacity (each
        group costs one copyback realignment, the paper's non-aligned path).
        A leftover singleton is read out as its own partial.
        """
        metas = [self.vectors[n] for n in names]
        enc = metas[0].encoding
        assert all(m.encoding == enc for m in metas), \
            "sense groups must share one encoding (bucket upstream)"
        cap = PAGES_PER_WL[enc]
        used: set = set()
        groups: List[Tuple[str, ...]] = []
        rest: List[str] = []
        for i, n in enumerate(names):
            if i in used:
                continue
            used.add(i)
            idx = [i]
            for p in self._group_of.get(n, ()):
                if p == n or len(idx) >= cap:
                    continue
                j = next((k for k in range(len(names))
                          if k not in used and names[k] == p), None)
                if j is not None:
                    idx.append(j)
                    used.add(j)
            if len(idx) > 1:
                groups.append(tuple(names[k] for k in idx))
            else:
                rest.append(n)
        while len(rest) >= 2:
            take, rest = rest[:cap], rest[cap:]
            groups.append(tuple(take))
        return groups, (rest[0] if rest else None)

    def pair_for_sense(self, names: List[str]) -> Tuple[List[Tuple[str, str]], "str | None"]:
        """MLC-era alias of :meth:`group_for_sense` (groups are pairs)."""
        groups, leftover = self.group_for_sense(names)
        return [tuple(g) for g in groups], leftover

    def ensure_aligned(self, name_a: str, name_b: str) -> None:
        """Copyback-realign A,B unless they already share wordlines."""
        if self.partner_of(name_a) != name_b:
            self.align(name_a, name_b)

    def ensure_colocated(self, names: Sequence[str]) -> None:
        """Copyback-realign a group unless its (distinct) members already
        share wordlines.  Duplicate operand names need no realignment: the
        encoded plan just reads the shared role twice."""
        distinct = list(dict.fromkeys(names))
        if len(distinct) == 1:
            return                     # one vector: its role reads in place
        group = self._group_of.get(distinct[0], ())
        pages = self.vectors[distinct[0]].pages
        if all(n in group for n in distinct) and \
                all(self.vectors[n].pages == pages for n in distinct):
            return
        self.align_group(distinct)

    def ensure_not_ready(self, name: str, *, backend=None) -> VectorMeta:
        """Placement for an in-flash NOT: the operand must sit in the MSB page
        over a zero LSB page (paper Table 1).  Vectors stored any other way
        are copyback-rewritten once into a NOT-ready placement (cached under
        a derived name) — the same realignment cost model as scattered
        operand pairs.  Returns the meta whose pages to sense.
        """
        from repro.kernels import ops as kops

        meta = self.vectors[name]
        assert meta.encoding == tlc.MLC, \
            "encoded wordlines run NOT as a direct inverse role read"
        if meta.role == "msb" and meta.zero_co_page and not self.group_of(name):
            return meta
        copy = self.derived_not_name(name)
        if copy not in self.vectors:
            with traced(self._tracer, "ftl", f"not-ready-copy[{name}]",
                        pages=len(meta.pages)):
                packed = self.device.page_read_batch(meta.pages, meta.role,
                                                     backend=backend)
                self.device.dma_to_controller_batch(meta.pages)
                bits = kops.unpack_bits(
                    packed.reshape(1, -1))[0][: meta.n_bits]
                # the derived placement stays on the source vector's home die
                self.write_scattered(copy, bits, role="msb", die=meta.die)
        return self.vectors[copy]

    # -- compute (deprecation shims over the session layer) -------------------
    def compute(self, op: str, name_a: str, name_b: str | None = None,
                to_host: bool = True) -> jnp.ndarray:
        """In-flash `op` over registered vectors -> packed result vector.

        Forwards to :class:`repro.api.ComputeSession`; prefer building
        expressions on session handles directly.
        """
        sess = self.session
        if name_b is None:
            assert op == "not", f"op {op!r} needs two operands"
            expr = ~sess.vector(name_a)
        else:
            expr = sess.vector(name_a)._binary(op, sess.vector(name_b))
        # Historical contract: truncated to whole words of the vector length
        # (materialize returns page-padded words with the tail masked).
        return sess.materialize(expr, to_host=to_host)[: expr.n_bits // 32]

    def mcflash_compute(self, op: str, name_a: str, name_b: str,
                        to_host: bool = True) -> jnp.ndarray:
        """Deprecated alias of :meth:`compute` (kept for existing callers)."""
        return self.compute(op, name_a, name_b, to_host=to_host)

    def mcflash_chain(self, op: str, pair_names: List[Tuple[str, str]],
                      to_host: bool = True) -> jnp.ndarray:
        """k-operand chain (op in and/or/xor): forwards to the session layer,
        which senses each aligned pair in-flash and fuses all partials into a
        single controller-side ``bitwise_reduce``."""
        sess = self.session
        expr = sess.chain(op, [n for pair in pair_names for n in pair])
        return sess.materialize(expr, to_host=to_host)[: expr.n_bits // 32]
