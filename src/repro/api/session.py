"""ComputeSession: the one public way to run MCFlash bulk bitwise compute.

A session owns (or wraps) a simulated flash device + FTL, registers named
bit-vectors as :class:`BitVector` handles, records bitwise expressions into a
lazy op DAG, and on :meth:`materialize`:

1. canonicalises the DAG (:func:`repro.api.graph.simplify`) — associative
   chains fuse into one k-ary node, ``~(a & b)`` becomes an inverse-read NAND;
2. hands the canonical DAG to the compiled :class:`~repro.api.executor.Executor`,
   which lowers it into a static ``ExecPlan`` (whole-graph senses grouped by
   read plan, homogeneous chains fused into one sense→reduce megakernel) and
   replays a cached jitted executable when the DAG shape was seen before;
3. threads the unified timing/energy :class:`~repro.api.ledger.Ledger`
   through every command via batched accounting entries.

Backends are pluggable (:class:`SimBackend` oracle / :class:`PallasBackend`
kernels) and bit-exact against each other.
"""
from __future__ import annotations

import dataclasses
import os
from collections import OrderedDict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.api.backends import Backend, get_backend
from repro.api.executor import OPERAND_TILE_BYTES, ExecPlan, Executor
from repro.api.graph import ASSOCIATIVE, BitVector, Leaf, simplify
from repro.api.hostio import DrainHandle, HostDrainQueue
from repro.api.plan_cache import PlanCache
from repro.core import encoding, tlc
from repro.core import mcflash as _mcflash
from repro.core.mcflash import ReadPlan
from repro.core.vth_model import ChipModel
from repro.kernels import ops as kops
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer, traced
from repro.reliability import FaultConfig, FaultModel
from repro.verify import PlanContext, PlanVerifier

__all__ = ["ComputeSession", "run_op"]

#: session-owned Counter metrics (the former ad-hoc integer attributes) —
#: each stays readable as a plain-int session attribute for back compat
_SESSION_COUNTERS = (
    ("fused_reduce_calls", "combine steps (incl. fused megakernels)"),
    ("in_flash_senses", "logical senses (one per pair / NOT)"),
    ("sense_items", "senses + leaf reads (grouped per plan)"),
    ("sense_batches", "batched per-die sense kernel dispatches"),
    ("sense_waves", "topology-schedule waves dispatched"),
    ("megakernel_calls", "fused sense->reduce(->popcount) passes"),
    ("tiled_megakernel_splits", "fused chains split for VMEM budget"),
    ("placed_unit_dispatches", "wave units dispatched on pinned shard devices"),
    ("arena_gather_programs", "unplaced batch arena-gather programs dispatched"),
    ("arena_gathered_stacks", "operand stacks gathered by those programs"),
    ("host_drain_submits", "async controller->host transfers enqueued"),
    ("host_drain_blocks", "drain-queue backpressure stalls (queue full)"),
    ("coalesced_sense_groups", "batch sense groups shared by >1 request"),
    ("waves_shared", "schedule waves carrying work of >1 request"),
    ("tail_mask_evictions", "tail-mask cache entries evicted (LRU bound)"),
    ("realignments", "copyback realignments while lowering batches"),
    ("copyback_wordlines", "wordlines those copybacks programmed"),
    ("arena_rows_reclaimed", "arena rows of retired wordlines freed"),
)

#: per-shape tail-mask cache bound — big enough for steady-state serving
#: mixes (a handful of distinct (n_bits, words) shapes), small enough that
#: adversarially varied n_bits traffic cannot grow the session unboundedly
TAIL_MASK_CACHE_CAP = 32


class ComputeSession:
    """Session-level MCFlash compute over named bit-vector handles."""

    def __init__(self, device=None, *, backend: "str | Backend" = "pallas",
                 ftl=None, chip=None, config=None, timing=None, energy=None,
                 seed: int = 0, vmem_budget_bytes: "int | None" = None,
                 encoding: str = tlc.MLC, trace: "bool | Tracer" = False,
                 verify: "str | None" = None, faults=None, recovery=None,
                 overlap: "bool | str | None" = None,
                 drain_depth: "int | None" = None):
        # Deferred imports keep repro.api import-light and cycle-free.
        from repro.flash.device import FlashDevice
        from repro.flash.ftl import FTL

        if encoding not in tlc.ENCODINGS:
            raise ValueError(f"unknown encoding {encoding!r}; "
                             f"pick one of {tlc.ENCODINGS}")
        #: row encoding this session writes (and senses) vectors under —
        #: vectors remember their own encoding, so sessions with different
        #: encodings can share one device
        self.encoding = encoding

        build_kwargs = {"chip": chip, "config": config, "timing": timing,
                        "energy": energy}
        if (ftl is not None or device is not None) and (
                any(v is not None for v in build_kwargs.values()) or seed != 0):
            given = [k for k, v in build_kwargs.items() if v is not None]
            if seed != 0:
                given.append("seed")
            raise ValueError(
                f"{given} only apply when the session constructs its own "
                "device; configure the FlashDevice you pass in instead")
        if ftl is not None:
            if device is not None and device is not ftl.device:
                raise ValueError("device and ftl disagree; pass one or the other")
            self.ftl = ftl
            self.device = ftl.device
        else:
            self.device = device or FlashDevice(seed=seed, **build_kwargs)
            # Reuse the device's existing FTL (a fresh one would restart the
            # wordline allocator and overwrite already-programmed pages).
            self.ftl = getattr(self.device, "ftl", None) or FTL(self.device)
        # Make this session the FTL's session so the compute shims
        # (FTL.mcflash_compute/chain) run on this backend, not a hidden
        # default-pallas one.  Latest session wins, consistent with
        # set_default_backend above.
        self.ftl._session = self
        self.backend: Backend = get_backend(backend)
        # Device-internal reads (copyback realignment) follow this session's
        # backend choice too — a sim session never touches Pallas.
        self.device.set_default_backend(self.backend)
        self.plans: PlanCache = self.device.plans     # shared per-chip plan cache
        self.ledger = self.device.ledger
        #: inter-resource ledger timing mode: ``overlap=None`` leaves the
        #: (device-shared) ledger's mode alone; ``True`` / ``"overlap"``
        #: books host-link/channel steps concurrently with later waves' die
        #: work (double-buffered pipelining, ``drain_depth`` deep),
        #: ``"sync"`` is the non-overlapped baseline (every step waits for
        #: everything booked before it), ``False`` / ``"independent"``
        #: restores the historical free-running timelines.  Latest session
        #: on a shared device wins, consistent with set_default_backend.
        if overlap is not None or drain_depth is not None:
            if overlap is None:
                mode = self.ledger.mode
            elif overlap is True or overlap == "overlap":
                mode = "overlap"
            elif overlap == "sync":
                mode = "sync"
            elif overlap is False or overlap == "independent":
                mode = "independent"
            else:
                raise ValueError(
                    f"overlap must be True/False, 'overlap', 'sync', or "
                    f"'independent', got {overlap!r}")
            self.ledger.set_mode(mode, drain_depth=drain_depth)
        self.executor = Executor(self, vmem_budget_bytes=vmem_budget_bytes)
        #: static ExecPlan verifier (``"off"`` | ``"on"`` | ``"paranoid"``),
        #: run at lowering time and memoized by plan signature; default from
        #: ``$REPRO_VERIFY`` (falling back to ``"on"`` — lowering is host-side
        #: and the check is amortized to ~zero by the signature memo)
        self.verifier = PlanVerifier(
            verify if verify is not None
            else os.environ.get("REPRO_VERIFY", "on"))
        #: typed metrics registry replacing the former ad-hoc integer
        #: attributes — each is still readable as a plain-int attribute
        #: (``sess.sense_batches`` etc.) via the properties below
        self.metrics = MetricsRegistry()
        for name, desc in _SESSION_COUNTERS:
            self.metrics.counter(name, desc)
        self.metrics.gauge("max_concurrent_dies",
                           "widest per-wave die concurrency seen")
        self.metrics.histogram("fused_operands", "operands per megakernel")
        #: bounded async controller->host drain queue backing
        #: :meth:`materialize_async` — transfers stream while the next
        #: expression senses; depth follows the ledger's ``drain_depth``
        self.host_queue = HostDrainQueue(
            depth=self.ledger.drain_depth,
            on_submit=self._on_drain_submit,
            on_block=lambda: self.metrics.counter("host_drain_blocks").add(1))
        #: device-timeline tracer (``trace=True`` builds one; pass a
        #: :class:`repro.obs.Tracer` to share/configure it).  Attaches to the
        #: device ledger, so every command this session triggers — senses,
        #: programs, realignment copybacks, DMA — lands on its virtual lanes.
        #: Latest traced session on a shared device wins, consistent with
        #: set_default_backend above.
        self.trace: "Tracer | None" = None
        if trace:
            self.trace = trace if isinstance(trace, Tracer) else Tracer()
            self.ledger.tracer = self.trace
        self._tail_masks: "OrderedDict[Tuple[int, int], jnp.ndarray]" = \
            OrderedDict()
        #: wear/retention fault injection + recovery (reliability layer):
        #: ``faults=`` (or ``$REPRO_FAULTS``) installs the seeded
        #: :class:`FaultModel` on the device — any spec
        #: :meth:`FaultConfig.parse` accepts.  ``recovery=`` controls the
        #: :class:`~repro.reliability.recovery.ReliabilityManager`:
        #: ``None`` auto-enables it when faults are installed (on this
        #: session or a sibling sharing the device), ``"off"`` disables
        #: detection/recovery even under injected faults (the
        #: negative-control mode), and a dict / :class:`RetryPolicy` /
        #: ``True`` enables it with that policy regardless of faults.
        fault_cfg = FaultConfig.parse(
            faults if faults is not None else os.environ.get("REPRO_FAULTS"))
        if fault_cfg is not None:
            self.device.faults = FaultModel(fault_cfg)
        self.reliability = None
        if recovery != "off" and (recovery is not None
                                  or self.device.faults is not None):
            from repro.reliability.recovery import ReliabilityManager
            self.reliability = ReliabilityManager(
                self, None if recovery in (None, True, "on") else recovery)

    # -- registration --------------------------------------------------------
    def write(self, name: str, bits: jnp.ndarray, role: str = "lsb",
              die: "int | None" = None) -> BitVector:
        """Store a single named bit-vector (scattered; realigned on demand).
        ``die`` pins the home die; default round-robins across dies."""
        self.ftl.write_scattered(name, jnp.asarray(bits), role=role, die=die,
                                 encoding=self.encoding)
        return self.vector(name)

    def write_pair(self, name_a: str, bits_a: jnp.ndarray,
                   name_b: str, bits_b: jnp.ndarray,
                   die: "int | None" = None) -> Tuple[BitVector, BitVector]:
        """Store two operands co-located on shared wordlines (the fast path).
        ``die`` pins the pair's home die; default round-robins across dies."""
        self.ftl.write_pair_aligned(name_a, jnp.asarray(bits_a),
                                    name_b, jnp.asarray(bits_b), die=die,
                                    encoding=self.encoding)
        return self.vector(name_a), self.vector(name_b)

    def write_triple(self, name_a: str, bits_a: jnp.ndarray,
                     name_b: str, bits_b: jnp.ndarray,
                     name_c: str, bits_c: jnp.ndarray,
                     die: "int | None" = None) -> Tuple[BitVector, BitVector,
                                                        BitVector]:
        """Store three operands co-located on one TLC wordline's LSB/CSB/MSB
        shared pages (§7) — the placement that gives 3-operand AND/OR their
        single-sense-group fast path.  TLC sessions only."""
        if tlc.PAGES_PER_WL[self.encoding] < 3:
            raise ValueError(
                f"write_triple needs a 3-page encoding, not {self.encoding!r}")
        self.ftl.write_group_aligned(
            [name_a, name_b, name_c],
            [jnp.asarray(bits_a), jnp.asarray(bits_b), jnp.asarray(bits_c)],
            die=die, encoding=self.encoding)
        return (self.vector(name_a), self.vector(name_b),
                self.vector(name_c))

    def vector(self, name: str) -> BitVector:
        """Handle to an already-registered vector."""
        meta = self.ftl.vectors[name]
        return BitVector(self, Leaf(name), meta.n_bits)

    def __getitem__(self, name: str) -> BitVector:
        return self.vector(name)

    def chain(self, op: str, operands: "Iterable[BitVector | str]") -> BitVector:
        """Fold handles (or registered names) into one lazy k-ary op node.

        ``op`` must be associative ('and' | 'or' | 'xor'); the result
        materializes as per-pair in-flash senses plus one fused combine.
        """
        if op not in ASSOCIATIVE:
            raise ValueError(f"chains are associative ops only, got {op!r}")
        vecs = [self.vector(v) if isinstance(v, str) else v for v in operands]
        if not vecs:
            raise ValueError("empty operand chain")
        expr = vecs[0]
        for v in vecs[1:]:
            expr = expr._binary(op, v)
        return expr

    # -- planning ------------------------------------------------------------
    @property
    def chip(self) -> ChipModel:
        return self.device.chip

    def plan(self, op: str, use_inverse_read: bool = True) -> ReadPlan:
        """Cached Table-1 read plan for this session's chip model."""
        return self.plans.get(op, self.chip, use_inverse_read)

    def describe_plans(self, ops: Iterable[str] = encoding.ALL_OPS) -> List[str]:
        return [self.plan(op).describe() for op in ops]

    # -- execution -----------------------------------------------------------
    def plan_context(self) -> PlanContext:
        """Device/session geometry the static plan verifier checks against."""
        return PlanContext(
            die_of_plane=self.device.die_of_plane,
            page_words=self.ftl.cfg.page_bits // 32,
            vmem_budget_bytes=self.executor.vmem_budget_bytes,
            max_fused_operands=self.executor.max_fused_operands,
            operand_tile_bytes=OPERAND_TILE_BYTES)

    def verify_lowered_plan(self, plan: ExecPlan,
                            signature: "tuple | None" = None) -> None:
        """Hook the executor calls on every freshly lowered plan; raises
        :class:`repro.verify.PlanInvariantError` before any dispatch when a
        schedule invariant is violated.  No-op with ``verify="off"``."""
        if self.verifier.enabled:
            self.verifier.verify(plan, self.plan_context(), signature)

    def lower(self, expr: BitVector) -> ExecPlan:
        """Canonicalize + lower ``expr`` to its static :class:`ExecPlan`
        without dispatching (the plan is still verified) — the entry point
        for plan-corpus checks and schedule inspection."""
        return self.executor.lower(simplify(expr.node))

    def materialize(self, expr: BitVector, *, unpacked: bool = False,
                    to_host: bool = True) -> jnp.ndarray:
        """Compile + execute the expression DAG; returns the result vector.

        Packed (uint32 words) by default — page-padded, with any bits beyond
        ``expr.n_bits`` masked to zero; ``unpacked=True`` returns per-cell
        uint8 bits trimmed to exactly ``expr.n_bits``.  ``to_host`` accounts
        the final controller->host transfer in the ledger.
        """
        node = simplify(expr.node)
        packed = self.executor.run(node, expr.n_bits)
        if self.reliability is not None:
            packed = self.reliability.verify_and_recover(node, expr.n_bits,
                                                         packed)
        if to_host:
            self.device.ext_to_host(int(packed.shape[-1]) * 4)
        if unpacked:
            return kops.unpack_bits(packed.reshape(1, -1))[0][: expr.n_bits]
        return packed

    def _on_drain_submit(self, n_bytes: int) -> None:
        self.metrics.counter("host_drain_submits").add(1)
        # booked at submit time: in the ledger's "overlap" mode the host
        # step starts at the channel frontier, concurrent with the NEXT
        # expression's die waves — exactly the pipelined shape the queue
        # realizes on the wall clock
        self.device.ext_to_host(n_bytes)

    def materialize_async(self, expr: BitVector) -> DrainHandle:
        """Compile + execute like :meth:`materialize`, but stream the packed
        result to the host *asynchronously* through the bounded drain queue:
        returns a :class:`~repro.api.hostio.DrainHandle` immediately so the
        caller can dispatch the next expression while this result's
        controller->host transfer overlaps it.  ``handle.result()`` (or
        :meth:`drain`) blocks for the bytes.  Submitting past the queue
        depth blocks on the oldest in-flight transfer (double-buffer
        backpressure)."""
        node = simplify(expr.node)
        packed = self.executor.run(node, expr.n_bits)
        if self.reliability is not None:
            packed = self.reliability.verify_and_recover(node, expr.n_bits,
                                                         packed)
        return self.host_queue.submit(packed, int(packed.shape[-1]) * 4)

    def drain(self) -> List[np.ndarray]:
        """Resolve every in-flight :meth:`materialize_async` transfer;
        returns the packed host arrays in submit order."""
        return [h.result() for h in self.host_queue.drain()]

    # -- cross-request batch execution (the serving engine's dispatch) -------
    def lower_batch(self, exprs: Sequence[BitVector],
                    rids: "Optional[Sequence[int]]" = None) -> ExecPlan:
        """Lower a batch of expressions through ONE shared pass without
        dispatching: identical sub-DAGs dedupe and same-(ReadPlan, die)
        senses coalesce into shared groups/waves.  ``rids`` tags the plan's
        sense items with owning request ids (trace/metrics attribution)."""
        return self.executor.lower_many(
            [simplify(e.node) for e in exprs],
            list(rids) if rids is not None else None)

    def _run_batch(self, exprs: Sequence[BitVector],
                   popcounts: Tuple[bool, ...],
                   rids: "Optional[Sequence[int]]" = None) -> List[jnp.ndarray]:
        """Shared batch dispatch: one coalesced executor run; under the
        reliability layer every root materializes as words first (the fused
        on-device popcount would hide bit errors), is verified/recovered per
        root, and counts fold host-side."""
        with traced(None, "lower"):
            nodes = [simplify(e.node) for e in exprs]
        n_bits = [e.n_bits for e in exprs]
        rid_list = list(rids) if rids is not None else None
        if self.reliability is not None:
            outs = self.executor.run_batch(nodes, n_bits,
                                           (False,) * len(nodes),
                                           rids=rid_list)
            fixed: List[jnp.ndarray] = []
            for node, nb, pc, packed in zip(nodes, n_bits, popcounts, outs):
                packed = self.reliability.verify_and_recover(node, nb, packed)
                fixed.append(self.backend.popcount(packed.reshape(1, -1))[0]
                             if pc else packed)
            return fixed
        return self.executor.run_batch(nodes, n_bits, popcounts,
                                       rids=rid_list)

    def materialize_batch(self, exprs: Sequence[BitVector], *,
                          popcount: "Optional[Sequence[bool]]" = None,
                          rids: "Optional[Sequence[int]]" = None,
                          to_host: bool = True) -> List:
        """Materialize N expressions through ONE coalesced lowering+dispatch
        (cross-request wave coalescing): returns one packed word array — or
        ``int`` count where ``popcount[i]`` — per expression, in order.
        Bit-exact vs. materializing each expression separately."""
        popcounts = (tuple(bool(p) for p in popcount) if popcount is not None
                     else (False,) * len(exprs))
        assert len(popcounts) == len(exprs), (len(popcounts), len(exprs))
        outs = self._run_batch(exprs, popcounts, rids)
        results: List = []
        for out, pc in zip(outs, popcounts):
            if to_host:
                self.device.ext_to_host(4 if pc else int(out.shape[-1]) * 4)
            results.append(int(out) if pc else out)
        return results

    def materialize_batch_async(self, exprs: Sequence[BitVector], *,
                                popcount: "Optional[Sequence[bool]]" = None,
                                rids: "Optional[Sequence[int]]" = None
                                ) -> List[DrainHandle]:
        """Batch variant of :meth:`materialize_async`: one coalesced dispatch,
        then every root's result streams host-ward through the bounded drain
        queue — one rid-tagged :class:`DrainHandle` per expression, in order.
        The queue bound applies per submission, so a batch wider than the
        drain depth resolves its oldest transfers inline (backpressure)."""
        popcounts = (tuple(bool(p) for p in popcount) if popcount is not None
                     else (False,) * len(exprs))
        assert len(popcounts) == len(exprs), (len(popcounts), len(exprs))
        outs = self._run_batch(exprs, popcounts, rids)
        rid_list = list(rids) if rids is not None else [None] * len(exprs)
        with traced(None, "drain.submit"):
            return [self.host_queue.submit(out, rid=rid)
                    for out, rid in zip(outs, rid_list)]

    def tail_mask(self, n_bits: int, total_words: int) -> jnp.ndarray:
        """Packed (total_words,) mask zeroing page-padding bits past
        ``n_bits`` (inverse-read ops turn padded zeros into ones, which would
        corrupt popcounts and packed consumers).  Cached per shape under a
        small LRU bound (:data:`TAIL_MASK_CACHE_CAP`) — many-request traffic
        with varied ``n_bits`` must not grow the session without bound."""
        total = total_words * 32
        key = (min(n_bits, total), total)
        mask = self._tail_masks.get(key)
        if mask is None:
            if n_bits >= total:
                mask = jnp.full((total_words,), 0xFFFFFFFF, jnp.uint32)
            else:
                bits = np.zeros(total, np.uint8)
                bits[:n_bits] = 1
                mask = kops.pack_bits(jnp.asarray(bits).reshape(1, -1))[0]
            self._tail_masks[key] = mask
            while len(self._tail_masks) > TAIL_MASK_CACHE_CAP:
                self._tail_masks.popitem(last=False)
                self.metrics.counter("tail_mask_evictions").add(1)
        else:
            self._tail_masks.move_to_end(key)
        return mask

    def popcount(self, expr: BitVector, *, to_host: bool = True) -> int:
        """Materialize + bit-count without leaving the device: the count
        fuses into the root megakernel when the plan allows, and only the
        4-byte count crosses to the host (``to_host`` accounts exactly
        that — not a page transfer)."""
        node = simplify(expr.node)
        if self.reliability is not None:
            # words must exist to checkword-verify; the count then folds
            # host-side (the fused on-device popcount would hide bit errors)
            packed = self.executor.run(node, expr.n_bits)
            packed = self.reliability.verify_and_recover(node, expr.n_bits,
                                                         packed)
            count = self.backend.popcount(packed.reshape(1, -1))[0]
        else:
            count = self.executor.run_popcount(node, expr.n_bits)
        if to_host:
            self.device.ext_to_host(4)
        return int(count)

    def stats(self) -> dict:
        return {
            "backend": self.backend.name,
            "encoding": self.encoding,
            "arena_rows_by_encoding": self.device.arena.used_by_encoding(),
            "plan_cache": self.plans.stats(),
            "executor": self.executor.stats(),
            "fused_reduce_calls": self.fused_reduce_calls,
            "in_flash_senses": self.in_flash_senses,
            "sense_items": self.sense_items,
            "sense_batches": self.sense_batches,
            "sense_waves": self.sense_waves,
            "max_concurrent_dies": self.max_concurrent_dies,
            "megakernel_calls": self.megakernel_calls,
            "tiled_megakernel_splits": self.tiled_megakernel_splits,
            "placed_unit_dispatches": self.placed_unit_dispatches,
            "arena_gather_programs": self.arena_gather_programs,
            "arena_gathered_stacks": self.arena_gathered_stacks,
            "realignments": self.realignments,
            "copyback_wordlines": self.copyback_wordlines,
            "arena_rows_reclaimed": self.arena_rows_reclaimed,
            "host_drain": {"submits": self.host_drain_submits,
                           "blocks": self.host_drain_blocks,
                           "pending": len(self.host_queue),
                           "depth": self.host_queue.depth},
            "coalesced_sense_groups": self.coalesced_sense_groups,
            "waves_shared": self.waves_shared,
            "tail_mask_cache": {"size": len(self._tail_masks),
                                "cap": TAIL_MASK_CACHE_CAP,
                                "evictions": self.tail_mask_evictions},
            "plans_verified": self.verifier.plans_verified,
            "verify_cache_hits": self.verifier.cache_hits,
            "verify": {"mode": self.verifier.mode,
                       "time_us": self.verifier.time_us},
            "arena_shards": self.device.arena.n_shards,
            "ledger": self.ledger.summary(),
            "faults": (dataclasses.asdict(self.device.faults.cfg)
                       if self.device.faults is not None else None),
            "reliability": (self.reliability.stats()
                            if self.reliability is not None else None),
        }

    def reset_stats(self, include_ledger: bool = True) -> None:
        """Zero this session's metrics (and, by default, the shared ledger)
        so repeated-materialize benchmark loops measure per-iteration counts
        instead of rebuilding sessions.  Device-shared cache counters
        (plan/executable hits+misses) are left alone — clear those caches
        explicitly if a cold-cache measurement is wanted.  An attached
        tracer keeps its spans (``sess.trace.clear()`` drops them)."""
        self.metrics.reset()
        self.verifier.reset()
        self.host_queue.reset()
        if self.reliability is not None:
            self.reliability.reset()
        if include_ledger:
            self.ledger.reset()


def _metric_value_property(name: str) -> property:
    def get(self) -> int:
        return int(self.metrics[name].value)
    get.__name__ = name
    return property(get)


# back-compat plain-int views of the registry-backed session counters
# (``sess.sense_batches`` etc. — the pre-registry attribute surface)
for _name, _ in _SESSION_COUNTERS:
    setattr(ComputeSession, _name, _metric_value_property(_name))
setattr(ComputeSession, "max_concurrent_dies",
        _metric_value_property("max_concurrent_dies"))


# ---------------------------------------------------------------------------
# Module-level one-shot path (the target of the `mcflash_op` shim): plan via a
# process-wide cache, execute with the reference sensing semantics.

_GLOBAL_PLANS = PlanCache()


def run_op(op: str, vth: jnp.ndarray, chip: ChipModel,
           use_inverse_read: bool = True,
           backend: "str | Backend | None" = None) -> jnp.ndarray:
    """One-shot MCFlash op on a raw Vth array through the session-layer
    plan cache.  With ``backend=None`` returns per-cell bits (the historical
    ``mcflash_op`` contract, any input shape); with a backend, ``vth`` must be
    (R, C) with C a multiple of 4096 and the result is packed uint32.
    """
    plan = _GLOBAL_PLANS.get(op, chip, use_inverse_read)
    if backend is None:
        return _mcflash.execute_plan(plan, vth)
    return get_backend(backend).sense(vth, plan)
