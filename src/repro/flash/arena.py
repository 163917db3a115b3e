"""Device-resident Vth storage: per-die shards of (slots, page_bits) buffers.

The functional device used to hold per-wordline Vth tensors in a Python
dict, so every batched sense paid a host-side ``jnp.stack`` over N separate
device arrays.  :class:`VthArena` replaced that with a single device-resident
2-D buffer plus a free-slot allocator: programming a wordline scatters one
row, and reading a batch of wordlines gathers their row indices.

:class:`ShardedVthArena` shards that storage per die — one lazily-created
:class:`VthArena` per die that holds data, addressed by ``(die, slot)``
refs — and shards can optionally be pinned to distinct JAX devices
(``devices=`` / ``devices="auto"``) so multi-die dispatch maps onto real
accelerator parallelism.  With unmapped shards, every operand stack a
compiled batch needs (its per-die sense groups and cross-die fused steps)
comes out of ONE jitted gather program (:meth:`ShardedVthArena.gather_many`)
fed by one index upload; mapped shards gather on each shard's own device.

Each shard grows geometrically (rows double, never shrink) so steady-state
programs/reads never reallocate; freed slots are recycled LIFO per shard.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tlc import ENCODINGS

__all__ = ["VthArena", "ShardedVthArena", "SlotRef"]

#: address of one arena row: (die, slot-within-die-shard)
SlotRef = Tuple[int, int]

#: static structure of one stack in :func:`_gather_parts`: rows taken from
#: each of its parts (one part per die shard it touches) and whether the
#: concatenated parts are then reordered into request order
StackLayout = Tuple[Tuple[int, ...], bool]


@jax.jit
def _scatter_rows(buf: jnp.ndarray, idx: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    return buf.at[idx].set(rows)


@functools.partial(jax.jit, static_argnames=("layout",))
def _gather_parts(bufs: Tuple[jnp.ndarray, ...], idx: jnp.ndarray,
                  layout: Tuple[StackLayout, ...]) -> Tuple[jnp.ndarray, ...]:
    """One ``(n, page_bits)`` stack per ``layout`` entry, in ONE program.

    Stack parts consume ``bufs`` one buffer each, in order, and the next
    ``rows`` entries of ``idx``; a permuted stack then takes its
    concatenated parts in the order of its next ``n`` entries.  The row
    indices come from the arena's own allocator, so they are in bounds and
    the takes clip (a plain gather) instead of masking a NaN fill over the
    whole output.  The compile key is the buffer shapes and ``layout``
    alone: the same plan on other dies of equal capacity reuses it.
    """
    stacks, b, off = [], 0, 0
    for part_rows, permuted in layout:
        parts = []
        for n in part_rows:
            parts.append(jnp.take(bufs[b], idx[off:off + n], axis=0,
                                  mode="clip"))
            b += 1
            off += n
        stack = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
        if permuted:
            n = stack.shape[0]
            stack = jnp.take(stack, idx[off:off + n], axis=0, mode="clip")
            off += n
        stacks.append(stack)
    return tuple(stacks)


class VthArena:
    """Preallocated (slots, page_bits) float32 Vth storage with a free list.

    ``device`` optionally pins the buffer (and every growth extension) to one
    JAX device — the single-shard building block of :class:`ShardedVthArena`.
    """

    def __init__(self, page_bits: int, init_slots: int = 16,
                 dtype=jnp.float32, device=None):
        self.page_bits = int(page_bits)
        self.dtype = dtype
        self.device = device
        self._buf = self._place(
            jnp.zeros((max(int(init_slots), 1), self.page_bits), dtype))
        self._free: List[int] = list(range(self._buf.shape[0] - 1, -1, -1))
        self.grows = 0                   # observable reallocation count
        self._row_encoding: Dict[int, str] = {}   # slot -> row layout

    def _place(self, x: jnp.ndarray) -> jnp.ndarray:
        return jax.device_put(x, self.device) if self.device is not None else x

    # -- allocation -----------------------------------------------------------
    @property
    def capacity(self) -> int:
        return int(self._buf.shape[0])

    @property
    def used(self) -> int:
        return self.capacity - len(self._free)

    def _grow(self, min_slots: int) -> None:
        new_cap = max(self.capacity * 2, min_slots)
        extra = self._place(
            jnp.zeros((new_cap - self.capacity, self.page_bits), self.dtype))
        old_cap = self.capacity
        self._buf = jnp.concatenate([self._buf, extra], axis=0)
        self._free.extend(range(new_cap - 1, old_cap - 1, -1))
        self.grows += 1

    def alloc(self, n: int = 1, encoding: str = "mlc") -> List[int]:
        """Reserve ``n`` row slots (growing the buffer if exhausted), tagged
        with the row layout's encoding."""
        assert encoding in ENCODINGS, encoding
        if len(self._free) < n:
            self._grow(self.capacity + n - len(self._free))
        slots = [self._free.pop() for _ in range(n)]
        for s in slots:
            self._row_encoding[s] = encoding
        return slots

    def free(self, slots: Sequence[int]) -> None:
        """Return slots to the free list: the next allocations on this shard
        reuse them (LIFO) before the buffer grows.  The device frees a row
        once no live placement uses it, as the next batch's lowering begins
        (:meth:`repro.flash.device.FlashDevice.begin_batch`); erasing a
        block frees its rows at once."""
        for s in slots:
            self._row_encoding.pop(int(s), None)
        self._free.extend(int(s) for s in slots)

    def encoding_of(self, slot: int) -> str:
        """Row layout of an allocated slot."""
        return self._row_encoding[int(slot)]

    def retag(self, slot: int, encoding: str) -> None:
        """Update an allocated slot's row layout (wordline reprogram under a
        different encoding reuses its slot)."""
        assert encoding in ENCODINGS, encoding
        assert int(slot) in self._row_encoding, slot
        self._row_encoding[int(slot)] = encoding

    def used_by_encoding(self) -> Dict[str, int]:
        """Allocated-slot count per row layout."""
        out: Dict[str, int] = {}
        for enc in self._row_encoding.values():
            out[enc] = out.get(enc, 0) + 1
        return out

    # -- data movement --------------------------------------------------------
    @property
    def buf(self) -> jnp.ndarray:
        """The whole device-resident buffer (feed this to compiled executables)."""
        return self._buf

    def write(self, slots: Sequence[int], rows: jnp.ndarray) -> None:
        """Scatter row data into slots: (len(slots), page_bits) in ONE update."""
        rows = jnp.asarray(rows, self.dtype).reshape(len(slots), self.page_bits)
        self._buf = _scatter_rows(self._buf, jnp.asarray(slots, jnp.int32),
                                  self._place(rows))

    def install(self, buf: jnp.ndarray) -> None:
        """Take an updated copy of the buffer, of the same shape, from a
        program that wrote rows into it (a copyback realignment)."""
        assert buf.shape == self._buf.shape, (buf.shape, self._buf.shape)
        self._buf = buf

    def rows(self, slots: Sequence[int]) -> jnp.ndarray:
        """Row-index vector for a slot list (executable input)."""
        return jnp.asarray(list(slots), jnp.int32)

    def gather(self, slots: Sequence[int]) -> jnp.ndarray:
        """(len(slots), page_bits) copy of the requested rows — one eager
        take on this shard's device (the mapped-shard path; unmapped shards
        gather through :meth:`ShardedVthArena.gather_many`)."""
        return jnp.take(self._buf, self.rows(slots), axis=0)


class ShardedVthArena:
    """Per-die Vth shards addressed by ``(die, slot)`` refs.

    Shards are created lazily on first allocation for a die (a 128-die SSD
    config must not eagerly allocate 128 buffers), each an independent
    :class:`VthArena` with its own free list, so alloc/free/grow on one die
    never touches — or retraces against — another die's storage.

    ``devices`` maps shards onto JAX devices round-robin: pass an explicit
    sequence, or ``"auto"`` for ``jax.devices()``.  On a single-device host
    this is a no-op; on a TPU slice each die's senses gather locally.
    """

    def __init__(self, page_bits: int, n_dies: int = 1, init_slots: int = 16,
                 dtype=jnp.float32, devices=None):
        assert n_dies >= 1, n_dies
        self.page_bits = int(page_bits)
        self.n_dies = int(n_dies)
        self.init_slots = int(init_slots)
        self.dtype = dtype
        if devices == "auto":
            devices = jax.devices()
        self.devices = list(devices) if devices else None
        self._shards: Dict[int, VthArena] = {}

    # -- shards ---------------------------------------------------------------
    def shard(self, die: int) -> VthArena:
        """The (lazily-created) per-die shard backing ``die``."""
        assert 0 <= die < self.n_dies, (die, self.n_dies)
        arena = self._shards.get(die)
        if arena is None:
            dev = (self.devices[die % len(self.devices)]
                   if self.devices else None)
            arena = self._shards[die] = VthArena(
                self.page_bits, self.init_slots, self.dtype, device=dev)
        return arena

    @property
    def n_shards(self) -> int:
        return len(self._shards)

    @property
    def capacity(self) -> int:
        return sum(s.capacity for s in self._shards.values())

    @property
    def used(self) -> int:
        return sum(s.used for s in self._shards.values())

    @property
    def grows(self) -> int:
        return sum(s.grows for s in self._shards.values())

    def used_on(self, die: int) -> int:
        """Rows allocated on ``die``'s shard (0 where it has none)."""
        shard = self._shards.get(die)
        return shard.used if shard is not None else 0

    def fits(self, die: int, n: int) -> bool:
        """``n`` rows can be allocated on ``die`` without growing a buffer
        in use: its shard has ``n`` free rows, or it has no shard yet."""
        shard = self._shards.get(die)
        return shard is None or shard.capacity - shard.used >= n

    def shard_stats(self) -> Dict[int, dict]:
        return {die: {"capacity": s.capacity, "used": s.used, "grows": s.grows,
                      "encodings": s.used_by_encoding()}
                for die, s in sorted(self._shards.items())}

    def used_by_encoding(self) -> Dict[str, int]:
        """Allocated-row count per row layout across all shards."""
        out: Dict[str, int] = {}
        for s in self._shards.values():
            for enc, n in s.used_by_encoding().items():
                out[enc] = out.get(enc, 0) + n
        return out

    # -- allocation -----------------------------------------------------------
    def alloc(self, die: int, n: int = 1,
              encoding: str = "mlc") -> List[SlotRef]:
        """Reserve ``n`` row slots on ``die``'s shard (die-affinity alloc),
        tagged with the row layout's encoding."""
        return [(die, s) for s in self.shard(die).alloc(n, encoding)]

    def encoding_of(self, ref: SlotRef) -> str:
        """Row layout of an allocated ``(die, slot)`` ref."""
        die, slot = ref
        return self.shard(int(die)).encoding_of(slot)

    def retag(self, ref: SlotRef, encoding: str) -> None:
        """Update an allocated ``(die, slot)`` ref's row layout."""
        die, slot = ref
        self.shard(int(die)).retag(slot, encoding)

    def free(self, refs: Sequence[SlotRef]) -> None:
        """Return ``(die, slot)`` refs to their shards' free lists (see
        :meth:`VthArena.free`)."""
        for die, slots in self._by_die(refs).items():
            self.shard(die).free(slots)

    @staticmethod
    def _by_die(refs: Sequence[SlotRef]) -> Dict[int, List[int]]:
        out: Dict[int, List[int]] = {}
        for die, slot in refs:
            out.setdefault(int(die), []).append(int(slot))
        return out

    # -- data movement --------------------------------------------------------
    def write(self, refs: Sequence[SlotRef], rows: jnp.ndarray) -> None:
        """Scatter row data into refs — one update per touched shard."""
        refs = list(refs)
        rows = jnp.asarray(rows, self.dtype).reshape(len(refs), self.page_bits)
        by_die: Dict[int, List[int]] = {}     # die -> positions in `refs`
        for i, (die, _) in enumerate(refs):
            by_die.setdefault(int(die), []).append(i)
        for die, idxs in by_die.items():
            self.shard(die).write([refs[i][1] for i in idxs], rows[jnp.asarray(idxs)])

    def _to_compute(self, x: jnp.ndarray) -> jnp.ndarray:
        """Move an array onto the primary compute device (a no-op when the
        shards are unmapped) — the one-device funnel the *unplaced*
        executable path needs, since a monolithic jitted executable's inputs
        must share a device."""
        return jax.device_put(x, self.devices[0]) if self.devices else x

    #: public alias: the executor's device-placed runners use this to collect
    #: cross-die partials for controller combines (arena-owned so the ledger
    #: linter's transfer rules stay centralized here)
    to_compute = _to_compute

    def compute_device(self):
        """The primary compute device (None when shards are unmapped)."""
        return self.devices[0] if self.devices else None

    def colocate(self, x: jnp.ndarray, like: jnp.ndarray) -> jnp.ndarray:
        """Place ``x`` on the device holding ``like`` (no-op when shards are
        unmapped or ``like`` is uncommitted) — the placed executor uses this
        to ship per-unit auxiliaries (the padding mask) to a shard-local
        kernel call, since one kernel cannot mix committed devices."""
        if not self.devices:
            return x
        devs = getattr(like, "devices", None)
        if devs is None:
            return x
        (dev,) = devs()
        return jax.device_put(x, dev)

    def device_of(self, die: int):
        """The JAX device pinning ``die``'s shard (None when unmapped)."""
        if not self.devices:
            return None
        return self.devices[die % len(self.devices)]

    def gather(self, refs: Sequence[SlotRef], *,
               place: bool = True) -> jnp.ndarray:
        """(len(refs), page_bits) rows in request order.

        Unmapped shards gather in one program (:meth:`gather_many`).
        Mapped shards take ONE gather per touched shard: die-local requests
        (the per-die sense groups) hit the single-shard fast path; cross-die
        requests (a fused megakernel spanning dies) concatenate the
        per-shard gathers and restore request order.

        ``place`` controls the single-device funnel for mapped shards:
        ``True`` (default) lands the result on the primary compute device —
        what a monolithic jitted executable needs; ``False`` leaves a
        die-local gather on its *own shard's* device, so the executor's
        device-placed wave dispatch senses each die's pages where they live
        (cross-die requests still concatenate on the compute device — a
        single kernel call cannot span devices).
        """
        refs = list(refs)
        if not self.devices:
            return self.gather_many([refs])[0]
        dies, slots, perm = self._parts(refs)
        if len(dies) == 1:
            local = self.shard(dies[0]).gather(slots[0])
            return self._to_compute(local) if place else local
        # shards pinned to distinct devices: gather on each shard's device,
        # collect the rows onto the compute device
        stacked = jnp.concatenate(
            [self._to_compute(self.shard(d).gather(s))
             for d, s in zip(dies, slots)], axis=0)
        if perm is None:
            return stacked
        return jnp.take(stacked, jnp.asarray(perm, jnp.int32), axis=0)

    def gather_many(self, ref_lists: Sequence[Sequence[SlotRef]]
                    ) -> Tuple[jnp.ndarray, ...]:
        """One ``(len(refs), page_bits)`` stack per ref list, in order.

        With unmapped shards this is ONE dispatch of :func:`_gather_parts`
        and one index upload for all the stacks, whatever dies they touch —
        what the executor's unplaced path feeds its cached executable.
        Mapped shards live on different devices, which one program cannot
        read, so there each stack is a :meth:`gather` of its own.
        """
        if self.devices:
            return tuple(self.gather(refs) for refs in ref_lists)
        bufs: List[jnp.ndarray] = []
        idx: List[int] = []
        layout: List[StackLayout] = []
        for refs in ref_lists:
            dies = {d for d, _ in refs}
            if len(dies) == 1:    # the sense groups: skip the split's passes
                bufs.append(self.shard(int(dies.pop())).buf)
                idx += [s for _, s in refs]
                layout.append(((len(refs),), False))
                continue
            dies, slots, perm = self._parts(refs)
            bufs += [self.shard(d).buf for d in dies]
            for part in slots:
                idx += part
            if perm is not None:
                idx += perm
            layout.append((tuple(map(len, slots)), perm is not None))
        return _gather_parts(tuple(bufs), np.asarray(idx, np.int32),
                             layout=tuple(layout))

    @classmethod
    def _parts(cls, refs: Sequence[SlotRef]
               ) -> Tuple[List[int], List[List[int]], Optional[List[int]]]:
        """Split a ref list by die shard: the dies (ascending), each die's
        slots in request order, and the permutation that puts the
        concatenated parts back in request order — ``None`` where they
        already are (a die-sorted request, such as the operand-major fused
        batches round-robined across dies)."""
        by_die = cls._by_die(refs)
        dies = sorted(by_die)
        offs, off = {}, 0
        for die in dies:
            offs[die] = off
            off += len(by_die[die])
        perm = []
        for die, _ in refs:
            perm.append(offs[int(die)])
            offs[int(die)] += 1
        if perm == list(range(len(perm))):
            perm = None
        return dies, [by_die[d] for d in dies], perm

    def die_of(self, ref: SlotRef) -> int:
        return int(ref[0])

    def shard_devices(self) -> Optional[List]:
        """The JAX device backing each created shard (None when unmapped)."""
        if not self.devices:
            return None
        return [self.devices[d % len(self.devices)] for d in sorted(self._shards)]
