"""Double-buffered controller->host result streaming.

:meth:`ComputeSession.materialize` resolves its result synchronously: the
device array crosses the host link before the next expression dispatches,
so on multi-wave workloads the host transfer of result *k* serializes with
the sensing of result *k+1*.  :class:`HostDrainQueue` breaks that chain:

- :meth:`~HostDrainQueue.submit` starts the device->host copy *asynchronously*
  (``jax.Array.copy_to_host_async``) and
  returns a :class:`DrainHandle` immediately — the caller goes on to lower
  and dispatch the next expression while the transfer streams.
- The queue is **bounded** (``depth`` in-flight transfers, default 2 — the
  double buffer): submitting past the bound blocks on the *oldest*
  transfer first, so device result buffers can't pile up without bound.
- :meth:`~HostDrainQueue.drain` resolves everything still in flight.

This is the host-side half of the ledger's ``"overlap"`` accounting mode
(:class:`repro.api.ledger.Ledger`): the simulated timeline books the host
link concurrently with the next wave's die work, and this queue makes the
real wall-clock execution match that shape.
"""
from __future__ import annotations

from collections import deque
from typing import Callable, Deque, List, Optional

import numpy as np

from repro.obs.trace import traced

__all__ = ["DrainHandle", "HostDrainQueue", "DEFAULT_DRAIN_DEPTH"]

#: in-flight transfers the bounded queue holds — 2 == classic double buffer
DEFAULT_DRAIN_DEPTH = 2


class DrainHandle:
    """One in-flight device->host result transfer.

    :meth:`result` blocks until the bytes are host-resident and returns the
    ``np.ndarray`` (memoized — repeat calls are free).
    """

    __slots__ = ("_array", "_out", "n_bytes", "rid")

    def __init__(self, array, n_bytes: int, rid: Optional[int] = None) -> None:
        self._array = array
        self._out: Optional[np.ndarray] = None
        self.n_bytes = int(n_bytes)
        #: owning request id (serving engine attribution), or None
        self.rid = rid
        # start the DMA now; resolution in result() then only waits, it
        # doesn't initiate (a numpy payload is host memory already)
        if not isinstance(array, np.ndarray):
            array.copy_to_host_async()

    @property
    def done(self) -> bool:
        """True once the bytes are host-resident — a non-blocking probe.

        Resolution order: a memoized :meth:`result` is definitively done; a
        plain ``np.ndarray`` submission is already host memory; otherwise
        ``jax.Array.is_ready()`` answers (True once the array is computed).
        """
        if self._out is not None:
            return True
        if isinstance(self._array, np.ndarray):
            return True
        return bool(self._array.is_ready())

    def result(self) -> np.ndarray:
        if self._out is None:
            with traced(None, "drain.wait"):
                self._out = np.asarray(self._array)
            self._array = None          # drop the device buffer reference
        return self._out


class HostDrainQueue:
    """Bounded async drain queue for controller->host result streaming.

    ``on_submit(n_bytes)`` fires once per submit (ledger/metrics hook);
    ``on_block()`` fires each time a submit had to resolve the oldest
    in-flight transfer to respect ``depth`` (backpressure events).
    """

    def __init__(self, depth: int = DEFAULT_DRAIN_DEPTH,
                 on_submit: Optional[Callable[[int], None]] = None,
                 on_block: Optional[Callable[[], None]] = None) -> None:
        if depth < 1:
            raise ValueError(f"drain depth must be >= 1, got {depth}")
        self.depth = int(depth)
        self._pending: Deque[DrainHandle] = deque()
        self._on_submit = on_submit
        self._on_block = on_block

    def __len__(self) -> int:
        return len(self._pending)

    def submit(self, array, n_bytes: Optional[int] = None,
               rid: Optional[int] = None) -> DrainHandle:
        """Enqueue one result transfer; blocks on the oldest in-flight
        transfer when the queue is full (the double-buffer bound).  ``rid``
        tags the handle with the owning request id (serving attribution)."""
        if n_bytes is None:
            n_bytes = int(array.size) * array.dtype.itemsize
        handle = DrainHandle(array, n_bytes, rid=rid)
        if self._on_submit is not None:
            self._on_submit(handle.n_bytes)
        self._pending.append(handle)
        while len(self._pending) > self.depth:
            oldest = self._pending.popleft()
            if self._on_block is not None:
                self._on_block()
            with traced(None, "drain.block"):
                oldest.result()
        return handle

    def drain(self) -> List[DrainHandle]:
        """Resolve every in-flight transfer; returns the handles in submit
        order (all ``done``)."""
        out: List[DrainHandle] = []
        while self._pending:
            h = self._pending.popleft()
            h.result()
            out.append(h)
        return out

    def reset(self) -> None:
        """Drop in-flight transfers without resolving them (session stat
        reset) — pending device buffers are released unread."""
        self._pending.clear()
