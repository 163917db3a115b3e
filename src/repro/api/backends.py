"""Pluggable execution backends for the compute-session layer.

A :class:`Backend` turns compiled read plans and packed bit-vectors into
numbers.  Two implementations ship:

- :class:`SimBackend` — the pure-jnp oracle path (``repro.kernels.ref``),
  bit-exact reference semantics, no Pallas involvement.
- :class:`PallasBackend` — the fused ``mlc_sense``/``bitops``/``popcount``
  TPU kernels (compiled on a TPU, interpreted on the CPU), the production
  path.

Both consume/produce the repo-wide lane-major packed uint32 convention, so a
session can swap backends without touching stored data, and parity tests can
diff them word-for-word.
"""
from __future__ import annotations

from typing import Protocol, runtime_checkable

import jax.numpy as jnp

from repro.core.mcflash import ReadPlan
from repro.kernels import ops as kops
from repro.kernels import ref as kernel_ref


def _padded_refs(plan: ReadPlan) -> jnp.ndarray:
    return kops.pad_refs(jnp.asarray(plan.refs, jnp.float32))


@runtime_checkable
class Backend(Protocol):
    """Minimal execution surface a session needs."""

    name: str

    def sense(self, vth: jnp.ndarray, plan: ReadPlan) -> jnp.ndarray:
        """(R, C) Vth + read plan -> (R, C//32) packed uint32."""
        ...

    def reduce(self, stack: jnp.ndarray, op: str, invert: bool = False) -> jnp.ndarray:
        """(N, R, W) packed operands -> (R, W) op-reduction (controller combine)."""
        ...

    def popcount(self, words: jnp.ndarray) -> jnp.ndarray:
        """(R, W) packed uint32 -> (R,) int32 bit counts."""
        ...

    def sense_reduce(self, vth: jnp.ndarray, plan: ReadPlan, *, op: str,
                     invert: bool = False) -> jnp.ndarray:
        """Fused chain: (N, R, C) same-plan Vth operands -> (R, C//32)
        packed op-reduction (sense epilogue feeds the reduce accumulator)."""
        ...

    def sense_reduce_popcount(self, vth: jnp.ndarray, plan: ReadPlan,
                              mask: jnp.ndarray, *, op: str,
                              invert: bool = False) -> jnp.ndarray:
        """Fused chain + masked popcount: (N, R, C) Vth -> (R,) int32."""
        ...


class SimBackend:
    """Pure-jnp oracle backend (``repro.kernels.ref``)."""

    name = "sim"

    def sense(self, vth: jnp.ndarray, plan: ReadPlan) -> jnp.ndarray:
        return kernel_ref.mlc_sense(vth, _padded_refs(plan), plan.kind,
                                    invert=plan.uses_inverse,
                                    n_refs=len(plan.refs))

    def reduce(self, stack: jnp.ndarray, op: str, invert: bool = False) -> jnp.ndarray:
        return kernel_ref.bitwise_reduce(stack, op, invert)

    def popcount(self, words: jnp.ndarray) -> jnp.ndarray:
        return kernel_ref.popcount_rows(words)

    def sense_reduce(self, vth: jnp.ndarray, plan: ReadPlan, *, op: str,
                     invert: bool = False) -> jnp.ndarray:
        return kernel_ref.sense_reduce(vth, _padded_refs(plan), plan.kind,
                                       plan.uses_inverse, op, invert,
                                       n_refs=len(plan.refs))

    def sense_reduce_popcount(self, vth: jnp.ndarray, plan: ReadPlan,
                              mask: jnp.ndarray, *, op: str,
                              invert: bool = False) -> jnp.ndarray:
        return kernel_ref.sense_reduce_popcount(vth, _padded_refs(plan), mask,
                                                plan.kind, plan.uses_inverse,
                                                op, invert,
                                                n_refs=len(plan.refs))


class PallasBackend:
    """Fused Pallas kernel backend.  ``interpret`` resolves once, at
    construction, through :func:`repro.kernels.ops.resolve_interpret`: a
    backend that finds a TPU runs compiled kernels."""

    name = "pallas"

    def __init__(self, interpret: bool | None = None):
        self.interpret = kops.resolve_interpret(interpret)

    def sense(self, vth: jnp.ndarray, plan: ReadPlan) -> jnp.ndarray:
        return kops.sense_plan(vth, plan, interpret=self.interpret)

    def reduce(self, stack: jnp.ndarray, op: str, invert: bool = False) -> jnp.ndarray:
        return kops.bitwise_reduce(stack, op=op, invert=invert,
                                   interpret=self.interpret)

    def popcount(self, words: jnp.ndarray) -> jnp.ndarray:
        return kops.popcount_rows(words, interpret=self.interpret)

    def sense_reduce(self, vth: jnp.ndarray, plan: ReadPlan, *, op: str,
                     invert: bool = False) -> jnp.ndarray:
        return kops.sense_reduce_plan(vth, plan, op=op, invert=invert,
                                      interpret=self.interpret)

    def sense_reduce_popcount(self, vth: jnp.ndarray, plan: ReadPlan,
                              mask: jnp.ndarray, *, op: str,
                              invert: bool = False) -> jnp.ndarray:
        return kops.sense_reduce_popcount_plan(vth, plan, mask, op=op,
                                               invert=invert,
                                               interpret=self.interpret)


_NAMED = {"sim": SimBackend, "pallas": PallasBackend}


def get_backend(spec: "str | Backend | None") -> Backend:
    """Resolve a backend name / instance; ``None`` -> PallasBackend."""
    if spec is None:
        return PallasBackend()
    if isinstance(spec, str):
        try:
            return _NAMED[spec]()
        except KeyError:
            raise ValueError(
                f"unknown backend {spec!r}; expected one of {sorted(_NAMED)}"
            ) from None
    if isinstance(spec, Backend):
        return spec
    raise TypeError(f"cannot interpret {spec!r} as a backend")
