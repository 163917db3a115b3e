"""The one traffic generator: turns a mix file ``bench/traffic/<name>.json``
into arrivals of request groups.

A mix file holds parameters only:

- ``arrivals``: how groups arrive.  ``{"loop": "closed", "clients": n}``
  keeps ``n`` groups in flight: a client sends its next group when every
  request of its previous one is back.  ``{"loop": "open", "rate_per_s": r}``
  sends groups at ``r`` per second whatever the system does, with
  exponential gaps; an optional ``"burst": {"every_s", "for_s", "factor"}``
  multiplies the rate by ``factor`` for ``for_s`` seconds of every
  ``every_s``.  The gaps are the same for every seed, in its own order;
- ``params``: one deck per parameter.  ``values`` (or ``range``: a count,
  or the name of a configuration key holding one) with optional ``counts``
  (how often each value appears per cycle of the deck).  Each cycle is
  shuffled from the seed, so every seed sees the same mix in its own order;
  ``reshuffle: false`` keeps one seeded order for every cycle;
- ``requests``: request templates, each an expression and ``popcount``.
  ``pick: "all"`` (the default) makes a group of every template;
  ``pick: "one"`` makes a group of one template, drawn by the deck named
  ``template`` in ``params`` (an index into ``requests``).

An expression is ``[op, operand, ...]`` (``op`` one of ``and``, ``or``,
``xor``); an operand is a vector name, a nested expression, or a range of
vectors ``{"vectors": prefix, "param": p, "scale": a, "offset": b,
"count": n}`` naming ``prefix<a*p+b> .. prefix<a*p+b+n-1>``, where ``p``
may be left out (start ``b``) and ``n`` may be a parameter's name.

A concrete expression is a tuple ``(op, operand, ...)`` whose leaves are
vector names: hashable, so the reference evaluates each distinct one once.
"""
from __future__ import annotations

import itertools
from typing import Dict, List, Tuple

import numpy as np

OPS = ("and", "or", "xor")
#: gaps per cycle of an open loop's arrival deck
GAPS = 512

Group = List[Tuple[tuple, bool]]


class Deck:
    """Seeded draws of one parameter: every cycle holds each value ``count``
    times, so every seed sees the same mix, in its own order."""

    def __init__(self, spec: dict, cfg: dict, rng: np.random.Generator):
        if "range" in spec:
            n = spec["range"]
            values = list(range(int(cfg[n] if isinstance(n, str) else n)))
        else:
            values = list(spec["values"])
        counts = spec.get("counts", [1] * len(values))
        if len(counts) != len(values) or min(counts) < 1:
            raise ValueError(f"bad deck counts {counts} for {values}")
        self.values = values
        self._cycle = np.repeat(np.asarray(values), counts)
        self._reshuffle = bool(spec.get("reshuffle", True))
        self._rng = rng
        self._order = rng.permutation(len(self._cycle))
        self._next = 0

    def draw(self):
        if self._next == len(self._order):
            if self._reshuffle:
                self._order = self._rng.permutation(len(self._cycle))
            self._next = 0
        value = self._cycle[self._order[self._next]]
        self._next += 1
        return value.item()


def instantiate(node, env: Dict[str, int]):
    """Concrete expression of a template node under parameter values."""
    if isinstance(node, str):
        return node
    op, *args = node
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}")
    operands: List = []
    for arg in args:
        if isinstance(arg, dict):
            start = arg.get("scale", 1) * env.get(arg.get("param"), 0) \
                + arg.get("offset", 0)
            count = arg["count"]
            count = env[count] if isinstance(count, str) else count
            operands.extend(f"{arg['vectors']}{start + k}"
                            for k in range(count))
        else:
            operands.append(instantiate(arg, env))
    return (op, *operands)


class Traffic:
    """A mix file bound to a configuration and a seed."""

    def __init__(self, mix: dict, cfg: dict, seed: int):
        arrivals = mix["arrivals"]
        self.loop = arrivals["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"unknown loop {self.loop!r}")
        self.clients = int(arrivals.get("clients", 0))
        self.requests = mix["requests"]
        self.pick = mix.get("pick", "all")
        rng = np.random.default_rng(seed)
        self.decks = {name: Deck(spec, cfg, rng)
                      for name, spec in sorted(mix["params"].items())}
        self.arrivals = arrivals
        if self.loop == "open":
            # exponential gaps at unit rate, by their quantiles
            q = (np.arange(GAPS) + 0.5) / GAPS
            self._gaps = Deck({"values": list(-np.log1p(-q))}, cfg, rng)

    def group(self, env: Dict[str, int]) -> Group:
        """The requests ``(expression, popcount)`` of one group."""
        chosen = (self.requests if self.pick == "all"
                  else [self.requests[env["template"]]])
        return [(instantiate(r["expr"], env), bool(r["popcount"]))
                for r in chosen]

    def next_group(self) -> Group:
        """The next group, drawn from the decks."""
        return self.group({name: d.draw() for name, d in self.decks.items()})

    def every_group(self) -> List[Group]:
        """One group for each combination of parameter values (warm-up)."""
        names = list(self.decks)
        return [self.group(dict(zip(names, combo))) for combo in
                itertools.product(*(self.decks[n].values for n in names))]

    def next_arrival(self, t: float) -> float:
        """Open loop: when, in seconds into the window, the group after one
        that arrived at ``t`` arrives."""
        rate = float(self.arrivals["rate_per_s"])
        burst = self.arrivals.get("burst")
        if burst and t % burst["every_s"] < burst["for_s"]:
            rate *= burst["factor"]
        return t + self._gaps.draw() / rate
