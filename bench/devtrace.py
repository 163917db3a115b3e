"""Reduction of a profiler trace to device busy time, kernel and module
time, and idle gaps labelled by what the host was doing.

The benchmark records its own host spans with ``jax.profiler.
TraceAnnotation``: ``bench.window`` around the measured window, and inside
it ``bench.step`` (``QueryEngine.step``: lowering and dispatch of a batch),
``bench.wait`` (blocking on a group's results) and ``bench.submit``
(building and submitting a group).  Device planes are ``/device:TPU:<n>``;
their ``XLA Ops`` line holds one event per HLO op run, and their ``XLA
Modules`` line one per program run (``jit_run(<program id>)``).  On a TPU
an op event's name is the op's whole HLO text
(``%mlc_sense.3 = u32[128,4096]{...} custom-call(...), ...``), which gives
the op's name and its operand and result shapes; a Pallas kernel's op is
named after the jitted wrapper that calls it (``mlc_sense.3``).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
#: host spans in the order an idle gap is attributed to them (innermost
#: first); time covered by none of them is ``host.other``
HOST_SPANS = ("bench.step", "bench.wait", "bench.submit")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\.\d+$")
_ARRAY = re.compile(r"\b(pred|[fsu]\d+|bf16)\[([\d,]*)\]")

Interval = Tuple[float, float]


@dataclasses.dataclass
class Event:
    name: str
    start: float            # ns
    end: float              # ns
    stats: Dict[str, object]

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]) -> List[Interval]:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs: Sequence[Interval], ys: Sequence[Interval]) -> List[Interval]:
    """``xs`` minus ``ys`` (both merged)."""
    out = []
    for a, b in xs:
        cur = a
        for c, d in ys:
            if d <= cur or c >= b:
                continue
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
        if cur < b:
            out.append((cur, b))
    return out


def op_name(event_name: str) -> str:
    """HLO op name of an op event: the name before `` = `` in the HLO text
    that a TPU trace gives as the event's name, without its ``%``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def base_name(op: str) -> str:
    """HLO op name of an op event without its ``.<n>`` instance suffix."""
    return _SUFFIX.sub("", op_name(op))


def _braced(text: str, key: str) -> Optional[str]:
    """The balanced ``{...}`` body that follows ``key`` in ``text``."""
    start = text.find(key + "={")
    if start < 0:
        return None
    depth, i = 0, start + len(key) + 1
    for j in range(i, len(text)):
        depth += {"{": 1, "}": -1}.get(text[j], 0)
        if depth == 0:
            return text[i + 1:j]
    return None


def _arrays(text: str) -> list:
    return [(d, tuple(int(x) for x in s.split(",") if x))
            for d, s in _ARRAY.findall(text)]


def hlo_shapes(text: str) -> Optional[Tuple[list, list]]:
    """``(operands, results)`` as ``(dtype, shape)`` lists from the HLO text
    of a custom call: the result before ``custom-call(``, the operands in
    ``operand_layout_constraints={...}``, or where the text has none, in the
    call's own argument list (where the printer gives operand shapes)."""
    head, sep, tail = text.partition("custom-call(")
    if not sep:
        return None
    operands = _braced(tail, "operand_layout_constraints")
    if operands is None:
        operands = tail.split(")", 1)[0]
    arrays = _arrays(operands)
    return (arrays, _arrays(head.split("=", 1)[-1])) if arrays else None


class Trace:
    """Events of one traced window, on the clock of the trace."""

    def __init__(self, window: Interval, ops: Dict[str, List[Event]],
                 modules: Dict[str, List[Event]],
                 host: Dict[str, List[Interval]]):
        self.window = window
        self.ops = ops              # device plane -> op events in the window
        self.modules = modules      # device plane -> module events
        self.host = host            # span name -> merged intervals

    @classmethod
    def from_profile(cls, data) -> "Trace":
        """Read a ``jax.profiler.ProfileData``."""
        ops: Dict[str, List[Event]] = {}
        modules: Dict[str, List[Event]] = {}
        host_raw: Dict[str, List[Interval]] = {}
        for plane in data.planes:
            device = bool(_DEVICE_PLANE.match(plane.name))
            for line in plane.lines:
                if device and line.name in (OPS_LINE, MODULES_LINE):
                    dest = ops if line.name == OPS_LINE else modules
                    dest[plane.name] = [
                        Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                              dict(e.stats)) for e in line.events]
                elif not device:
                    for e in line.events:
                        if e.name == WINDOW_SPAN or e.name in HOST_SPANS:
                            host_raw.setdefault(e.name, []).append(
                                (e.start_ns, e.start_ns + e.duration_ns))
        if not host_raw.get(WINDOW_SPAN):
            raise ValueError(f"no {WINDOW_SPAN} span in the trace")
        window = max(host_raw.pop(WINDOW_SPAN), key=lambda iv: iv[1] - iv[0])
        clip = [window]
        host = {k: intersect(merge(v), clip) for k, v in host_raw.items()}

        def inside(events: List[Event]) -> List[Event]:
            return [e for e in events
                    if e.end > window[0] and e.start < window[1]]

        return cls(window, {k: inside(v) for k, v in ops.items()},
                   {k: inside(v) for k, v in modules.items()}, host)

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(path))

    # -- device time --------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def devices(self) -> List[str]:
        """Device planes with at least one op in the window."""
        return sorted(k for k, v in self.ops.items() if v)

    def busy(self, device: str) -> List[Interval]:
        """Merged intervals in which some op ran on ``device``."""
        return intersect(merge((e.start, e.end) for e in self.ops[device]),
                         [self.window])

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices used (0 with none)."""
        devs = self.devices
        if not devs:
            return 0.0
        return sum(length(self.busy(d)) for d in devs) * 1e-9 / len(devs)

    def op_events(self, base: str) -> List[Event]:
        """Op events, on every device, of HLO ops named ``base[.n]``, or of
        a Pallas call made inside the jitted function ``base`` (by the name
        stack in the op's metadata)."""
        stack = f"jit({base})/pallas_call"
        return [e for d in self.devices for e in self.ops[d]
                if base_name(e.name) == base or any(
                    isinstance(v, str) and stack in v
                    for v in e.stats.values())]

    def module_events(self, pattern: str) -> List[Event]:
        """Program runs, on every device, whose module name matches
        ``pattern``: the ``XLA Modules`` line's events, or where a device
        has no such line, its op events by their ``hlo_module`` stat."""
        rx = re.compile(pattern)
        out = []
        for d in self.devices:
            if self.modules.get(d):
                out += [e for e in self.modules[d] if rx.search(e.name)]
            else:
                out += [e for e in self.ops[d]
                        if rx.search(str(e.stats.get("hlo_module", "")))]
        return out

    # -- breakdown ----------------------------------------------------------
    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` op names (instance suffix dropped) that took the most
        device time in the window, with their seconds."""
        total: Dict[str, float] = {}
        for d in self.devices:
            for e in self.ops[d]:
                key = base_name(e.name)
                total[key] = total.get(key, 0.0) + e.seconds
        return [[k, v] for k, v in
                sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """Idle device seconds in the window by what the host was doing
        (the innermost benchmark span over the gap), averaged over the
        devices used, longest first."""
        devs = self.devices or [None]
        total: Dict[str, float] = {}
        for d in devs:
            idle = subtract([self.window], self.busy(d) if d else [])
            for name in HOST_SPANS:
                spans = self.host.get(name, [])
                total[name] = total.get(name, 0.0) + length(
                    intersect(idle, spans)) * 1e-9 / len(devs)
                idle = subtract(idle, spans)
            total["host.other"] = total.get("host.other", 0.0) + \
                length(idle) * 1e-9 / len(devs)
        return [[k, v] for k, v in
                sorted(total.items(), key=lambda kv: -kv[1])[:n] if v > 0]

    def describe(self) -> str:
        """What the trace holds, for a run whose metrics found nothing: per
        device plane its op and module counts, the commonest names, and the
        stat keys of its first op."""
        out = [f"window {self.window_s} s; host spans {sorted(self.host)}"]
        for d in sorted(set(self.ops) | set(self.modules)):
            ops, mods = self.ops.get(d, []), self.modules.get(d, [])
            names: Dict[str, int] = {}
            for e in ops:
                names[base_name(e.name)] = names.get(base_name(e.name), 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:8]
            mnames = sorted({base_name(e.name) for e in mods})[:8]
            keys = sorted(ops[0].stats) if ops else []
            out.append(f"{d}: {len(ops)} ops {top}; {len(mods)} modules "
                       f"{mnames}; op stats {keys}")
        return "\n".join(out)

    def kernel_calls(self, kernel: str) -> List[Tuple[float, list, list]]:
        """``(seconds, operands, results)`` of each call of a Pallas kernel
        whose shapes the trace gives (in the event's name or a stat)."""
        out = []
        for e in self.op_events(kernel):
            for value in (e.name, *e.stats.values()):
                shapes = (hlo_shapes(value) if isinstance(value, str)
                          else None)
                if shapes is not None:
                    out.append((e.seconds, *shapes))
                    break
        return out
