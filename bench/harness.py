"""One run of one benchmark cell: build the deployment, warm up, drive the
closed loop for the window, then check every answer due in it.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

- ``bench/configs/<config>.json`` (sizes, guarantees) and
  ``bench/configs/<config>.py`` (``make_vectors(cfg, seed)``);
- ``bench/traffic/<traffic>.json`` (read by ``generator.Traffic``, driven
  by ``drive``);
- ``bench/metrics/<metric>.py`` (``read(ctx)``, see ``layers.py``);
- ``bench/kernels/<kernel>.py`` (``bytes_moved(operands, results)``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import itertools
import json
import time
from collections import deque
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import generator, reference

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

#: bitmap answers kept for the comparison per run (a seeded reservoir over
#: every bitmap answer due in the window); counts are all kept
SAMPLED_BITMAPS = 96
#: most batch compositions warm-up serves one by one
WARM_COMBOS = 64


def load_module(path: Path):
    """Import a benchmark file by path (configuration, metric or kernel)."""
    name = "bench_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """A cell of the manifest with its configuration and traffic mix; files
    are found under ``root / "bench"`` by the names the manifest gives."""
    name: str
    chips: int
    config: dict
    config_module: object
    mix: dict
    per_layer: List[dict]
    end_to_end: List[dict]
    bench: Path

    @classmethod
    def load(cls, name: str, root: Path = ROOT) -> "Cell":
        manifest = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        w = cells[name]
        entry = {c["name"]: c for c in manifest["configs"]}[w["config"]]
        cfg_path = root / entry["file"]
        bench = root / "bench"
        return cls(
            name=name, chips=int(w["chips"]),
            config=json.loads(cfg_path.read_text()),
            config_module=load_module(cfg_path.with_suffix(".py")),
            mix=json.loads(
                (bench / "traffic" / f"{w['traffic']}.json").read_text()),
            per_layer=[m for m in manifest["per_layer"]
                       if name in m.get("workloads", [name])],
            end_to_end=[m for m in manifest["end_to_end"]
                        if name in m.get("workloads", [name])],
            bench=bench)

    def metric_reader(self, name: str):
        """The ``read(ctx)`` of per-layer metric ``name``."""
        return load_module(self.bench / "metrics" / f"{name}.py").read


def device_seed(seed: int) -> int:
    """The run's seed folded into the 31 bits a device PRNG key keeps."""
    return (seed ^ (seed >> 31)) & 0x7FFFFFFF


class Deployment:
    """A configuration loaded through ``ComputeSession.write_pair`` and served
    by a ``QueryEngine``; ``bits`` keeps the host bits for the reference."""

    def __init__(self, cfg: dict, module, seed: int, **session_kw):
        from repro.api import ComputeSession
        from repro.flash.geometry import SSDConfig
        from repro.serve import QueryEngine, SLOConfig

        pairs, self.bits = module.make_vectors(cfg, seed)
        session_kw.setdefault("faults", False)
        self.session = ComputeSession(
            config=SSDConfig(**cfg["ssd"]), seed=device_seed(seed),
            encoding=cfg["encoding"], verify=cfg["verify"], **session_kw)
        for a, b in pairs:
            self.session.write_pair(a, self.bits[a], b, self.bits[b])
        self.engine = QueryEngine(self.session, SLOConfig(**cfg["slo"]))

    def release(self) -> None:
        """Drop the program's state; the host bits stay for the reference."""
        self.engine = self.session = None
        gc.collect()

    def vector(self, expr):
        """The BitVector expression of a concrete expression."""
        if isinstance(expr, str):
            return self.session[expr]
        op, *args = expr
        return self.session.chain(op, [self.vector(a) for a in args])


@dataclasses.dataclass
class Record:
    """One request of the closed loop."""
    expr: tuple
    popcount: bool
    submitted: float
    ticket: object = None
    done: Optional[float] = None
    answer: object = None
    missing: bool = False


def _no_span(_name: str):
    return contextlib.nullcontext()


class Sampler:
    """Keeps every count and a seeded reservoir of ``k`` bitmap answers; a
    bitmap answer that drops out of the reservoir is released."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = np.random.default_rng(seed)
        self.bitmaps: List[Record] = []
        self.seen = 0

    def offer(self, r: Record) -> None:
        if r.popcount or r.missing:
            return
        self.seen += 1
        if len(self.bitmaps) < self.k:
            self.bitmaps.append(r)
            return
        j = int(self.rng.integers(self.seen))
        if j < self.k:
            self.bitmaps[j].answer = None
            self.bitmaps[j] = r
        else:
            r.answer = None


def dispatch(eng, ticket) -> bool:
    """Step the engine until ``ticket`` is dispatched; False where the engine
    has nothing left to step and the ticket never went out (its answer
    never comes)."""
    while not ticket.dispatched:
        if eng.step() == 0:
            return False
    return True


class Loop:
    """Submits groups to a deployment's engine and resolves their answers;
    ``records`` holds every request submitted."""

    def __init__(self, dep: Deployment, sampler: Optional[Sampler] = None,
                 span: Callable = _no_span):
        self.dep, self.sampler, self.span = dep, sampler, span
        self.records: List[Record] = []

    def submit(self, group, arrived: Optional[float] = None) -> List[Record]:
        """Submit a group's requests; their latency counts from ``arrived``
        (by default, from each submit)."""
        eng = self.dep.engine
        recs = []
        with self.span("bench.submit"):
            for expr, popcount in group:
                vec = self.dep.vector(expr)
                r = Record(expr, popcount, time.perf_counter()
                           if arrived is None else arrived)
                r.ticket = eng.submit(vec, popcount=popcount)
                recs.append(r)
            while eng.poll():
                pass
        self.records.extend(recs)
        return recs

    def resolve(self, r: Record) -> None:
        """Wait for one answer (marking it missing if it never comes)."""
        if not dispatch(self.dep.engine, r.ticket):
            r.missing, r.ticket = True, None
            return
        r.answer = r.ticket.result()
        r.done = time.perf_counter()
        r.ticket = None
        if self.sampler is not None:
            self.sampler.offer(r)


def closed_loop(loop: Loop, next_group: Callable, clients: int,
                until: float) -> List[Record]:
    """Keep ``clients`` groups in flight: a client submits its group, waits
    until every answer of it is back, and submits its next one while the
    clock is before ``until`` and ``next_group`` gives one.  Returns every
    request, resolved."""
    outstanding: deque = deque()

    def issue() -> None:
        group = next_group()
        if group is not None:
            outstanding.append(loop.submit(group))

    for _ in range(clients):
        issue()
    while outstanding:
        finished = [outstanding.popleft()]
        with loop.span("bench.wait"):
            for r in finished[0]:
                loop.resolve(r)
        pending: deque = deque()
        for group in outstanding:         # groups already back come along
            if all(r.ticket is not None and r.ticket.done for r in group):
                for r in group:
                    loop.resolve(r)
                finished.append(group)
            else:
                pending.append(group)
        outstanding = pending
        if time.perf_counter() < until:
            for _ in finished:
                issue()
    return loop.records


def open_loop(loop: Loop, traffic: generator.Traffic,
              until: float) -> List[Record]:
    """Submit a group at each of the mix's arrival times before ``until``,
    whatever is still outstanding, and resolve each answer once it is on
    the host.  A request's latency counts from its group's arrival time, so
    the time the loop falls behind is part of it.  Returns every request,
    resolved."""
    eng = loop.dep.engine
    start = due = time.perf_counter()
    outstanding: List[Record] = []
    while due < until or outstanding:
        if due < until and time.perf_counter() >= due:
            outstanding += loop.submit(traffic.next_group(), arrived=due)
            due = start + traffic.next_arrival(due - start)
            continue
        if due >= until:                  # nothing more arrives
            with loop.span("bench.wait"):
                for r in outstanding:
                    loop.resolve(r)
            break
        if eng.poll():
            continue
        if not outstanding:
            time.sleep(max(0.0, due - time.perf_counter()))
        ready = [r for r in outstanding if r.ticket.done]
        if ready:
            with loop.span("bench.wait"):
                for r in ready:
                    loop.resolve(r)
            outstanding = [r for r in outstanding if r.ticket is not None]
    return loop.records


def drive(dep: Deployment, traffic: generator.Traffic, until: float,
          sampler: Optional[Sampler] = None,
          span: Callable = _no_span) -> List[Record]:
    """Run the mix's loop until ``until``; every request, resolved."""
    loop = Loop(dep, sampler, span)
    if traffic.loop == "open":
        return open_loop(loop, traffic, until)
    return closed_loop(loop, traffic.next_group, traffic.clients, until)


class CompileCounter:
    """Counts XLA programs compiled, or loaded from the persistent cache,
    while it is open (JAX's monitoring events)."""

    def __init__(self):
        self.n = 0

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.n += 1

    def __enter__(self) -> "CompileCounter":
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._duration)
        monitoring.unregister_event_listener(self._event)


def counters(dep: Deployment) -> Dict[str, int]:
    """The program's counters the per-layer metrics read as deltas."""
    st = dep.engine.stats()
    return {"batches": st["batches_dispatched"],
            "coalesced_sense_groups": st["coalesced_sense_groups"],
            "executable_misses": dep.session.executor.stats()["misses"]}


def warm_batches(traffic: generator.Traffic, cap: int) -> List[list]:
    """The batches, as request lists, that the mix's loop can form.

    Where a closed loop's clients fit their groups into one batch, the
    engine dispatches one group, or the groups of several clients in their
    order of submission: every such sequence of groups is served, where
    there are at most ``WARM_COMBOS``.  Otherwise every request goes out
    in full batches, and batches of every smaller size too, which the
    delay bound dispatches."""
    groups = traffic.every_group()
    if traffic.loop == "closed" and \
            traffic.clients * max(map(len, groups)) <= cap:
        combos = [[r for g in seq for r in g]
                  for k in range(1, traffic.clients + 1)
                  for seq in itertools.product(groups, repeat=k)]
        if len(combos) <= WARM_COMBOS:
            return combos
    requests = [r for g in groups for r in g]
    return ([requests[i:i + cap] for i in range(0, len(requests), cap)]
            + [requests[:k] for k in range(1, cap)])


def warm_up(dep: Deployment, traffic: generator.Traffic) -> None:
    """Serve every batch the mix can form (``warm_batches``) twice, so that
    each is compiled, and run with warm caches, before the window."""
    eng = dep.engine
    batches = warm_batches(traffic, eng.slo.max_batch_requests)
    for _ in range(2):
        for batch in batches:
            tickets = [eng.submit(dep.vector(e), popcount=p)
                       for e, p in batch]
            for t in tickets:
                if dispatch(eng, t):
                    t.result()


@dataclasses.dataclass
class Window:
    """What one measured window served."""
    seconds: float
    records: List[Record]
    before: Dict[str, int]
    after: Dict[str, int]
    compiles: int
    start: float

    @property
    def completed(self) -> List[Record]:
        end = self.start + self.seconds
        return [r for r in self.records
                if r.done is not None and r.done <= end]

    def requests_per_s(self) -> float:
        return len(self.completed) / self.seconds

    def latency_p95_ms(self) -> float:
        lat = [r.done - r.submitted for r in self.completed]
        return float(np.percentile(lat, 95)) * 1e3

    def delta(self, key: str) -> int:
        return self.after[key] - self.before[key]

    def answers(self):
        """``(expression, popcount, answer)`` of every answer kept."""
        for r in self.records:
            if r.answer is not None:
                yield r.expr, r.popcount, r.answer

    def check(self, bits: Dict[str, np.ndarray]) -> Dict[str, int]:
        missing = sum(r.missing for r in self.records)
        return reference.compare(self.answers(), missing, bits.__getitem__)


def measure(dep: Deployment, traffic: generator.Traffic, seconds: float,
            seed: int, span: Callable = _no_span) -> Window:
    """Drive the closed loop for ``seconds`` and resolve every answer."""
    sampler = Sampler(SAMPLED_BITMAPS, seed)
    before = counters(dep)
    with CompileCounter() as compiles:
        start = time.perf_counter()
        records = drive(dep, traffic, start + seconds, sampler, span)
    return Window(seconds, records, before, counters(dep), compiles.n, start)
