"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix.  Set-up makes the data from the seed, loads it through
``ComputeSession.write_pair`` and serves every batch shape of the mix once
per warm-up pass; the window then drives the mix's loop through
``QueryEngine`` for ``--seconds``.  Afterwards every count and a seeded
sample of the bitmaps due in the window are compared with a NumPy reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics read from a profiler trace of the
window), ``device``, ``breakdown`` (traced runs) and ``check``, the numbers
compared beside their limits.  Without a TPU, or with fewer chips than the
cell asks for, the run exits 2 and prints no result; a traced run in which
a per-layer metric declared for the cell reads nothing exits 1 with none.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


class MetricNotRead(RuntimeError):
    """A per-layer metric declared for the cell found nothing to read."""


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(devices) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def traced_window(dep, traffic, seconds: float, seed: int):
    """The window under the profiler, with the benchmark's host spans, and
    the trace's reduction."""
    import jax.profiler as prof

    from bench import harness
    from bench.devtrace import WINDOW_SPAN, Trace

    inner = dep.engine.step

    def step():
        with prof.TraceAnnotation("bench.step"):
            return inner()

    dep.engine.step = step              # QueryTicket.result and poll call it
    opts = prof.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    out = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        prof.start_trace(out, profiler_options=opts)
        try:
            with prof.TraceAnnotation(WINDOW_SPAN):
                window = harness.measure(dep, traffic, seconds, seed,
                                         span=prof.TraceAnnotation)
        finally:
            prof.stop_trace()
        files = sorted(Path(out).rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        return window, Trace.from_file(str(files[-1]))
    finally:
        dep.engine.step = inner
        shutil.rmtree(out, ignore_errors=True)


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             chip_peaks=None, t0: float = None, session_kw=None) -> dict:
    """Set up, warm up, measure and check one cell on ``devices``; returns
    the result object (``t0``: when set-up began, by ``time.perf_counter``).
    ``session_kw`` goes to ``ComputeSession`` (the control's worn blocks)."""
    from bench import harness, layers, reference
    from bench.generator import Traffic

    t0 = time.perf_counter() if t0 is None else t0
    dep = harness.Deployment(cell.config, cell.config_module, seed,
                             **(session_kw or {}))
    traffic = Traffic(cell.mix, cell.config, seed)
    harness.warm_up(dep, traffic)
    setup_s = time.perf_counter() - t0

    tr = None
    if trace:
        window, tr = traced_window(dep, traffic, seconds, seed)
    else:
        window = harness.measure(dep, traffic, seconds, seed)
    device = device_info(devices)
    dep.release()
    numbers = window.check(dep.bits)
    correct, shown = reference.verdict(numbers)

    if tr is None:
        values = {"requests_per_s": window.requests_per_s,
                  "latency_p95_ms": window.latency_p95_ms,
                  "setup_s": lambda: setup_s}
        metrics = {m["name"]: {"value": values[m["name"]](), "unit": m["unit"]}
                   for m in cell.end_to_end}
    else:
        ctx = layers.Context(window, tr, chip_peaks)
        metrics = {m["name"]: {"value": cell.metric_reader(m["name"])(ctx),
                               "unit": m["unit"]} for m in cell.per_layer}
        silent = [k for k, v in metrics.items() if v["value"] is None]
        if silent:
            raise MetricNotRead(
                f"per-layer metrics declared for {cell.name} read nothing "
                f"in the traced window: {', '.join(silent)}\n{tr.describe()}")
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
    print(f"set-up {setup_s} s; window: {len(window.records)} requests, "
          f"{window.delta('batches')} batches, {window.compiles} compiles, "
          f"{window.delta('executable_misses')} executable-cache misses",
          file=sys.stderr)
    result = {"correct": bool(correct), "attempted": len(window.records),
              "failed": numbers["wrong_answers"] + numbers["missing_answers"],
              "metrics": metrics, "device": device}
    if tr is not None:
        result["breakdown"] = {"device_ops": tr.top_ops(),
                               "idle_gaps": tr.idle_gaps()}
    result["check"] = shown
    return result


def prepare() -> None:
    """Before JAX is imported: keep JAX's persistent compilation cache at a
    fixed path inside the checkout (unless the environment names one), and
    put the benchmark and the system under test on the import path."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def cache_compiles() -> None:
    """Let every program, however quick to compile, into the cache, so that
    only a cell's first run in a checkout compiles."""
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    t0 = time.perf_counter()
    args = parse(argv)
    prepare()
    from bench import harness, layers

    cell = harness.Cell.load(args.workload)
    import jax

    if jax.default_backend() != "tpu":
        print(f"no TPU: JAX backend is {jax.default_backend()!r}",
              file=sys.stderr)
        return 2
    devices = jax.devices()[:cell.chips]
    if len(devices) < cell.chips:
        print(f"the cell needs {cell.chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    chip_peaks = layers.peaks(devices[0].device_kind)
    cache_compiles()
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          devices, chip_peaks, t0)
    except MetricNotRead as e:
        print(e, file=sys.stderr)
        return 1
    for name, entry in result["check"].items():
        limit = " ".join(f"{k} {v}" for k, v in entry.items() if k != "value")
        print(f"check {name}: {entry['value']} ({limit})", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
