"""What a per-layer metric's reader sees, and the arithmetic readers share.

A metric ``<name>`` of ``BENCHMARK.json``'s ``per_layer`` is read by
``bench/metrics/<name>.py``, whose ``read(ctx)`` returns the value in the
metric's unit, or ``None`` where the run gave it nothing to read.  A
metric the manifest declares for a cell has to read something in that
cell's traced run, or the run fails (``run.MetricNotRead``).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, Optional

BENCH = Path(__file__).resolve().parent


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind`` (``bench/peaks.json``);
    a device that is not in the table is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


@dataclasses.dataclass
class Context:
    """One run's window (counters, requests), its trace and the chip's
    peaks."""
    window: object                  # harness.Window
    trace: object = None            # devtrace.Trace, or None untraced
    peaks: Optional[Dict[str, float]] = None

    @property
    def batches(self) -> int:
        return self.window.delta("batches")

    def roofline(self, kernel: str) -> Optional[float]:
        """Percent of the HBM roofline a Pallas kernel reached over its calls
        in the window: bytes moved (``bench/kernels/<kernel>.py``) over the
        peak bandwidth, divided by the kernel's device time.  These kernels
        do a few vector compares per 4-byte cell, so bandwidth bounds them;
        ``None`` where the window made no call the trace could size."""
        from bench.harness import load_module

        calls = self.trace.kernel_calls(kernel) if self.trace else []
        seconds = sum(c[0] for c in calls)
        if not calls or seconds <= 0:
            return None
        mod = load_module(BENCH / "kernels" / f"{kernel}.py")
        moved = sum(mod.bytes_moved(ops, res) for _, ops, res in calls)
        return 100.0 * moved / self.peaks["hbm_bytes_per_s"] / seconds
