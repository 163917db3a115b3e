"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--full`` uses larger sample
sizes (slower, tighter RBER statistics).
"""
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks import (discussion_tlc, fig6_retention, fig7_offset,
                        fig8_latency_energy, fig9_system, fig10_apps,
                        kernel_throughput, table1_ops, table2_rber)
from repro.compile_cache import enable_compile_cache

MODULES = (
    ("table1_ops", table1_ops),
    ("table2_rber", table2_rber),
    ("fig6_retention", fig6_retention),
    ("fig7_offset", fig7_offset),
    ("fig8_latency_energy", fig8_latency_energy),
    ("fig9_system", fig9_system),
    ("fig10_apps", fig10_apps),
    ("kernel_throughput", kernel_throughput),
    ("discussion_tlc", discussion_tlc),
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    print("name,us_per_call,derived")
    failed = []
    for name, mod in MODULES:
        if args.only and args.only != name:
            continue
        try:
            mod.main(quick=not args.full)
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    enable_compile_cache()
    main()
