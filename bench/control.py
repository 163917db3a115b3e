"""The control of the comparison that decides ``correct``: the cell served
from worn blocks.

Each configuration states fresh blocks (0 P/E cycles), on which every read
margin holds and every answer is exact.  Its ``control`` entry names the
wear that breaks that guarantee: the program's own fault injection
(``ComputeSession(faults=...)``) with its recovery switched off.  The same
traffic, window and comparison as ``run.py`` must then come out not
correct.  The benchmark's own runs never run this.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

Prints one JSON line per seed: the numbers compared beside their limits.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


def control_kw(cfg: dict, seed: int) -> dict:
    """``ComputeSession`` arguments of the configuration's control."""
    from bench.harness import device_seed

    ctl = cfg["control"]
    return {"faults": dict(ctl["faults"], seed=device_seed(seed)),
            "recovery": ctl["recovery"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench import run

    run.prepare()
    import jax

    from bench import harness

    if jax.default_backend() != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    run.cache_compiles()
    cell = harness.Cell.load(args.workload)
    for seed in args.seeds:
        result = run.run_cell(cell, seed, args.seconds, False,
                              jax.devices()[:cell.chips], t0=time.perf_counter(),
                              session_kw=control_kw(cell.config, seed))
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": cell.config["control"],
                          "correct": result["correct"],
                          "check": result["check"],
                          "metrics": result["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
