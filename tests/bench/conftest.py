"""The benchmark's CPU tests: they import ``bench`` from the checkout root
and shrink each configuration to a size the Pallas interpreter serves in
seconds."""
import copy
import dataclasses
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

#: small sizes per configuration key: 1 KiB pages, few users or images
SMALL = {"users": 1 << 13, "images": 16, "height": 30, "width": 40}


def small(cell):
    """``cell`` with its configuration cut to CPU-test size."""
    cfg = copy.deepcopy(cell.config)
    cfg["ssd"]["page_kb"] = 1
    for key, value in SMALL.items():
        if key in cfg:
            cfg[key] = value
    return dataclasses.replace(cell, config=cfg)


@pytest.fixture
def small_cell():
    from bench import harness

    return lambda name: small(harness.Cell.load(name))
