"""Compile the main path's Pallas kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler lowers each kernel at full page width
(one 16 KiB page = 131072 cells per row, 128 pages = one 2^24-bit bitmap) for
a device that is described, not attached.  This catches what interpret mode
cannot — a reduction Mosaic does not implement, a block not aligned to the
tiling, more fast memory than a kernel may use.  Nothing runs, so results
and times are out of scope here.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, so the call waits
until a test of this file has started on its worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bitops import bitwise_reduce
from repro.kernels.fused import sense_reduce, sense_reduce_popcount
from repro.kernels.mlc_sense import MAX_REFS
from repro.kernels.mlc_sense import mlc_sense as mlc_sense_kernel
from repro.kernels.popcount import popcount_rows

PAGE_CELLS = 131072                  # one 16 KiB page
PAGES = 128                          # one 2^24-user bitmap
WORDS = PAGE_CELLS // 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kind,n_refs", [("lsb", 1), ("msb", 2), ("sbr", 4),
                                         ("parity", 7)])
def test_mlc_sense_compiles(one_chip, kind, n_refs):
    text = _compile_text(
        lambda v, r: mlc_sense_kernel(v, r, kind=kind, n_refs=n_refs,
                                      interpret=False),
        _spec(one_chip, (PAGES, PAGE_CELLS), jnp.float32),
        _spec(one_chip, (MAX_REFS,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [2, 8, 32])
def test_sense_reduce_compiles(one_chip, n):
    text = _compile_text(
        lambda v, r: sense_reduce(v, r, kind="msb", sense_invert=False,
                                  op="and", interpret=False),
        _spec(one_chip, (n, PAGES, PAGE_CELLS), jnp.float32),
        _spec(one_chip, (MAX_REFS,), jnp.float32))
    assert "tpu_custom_call" in text


def test_sense_reduce_popcount_compiles(one_chip):
    text = _compile_text(
        lambda v, r, m: sense_reduce_popcount(v, r, m, kind="lsb",
                                              sense_invert=True, op="or",
                                              interpret=False),
        _spec(one_chip, (8, PAGES, PAGE_CELLS), jnp.float32),
        _spec(one_chip, (MAX_REFS,), jnp.float32),
        _spec(one_chip, (PAGES, WORDS), jnp.uint32))
    assert "tpu_custom_call" in text


def test_bitwise_reduce_compiles(one_chip):
    text = _compile_text(
        lambda s: bitwise_reduce(s, op="xor", invert=True, interpret=False),
        _spec(one_chip, (8, PAGES, WORDS), jnp.uint32))
    assert "tpu_custom_call" in text


def test_popcount_rows_compiles(one_chip):
    text = _compile_text(
        lambda w: popcount_rows(w, interpret=False),
        _spec(one_chip, (PAGES, WORDS), jnp.uint32))
    assert "tpu_custom_call" in text


def test_copyback_rows_compiles(one_chip, monkeypatch):
    """One realignment of a 92-page bitmap pair (TPC-H Q6 at 12M rows) as
    one program, from two 512-row die shards into a third."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # compiled
    from repro.api.backends import PallasBackend
    from repro.core.mcflash import ReadPlan
    from repro.core.vth_model import get_chip_model
    from repro.flash.device import _copyback_rows

    chip = get_chip_model()
    v0, v1, v2 = chip.vref_default
    plans = (ReadPlan("page_lsb", "lsb", (v1,), 1),
             ReadPlan("page_msb", "msb", (v0, v2), 2))
    n = 92
    shard = _spec(one_chip, (512, PAGE_CELLS), jnp.float32)
    text = _copyback_rows.lower(
        (shard, shard, shard), _spec(one_chip, (3 * n,), jnp.int32),
        _spec(one_chip, (2,), jnp.uint32), plans=plans,
        backend=PallasBackend(), encoding="mlc", chip=chip,
        n_pe=((0.0, n),)).compile().as_text()
    assert text.count("tpu_custom_call") == 2          # one sense per operand
