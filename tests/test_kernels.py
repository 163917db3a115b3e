"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from repro.testing.hypothesis_compat import given, settings, st

from repro.api import PallasBackend
from repro.core import mcflash, vth_model
from repro.kernels import ops, ref
from repro.kernels.fused import sense_reduce, sense_reduce_popcount
from repro.kernels.mlc_sense import mlc_sense as mlc_sense_kernel


@pytest.mark.parametrize("rows", [8, 16])
@pytest.mark.parametrize("cols", [4096, 8192])
@pytest.mark.parametrize("kind", ["lsb", "msb", "sbr"])
def test_mlc_sense_shape_sweep(rows, cols, kind, rng):
    vth = jnp.asarray(rng.normal(2.0, 2.0, (rows, cols)).astype(np.float32))
    refs = jnp.asarray([0.1, 3.7, 1.9, 5.5], jnp.float32)
    got = ops.mlc_sense(vth, refs, kind=kind)
    want = ref.mlc_sense(vth, refs, kind)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.slow
@pytest.mark.parametrize("rows", [40])
@pytest.mark.parametrize("cols", [16384])
@pytest.mark.parametrize("kind", ["lsb", "msb", "sbr"])
def test_mlc_sense_shape_sweep_full(rows, cols, kind, rng):
    vth = jnp.asarray(rng.normal(2.0, 2.0, (rows, cols)).astype(np.float32))
    refs = jnp.asarray([0.1, 3.7, 1.9, 5.5], jnp.float32)
    got = ops.mlc_sense(vth, refs, kind=kind)
    want = ref.mlc_sense(vth, refs, kind)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("invert", [False, True])
def test_mlc_sense_invert(invert, rng):
    vth = jnp.asarray(rng.normal(2.0, 2.0, (8, 4096)).astype(np.float32))
    refs = jnp.asarray([1.9, 0, 0, 0], jnp.float32)
    got = ops.mlc_sense(vth, refs, kind="lsb", invert=invert)
    want = ref.mlc_sense(vth, refs, "lsb", invert)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mlc_sense_row_padding(rng):
    """Non-multiple-of-8 rows are padded and sliced back."""
    vth = jnp.asarray(rng.normal(2.0, 2.0, (5, 4096)).astype(np.float32))
    got = ops.mlc_sense(vth, [1.9, 0, 0, 0], kind="lsb")
    assert got.shape == (5, 128)


def _top_bit_tile(rng) -> np.ndarray:
    """(8, 4096) bits, random except that bit 31 of every packed word (cell
    columns 31*128 .. 32*128-1) is set."""
    bits = (rng.random((8, 4096)) < 0.5).astype(np.uint8)
    bits[:, 31 * 128:] = 1
    return bits


@pytest.mark.parametrize("kernel", ["mlc_sense", "sense_reduce",
                                    "sense_reduce_popcount"])
def test_pack_keeps_bit_31(kernel, rng):
    """The kernels pack in int32 and bitcast: bit 31 must survive in every
    word, exactly as the unsigned reference packs it."""
    bits = _top_bit_tile(rng)
    vth = jnp.asarray(np.where(bits == 1, -1.0, 1.0).astype(np.float32))
    refs = jnp.zeros((1,), jnp.float32)              # lsb: bit = vth < 0
    want = np.asarray(ref.pack_bits(jnp.asarray(bits)))
    assert (want >> 31 == 1).all()
    if kernel == "mlc_sense":
        got = mlc_sense_kernel(vth, refs, kind="lsb", interpret=True)
    elif kernel == "sense_reduce":
        got = sense_reduce(vth[None], refs, kind="lsb", sense_invert=False,
                           op="and", interpret=True)
    else:
        mask = jnp.full(want.shape, 0x80000000, jnp.uint32)   # bit 31 only
        got = sense_reduce_popcount(vth[None], refs, mask, kind="lsb",
                                    sense_invert=False, op="and",
                                    interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.full(8, 128))
        return
    np.testing.assert_array_equal(np.asarray(got), want)


def test_interpret_follows_the_backend():
    """On the CPU backend the kernels interpret; asking for compiled kernels
    there is an error, not a silent fallback."""
    assert jax.default_backend() == "cpu"
    assert ops.resolve_interpret(None) is True
    assert ops.resolve_interpret(True) is True
    with pytest.raises(RuntimeError, match="interpret=False"):
        ops.resolve_interpret(False)
    with pytest.raises(RuntimeError, match="interpret=False"):
        PallasBackend(interpret=False)
    assert PallasBackend().interpret is True


def test_pack_unpack_roundtrip(rng):
    bits = (rng.random((16, 8192)) < 0.5).astype(np.uint8)
    packed = ref.pack_bits(jnp.asarray(bits))
    np.testing.assert_array_equal(np.asarray(ref.unpack_bits(packed)), bits)


@pytest.mark.parametrize(
    "n_ops", [2, 3, 8, pytest.param(16, marks=pytest.mark.slow)])
@pytest.mark.parametrize("op", ["and", "or", "xor"])
def test_bitwise_reduce_sweep(n_ops, op, rng):
    stack = jnp.asarray(rng.integers(0, 2**32, (n_ops, 16, 512),
                                     dtype=np.uint64).astype(np.uint32))
    got = ops.bitwise_reduce(stack, op=op)
    want = ref.bitwise_reduce(stack, op)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bitwise_reduce_odd_shapes(rng):
    stack = jnp.asarray(rng.integers(0, 2**32, (3, 5, 130),
                                     dtype=np.uint64).astype(np.uint32))
    got = ops.bitwise_reduce(stack, op="xor")
    want = ref.bitwise_reduce(stack, "xor")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_popcount_vs_numpy(rng):
    words = jnp.asarray(rng.integers(0, 2**32, (24, 1024),
                                     dtype=np.uint64).astype(np.uint32))
    got = ops.popcount_rows(words)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.unpackbits(np.asarray(words).view(np.uint8), axis=1).sum(1))


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**32 - 1), st.integers(0, 2**32 - 1))
def test_popcount_word_property(a, b):
    words = jnp.asarray(np.array([[a, b] * 256], dtype=np.uint32))
    got = int(ops.popcount_rows(words)[0])
    assert got == 256 * (bin(a).count("1") + bin(b).count("1"))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_sense_plan_equals_core_path_property(seed):
    """Kernel-path sensing == pure-jnp core path for every op (random data)."""
    chip = vth_model.get_chip_model()
    key = jax.random.PRNGKey(seed)
    lsb = jax.random.bernoulli(key, 0.5, (8, 4096)).astype(jnp.uint8)
    msb = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, (8, 4096)).astype(jnp.uint8)
    vth, _ = vth_model.program_page(jax.random.fold_in(key, 2),
                                    lsb.reshape(-1), msb.reshape(-1), chip)
    vth = vth.reshape(8, 4096)
    for op in ("and", "or", "xnor", "not"):
        plan = mcflash.plan_op(op, chip)
        packed = ops.sense_plan(vth, plan)
        core_bits = mcflash.execute_plan(plan, vth)
        np.testing.assert_array_equal(np.asarray(ref.unpack_bits(packed)),
                                      np.asarray(core_bits))
