"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The script itself refuses to run without a TPU; these tests drive its
phases directly (interpret-mode kernels, 1 KiB pages, 2^14 users) so that a
wrong path, request or reference shows up here before it costs chip time.
"""
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.flash.geometry import SSDConfig
from repro.kernels import ref

SMALL = SSDConfig(page_kb=1)
USERS = 1 << 14                      # two 1 KiB pages per daily bitmap


@pytest.fixture(scope="module")
def smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_to_run_without_a_tpu(smoke, capsys):
    assert jax.default_backend() == "cpu"
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""                       # no result line, no work
    assert "no TPU" in out.err


def test_lane_major_unpack_inverts_the_packing(smoke, rng):
    bits = (rng.random(3 * 4096) < 0.5).astype(np.uint8)
    words = ref.pack_bits(jnp.asarray(bits).reshape(1, -1))[0]
    np.testing.assert_array_equal(smoke.lane_major_bits(words, bits.size),
                                  bits)


def test_one_chip_phases_are_bit_exact(smoke, capsys):
    sessions = smoke.one_chip(USERS, 0, config=SMALL)
    assert [s.encoding for s in sessions] == ["mlc", "tlc"]
    assert all(s.backend.interpret for s in sessions)    # CPU: interpreted
    out = capsys.readouterr().out
    mlc = json.loads(out.split("mlc: ", 1)[1].splitlines()[0])
    assert mlc["requests_completed"] == 34
    assert mlc["batches"] == 3                          # 8 + 8 + a lone root
    assert mlc["executable_misses_second_pass"] == 0
    assert mlc["megakernel_calls"] > 0
    assert "bit-exact: 37 requests" in out


def test_served_mix_covers_every_kernel(smoke):
    """The request mix reaches grouped senses, both megakernels, the packed
    reduce and the popcount."""
    from repro.api import ComputeSession, PallasBackend
    from repro.api import backends

    calls = {}

    class Counting(PallasBackend):
        pass

    for name in ("sense", "reduce", "popcount", "sense_reduce",
                 "sense_reduce_popcount"):
        def wrap(self, *a, _name=name, **k):
            calls[_name] = calls.get(_name, 0) + 1
            return getattr(backends.PallasBackend, _name)(self, *a, **k)
        setattr(Counting, name, wrap)

    days = smoke.make_days(USERS, smoke.DAYS, 0)
    reqs = smoke.requests(days)
    sess = ComputeSession(config=SMALL, backend=Counting())
    smoke.load(sess, days)
    results, _ = smoke.serve(sess, reqs)
    smoke.compare(reqs, results, USERS)
    assert set(calls) == {"sense", "reduce", "popcount", "sense_reduce",
                          "sense_reduce_popcount"}, calls


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs 4 host devices (run under XLA_FLAGS="
                           "--xla_force_host_platform_device_count=4)")
def test_four_chip_phase_matches_single_device(smoke, capsys):
    smoke.four_chips(USERS, 0, config=SMALL)
    assert "arena shards on devices [0, 1, 2, 3]" in capsys.readouterr().out
