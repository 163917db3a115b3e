"""Each cell's run rehearsed on the CPU at 1 KiB pages with interpreted
kernels, through the harness's own internals (``run.run_cell``): the sound
program passes the comparison, and the program broken underneath, the
configuration's control included, fails it."""
import jax
import jax.numpy as jnp
import pytest

from bench import control, run
from repro.api import ComputeSession, PallasBackend

CELLS = ["userbitmap.dashboard", "imgcrypt.bulk"]


def _run(cell, seed=3, **kw):
    return run.run_cell(cell, seed, 0.5, False, jax.devices(), **kw)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(small_cell, name, capsys):
    cell = small_cell(name)
    result = _run(cell)
    assert result["correct"], result["check"]
    assert set(result["metrics"]) == {"requests_per_s", "latency_p95_ms",
                                      "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "check"
    assert " 0 compiles" in capsys.readouterr().err


def _altered_sense(monkeypatch):
    """Every sense kernel's first word comes out inverted."""
    sense = PallasBackend.sense

    def altered(self, vth, plan):
        out = sense(self, vth, plan)
        return out.at[0, 0].set(~out[0, 0])

    monkeypatch.setattr(PallasBackend, "sense", altered)


def _half_batch(monkeypatch):
    """Each batch dispatches only its first half; the rest never come."""
    batch = ComputeSession.materialize_batch_async

    def half(self, exprs, *, popcount=None, rids=None):
        k = max(1, len(exprs) // 2)
        return batch(self, exprs[:k], popcount=popcount[:k], rids=rids[:k])

    monkeypatch.setattr(ComputeSession, "materialize_batch_async", half)


@pytest.mark.parametrize("fault,number", [(_altered_sense, "wrong_bits"),
                                          (_half_batch, "missing_answers")])
@pytest.mark.parametrize("name", CELLS)
def test_broken_program_is_not_correct(small_cell, monkeypatch, name, fault,
                                       number):
    fault(monkeypatch)
    result = _run(small_cell(name))
    assert not result["correct"]
    assert result["check"][number]["value"] > 0
    assert result["failed"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(small_cell, name):
    """Worn blocks with recovery off break the configuration's fresh-block
    guarantee, and the comparison sees it."""
    cell = small_cell(name)
    result = _run(cell, seed=5,
                  session_kw=control.control_kw(cell.config, 5))
    assert not result["correct"]
    assert result["check"]["wrong_bits"]["value"] > 0
    assert result["check"]["missing_answers"]["value"] == 0


def test_lane_major_unpack_inverts_the_packing(rng):
    from bench.reference import lane_major_bits
    from repro.kernels import ref

    bits = (rng.random(3 * 4096) < 0.5).astype("uint8")
    words = ref.pack_bits(jnp.asarray(bits).reshape(1, -1))[0]
    assert (lane_major_bits(words) == bits).all()
