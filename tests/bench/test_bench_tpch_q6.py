"""The ``tpch.q6`` cell at CPU-test size: the bitmap index against the
generated rows, the cell's run (sound and under its control), and Q6's
revenue assembled from the served counts against the row-level SQL
reference."""
import copy
import dataclasses
import itertools

import jax
import numpy as np
import pytest

from bench import control, generator, harness, run

#: ``conftest.SMALL`` has no ``rows``: 8192 rows fill one 1 KiB page
ROWS = 8192


@pytest.fixture
def q6_cell(small_cell):
    cell = small_cell("tpch.q6")
    cfg = copy.deepcopy(cell.config)
    cfg["rows"] = ROWS
    return dataclasses.replace(cell, config=cfg)


def test_each_bitmap_indexes_the_generated_rows_exactly(q6_cell):
    mod = q6_cell.config_module
    rows = mod.columns(q6_cell.config, 2**31 + 11)
    pairs, bits = mod.make_vectors(q6_cell.config, 2**31 + 11)
    names = [n for pair in pairs for n in pair]
    assert names == list(bits) and len(names) == 92
    assert all(b.dtype == np.uint8 and b.shape == (ROWS,)
               for b in bits.values())
    year = rows["l_shipdate"].astype("datetime64[Y]").astype(int) + 1970
    for prefix, column, values in (
            ("y", year, range(1992, 1999)),
            ("disc", rows["l_discount"], range(0, 11)),
            ("qty", rows["l_quantity"], range(1, 51))):
        onehot = np.stack([bits[f"{prefix}{v}"] for v in values])
        assert (onehot.sum(axis=0) == 1).all()      # every row in one bitmap
        assert (np.asarray(values)[onehot.argmax(axis=0)] == column).all()
    price = sum(bits[f"price{i}"].astype(np.int64) << i for i in range(24))
    assert (price == rows["l_extendedprice"]).all()
    assert rows["l_extendedprice"].max() < 2**24


def test_sound_run_is_correct_without_compiles_in_the_window(q6_cell,
                                                             capsys):
    result = run.run_cell(q6_cell, 3, 0.5, False, jax.devices())
    assert result["correct"], result["check"]
    assert result["attempted"] >= 72 and result["failed"] == 0
    assert " 0 compiles" in capsys.readouterr().err


def test_control_is_not_correct(q6_cell):
    result = run.run_cell(q6_cell, 5, 0.5, False, jax.devices(),
                          session_kw=control.control_kw(q6_cell.config, 5))
    assert not result["correct"]
    assert result["check"]["wrong_bits"]["value"] > 0
    assert result["check"]["missing_answers"]["value"] == 0


def test_revenue_from_served_counts_equals_q6_for_every_substitution(
        q6_cell):
    """All 16 substitution sets served through the engine; the client's
    revenue from each query's 72 counts equals the SQL over the rows."""
    seed = 7
    mod = q6_cell.config_module
    rows = mod.columns(q6_cell.config, seed)
    dep = harness.Deployment(q6_cell.config, mod, seed)
    traffic = generator.Traffic(q6_cell.mix, q6_cell.config, seed)
    names = list(traffic.decks)
    combos = [dict(zip(names, c)) for c in itertools.product(
        *(traffic.decks[n].values for n in names))]
    assert len(combos) == 16
    eng = dep.engine
    revenues = set()
    for env in combos:
        group = traffic.group(env)
        tickets = [eng.submit(dep.vector(e), popcount=p) for e, p in group]
        counts = eng.drain(tickets)
        terms = [(int(e[2][len("disc"):]), int(e[4][len("price"):]), c)
                 for (e, _), c in zip(group, counts)]
        got = mod.revenue(terms)
        assert got == mod.q6_revenue(rows, env["y"], env["d"],
                                     env["qn"] + 1), env
        revenues.add(got)
    assert len(revenues) == 16 and min(revenues) > 0
