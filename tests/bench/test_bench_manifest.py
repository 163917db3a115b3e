"""``BENCHMARK.json`` against its contract, the command line without a chip,
and a configuration, traffic mix and metric added as files alone."""
import json
import re
import shutil
import subprocess
import sys

import jax
import pytest

from bench import generator, harness, layers, run

ROOT = harness.ROOT
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")


def test_manifest_keys_and_names():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    for path in MANIFEST["paths"]:
        assert (ROOT / path).is_dir()
    names = set()
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert (ROOT / c["file"]).with_suffix(".py").is_file()
        assert TEXT.match(c["why"]) and TEXT.match(c["source"])
        assert all(NAME.match(k) for k in c["reduced"])
        names.add(c["name"])
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert TEXT.match(w["why"])
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}


def test_every_per_layer_metric_has_a_reader_and_its_cells():
    cells = {w["name"] for w in MANIFEST["workloads"]}
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and TEXT.match(m["layer"])
        assert set(m["workloads"]) <= cells
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        if m["name"].endswith("_roofline"):
            kernel = m["name"][:-len("_roofline")]
            assert (ROOT / "bench" / "kernels" / f"{kernel}.py").is_file()
    for w in cells:
        layer = [m for m in MANIFEST["per_layer"] if w in m["workloads"]]
        assert layer and all(m["moves"] == "requests_per_s" or
                             m["moves"] == "latency_p95_ms" for m in layer)


def _cli(*args, cwd=ROOT):
    cmd = [sys.executable, "bench/run.py", "--workload",
           "userbitmap.dashboard", "--seed", str(2**31 + 7), "--seconds",
           "1", "--trace", "0", *args]
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_cli_without_a_tpu_prints_no_result():
    done = _cli()
    assert done.returncode == 2
    assert done.stdout == ""
    assert "no TPU" in done.stderr


def test_cli_with_only_the_benchmark_files_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = _cli(cwd=tmp_path)
    assert done.returncode != 0
    assert done.stdout == ""


def test_decks_give_every_seed_the_same_mix():
    mix = {"arrivals": {"loop": "closed", "clients": 1},
           "params": {"w": {"values": [8, 7], "counts": [2, 1]}},
           "requests": [{"popcount": True, "expr": [
               "and", {"vectors": "d", "param": "w", "scale": 7,
                       "offset": -1, "count": 3}]}]}
    draws = []
    for seed in (1, 2**31 + 5):
        t = generator.Traffic(mix, {}, seed)
        draws.append([t.next_group()[0][0][1] for _ in range(30)])
    for d in draws:
        assert sorted(d) == ["d48"] * 10 + ["d55"] * 20
    assert generator.Traffic(mix, {}, 1).every_group() == [
        [(("and", "d55", "d56", "d57"), True)],
        [(("and", "d48", "d49", "d50"), True)]]


def test_open_loop_gaps_are_the_same_for_every_seed():
    mix = {"arrivals": {"loop": "open", "rate_per_s": 50.0,
                        "burst": {"every_s": 1.0, "for_s": 0.25,
                                  "factor": 4.0}},
           "params": {}, "requests": []}
    gaps = []
    for seed in (3, 2**31 + 9):
        t = generator.Traffic(mix, {}, seed)
        at, steady = 0.5, []
        for _ in range(generator.GAPS):
            nxt = t.next_arrival(at)
            steady.append(round(nxt - at, 12))
        gaps.append(sorted(steady))
    assert gaps[0] == gaps[1]
    assert abs(sum(gaps[0]) / len(gaps[0]) - 1 / 50.0) < 1e-3
    burst = generator.Traffic(mix, {}, 3)
    inside = [burst.next_arrival(0.1) - 0.1 for _ in range(generator.GAPS)]
    assert abs(sum(inside) / len(inside) - 1 / 200.0) < 1e-3


def test_warm_up_serves_every_batch_the_loop_forms():
    mix = {"arrivals": {"loop": "closed", "clients": 2},
           "params": {"e": {"values": [1, 2, 3]}},
           "requests": [{"popcount": True, "expr": [
               "or", {"vectors": "d", "param": "e", "count": 2}]}] * 3}
    batches = harness.warm_batches(generator.Traffic(mix, {}, 1), cap=8)
    assert len(batches) == 3 + 3 * 3          # each report, each ordered pair
    assert sorted(map(len, batches)) == [3] * 3 + [6] * 9
    mix["arrivals"]["clients"] = 3            # 9 requests do not fit 8
    batches = harness.warm_batches(generator.Traffic(mix, {}, 1), cap=8)
    assert sorted(map(len, batches)) == [1, 1, 2, 3, 4, 5, 6, 7, 8]


def _add_cell(tmp_path, base, mix, metrics):
    """A copy of the benchmark with a tiny configuration, the mix ``mix``
    and the per-layer ``metrics`` (name -> reader source) added as files
    and manifest entries only; returns the new cell."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = dict(base, name="tiny-mlc", days=8)
    (tmp_path / "bench/configs/tiny-mlc.json").write_text(json.dumps(cfg))
    shutil.copy(ROOT / "bench/configs/userbitmap-mlc-16m.py",
                tmp_path / "bench/configs/tiny-mlc.py")
    (tmp_path / "bench/traffic/adhoc.json").write_text(json.dumps(mix))
    for name, source in metrics.items():
        (tmp_path / f"bench/metrics/{name}.py").write_text(source)
        manifest["per_layer"].append({"name": name, "unit": "requests",
                                      "better": "higher",
                                      "source": "program_counter",
                                      "layer": "serving engine",
                                      "moves": "requests_per_s",
                                      "workloads": ["tiny.adhoc"]})
    manifest["configs"].append({"name": "tiny-mlc", "source": "test",
                                "file": "bench/configs/tiny-mlc.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "tiny.adhoc", "config": "tiny-mlc",
                                  "traffic": "adhoc", "chips": 1,
                                  "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return harness.Cell.load("tiny.adhoc", root=tmp_path)


#: an open-loop, ad-hoc mix: each arrival is one of two templates, one of
#: them a chain whose length is drawn too
ADHOC = {"arrivals": {"loop": "open", "rate_per_s": 200.0},
         "pick": "one",
         "params": {"template": {"values": [0, 1], "counts": [1, 2]},
                    "p": {"values": [0, 1, 2]}, "n": {"values": [2, 3, 5]}},
         "requests": [
             {"popcount": False, "expr": [
                 "xor", {"vectors": "d", "param": "p", "scale": 2,
                         "count": 2}]},
             {"popcount": True, "expr": [
                 "and", {"vectors": "d", "param": "p", "count": "n"}]}]}


def test_new_config_mix_and_metric_need_only_new_files(tmp_path, small_cell):
    """A later change adds a deployment, an open-loop ad-hoc mix and a
    per-layer metric as new files and manifest entries; the harness finds
    them by name."""
    cell = _add_cell(tmp_path, small_cell("userbitmap.dashboard").config,
                     ADHOC, {"answers_per_batch": (
                         "def read(ctx):\n"
                         "    return len(ctx.window.records) / "
                         "ctx.batches\n")})
    assert [m["name"] for m in cell.per_layer] == ["answers_per_batch"]
    result = run.run_cell(cell, 9, 0.5, True, jax.devices(),
                          layers.peaks("TPU v5 lite"))
    assert result["correct"], result["check"]
    assert result["attempted"] > 20
    assert result["metrics"]["answers_per_batch"]["value"] >= 1


def test_declared_metric_that_reads_nothing_fails_the_run(tmp_path,
                                                          small_cell):
    cell = _add_cell(tmp_path, small_cell("userbitmap.dashboard").config,
                     ADHOC, {"never_read": "def read(ctx):\n    return None\n"})
    with pytest.raises(run.MetricNotRead, match="never_read"):
        run.run_cell(cell, 9, 0.3, True, jax.devices(),
                     layers.peaks("TPU v5 lite"))


def test_open_loop_with_half_of_each_batch_left_out_is_not_correct(
        tmp_path, small_cell, monkeypatch):
    """The open loop ends, with the requests that never went out counted
    missing, where the program drops half of each batch."""
    from repro.api import ComputeSession

    batch = ComputeSession.materialize_batch_async

    def half(self, exprs, *, popcount=None, rids=None):
        k = max(1, len(exprs) // 2)
        return batch(self, exprs[:k], popcount=popcount[:k], rids=rids[:k])

    cell = _add_cell(tmp_path, small_cell("userbitmap.dashboard").config,
                     ADHOC, {})
    dep = harness.Deployment(cell.config, cell.config_module, 4)
    monkeypatch.setattr(ComputeSession, "materialize_batch_async", half)
    window = harness.measure(dep, generator.Traffic(cell.mix, cell.config, 4),
                             0.3, 4)
    numbers = window.check(dep.bits)
    assert numbers["missing_answers"] > 0
    assert numbers["missing_answers"] + numbers["answers_compared"] == \
        len(window.records)
