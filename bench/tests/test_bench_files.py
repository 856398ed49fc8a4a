"""Every configuration, traffic mix and metric is found by name, the names
and units keep to their alphabet, and a new cell needs new files and a new
``BENCHMARK.json`` entry only."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.harness import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_benchmark_keys_and_alphabet():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [c["name"] for c in BENCH["configs"]] + CELLS + [
        m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= set(CELLS)
    texts = [c["why"] for c in BENCH["configs"] + BENCH["workloads"]] + [
        c["source"] for c in BENCH["configs"]] + [
        m["layer"] for m in BENCH["per_layer"]] + BENCH["command"]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for k in c["reduced"]:
            assert NAME.match(k)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load_by_name(cell):
    c = harness.load_cell(cell)
    assert c.traffic["mode"] in ("replay", "live")
    assert {"setup_s", "records_per_s"} <= {m["name"] for m in c.end_to_end}
    assert c.per_layer
    for m in c.per_layer:
        assert callable(harness.load_reader(m["name"]))
    assert set(c.config["limits"]) >= {"rows_wrong", "pr_err", "cut_gap",
                                       "measure_err", "vet_job_err"}


def test_a_new_cell_needs_new_files_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCH))
    cfg = json.loads((ROOT / bench["configs"][0]["file"]).read_text())
    cfg["streams"] = 99
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "burst.json").write_text(json.dumps(
        {**json.loads((ROOT / "bench" / "traffic" / "replay.json")
                      .read_text()), "strides_per_tick": 3}))
    (root / "bench" / "metrics" / "ticks.tiny.py").write_text(
        "def read(ctx):\n    return ctx.ticks\n")
    bench["configs"].append({**bench["configs"][0], "name": "tiny",
                             "file": "bench/configs/tiny.json"})
    bench["workloads"].append({"name": "tiny.burst", "config": "tiny",
                               "traffic": "burst", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "ticks.tiny", "unit": "ticks",
                               "better": "lower", "source": "program_span",
                               "layer": "mux plan", "moves": "setup_s",
                               "workloads": ["tiny.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("tiny.burst", root)
    assert cell.config["streams"] == 99
    assert cell.traffic["strides_per_tick"] == 3
    assert [m["name"] for m in cell.per_layer] == ["ticks.tiny"]
    assert harness.load_reader("ticks.tiny", root)(
        type("Ctx", (), {"ticks": 4})()) == 4
    with pytest.raises(KeyError):
        harness.load_cell("no.such", root)


TWO_SHARDS = r'''
import json, sys
from pathlib import Path
root, src = Path(sys.argv[1]), sys.argv[2]
sys.path[:0] = [src, str(root)]
import jax
from bench import harness
built = []


def build(cfg):
    built.append(harness.build_mux(cfg))
    return built[-1]


cell = harness.load_cell("two.replay", root)
res = harness.run_cell(cell, seed=2 ** 31 + 3, seconds=1.0, build=build)
mux = built[0]
print(json.dumps({
    "correct": res.correct, "checks": res.checks,
    "shards": mux.n_shards,
    "streams": [len(list(mux.shard(k).ids())) for k in range(mux.n_shards)],
    "used": [str(d) for d in harness.devices_used(mux, jax.devices())],
    "ran_on": [str(e.result_device) for e in harness.engines(mux)],
    "count": res.device["count"]}))
'''


def _two_shard_checkout(tmp_path, chips):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCH))
    cfg = json.loads((ROOT / bench["configs"][0]["file"]).read_text())
    cfg.update({"shards": 2, "chips": 2, "streams": 6, "windows": [8, 16],
                "check": {"sample_windows": 8}})
    (root / "bench" / "configs" / "two.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "small.json").write_text(json.dumps(
        {**json.loads((ROOT / "bench" / "traffic" / "replay.json")
                      .read_text()), "pool_records": 1 << 14}))
    bench["configs"].append({**bench["configs"][0], "name": "two",
                             "file": "bench/configs/two.json"})
    bench["workloads"].append({"name": "two.replay", "config": "two",
                               "traffic": "small", "chips": chips,
                               "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_sharded_cell_needs_new_files_only(tmp_path):
    """A configuration that states two shards runs as a ``ShardedVetMux``
    with shard ``k`` on device ``k``, from new files and entries alone (two
    CPU devices stand in for two chips)."""
    root = _two_shard_checkout(tmp_path, chips=2)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
    out = subprocess.run(
        [sys.executable, "-c", TWO_SHARDS, str(root), str(ROOT / "src")],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["correct"], got["checks"]
    assert got["shards"] == 2 and sum(got["streams"]) == 6
    assert min(got["streams"]) > 0
    assert len(set(got["used"])) == 2 and got["count"] == 2
    assert len(set(got["ran_on"])) == 2


def test_a_cell_its_system_does_not_fill_is_refused(tmp_path):
    """A cell whose chips differ from its configuration's is refused when
    it is loaded, and one whose system runs on fewer devices than it asks
    for, before its set-up."""
    root = _two_shard_checkout(tmp_path, chips=1)
    with pytest.raises(ValueError, match="chips"):
        harness.load_cell("two.replay", root)
    root = _two_shard_checkout(tmp_path / "b", chips=2)
    cell = harness.load_cell("two.replay", root)
    one = cell._replace(config={**cell.config, "shards": 1})
    with pytest.raises(ValueError, match="runs on 1"):
        harness.run_cell(one, seed=1, seconds=0.1)
