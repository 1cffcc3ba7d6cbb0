"""The benchmark's files: found by name, shaped as the contract says."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import harness, testing
from chipbench.run import Cell

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_shape():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    for p in b["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/") and (ROOT / c["file"]).is_file()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    cells = {w["name"] for w in b["workloads"]}
    pairs = {(w["config"], w["traffic"]) for w in b["workloads"]}
    assert len(pairs) == len(cells)
    for w in b["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        mine = [m["name"] for m in b["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in b["per_layer"])


def test_every_name_has_its_files():
    b = bench()
    for w in b["workloads"]:
        cell = Cell(b, w["name"])
        assert cell.config["driver"] in ("sim", "serve")
        assert (ROOT / "chipbench" / "drivers"
                / f"{cell.config['driver']}.py").is_file()
    for m in b["per_layer"]:
        assert callable(harness.reader(m["name"]).read)


def test_new_cell_is_found_by_name_alone(tmp_path):
    """A cell added as data (a traffic file, an entry, a metric reader) is
    driven with no edit to any file the benchmark has."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = bench()
    tr = json.loads((ROOT / "chipbench/traffic/poisson-0.10-b256-greedy.json")
                    .read_text())
    tr["rate_per_stream"] = 0.14
    (tmp_path / "chipbench/traffic/poisson-0.14-b4-greedy.json").write_text(
        json.dumps(tr))
    (tmp_path / "chipbench/metrics/windows_run.py").write_text(
        "def read(ctx, out):\n    return float(out.counts['windows'])\n")
    b["workloads"].append({"name": "sim-paper8-greedy-0.14",
                           "config": "paper-8srv",
                           "traffic": "poisson-0.14-b4-greedy", "chips": 1,
                           "why": "a test cell"})
    b["per_layer"].append({"name": "windows_run", "unit": "windows",
                           "better": "higher", "source": "host_clock",
                           "layer": "stream window engine",
                           "moves": "sim_tasks_per_s",
                           "workloads": ["sim-paper8-greedy-0.14"]})
    cell = testing.tiny_cell("sim-paper8-greedy-0.14", b, tmp_path)
    assert cell.traffic["rate_per_stream"] == 0.14
    assert [m["name"] for m in cell.per_layer] == ["windows_run"]
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    ctx, out = testing.tiny_run(cell, seconds=0.3)
    assert testing.correct(out)
    assert harness.reader("windows_run", tmp_path).read(ctx, out) >= 1


def test_result_line_has_the_contract_keys():
    cell = testing.tiny_cell("sim-paper8-greedy")
    ctx, out = testing.tiny_run(cell, seconds=0.2)
    line = harness.result_line(cell, (ctx, out), False, ctx.devs)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert set(line["metrics"]) == {"setup_s", "sim_tasks_per_s"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert all(set(c) == {"name", "value", "limit"} for c in line["checks"])
    json.dumps(line)


def test_no_tpu_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "sim-paper8-eat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 3
    assert "{" not in p.stdout


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")
