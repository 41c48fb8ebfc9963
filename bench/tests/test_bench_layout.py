"""BENCHMARK.json against the benchmark's contract, and discovery by
name: a cell, a traffic mix and a per-layer metric written into new
files are found without editing a file that is already there."""
import json
import pathlib
import re
import subprocess
import sys

import pytest

from bench import compare, run

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_top_level_keys():
    assert set(SPEC) == KEYS
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def _names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            yield group, entry


@pytest.mark.parametrize("group,entry", list(_names()),
                         ids=lambda x: x if isinstance(x, str) else x["name"])
def test_names_and_units(group, entry):
    assert NAME.match(entry["name"]), entry["name"]
    if "unit" in entry:
        assert UNIT.match(entry["unit"]), entry["unit"]
        assert entry["better"] in ("lower", "higher")
    if group == "workloads":
        assert NAME.match(entry["config"]) and NAME.match(entry["traffic"])
        assert entry["chips"] in (1, 4)
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    if group == "configs":
        assert all(NAME.match(k) for k in entry["reduced"])
        cfg = json.loads((ROOT / entry["file"]).read_text())
        assert cfg["name"] == entry["name"]
        assert cfg["source"] == entry["source"]
        assert cfg["reduced"] == entry["reduced"]
    if group in ("end_to_end", "per_layer"):
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    if group == "end_to_end":
        assert 0.01 <= entry["bound"] <= 0.25
        assert entry["source"] in ("device_trace", "host_clock")
    if group == "per_layer":
        assert entry["moves"] in {m["name"] for m in SPEC["end_to_end"]}
        assert (ROOT / "bench" / "metrics" / f"{entry['name']}.py").is_file()


def test_names_unique():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names)), group
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads(cell):
    c = run.load_cell(cell)
    held = c.limits.keys() & set(compare.NUMBERS)
    assert held >= {"update1", "change3"} and held & {"loss", "loss1"}
    assert {m["name"] for m in c.end_to_end} >= {"setup_s", "rounds_per_s"}
    assert c.per_layer


def test_new_cell_and_metric_are_found_from_new_files(tmp_path):
    spec = dict(SPEC)
    spec["workloads"] = SPEC["workloads"] + [{
        "name": "lenet5.p2-fedavg-k20", "config": "lenet5-cifar10",
        "traffic": "p2-fedavg-paper-k20", "chips": 1, "why": "test"}]
    spec["per_layer"] = SPEC["per_layer"] + [{
        "name": "rounds_seen", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "round engine",
        "moves": "rounds_per_s", "workloads": ["lenet5.p2-fedavg-k20"]}]
    bench_json = tmp_path / "BENCHMARK.json"
    bench_json.write_text(json.dumps(spec))
    extra = tmp_path / "bench"
    for d in ("traffic", "cells", "metrics"):
        (extra / d).mkdir(parents=True)
    t = json.loads((ROOT / "bench/traffic/p2-fedavg-paper.json").read_text())
    t["fl"]["participation"] = 0.2
    (extra / "traffic/p2-fedavg-paper-k20.json").write_text(json.dumps(t))
    (extra / "cells/lenet5.p2-fedavg-k20.json").write_text(
        json.dumps({"loss": 1e-3, "update1": 1e-2, "change3": 1e-2}))
    (extra / "metrics/rounds_seen.py").write_text(
        "def read(ctx):\n    return ctx.rounds\n")

    cell = run.load_cell("lenet5.p2-fedavg-k20", bench_json,
                         [extra, run.BENCH])
    assert cell.traffic["fl"]["participation"] == 0.2
    assert cell.config["name"] == "lenet5-cifar10"
    assert [m["name"] for m in cell.per_layer] == ["rounds_seen"]
    reader = run.load_module(cell.dirs, "metrics", "rounds_seen")
    assert reader.read(type("Ctx", (), {"rounds": 24})) == 24
    # the cell already in the benchmark still reads its own files
    old = run.load_cell("lenet5.p2-fedavg", bench_json, [extra, run.BENCH])
    assert old.traffic["fl"]["participation"] == 0.1
    assert "rounds_seen" not in [m["name"] for m in old.per_layer]


def _run(args, cwd):
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          capture_output=True, text=True, timeout=120,
                          env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                               "HOME": str(cwd)})


def test_cpu_run_exits_nonzero_and_prints_no_result(tmp_path):
    cell = SPEC["workloads"][0]["name"]
    p = _run(["--workload", cell, "--seed", "2147483901", "--seconds", "1",
              "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "nothing was measured" in p.stderr


def test_bench_files_alone_run_nothing(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = SPEC["workloads"][-1]["name"]
    p = _run(["--workload", cell, "--seed", "3", "--seconds", "1"], tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
