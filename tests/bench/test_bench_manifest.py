"""BENCHMARK.json against the benchmark's contract, and the command's
refusal to run without a TPU."""

from __future__ import annotations

import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")


@pytest.fixture(scope="module")
def manifest():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "bench/run.py"]
    assert 1 <= manifest["run_seconds"] <= 51
    for p in manifest["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert (ROOT / p).is_dir()
    assert (ROOT / "bench" / "run.py").exists()


def test_names_units_and_text(manifest):
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[group]]
        assert len(names) == len(set(names)), group
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert TEXT.match(str(entry[key])), entry[key]
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for c in manifest["configs"]:
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])


def test_entry_keys(manifest):
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_files_resolve_by_name(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    for c in manifest["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert sorted(data["reduced"]) == sorted(c["reduced"])
    for w in manifest["workloads"]:
        assert w["config"] in configs
        cell = json.loads((BENCH / "workloads" / f"{w['name']}.json")
                          .read_text())
        assert cell["config"] == w["config"]
        assert cell["traffic"] == w["traffic"]
        assert cell["chips"] == w["chips"]
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        src = (BENCH / "metrics" / f"{m['name']}.py").read_text()
        assert "def read(r)" in src


def _reported(manifest, cell: str) -> set:
    return {m["name"] for m in manifest["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}


def test_every_cell_reports_enough(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for cell in cells:
        rep = _reported(manifest, cell)
        assert "setup_s" in rep and len(rep) >= 2
        layer = [m for m in manifest["per_layer"]
                 if "workloads" not in m or cell in m["workloads"]]
        assert layer, cell
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert m["moves"] in _reported(manifest, cell), (m, cell)


def test_layers_named_alike(manifest):
    layers = {m["layer"] for m in manifest["per_layer"]}
    assert layers <= {"device", "step", "engine", "scheduler",
                      "collectives"}


def test_four_chip_cells_at_most_half(manifest):
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 2)


def test_limits_and_plain_check_names():
    for path in (BENCH / "workloads").glob("*.json"):
        cell = json.loads(path.read_text())
        assert cell["limits"], path
        for k, v in cell["limits"].items():
            assert NAME.match(k) and v >= 0


def _env():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_run_refuses_the_cpu():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "olmo-1b.train.seq2048", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=_env(), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_run_needs_the_program(tmp_path):
    """A tree holding only BENCHMARK.json and the benchmark's paths does
    not run: the benchmark measures the program, it is not one."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "olmo-1b.train.seq2048", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=_env(), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
