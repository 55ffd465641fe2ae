"""BENCHMARK.json keeps to the contract's limits, and every name in it finds
its files."""

import json
import re
import subprocess
import sys

import pytest

from benchmark import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


def test_top_level_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert isinstance(manifest["run_seconds"], int)
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert manifest["command"][1].startswith("benchmark/")
    n = len(manifest["workloads"])
    assert 1 <= n <= 24
    # a full check fits: 2 + 14 x 24 runs of run_seconds + 60, 2 x 90 a cell
    full = (2 + 14 * 24) * (manifest["run_seconds"] + 60) + 24 * 180 + 1200
    assert full <= 43200
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) <= max(1, n // 4)


def test_names_units_and_sources(manifest):
    names = []
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        names.append(m["name"])
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for group in ("configs", "workloads"):
        for e in manifest[group]:
            assert NAME.match(e["name"]) and 1 <= len(e["why"]) <= 200
            names.append(group + e["name"])
    assert len(names) == len(set(names))
    assert "setup_s" in {m["name"] for m in manifest["end_to_end"]}
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)


def test_every_configuration_is_used_and_states_what_it_changed(manifest):
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("benchmark/")
        on_file = json.loads((ROOT / c["file"]).read_text())
        assert on_file["source"] == c["source"]
        assert on_file["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank")) and key not in (
                "n_embd", "n_head", "n_inner", "hidden_size")
        assert (harness.HERE / "reference"
                / f"{on_file['reference']}.py").exists()


def test_every_cell_finds_its_files_and_its_metrics(manifest):
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for w in manifest["workloads"]:
        cell = harness.load_cell(w["name"], manifest)
        kind = harness.load_named("kinds", cell.traffic["kind"])
        assert callable(kind.run)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, f"{w['name']} reports no per-layer metric"
        for m in cell.per_layer:
            assert m["moves"] in e2e and m["moves"] in reported, (w, m)
            assert callable(harness.load_reader(m["name"]))
        for limit in cell.limits.values():
            assert "limit" in limit
    for m in manifest["per_layer"]:
        assert any(m in harness.load_cell(w["name"], manifest).per_layer
                   for w in manifest["workloads"]), f"{m['name']}: no cell"


def test_reader_files_say_what_the_manifest_says(manifest):
    for m in manifest["per_layer"]:
        path = harness.HERE / "metrics" / f"{m['name']}.py"
        scope = {}
        exec(compile(path.read_text().split("def read")[0], str(path), "exec"),
             scope)
        assert (scope["NAME"], scope["UNIT"], scope["SOURCE"], scope["LAYER"],
                scope["MOVES"]) == (m["name"], m["unit"], m["source"],
                                    m["layer"], m["moves"])
    on_disk = {p.stem for p in (harness.HERE / "metrics").glob("*.py")}
    assert on_disk == {m["name"] for m in manifest["per_layer"]}


def test_kernel_and_whole_step_shares_are_named_as_such(manifest):
    names = {m["name"]: m for m in manifest["per_layer"]}
    for name, m in names.items():
        if name.endswith("_roofline") or "mfu" in name.split("_"):
            assert m["unit"] == "%"
    for name, m in names.items():
        if name.endswith("_roofline"):
            assert any("mfu" in other.split("_") and o["moves"] == m["moves"]
                       for other, o in names.items())


def _run_cli(cwd, env_extra):
    import os
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "gpt2-medium.train.seq1024", "--seed", "3000000000", "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_cli_refuses_to_measure_without_a_chip():
    done = _run_cli(ROOT, {})
    assert done.returncode != 0
    assert "no accelerator" in done.stderr
    assert '"correct"' not in done.stdout


def test_cli_prints_no_result_where_only_the_benchmark_is(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = _run_cli(tmp_path, {"PYTHONPATH": ""})
    assert done.returncode != 0
    assert '"correct"' not in done.stdout
