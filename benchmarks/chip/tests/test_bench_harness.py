"""The harness finds every part of a cell by name from files, refuses to
run without a TPU, and ``BENCHMARK.json`` names only files that exist."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from fqabench import harness
from fqabench.traffic import Mix

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_finds_a_cell_by_name_from_files_alone(tmp_path):
    """A new configuration, mix, limit, reference and per-layer metric
    are picked up by adding files, with no edit to the harness."""
    for sub in ("configs", "traffic", "limits", "reference",
                "layer_metrics"):
        (tmp_path / sub).mkdir()
    (tmp_path / "configs" / "toy-1b.json").write_text(
        json.dumps({"name": "toy-1b", "reference": "toy_ref"}))
    (tmp_path / "traffic" / "burst.json").write_text(json.dumps({
        "kind": "open_loop", "rate_per_s": 1.0, "n_slots": 2,
        "cache_len": 64, "check_requests": 2,
        "prompt": {"dist": "uniform", "min": 9, "max": 16},
        "output": {"dist": "uniform", "min": 2, "max": 8}}))
    (tmp_path / "limits" / "toy-1b.burst.json").write_text(
        json.dumps({"logit_err_max": {"limit": 0.5}}))
    (tmp_path / "reference" / "toy_ref.py").write_text("KIND = 'toy'\n")
    (tmp_path / "layer_metrics" / "toy.steps.count.py").write_text(
        "def read(run):\n    return len(run.steps) or None\n")

    assert harness.load_config(tmp_path, "toy-1b")["reference"] == "toy_ref"
    mix = harness.load_mix(tmp_path, "burst")
    assert isinstance(mix, Mix) and mix.n_slots == 2
    assert harness.load_limits(tmp_path, "toy-1b.burst")["logit_err_max"] == \
        {"limit": 0.5}
    assert harness.load_reference(tmp_path, "toy_ref").KIND == "toy"
    read = harness.load_reader(tmp_path, "toy.steps.count")
    run = harness.Run(None, {}, 1.0, [harness.Step(0, 1, 0, [], [3])],
                      None, None)
    assert read(run) == 1
    with pytest.raises(FileNotFoundError):
        harness.load_reader(tmp_path, "no.such.metric")


def test_cell_metrics_selects_by_workloads_key():
    spec = {"end_to_end": [{"name": "a"}, {"name": "b", "workloads": ["x"]}]}
    assert [m["name"] for m in harness.cell_metrics(spec, "x",
                                                    "end_to_end")] == ["a", "b"]
    assert [m["name"] for m in harness.cell_metrics(spec, "y",
                                                    "end_to_end")] == ["a"]


def test_benchmark_json_names_files_that_exist():
    assert SPEC["command"] == ["python3", "benchmarks/chip/run.py"]
    assert SPEC["paths"] == ["benchmarks/chip"]
    configs = {c["name"]: c for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        conf = harness.load_config(BENCH, c["name"])
        assert (ROOT / c["file"]).is_file()
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert (BENCH / "reference" / f"{conf['reference']}.py").is_file()
        program = harness.load_program(BENCH, conf)
        assert all(callable(getattr(program, f)) for f in
                   ("program_cfg", "program_params", "counts"))
    for w in SPEC["workloads"]:
        assert w["config"] in configs
        harness.load_mix(BENCH, w["traffic"])
        limits = harness.load_limits(BENCH, w["name"])
        assert limits and all(k in harness.READINGS
                              and "limit" in v for k, v in limits.items())
        e2e = [m["name"] for m in harness.cell_metrics(SPEC, w["name"],
                                                       "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.cell_metrics(SPEC, w["name"], "per_layer")
    for m in SPEC["per_layer"]:
        assert callable(harness.load_reader(BENCH, m["name"]))
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}


def test_names_units_and_bounds_keep_the_contract():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert 1 <= SPEC["run_seconds"] <= 51


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_exits_nonzero_and_prints_no_result():
    p = _run_cli(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_bare_copy_of_the_benchmark_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()
