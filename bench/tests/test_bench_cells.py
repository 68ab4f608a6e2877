"""Cells added only as files are found by name and run through the
harness's dry path on the CPU; the command refuses a machine with no TPU
and a directory with no program."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import benchtest


@pytest.fixture(scope="module")
def suite(tmp_path_factory):
    return benchtest.tiny_suite(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize(
    "cell,e2e",
    [
        ("tiny_f4.solve", {"setup_s", "solve_s"}),
        ("tiny_gauss.closed4", {"setup_s", "fleet_req_per_s", "fleet_latency_p90_s"}),
    ],
)
def test_cell_added_as_files_runs(suite, cell, e2e):
    result = benchtest.run_dry(suite, cell, seconds=0.5)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == e2e
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize(
    "cell,layer",
    [
        ("tiny_f4.solve", {"solve.iterations", "solve.eval_s", "solve.advance_s"}),
        ("tiny_gauss.closed4", {"fleet.occupancy"}),
    ],
)
def test_traced_run_reports_per_layer_metrics(suite, cell, layer):
    result = benchtest.run_dry(suite, cell, seconds=0.5, trace=True)
    assert result["correct"] is True, result["checks"]
    # the CPU has no device plane: the device readers find nothing and
    # their metrics are left out, never reported as 0
    assert set(result["metrics"]) == layer
    assert result["device"]["busy_s"] == 0.0 and result["device"]["window_s"] > 0


NEW_LOOP = """
from harness import loops
from harness.check import Check


def run(record, devices, trace, compiles, t0):
    from repro.core import integrate

    loops.solve_window(record, trace, compiles, t0,
                       lambda cfg, rec: integrate(cfg, recorder=rec))


answers = loops.solve_answers


def checks(record):
    return [Check("solves", float(len(record.solves)), 1e9)]
"""


def test_traffic_loop_added_as_a_file_runs(tmp_path):
    suite = benchtest.tiny_suite(tmp_path)
    with open(tmp_path / "bench" / "loops" / "tiny_new.py", "w") as f:
        f.write(NEW_LOOP)
    with open(tmp_path / "bench" / "traffic" / "tiny_new.json", "w") as f:
        json.dump({"loop": "tiny_new"}, f)
    spec = suite.spec
    spec["workloads"].append({"name": "tiny_f4.new", "config": "tiny_f4",
                              "traffic": "tiny_new", "chips": 1, "why": "a test"})
    for m in spec["end_to_end"]:
        if m["name"] == "solve_s":
            m["workloads"].append("tiny_f4.new")
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    result = benchtest.run_dry(benchtest.Suite(str(tmp_path)), "tiny_f4.new")
    assert result["correct"] is True, result["checks"]
    assert list(result["checks"]) == ["false_certified", "uncertified_share", "solves"]
    assert set(result["metrics"]) == {"setup_s", "solve_s"}


def _run_py(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "f4_d5.solve",
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_command_refuses_a_machine_without_tpu():
    proc = _run_py(benchtest.ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_command_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(benchtest.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(benchtest.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_every_configured_cell_resolves():
    suite = benchtest.Suite(benchtest.ROOT)
    with open(os.path.join(benchtest.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        cell = suite.cell(w["name"])
        assert cell.chips == w["chips"]
        assert {m.name for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer, w["name"]
        for m in cell.end_to_end + cell.per_layer:
            assert callable(m.reader.read)
