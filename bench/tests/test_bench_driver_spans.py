"""The readers of the drivers' program spans: ``solve.program_s`` from the
recorder's span totals, ``solve.idle_program_share`` from the spans as
profiler annotations beside the device's idle gaps.  Each returns ``None``
where its input is absent, as on a program that has no such span."""

import json
import os

import pytest

import benchtest
from harness import cells, loops, trace


def _reader(name):
    path = os.path.join(benchtest.BENCH, "metrics", f"{name}.py")
    return cells.load_module(path, "test_reader_" + name.replace(".", "_"))


program_s = _reader("solve.program_s")
idle_program_share = _reader("solve.idle_program_share")


def _run(solves=(), summary=None):
    run = loops.RunRecord(cell=None, seed=0, seconds=1.0)
    run.solves = list(solves)
    run.trace = summary
    return run


def _plane(name, **lines):
    return trace.Plane(name, {k: [trace.Event(*e) for e in v] for k, v in lines.items()})


def test_program_s_is_the_mean_per_solve_of_both_drivers_spans():
    solves = [
        loops.Solve(0, 1, None, {"core.eval": 5.0, "core.program": 1.5}),
        loops.Solve(1, 2, None, {"dist.dispatch": 4.0, "dist.program": 0.5}),
    ]
    assert program_s.read(_run(solves)) == pytest.approx(1.0)


def test_program_s_is_none_without_program_spans():
    assert program_s.read(_run()) is None
    # a program without the spans (the recorder's totals hold only the others)
    assert program_s.read(_run([loops.Solve(0, 1, None, {"core.eval": 5.0})])) is None


def _summary(host_lines, busy=((100, 200), (500, 300))):
    ops = [(f"fusion.{i}", s, d) for i, (s, d) in enumerate(busy)]
    quiet = _plane("/device:TPU:1", **{"XLA Ops": [("fusion.9", 0, 50)]})
    dev = _plane("/device:TPU:0", **{"XLA Ops": ops})
    return trace.reduce_planes([dev, quiet, _plane("/host:CPU", **host_lines)], 1000)


def test_idle_program_share_on_synthetic_planes():
    # the busiest chip is idle in [0, 100), [300, 500) and [800, 1000)
    host = {
        "driver": [
            ("bench.solve", 0, 1000),
            ("core.eval", 50, 400),
            ("core.program", 50, 100),  # idle over [50, 100)
            ("core.dispatch", 150, 100),
            ("core.program", 280, 40),  # idle over [300, 320)
            ("core.program", 400, 200),  # idle over [400, 500)
            ("core.sync", 600, 50),
            ("dist.program", 850, 300),  # idle over [850, 1000): clipped
        ],
        # another thread's spans are not the driver's
        "other": [("core.program", 0, 1000)],
    }
    s = _summary(host)
    assert idle_program_share.read(_run(summary=s)) == pytest.approx(
        100.0 * (50 + 20 + 100 + 150) / 1000
    )
    # the idle gaps are now named by the spans
    idle = dict(s.idle_by_host())
    assert idle["core.program"] == pytest.approx(300e-9)
    assert idle["dist.program"] == pytest.approx(200e-9)


def test_idle_program_share_counts_nested_spans_once():
    host = {"driver": [("bench.solve", 0, 1000), ("core.program", 0, 100),
                       ("core.program", 20, 30)]}
    s = _summary(host)
    assert idle_program_share.read(_run(summary=s)) == pytest.approx(10.0)


def test_idle_program_share_is_none_without_its_inputs():
    assert idle_program_share.read(_run()) is None
    no_device = trace.reduce_planes([_plane("/host:CPU", t=[("bench.solve", 0, 10)])], 100)
    assert idle_program_share.read(_run(summary=no_device)) is None
    # a program without the spans: only the benchmark's own annotation
    parent = _summary({"driver": [("bench.solve", 0, 1000)]})
    assert idle_program_share.read(_run(summary=parent)) is None


def test_traced_dry_run_reads_the_program_spans(tmp_path):
    benchtest.tiny_suite(tmp_path)
    path = os.path.join(str(tmp_path), "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    for m in spec["per_layer"]:
        if m["name"] in ("solve.program_s", "solve.idle_program_share"):
            m["workloads"].append("tiny_f4.solve")
    with open(path, "w") as f:
        json.dump(spec, f)
    suite = cells.Suite(str(tmp_path))
    result = benchtest.run_dry(suite, "tiny_f4.solve", seconds=0.5, trace=True)
    assert result["correct"] is True, result["checks"]
    assert result["metrics"]["solve.program_s"]["value"] > 0
    # the CPU writes no device plane: the share is left out, never 0
    assert "solve.idle_program_share" not in result["metrics"]
