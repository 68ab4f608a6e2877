"""Unit tests of the yardstick: work counts, peaks, the trace reduction,
the comparison, the traffic sampler, the readers and BENCHMARK.json."""

import glob
import json
import math
import os
import re
import statistics

import numpy as np
import pytest

import benchtest
from harness import cells, check, loops, peaks, trace, work


@pytest.mark.parametrize("d,nodes", [(2, 17), (5, 93), (8, 401)])
def test_gm_nodes(d, nodes):
    assert work.gm_nodes(d) == nodes
    assert work.gm_nodes(d) == 2**d + 2 * d * d + 2 * d + 1


def test_gm_work_counts_from_shapes():
    flops, nbytes = work.gm_work(5, 1000, flops_per_point=17, itemsize=8)
    assert flops == 1000 * (93 * 17 + work.gm_rule_flops(5))
    # centre + half-widths read, estimate + error written (f64), axis (i32),
    # two mask bytes
    assert nbytes == 1000 * (2 * 5 * 8 + 2 * 8 + 4 + 2)
    assert work.gm_work(5, 2000, 17, 8) == (2 * flops, 2 * nbytes)
    assert work.gm_work(5, 1000, 17, 4)[1] < nbytes


def test_peaks_of_v5e():
    p = peaks.lookup("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5"])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.lookup(kind)


def _plane(name, **lines):
    return trace.Plane(
        name, {k: [trace.Event(*e) for e in v] for k, v in lines.items()}
    )


def test_reduce_planes_busy_gaps_programs_collectives():
    dev = _plane(
        "/device:TPU:0",
        **{
            "XLA Modules": [("jit_eval_step(7)", 100, 300), ("jit_advance(9)", 500, 200)],
            "XLA Ops": [
                ("fusion.1", 100, 200),
                ("fusion.2", 150, 250),  # overlaps fusion.1
                ("all-reduce.3", 500, 50),
                ("collective-permute-done.1", 540, 60),
                ("sort.4", 600, 100),
            ],
        },
    )
    other = _plane("/device:TPU:1", **{"XLA Ops": [("fusion.1", 0, 100)]})
    host = _plane("/host:CPU", python=[("bench.solve", 0, 1000), ("dispatch", 400, 90)],
                  runtime=[("ReadSyncFlag", 420, 10)])  # another thread: not read
    s = trace.reduce_planes([dev, other, host, _plane("/host:metadata")], 1000)
    d0, d1 = s.devices
    assert d0.busy_ns == 300 + 200  # [100, 400) and [500, 700)
    assert d0.collective_ns == 100  # [500, 600) as one union
    assert d0.gaps == [(0.0, 100), (400, 100), (700, 300)]
    assert d0.programs == {"jit_eval_step": 300, "jit_advance": 200}
    assert s.program_s("jit_eval_step") == 300e-9
    assert d1.busy_ns == 100
    assert math.isclose(s.busy_s, 300e-9)
    assert s.window_s == 1e-6
    top = dict(s.top_ops(3))
    assert top["jit_eval_step/fusion.2"] == pytest.approx(250 / 2 / 1e9)
    assert "?/fusion.1" in dict(s.top_ops(10))  # device 1 ran it outside a module
    # the gap [400, 500) is named by the innermost host event at its middle
    idle = dict(s.idle_by_host())
    assert idle == {"bench.solve": pytest.approx(400e-9), "dispatch": pytest.approx(100e-9)}


def test_reduce_planes_clips_to_window_and_skips_idle_devices():
    dev = _plane("/device:TPU:0", **{"XLA Ops": [("f", -50, 100), ("g", 900, 300)]})
    idle = _plane("/device:TPU:1", **{"XLA Ops": []})
    s = trace.reduce_planes([dev, idle], 1000)
    assert [d.index for d in s.devices] == [0]
    assert s.devices[0].busy_ns == 50 + 100
    assert s.devices[0].ops == {"?/f": 50, "?/g": 100}


def test_host_activity_names_the_innermost_event():
    host = [trace.Event(*e) for e in [("outer", 0, 100), ("inner", 10, 20),
                                      ("later", 50, 10), ("overlap", 55, 30)]]
    got = trace.host_activity(host, [15, 40, 52, 70, 99, 150])
    assert got == ["inner", "outer", "later", "overlap", "outer", "no host event"]
    assert trace.host_activity([], [5]) == ["no host event"]


@pytest.mark.parametrize("hlo,name", [
    ("%fusion.11 = (f32[1048576,5]{0,1:T(8,128)}) fusion(f32[1048576,5] %get-tuple-element.1)",
     "fusion.11"),
    ("%all-reduce.3 = f32[] all-reduce(f32[] %x), replica_groups={}", "all-reduce.3"),
    ("fusion.2", "fusion.2"),
])
def test_op_name(hlo, name):
    assert trace.op_name(hlo) == name


def test_a_fusion_reading_a_collective_is_not_a_collective():
    ops = [("%fusion.1 = f32[] fusion(f32[] %all-reduce.3)", 0, 10),
           ("%all-reduce.3 = f32[] all-reduce(f32[] %x)", 10, 5)]
    s = trace.reduce_planes([_plane("/device:TPU:0", **{"XLA Ops": ops})], 100)
    assert s.devices[0].collective_ns == 5


def test_cpu_recorded_trace_reduces(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.probe"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    s = trace.summarize(str(tmp_path), window_ns=1e12)
    # the CPU backend writes no device plane: nothing to call busy
    assert s.devices == [] and s.busy_s == 0.0
    assert "bench.probe" in {e.name for e in s.host}
    assert len(glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)) == 1


def _answer(status="converged", integral=1.0, exact=1.0):
    return check.Answer(integral, 1e-9, status, exact)


@pytest.mark.parametrize(
    "answers,share",
    [
        ([_answer(), _answer(integral=1 + 5e-8)], 0.0),
        ([_answer(), _answer(integral=1 - 2e-7)], 0.5),  # certified, but wrong
        ([_answer(), _answer("capacity", 1.0)], 0.5),  # right, not certified
        ([_answer("no_active", 0.5)], 1.0),
        ([_answer(integral=float("nan"))], 1.0),
    ],
)
def test_failed_share(answers, share):
    # the result line's ``failed``: answers not certified within rel_tol
    assert check.failed(answers, 1e-7) == share * len(answers)


@pytest.mark.parametrize(
    "answers,false,uncertified",
    [
        ([_answer(), _answer(integral=1 + 5e-8)], 0, 0.0),
        ([_answer(), _answer(integral=1 - 2e-7)], 1, 0.0),  # certified, but wrong
        ([_answer(), _answer("capacity", 1.0)], 0, 0.5),  # right, not certified
        ([_answer("no_active", 0.5)], 0, 1.0),
        ([_answer(integral=float("nan"))], 1, 0.0),
    ],
)
def test_false_certified_and_uncertified_share(answers, false, uncertified):
    got = check.compare(answers, rel_tol=1e-7, limits={"uncertified_share": 0.0})
    assert [c.name for c in got] == ["false_certified", "uncertified_share"]
    assert [c.value for c in got] == [false, uncertified]
    assert [c.ok for c in got] == [false == 0, uncertified == 0.0]
    assert check.failed(answers, 1e-7) == false + uncertified * len(answers)


def test_no_answer_is_not_correct():
    got = check.compare([], rel_tol=1e-7, limits={"uncertified_share": 1.0})
    assert [c.value for c in got] == [None, None] and not any(c.ok for c in got)


def test_failed_share_within_the_stated_limit():
    answers = [_answer()] * 39 + [_answer("capacity", 2.0)]
    limits = {"uncertified_share": 0.05}
    assert all(c.ok for c in check.compare(answers, 1e-7, limits))
    assert not check.compare(answers[-3:], 1e-7, limits)[1].ok
    # one false certification among many fails, whatever the share's limit
    wrong = answers + [_answer(integral=1.1)]
    assert not check.compare(wrong, 1e-7, {"uncertified_share": 1.0})[0].ok
    assert "certified=39" in check.describe(answers)


def _fleet_loop():
    return cells.load_module(os.path.join(benchtest.BENCH, "loops", "closed_fleet.py"),
                             "bench_loop_closed_fleet")


def test_latin_hypercube_stratifies_every_column():
    fleet = _fleet_loop()
    rng = np.random.default_rng(2**31 + 3)
    sets = fleet.latin_hypercube(rng, 64, {"a": (3.0, 10.0), "u": (0.2, 0.8)}, 5)
    assert len(sets) == 64
    a = np.stack([s["a"] for s in sets])  # (64, 5)
    strata = np.floor((a - 3.0) / 7.0 * 64).astype(int)
    for col in strata.T:
        assert sorted(col) == list(range(64))
    again = fleet.latin_hypercube(np.random.default_rng(2**31 + 3), 64,
                                  {"a": (3.0, 10.0), "u": (0.2, 0.8)}, 5)
    assert all(np.array_equal(x["u"], y["u"]) for x, y in zip(sets, again))


def _fleet_run(tmp_path):
    suite = benchtest.tiny_suite(tmp_path)
    run = loops.RunRecord(cell=suite.cell("tiny_gauss.closed4"), seed=1, seconds=10.0)
    run.slots, run.window_start, run.window_end = 2, 100.0, 110.0
    run.requests = [
        loops.Request(0, {}, submit=95.0, done=101.0),  # 1 s inside
        loops.Request(1, {}, submit=100.5, done=104.5),
        loops.Request(2, {}, submit=101.0, done=115.0),  # answered after
        loops.Request(3, {}, submit=104.5, done=None),  # in flight
    ]
    return suite, run


def test_fleet_readers(tmp_path):
    suite, run = _fleet_run(tmp_path)
    read = {m.name: m.reader.read for m in suite.cell("tiny_gauss.closed4").per_layer}
    e2e = {m.name: m.reader.read for m in suite.cell("tiny_gauss.closed4").end_to_end}
    assert e2e["fleet_req_per_s"](run) == 2 / 10.0
    lat = [6.0, 4.0]
    assert e2e["fleet_latency_p90_s"](run) == statistics.quantiles(
        lat, n=10, method="inclusive")[8]
    # held: 1 + 4 + 9 + 5.5 slot-seconds of 2 slots x 10 s
    assert read["fleet.occupancy"](run) == pytest.approx(100 * 19.5 / 20)


@pytest.mark.parametrize(
    "submit,unanswered",
    [
        (90.0, 1.0),  # due 14 s before the close, never answered: lost
        (97.0, 0.0),  # in flight for less than the worst latency: not due
    ],
)
def test_fleet_unanswered_counts_lost_requests(tmp_path, submit, unanswered):
    _, run = _fleet_run(tmp_path)
    fleet = _fleet_loop()
    # the worst latency is 14 s (request 2): due are requests submitted by
    # 110 - 14 = 96, and request 0 (95) was answered
    assert fleet.checks(run)[0].value == 0.0
    run.requests.append(loops.Request(4, {}, submit=submit, done=None))
    got = fleet.checks(run)[0]
    assert got.name == "unanswered" and got.value == unanswered
    assert got.ok is (unanswered == 0.0)


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(benchtest.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        with open(os.path.join(benchtest.ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["quadrature"]["dtype"] == "float64"
        assert os.path.exists(os.path.join(
            benchtest.BENCH, "references", f"{body['integrand']}.py"))
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for w in spec["workloads"]:
        assert name.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        with open(os.path.join(benchtest.BENCH, "traffic", f"{w['traffic']}.json")) as f:
            loop = json.load(f)["loop"]
        assert os.path.exists(os.path.join(benchtest.BENCH, "loops", f"{loop}.py"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert name.match(m["name"]) and m["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(benchtest.BENCH, "metrics", f"{m['name']}.py"))
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in spec["workloads"]}
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(1, len(spec["workloads"]) // 2)
