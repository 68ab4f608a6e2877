"""Telemetry subsystem: recorder semantics, sinks, trace export, load views.

Pure-Python units (fake clock, hand-built event streams) plus the two
integration invariants the subsystem is built around:

- recorder-on/off **bit-parity**: attaching a Recorder to the batch service
  changes no result bit (host-side recording at dispatch boundaries only;
  the 4-device variant of this assertion lives in
  ``repro.service.sharded_selftest`` via ``test_sharded_service.py``);
- the live-telemetry imbalance equals the offline
  ``DistributedResult.mean_imbalance()`` on the same run — the fig-4b
  number is one computation, whichever path reports it.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.config import QuadratureConfig
from repro.core.integrands import get_param
from repro.service import BatchScheduler, GracefulScheduler, QuadRequest
from repro.telemetry import (
    NULL,
    JsonlSink,
    MemorySink,
    Recorder,
    ServiceStats,
    read_jsonl,
    summary_table,
    to_chrome,
    write_chrome_trace,
)
from repro.telemetry import loadview
from repro.telemetry.check import check_metrics, check_trace

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FAMILY = get_param("genz_gaussian")


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# --- recorder core -----------------------------------------------------------


def test_span_nesting_ordering_and_durations():
    clock = FakeClock()
    sink = MemorySink()
    rec = Recorder(sinks=(sink,), clock=clock)
    with rec.span("outer", lane=None) as outer:
        clock.advance(1.0)
        with rec.span("inner", lane=2, it=7):
            clock.advance(0.5)
        clock.advance(0.25)
        outer["executed"] = 3
    kinds = [(e["kind"], e["name"]) for e in sink.events]
    assert kinds == [
        ("span_begin", "outer"),
        ("span_begin", "inner"),
        ("span_end", "inner"),
        ("span_end", "outer"),
    ]
    begin_outer, begin_inner, end_inner, end_outer = sink.events
    assert begin_outer["depth"] == 0 and begin_inner["depth"] == 1
    assert end_inner["dur"] == 0.5 and end_inner["it"] == 7
    assert end_inner["lane"] == 2
    assert end_outer["dur"] == 1.75
    assert end_outer["executed"] == 3  # body-added attr rides on span_end
    assert [e["seq"] for e in sink.events] == [0, 1, 2, 3]
    # aggregates for the summary table
    assert rec.span_totals["outer"] == {"count": 1, "total_s": 1.75}


def test_counters_gauges_hists_aggregate():
    rec = Recorder(sinks=(MemorySink(),), clock=FakeClock())
    rec.count("service.admissions", 2)
    rec.count("service.admissions")
    rec.gauge("service.n_live", 5, lane=1)
    rec.gauge("service.n_live", 3, lane=1)
    rec.observe("dispatch_ms", 4.0)
    rec.observe("dispatch_ms", 6.0)
    assert rec.counters["service.admissions"] == 3
    assert rec.gauges["service.n_live[1]"] == 3  # last write wins
    assert rec.hists["dispatch_ms"] == {
        "count": 2,
        "sum": 10.0,
        "min": 4.0,
        "max": 6.0,
    }
    table = summary_table(rec)
    assert "service.admissions" in table and "dispatch_ms" in table


def test_null_recorder_is_inert():
    assert not NULL.enabled
    NULL.count("x")
    NULL.gauge("x", 1)
    with NULL.span("x") as sp:
        sp["attr"] = 1  # swallowed, not an error
    assert NULL.flow("x", 0, 1) == 0
    with pytest.raises(RuntimeError):
        NULL.add_sink(MemorySink())


# --- sinks -------------------------------------------------------------------


def test_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "m.jsonl")
    rec = Recorder(sinks=(JsonlSink(path),), clock=FakeClock())
    rec.count("a", 1)
    rec.gauge("b", np.float64(2.5), lane=np.int32(1))  # numpy scalars tolerated
    rec.event("c", note="hi")
    rec.close()
    events = read_jsonl(path)
    assert [e["kind"] for e in events] == ["counter", "gauge", "instant"]
    assert events[1]["value"] == 2.5 and events[1]["lane"] == 1
    assert events[2]["note"] == "hi"
    assert check_metrics(path) == []


# --- chrome trace export -----------------------------------------------------


def _synthetic_run_events():
    clock = FakeClock()
    sink = MemorySink()
    rec = Recorder(sinks=(sink,), clock=clock)
    rec.event("service.start", backend="cubature")
    for it in range(3):
        with rec.span("service.dispatch", it=it):
            clock.advance(0.01)
        for dev in range(2):
            rec.gauge("service.n_live", 2 - dev, lane=dev, it=it + 1)
    rec.flow("service.migrate", 0, 1, req_id=5)
    rec.count("service.iterations", 3)
    return sink.events


def test_chrome_trace_schema_valid(tmp_path):
    path = str(tmp_path / "trace.json")
    write_chrome_trace(path, _synthetic_run_events())
    assert check_trace(path, n_devices=2, expect_flow=True) == []
    doc = json.load(open(path))
    events = doc["traceEvents"]
    phases = {e["ph"] for e in events}
    assert {"M", "B", "E", "i", "C", "s", "f"} <= phases
    for e in events:
        assert "pid" in e and "tid" in e
        if e["ph"] != "M":
            assert "ts" in e
    # balanced B/E per lane
    opens = {}
    for e in events:
        key = (e["pid"], e["tid"])
        if e["ph"] == "B":
            opens[key] = opens.get(key, 0) + 1
        elif e["ph"] == "E":
            opens[key] -= 1
    assert all(v == 0 for v in opens.values()), opens


def test_chrome_trace_checker_flags_problems(tmp_path):
    path = str(tmp_path / "bad.json")
    with open(path, "w") as f:
        json.dump(
            {
                "traceEvents": [
                    {"ph": "B", "name": "x", "pid": 1, "tid": 1, "ts": 0}
                ]
            },
            f,
        )
    problems = check_trace(path, n_devices=1, expect_flow=True)
    assert any("unclosed" in p for p in problems)
    assert any("device 0" in p for p in problems)
    assert any("flow" in p for p in problems)


def test_checker_expect_flow_name(tmp_path):
    path = str(tmp_path / "trace.json")
    write_chrome_trace(path, _synthetic_run_events())
    assert check_trace(path, expect_flow_name="service.migrate") == []
    problems = check_trace(path, expect_flow_name="service.evacuate")
    assert any("service.evacuate" in p for p in problems), problems


def test_attrs_cannot_clobber_event_envelope():
    """Regression: rec.flow(..., kind="readmit") once overwrote the event's
    own "kind" field, silently turning both flow halves into unknown-typed
    events every consumer dropped.  The envelope must win for all emitters."""
    sink = MemorySink()
    rec = Recorder(sinks=(sink,), clock=FakeClock())
    rec.count("c", kind="evil", ts=99)
    rec.gauge("g", 1.0, kind="evil")
    rec.observe("h", 1.0, kind="evil")
    rec.event("i", kind="evil")
    with rec.span("s", kind="evil") as sp:
        sp["kind"] = "evil"  # body attrs ride span_end, envelope still wins
    rec.flow("f", 0, 1, kind="evil", id=-1)
    kinds = [e["kind"] for e in sink.events]
    assert kinds == [
        "counter",
        "gauge",
        "hist",
        "instant",
        "span_begin",
        "span_end",
        "flow_begin",
        "flow_end",
    ]
    assert [e["name"] for e in sink.events][:1] == ["c"]
    assert sink.events[0]["ts"] == 0.0
    assert sink.events[-1]["id"] == sink.events[-2]["id"] == 1


# --- load views --------------------------------------------------------------


def test_imbalance_matches_dist_step_formula():
    assert loadview.imbalance([4, 4, 4, 4]) == 0.0
    assert loadview.imbalance([8, 0, 0, 0]) == pytest.approx(1 - 2 / 8)
    assert loadview.imbalance([0, 0]) == 0.0  # all-idle iteration
    assert loadview.imbalance([]) == 0.0


def test_idle_fraction_on_hand_built_timeline():
    # 2 devices x 3 iterations, 4 slots per device
    events = []
    series = {0: [4, 4, 2], 1: [4, 0, 0]}
    for it in range(3):
        for dev in (0, 1):
            events.append(
                {
                    "kind": "gauge",
                    "name": "service.n_live",
                    "ts": float(it),
                    "seq": len(events),
                    "lane": dev,
                    "value": series[dev][it],
                    "it": it,
                }
            )
    tl = loadview.occupancy_from_events(events)
    assert tl.devices == [0, 1] and tl.iterations == [0, 1, 2]
    assert tl.series(0) == [4, 4, 2] and tl.series(1) == [4, 0, 0]
    idle = loadview.idle_fraction(tl, slots_per_device=4)
    assert idle[0] == pytest.approx(1 - 10 / 12)
    assert idle[1] == pytest.approx(1 - 4 / 12)
    imb = loadview.imbalance_series(tl)
    assert imb[0] == 0.0
    assert imb[1] == pytest.approx(1 - 2 / 4)
    assert loadview.mean_imbalance(tl) == pytest.approx(sum(imb) / 3)


# --- ServiceStats ------------------------------------------------------------


def test_service_stats_add_merge_round_trip():
    a = ServiceStats()
    a.add("admissions", 3)
    a.add("migrations")
    b = ServiceStats(iterations=5, admissions=1)
    a.merge(b)
    assert a.admissions == 4 and a.iterations == 5 and a.migrations == 1
    assert ServiceStats.from_dict(a.as_dict()) == a


def test_service_stats_drift_guard():
    # missing keys default (old snapshots), unknown keys are loud (drift)
    assert ServiceStats.from_dict({"admissions": 2}).admissions == 2
    with pytest.raises(ValueError, match="frobnications"):
        ServiceStats.from_dict({"frobnications": 1})
    with pytest.raises(AttributeError):
        ServiceStats().add("frobnications")


def test_service_stats_elastic_counters_in_schema():
    """The device-loss counters are first-class schema fields: they round-trip
    through from_dict (so GracefulScheduler's field-wise merge aggregates
    them) and appear in every pool's stats dict."""
    s = ServiceStats.from_dict(
        {
            "dispatch_retries": 2,
            "evacuations": 4,
            "mesh_shrinks": 1,
            "mesh_regrows": 1,
        }
    )
    assert (s.dispatch_retries, s.evacuations, s.mesh_shrinks, s.mesh_regrows) == (
        2,
        4,
        1,
        1,
    )
    merged = ServiceStats()
    merged.merge(s)
    merged.merge(s)
    assert merged.evacuations == 8 and merged.mesh_shrinks == 2
    assert {"dispatch_retries", "evacuations", "mesh_shrinks", "mesh_regrows"} <= set(
        s.as_dict()
    )


# --- bit-parity: recorder on vs off ------------------------------------------


def _cfg(**kw):
    base = dict(
        d=2,
        integrand="genz_gaussian",
        rel_tol=1e-4,
        capacity=1 << 9,
        batch_slots=4,
        max_iters=60,
        sync_every=4,
    )
    base.update(kw)
    return QuadratureConfig(**base)


def _requests(n, seed=0):
    rng = np.random.default_rng(seed)
    return [
        QuadRequest(req_id=i, theta=FAMILY.sample_theta(2, rng))
        for i in range(n)
    ]


def _tuples(results):
    return [
        (
            r.req_id,
            float(r.integral).hex(),
            float(r.error).hex(),
            r.status,
            r.iterations,
            r.n_evals,
            r.admitted_at,
            r.finished_at,
        )
        for r in sorted(results, key=lambda r: r.req_id)
    ]


def test_recorder_on_off_bit_parity_single_device():
    off = list(BatchScheduler(_cfg(), FAMILY).serve(_requests(6)))
    rec = Recorder(sinks=(MemorySink(),))
    on = list(BatchScheduler(_cfg(), FAMILY, recorder=rec).serve(_requests(6)))
    assert _tuples(on) == _tuples(off)
    assert rec.counters["service.collections"] == 6


def test_graceful_recorder_parity_and_stats_view():
    off_sched = GracefulScheduler(_cfg(), FAMILY)
    off = list(off_sched.serve(_requests(5)))
    sink = MemorySink()
    on_sched = GracefulScheduler(_cfg(), FAMILY, recorder=Recorder(sinks=(sink,)))
    on = list(on_sched.serve(_requests(5)))
    assert _tuples(on) == _tuples(off)
    assert on_sched.last_stats == off_sched.last_stats  # compat dict view
    assert set(on_sched.last_stats) == {
        f.name for f in __import__("dataclasses").fields(ServiceStats)
    }
    assert any(e["name"] == "service.drain" for e in sink.events)


def test_scheduler_records_per_device_occupancy():
    sink = MemorySink()
    sched = BatchScheduler(_cfg(), FAMILY, recorder=Recorder(sinks=(sink,)))
    list(sched.serve(_requests(6)))
    tl = loadview.occupancy_from_events(sink.events)
    assert tl.devices == [0]  # single-device pytest process
    assert len(tl.iterations) > 0
    assert max(tl.series(0)) <= 4  # never exceeds slots per device
    idle = loadview.idle_fraction(tl, slots_per_device=4)
    assert 0.0 <= idle[0] < 1.0


# --- distributed imbalance: live telemetry == offline statistic --------------


def test_distributed_imbalance_telemetry_matches_offline():
    """The fig-4b number is one computation: the mean of the recorded
    ``dist.work_imb`` gauges must equal ``DistributedResult.mean_imbalance()``
    on the same run (2 virtual devices, subprocess so XLA_FLAGS applies)."""
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = ('--xla_force_host_platform_device_count=2 '"
        " + os.environ.get('XLA_FLAGS', ''))\n"
        "import jax, json\n"
        "jax.config.update('jax_enable_x64', True)\n"
        "from repro.core.config import QuadratureConfig\n"
        "from repro.core.distributed import integrate_distributed\n"
        "from repro.telemetry import MemorySink, Recorder\n"
        "from repro.telemetry.loadview import mean_work_imbalance_from_events\n"
        "sink = MemorySink()\n"
        "cfg = QuadratureConfig(d=3, integrand='f6', rel_tol=1e-5,"
        " capacity=1 << 12, max_iters=100)\n"
        "res = integrate_distributed(cfg, recorder=Recorder(sinks=(sink,)))\n"
        "print('RESULT_JSON:' + json.dumps({\n"
        "    'offline': res.mean_imbalance(),\n"
        "    'telemetry': mean_work_imbalance_from_events(sink.events),\n"
        "    'n': len(res.history), 'status': res.status}))\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=600,
        cwd=_REPO,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT_JSON:")]
    out = json.loads(line[-1][len("RESULT_JSON:") :])
    assert out["n"] > 0 and out["status"] == "converged"
    # np.mean (pairwise) vs pure-python mean (sequential): identical values,
    # summation order may differ in the last ulp
    assert out["telemetry"] == pytest.approx(out["offline"], rel=1e-12, abs=1e-15)


# --- histogram quantiles + scheduler dispatch-latency histograms -------------


def test_quantile_function():
    from repro.telemetry import quantile

    assert quantile([], 0.5) == 0.0
    assert quantile([3.0], 0.0) == quantile([3.0], 1.0) == 3.0
    vals = [4.0, 1.0, 3.0, 2.0]
    assert quantile(vals, 0.5) == 2.5  # linear interpolation, order-free
    assert quantile(vals, 0.0) == 1.0 and quantile(vals, 1.0) == 4.0
    assert quantile(vals, 0.25) == 1.75
    with pytest.raises(ValueError):
        quantile(vals, 1.5)


def test_recorder_hist_quantiles_and_summary_columns():
    rec = Recorder(sinks=(MemorySink(),), clock=FakeClock())
    for v in (1.0, 2.0, 3.0, 4.0, 100.0):
        rec.observe("lat_s", v)
    assert rec.quantile("lat_s", 0.5) == 3.0
    assert rec.quantile("missing", 0.5) == 0.0
    assert rec.quantile("lat_s", 0.99) > rec.quantile("lat_s", 0.5)
    table = summary_table(rec)
    assert "p50" in table and "p99" in table
    # NullRecorder mirrors the API inertly
    assert NULL.quantile("lat_s", 0.5) == 0.0
    assert NULL.quantile("lat_s", 0.99) == 0.0


def test_hist_sample_cap_keeps_aggregates_exact():
    from repro.telemetry.core import HIST_SAMPLE_CAP

    rec = Recorder(sinks=(), clock=FakeClock())
    n = HIST_SAMPLE_CAP + 100
    for i in range(n):
        rec.observe("x", float(i))
    # aggregates see every value; the quantile sample is the first N
    assert rec.hists["x"]["count"] == n
    assert rec.hists["x"]["max"] == float(n - 1)
    assert len(rec.hist_samples["x"]) == HIST_SAMPLE_CAP
    assert rec.quantile("x", 1.0) == float(HIST_SAMPLE_CAP - 1)


def test_scheduler_records_dispatch_latency_histograms():
    sink = MemorySink()
    rec = Recorder(sinks=(sink,))
    list(BatchScheduler(_cfg(), FAMILY, recorder=rec).serve(_requests(6)))
    # one wall-time sample per executed dispatch, recorded host-side at the
    # dispatch boundary (DESIGN.md §9); queue-wait has one fewer sample
    # (it measures the gap since the *previous* dispatch)
    wall = loadview.hist_values_from_events(sink.events, "service.dispatch_wall_s")
    wait = loadview.hist_values_from_events(sink.events, "service.queue_wait_s")
    n_dispatch = rec.counters["service.dispatches"]
    assert len(wall) == n_dispatch > 0
    assert len(wait) == n_dispatch - 1
    assert all(v > 0 for v in wall)
    assert all(v >= 0 for v in wait)
    # live aggregates match the event stream (same samples, same math)
    from repro.telemetry import quantile

    assert rec.quantile("service.dispatch_wall_s", 0.5) == quantile(wall, 0.5)
    assert rec.hists["service.dispatch_wall_s"]["count"] == len(wall)
