"""The solver's spans, counters and named scopes (DESIGN.md §8).

- every span of an enabled recorder is also a profiler trace annotation;
- ``jax_events`` forwards JAX's build events as counters, by thread;
- ``RungPrograms`` counts the rungs it compiles ahead and those never used;
- the compiled programs carry the ``quad.*`` named scopes;
- the drivers name each host activity, and their answers do not change
  with the recorder on.

The four-device checks run in a child process, so that the virtual devices
are asked for before JAX starts.
"""

import contextlib
import json
import os
import re
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import QuadratureConfig, integrate, region_store
from repro.core.adaptive import make_advance_step, make_eval_step
from repro.core.programs import AHEAD, RungPrograms
from repro.core.rules import make_rule
from repro.telemetry import NULL, MemorySink, Recorder, jax_events
from repro.telemetry import monitoring

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeAnnotations:
    """Annotation factory that logs each open and close with its name."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class Annotation:
            def __enter__(self):
                log.append(("open", name))

            def __exit__(self, *exc):
                log.append(("close", name))

        return Annotation()


# --- spans as trace annotations -------------------------------------------


def test_each_span_opens_one_annotation_with_its_name_and_nesting():
    ann = FakeAnnotations()
    rec = Recorder(annotate=ann)
    with rec.span("outer", it=1) as sp:
        with rec.span("inner"):
            pass
        with rec.span("inner"):
            pass
        sp["k"] = 2
    assert ann.log == [
        ("open", "outer"),
        ("open", "inner"),
        ("close", "inner"),
        ("open", "inner"),
        ("close", "inner"),
        ("close", "outer"),
    ]
    assert rec.span_totals["inner"]["count"] == 2


def test_annotation_closes_when_the_body_raises():
    ann = FakeAnnotations()
    sink = MemorySink()
    rec = Recorder(sinks=(sink,), annotate=ann)
    with pytest.raises(ValueError):
        with rec.span("boom"):
            raise ValueError("x")
    assert ann.log == [("open", "boom"), ("close", "boom")]
    assert [e["kind"] for e in sink.events] == ["span_begin", "span_end"]


def test_null_recorder_opens_no_annotation(monkeypatch):
    from repro.telemetry import core

    ann = FakeAnnotations()
    monkeypatch.setattr(core, "trace_annotation", ann)
    with NULL.span("x"):
        pass
    assert ann.log == []


def test_default_annotation_lands_in_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    rec = Recorder()
    with rec.span("probe.outer"):
        with rec.span("probe.inner"):
            jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = [
        os.path.join(d, f)
        for d, _, fs in os.walk(tmp_path)
        for f in fs
        if f.endswith(".xplane.pb")
    ]
    names = {
        e.name
        for p in ProfileData.from_file(path).planes
        for line in p.lines
        for e in line.events
    }
    assert {"probe.outer", "probe.inner"} <= names


# --- JAX's build events as counters -----------------------------------------


def _counter_events(sink):
    return [e for e in sink.events if e["kind"] == "counter"]


def test_jax_events_forwards_trace_lower_build_and_cache_loads_by_thread():
    sink = MemorySink()
    rec = Recorder(sinks=(sink,))
    with jax_events(rec):
        jax.jit(lambda x: jnp.sin(x) * 3.0)(np.arange(5.0)).block_until_ready()
        # a build on another thread, as the ahead compiles do
        t = threading.Thread(
            target=lambda: jax.jit(lambda x: x - 7.0).lower(1.0).compile()
        )
        t.start()
        t.join(timeout=120)
        assert not t.is_alive()
        jax.monitoring.record_event(monitoring.CACHE_HIT_EVENT)
    by = {}
    for e in _counter_events(sink):
        by.setdefault((e["name"], e["thread"]), []).append(e["n"])
    for thread in ("driver", "other"):
        for name in ("jax.trace_s", "jax.lower_s", "jax.build_s"):
            assert sum(by[(name, thread)]) > 0, (name, thread)
        assert sum(by[("jax.builds", thread)]) == 1
    assert by[("jax.cache_loads", "driver")] == [1]
    assert rec.counters["jax.builds"] == 2


def test_jax_events_counts_nested_events_once():
    sink = MemorySink()
    rec = Recorder(sinks=(sink,))
    span = jax.monitoring.record_event_time_span
    with jax_events(rec):
        span(monitoring.TRACE_EVENT, 10.0, 11.0)  # a function's trace
        span(monitoring.TRACE_EVENT, 12.0, 12.5)  # these two are nested
        span(monitoring.TRACE_EVENT, 12.6, 12.8)  # in the trace after them
        span(monitoring.TRACE_EVENT, 11.5, 14.0)
        span(monitoring.LOWER_EVENT, 11.2, 15.0)  # encloses that trace
        span(monitoring.BUILD_EVENT, 15.0, 17.0)
    events = _counter_events(sink)
    assert [e["name"] for e in events] == ["jax.trace_s"] * 4 + [
        "jax.lower_s",
        "jax.build_s",
        "jax.builds",
    ]
    assert [e["n"] for e in events] == pytest.approx(
        [1.0, 0.5, 0.2, 2.5 - 0.7, 3.8 - 2.5, 2.0, 1]
    )
    # the three add up to the union of the intervals: [10, 11) and [11.2, 17)
    seconds = sum(rec.counters[k] for k in ("jax.trace_s", "jax.lower_s", "jax.build_s"))
    assert seconds == pytest.approx(1.0 + 5.8)


def test_counts_from_many_threads_are_not_lost():
    sink = MemorySink()
    rec = Recorder(sinks=(sink,))
    workers, each = min((os.cpu_count() or 4) + 2, 64), 500
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with jax_events(rec):
            threads = [
                threading.Thread(
                    target=lambda: [
                        jax.monitoring.record_event(monitoring.CACHE_HIT_EVENT)
                        for _ in range(each)
                    ]
                )
                for _ in range(workers)
            ]
            for t in threads:
                t.start()
            for _ in range(each):  # the driving thread records meanwhile
                rec.event("driver.tick")
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert rec.counters["jax.cache_loads"] == workers * each
    seqs = [e["seq"] for e in sink.events]
    assert sorted(seqs) == list(range(len(seqs))) == seqs


def test_jax_events_unregisters_on_exit_and_ignores_a_disabled_recorder():
    sink = MemorySink()
    rec = Recorder(sinks=(sink,))
    with jax_events(rec):
        pass
    with jax_events(NULL):
        jax.monitoring.record_event(monitoring.CACHE_HIT_EVENT)
    jax.monitoring.record_event_time_span(monitoring.BUILD_EVENT, 1.0, 2.0)
    jax.monitoring.record_event(monitoring.CACHE_HIT_EVENT)
    assert not sink.events and rec.counters == {}


# --- rungs compiled ahead ----------------------------------------------------


def test_rung_programs_count_ahead_and_unused_rungs():
    ladder = (8, 16, 32, 64, 128, 256)
    rec = Recorder()
    rungs = RungPrograms(ladder, lambda w: jax.jit(lambda x: x[:w].sum()), rec)
    x = jnp.arange(256.0)
    rungs(8, x)(x)
    assert rec.counters["core.programs.ahead"] == AHEAD  # 16, 32, 64
    rungs(16, x)(x)  # starts 128
    assert rec.counters["core.programs.ahead"] == AHEAD + 1
    for f in rungs._ahead.values():
        f.result(timeout=120)
    rungs.close()
    # 32, 64 and 128 were compiled ahead; the run stopped at 16
    assert rec.counters["core.programs.ahead_unused"] == 3


def test_rung_programs_count_nothing_without_a_recorder():
    rungs = RungPrograms((8, 16), lambda w: jax.jit(lambda x: x[:w].sum()))
    x = jnp.arange(16.0)
    rungs(8, x)(x)
    rungs.close()
    assert NULL.counters == {}


# --- named scopes in the compiled programs ----------------------------------


def _state_and_rule(capacity=1 << 10):
    cfg = QuadratureConfig(d=3, integrand="f4", rel_tol=1e-5, capacity=capacity)
    cfg = cfg.validate()
    lo, hi = np.asarray(cfg.lo(), float), np.asarray(cfg.hi(), float)
    state = region_store.init_state(
        cfg.capacity, lo, hi, cfg.resolved_n_init(), jnp.float64
    )
    return cfg, make_rule(cfg, None), state, lo, hi


def test_eval_and_advance_programs_carry_their_scopes():
    cfg, rule, state, lo, hi = _state_and_rule()
    eval_hlo = jax.jit(make_eval_step(cfg, rule, window=256)).lower(state).compile()
    adv = make_advance_step(cfg, float(np.prod(hi - lo)), hi - lo, window=512)
    adv_hlo = jax.jit(adv).lower(state).compile()
    metrics_hlo = jax.jit(lambda s: s.global_estimates(window=256)).lower(state).compile()
    assert "quad.eval" in eval_hlo.as_text()
    assert "quad.advance" in adv_hlo.as_text()
    assert "quad.advance/quad.estimates" in adv_hlo.as_text()
    assert "quad.estimates" in metrics_hlo.as_text()


def _instructions(compiled) -> list:
    """The compiled HLO's computations without the instructions' metadata."""
    lines = re.sub(r", metadata=\{[^}]*\}", "", compiled.as_text()).splitlines()
    return [l for l in lines if l.startswith(("%", "ENTRY", " "))]


def test_scopes_change_only_metadata(monkeypatch):
    cfg, rule, state, lo, hi = _state_and_rule()

    def programs():
        adv = make_advance_step(cfg, float(np.prod(hi - lo)), hi - lo, window=512)
        return [
            jax.jit(fn, donate_argnums=0).lower(state).compile()
            for fn in (make_eval_step(cfg, rule, window=256), adv)
        ]

    scoped = programs()
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = programs()
    assert not any("quad." in p.as_text() for p in bare)
    assert [_instructions(p) for p in bare] == [_instructions(p) for p in scoped]


# --- the drivers' spans and answers -----------------------------------------


def _answer(r):
    return (
        float(r.integral).hex(),
        float(r.error).hex(),
        r.status,
        r.iterations,
        r.n_evals,
        r.n_active,
    )


def test_integrate_names_each_host_activity_and_answers_alike():
    cfg = QuadratureConfig(d=3, integrand="f4", rel_tol=1e-5, capacity=1 << 12)
    off = integrate(cfg)
    sink = MemorySink()
    ann = FakeAnnotations()
    rec = Recorder(sinks=(sink,), annotate=ann)
    on = integrate(cfg, recorder=rec)
    assert _answer(on) == _answer(off)
    spans = rec.span_totals
    assert {"core.setup", "core.eval", "core.advance", "core.program",
            "core.dispatch", "core.sync", "core.finish"} <= set(spans)
    # per iteration: the eval and metrics programs, then the advance's
    iters = spans["core.eval"]["count"]
    assert spans["core.program"]["count"] == 2 * iters + spans["core.advance"]["count"]
    # the children sit inside core.eval and core.advance, one level down
    depth = {(e["name"], e["depth"]) for e in sink.events if e["kind"] == "span_end"}
    assert ("core.program", 1) in depth and ("core.sync", 1) in depth
    assert ("core.setup", 0) in depth and ("core.finish", 0) in depth
    assert len([x for x in ann.log if x[0] == "open"]) == sum(
        t["count"] for t in spans.values()
    )
    assert rec.counters["core.programs.ahead"] > 0
    assert rec.counters["core.programs.ahead_unused"] >= 0
    assert rec.counters["jax.builds"] > 0  # fresh programs on every call
    assert {e["thread"] for e in sink.events if e["name"] == "jax.builds"} <= {
        "driver",
        "other",
    }


CHILD = r"""
import json
import jax
import jax.numpy as jnp
import numpy as np
jax.config.update("jax_enable_x64", True)
from repro.core import QuadratureConfig
from repro.core.distributed import (AXIS, _stacked_initial_state, dist_program,
                                    integrate_distributed)
from repro.core.rules import make_rule
from repro.telemetry import MemorySink, Recorder
from jax.sharding import NamedSharding, PartitionSpec as P

cfg = QuadratureConfig(d=3, integrand="f4", rel_tol=1e-5, capacity=1 << 12,
                       redistribution="ring").validate()
mesh = jax.make_mesh((4,), (AXIS,))
lo, hi = np.asarray(cfg.lo(), float), np.asarray(cfg.hi(), float)
state = jax.device_put(_stacked_initial_state(cfg, 4, jnp.float64),
                       NamedSharding(mesh, P(AXIS)))
prog = dist_program(cfg, make_rule(cfg, None), mesh, float(np.prod(hi - lo)),
                    hi - lo, 1024)
hlo = prog.lower(state).compile().as_text()

def answer(r):
    return [float(r.integral).hex(), float(r.error).hex(), r.status,
            r.iterations, r.n_evals, [float(x) for x in r.evals_per_device],
            [list(map(float, h)) for h in r.history]]

off = integrate_distributed(cfg)
sink = MemorySink()
rec = Recorder(sinks=(sink,))
on = integrate_distributed(cfg, recorder=rec)
depth = sorted({(e["name"], e["depth"]) for e in sink.events
                if e["kind"] == "span_end"})
print(json.dumps({
    "scopes": [s for s in ("quad.eval", "quad.estimates", "quad.advance",
                           "quad.redistribute") if s in hlo],
    "same": answer(on) == answer(off),
    "devices": on.n_devices,
    "depth": depth,
    "counters": sorted(rec.counters),
}))
"""


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=os.path.join(_REPO, "src"),
    )
    proc = subprocess.run(
        [sys.executable, "-c", CHILD],
        env=env,
        capture_output=True,
        text=True,
        timeout=900,
        cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_dist_step_carries_all_four_scopes(four_devices):
    assert four_devices["scopes"] == [
        "quad.eval",
        "quad.estimates",
        "quad.advance",
        "quad.redistribute",
    ]


def test_integrate_distributed_names_each_host_activity_and_answers_alike(
    four_devices,
):
    assert four_devices["devices"] == 4 and four_devices["same"] is True
    depth = {tuple(x) for x in four_devices["depth"]}
    assert {("dist.setup", 0), ("dist.dispatch", 0), ("dist.program", 1),
            ("dist.sync", 1)} <= depth
    assert {"jax.builds", "jax.trace_s", "core.programs.ahead",
            "core.programs.ahead_unused"} <= set(four_devices["counters"])
