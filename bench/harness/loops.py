"""What every traffic loop shares: the record of a run, the profiler over
the window, and the closed solve loop that the solve loops drive.

A traffic mix ``bench/traffic/<name>.json`` names its loop under
``"loop"``; the loop is the file ``bench/loops/<loop>.py``, found by name
(:mod:`harness.cells`).  A loop file defines

- ``run(record, devices, trace, compiles, t0)``: set-up, then the window,
  filling the :class:`RunRecord`;
- ``answers(record)``: the window's answers, each beside its reference
  value (:class:`harness.check.Answer`);

and may define ``checks(record)``: further numbers compared for
``correct`` (:class:`harness.check.Check`), such as requests left
unanswered.  A new kind of traffic is one new loop file.

With ``trace`` on, a profiler session covers the window and each solve gets
a ``repro.telemetry.Recorder`` for its spans; the trace is read after the
window.  With it off nothing is recorded.
"""

from __future__ import annotations

import contextlib
import dataclasses
import shutil
import tempfile
import time
from typing import Callable, Optional

import numpy as np

from harness import trace as trace_lib


@dataclasses.dataclass
class Solve:
    start: float
    end: float
    result: object  # the entry point's result
    spans: dict  # span name -> total seconds, with tracing on


@dataclasses.dataclass
class Request:
    req_id: int
    theta: dict
    submit: float
    done: Optional[float] = None
    result: object = None


@dataclasses.dataclass
class RunRecord:
    """What one run leaves for the metric readers and the check."""

    cell: object  # harness.cells.Cell
    seed: int
    seconds: float
    setup_s: float = 0.0
    window_start: float = 0.0
    window_end: float = 0.0
    solves: list = dataclasses.field(default_factory=list)
    requests: list = dataclasses.field(default_factory=list)
    slots: int = 0
    trace: Optional[trace_lib.TraceSummary] = None
    device_kind: str = ""
    compiles_in_window: int = 0
    cache_loads_in_window: int = 0

    @property
    def window_s(self) -> float:
        return self.window_end - self.window_start

    @property
    def d(self) -> int:
        return int(self.cell.config["quadrature"]["d"])

    @property
    def itemsize(self) -> int:
        return np.dtype(self.cell.config["quadrature"]["dtype"]).itemsize

    def window_requests(self) -> list:
        """Requests answered inside the window."""
        return [
            r
            for r in self.requests
            if r.done is not None and self.window_start < r.done <= self.window_end
        ]


class Profiler:
    """One profiler session over the window, in a temporary directory.
    :meth:`stop` ends the session at the close; :meth:`reduce`, called
    after the window, reads the trace and deletes it."""

    def __init__(self) -> None:
        self.dir: Optional[str] = None
        self.t0 = 0.0
        self.window_ns = 0.0

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        # no Python function tracing (it multiplies the trace ~20-fold and
        # slows the host), no HLO protos: device ops and host annotations
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.t0 = time.monotonic()

    def stop(self) -> None:
        import jax

        self.window_ns = (time.monotonic() - self.t0) * 1e9
        jax.profiler.stop_trace()

    def reduce(self) -> trace_lib.TraceSummary:
        try:
            return trace_lib.summarize(self.dir, self.window_ns)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def annotate(on: bool, name: str):
    """A host annotation in the trace while ``on``, else nothing."""
    import jax

    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


def quadrature_config(cell):
    """The configuration's solver settings, with the traffic's on top."""
    from repro.core import QuadratureConfig

    return QuadratureConfig(
        **{**cell.config["quadrature"], **cell.traffic.get("quadrature", {})}
    )


def solve_window(
    run: RunRecord, trace: bool, compiles, t0: float, solve: Callable
) -> None:
    """One client solving the configuration's integral back to back through
    ``solve(cfg, recorder)``.  Set-up makes one solve (it compiles, or loads
    every program from the cache).  The window then runs solves until
    ``run.seconds`` have passed; the solve in progress finishes."""
    from repro.telemetry import NULL, Recorder

    cfg = quadrature_config(run.cell)
    solve(cfg, NULL)  # set-up: compile or load every program

    profiler = Profiler()
    b0, h0 = compiles.snapshot()
    run.window_start = time.monotonic()
    run.setup_s = run.window_start - t0
    if trace:
        profiler.start()
    while True:
        rec = Recorder() if trace else NULL
        start = time.monotonic()
        with annotate(trace, "bench.solve"):
            result = solve(cfg, rec)
        end = time.monotonic()
        spans = {k: v["total_s"] for k, v in rec.span_totals.items()}
        run.solves.append(Solve(start, end, result, spans))
        if end - run.window_start >= run.seconds:
            break
    run.window_end = run.solves[-1].end
    b1, h1 = compiles.snapshot()
    if trace:
        profiler.stop()
        run.trace = profiler.reduce()
    run.compiles_in_window = (b1 - b0) - (h1 - h0)
    run.cache_loads_in_window = h1 - h0


def solve_answers(run: RunRecord) -> list:
    """Every solve of the window beside the reference's value."""
    from harness.check import Answer

    exact = run.cell.reference.exact(run.d, None)
    return [
        Answer(s.result.integral, s.result.error, s.result.status, exact)
        for s in run.solves
    ]
