"""``clients`` clients, each submitting a new problem as soon as its previous
answer returns, through one ``repro.service.serve`` stream.

A problem is submitted when the scheduler pulls it from the stream, which
it does only for a free slot, so the loop keeps every slot full.  Problems
are drawn from ``--seed`` by Latin hypercube sampling in blocks of
``clients`` problems over the configuration's ``theta`` ranges, so every
block spans the ranges alike.

Set-up serves ``warmup_requests`` answers, which brings the slots to a
steady mix of ages and loads every window rung the stream reaches; the
window follows in the same stream.  At the close the stream ends, and the
loop goes on reading answers until every request submitted earlier than
the worst latency seen before the close has been answered, or
``drain_seconds`` have passed.  ``unanswered`` counts the requests of that
kind still without an answer; its limit is 0.  A fleet that answers nothing
for ``stall_seconds`` ends the run.
"""

from __future__ import annotations

import time

import numpy as np

from harness import loops
from harness.check import Answer, Check


def latin_hypercube(rng: np.random.Generator, n: int, ranges: dict, d: int) -> list:
    """``n`` parameter sets; each field is ``d`` values, each of the ``n * d``
    columns stratified over its range ``[lo, hi]``."""
    out = [dict() for _ in range(n)]
    for field, (lo, hi) in ranges.items():
        cols = (rng.permuted(np.tile(np.arange(n), (d, 1)), axis=1).T
                + rng.random((n, d))) / n
        for i in range(n):
            out[i][field] = lo + (hi - lo) * cols[i]
    return out


class Stalled(RuntimeError):
    """The fleet answered nothing for the traffic's ``stall_seconds``."""


def _worst_latency(requests: list) -> float:
    return max((r.done - r.submit for r in requests if r.done is not None),
               default=float("inf"))


def _due(requests: list, close: float) -> list:
    """Requests submitted earlier than the worst latency before ``close``."""
    worst = _worst_latency(requests)
    return [r for r in requests if r.submit <= close - worst]


def run(record, devices, trace, compiles, t0):
    from repro.core.integrands import get_param
    from repro.service import QuadRequest, serve

    cell = record.cell
    cfg = loops.quadrature_config(cell)
    clients = int(cell.traffic["clients"])
    if clients != cfg.batch_slots:
        raise ValueError(
            f"a closed fleet of {clients} clients needs {clients} slots, "
            f"the configuration has {cfg.batch_slots}"
        )
    record.slots = clients
    family = get_param(cell.config["integrand"])
    ranges = {k: tuple(v) for k, v in cell.config["theta"].items()}
    rng = np.random.default_rng(record.seed)
    profiler = loops.Profiler()
    in_window = closed = False

    def stream():
        i = 0
        while True:
            for theta in latin_hypercube(rng, clients, ranges, cfg.d):
                if closed:
                    return
                with loops.annotate(trace and in_window, "bench.submit"):
                    record.requests.append(loops.Request(i, theta, time.monotonic()))
                    yield QuadRequest(req_id=i, theta=theta)
                i += 1

    stall_s = float(cell.traffic["stall_seconds"])
    drain_s = float(cell.traffic["drain_seconds"])
    last_answer = time.monotonic()

    def on_tick(it, state, slot_req):
        # called between dispatches: a fleet that answers nothing for
        # stall_s has lost its requests
        if time.monotonic() - last_answer > stall_s:
            raise Stalled(f"no answer in {stall_s} s")

    served = serve(cfg, stream(), family, devices=devices, on_tick=on_tick)
    warmup = int(cell.traffic["warmup_requests"])
    answered = 0
    b0 = h0 = b1 = h1 = 0

    def close_window():
        nonlocal closed, b1, h1
        closed = True
        b1, h1 = compiles.snapshot()
        if trace and in_window:
            profiler.stop()

    try:
        for res in served:
            now = last_answer = time.monotonic()
            req = record.requests[res.req_id]
            req.done, req.result = now, res
            answered += 1
            if not in_window:
                if answered >= warmup:
                    in_window = True
                    b0, h0 = compiles.snapshot()
                    record.window_start = now
                    record.setup_s = now - t0
                    if trace:
                        profiler.start()
                continue
            close = record.window_start + record.seconds
            if not closed and now >= close:
                close_window()
            if closed and (
                now - close >= drain_s
                or all(r.done is not None for r in _due(record.requests, close))
            ):
                break
    except Stalled:
        if not in_window:
            record.window_start = time.monotonic()
    finally:
        if not closed:
            close_window()
        served.close()
    record.window_end = record.window_start + record.seconds
    if trace and in_window:
        record.trace = profiler.reduce()
    record.compiles_in_window = (b1 - b0) - (h1 - h0)
    record.cache_loads_in_window = h1 - h0


def answers(record) -> list:
    """Every request answered inside the window, beside the reference's
    value of its integrand."""
    ref = record.cell.reference
    return [
        Answer(r.result.integral, r.result.error, r.result.status,
               ref.exact(record.d, r.theta))
        for r in record.window_requests()
    ]


def checks(record) -> list:
    """Requests submitted earlier than the worst latency before the close
    and never answered: lost, not late."""
    due = _due(record.requests, record.window_end)
    return [Check("unanswered", float(sum(r.done is None for r in due)), 0.0)]
