"""Share (%) of the traced window in which the busiest chip was idle while
the thread that drives it was inside a ``core.program`` or ``dist.program``
span, at any depth: the device's idle time spent getting jitted programs
and waiting on their compiles.  The recorder opens each span as a profiler
annotation on the driving thread, so the spans and the device's idle gaps
are on one clock."""

from harness import trace

PROGRAM_SPANS = ("core.program", "dist.program")


def _overlap(a: list, b: list) -> float:
    """Length of the intersection of two sorted lists of disjoint
    ``[start, end)`` intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    window = run.trace.window_ns
    spans = trace._union(
        [(e.start_ns, e.end_ns) for e in run.trace.host if e.name in PROGRAM_SPANS],
        0.0,
        window,
    )
    if not spans:
        return None
    dev = max(run.trace.devices, key=lambda d: d.busy_ns)
    idle = [(start, start + dur) for start, dur in dev.gaps]
    return 100.0 * _overlap(spans, idle) / window
