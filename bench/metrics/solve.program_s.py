"""Seconds per solve in the ``core.program`` spans of ``repro.core.integrate``
and the ``dist.program`` spans of ``integrate_distributed``: getting each
window rung's jitted program, built anew by every call, and waiting on its
compile started ahead.  The mean over the window's solves."""

PROGRAM_SPANS = ("core.program", "dist.program")


def read(run):
    spans = [
        sum(s.spans.get(name, 0.0) for name in PROGRAM_SPANS)
        for s in run.solves
        if any(name in s.spans for name in PROGRAM_SPANS)
    ]
    return sum(spans) / len(spans) if spans else None
