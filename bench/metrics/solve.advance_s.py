"""Seconds per solve in the ``core.advance`` spans of
``repro.core.integrate`` (classify, split and compact of each iteration and
the host's wait for the live count), the mean over the window's solves."""


def read(run):
    spans = [s.spans["core.advance"] for s in run.solves if "core.advance" in s.spans]
    return sum(spans) / len(spans) if spans else None
