"""Seconds per solve in the ``core.eval`` spans of ``repro.core.integrate``
(the eval and metrics programs of each iteration and the host's wait for
the estimates), the mean over the window's solves."""


def read(run):
    spans = [s.spans["core.eval"] for s in run.solves if "core.eval" in s.spans]
    return sum(spans) / len(spans) if spans else None
