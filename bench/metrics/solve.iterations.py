"""Adaptive iterations per solve, the mean over the window's solves."""


def read(run):
    if not run.solves:
        return None
    return sum(s.result.iterations for s in run.solves) / len(run.solves)
