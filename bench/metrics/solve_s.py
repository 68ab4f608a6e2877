"""Seconds per solve: the total seconds of the window's solves over their
count (the solve in progress at the close finishes and counts)."""


def read(run):
    if not run.solves:
        return None
    return sum(s.end - s.start for s in run.solves) / len(run.solves)
