"""Requests answered inside the window, over the window's seconds."""


def read(run):
    if not run.slots or run.window_s <= 0:
        return None
    return len(run.window_requests()) / run.window_s
