"""Share (%) of the slots' time in the window that held a request: the sum
over requests of the part of [submission, answer] inside the window, over
slots times the window.  A request still in flight at the close holds its
slot to the close."""


def read(run):
    if not run.slots:
        return None
    lo, hi = run.window_start, run.window_end
    held = 0.0
    for r in run.requests:
        end = hi if r.done is None else min(r.done, hi)
        held += max(0.0, end - max(r.submit, lo))
    return 100.0 * held / (run.slots * (hi - lo))
