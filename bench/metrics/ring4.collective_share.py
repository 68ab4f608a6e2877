"""Share (%) of the traced window in which a collective operation (an
all-reduce, collective-permute, all-gather, ...) ran, on the chip where
that share is largest."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    worst = max(d.collective_ns for d in run.trace.devices)
    return 100.0 * worst / run.trace.window_ns
