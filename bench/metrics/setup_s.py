"""Seconds from the start of the process to the start of the window: JAX's
start-up, loading or compiling every program, and the traffic's warm-up."""


def read(run):
    return run.setup_s
