"""One client solving the configuration's integral back to back through
``repro.core.distributed.integrate_distributed`` over the cell's chips
(:func:`harness.loops.solve_window`).  Every solve of the window is an
answer."""

from harness import loops


def run(record, devices, trace, compiles, t0):
    from repro.core.distributed import integrate_distributed

    loops.solve_window(
        record, trace, compiles, t0,
        lambda cfg, rec: integrate_distributed(cfg, devices=devices, recorder=rec),
    )


answers = loops.solve_answers
