"""One client solving the configuration's integral back to back through
``repro.core.integrate`` on one chip (:func:`harness.loops.solve_window`).
Every solve of the window is an answer."""

from harness import loops


def run(record, devices, trace, compiles, t0):
    from repro.core import integrate

    loops.solve_window(
        record, trace, compiles, t0, lambda cfg, rec: integrate(cfg, recorder=rec)
    )


answers = loops.solve_answers
