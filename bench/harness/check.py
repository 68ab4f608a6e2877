"""The comparison that decides ``correct``.

Every answer the window produced is held against the plain reference, the
analytic value of its integrand (``bench/references``).  Two numbers are
compared:

- ``false_certified``: answers that certify convergence while their true
  error ``|I - exact| / |exact|`` exceeds the configuration's ``rel_tol``.
  Such an answer breaks the solver's guarantee, so the limit is 0.
- ``uncertified_share``: the share of answers that end without certifying
  convergence (out of regions, out of iterations, nothing left to refine).
  Its limit is in the configuration's file, set from the readings of sound
  runs and of the float32 control (``PERF.md`` gives them).

A run with no answer at all has no share and is not correct.  A loop may
add numbers of its own (``checks`` in its file).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional


@dataclasses.dataclass(frozen=True)
class Answer:
    integral: float
    error: float  # the program's claimed error
    status: str
    exact: float  # the reference's value

    @property
    def certified(self) -> bool:
        return self.status == "converged"

    @property
    def true_rel_err(self) -> float:
        return abs(self.integral - self.exact) / abs(self.exact)

    def falsely_certified(self, rel_tol: float) -> bool:
        return self.certified and not self.true_rel_err <= rel_tol

    def failed(self, rel_tol: float) -> bool:
        return not self.certified or self.falsely_certified(rel_tol)


@dataclasses.dataclass(frozen=True)
class Check:
    name: str
    value: Optional[float]  # None: the run gave no number
    limit: float

    @property
    def ok(self) -> bool:
        return self.value is not None and math.isfinite(self.value) and (
            self.value <= self.limit
        )


def failed(answers: list, rel_tol: float) -> int:
    """Answers not certified within ``rel_tol`` of the reference."""
    return sum(a.failed(rel_tol) for a in answers)


def compare(answers: list, rel_tol: float, limits: dict) -> list:
    """The checks of one run's answers."""
    n = len(answers)
    false = sum(a.falsely_certified(rel_tol) for a in answers)
    uncertified = sum(not a.certified for a in answers)
    return [
        Check("false_certified", float(false) if n else None, 0.0),
        Check("uncertified_share", uncertified / n if n else None,
              limits["uncertified_share"]),
    ]


def describe(answers: list) -> str:
    """What the answers say beyond the checks, for the run's error stream."""
    certified = [a.true_rel_err for a in answers if a.certified]
    ratios = [
        abs(a.integral - a.exact) / a.error
        for a in answers
        if a.certified and a.error > 0
    ]
    return (
        f"answers={len(answers)} certified={len(certified)} "
        f"worst_true_rel_err={max(certified, default=None)} "
        f"worst_true_over_claimed={max(ratios, default=None)}"
    )
