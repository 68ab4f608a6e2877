"""Plain reference of Genz's Gaussian family on [0, 1]^d:
f(x; a, u) = exp(-sum_i a_i^2 (x_i - u_i)^2).

The integral separates: int_0^1 exp(-a^2 (t - u)^2) dt
= sqrt(pi) / (2a) * (erf(a (1 - u)) + erf(a u)).
"""

import math


def exact(d: int, theta) -> float:
    """The integral over [0, 1]^d at ``theta = {"a": (d,), "u": (d,)}``."""
    a, u = theta["a"], theta["u"]
    if len(a) != d or len(u) != d:
        raise ValueError(f"theta has {len(a)}, {len(u)} values for d={d}")
    p = 1.0
    for ai, ui in zip(a, u):
        ai, ui = float(ai), float(ui)
        p *= math.sqrt(math.pi) / (2.0 * ai) * (math.erf(ai * (1.0 - ui)) + math.erf(ai * ui))
    return p


def flops_per_point(d: int) -> int:
    """Per point: a subtract, a multiply by a_i, a square and an add per
    axis, and the exp."""
    return 4 * d + 1
