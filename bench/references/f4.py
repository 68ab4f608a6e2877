"""Plain reference of f4, Genz's Gaussian with a = 25 and u = 1/2 on
[0, 1]^d: f(x) = exp(-625 * sum_i (x_i - 1/2)^2).

The integral separates into d equal factors,
int_0^1 exp(-625 (t - 1/2)^2) dt = sqrt(pi) / 25 * erf(12.5).
"""

import math


def exact(d: int, theta=None) -> float:
    """The integral over [0, 1]^d; f4 has no parameters."""
    return (math.sqrt(math.pi) / 25.0 * math.erf(12.5)) ** d


def flops_per_point(d: int) -> int:
    """Per point: a subtract, a square and an add per axis, the scale by
    625 and the exp."""
    return 3 * d + 2
