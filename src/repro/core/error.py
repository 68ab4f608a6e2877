"""Two-level heuristic error estimator tailored to the embedded GM family.

Follows the philosophy of Berntsen-Espelid-Genz [2]: compare two levels of
embedded differences,

    n1 = |I7 - I5|   (behaves like a degree-5 null rule)
    n2 = |I5 - I3|   (degree-3 level)

When the integrand is *smooth and resolved* on the subregion, the ratio
``r = n1/n2`` is small and the true error of I7 is far below n1; we then
extrapolate the estimate down by ``sqrt(2 r)``.

Two gates keep the extrapolation honest (both regression-tested):

- ratio gate ``r < 1/8``: at the gate boundary the shrink factor is at most
  ``sqrt(2/8) = 1/2``, and it never falls below ``1/4``: a tiny ``r`` can
  also mean that the I5/I3 difference is large for a reason the degree-7
  rule shares (an unresolved peak), and an unbounded shrink then claimed
  up to 12x less than the true error on genz_gaussian at d=5
  (regression-tested).  ``benchmarks/error_floor_sweep.py`` (270 runs:
  f1-f7 at d=5 and 8, the three families at d=5-8, the smoke's 64-theta
  fleet) sets the floor: unbounded, 5 runs claim convergence with a true
  error over tolerance; at 1/4, only f4 at d=8, which no floor changes,
  for 4.7% more evaluations; 1/2 costs more again (fewer runs converge
  within capacity) and removes no further case;
- smoothness gate: the per-axis fourth divided differences must be small
  relative to the mean integrand magnitude ``|I7|/vol``.  On boxes straddling
  a discontinuity (f6) or an unresolved oscillation (f1) the fourth
  differences are O(f), and extrapolating there systematically understates
  the error: I7 and I5 share all their nodes, so their difference measures
  only *weight* disagreement and misses the common sampling bias.  Without
  this gate the solver declares convergence on f6 with a true error ~40x the
  claimed estimate; with it, claimed >= true across the whole f1..f7 suite.

A round-off noise floor (Gander-Gautschi style guard, [4]) prevents
over-refinement once differences reach machine noise.
"""

from __future__ import annotations

import jax.numpy as jnp

_R_CRIT = 0.125
_SHRINK_MIN = 0.25  # the extrapolation reduces n1 by at most 4x (see above)
_SMOOTH_FRAC = 0.05  # fourth differences below 5% of mean |f| => smooth


def two_level_error(
    i7: jnp.ndarray,
    i5: jnp.ndarray,
    i3: jnp.ndarray,
    vol: jnp.ndarray,
    max_fourth_diff: jnp.ndarray,
    noise_mult: float,
) -> jnp.ndarray:
    """Per-region heuristic error estimate.

    Args:
      i7, i5, i3: embedded rule estimates, shape (B,).
      vol: region volumes (B,).
      max_fourth_diff: max over axes of the fourth divided differences (B,) —
        raw function-value scale, not volume-scaled.
      noise_mult: multiplier on machine epsilon for the noise floor.
    """
    eps = jnp.finfo(i7.dtype).eps
    tiny = jnp.finfo(i7.dtype).tiny
    n1 = jnp.abs(i7 - i5)
    n2 = jnp.abs(i5 - i3)

    r = n1 / jnp.maximum(n2, tiny)
    shrink = jnp.clip(jnp.sqrt(2.0 * r), _SHRINK_MIN, 1.0)
    f_mean = jnp.abs(i7) / jnp.maximum(vol, tiny)
    smooth = max_fourth_diff <= _SMOOTH_FRAC * f_mean
    asymptotic = (n2 > tiny) & (r < _R_CRIT) & smooth
    err = jnp.where(asymptotic, n1 * shrink, n1)

    # Round-off noise floor: differences below eps * local magnitude are
    # numerical noise, not signal; clamp so the classifier finalises them.
    noise = noise_mult * eps * (jnp.abs(i7) + vol * f_mean)
    return jnp.maximum(err, noise)
