"""Genz-Malik fully-symmetric embedded cubature rules.

Implements the degree-7 Genz-Malik rule [Genz & Malik 1983] on the reference
cube ``[-1, 1]^d`` together with its embedded degree-5 and degree-3 members,
which drive the Berntsen-Espelid-Genz style two-level error heuristic
(``repro.core.error``) and the fourth-divided-difference axis selection
heuristic used by Cuba/cubature and by the paper.

Node layout (counts for dimension ``d``):

    group 0: centre                                   1
    group 1: (+-lam2, 0, ..., 0) and perms            2d
    group 2: (+-lam3, 0, ..., 0) and perms            2d
    group 3: (+-lam4, +-lam4, 0, ..., 0) and perms    2d(d-1)
    group 4: (+-lam5, ..., +-lam5)                    2^d

    total n(d) = 1 + 4d + 2d(d-1) + 2^d

All weights are exact rationals evaluated in float64; exactness on
polynomials of total degree <= 7 (resp. 5, 3) is asserted by the tests.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

# Reference-cube generator radii (squared values are exact rationals).
LAMBDA2 = float(np.sqrt(9.0 / 70.0))
LAMBDA3 = float(np.sqrt(9.0 / 10.0))
LAMBDA4 = float(np.sqrt(9.0 / 10.0))
LAMBDA5 = float(np.sqrt(9.0 / 19.0))

# Ratio used by the fourth-divided-difference axis heuristic.
FOURTH_DIFF_RATIO = (9.0 / 70.0) / (9.0 / 10.0)  # lam2^2 / lam3^2 == 1/7


def n_nodes(d: int) -> int:
    """Total number of integrand evaluations of the GM rule in dimension d."""
    return 1 + 4 * d + 2 * d * (d - 1) + 2**d


@dataclasses.dataclass(frozen=True)
class GMWeights:
    """Weights of the embedded degree-7/5/3 GM family (volume included).

    ``w*`` are per-node weights on [-1,1]^d; multiplying the weighted node
    sum by ``prod(halfwidths)`` yields the integral over the actual box
    (the 2^d reference volume is folded into the weights).
    """

    d: int
    # degree-7 rule
    w1: float
    w2: float
    w3: float
    w4: float
    w5: float
    # embedded degree-5 rule (groups 0..3 only)
    e1: float
    e2: float
    e3: float
    e4: float
    # embedded degree-3 rule (centre + lam3 group only)
    t1: float
    t3: float


@functools.lru_cache(maxsize=None)
def gm_weights(d: int) -> GMWeights:
    if d < 1:
        raise ValueError(f"Genz-Malik rule needs d >= 1, got {d}")
    if d == 1:
        # Degree-7 weights w4 multiply an empty group in d=1; keep zero.
        pass
    vol = float(2**d)
    w1 = vol * (12824.0 - 9120.0 * d + 400.0 * d * d) / 19683.0
    w2 = vol * 980.0 / 6561.0
    w3 = vol * (1820.0 - 400.0 * d) / 19683.0
    w4 = vol * 200.0 / 19683.0
    w5 = vol * 6859.0 / 19683.0 / (2**d)

    e1 = vol * (729.0 - 950.0 * d + 50.0 * d * d) / 729.0
    e2 = vol * 245.0 / 486.0
    e3 = vol * (265.0 - 100.0 * d) / 1458.0
    e4 = vol * 25.0 / 729.0

    # Degree-3 rule using the centre and the lam3 single-coordinate group:
    #   2 * t3 * lam3^2 = vol / 3  (per-axis second moment)
    t3 = vol / (6.0 * (9.0 / 10.0))
    t1 = vol - 2.0 * d * t3
    return GMWeights(d, w1, w2, w3, w4, w5, e1, e2, e3, e4, t1, t3)


def pair_generators(d: int) -> np.ndarray:
    """Static (n_pairs*4, 2, 2) array of ((i, si), (j, sj)) for group 3."""
    out = []
    for i in range(d):
        for j in range(i + 1, d):
            for si in (1.0, -1.0):
                for sj in (1.0, -1.0):
                    out.append(((i, si), (j, sj)))
    return np.array(out, dtype=object)


# The node groups below loop over their axes / axis pairs with fori_loop
# rather than unrolling in Python: the program then holds a handful of
# copies of the integrand (four per axis step, one per pair or corner node)
# instead of one per node (4d + 4 d(d-1)/2 of them).  On a TPU every float64
# op is emulated, so an unrolled rule made each window rung of the solver a
# multi-ten-second compile.


def _eval_axis_groups(f, centers, halfw, dtype):
    """Single-coordinate displacement sums + per-axis fourth differences.

    centers/halfw: (d, B).  Returns (sum2, sum3, f0, fourth_diff) with
    sum2/sum3/f0 of shape (B,) and fourth_diff (d, B).
    """
    d = centers.shape[0]
    f0 = f(centers)
    rows = jnp.arange(d)[:, None]

    def body(i, carry):
        sum2, sum3, diffs = carry
        onehot = (rows == i).astype(dtype)
        d2 = halfw * (LAMBDA2 * onehot)
        d3 = halfw * (LAMBDA3 * onehot)
        f2p = f(centers + d2)
        f2m = f(centers - d2)
        f3p = f(centers + d3)
        f3m = f(centers - d3)
        diff = jnp.abs(
            f2p + f2m - 2.0 * f0 - FOURTH_DIFF_RATIO * (f3p + f3m - 2.0 * f0)
        )
        return sum2 + f2p + f2m, sum3 + f3p + f3m, diffs.at[i].set(diff)

    zeros = jnp.zeros_like(f0)
    sum2, sum3, diffs = jax.lax.fori_loop(
        0, d, body, (zeros, zeros, jnp.zeros((d,) + f0.shape, dtype))
    )
    return sum2, sum3, f0, diffs


def _eval_pair_group(f, centers, halfw, dtype):
    """Group 3 sum: (+-lam4, +-lam4) over all axis pairs.  (B,)."""
    d = centers.shape[0]
    total = jnp.zeros(centers.shape[1], dtype=dtype)
    pairs = [(i, j) for i in range(d) for j in range(i + 1, d)]
    if not pairs:
        return total
    first = jnp.asarray([i for i, _ in pairs])
    second = jnp.asarray([j for _, j in pairs])
    # node k of the loop: pair k // 4 at (+,+), (+,-), (-,+), (-,-)
    sign_i = jnp.asarray([1.0, 1.0, -1.0, -1.0], dtype) * LAMBDA4
    sign_j = jnp.asarray([1.0, -1.0, 1.0, -1.0], dtype) * LAMBDA4
    rows = jnp.arange(d)[:, None]

    def body(k, total):
        p, kind = k // 4, k % 4
        step = sign_i[kind] * (rows == first[p]).astype(dtype) + sign_j[kind] * (
            rows == second[p]
        ).astype(dtype)
        return total + f(centers + halfw * step)

    return jax.lax.fori_loop(0, 4 * len(pairs), body, total)


def _eval_corner_group(f, centers, halfw, dtype):
    """Group 4 sum: full-sign (+-lam5, ..., +-lam5) points via fori_loop."""
    d, b = centers.shape

    def body(k, acc):
        # signs from the bits of k: axis i sign = +1 if bit clear else -1
        bits = jnp.stack([(k >> i) & 1 for i in range(d)]).astype(dtype)
        signs = 1.0 - 2.0 * bits  # (d,)
        x = centers + LAMBDA5 * halfw * signs[:, None]
        return acc + f(x)

    return jax.lax.fori_loop(0, 2**d, body, jnp.zeros(b, dtype=dtype))


def gm_eval_reference(
    f: Callable[[jnp.ndarray], jnp.ndarray],
    centers: jnp.ndarray,
    halfw: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pure-jnp oracle for the batched GM evaluation.

    Args:
      f: integrand mapping (d, N) coordinates -> (N,) values.
      centers, halfw: (B, d) region centres / halfwidths.

    Returns:
      (i7, i5, i3, fourth_diff): degree-7/5/3 estimates (B,) each, already
      scaled by the region volume factor prod(halfw), and the per-axis
      fourth differences (B, d) for axis selection.
    """
    dtype = centers.dtype
    b, d = centers.shape
    w = gm_weights(d)
    ct = centers.T  # (d, B) SoA layout
    ht = halfw.T

    sum2, sum3, f0, diffs = _eval_axis_groups(f, ct, ht, dtype)
    sum4 = _eval_pair_group(f, ct, ht, dtype)
    sum5 = _eval_corner_group(f, ct, ht, dtype)

    scale = jnp.prod(ht, axis=0)  # (B,)
    i7 = scale * (w.w1 * f0 + w.w2 * sum2 + w.w3 * sum3 + w.w4 * sum4 + w.w5 * sum5)
    i5 = scale * (w.e1 * f0 + w.e2 * sum2 + w.e3 * sum3 + w.e4 * sum4)
    i3 = scale * (w.t1 * f0 + w.t3 * sum3)
    return i7, i5, i3, diffs.T  # (B,), (B,), (B,), (B, d)
