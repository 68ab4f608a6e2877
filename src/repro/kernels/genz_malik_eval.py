"""Fused Genz-Malik evaluation kernel (Pallas TPU).

The paper's hot spot is the per-iteration evaluation of the GM rule over the
whole active region population.  On GPU the reference code (PAGANI-style)
streams SoA region arrays through a CUDA kernel with coalesced loads.  The
TPU-native rethink (DESIGN.md §2):

- regions ride the 128-wide *lane* axis, the d coordinate axes ride the
  sublane axis — one `(d, BLOCK)` VMEM tile per block of regions;
- the rule's node coordinates are *generated on the fly* inside the kernel
  (centre + lambda * halfwidth * sign pattern) and the integrand is inlined,
  so no `(n_nodes, d)` coordinate matrix and no `(B, n_nodes)` value matrix
  ever exist in HBM — the kernel reads ``2 * d * BLOCK`` floats and writes
  ``(3 + d) * BLOCK`` floats, i.e. arithmetic intensity grows with
  ``n_nodes(d) = O(2^d)`` (where that puts it on a chip's roofline has not
  been measured yet);
- the O(2^d) full-sign group is a `fori_loop` with the sign pattern decoded
  from the loop counter's bits (no table in memory);
- the degree-7, degree-5, degree-3 sums and the per-axis fourth differences
  (axis-selection heuristic) are accumulated in registers/VMEM in the same
  pass — the embedded family costs zero extra evaluations by construction.

Weights/lambdas come from `repro.core.genz_malik` so kernel and oracle can
never drift apart.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax._src.pallas.mosaic import lowering as mosaic_lowering
from jax.experimental import pallas as pl

from repro.core.genz_malik import (
    FOURTH_DIFF_RATIO,
    LAMBDA2,
    LAMBDA3,
    LAMBDA4,
    LAMBDA5,
    gm_weights,
)


def _prod_by_slices(x, *, axes, **_):
    """``reduce_prod`` as a left fold of multiplies along each reduced axis."""
    for ax in sorted(axes, reverse=True):
        out = lax.index_in_dim(x, 0, ax, keepdims=False)
        for i in range(1, x.shape[ax]):
            out = out * lax.index_in_dim(x, i, ax, keepdims=False)
        x = out
    return x


# Mosaic has no rule for reduce_prod, which the kernel's halfwidth product
# and the product integrands (f2, genz_product_peak, monomial: jnp.prod over
# the coordinate rows) trace to; this one multiplies the slices out.  Only
# the Mosaic lowering changes: XLA paths, the interpreter included, keep
# their own reduce.
mosaic_lowering.register_lowering_rule(lax.reduce_prod_p)(
    mosaic_lowering.lower_fun(_prod_by_slices, multiple_results=False)
)


def _kernel(
    centers_ref,  # (d, B) VMEM
    halfw_ref,  # (d, B) VMEM
    *refs,  # [theta_ref (n_theta, B)] + i7 (1, B), i5, i3, diffs (d, B)
    f: Callable[..., jnp.ndarray],
    d: int,
    has_theta: bool,
):
    if has_theta:
        # ParamIntegrand families take their per-problem coefficients as a
        # proper kernel operand: an (n_theta, B) ref whose rows are the
        # flattened theta leaves broadcast over the lane axis (a closure
        # over theta would be a captured constant, which pallas_call
        # rejects — and under the batch service's vmap, a traced value).
        theta_ref, i7_ref, i5_ref, i3_ref, diffs_ref = refs
        theta = theta_ref[...]
    else:
        i7_ref, i5_ref, i3_ref, diffs_ref = refs
        theta = None
    c = centers_ref[...]
    h = halfw_ref[...]
    dtype = c.dtype
    w = gm_weights(d)

    def feval(x):
        v = f(x) if theta is None else f(x, theta)
        return v.reshape(1, -1)  # keep 2-D for TPU layout

    f0 = feval(c)
    sum2 = jnp.zeros_like(f0)
    sum3 = jnp.zeros_like(f0)
    diffs = []
    rows = jax.lax.broadcasted_iota(jnp.int32, (d, 1), 0)

    # --- single-coordinate groups (lambda2, lambda3) + fourth differences ----
    for i in range(d):
        onehot = (rows == i).astype(dtype)
        d2 = LAMBDA2 * h * onehot
        d3 = LAMBDA3 * h * onehot
        f2p = feval(c + d2)
        f2m = feval(c - d2)
        f3p = feval(c + d3)
        f3m = feval(c - d3)
        sum2 = sum2 + f2p + f2m
        sum3 = sum3 + f3p + f3m
        diffs.append(
            jnp.abs(f2p + f2m - 2.0 * f0 - FOURTH_DIFF_RATIO * (f3p + f3m - 2.0 * f0))
        )

    # --- pair group (lambda4, lambda4) ----------------------------------------
    sum4 = jnp.zeros_like(f0)
    for i in range(d):
        for j in range(i + 1, d):
            ei = (rows == i).astype(dtype)
            ej = (rows == j).astype(dtype)
            di = LAMBDA4 * h * ei
            dj = LAMBDA4 * h * ej
            sum4 = (
                sum4
                + feval(c + di + dj)
                + feval(c + di - dj)
                + feval(c - di + dj)
                + feval(c - di - dj)
            )

    # --- full-sign corner group (lambda5): signs decoded from loop bits ------
    def corner_body(k, acc):
        bits = jnp.right_shift(k, rows) & 1  # (d, 1): bit i of k on row i
        signs = (1 - 2 * bits).astype(dtype)
        return acc + feval(c + LAMBDA5 * h * signs)

    # int32 bounds: under x64 Python-int bounds make an int64 counter, which
    # Mosaic cannot lower
    sum5 = jax.lax.fori_loop(
        jnp.int32(0), jnp.int32(2**d), corner_body, jnp.zeros_like(f0)
    )

    scale = jnp.prod(h, axis=0, keepdims=True)  # (1, B)
    i7_ref[...] = scale * (
        w.w1 * f0 + w.w2 * sum2 + w.w3 * sum3 + w.w4 * sum4 + w.w5 * sum5
    )
    i5_ref[...] = scale * (w.e1 * f0 + w.e2 * sum2 + w.e3 * sum3 + w.e4 * sum4)
    i3_ref[...] = scale * (w.t1 * f0 + w.t3 * sum3)
    diffs_ref[...] = jnp.concatenate(diffs, axis=0)


@functools.partial(
    jax.jit, static_argnames=("f", "block_regions", "interpret")
)
def genz_malik_eval_soa(
    f: Callable,
    centers: jnp.ndarray,  # (d, C) SoA
    halfw: jnp.ndarray,  # (d, C)
    theta_rows: jnp.ndarray | None = None,  # (n_theta, C) broadcast operand
    *,
    block_regions: int,
    interpret: bool,
):
    """Run the fused GM kernel over an SoA batch. Returns (i7, i5, i3, diffs).

    ``block_regions`` is required (the batch must already be padded to a
    block multiple): block sizing and padding live in ``kernels.ops``, the
    single source of truth for the default.  So is ``interpret``: the
    platform-derived default lives in ``kernels.ops.resolve_interpret``.

    ``theta_rows`` carries a ParamIntegrand family's flattened coefficients
    as an extra ``(n_theta, C)`` input (each row one scalar broadcast over
    the lane axis); ``f`` then has signature ``f(x, theta_block)`` with
    ``theta_block`` the matching ``(n_theta, BLOCK)`` VMEM tile.  Packing
    and unpacking of the theta pytree live in ``kernels.ops``.
    """
    d, n = centers.shape
    if n % block_regions:
        raise ValueError(f"region count {n} not divisible by block {block_regions}")
    grid = (n // block_regions,)
    dtype = centers.dtype

    kernel = functools.partial(_kernel, f=f, d=d, has_theta=theta_rows is not None)
    # block index maps return int32 (a Python 0 is int64 under x64, which
    # Mosaic cannot lower)
    def col(i):
        return jnp.int32(0), i

    row_spec = pl.BlockSpec((d, block_regions), col)
    one_spec = pl.BlockSpec((1, block_regions), col)

    in_specs = [row_spec, row_spec]
    operands = [centers, halfw]
    if theta_rows is not None:
        in_specs.append(
            pl.BlockSpec((theta_rows.shape[0], block_regions), col)
        )
        operands.append(theta_rows)

    i7, i5, i3, diffs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[one_spec, one_spec, one_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), dtype),
            jax.ShapeDtypeStruct((1, n), dtype),
            jax.ShapeDtypeStruct((1, n), dtype),
            jax.ShapeDtypeStruct((d, n), dtype),
        ],
        interpret=interpret,
    )(*operands)
    return i7[0], i5[0], i3[0], diffs
