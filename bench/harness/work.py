"""Work of the Genz-Malik rule, counted from the algorithm's shapes.

These counts are what a roofline share divides by: the operations and bytes
the rule needs for the regions it had to evaluate, whatever implements it
(jnp in float64, float32, or a Pallas kernel).  They are never taken from
XLA's cost analysis, which changes with the implementation, counts a loop
body once and counts the chip's float64 emulation.

Counting rules: an add, subtract, multiply or divide is one operation, and
so is one ``exp``; comparisons and selects are not counted.
"""

from __future__ import annotations


def gm_nodes(d: int) -> int:
    """Nodes of the degree-7 Genz-Malik rule in dimension ``d``:
    the centre, 2d points on each of two axis radii, 2d(d-1) pair points
    and the 2^d corners."""
    if d < 2:
        raise ValueError(f"the Genz-Malik rule needs d >= 2, got {d}")
    return 1 + 4 * d + 2 * d * (d - 1) + 2**d


def gm_rule_flops(d: int) -> int:
    """Operations of the rule itself per region, the integrand excluded:
    each node's coordinates (c + h * g: two per coordinate), the weighted
    sums of the degree-7, 5 and 3 members (one add per node each, one
    multiply per node group), the fourth differences that pick the split
    axis (six per axis), and the error and volume scaling (ten)."""
    n7 = gm_nodes(d)
    n5 = 1 + 4 * d + 2 * d * (d - 1)
    n3 = 1 + 2 * d
    return 2 * d * n7 + n7 + n5 + n3 + (5 + 4 + 2) + 6 * d + 10


def gm_region_bytes(d: int, itemsize: int) -> int:
    """Bytes of region-store rows one evaluation reads and writes: the
    centre and half-widths read, the estimate and error written in the
    store's float type, the split axis (int32) written, and the active and
    fresh masks (one byte each) read."""
    return 2 * d * itemsize + 2 * itemsize + 4 + 2


def gm_work(d: int, regions: float, flops_per_point: int, itemsize: int):
    """``(flops, bytes)`` of evaluating ``regions`` regions."""
    flops = regions * (gm_nodes(d) * flops_per_point + gm_rule_flops(d))
    return flops, regions * gm_region_bytes(d, itemsize)
