"""Jit'd public wrappers around the Pallas kernels.

`genz_malik_eval` is the entry point used by
:class:`repro.core.rules.GenzMalikRule` when ``use_kernel=True``.  It adapts
the region store's AoS ``(B, d)`` layout to the kernel's SoA ``(d, B)``
layout, pads the batch to the block size, and dispatches to the fused
Pallas kernel.  ``interpret`` defaults to what the platform needs
(:func:`resolve_interpret`): the Pallas interpreter on CPU, Mosaic on a TPU.

ParamIntegrand families ride the same kernel with their coefficients as a
proper operand: ``theta`` (a pytree of per-axis coefficient leaves) is
flattened into an ``(n_theta, B)``-broadcast row matrix and rebuilt into the
pytree inside the kernel wrapper, so the integrand never closes over a
theta array (``pallas_call`` rejects captured constants, and the batch
service passes theta as a traced, vmapped value).
"""

from __future__ import annotations

from functools import lru_cache
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.genz_malik_eval import genz_malik_eval_soa

# Default chosen by the VMEM budget sweep in EXPERIMENTS.md §Perf: the
# working set per block is ~(4 + 4d) * BLOCK * 4 bytes; 512 lanes keeps the
# d=13 worst case ~110 KiB, far under the ~16 MiB v5e VMEM, while giving the
# MXU-free VPU pipeline full 128-lane occupancy x 4 sublane tiles.
# This is the single source of truth for the block size: GenzMalikRule and
# QuadratureConfig use 0 to mean "defer to this default".
DEFAULT_BLOCK_REGIONS = 512


def resolve_interpret(
    interpret: Optional[bool], platform: Optional[str] = None
) -> bool:
    """``interpret=None`` means "interpret iff the rule runs on CPU".

    ``platform`` is that of the devices that run the kernel (the default
    backend when not given).  Pallas has no compiled CPU lowering, so the
    interpreter is the only way to run the kernel there; anywhere else the
    kernel is compiled.
    """
    if interpret is not None:
        return bool(interpret)
    return (platform or jax.default_backend()) == "cpu"


def block_and_pad(b: int, block_regions: int = 0) -> tuple[int, int]:
    """Resolve (block, pad) for a batch of ``b`` regions.

    The single place that rounds an evaluation batch (in particular the
    active-window sizes chosen by the adaptive drivers) up to a block
    multiple: batches smaller than the block shrink the block to the batch,
    larger batches are padded to the next multiple.  ``block_regions=0``
    selects :data:`DEFAULT_BLOCK_REGIONS`.
    """
    block_regions = block_regions or DEFAULT_BLOCK_REGIONS
    block = min(block_regions, b) if b % block_regions else block_regions
    return block, (-b) % block


@lru_cache(maxsize=None)
def _theta_wrapper(f: Callable, treedef, sizes: tuple[int, ...]) -> Callable:
    """Kernel-side adapter ``f(x, theta_rows) -> f(x, theta_pytree)``.

    Splits the stacked ``(n_theta, BLOCK)`` operand tile back into the
    family's theta leaves (each a broadcast ``(leaf_len, BLOCK)`` slab the
    integrand consumes via ``integrands._col``).  Cached so repeated calls
    hand ``genz_malik_eval_soa`` the *same* function object — its jit cache
    keys on ``f`` statically, and a fresh closure per call would recompile
    the kernel every iteration.
    """
    splits = tuple(int(s) for s in np.cumsum(sizes)[:-1])

    def f_soa(x: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
        parts = jnp.split(rows, splits, axis=0) if splits else [rows]
        return f(x, jax.tree.unflatten(treedef, parts))

    return f_soa


def genz_malik_eval(
    f: Callable,
    centers: jnp.ndarray,  # (B, d) AoS, as stored by RegionState
    halfw: jnp.ndarray,  # (B, d)
    *,
    theta=None,  # optional ParamIntegrand theta pytree, leaves (leaf_len,)
    block_regions: int = 0,
    interpret: Optional[bool] = None,  # None: resolve_interpret
):
    """Fused GM rule evaluation. Returns (i7, i5, i3, diffs[B, d]).

    Without ``theta``, ``f`` maps ``(d, N)`` coordinates to ``(N,)`` values.
    With ``theta``, ``f`` is a family function ``f(x, theta)`` and the theta
    leaves enter the kernel as broadcast operand rows (see module docstring).
    """
    b, d = centers.shape
    interpret = resolve_interpret(interpret)
    block, pad = block_and_pad(b, block_regions)
    ct = centers.T
    ht = halfw.T
    if pad:
        ct = jnp.pad(ct, ((0, 0), (0, pad)))
        # halfwidth 1 on padded lanes avoids spurious inf/nan in integrands
        ht = jnp.pad(ht, ((0, 0), (0, pad)), constant_values=1.0)
    if theta is None:
        i7, i5, i3, diffs = genz_malik_eval_soa(
            f, ct, ht, block_regions=block, interpret=interpret
        )
    else:
        leaves, treedef = jax.tree.flatten(theta)
        leaves = [jnp.asarray(leaf, centers.dtype).reshape(-1) for leaf in leaves]
        sizes = tuple(int(leaf.shape[0]) for leaf in leaves)
        rows = jnp.concatenate(leaves)
        theta_rows = jnp.broadcast_to(rows[:, None], (rows.shape[0], ct.shape[1]))
        i7, i5, i3, diffs = genz_malik_eval_soa(
            _theta_wrapper(f, treedef, sizes),
            ct,
            ht,
            theta_rows,
            block_regions=block,
            interpret=interpret,
        )
    return i7[:b], i5[:b], i3[:b], diffs[:, :b].T
