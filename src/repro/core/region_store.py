"""Fixed-capacity Structure-of-Arrays region store.

XLA requires static shapes, so the dynamic region list of CPU adaptive codes
becomes a fixed-capacity SoA store plus an ``active`` mask — the same design
PAGANI uses on GPU (the paper keeps "all subregion data resident on the
device" in SoA layout; here the arrays additionally live in a jit-compiled
program so the whole iteration is one XLA module).

Slot discipline maintained by ``repro.core.split.classify_split_compact``:
active regions are compacted to the front and sorted by descending error
estimate; finalised regions are folded into scalar accumulators and their
slots freed.

**Active-window invariant.**  Every operation that mutates the region
population keeps the active slots contiguous in ``[0, n_active)``:
``init_state`` fills the leading slots, ``classify_split_compact`` compacts
survivors to the front and appends children directly after them, and
``redistribution.redistribute`` only retires or splices the tail of the
occupied block.  The adaptive drivers exploit this to run the *whole
iteration* — rule evaluation, classification/global reductions, and the
sort-based split/compact advance — on a leading *window* of the SoA arrays
sized from a geometric ladder (:func:`window_ladder` / :func:`select_window`)
instead of all ``capacity`` slots, so per-iteration cost scales with the live
population (the advance stage needs ``window >= min(2 * n_active, capacity)``
because splitting can double the population; see DESIGN.md §3).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "centers",
        "halfw",
        "est",
        "err",
        "axis",
        "active",
        "fresh",
        "fin_integral",
        "fin_error",
        "n_evals",
        "it",
        "overflowed",
    ],
    meta_fields=[],
)
@dataclasses.dataclass
class RegionState:
    """One device's region population + finalised accumulators."""

    centers: jnp.ndarray  # (C, d)
    halfw: jnp.ndarray  # (C, d)
    est: jnp.ndarray  # (C,)   degree-7 estimate
    err: jnp.ndarray  # (C,)   heuristic error estimate
    axis: jnp.ndarray  # (C,)   int32 split axis
    active: jnp.ndarray  # (C,)   bool
    fresh: jnp.ndarray  # (C,)   bool — needs (re-)evaluation
    fin_integral: jnp.ndarray  # ()  accumulated finalised integral
    fin_error: jnp.ndarray  # ()  accumulated finalised error
    n_evals: jnp.ndarray  # ()  float64 integrand-evaluation counter
    it: jnp.ndarray  # ()  int32 iteration counter
    overflowed: jnp.ndarray  # () bool — capacity pressure was ever hit

    @property
    def capacity(self) -> int:
        return self.centers.shape[0]

    @property
    def d(self) -> int:
        return self.centers.shape[1]

    def n_active(self) -> jnp.ndarray:
        return jnp.sum(self.active)

    def global_estimates(
        self, window: int | None = None
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """(integral, error) combining finalised + active contributions.

        ``window`` reduces over the leading rows only — exact whenever every
        active slot sits inside the window, which the active-window invariant
        guarantees for any ``window >= n_active`` (the masked tail contributes
        exact zeros, so the windowed and full reductions agree bitwise).
        """
        act = self.active if window is None else self.active[:window]
        est = self.est if window is None else self.est[:window]
        err = self.err if window is None else self.err[:window]
        integral = self.fin_integral + jnp.sum(jnp.where(act, est, 0.0))
        error = self.fin_error + jnp.sum(jnp.where(act, err, 0.0))
        return integral, error


def empty_state(capacity: int, d: int, dtype) -> RegionState:
    z = jnp.zeros
    return RegionState(
        centers=z((capacity, d), dtype),
        halfw=z((capacity, d), dtype),
        est=z((capacity,), dtype),
        err=z((capacity,), dtype),
        axis=z((capacity,), jnp.int32),
        active=z((capacity,), bool),
        fresh=z((capacity,), bool),
        fin_integral=jnp.asarray(0.0, dtype),
        fin_error=jnp.asarray(0.0, dtype),
        n_evals=jnp.asarray(0.0, dtype),
        it=jnp.asarray(0, jnp.int32),
        overflowed=jnp.asarray(False, bool),
    )


def uniform_partition(
    lo: np.ndarray, hi: np.ndarray, n_boxes: int
) -> tuple[np.ndarray, np.ndarray]:
    """Bisect [lo, hi] into ``n_boxes`` (power of two) equal boxes.

    Axes are cycled in round-robin order, so the partition stays as cubic as
    possible — this is the paper's "initial uniform partition".
    Returns (centers, halfw) as float64 arrays of shape (n_boxes, d).
    """
    lo = np.asarray(lo, np.float64)
    hi = np.asarray(hi, np.float64)
    d = lo.shape[0]
    assert n_boxes & (n_boxes - 1) == 0, "n_boxes must be a power of two"
    boxes = [(lo.copy(), hi.copy())]
    level = 0
    while len(boxes) < n_boxes:
        axis = level % d
        nxt = []
        for blo, bhi in boxes:
            mid = 0.5 * (blo[axis] + bhi[axis])
            left_hi = bhi.copy()
            left_hi[axis] = mid
            right_lo = blo.copy()
            right_lo[axis] = mid
            nxt.append((blo, left_hi))
            nxt.append((right_lo, bhi))
        boxes = nxt
        level += 1
    centers = np.stack([0.5 * (b[0] + b[1]) for b in boxes])
    halfw = np.stack([0.5 * (b[1] - b[0]) for b in boxes])
    return centers, halfw


def init_state(
    capacity: int,
    lo: np.ndarray,
    hi: np.ndarray,
    n_init: int,
    dtype,
) -> RegionState:
    """Fresh state holding the initial uniform partition."""
    lo = np.asarray(lo, np.float64)
    d = lo.shape[0]
    centers, halfw = uniform_partition(lo, hi, n_init)
    st = empty_state(capacity, d, dtype)
    st = dataclasses.replace(
        st,
        centers=st.centers.at[:n_init].set(jnp.asarray(centers, dtype)),
        halfw=st.halfw.at[:n_init].set(jnp.asarray(halfw, dtype)),
        active=st.active.at[:n_init].set(True),
        fresh=st.fresh.at[:n_init].set(True),
    )
    return st


def stacked_empty_state(n: int, capacity: int, d: int, dtype) -> RegionState:
    """Empty store with a leading ``(n,)`` axis on every leaf.

    Used by the batch service (one sub-store per problem slot); each slice
    along the leading axis independently satisfies the active-window
    invariant.
    """
    one = empty_state(capacity, d, dtype)
    return jax.tree.map(lambda x: jnp.zeros((n,) + x.shape, x.dtype), one)


def write_slot(
    stacked: RegionState, slot, single: RegionState, mode: str | None = None
) -> RegionState:
    """Overwrite slice ``slot`` of a stacked store with a single-store state.

    Jit-safe with a traced ``slot`` index — the batch service uses this to
    splice a fresh initial partition into a slot freed by a converged
    problem without recompiling per slot.  ``mode`` is forwarded to the
    scatter (the sharded service writes with ``mode="drop"`` and an
    out-of-bounds index on every device but the slot's owner).
    """
    return jax.tree.map(
        lambda dst, src: dst.at[slot].set(src, mode=mode), stacked, single
    )


def window_ladder(capacity: int, min_window: int = 256) -> tuple[int, ...]:
    """Geometric ladder of power-of-two eval-window sizes up to ``capacity``.

    Each rung doubles the previous one, so at most
    ``log2(capacity / min_window) + 1`` distinct window shapes (and therefore
    jit-compiled eval variants) ever exist.  The top rung is always exactly
    ``capacity`` so a full store degrades to the legacy full-capacity path.
    """
    if capacity < 1 or capacity & (capacity - 1):
        raise ValueError("capacity must be a positive power of two")
    w = max(1, min(min_window, capacity))
    w = 1 << (w - 1).bit_length()  # round up to a power of two
    ladder = []
    while w < capacity:
        ladder.append(w)
        w <<= 1
    ladder.append(capacity)
    return tuple(ladder)


def rung_index(rungs: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """Index of the smallest ladder rung covering ``n`` (clamped to the top).

    The device-side rung pick of the ``lax.switch``-dispatched windowed
    stages (the device-resident driver), so they agree bit-for-bit with the
    host-side :func:`select_window`.
    """
    return jnp.minimum(jnp.searchsorted(rungs, n), rungs.shape[0] - 1)


def select_window(ladder: tuple[int, ...], n_active: int) -> int:
    """Smallest ladder rung that covers ``n_active`` contiguous rows.

    Host-side mirror of the device-side rung choice in
    ``adaptive.make_switched_eval_step`` — both are left-searchsorted, so the
    host- and device-driven loops pick identical windows for the same count.
    ``n_active == 0`` selects the smallest rung (the drivers still dispatch
    one eval before observing the empty population; keep it cheap).
    """
    ix = int(np.searchsorted(np.asarray(ladder), n_active, side="left"))
    return ladder[min(ix, len(ladder) - 1)]


def check_invariants(state: RegionState, lo, hi, atol: float = 1e-12) -> None:
    """Host-side structural checks (used by tests, not in the hot path)."""
    c = np.asarray(state.centers)
    h = np.asarray(state.halfw)
    act = np.asarray(state.active)
    assert np.all(h[act] > 0), "active region with non-positive halfwidth"
    assert np.all(c[act] - h[act] >= np.asarray(lo) - atol), "region below domain"
    assert np.all(c[act] + h[act] <= np.asarray(hi) + atol), "region above domain"
    fresh = np.asarray(state.fresh)
    assert not np.any(fresh & ~act), "fresh flag set on inactive slot"
    # active-window invariant: actives contiguous at the front of the store
    assert not np.any(act[int(act.sum()) :]), "active slots not contiguous"
