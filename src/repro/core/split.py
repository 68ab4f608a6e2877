"""Fused filter + split + compaction (the paper's fused GPU stage).

One sort-based pass that (i) folds finalised regions into the scalar
accumulators, (ii) compacts survivors to the front ordered by descending
error, and (iii) splits as many survivors as capacity allows along their
assigned axes (children replace the parent slot and append after the
survivor block, so all fresh children occupy a predictable range).

On GPU the paper fuses filtering and splitting into a single kernel to cut
data movement; under XLA the whole step is one compiled module, so the fusion
here is algorithmic (single argsort, single gather) rather than a hand-written
kernel — see DESIGN.md §2.

**Windowed advance** (DESIGN.md §3).  Both entry points take an optional
``window`` so the sort, the gathers and the child writes run on the leading
``window`` rows only, leaving the tail ``[window, capacity)`` out of the
compiled computation entirely.  The caller owes two guarantees, both free
under the active-window invariant (every active slot lives in
``[0, n_active)``):

- every active slot is inside the window (so the sort sees the whole live
  population and the tail is all-inactive);
- ``window >= min(2 * n_active, capacity)`` (post-split the population can
  double, and under capacity pressure the child block extends to exactly
  ``capacity``).

The capacity-semantics scalars — the ``3C//4`` forced-finalise limit and the
split budget ``k = min(n_act, C - n_act)`` — stay defined against the FULL
capacity ``C``, never the window: whenever they could bite (``n_act > C/2``),
the second guarantee already forces the full-capacity window, so a windowed
advance is bit-identical to the legacy full one in every regime (argsort is
stable, so survivors order identically; freed-slot *garbage* may land in
different slots, but garbage is never re-exposed — every slot that becomes
active is overwritten with child data first).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.region_store import RegionState


def survivor_sort_perm(err: jnp.ndarray, active: jnp.ndarray) -> jnp.ndarray:
    """Permutation compacting active slots to the front by descending error.

    The single source of truth for the compaction order, shared by
    :func:`classify_split_compact` and :func:`compact`: survivors sort by
    descending error estimate, freed/inactive slots sink to the back (stable,
    so equal keys — and the inactive block — keep their relative order; the
    windowed and full-capacity sorts therefore agree on every live slot).

    The key is the error rounded to float32 and mapped to an int32 of the
    same order: XLA's TPU backend takes minutes to compile an argsort over
    (emulated) float64 keys at each large window rung, and one int32 key
    costs a small fraction of that.  Errors equal to float32 precision tie
    (errors below float32's normal range tie with zero), and ties keep their
    slot order.
    """
    e32 = (-err).astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(e32, jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)  # monotone
    last = jnp.iinfo(jnp.int32).max
    key = jnp.where(jnp.isnan(e32), last - 1, key)  # NaN after finite errors
    key = jnp.where(active, key, last)
    slots = jnp.arange(err.shape[0], dtype=jnp.int32)
    return jax.lax.sort((key, slots), num_keys=1, is_stable=True)[1]


def _window(state: RegionState, window: Optional[int]) -> int:
    w = state.capacity if window is None else int(window)
    if not 0 < w <= state.capacity:
        raise ValueError(f"window {w} outside (0, {state.capacity}]")
    return w


def classify_split_compact(
    state: RegionState,
    finalize_mask: jnp.ndarray,
    window: Optional[int] = None,
) -> RegionState:
    """Apply the classifier verdict, then split every surviving region.

    Under capacity pressure only the top-(free slots) regions by error are
    split; the rest stay active-but-unsplit (their estimates remain valid,
    they are split on a later iteration).  ``overflowed`` records that
    pressure was ever hit — this is the feasibility limit of Fig. 3a.

    ``finalize_mask`` must have shape ``(window,)`` (``(capacity,)`` when
    ``window`` is ``None``); ``window`` obligations are in the module
    docstring.
    """
    C = state.capacity
    w = _window(state, window)
    act_w = state.active[:w]
    fin = finalize_mask & act_w
    fin_integral = state.fin_integral + jnp.sum(jnp.where(fin, state.est[:w], 0.0))
    fin_error = state.fin_error + jnp.sum(jnp.where(fin, state.err[:w], 0.0))
    active = act_w & ~fin

    perm = survivor_sort_perm(state.err[:w], active)

    centers = state.centers[:w][perm]
    halfw = state.halfw[:w][perm]
    est = state.est[:w][perm]
    err = state.err[:w][perm]
    axis = state.axis[:w][perm]
    active = active[perm]

    n_act = jnp.sum(active)
    idx = jnp.arange(w)

    # Graceful degradation under memory pressure (the paper's Fig. 3a
    # feasibility limit): if the store is nearly full, force-finalise the
    # *lowest-error* tail so splitting can always make progress.  Their
    # (conservative) error estimates are folded into the accumulators, so the
    # global bound remains honest; without this, a full store deadlocks
    # (n_act == C allows zero splits and the classifier threshold, which
    # scales as budget/n_act, can no longer finalise anything).  The limit is
    # a property of the store, not of the window: it can only bite when
    # n_act > 3C/4, which the window contract escalates to the full rung.
    limit = 3 * C // 4
    forced = active & (idx >= limit)
    fin_integral = fin_integral + jnp.sum(jnp.where(forced, est, 0.0))
    fin_error = fin_error + jnp.sum(jnp.where(forced, err, 0.0))
    active = active & ~forced
    n_act = jnp.minimum(n_act, limit)

    k = jnp.minimum(n_act, C - n_act)  # number of regions we can split (+1 slot each)
    overflowed = state.overflowed | (k < n_act) | jnp.any(forced)

    split_row = idx < k  # rows being split (highest error first)

    onehot = jnp.arange(state.d)[None, :] == axis[:, None]  # (w, d)
    h_half = jnp.where(onehot, 0.5 * halfw, halfw)
    # children tile the parent exactly: centres at c -+ h/2 along the axis
    shift = jnp.where(onehot, h_half, 0.0)

    child_a_centers = centers - shift
    child_b_centers = centers + shift

    # Child A overwrites the parent row.
    centers = jnp.where(split_row[:, None], child_a_centers, centers)
    halfw = jnp.where(split_row[:, None], h_half, halfw)

    # Child B appended after the survivor block in REVERSED error order
    # (row i -> n_act + k - 1 - i), so the occupied block's tail holds the
    # children of the highest-error parents — the redistribution layer sends
    # the tail window, which is then exactly "the largest-error subregions,
    # chosen after sorting" (paper §3) while keeping the block contiguous.
    # The window contract (w >= n_act + k) keeps every destination in-window.
    dest = jnp.where(split_row, n_act + k - 1 - idx, w)  # w == OOB, dropped
    centers = centers.at[dest].set(child_b_centers, mode="drop")
    halfw = halfw.at[dest].set(h_half, mode="drop")

    active = active | (idx < n_act + k)
    fresh = split_row | ((idx >= n_act) & (idx < n_act + k))
    # Invalidate stale values on fresh rows so masked reductions stay exact.
    est = jnp.where(fresh, 0.0, est)
    err = jnp.where(fresh, 0.0, err)
    axis = jnp.where(fresh, 0, axis)
    fresh = fresh & active

    if w == C:
        return dataclasses.replace(
            state,
            centers=centers,
            halfw=halfw,
            est=est,
            err=err,
            axis=axis,
            active=active,
            fresh=fresh,
            fin_integral=fin_integral,
            fin_error=fin_error,
            overflowed=overflowed,
        )
    # Write the window back; the untouched tail is all-inactive (and
    # fresh-free) by the window contract, so the full-state invariants hold.
    return dataclasses.replace(
        state,
        centers=state.centers.at[:w].set(centers),
        halfw=state.halfw.at[:w].set(halfw),
        est=state.est.at[:w].set(est),
        err=state.err.at[:w].set(err),
        axis=state.axis.at[:w].set(axis),
        active=state.active.at[:w].set(active),
        fresh=state.fresh.at[:w].set(fresh),
        fin_integral=fin_integral,
        fin_error=fin_error,
        overflowed=overflowed,
    )


def compact(state: RegionState, window: Optional[int] = None) -> RegionState:
    """Compact actives to the front by descending error (no split).

    ``window`` restricts the sort/gather to the leading rows; every active
    slot must already sit inside the window (post-compaction the population
    cannot grow, so ``window >= n_active`` suffices here).
    """
    w = _window(state, window)
    perm = survivor_sort_perm(state.err[:w], state.active[:w])
    leaves = dict(
        centers=state.centers[:w][perm],
        halfw=state.halfw[:w][perm],
        est=state.est[:w][perm],
        err=state.err[:w][perm],
        axis=state.axis[:w][perm],
        active=state.active[:w][perm],
        fresh=state.fresh[:w][perm],
    )
    if w == state.capacity:
        return dataclasses.replace(state, **leaves)
    return dataclasses.replace(
        state,
        **{k: getattr(state, k).at[:w].set(v) for k, v in leaves.items()},
    )
