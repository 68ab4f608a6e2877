"""Decentralised cyclic load redistribution (the paper's §3 contribution).

Pairing: at iteration ``t`` every device pairs with the rank at ring
distance ``s = schedule[t mod len(schedule)]`` — the paper's "cyclic
round-robin policy".  Because XLA SPMD collectives require *static*
communication patterns, the shift sequence is a compile-time schedule and
each round is dispatched through ``lax.switch`` over per-shift branches
(DESIGN.md §2).  The default schedule front-loads power-of-two strides,
which map onto ICI torus dimensions — addressing the topology-blindness the
paper lists as a limitation of its own round-robin.

Transfer protocol per round (all inside one shard_map body):

  phase 1 (stats):   ppermute of a 4-int vector [n_rows, free, surplus,
                     deficit] from each rank to its upstream neighbour, so
                     donors see their receiver's capacity, and the mirror
                     direction so receivers know what is coming.
  phase 2 (payload): ppermute of a fixed ``(cap, 2d)`` buffer carrying ONLY
                     subregion coordinates (centres ++ halfwidths) — the
                     paper transfers "subregion coordinates rather than full
                     data structures"; receivers mark them fresh and
                     re-evaluate.

A transfer happens only donor->receiver (a rank with surplus never has a
deficit, so at most one direction of each pair is live — donor/donor pairs
idle, the same limitation the paper documents).  The transferred regions are
the *largest-error* ones: `split.classify_split_compact` stores the B-child
of the highest-error parents at the tail of the occupied block, so the tail
window [n_rows - n_send, n_rows) is exactly "the top of the sorted error
list", and removing it keeps the occupied block contiguous with no extra
compaction pass.

Conservative in-flight accounting: convergence metadata is psum'd *before*
redistribution from fully-evaluated regions, and a transferred region is
re-evaluated by its receiver before the next metadata exchange — every
region is therefore counted in every global error estimate exactly once
(DESIGN.md §4), which is the structural version of the paper's "in-flight
estimates that conservatively bound the contribution of subregions
currently in transit".
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.region_store import RegionState


def make_schedule(n_devices: int, max_len: int = 8) -> tuple[int, ...]:
    """Ring-shift schedule: powers of two first (ICI-torus friendly), then
    the remaining strides in ascending order up to ``max_len`` entries."""
    if n_devices <= 1:
        return ()
    shifts: list[int] = []
    s = 1
    while s < n_devices and len(shifts) < max_len:
        shifts.append(s)
        s <<= 1
    s = 3
    while len(shifts) < min(n_devices - 1, max_len):
        if s < n_devices and s not in shifts:
            shifts.append(s)
        s += 1
    return tuple(shifts)


def ring_perms(n: int, shift: int) -> tuple[list, list]:
    """The two ppermute index lists of one cyclic round at ring distance
    ``shift``: ``down`` routes rank ``i``'s data to ``i - shift`` (so every
    rank sees its downstream partner ``i + shift``), ``up`` routes to
    ``i + shift`` (payload direction: donor ``i`` feeds ``i + shift``).

    Shared by region-level :func:`redistribute` and the batch service's
    problem-level rebalancer — both implement the paper's cyclic round-robin
    pairing, at different granularities.
    """
    down = [(i, (i - shift) % n) for i in range(n)]  # i's stats -> upstream
    up = [(i, (i + shift) % n) for i in range(n)]  # payload / stats downstream
    return down, up


_ring_perms = ring_perms  # backward-compatible private alias


def check_ring_invariants(n_devices: int) -> None:
    """Assert the schedule/permutation invariants for an ``n_devices`` ring.

    Every shift in :func:`make_schedule` must be a nonzero ring distance
    strictly below ``n_devices``, with no duplicates, and each of its
    :func:`ring_perms` directions must be a bijection on ranks with the two
    directions mutually inverse.  The trivial ring (``n_devices <= 1``) has
    an empty schedule.  Used by the chaos selftest to certify that an
    elastically shrunken mesh still presents a valid cyclic-pairing topology
    to the compiled collectives.
    """
    schedule = make_schedule(n_devices)
    if n_devices <= 1:
        assert schedule == (), schedule
        return
    assert len(set(schedule)) == len(schedule), schedule
    ranks = list(range(n_devices))
    for shift in schedule:
        assert 0 < shift < n_devices, (shift, n_devices)
        down, up = ring_perms(n_devices, shift)
        for perm in (down, up):
            assert sorted(s for s, _ in perm) == ranks, perm
            assert sorted(d for _, d in perm) == ranks, perm
        assert {(d, s) for s, d in down} == set(up), (down, up)


def exchange_pair_stats(
    stats: jnp.ndarray, axis_name: str, n_devices: int, shift: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Phase-1 stats swap of a cyclic round (see module docstring).

    Returns ``(down_stats, up_stats)``: on rank ``i``, ``down_stats`` is the
    stats vector of its receiver ``i + shift`` and ``up_stats`` that of its
    donor ``i - shift`` — both sides of a pair can therefore agree on the
    transfer size from the same four numbers without a second round trip.
    """
    down, up = ring_perms(n_devices, shift)
    return (
        jax.lax.ppermute(stats, axis_name, down),
        jax.lax.ppermute(stats, axis_name, up),
    )


def dispatch_cyclic(schedule: Sequence[int], t, make_round, *operands):
    """Run round ``t`` of a static cyclic schedule via ``lax.switch``.

    XLA SPMD collectives need compile-time communication patterns, so every
    shift in ``schedule`` is traced into its own branch (``make_round(shift)``
    returns the round body) and the iteration counter picks the branch at run
    time.  This is the pairing discipline shared by region redistribution and
    the batch service's problem migration.
    """
    branches = [make_round(s) for s in schedule]
    return jax.lax.switch(jnp.mod(t, len(schedule)), branches, *operands)


def redistribute(
    state: RegionState,
    *,
    axis_name: str,
    n_devices: int,
    schedule: Sequence[int],
    cap: int,
    limit: int,
) -> RegionState:
    """One redistribution round (inside shard_map). See module docstring.
    Its operations carry the named scope ``quad.redistribute``."""
    if n_devices <= 1 or not schedule:
        return state
    with jax.named_scope("quad.redistribute"):
        return _redistribute(state, axis_name, n_devices, schedule, cap, limit)


def _redistribute(
    state: RegionState,
    axis_name: str,
    n_devices: int,
    schedule: Sequence[int],
    cap: int,
    limit: int,
) -> RegionState:
    C = state.capacity
    d = state.d
    idx = jnp.arange(C)
    j = jnp.arange(cap)

    n_rows = jnp.sum(state.active).astype(jnp.int32)
    total = jax.lax.psum(n_rows, axis_name)
    fair_lo = total // n_devices
    fair_hi = -(-total // n_devices)  # ceil
    surplus = jnp.maximum(n_rows - fair_hi, 0)
    deficit = jnp.maximum(fair_lo - n_rows, 0)
    free = jnp.maximum(jnp.int32(limit) - n_rows, 0)
    stats = jnp.stack([n_rows, free, surplus, deficit])

    def round_fn(shift: int):
        _, perm_up = ring_perms(n_devices, shift)

        def fn(state: RegionState) -> RegionState:
            # --- phase 1: stats both ways ---------------------------------
            down_stats, up_stats = exchange_pair_stats(
                stats, axis_name, n_devices, shift
            )
            _, down_free, _, down_deficit = down_stats
            _, _, up_surplus, _ = up_stats

            n_send = jnp.minimum(
                jnp.minimum(jnp.int32(cap), surplus),
                jnp.minimum(down_deficit, down_free),
            )
            n_recv = jnp.minimum(
                jnp.minimum(jnp.int32(cap), up_surplus),
                jnp.minimum(deficit, free),
            )

            # --- phase 2: payload (coordinates only) ----------------------
            src = jnp.clip(n_rows - n_send + j, 0, C - 1)
            valid_send = j < n_send
            payload = jnp.concatenate(
                [state.centers[src], state.halfw[src]], axis=1
            )  # (cap, 2d)
            payload = jnp.where(valid_send[:, None], payload, 0.0)
            incoming = jax.lax.ppermute(payload, axis_name, perm_up)

            # --- donor side: retire the sent tail window -------------------
            sent = (idx >= n_rows - n_send) & (idx < n_rows)
            active = state.active & ~sent
            fresh = state.fresh & ~sent

            # --- receiver side: splice into the contiguous tail ------------
            base = n_rows - n_send
            dest = jnp.where(j < n_recv, base + j, C)  # C = dropped
            centers = state.centers.at[dest].set(incoming[:, :d], mode="drop")
            halfw = state.halfw.at[dest].set(incoming[:, d:], mode="drop")
            active = active.at[dest].set(True, mode="drop")
            fresh = fresh.at[dest].set(True, mode="drop")
            est = state.est.at[dest].set(0.0, mode="drop")
            err = state.err.at[dest].set(0.0, mode="drop")
            axv = state.axis.at[dest].set(0, mode="drop")
            return dataclasses.replace(
                state,
                centers=centers,
                halfw=halfw,
                est=est,
                err=err,
                axis=axv,
                active=active,
                fresh=fresh,
            )

        return fn

    return dispatch_cyclic(schedule, state.it, round_fn, state)


def balance_stats(n_rows: jnp.ndarray, axis_name: str, n_devices: int):
    """(max, mean, imbalance) of per-device active counts — the idle-time
    proxy reported in the Fig. 4b benchmark (idle ~ 1 - mean/max)."""
    total = jax.lax.psum(n_rows, axis_name)
    biggest = jax.lax.pmax(n_rows, axis_name)
    mean = total / n_devices
    imb = jnp.where(biggest > 0, 1.0 - mean / jnp.maximum(biggest, 1), 0.0)
    return biggest, mean, imb
