"""Unit tests for the static ring-shift redistribution schedule, plus
property tests of the transfer-round invariants on a real (virtual) mesh."""

import dataclasses

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.redistribution import make_schedule


def _is_pow2(x: int) -> bool:
    return x > 0 and x & (x - 1) == 0


@pytest.mark.parametrize("n", [1, 2, 3, 4, 5, 6, 7, 8, 12, 16, 100, 1000])
def test_schedule_shifts_unique_and_in_range(n):
    sched = make_schedule(n)
    assert len(set(sched)) == len(sched), "duplicate shift"
    for s in sched:
        assert 1 <= s < n, f"shift {s} out of ring range for {n} devices"


def test_single_device_has_empty_schedule():
    assert make_schedule(1) == ()
    assert make_schedule(0) == ()


def test_two_and_three_devices():
    assert make_schedule(2) == (1,)
    assert make_schedule(3) == (1, 2)


def test_powers_of_two_come_first():
    """ICI-torus-friendly ordering: every power-of-two stride < n precedes
    every non-power-of-two stride (within the max_len budget)."""
    for n in (4, 6, 8, 12, 16, 32, 100):
        sched = make_schedule(n)
        seen_non_pow2 = False
        for s in sched:
            if _is_pow2(s):
                assert not seen_non_pow2, f"pow2 shift {s} after non-pow2 in {sched}"
            else:
                seen_non_pow2 = True
        # the pow2 prefix is complete: all powers of two below n (up to the
        # length cap) are present
        pow2_in = [s for s in sched if _is_pow2(s)]
        expected = []
        s = 1
        while s < n and len(expected) < 8:
            expected.append(s)
            s <<= 1
        assert pow2_in == expected


@pytest.mark.parametrize("n", [2, 3, 4, 7, 8, 9])
def test_small_rings_cover_every_distance(n):
    """With few devices the schedule should reach every ring distance, so
    any imbalance pattern is eventually smoothed."""
    sched = make_schedule(n)
    assert set(sched) == set(range(1, n))


def test_max_len_caps_schedule():
    for n in (1 << 10, 1 << 13):
        sched = make_schedule(n)
        assert len(sched) == 8  # default max_len
        assert make_schedule(n, max_len=4) == sched[:4]


def test_huge_ring_beyond_pow2_budget():
    """n > 2^max_len: the schedule is all powers of two (the budget is spent
    before any odd stride fits)."""
    sched = make_schedule(1 << 12, max_len=8)
    assert sched == (1, 2, 4, 8, 16, 32, 64, 128)


def test_non_power_of_two_fill():
    # 6 devices: pow2 strides 1,2,4 then odd strides 3,5
    assert make_schedule(6) == (1, 2, 4, 3, 5)


# --- transfer-round invariants (needs a multi-device mesh) --------------------
#
# Hypothesis drives random per-device populations through one redistribute
# round inside shard_map and checks the structural invariants the adaptive
# drivers rely on: conservation of the live-region population (count and
# coordinate multiset — transfers move coordinates, never duplicate or drop
# them), contiguity of the occupied block on both donor and receiver, and
# re-evaluation marking of everything that moved.

_N_DEV = len(jax.devices())
_needs_mesh = pytest.mark.skipif(
    _N_DEV < 2, reason="redistribute is an inter-device transfer; needs >= 2 devices"
)

_C = 64  # store capacity per device (small: compile once, run many examples)
_D = 2
_CAP = 8  # message cap per round
_LIMIT = 3 * _C // 4


def _stacked_state(n_dev, counts, it, seed):
    from repro.core.region_store import RegionState

    rng = np.random.default_rng(seed)
    z = np.zeros
    centers = rng.uniform(0.1, 0.9, (n_dev, _C, _D))
    halfw = rng.uniform(0.01, 0.1, (n_dev, _C, _D))
    est = rng.uniform(-1.0, 1.0, (n_dev, _C))
    err = rng.uniform(1e-6, 1.0, (n_dev, _C))
    active = z((n_dev, _C), bool)
    for dev, cnt in enumerate(counts):
        active[dev, :cnt] = True
    return RegionState(
        centers=jnp.asarray(centers),
        halfw=jnp.asarray(halfw),
        est=jnp.where(jnp.asarray(active), jnp.asarray(est), 0.0),
        err=jnp.where(jnp.asarray(active), jnp.asarray(err), 0.0),
        axis=jnp.zeros((n_dev, _C), jnp.int32),
        active=jnp.asarray(active),
        fresh=jnp.zeros((n_dev, _C), bool),
        fin_integral=jnp.zeros((n_dev,)),
        fin_error=jnp.zeros((n_dev,)),
        n_evals=jnp.zeros((n_dev,)),
        it=jnp.full((n_dev,), it, jnp.int32),
        overflowed=jnp.zeros((n_dev,), bool),
    )


_ROUND_CACHE: dict = {}


def _run_round(state, n_dev):
    from jax.sharding import PartitionSpec as P

    from repro.core.redistribution import redistribute

    fn = _ROUND_CACHE.get(n_dev)
    if fn is None:
        mesh = jax.make_mesh((n_dev,), ("dev",), devices=jax.devices()[:n_dev])
        schedule = make_schedule(n_dev)

        def body(state):
            state = jax.tree.map(lambda x: x[0], state)
            state = redistribute(
                state,
                axis_name="dev",
                n_devices=n_dev,
                schedule=schedule,
                cap=_CAP,
                limit=_LIMIT,
            )
            return jax.tree.map(lambda x: x[None], state)

        fn = jax.jit(
            jax.shard_map(
                body, mesh=mesh, in_specs=P("dev"), out_specs=P("dev"), check_vma=False
            )
        )
        _ROUND_CACHE[n_dev] = fn
    return fn(state)


def _coord_multiset(state):
    c = np.asarray(state.centers)
    h = np.asarray(state.halfw)
    act = np.asarray(state.active)
    rows = np.concatenate([c, h], axis=-1)[act]  # exact float64 copies
    return sorted(map(tuple, rows))


@_needs_mesh
def test_transfer_round_invariants_hypothesis():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @given(
        n_dev=st.sampled_from(sorted({2, min(4, _N_DEV), _N_DEV})),
        counts_seed=st.integers(0, 2**31 - 1),
        it=st.integers(0, 12),
    )
    @settings(max_examples=15, deadline=None)
    def check(n_dev, counts_seed, it):
        rng = np.random.default_rng(counts_seed)
        counts = rng.integers(0, _LIMIT + 1, n_dev).tolist()
        state = _stacked_state(n_dev, counts, it, counts_seed)
        before = _coord_multiset(state)
        out = _run_round(state, n_dev)

        act = np.asarray(out.active)
        fresh = np.asarray(out.fresh)
        err = np.asarray(out.err)
        new_counts = act.sum(axis=1)
        # conservation: live-region count and coordinate multiset
        assert int(new_counts.sum()) == sum(counts)
        assert _coord_multiset(out) == before
        for dev in range(n_dev):
            n = int(new_counts[dev])
            # occupied block stays contiguous on donor and receiver alike
            assert not act[dev, n:].any(), (dev, counts, new_counts)
            # a receiver never exceeds the transfer limit
            if n > counts[dev]:
                assert n <= _LIMIT, (dev, counts, new_counts)
                # every spliced-in region is marked for re-evaluation with
                # invalidated estimates (conservative in-flight accounting)
                moved = fresh[dev] & act[dev]
                assert moved.sum() == n - counts[dev]
                assert not err[dev][moved].any()
            # donors / bystanders keep their surviving prefix untouched
            keep = min(n, counts[dev])
            np.testing.assert_array_equal(
                np.asarray(out.est)[dev, :keep],
                np.asarray(state.est)[dev, :keep],
            )

    check()


@_needs_mesh
def test_transfer_round_moves_from_overloaded_to_idle():
    """Deterministic smoke: with all work on rank 0, one round transfers a
    full fair-share-capped payload to its shift-1 ring neighbour."""
    counts = [40] + [0] * (_N_DEV - 1)
    # the donor may not send below its fair ceiling, the receiver not pull
    # above its fair floor, and the message cap bounds everything
    expected = min(_CAP, 40 - (-(-40 // _N_DEV)), 40 // _N_DEV)
    state = _stacked_state(_N_DEV, counts, it=0, seed=7)  # shift = schedule[0] = 1
    out = _run_round(state, _N_DEV)
    new_counts = np.asarray(out.active).sum(axis=1)
    assert int(new_counts.sum()) == 40
    assert new_counts[0] == 40 - expected
    assert new_counts[1] == expected  # ring neighbour at shift 1
    # the donor sheds its tail window [n - sent, n): the paper's "largest
    # error subregions, chosen after sorting"
    sent = np.concatenate(
        [np.asarray(state.centers)[0], np.asarray(state.halfw)[0]], axis=-1
    )[40 - expected : 40]
    got = np.concatenate(
        [np.asarray(out.centers)[1], np.asarray(out.halfw)[1]], axis=-1
    )[:expected]
    assert sorted(map(tuple, sent)) == sorted(map(tuple, got))
