"""Hypothesis property tests on system invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import genz_malik
from repro.core.redistribution import make_schedule, ring_perms
from repro.core.region_store import uniform_partition
from repro.models.layers import blockwise_attention, rmsnorm, rmsnorm_init

_SETTINGS = dict(max_examples=20, deadline=None)


# --- quadrature invariants ----------------------------------------------------


@given(
    d=st.integers(1, 5),
    m=st.integers(0, 6),
    lo=st.floats(-2.0, 0.0),
    width=st.floats(0.1, 3.0),
)
@settings(**_SETTINGS)
def test_uniform_partition_conserves_volume(d, m, lo, width):
    los = np.full(d, lo)
    his = los + width
    centers, halfw = uniform_partition(los, his, 2**m)
    assert centers.shape == (2**m, d)
    total = np.sum(np.prod(2 * halfw, axis=1))
    assert np.isclose(total, width**d, rtol=1e-10)
    assert np.all(centers - halfw >= los - 1e-12)
    assert np.all(centers + halfw <= his + 1e-12)


@given(
    d=st.integers(1, 4),
    degree=st.integers(0, 7),
    seed=st.integers(0, 2**31 - 1),
)
@settings(**_SETTINGS)
def test_gm_rule_exact_on_random_monomial(d, degree, seed):
    rng = np.random.default_rng(seed)
    # random powers with total degree <= 7
    powers = np.zeros(d, np.int64)
    remaining = degree
    for i in range(d):
        p = rng.integers(0, remaining + 1)
        powers[i] = p
        remaining -= p

    def f(x):
        return jnp.prod(x ** jnp.asarray(powers, x.dtype)[:, None], axis=0)

    c = jnp.full((1, d), 0.5, jnp.float64)
    h = jnp.full((1, d), 0.5, jnp.float64)
    i7, _, _, _ = genz_malik.gm_eval_reference(f, c, h)
    exact = float(np.prod(1.0 / (powers + 1.0)))
    assert np.isclose(float(i7[0]), exact, rtol=1e-10, atol=1e-12)


@given(seed=st.integers(0, 2**31 - 1), axis=st.integers(0, 2))
@settings(**_SETTINGS)
def test_split_children_partition_parent(seed, axis):
    """Volume + containment invariants of axis bisection (any box, any axis)."""
    rng = np.random.default_rng(seed)
    center = rng.uniform(-1, 1, 3)
    halfw = rng.uniform(0.05, 1.0, 3)
    h_child = halfw.copy()
    h_child[axis] *= 0.5
    ca = center.copy()
    ca[axis] -= h_child[axis]
    cb = center.copy()
    cb[axis] += h_child[axis]
    # children tile the parent: volumes sum, bounds match
    assert np.isclose(2 * np.prod(2 * h_child), np.prod(2 * halfw))
    assert np.isclose(ca[axis] - h_child[axis], center[axis] - halfw[axis])
    assert np.isclose(cb[axis] + h_child[axis], center[axis] + halfw[axis])
    assert np.isclose(ca[axis] + h_child[axis], cb[axis] - h_child[axis])


@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 300))
@settings(**_SETTINGS)
def test_survivor_sort_is_a_stable_descending_error_order(seed, n):
    """Active slots first by non-increasing float32-rounded error, ties and
    the inactive tail in slot order — a stable argsort of that key (errors
    below float32's normal range count as zero: the device flushes them)."""
    from repro.core.split import survivor_sort_perm

    rng = np.random.default_rng(seed)
    err = np.abs(rng.standard_normal(n)) * 10.0 ** rng.integers(-40, 10, n)
    err[rng.random(n) < 0.2] = 0.0
    err[rng.random(n) < 0.2] = err[0]  # exact ties
    active = rng.random(n) < 0.7
    perm = np.asarray(survivor_sort_perm(jnp.asarray(err), jnp.asarray(active)))
    e32 = err.astype(np.float32)
    e32[e32 < np.finfo(np.float32).tiny] = 0.0
    key = np.where(active, -e32.astype(np.float64), np.inf)
    np.testing.assert_array_equal(perm, np.argsort(key, kind="stable"))


# --- cyclic redistribution schedule invariants ----------------------------------


@given(n=st.integers(0, 5000), max_len=st.integers(1, 16))
@settings(**_SETTINGS)
def test_schedule_shifts_unique_bounded_in_range(n, max_len):
    """Any ring size, any budget: shifts are unique, within the budget, and
    always a valid ring distance (never 0 = self-pairing)."""
    sched = make_schedule(n, max_len)
    assert len(sched) == len(set(sched))
    assert len(sched) <= max_len
    for s in sched:
        assert 1 <= s < n
    if n > 1:
        assert sched[0] == 1, "unit stride must lead the schedule"


@given(n=st.integers(2, 64))
@settings(**_SETTINGS)
def test_schedule_visits_every_ring_shift_when_budget_allows(n):
    """With budget for all n-1 distances, every one is visited — any
    imbalance pattern is eventually smoothed regardless of where it sits."""
    sched = make_schedule(n, max_len=n - 1)
    assert set(sched) == set(range(1, n))


@given(n=st.integers(2, 128), shift=st.integers(1, 127))
@settings(**_SETTINGS)
def test_ring_perms_are_self_pair_free_bijections(n, shift):
    """Both ppermute index lists of a round are bijections of the ring with
    no rank paired to itself, and they are mutual inverses (the stats that
    go down come back up)."""
    shift = 1 + shift % (n - 1) if n > 1 else 0
    down, up = ring_perms(n, shift)
    for perm in (down, up):
        srcs = [s for s, _ in perm]
        dsts = [d for _, d in perm]
        assert sorted(srcs) == list(range(n))
        assert sorted(dsts) == list(range(n))
        assert all(s != d for s, d in perm), "rank paired with itself"
    assert {(d, s) for s, d in down} == set(up)


# --- model invariants -----------------------------------------------------------


@given(seed=st.integers(0, 1000), t=st.integers(1, 30))
@settings(max_examples=10, deadline=None)
def test_causal_attention_ignores_future(seed, t):
    """Output at position t must not change when tokens after t change."""
    rng = np.random.default_rng(seed)
    b, s, h, hd = 1, 32, 2, 8
    q = rng.standard_normal((b, s, h, hd)).astype(np.float32)
    k = rng.standard_normal((b, s, h, hd)).astype(np.float32)
    v = rng.standard_normal((b, s, h, hd)).astype(np.float32)
    out1 = blockwise_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True, kv_block=8
    )
    k2, v2 = k.copy(), v.copy()
    k2[:, t:] = rng.standard_normal(k2[:, t:].shape)
    v2[:, t:] = rng.standard_normal(v2[:, t:].shape)
    out2 = blockwise_attention(
        jnp.asarray(q), jnp.asarray(k2), jnp.asarray(v2), causal=True, kv_block=8
    )
    np.testing.assert_allclose(
        np.asarray(out1[:, :t]), np.asarray(out2[:, :t]), rtol=1e-5, atol=1e-5
    )


@given(seed=st.integers(0, 1000), scale=st.floats(0.1, 100.0))
@settings(max_examples=10, deadline=None)
def test_rmsnorm_scale_invariance(seed, scale):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((2, 8, 16)).astype(np.float32)
    p = rmsnorm_init(16)
    a = rmsnorm(p, jnp.asarray(x))
    b = rmsnorm(p, jnp.asarray(scale * x))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)


@given(block=st.sampled_from([4, 8, 16, 64]))
@settings(max_examples=8, deadline=None)
def test_blockwise_attention_block_invariance(block):
    """Result must not depend on the streaming block size."""
    rng = np.random.default_rng(0)
    b, s, h, hd = 1, 48, 2, 8
    q = jnp.asarray(rng.standard_normal((b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, hd)), jnp.float32)
    ref = blockwise_attention(q, k, v, causal=True, kv_block=48)
    out = blockwise_attention(q, k, v, causal=True, kv_block=block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)
