"""Graceful degradation: quarantine, re-routing, deadlines, service resume.

In-process single-device unit tests for the fault-tolerance layer (the
multi-device chaos run lives in ``repro.service.chaos_selftest``, driven by
``test_chaos.py``): non-finite quarantine in the serial driver and both
engine pools, fallback re-routing with attempt provenance, deadline SLOs,
service checkpoint/resume parity, and the CheckpointManager async-error
regression.
"""

import dataclasses

import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.core.adaptive import integrate, result_status
from repro.core.config import QuadratureConfig
from repro.core.integrands import get_param
from repro.service import (
    BatchScheduler,
    GracefulScheduler,
    QuadRequest,
    ReroutePolicy,
    ServiceCheckpointer,
)
from repro.service.faults import (
    NAN_SENTINEL,
    DeviceDown,
    DeviceLostError,
    SimulatedCrash,
    corrupt_slot_hook,
    crash_at,
    nan_family,
    poison_theta,
)
from repro.service.scheduler import decode_request, encode_request

FAMILY = get_param("genz_gaussian")


def _cfg(**kw):
    base = dict(
        d=2,
        integrand="genz_gaussian",
        rel_tol=1e-3,
        capacity=1 << 9,
        batch_slots=4,
        max_iters=60,
        sync_every=4,
    )
    base.update(kw)
    return QuadratureConfig(**base)


def _requests(n, seed=0, **kw):
    rng = np.random.default_rng(seed)
    return [
        QuadRequest(req_id=i, theta=FAMILY.sample_theta(2, rng), **kw)
        for i in range(n)
    ]


def _vals(results):
    return {
        r.req_id: (r.integral.hex(), r.error.hex(), r.status, r.iterations)
        for r in results
    }


# --- non-finite quarantine ----------------------------------------------------


def test_result_status_ranks_nonfinite_first():
    cfg = _cfg()
    assert result_status(True, 0, 3, cfg, False, nonfinite=True) == "nonfinite"
    assert result_status(True, 0, 3, cfg, False) == "converged"


def test_serial_integrate_quarantines_nan_integrand():
    wrapped = nan_family(FAMILY)
    theta = poison_theta(FAMILY.sample_theta(2, np.random.default_rng(0)))
    res = integrate(_cfg(), integrand=lambda x: wrapped.fn(x, theta))
    assert res.status == "nonfinite"
    assert np.isfinite(res.integral) and np.isfinite(res.error)


def test_nan_wrapper_is_identity_for_healthy_theta():
    wrapped = nan_family(FAMILY)
    theta = FAMILY.sample_theta(2, np.random.default_rng(1))
    base = integrate(_cfg(), integrand=lambda x: FAMILY.fn(x, theta))
    via = integrate(_cfg(), integrand=lambda x: wrapped.fn(x, theta))
    assert base.integral.hex() == via.integral.hex()
    assert base.error.hex() == via.error.hex()
    assert base.status == via.status == "converged"


def test_cubature_fleet_quarantine_contains_poison():
    """One NaN slot must not perturb healthy slots' bits, and is collected
    with status nonfinite instead of grinding to max_iters."""
    reqs = _requests(4)
    clean = BatchScheduler(_cfg(), FAMILY)
    base = _vals(clean.serve(list(reqs)))

    wrapped = nan_family(FAMILY)
    poisoned = reqs + [
        QuadRequest(req_id=99, theta=poison_theta(reqs[0].theta))
    ]
    sched = BatchScheduler(_cfg(), wrapped)
    results = list(sched.serve(poisoned))
    vals = _vals(results)
    bad = vals.pop(99)
    assert bad[2] == "nonfinite"
    assert vals == base
    assert sched.last_stats["quarantines"] == 1


def test_vegas_fleet_quarantine():
    wrapped = nan_family(FAMILY)
    cfg = _cfg(backend="vegas", mc_samples=512, mc_max_iters=20)
    reqs = _requests(2, rel_tol=1e-2) + [
        QuadRequest(
            req_id=50,
            theta=poison_theta(FAMILY.sample_theta(2, np.random.default_rng(5))),
        )
    ]
    sched = BatchScheduler(cfg, wrapped)
    results = list(sched.serve(reqs))
    by_id = {r.req_id: r for r in results}
    assert by_id[50].status == "nonfinite"
    assert by_id[50].backend == "vegas"
    for i in (0, 1):
        assert by_id[i].status in ("converged", "max_iters")
        assert np.isfinite(by_id[i].integral)
    assert sched.last_stats["quarantines"] == 1


# --- fallback re-routing ------------------------------------------------------


def test_capacity_eviction_reroutes_to_vegas():
    """A region-store-starved cubature request must come back converged
    through the MC pool, with full attempt provenance."""
    cfg = _cfg(
        capacity=1 << 5, rel_tol=1e-7, mc_samples=4096, mc_max_iters=30
    )
    reqs = _requests(2)
    sched = BatchScheduler(cfg, FAMILY)
    statuses = {r.req_id: r.status for r in sched.serve(list(reqs))}
    assert "capacity" in statuses.values(), statuses  # scenario sanity

    graceful = GracefulScheduler(cfg, FAMILY)
    results = {r.req_id: r for r in graceful.serve(list(reqs))}
    assert len(results) == 2
    rerouted = [r for r in results.values() if r.attempts == 2]
    assert rerouted, results
    for r in rerouted:
        assert r.retried_from == "capacity"
        assert r.backend == "vegas"
        exact = FAMILY.exact(2, reqs[r.req_id].theta)
        assert abs(r.integral - exact) <= 1e-2 * abs(exact)
    assert graceful.last_stats["reroutes"] == len(rerouted)


def test_reroute_respects_attempt_budget():
    policy = ReroutePolicy(max_attempts=1)
    cfg = _cfg(capacity=1 << 5, rel_tol=1e-7)
    graceful = GracefulScheduler(cfg, FAMILY, policy=policy)
    results = list(graceful.serve(_requests(2)))
    assert all(r.attempts == 1 for r in results)
    assert any(r.status == "capacity" for r in results)
    assert graceful.last_stats["reroutes"] == 0


def test_reroute_policy_validation():
    with pytest.raises(ValueError):
        ReroutePolicy(max_attempts=0).validate()
    with pytest.raises(ValueError):
        ReroutePolicy(tol_relax=0.5).validate()


def test_slot_corruption_detected_and_rerouted():
    reqs = _requests(4)
    reqs[0] = dataclasses.replace(reqs[0], rel_tol=1e-7)
    graceful = GracefulScheduler(
        _cfg(), FAMILY, on_tick=corrupt_slot_hook(0, 1, req_id=0)
    )
    results = {r.req_id: r for r in graceful.serve(list(reqs))}
    assert results[0].retried_from == "nonfinite"
    assert results[0].backend == "vegas"
    assert np.isfinite(results[0].integral)


# --- deadlines ----------------------------------------------------------------


def test_max_evals_deadline_evicts_with_partial():
    reqs = _requests(4)
    reqs[0] = dataclasses.replace(reqs[0], rel_tol=1e-12, max_evals=2e4)
    sched = BatchScheduler(_cfg(capacity=1 << 11, max_iters=200), FAMILY)
    results = {r.req_id: r for r in sched.serve(list(reqs))}
    assert results[0].status == "deadline"
    assert results[0].n_evals > 2e4
    assert np.isfinite(results[0].integral)
    # the partial is a real estimate, not garbage
    exact = FAMILY.exact(2, reqs[0].theta)
    assert abs(results[0].integral - exact) <= 1e-3 * abs(exact)
    assert all(r.status == "converged" for i, r in results.items() if i != 0)
    assert sched.last_stats["deadlines"] == 1


def test_wall_clock_deadline_evicts():
    reqs = _requests(2)
    # deadline_s=0: expired at the first dispatch boundary, guaranteed
    reqs[0] = dataclasses.replace(reqs[0], rel_tol=1e-9, deadline_s=0.0)
    sched = BatchScheduler(_cfg(), FAMILY)
    results = {r.req_id: r for r in sched.serve(list(reqs))}
    assert results[0].status == "deadline"
    assert results[1].status == "converged"


# --- service checkpoint/resume ------------------------------------------------


def test_request_roundtrip_is_bit_exact():
    req = QuadRequest(
        req_id=7,
        theta=FAMILY.sample_theta(2, np.random.default_rng(3)),
        rel_tol=1e-7,
        deadline_s=2.5,
    )
    back = decode_request(encode_request(req), req.theta)
    assert back.req_id == req.req_id
    assert back.rel_tol == req.rel_tol and back.abs_tol is None
    assert back.deadline_s == 2.5 and back.max_evals is None
    import jax

    for a, b in zip(
        jax.tree_util.tree_leaves(req.theta), jax.tree_util.tree_leaves(back.theta)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_crash_resume_union_is_bit_identical(tmp_path):
    cfg = _cfg()
    reqs = _requests(8)
    reqs[0] = dataclasses.replace(reqs[0], rel_tol=1e-8)
    baseline = BatchScheduler(cfg, FAMILY)
    want = _vals(baseline.serve(list(reqs)))

    ckpt = ServiceCheckpointer(str(tmp_path))
    crashing = BatchScheduler(
        cfg, FAMILY, checkpointer=ckpt, checkpoint_every=1, on_tick=crash_at(3)
    )
    pre = []
    with pytest.raises(SimulatedCrash):
        for r in crashing.serve(list(reqs)):
            pre.append(r)
    assert ckpt.latest_step() is not None
    resumed = BatchScheduler(cfg, FAMILY, checkpointer=ckpt)
    post = list(resumed.serve(list(reqs), resume=True))
    got = {}
    for r in pre + post:
        t = _vals([r])[r.req_id]
        assert got.setdefault(r.req_id, t) == t  # replays are bit-identical
    assert got == want


def test_scheduler_checkpoint_arg_validation(tmp_path):
    with pytest.raises(ValueError, match="requires a checkpointer"):
        BatchScheduler(_cfg(), FAMILY, checkpoint_every=2)
    sched = BatchScheduler(_cfg(), FAMILY)
    with pytest.raises(ValueError, match="requires a checkpointer"):
        next(iter(sched.serve(_requests(1), resume=True)))
    ckpt = ServiceCheckpointer(str(tmp_path))
    sched = BatchScheduler(_cfg(), FAMILY, checkpointer=ckpt)
    with pytest.raises(FileNotFoundError):
        next(iter(sched.serve(_requests(1), resume=True)))


# --- device loss: single-device watchdog paths --------------------------------
# (evacuation/shrink/regrow need a real multi-device mesh and live in
# repro.service.chaos_selftest, driven by test_chaos.py)


def test_transient_device_fault_retry_is_bit_identical():
    """A fault that clears within the retry budget must leave the run fully
    bit-identical to a fault-free one — scheduling decisions included."""
    reqs = _requests(4)
    clean = BatchScheduler(_cfg(), FAMILY)
    want = _vals(clean.serve(list(reqs)))

    sched = BatchScheduler(
        _cfg(),
        FAMILY,
        fault_injector=DeviceDown(device=0, at_tick=1, transient_failures=2),
        max_dispatch_retries=3,
        retry_backoff_s=0.0,
    )
    assert _vals(sched.serve(list(reqs))) == want
    assert sched.last_stats["dispatch_retries"] == 2
    assert sched.last_stats["evacuations"] == 0
    assert sched.last_stats["mesh_shrinks"] == 0


def test_permanent_loss_on_single_device_is_fatal():
    """No surviving sub-mesh to evacuate onto: the loss must propagate."""
    sched = BatchScheduler(
        _cfg(),
        FAMILY,
        fault_injector=DeviceDown(device=0, at_tick=1),
        max_dispatch_retries=1,
        retry_backoff_s=0.0,
    )
    with pytest.raises(DeviceLostError):
        list(sched.serve(_requests(2)))
    assert sched.last_stats["dispatch_retries"] == 1


def test_hung_dispatch_converted_to_timeout_and_retried():
    """mode='hang' wedges the dispatch; the watchdog must convert it into a
    retryable timeout rather than hanging the serve loop forever."""
    reqs = _requests(2)
    clean = BatchScheduler(_cfg(), FAMILY)
    want = _vals(clean.serve(list(reqs)))

    # the timeout must sit above the cost of a *genuine* dispatch — which on
    # CPU includes multi-second window-rung recompiles — and below the hang
    sched = BatchScheduler(
        _cfg(),
        FAMILY,
        fault_injector=DeviceDown(
            device=0, at_tick=1, transient_failures=1, mode="hang"
        ),
        max_dispatch_retries=2,
        dispatch_timeout_s=10.0,
        retry_backoff_s=0.0,
    )
    assert _vals(sched.serve(list(reqs))) == want
    assert sched.last_stats["dispatch_retries"] == 1


def test_hung_dispatch_permanent_raises_device_lost():
    sched = BatchScheduler(
        _cfg(),
        FAMILY,
        fault_injector=DeviceDown(device=0, at_tick=1, mode="hang"),
        max_dispatch_retries=0,
        dispatch_timeout_s=10.0,
        retry_backoff_s=0.0,
    )
    # the hang is attributed to device 0 via the injector's healthy() probe;
    # a single-device fleet then has nowhere to evacuate
    with pytest.raises(DeviceLostError):
        list(sched.serve(_requests(2)))


def test_device_down_injector_validation():
    with pytest.raises(ValueError, match="mode"):
        DeviceDown(device=0, at_tick=1, mode="explode")


# --- corrupted-snapshot fallback ----------------------------------------------


def test_restore_falls_back_past_corrupt_meta(tmp_path):
    """A truncated meta sidecar (torn write on a dirty filesystem) must not
    brick resume: restore() skips it and loads the previous snapshot."""
    import json

    sched = BatchScheduler(_cfg(), FAMILY)
    eng = sched.engine
    state = eng.init()
    ckpt = ServiceCheckpointer(str(tmp_path))
    meta = {"ticks": 1, "stats": {}, "pulled_ids": [], "slots": []}
    ckpt.save(1, state, dict(meta, it=4))
    ckpt.save(2, state, dict(meta, it=8))

    p = tmp_path / "meta_00000002.json"
    raw = p.read_bytes()
    p.write_bytes(raw[: len(raw) // 2])

    _, got = ckpt.restore(eng)
    assert got["it"] == 4  # fell back to step 1
    with pytest.raises(json.JSONDecodeError):
        ckpt.restore(eng, step=2)  # explicit step: no silent fallback


def test_restore_rejects_meta_missing_required_keys(tmp_path):
    import json

    sched = BatchScheduler(_cfg(), FAMILY)
    eng = sched.engine
    state = eng.init()
    ckpt = ServiceCheckpointer(str(tmp_path))
    ckpt.save(1, state, {"it": 2, "ticks": 1, "stats": {}, "pulled_ids": [], "slots": []})
    # valid JSON, but a partial dict: must be treated as corrupt, not restored
    ckpt.save(2, state, {"it": 9})
    # save() validates nothing (the writer trusts the scheduler); break it
    # after the fact to model a torn-but-parseable sidecar
    p = tmp_path / "meta_00000002.json"
    assert json.loads(p.read_text())["it"] == 9  # parseable ...
    _, got = ckpt.restore(eng)
    assert got["it"] == 2  # ... but restore fell back past it
    with pytest.raises(KeyError):
        ckpt.restore(eng, step=2)


def test_restore_raises_when_every_snapshot_corrupt(tmp_path):
    sched = BatchScheduler(_cfg(), FAMILY)
    eng = sched.engine
    ckpt = ServiceCheckpointer(str(tmp_path))
    meta = {"it": 1, "ticks": 1, "stats": {}, "pulled_ids": [], "slots": []}
    ckpt.save(1, eng.init(), meta)
    p = tmp_path / "meta_00000001.json"
    p.write_bytes(p.read_bytes()[:10])
    with pytest.raises(FileNotFoundError, match="all corrupt"):
        ckpt.restore(eng)


# --- CheckpointManager async-error regression ---------------------------------


def test_async_write_error_resurfaces_on_wait(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = {"x": np.arange(4.0)}
    mgr.save(1, tree, blocking=True)
    # re-saving an existing step fails in the background thread; before the
    # fix the FileExistsError died with the thread and the caller never knew
    mgr.save(1, tree)
    with pytest.raises(FileExistsError):
        mgr.wait()
    # the error is surfaced once, then the manager is usable again
    mgr.wait()
    mgr.save(2, tree)
    mgr.wait()
    assert mgr.latest_step() == 2


def test_async_write_error_resurfaces_on_next_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = {"x": np.arange(4.0)}
    mgr.save(1, tree, blocking=True)
    mgr.save(1, tree)
    with pytest.raises(FileExistsError):
        mgr.save(3, tree)  # save() waits on the pending thread first
    mgr.save(3, tree)
    mgr.wait()
    assert mgr.latest_step() == 3


# --- injector hygiene ---------------------------------------------------------


def test_poison_theta_only_touches_first_leaf():
    theta = FAMILY.sample_theta(2, np.random.default_rng(0))
    bad = poison_theta(theta)
    import jax

    leaves = jax.tree_util.tree_leaves(theta)
    bad_leaves = jax.tree_util.tree_leaves(bad)
    assert np.all(np.asarray(bad_leaves[0]) == NAN_SENTINEL)
    for a, b in zip(leaves[1:], bad_leaves[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_watchdog_abandoned_attempt_never_takes_the_state():
    """A hung attempt that wakes after the watchdog gave up must not consume
    (donate) the fleet state its retry is using."""
    import threading

    from repro.service.scheduler import DispatchTimeout, _call_with_timeout

    wake, done = threading.Event(), threading.Event()
    took = []

    def hung(attempt):
        wake.wait(10)
        took.append(attempt.take())
        done.set()

    with pytest.raises(DispatchTimeout) as exc:
        _call_with_timeout(hung, 0.05)
    assert exc.value.retryable
    wake.set()
    assert done.wait(10)
    assert took == [False]


def test_watchdog_timeout_after_the_state_was_taken_is_not_retried():
    import threading

    from repro.service.scheduler import DispatchTimeout, _call_with_timeout

    release = threading.Event()

    def wedged(attempt):
        assert attempt.take()
        release.wait(10)

    try:
        with pytest.raises(DispatchTimeout, match="cannot be retried") as exc:
            _call_with_timeout(wedged, 0.05)
        assert not exc.value.retryable
    finally:
        release.set()
