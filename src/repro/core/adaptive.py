"""Single-device batch-adaptive quadrature driver (paper Fig. 1a).

Unlike heap-driven h-adaptivity, *every* region whose error contribution is
non-negligible is refined each iteration (PAGANI-style batch adaptivity).
Two drivers are provided:

- :func:`integrate` — host-driven loop around a jitted step, one scalar sync
  per iteration (mirrors the paper's workflow, and is what the distributed
  driver extends);
- :func:`integrate_device` — fully device-resident ``lax.while_loop`` with no
  host synchronisation at all (TPU-native improvement; the convergence check
  runs on device, which is what the paper's global sync point becomes when
  the whole solver is one XLA program).

Both drivers evaluate the rule over an *active window* — the leading slice of
the compacted store sized from a geometric ladder (see
``region_store.window_ladder``) — so per-iteration cost scales with the live
region population rather than store capacity.  The host driver picks the
window from the active count it already syncs (one cached jit per rung); the
device driver selects the statically-shaped branch with ``lax.switch``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import region_store
from repro.core.classify import classify, error_budget, nonfinite_mask
from repro.core.config import QuadratureConfig
from repro.core.integrands import get as get_integrand
from repro.core.programs import RungPrograms
from repro.core.region_store import RegionState
from repro.core.rules import make_rule
from repro.core.split import classify_split_compact
from repro.telemetry import NULL, jax_events


@dataclasses.dataclass
class AdaptiveResult:
    integral: float
    error: float  # global error estimate (the paper's epsilon)
    status: str  # converged | max_iters | no_active | capacity
    iterations: int
    n_evals: float
    n_active: int
    overflowed: bool

    def summary(self) -> str:
        return (
            f"I={self.integral:.15e} eps={self.error:.3e} [{self.status}] "
            f"iters={self.iterations} evals={self.n_evals:.3g}"
        )


def make_eval_step(
    cfg: QuadratureConfig, rule, window: Optional[int] = None
) -> Callable[[RegionState], RegionState]:
    """Evaluate fresh regions, update per-region estimates + eval counter.

    ``window`` restricts the rule evaluation to the leading ``window`` rows
    of the store.  By the active-window invariant (region_store docstring)
    every active — hence every fresh — region lives in ``[0, n_active)``, so
    any ``window >= n_active`` produces bit-identical results to the legacy
    full-capacity evaluation while doing ``window / capacity`` of the work.
    ``None`` evaluates the full store.  Its operations carry the named
    scope ``quad.eval`` in every program that fuses it.
    """

    def eval_step(state: RegionState) -> RegionState:
        with jax.named_scope("quad.eval"):
            w = state.capacity if window is None else min(window, state.capacity)
            need = state.active[:w] & state.fresh[:w]
            est, err, axis = rule.eval_batch(state.centers[:w], state.halfw[:w])
            return dataclasses.replace(
                state,
                est=state.est.at[:w].set(jnp.where(need, est, state.est[:w])),
                err=state.err.at[:w].set(jnp.where(need, err, state.err[:w])),
                axis=state.axis.at[:w].set(jnp.where(need, axis, state.axis[:w])),
                fresh=jnp.zeros_like(state.fresh),
                n_evals=state.n_evals
                + jnp.sum(need).astype(state.n_evals.dtype)
                * rule.n_evals_per_region,
            )

    return eval_step


def make_switched_eval_step(
    cfg: QuadratureConfig, rule
) -> Callable[[RegionState], RegionState]:
    """Device-resident windowed evaluation: ``lax.switch`` over the ladder.

    For drivers that never sync the active count to the host
    (:func:`integrate_device`, the distributed per-device step) the window is
    chosen on device: the active count indexes the smallest ladder rung that
    covers the population and dispatches the matching statically-shaped
    branch.
    """
    if not cfg.eval_window:
        return make_eval_step(cfg, rule)
    ladder = eval_ladder(cfg)
    branches = [make_eval_step(cfg, rule, window=w) for w in ladder]
    rungs = jnp.asarray(ladder, jnp.int32)

    def eval_step(state: RegionState) -> RegionState:
        n = jnp.sum(state.active).astype(jnp.int32)
        ix = region_store.rung_index(rungs, n)
        return jax.lax.switch(ix, branches, state)

    return eval_step


def eval_ladder(cfg: QuadratureConfig) -> tuple[int, ...]:
    """The eval-window ladder, or the single full-capacity rung when the
    active-window path is disabled — shared by every driver so they can
    never disagree on the available window shapes."""
    if not cfg.eval_window:
        return (cfg.capacity,)
    return region_store.window_ladder(cfg.capacity, cfg.eval_window_min)


def advance_ladder(cfg: QuadratureConfig) -> tuple[int, ...]:
    """The advance-window ladder, gated by ``cfg.advance_window``.

    Advance rungs are picked to cover ``min(2 * n_active, capacity)`` — see
    :func:`advance_target` — because splitting can double the live population
    and capacity pressure needs the full-capacity rung for its
    forced-finalise semantics.

    The ladder is the *coarse* (x4-geometric) sub-ladder of the eval ladder,
    top rung always exactly ``capacity``: any rung covering the target is
    bit-identical, and the advance at a rung costs far less than the eval at
    the same rung, so fine granularity buys almost no runtime — while every
    extra rung is one more compiled program (or ``lax.switch`` branch in
    the device-resident loop), where compile time is a real cost for
    short-lived engines.
    """
    if not cfg.advance_window:
        return (cfg.capacity,)
    full = region_store.window_ladder(cfg.capacity, cfg.eval_window_min)
    return tuple(sorted(full[::-2]))  # top-down every other rung, keeps C


def advance_target(n_active, capacity: int):
    """Row count the advance window must cover for an ``n_active`` population.

    Post-split the population is ``n_act + k`` with
    ``k = min(n_act, C - n_act)``, i.e. at most ``min(2 * n_active, C)``; and
    whenever the capacity-pressure path (forced finalise at ``3C//4``, split
    budget truncation) can bite, ``2 * n_active > C`` already escalates to the
    full-capacity rung.  Works on ints (host drivers) and traced values
    (device drivers) alike.
    """
    return jnp.minimum(2 * n_active, capacity) if isinstance(
        n_active, jnp.ndarray
    ) else min(2 * int(n_active), capacity)


def make_advance_step(
    cfg: QuadratureConfig,
    total_volume: float,
    domain_width: np.ndarray,
    window: Optional[int] = None,
) -> Callable[..., RegionState]:
    """Classify (finalise negligible) + split survivors + compact.

    ``budget`` / ``rel_tol`` override the config-derived error budget and
    relative tolerance (the batch service passes per-request tolerances as
    traced values); ``None`` derives them from ``cfg`` as the serial
    drivers do.

    ``window`` runs the whole advance — the global-estimate reduction, the
    classify thresholding, and the sort-based split/compact — on the leading
    ``window`` rows only.  Exact (bit-identical to the full advance) whenever
    ``window >= advance_target(n_active, capacity)``; the drivers guarantee
    this by picking the rung from :func:`advance_ladder` for the active count
    they already track.  Its operations carry the named scope
    ``quad.advance``.
    """
    width = jnp.asarray(domain_width)
    w = None if window is None else min(int(window), cfg.capacity)

    def advance(state: RegionState, budget=None, rel_tol=None) -> RegionState:
        with jax.named_scope("quad.advance"):
            sl = slice(None) if w is None else slice(0, w)
            integral, _ = state.global_estimates(window=w)
            fin = classify(
                cfg,
                state.est[sl],
                state.err[sl],
                state.halfw[sl],
                state.active[sl],
                integral,
                total_volume,
                width,
                budget=budget,
                rel_tol=rel_tol,
            )
            state = classify_split_compact(state, fin, window=w)
            return dataclasses.replace(state, it=state.it + 1)

    return advance


def make_switched_advance_step(
    cfg: QuadratureConfig, total_volume: float, domain_width: np.ndarray
) -> Callable[..., RegionState]:
    """Device-resident windowed advance: ``lax.switch`` over the ladder.

    The rung is chosen on device from the live count to cover
    ``advance_target(n_active)`` — the mirror of the host drivers' cached
    per-rung jits, for loops that never sync the count
    (:func:`integrate_device`, the batch engine's fused run).
    """
    ladder = advance_ladder(cfg)
    if len(ladder) == 1:
        return make_advance_step(cfg, total_volume, domain_width)
    branches = [
        make_advance_step(cfg, total_volume, domain_width, window=w)
        for w in ladder
    ]
    rungs = jnp.asarray(ladder, jnp.int32)

    def advance(state: RegionState, budget=None, rel_tol=None) -> RegionState:
        n = jnp.sum(state.active).astype(jnp.int32)
        ix = region_store.rung_index(rungs, advance_target(n, cfg.capacity))
        return jax.lax.switch(ix, branches, state, budget, rel_tol)

    return advance


def make_switched_estimates(cfg: QuadratureConfig) -> Callable[[RegionState], tuple]:
    """Windowed ``global_estimates`` for device-resident loops.

    Any rung covering ``n_active`` is exact (the masked tail contributes
    exact zeros), so the estimate reductions use the plain count — not the
    doubled advance target.  Falls back to the full reduction when advance
    windowing is off.
    """
    ladder = advance_ladder(cfg)
    if len(ladder) == 1:
        return lambda state: state.global_estimates()
    branches = [
        (lambda state, _w=w: state.global_estimates(window=_w)) for w in ladder
    ]
    rungs = jnp.asarray(ladder, jnp.int32)

    def estimates(state: RegionState):
        n = jnp.sum(state.active).astype(jnp.int32)
        return jax.lax.switch(region_store.rung_index(rungs, n), branches, state)

    return estimates


def quarantine_step(state: RegionState):
    """Zero + deactivate non-finite regions, recompute global estimates.

    The cold recovery path for the host drivers: jitted on first use, runs
    at most once per problem (the problem is terminal with status
    ``nonfinite`` immediately after).  The compaction invariant may be
    broken by the mid-store deactivations, which is safe exactly because
    nothing windowed runs afterwards — the full-store reduction here is the
    problem's last device op.
    """
    bad = nonfinite_mask(state.est, state.err, state.active)
    state = dataclasses.replace(
        state,
        est=jnp.where(bad, 0.0, state.est),
        err=jnp.where(bad, 0.0, state.err),
        active=state.active & ~bad,
    )
    integral, error = state.global_estimates()
    return state, integral, error, jnp.sum(state.active)


def _setup(cfg: QuadratureConfig, integrand):
    cfg = cfg.validate()
    lo = np.asarray(cfg.lo(), np.float64)
    hi = np.asarray(cfg.hi(), np.float64)
    total_volume = float(np.prod(hi - lo))
    dtype = jnp.dtype(cfg.dtype)
    rule = make_rule(cfg, integrand)
    state = region_store.init_state(
        cfg.capacity, lo, hi, cfg.resolved_n_init(), dtype
    )
    return cfg, lo, hi, total_volume, rule, state


def result_status(
    converged: bool,
    n_active: int,
    it: int,
    cfg,
    overflowed: bool,
    nonfinite: bool = False,
) -> str:
    """Terminal-status taxonomy shared by the serial drivers and the batch
    service (which promises 'statuses as in AdaptiveResult').

    ``nonfinite`` wins over everything: a quarantined problem's remaining
    finite regions may happen to satisfy the budget, but the quarantined
    volume is unaccounted for, so reporting ``converged`` would overstate
    what the estimate covers.
    """
    if nonfinite:
        return "nonfinite"
    if converged:
        return "converged"
    if overflowed:
        return "capacity"
    if n_active == 0:
        return "no_active"
    if it >= cfg.max_iters:
        return "max_iters"
    return "running"


def integrate(
    cfg: QuadratureConfig,
    integrand: Optional[Callable] = None,
    callback: Optional[Callable[[int, float, float, int], None]] = None,
    recorder=NULL,
) -> AdaptiveResult:
    """Host-driven adaptive integration (one scalar sync per iteration).

    ``recorder`` (a :class:`repro.telemetry.Recorder`) gets per-iteration
    ``core.eval``/``core.advance`` spans and a ``core.iter`` instant with
    the synced estimates — all recorded host-side between dispatches, so
    telemetry cannot change the refinement trajectory.  Inside those spans
    each host activity has a span of its own: ``core.program`` (getting a
    rung's jitted program, waiting on its ahead compile), ``core.dispatch``
    (calling it: a fresh program traces, lowers and loads here) and
    ``core.sync`` (the blocking read-back); ``core.setup`` and
    ``core.finish`` bracket the loop.  JAX's build events are counted
    throughout (:func:`repro.telemetry.jax_events`).
    """
    with jax_events(recorder):
        return _integrate(cfg, integrand, callback, recorder)


def _integrate(cfg, integrand, callback, recorder) -> AdaptiveResult:
    with recorder.span("core.setup"):
        cfg, lo, hi, total_volume, rule, state = _setup(cfg, integrand)

    ladder = eval_ladder(cfg)
    adv_ladder = advance_ladder(cfg)
    C = cfg.capacity
    # One jitted variant per ladder rung, compiled on first use (the next
    # rungs up compiled ahead, see repro.core.programs).  The host loop
    # already syncs the active count each iteration, so the next window is
    # known before dispatch and the switch costs nothing on device.  The
    # advance (and the metric reductions) get the same treatment as the
    # eval: a per-rung program keyed by the windows the counts demand.  Both
    # donate the state: the (C, d) SoA arrays are the dominant allocation,
    # and donation lets XLA update the population in place.
    def build_eval(w: int) -> Callable:
        return jax.jit(make_eval_step(cfg, rule, window=w), donate_argnums=0)

    def build_metrics(w: int) -> Callable:
        # any rung covering n_active reduces the same active mass bit-exactly
        ww = None if w == C else w

        def metrics(state):
            integral, error = state.global_estimates(window=ww)
            act = state.active if ww is None else state.active[:ww]
            return integral, error, jnp.sum(act)

        return jax.jit(metrics)

    def build_advance(w: int) -> Callable:
        ww = None if w == C else w
        core = make_advance_step(cfg, total_volume, hi - lo, window=ww)

        def advance_and_count(state):
            state = core(state)
            # post-split the population fits the advance window
            act = state.active if ww is None else state.active[:ww]
            return state, jnp.sum(act)

        return jax.jit(advance_and_count, donate_argnums=0)

    evals = RungPrograms(ladder, build_eval, recorder)
    metrics = RungPrograms(adv_ladder, build_metrics, recorder)
    advances = RungPrograms(adv_ladder, build_advance, recorder)

    def eval_step_for(n_active: int, state) -> Callable:
        return evals(region_store.select_window(ladder, n_active), state)

    def metrics_for(n_active: int, state) -> Callable:
        return metrics(region_store.select_window(adv_ladder, n_active), state)

    def advance_for(n_active: int, state) -> Callable:
        w = region_store.select_window(adv_ladder, advance_target(n_active, C))
        return advances(w, state)

    converged = False
    nonfinite = False
    integral = error = 0.0
    n_active = n_next = cfg.resolved_n_init()
    for _ in range(cfg.max_iters):
        with recorder.span("core.eval", window=int(n_next)):
            with recorder.span("core.program"):
                eval_fn = eval_step_for(n_next, state)
            with recorder.span("core.dispatch"):
                state = eval_fn(state)
            with recorder.span("core.program"):
                metrics_fn = metrics_for(n_next, state)
            with recorder.span("core.dispatch"):
                out = metrics_fn(state)
            with recorder.span("core.sync"):
                integral, error, n_active = (float(x) for x in out)
        if recorder.enabled:
            recorder.event(
                "core.iter",
                it=int(state.it),
                integral=integral,
                error=error,
                n_active=int(n_active),
            )
        if callback is not None:
            callback(int(state.it), integral, error, int(n_active))
        if not (np.isfinite(integral) and np.isfinite(error)):
            # an integrand NaN/Inf reached the global reductions: quarantine
            # the offending regions and stop with the best-effort estimate
            # of the surviving population (terminal status "nonfinite")
            state, gi, ge, na = jax.jit(quarantine_step)(state)
            integral, error, n_active = float(gi), float(ge), int(na)
            nonfinite = True
            break
        budget = max(cfg.abs_tol, abs(integral) * cfg.rel_tol)
        if error <= budget:
            converged = True
            break
        if n_active == 0:
            break
        with recorder.span("core.advance", n_active=int(n_active)):
            with recorder.span("core.program"):
                advance_fn = advance_for(int(n_active), state)
            with recorder.span("core.dispatch"):
                state, n_dev = advance_fn(state)
            with recorder.span("core.sync"):
                n_next = int(n_dev)

    with recorder.span("core.finish"):
        for programs in (evals, metrics, advances):
            programs.close()
        return AdaptiveResult(
            integral=integral,
            error=error,
            status=result_status(
                converged,
                int(n_active),
                int(state.it),
                cfg,
                bool(state.overflowed),
                nonfinite,
            ),
            iterations=int(state.it),
            n_evals=float(state.n_evals),
            n_active=int(n_active),
            overflowed=bool(state.overflowed),
        )


def integrate_device(
    cfg: QuadratureConfig, integrand: Optional[Callable] = None, recorder=NULL
) -> AdaptiveResult:
    """Fully device-resident driver: lax.while_loop, zero host syncs.

    The host cannot observe per-iteration state here, so telemetry is one
    ``core.device_loop`` span around the whole resident loop — by design
    (DESIGN.md §8: nothing is ever recorded inside traced code).
    """
    cfg, lo, hi, total_volume, rule, state = _setup(cfg, integrand)
    eval_step = make_switched_eval_step(cfg, rule)
    advance = make_switched_advance_step(cfg, total_volume, hi - lo)
    estimates = make_switched_estimates(cfg)

    def cond(state: RegionState):
        integral, error = estimates(state)
        pending = jnp.any(state.active & state.fresh)
        converged = (error <= error_budget(cfg, integral)) & ~pending
        return (~converged) & (state.it < cfg.max_iters) & jnp.any(state.active)

    def body(state: RegionState):
        state = eval_step(state)
        integral, error = estimates(state)
        done = error <= error_budget(cfg, integral)
        # Only refine when not converged (cond re-checks next trip).
        return jax.lax.cond(done, lambda s: s, advance, state)

    with recorder.span("core.device_loop", max_iters=cfg.max_iters):
        final = jax.lax.while_loop(cond, body, state)
        integral, error = (float(x) for x in final.global_estimates())
        n_active = int(final.n_active())
    # the device-resident loop has no recovery path (NaN fails the on-device
    # convergence check until another bound fires); report honestly
    nonfinite = not (np.isfinite(integral) and np.isfinite(error))
    budget = max(cfg.abs_tol, abs(integral) * cfg.rel_tol)
    converged = error <= budget
    return AdaptiveResult(
        integral=integral,
        error=error,
        status=result_status(
            converged,
            n_active,
            int(final.it),
            cfg,
            bool(final.overflowed),
            nonfinite,
        ),
        iterations=int(final.it),
        n_evals=float(final.n_evals),
        n_active=n_active,
        overflowed=bool(final.overflowed),
    )


def integrate_exact_check(cfg: QuadratureConfig) -> tuple[AdaptiveResult, float]:
    """Convenience: integrate a registry integrand and return true rel-error."""
    spec = get_integrand(cfg.integrand)
    res = integrate(cfg)
    exact = spec.exact(cfg.d)
    rel = abs(res.integral - exact) / max(abs(exact), 1e-300)
    return res, rel
