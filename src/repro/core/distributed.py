"""Multi-device adaptive quadrature (paper Fig. 1b) via shard_map.

Each device owns a fixed-capacity region store and runs the single-device
iteration locally; three collectives per iteration implement the paper's
distributed extension:

  1. *metadata exchange* — `psum` of (integral, error, active count) right
     after evaluation: the paper's compact per-iteration summary and its only
     global synchronisation point.  Convergence is decided on these values —
     on device, so ``sync_every`` iterations can be fused into one dispatch
     and the host only reads back (stacked) metrics at that cadence.
  2. *classification with global context* — the equal-share classifier uses
     the GLOBAL active count, so all devices finalise against the same
     threshold (a single-device run and a P-device run of the same problem
     therefore walk the same refinement tree, modulo redistribution).
  3. *redistribution* — `repro.core.redistribution.redistribute`: cyclic
     donor/receiver pairing, capped coordinate-only payloads, overlapping
     with compute courtesy of XLA's latency-hiding scheduler.

The initial domain decomposition over-partitions: ``init_regions_per_device``
(paper default 8) boxes per rank, assigned round-robin so neighbouring boxes
land on different ranks.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import region_store
from repro.core.adaptive import (
    AdaptiveResult,
    advance_ladder,
    advance_target,
    eval_ladder,
    make_eval_step,
)
from repro.core.classify import classify, error_budget
from repro.core.config import QuadratureConfig
from repro.core.programs import RungPrograms
from repro.core.redistribution import balance_stats, make_schedule, redistribute
from repro.core.region_store import RegionState
from repro.core.rules import make_rule
from repro.core.split import classify_split_compact

AXIS = "dev"


@dataclasses.dataclass
class DistributedResult(AdaptiveResult):
    n_devices: int = 1
    # per-iteration history rows:
    #   (iter, integral, error, n_active, work_imbalance, max_rows)
    history: list = dataclasses.field(default_factory=list)
    # final per-device evaluation counts (work distribution; Fig. 4b input)
    evals_per_device: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0)
    )

    def mean_imbalance(self) -> float:
        if not self.history:
            return 0.0
        return float(np.mean([h[4] for h in self.history]))


def _initial_global_partition(cfg: QuadratureConfig, n_devices: int):
    """Over-decomposed initial partition, strided across ranks."""
    lo = np.asarray(cfg.lo(), np.float64)
    hi = np.asarray(cfg.hi(), np.float64)
    want = n_devices * cfg.init_regions_per_device
    # keep the "every axis split at least once" guarantee of the
    # single-device driver (see QuadratureConfig.n_init)
    want = max(want, min(2**cfg.d, n_devices * cfg.capacity // 4))
    n_init = 1 << (want - 1).bit_length()  # next power of two
    n_init = min(n_init, n_devices * (cfg.capacity // 4))
    centers, halfw = region_store.uniform_partition(lo, hi, n_init)
    return centers, halfw, n_init


def _stacked_initial_state(cfg: QuadratureConfig, n_devices: int, dtype):
    centers, halfw, n_init = _initial_global_partition(cfg, n_devices)
    C, d = cfg.capacity, cfg.d
    per_dev = -(-n_init // n_devices)
    if per_dev > C // 2:
        raise ValueError("initial partition exceeds half the per-device store")

    stacked = {
        "centers": np.zeros((n_devices, C, d)),
        "halfw": np.zeros((n_devices, C, d)),
        "active": np.zeros((n_devices, C), bool),
        "fresh": np.zeros((n_devices, C), bool),
    }
    counts = np.zeros(n_devices, np.int64)
    for r in range(n_init):
        dev = r % n_devices  # strided assignment (paper: several regions/rank)
        slot = counts[dev]
        stacked["centers"][dev, slot] = centers[r]
        stacked["halfw"][dev, slot] = halfw[r]
        stacked["active"][dev, slot] = True
        stacked["fresh"][dev, slot] = True
        counts[dev] += 1

    z = jnp.zeros
    return RegionState(
        centers=jnp.asarray(stacked["centers"], dtype),
        halfw=jnp.asarray(stacked["halfw"], dtype),
        est=z((n_devices, C), dtype),
        err=z((n_devices, C), dtype),
        axis=z((n_devices, C), jnp.int32),
        active=jnp.asarray(stacked["active"]),
        fresh=jnp.asarray(stacked["fresh"]),
        fin_integral=z((n_devices,), dtype),
        fin_error=z((n_devices,), dtype),
        n_evals=z((n_devices,), dtype),
        it=z((n_devices,), jnp.int32),
        overflowed=z((n_devices,), bool),
    )


def make_classify_split(
    cfg: QuadratureConfig,
    total_volume: float,
    domain_width: np.ndarray,
    window: Optional[int],
):
    """Classify + split/compact at one advance window, for the fused step.

    Unlike :func:`repro.core.adaptive.make_advance_step` this takes the
    *psum'd* integral and global active count (every device classifies
    against the same equal-share threshold) and does NOT bump ``it`` — the
    redistribution schedule indexes on the pre-bump counter.
    """
    width = jnp.asarray(domain_width)
    sl = slice(None) if window is None else slice(0, window)

    def apply(state: RegionState, integral, n_global) -> RegionState:
        fin = classify(
            cfg,
            state.est[sl],
            state.err[sl],
            state.halfw[sl],
            state.active[sl],
            integral,
            total_volume,
            width,
            n_active=n_global,
        )
        return classify_split_compact(state, fin, window=window)

    return apply


def make_dist_step(
    cfg: QuadratureConfig,
    rule,
    n_devices: int,
    total_volume: float,
    domain_width: np.ndarray,
    schedule,
    window: int,
):
    """K-fused per-device step (K = ``cfg.sync_every``) at one window rung.

    ``dist_step`` runs up to K full iterations inside one dispatch.  The
    convergence check runs on device against the psum'd metadata (which is
    identical on every rank, so all ranks take the same branch) and iterations
    after convergence become pass-throughs; the host only syncs once per
    dispatch, reading back the stacked per-iteration metrics plus an
    ``executed`` mask — the paper's "overlap communication with computation"
    applied to the host<->device channel.

    The step is compiled for ONE eval window (an eval-ladder rung) and its
    advance window, the way the single-device host driver compiles one
    program per rung: an iteration runs only while the widest device's live
    count fits ``window`` (a pmax, so every rank agrees), and otherwise the
    dispatch ends early and the host re-dispatches at the rung that count
    needs.  Any covering window is bit-exact, so the trajectory is the one a
    full-capacity step walks, and only the rungs a run reaches are compiled.
    """
    C = cfg.capacity
    adv = region_store.select_window(advance_ladder(cfg), advance_target(window, C))
    # None = the whole store
    eval_w, adv_w = (None if window == C else window), (None if adv == C else adv)
    eval_step = make_eval_step(cfg, rule, window=eval_w)
    classify_split = make_classify_split(cfg, total_volume, domain_width, adv_w)
    limit = 3 * cfg.capacity // 4
    dtype = jnp.dtype(cfg.dtype)

    def widest(state: RegionState):
        return jax.lax.pmax(jnp.sum(state.active).astype(jnp.int32), AXIS)

    def dist_core(state: RegionState):
        # counts ride the collectives as int32: the TPU lowers only sums of
        # 64-bit integers across devices, not their max
        work_loc = jnp.sum(state.active & state.fresh).astype(jnp.int32)
        state = eval_step(state)

        # --- metadata exchange (the only global sync point) ----------------
        i_loc, e_loc = state.global_estimates(window=eval_w)
        integral = jax.lax.psum(i_loc, AXIS)
        error = jax.lax.psum(e_loc, AXIS)
        n_loc = jnp.sum(state.active).astype(jnp.int32)
        n_global = jax.lax.psum(n_loc, AXIS)
        work_max = jax.lax.pmax(work_loc, AXIS)
        work_sum = jax.lax.psum(work_loc, AXIS)
        work_imb = jnp.where(
            work_max > 0,
            1.0 - (work_sum / n_devices) / jnp.maximum(work_max, 1),
            0.0,
        )
        max_rows, _, _ = balance_stats(n_loc, AXIS, n_devices)

        # --- classify + split (global equal-share threshold) ---------------
        state = classify_split(state, integral, n_global)

        # --- decentralised redistribution ----------------------------------
        if cfg.redistribution != "off":
            state = redistribute(
                state,
                axis_name=AXIS,
                n_devices=n_devices,
                schedule=schedule,
                cap=cfg.message_cap,
                limit=limit,
            )
        state = dataclasses.replace(state, it=state.it + 1)

        metrics = {
            "integral": integral.astype(dtype),
            "error": error.astype(dtype),
            "n_active": n_global.astype(jnp.int32),
            "work_imb": work_imb.astype(dtype),
            "max_rows": max_rows.astype(jnp.int32),
        }
        return state, metrics

    def _zero_metrics():
        return {
            "integral": jnp.zeros((), dtype),
            "error": jnp.zeros((), dtype),
            "n_active": jnp.zeros((), jnp.int32),
            "work_imb": jnp.zeros((), dtype),
            "max_rows": jnp.zeros((), jnp.int32),
        }

    def dist_step(state: RegionState):
        # squeeze the leading per-device axis added by shard_map
        state = jax.tree.map(lambda x: x[0], state)

        def one(carry, _):
            state, done = carry
            # a count past the compiled window ends the dispatch unexecuted
            done = done | (widest(state) > window)
            executed = ~done

            def run(s):
                s2, m = dist_core(s)
                # device-side convergence: the same decision the host made
                # per-iteration, on the same psum'd (replicated) metadata
                stop = (
                    (m["error"] <= error_budget(cfg, m["integral"]))
                    | (m["n_active"] == 0)
                    | (s2.it >= cfg.max_iters)
                )
                return s2, stop, m

            def skip(s):
                return s, jnp.asarray(True), _zero_metrics()

            state, done, m = jax.lax.cond(done, skip, run, state)
            return (state, done), (m, executed)

        (state, _), (ms, executed) = jax.lax.scan(
            one, (state, jnp.asarray(False)), None, length=cfg.sync_every
        )
        n_widest = widest(state)
        state = jax.tree.map(lambda x: x[None], state)
        return state, ms, executed, n_widest

    return dist_step


def dist_program(
    cfg: QuadratureConfig,
    rule,
    mesh: Mesh,
    total_volume: float,
    domain_width: np.ndarray,
    window: int,
):
    """The jitted, donating SPMD program of :func:`make_dist_step` over
    ``mesh`` at one eval rung: the program ``integrate_distributed``
    dispatches while every device's live count fits ``window``."""
    n_devices = mesh.shape[AXIS]
    dist_step = make_dist_step(
        cfg,
        rule,
        n_devices,
        total_volume,
        domain_width,
        make_schedule(n_devices),
        window,
    )
    # check_vma=False: loop carries built inside the body start
    # device-invariant and become device-varying after the first iteration,
    # which the static replication checker cannot express
    return jax.jit(
        jax.shard_map(
            dist_step,
            mesh=mesh,
            in_specs=P(AXIS),
            out_specs=(P(AXIS), P(), P(), P()),
            check_vma=False,
        ),
        donate_argnums=0,
    )


def integrate_distributed(
    cfg: QuadratureConfig,
    integrand: Optional[Callable] = None,
    mesh: Optional[Mesh] = None,
    devices=None,
    recorder=None,
) -> DistributedResult:
    """Host-driven multi-device integration over ``devices`` (default: every
    visible device; ``mesh`` overrides both).

    ``recorder`` (a :class:`repro.telemetry.Recorder`) gets a
    ``dist.dispatch`` span per fused launch and, per executed iteration, a
    ``dist.work_imb`` gauge (the paper's Fig. 4b idle-time proxy, the same
    value appended to ``history``) plus a ``dist.iter`` instant — recorded
    from the read-back metrics only, after the dispatch returns.
    """
    cfg = cfg.validate()
    if mesh is None:
        devices = devices if devices is not None else jax.devices()
        mesh = jax.make_mesh((len(devices),), (AXIS,), devices=devices)
    n_devices = mesh.shape[AXIS]

    lo = np.asarray(cfg.lo(), np.float64)
    hi = np.asarray(cfg.hi(), np.float64)
    total_volume = float(np.prod(hi - lo))
    dtype = jnp.dtype(cfg.dtype)
    rule = make_rule(cfg, integrand, platform=mesh.devices.flat[0].platform)

    state = _stacked_initial_state(cfg, n_devices, dtype)
    n_widest = int(jnp.max(jnp.sum(state.active, axis=1)))
    shard = NamedSharding(mesh, P(AXIS))
    state = jax.device_put(state, shard)

    ladder = eval_ladder(cfg)
    steps = RungPrograms(
        ladder,
        lambda w: dist_program(cfg, rule, mesh, total_volume, hi - lo, w),
    )

    from repro.telemetry import NULL

    recorder = NULL if recorder is None else recorder
    history = []
    converged = False
    integral = error = 0.0
    n_active = 0
    it = 0
    while it < cfg.max_iters:
        with recorder.span("dist.dispatch", it=it) as sp:
            w = region_store.select_window(ladder, n_widest)
            state, ms, executed, n_widest = steps(w, state)(state)
            executed = np.asarray(executed)
            n_widest = int(n_widest)
            ms = jax.device_get(ms)
            sp["executed"] = int(np.sum(executed))
        for t in range(len(executed)):
            if not executed[t]:
                break
            integral = float(ms["integral"][t])
            error = float(ms["error"][t])
            n_active = int(ms["n_active"][t])
            work_imb = float(ms["work_imb"][t])
            if recorder.enabled:
                recorder.gauge("dist.work_imb", work_imb, it=it)
                recorder.event(
                    "dist.iter",
                    it=it,
                    integral=integral,
                    error=error,
                    n_active=n_active,
                    max_rows=int(ms["max_rows"][t]),
                )
            history.append(
                (
                    it,
                    integral,
                    error,
                    n_active,
                    work_imb,
                    int(ms["max_rows"][t]),
                )
            )
            it += 1
        budget = max(cfg.abs_tol, abs(integral) * cfg.rel_tol)
        if error <= budget:
            converged = True
            break
        if n_active == 0:
            break
    steps.close()

    overflowed = bool(np.any(np.asarray(state.overflowed)))
    if converged:
        status = "converged"
    elif overflowed:
        status = "capacity"
    elif n_active == 0:
        status = "no_active"
    else:
        status = "max_iters"

    return DistributedResult(
        integral=integral,
        error=error,
        status=status,
        iterations=it,
        n_evals=float(np.sum(np.asarray(state.n_evals))),
        n_active=n_active,
        overflowed=overflowed,
        n_devices=n_devices,
        history=history,
        evals_per_device=np.asarray(state.n_evals),
    )
