"""Batched adaptive quadrature engine: one compiled step for B problems,
sharded across the device mesh.

The single-problem drivers in :mod:`repro.core.adaptive` solve one integral
per invocation.  Fleets of *related* integrals ``∫ f(x; theta_k) dx`` over a
shared domain (parameter sweeps, Bayesian evidence grids, PDF convolutions)
instead run here: the SoA :class:`~repro.core.region_store.RegionState` gains
a leading problem axis and the whole adaptive step — windowed rule
evaluation, classification, split/compact — is ``vmap``-ped across it, so
the fleet shares one XLA program and the hardware sees one big batch of
regions instead of B small ones.

Heterogeneous convergence across the fleet is the same load-imbalance
problem the paper solves across devices, and here both axes compose:

- *across batch slots* — continuous batching (the idiom of the LLM serving
  engine in ``repro.serving``): per-slot ``done`` masks turn converged
  problems into pass-throughs and the scheduler splices a fresh initial
  partition into a freed slot mid-flight
  (:func:`~repro.core.region_store.write_slot`) without recompilation;
- *across devices* — the leading problem axis is sharded over a mesh
  (``shard_map``): each device owns a contiguous block of
  ``batch_slots / n_devices`` slots and runs the vmapped windowed step
  locally; fleet-wide progress (any slot newly done? how many live?) is
  decided from a ``psum`` of per-slot done masks once per fused dispatch;
  and when a device's live slots drain, whole *problems* migrate from its
  cyclic ring partner — the paper's round-robin redistribution scheme
  (:mod:`repro.core.redistribution`), lifted from regions to problems.

Because batch slots evolve independently (a problem's trajectory never
depends on which slot or device hosts it), sharding and migration preserve
bit-identical results: every terminal ``QuadResult`` — converged, max_iters,
or evicted — matches the single-device service exactly.

Window discipline: each window must be a single static shape per dispatch,
so the host picks the smallest ladder rung covering the widest live slot of
the fleet and dispatches the program compiled for that rung (a dispatch
that outgrows its rung stops early and the next one takes the wider rung).
By the active-window invariance argument (any window >= n_active is exact
for eval/reductions, any window >= min(2 * n_active, capacity) for the
sort-based advance) every slot gets bit-identical estimates and trajectories
to its own serial run at that rung — there is exactly one compiled
executable per (d, rule, window-rung), shared across the whole batch, and
only the rungs a fleet reaches are compiled.  The advance stage (classify +
split/compact) and the global-estimate reductions are windowed the same way
when ``cfg.advance_window`` is on, so the whole vmapped iteration scales
with the widest live population, not store capacity.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import region_store
from repro.core.adaptive import (
    advance_ladder,
    advance_target,
    eval_ladder,
    make_advance_step,
    make_eval_step,
    result_status,
)
from repro.core.classify import nonfinite_mask
from repro.core.config import QuadratureConfig
from repro.core.integrands import ParamIntegrand, get_param
from repro.core.programs import RungPrograms
from repro.core.redistribution import (
    dispatch_cyclic,
    exchange_pair_stats,
    make_schedule,
    ring_perms,
)
from repro.core.region_store import RegionState
from repro.core.rules import make_rule
from repro.telemetry import NULL

AXIS = "dev"


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "regions",
        "theta",
        "rel_tol",
        "abs_tol",
        "occupied",
        "done",
        "overflow_it",
    ],
    meta_fields=[],
)
@dataclasses.dataclass
class BatchState:
    """B independent problems in lockstep: stacked stores + per-slot masks."""

    regions: RegionState  # every leaf has a leading (B,) axis
    theta: Any  # family theta pytree, leaves (B, d)
    rel_tol: jnp.ndarray  # (B,) per-request tolerances
    abs_tol: jnp.ndarray  # (B,)
    occupied: jnp.ndarray  # (B,) bool — slot holds an admitted problem
    done: jnp.ndarray  # (B,) bool — result ready, frozen until released
    overflow_it: jnp.ndarray  # (B,) int32 — it at first overflow, -1 = never

    @property
    def n_slots(self) -> int:
        return self.occupied.shape[0]


def _select_slots(mask: jnp.ndarray, new, old):
    """Per-slot select over a stacked pytree (mask broadcast over trailing dims)."""

    def sel(n, o):
        m = mask.reshape(mask.shape + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)

    return jax.tree.map(sel, new, old)


def _widest_live(state: "BatchState") -> jnp.ndarray:
    """Largest active-region count over the live (occupied, not done) slots."""
    live = state.occupied & ~state.done
    counts = jnp.sum(state.regions.active, axis=1).astype(jnp.int32)
    return jnp.max(jnp.where(live, counts, 0))


def _ppermute_tree(tree, axis_name: str, perm):
    """ppermute every leaf of a pytree (bools ride as uint8 for portability)."""

    def pp(leaf):
        if leaf.dtype == jnp.bool_:
            sent = jax.lax.ppermute(leaf.astype(jnp.uint8), axis_name, perm)
            return sent.astype(bool)
        return jax.lax.ppermute(leaf, axis_name, perm)

    return jax.tree.map(pp, tree)


def estimate_state_bytes(
    cfg: QuadratureConfig, family: Union[ParamIntegrand, str, None] = None
) -> int:
    """Device bytes of the engine's :class:`BatchState` for ``cfg``.

    The stacked store is the dominant service allocation
    (``batch_slots x capacity`` regions); CLIs use this to fail fast on
    slot counts the store memory cannot accommodate, before the engine
    tries (and fails, unhelpfully) to allocate them.
    """
    cfg = cfg.validate()
    if family is None:
        family = cfg.integrand.partition(":")[0]
    if isinstance(family, str):
        family = get_param(family)
    item = jnp.dtype(cfg.dtype).itemsize
    C, d = cfg.capacity, cfg.d
    per_slot = (
        2 * C * d * item  # centers + halfw
        + 2 * C * item  # est + err
        + 4 * C  # axis (int32)
        + 2 * C  # active + fresh (bool)
        + 3 * item + 4 + 1  # fin_integral, fin_error, n_evals, it, overflowed
        + len(family.theta_fields) * d * item  # theta
        + 2 * item + 4 + 2  # rel_tol, abs_tol, overflow_it, occupied, done
    )
    return cfg.batch_slots * per_slot


class BatchEngine:
    """Compiled-step executor for a fixed-shape fleet of one integrand family.

    All problems share ``cfg``'s static shape (d, capacity, rule, domain) and
    differ only in theta and tolerances — that is what makes the batch a
    single XLA program.  The scheduler (:mod:`repro.service.scheduler`)
    drives :meth:`run` from the host, admitting and collecting per slot.

    ``mesh`` / ``devices`` shard the slot axis: slot ``s`` lives on device
    ``s // (batch_slots / n_devices)``.  With one device (the default) the
    engine is the plain single-device vmapped fleet.  ``cfg.service_devices``
    picks a mesh size when neither argument is given (0 = all visible).
    """

    def __init__(
        self,
        cfg: QuadratureConfig,
        family: Union[ParamIntegrand, str, None] = None,
        mesh=None,
        devices=None,
        recorder=NULL,
    ):
        cfg = cfg.validate()
        if family is None:
            family = cfg.integrand.partition(":")[0]
        if isinstance(family, str):
            family = get_param(family)
        self.cfg = cfg
        self.family = family
        self.recorder = recorder
        self.n_slots = cfg.batch_slots

        mesh = self._resolve_mesh(cfg, mesh, devices)
        self.mesh = mesh
        self.n_devices = 1 if mesh is None else mesh.shape[AXIS]
        if self.n_slots % self.n_devices:
            raise ValueError(
                f"batch_slots={self.n_slots} must be a multiple of the mesh "
                f"size ({self.n_devices} devices): each device owns a "
                "contiguous block of batch_slots / n_devices slots"
            )
        self.slots_per_device = self.n_slots // self.n_devices
        # a pair can never usefully exchange more problems than one side owns
        self.rebalance_cap = min(cfg.rebalance_cap, self.slots_per_device)

        lo = np.asarray(cfg.lo(), np.float64)
        hi = np.asarray(cfg.hi(), np.float64)
        self._total_volume = float(np.prod(hi - lo))
        self._width = hi - lo
        self._dtype = jnp.dtype(cfg.dtype)
        # fresh single-slot state spliced into a slot on admit
        self._fresh_slot = region_store.init_state(
            cfg.capacity, lo, hi, cfg.resolved_n_init(), self._dtype
        )
        # theta template fixes the pytree structure + leaf shapes of the fleet
        self.theta_template = jax.tree.map(
            lambda x: np.zeros(np.shape(x), np.float64),
            family.sample_theta(cfg.d, np.random.default_rng(0)),
        )

        # the platform of the devices that run the rule resolves the
        # kernel's interpret mode (rules.make_rule)
        self._platform = (
            mesh.devices.flat[0].platform
            if mesh is not None
            else (devices[0].platform if devices else None)
        )
        # every jitted op donates the fleet state (argument 0).  Build span
        # only: the jits trace/compile lazily on first dispatch, which the
        # scheduler's "service.compile" span captures instead
        with recorder.span(
            "engine.build",
            backend=self.backend,
            slots=self.n_slots,
            devices=self.n_devices,
        ):
            # one step and one fused run per eval-window rung, compiled on
            # first use (see _rung)
            self._ladder = eval_ladder(cfg)
            self._steps = RungPrograms(
                self._ladder,
                lambda w: jax.jit(self._make_step(w), donate_argnums=0),
            )
            self._runs = RungPrograms(
                self._ladder,
                lambda w: jax.jit(self._make_run(w), donate_argnums=0),
            )
            self._widest = jax.jit(_widest_live)
            self._admit = jax.jit(self._sharded(self._make_admit()), donate_argnums=0)
            self._release = jax.jit(
                self._sharded(self._make_release()), donate_argnums=0
            )

    @staticmethod
    def _resolve_mesh(cfg: QuadratureConfig, mesh, devices):
        if mesh is not None:
            if AXIS not in mesh.shape:
                raise ValueError(f"mesh must have a {AXIS!r} axis, got {mesh}")
        else:
            if devices is None:
                if cfg.service_devices == 1:
                    return None
                avail = jax.devices()
                want = (
                    len(avail)
                    if cfg.service_devices == 0
                    else cfg.service_devices
                )
                if want > len(avail):
                    raise ValueError(
                        f"service_devices={cfg.service_devices} but only "
                        f"{len(avail)} devices are visible"
                    )
                devices = avail[:want]
            if len(devices) == 1:
                return None
            mesh = jax.make_mesh((len(devices),), (AXIS,), devices=devices)
        if mesh.shape[AXIS] == 1:
            return None  # a 1-mesh is just the single-device path
        return mesh

    def _sharded(self, fn):
        """Wrap a (state, *scalars) -> state op in shard_map when meshed.

        The state rides split over the slot axis; every other argument is
        replicated.  On a single device the op is used as-is.
        """
        if self.mesh is None:
            return fn

        def wrapper(state, *args):
            return jax.shard_map(
                fn,
                mesh=self.mesh,
                in_specs=(P(AXIS),) + (P(),) * len(args),
                out_specs=P(AXIS),
                check_vma=False,
            )(state, *args)

        return wrapper

    # --- state construction --------------------------------------------------

    def init(self) -> BatchState:
        """All slots empty; admit problems before stepping."""
        cfg = self.cfg
        B = self.n_slots
        state = BatchState(
            regions=region_store.stacked_empty_state(
                B, cfg.capacity, cfg.d, self._dtype
            ),
            theta=jax.tree.map(
                lambda x: jnp.zeros((B,) + x.shape, self._dtype),
                self.theta_template,
            ),
            rel_tol=jnp.full((B,), cfg.rel_tol, self._dtype),
            abs_tol=jnp.full((B,), cfg.abs_tol, self._dtype),
            occupied=jnp.zeros((B,), bool),
            done=jnp.zeros((B,), bool),
            overflow_it=jnp.full((B,), -1, jnp.int32),
        )
        if self.mesh is not None:
            state = jax.device_put(state, NamedSharding(self.mesh, P(AXIS)))
        return state

    def place(self, state):
        """Re-place a full logical fleet state onto *this* engine's mesh.

        Every :class:`BatchState` leaf carries the slot axis leading, so one
        sharding re-slices the whole pytree.  This is the elastic half of the
        checkpoint contract (DESIGN.md §6) exposed directly: a state captured
        on any mesh (host arrays included) becomes valid input for this
        engine's fused dispatch — the scheduler's device-loss shrink/regrow
        rebuilds the engine on the surviving sub-mesh and pushes the
        evacuated state through here.
        """
        if self.mesh is None:
            return jax.tree.map(jnp.asarray, state)
        sharding = NamedSharding(self.mesh, P(AXIS))
        return jax.tree.map(lambda x: jax.device_put(jnp.asarray(x), sharding), state)

    # --- jitted slot operations ----------------------------------------------

    def _localize(self, slot):
        """Global slot index -> per-device local index (OOB on non-owners).

        Inside shard_map every device sees its own contiguous slot block;
        the owner writes at ``slot - base`` and everyone else scatters to the
        out-of-bounds sentinel, dropped by ``mode="drop"``.
        """
        if self.n_devices == 1:
            return slot
        local = self.slots_per_device
        base = jax.lax.axis_index(AXIS) * local
        owns = (slot >= base) & (slot < base + local)
        return jnp.where(owns, slot - base, local)

    def _make_admit(self):
        fresh = self._fresh_slot

        def admit(state: BatchState, slot, theta, rel_tol, abs_tol) -> BatchState:
            at = self._localize(slot)
            put = lambda dst, src: dst.at[at].set(src, mode="drop")
            return dataclasses.replace(
                state,
                regions=region_store.write_slot(state.regions, at, fresh, mode="drop"),
                theta=jax.tree.map(put, state.theta, theta),
                rel_tol=put(state.rel_tol, rel_tol),
                abs_tol=put(state.abs_tol, abs_tol),
                occupied=put(state.occupied, True),
                done=put(state.done, False),
                overflow_it=put(state.overflow_it, -1),
            )

        return admit

    def _make_release(self):
        def release(state: BatchState, slot) -> BatchState:
            at = self._localize(slot)
            return dataclasses.replace(
                state,
                occupied=state.occupied.at[at].set(False, mode="drop"),
                done=state.done.at[at].set(False, mode="drop"),
            )

        return release

    def admit(
        self,
        state: BatchState,
        slot: int,
        theta,
        rel_tol: Optional[float] = None,
        abs_tol: Optional[float] = None,
    ) -> BatchState:
        """Write a fresh initial partition + theta into ``slot`` (mid-flight safe)."""
        self._check_slot(slot)
        got = jax.tree.map(lambda x: np.shape(x), theta)
        want = jax.tree.map(lambda x: np.shape(x), self.theta_template)
        if got != want:
            raise ValueError(
                f"theta shape mismatch for family {self.family.name!r}: "
                f"got {got}, want {want}"
            )
        return self._admit(
            state,
            jnp.asarray(slot, jnp.int32),
            jax.tree.map(lambda x: jnp.asarray(x, self._dtype), theta),
            jnp.asarray(self.cfg.rel_tol if rel_tol is None else rel_tol, self._dtype),
            jnp.asarray(self.cfg.abs_tol if abs_tol is None else abs_tol, self._dtype),
        )

    def release(self, state: BatchState, slot: int) -> BatchState:
        """Free a collected slot (its store stays stale until the next admit)."""
        self._check_slot(slot)
        return self._release(state, jnp.asarray(slot, jnp.int32))

    def _check_slot(self, slot: int) -> None:
        # JAX drops out-of-bounds scatter updates, so a bad index would
        # otherwise no-op silently and strand the request.
        if not 0 <= int(slot) < self.n_slots:
            raise IndexError(f"slot {slot} out of range [0, {self.n_slots})")

    # --- the batched adaptive step -------------------------------------------

    def _make_iter(self, window: int):
        """One adaptive iteration over whatever slot block the caller holds,
        at eval window ``window`` (a rung covering every live slot).

        Shape-polymorphic in the leading slot axis: the single-device step
        applies it to all ``batch_slots`` slots, the sharded fused run to each
        device's local block — the same traced math either way, which is what
        makes device-count parity structural rather than coincidental.
        Returns ``(state, metrics, n_new_done)``.
        """
        cfg = self.cfg
        family = self.family
        total_volume = self._total_volume
        C = cfg.capacity

        def eval_one(regions: RegionState, theta) -> RegionState:
            # theta rides as a rule operand (not a closure) so the Pallas
            # kernel path works under vmap — see rules.make_rule
            rule = make_rule(cfg, family.fn, theta=theta, platform=self._platform)
            return make_eval_step(cfg, rule, window=window)(regions)

        evaluate = jax.vmap(eval_one)

        # The post-eval tail of the iteration at the advance rung covering
        # min(2 * window, C): the global-estimate reductions, the per-slot
        # budget, and the serial drivers' advance (vmapped with per-slot
        # traced tolerances).  Any wider window is bit-identical for the
        # narrower slots, so one shared rung is exact.
        adv_window = region_store.select_window(
            advance_ladder(cfg), advance_target(window, C)
        )
        adv = jax.vmap(
            make_advance_step(cfg, total_volume, self._width, window=adv_window)
        )

        def est_one(regions: RegionState):
            integral, error = regions.global_estimates(window=adv_window)
            n = jnp.sum(regions.active[:adv_window]).astype(jnp.int32)
            return integral, error, n

        def tail(regions: RegionState, abs_tol, rel_tol):
            integral, error, n_active = jax.vmap(est_one)(regions)
            budget = jnp.maximum(abs_tol, jnp.abs(integral) * rel_tol)
            advanced = adv(regions, budget, rel_tol)
            return integral, error, n_active, budget, advanced

        def iter_fn(state: BatchState):
            live = state.occupied & ~state.done
            evald = evaluate(state.regions, state.theta)
            regions = _select_slots(live, evald, state.regions)

            # --- non-finite quarantine ---------------------------------------
            # A NaN/Inf region estimate (pathological theta, corrupted slot)
            # must be contained to its own slot BEFORE the global-estimate
            # reductions run, or it poisons the slot's budget check forever
            # and — worse — every psum'd fleet metric downstream.  Zero the
            # offending regions' contributions, deactivate them, and flag the
            # slot terminal with status "nonfinite".  For healthy slots the
            # masks are all-False and every where() is a bitwise identity, so
            # serial parity is untouched.
            bad = nonfinite_mask(regions.est, regions.err, regions.active)
            bad = bad & live[:, None]
            # the finalised accumulators are equally load-bearing: once one
            # goes non-finite (corrupted state — nothing healthy writes NaN
            # there) the slot's global estimate can never recover, so flag
            # the slot and zero the accumulator out of the reductions
            bad_fin = live & ~(
                jnp.isfinite(regions.fin_integral)
                & jnp.isfinite(regions.fin_error)
            )
            nonfinite = jnp.any(bad, axis=1) | bad_fin
            regions = dataclasses.replace(
                regions,
                est=jnp.where(bad, 0.0, regions.est),
                err=jnp.where(bad, 0.0, regions.err),
                active=regions.active & ~bad,
                fin_integral=jnp.where(bad_fin, 0.0, regions.fin_integral),
                fin_error=jnp.where(bad_fin, 0.0, regions.fin_error),
            )

            integral, error, n_active, budget, advanced = tail(
                regions, state.abs_tol, state.rel_tol
            )
            converged = error <= budget
            # Capacity pressure is not instantly terminal: the serial driver
            # grinds past overflow and often converges, so an overflowed slot
            # keeps refining for ``evict_patience`` further iterations (exact
            # serial parity for transient saturation) before being evicted.
            overflow_it = jnp.where(
                regions.overflowed & (state.overflow_it < 0),
                regions.it,
                state.overflow_it,
            )
            evicted = regions.overflowed & (
                regions.it - overflow_it >= cfg.evict_patience
            )
            # The serial driver runs exactly max_iters eval sweeps: post-eval
            # ``it == max_iters - 1`` means this sweep was the last one, so
            # the slot freezes NOW — checking ``it >= max_iters`` instead
            # would eval the final advance's children one extra time and
            # break bit-parity with `integrate` on the max_iters path.
            capped = regions.it >= cfg.max_iters - 1
            terminal = converged | (n_active == 0) | capped | evicted | nonfinite
            done = state.done | (live & terminal)
            n_new_done = jnp.sum(done & ~state.done).astype(jnp.int32)

            regions = _select_slots(state.occupied & ~done, advanced, regions)
            # Serial parity on the counter too: after capturing its final
            # metrics the serial driver still runs (and counts) one advance
            # before the loop exhausts.  The frozen slot skips the splitting
            # (its estimates must stay collectable) but mirrors the counter.
            bump = live & capped & ~converged & (n_active > 0)
            regions = dataclasses.replace(
                regions, it=regions.it + bump.astype(regions.it.dtype)
            )

            metrics = {
                "integral": integral,
                "error": error,
                "n_active": n_active,
                "it": regions.it,
                "n_evals": regions.n_evals,
                "overflowed": regions.overflowed,
                "converged": converged,
                "nonfinite": nonfinite,
                "done": done,
                "occupied": state.occupied,
                "window": jnp.asarray(window, jnp.int32),
            }
            return (
                dataclasses.replace(
                    state, regions=regions, done=done, overflow_it=overflow_it
                ),
                metrics,
                n_new_done,
            )

        return iter_fn

    def _make_step(self, window: int):
        iter_fn = self._make_iter(window)

        def step(state: BatchState):
            state, metrics, _ = iter_fn(state)
            return state, metrics

        return step

    def step(self, state: BatchState):
        """One fused iteration for every live slot; returns (state, metrics).

        ``metrics`` holds per-slot device arrays: ``integral``, ``error``,
        ``n_active``, ``it``, ``n_evals``, ``overflowed``, ``converged``,
        ``done``, ``occupied`` plus the scalar eval ``window`` used.  Slots
        whose ``done`` flips on are frozen (no further advance) until the
        scheduler collects and releases them.  (On a sharded engine this is
        the GSPMD form; the scheduler drives :meth:`run` instead.)
        """
        return self._steps(self._rung(state), state)(state)

    def _rung(self, state: BatchState) -> int:
        """The eval-ladder rung covering the widest live slot of ``state``.

        Each step and fused run is compiled for one rung, as the serial host
        driver compiles one eval per rung: only the rungs a fleet reaches
        are ever compiled, where one program switching over the whole ladder
        compiled every rung up front.
        """
        return region_store.select_window(self._ladder, int(self._widest(state)))

    # --- problem-level cyclic rebalancing ------------------------------------

    def _make_rebalance_round(self):
        """One migration round: the paper's cyclic round-robin pairing
        (:func:`repro.core.redistribution.redistribute`), lifted from regions
        to whole problems.  A device whose live-slot count fell below the
        fleet's fair share — its problems converged and were collected while
        the queue ran dry — receives up to ``rebalance_cap`` entire problems
        (region store + theta + tolerances) from its ring partner at the
        scheduled shift.  Migration cannot change any result: slots evolve
        independently, so moving one only changes which device pays for it.
        """
        n_dev = self.n_devices
        cap = self.rebalance_cap
        local = self.slots_per_device
        schedule = make_schedule(n_dev)

        def round_fn(shift: int):
            _, perm_up = ring_perms(n_dev, shift)

            def fn(state: BatchState):
                occupied = state.occupied
                live = occupied & ~state.done
                n_live = jnp.sum(live).astype(jnp.int32)
                n_free = jnp.sum(~occupied).astype(jnp.int32)
                total = jax.lax.psum(n_live, AXIS)
                fair = total // n_dev  # floor: migrate only into real holes
                surplus = jnp.maximum(n_live - fair, 0)
                deficit = jnp.maximum(fair - n_live, 0)
                stats = jnp.stack([n_live, n_free, surplus, deficit])
                down_stats, up_stats = exchange_pair_stats(
                    stats, AXIS, n_dev, shift
                )
                _, down_free, _, down_deficit = down_stats
                _, _, up_surplus, _ = up_stats
                n_send = jnp.minimum(
                    jnp.minimum(jnp.int32(cap), surplus),
                    jnp.minimum(down_deficit, down_free),
                )
                n_recv = jnp.minimum(
                    jnp.minimum(jnp.int32(cap), up_surplus),
                    jnp.minimum(deficit, n_free),
                )

                idx = jnp.arange(local, dtype=jnp.int32)
                j = jnp.arange(cap, dtype=jnp.int32)
                base = (jax.lax.axis_index(AXIS) * local).astype(jnp.int32)

                # --- donor: pick the highest-index live slots --------------
                skey = jnp.where(live, -idx, jnp.int32(local + 1))
                src_local = jnp.argsort(skey)[:cap].astype(jnp.int32)
                valid_send = j < n_send
                payload = (
                    state.regions,
                    state.theta,
                    state.rel_tol,
                    state.abs_tol,
                    state.overflow_it,
                )
                picked = jax.tree.map(lambda leaf: leaf[src_local], payload)
                src_global = jnp.where(valid_send, base + src_local, -1)
                incoming = _ppermute_tree(picked, AXIS, perm_up)
                src_global_in = jax.lax.ppermute(src_global, AXIS, perm_up)
                send_mask = jnp.zeros((local,), bool).at[src_local].set(valid_send)
                occupied = occupied & ~send_mask

                # --- receiver: splice into the lowest-index free slots -----
                rkey = jnp.where(state.occupied, jnp.int32(local + 1), idx)
                dst_local = jnp.argsort(rkey)[:cap].astype(jnp.int32)
                valid_recv = j < n_recv
                dst = jnp.where(valid_recv, dst_local, local)  # local = dropped
                in_regions, in_theta, in_rel, in_abs, in_overflow = incoming
                put = lambda cur, new: cur.at[dst].set(new, mode="drop")
                moved = jnp.stack(
                    [
                        jnp.where(valid_recv, src_global_in, -1),
                        jnp.where(valid_recv, base + dst_local, -1),
                    ],
                    axis=1,
                )
                return (
                    dataclasses.replace(
                        state,
                        regions=jax.tree.map(put, state.regions, in_regions),
                        theta=jax.tree.map(put, state.theta, in_theta),
                        rel_tol=put(state.rel_tol, in_rel),
                        abs_tol=put(state.abs_tol, in_abs),
                        overflow_it=put(state.overflow_it, in_overflow),
                        occupied=occupied.at[dst].set(True, mode="drop"),
                        done=put(state.done, jnp.zeros((cap,), bool)),
                    ),
                    moved,
                )

            return fn

        def rebalance(state: BatchState, t):
            return dispatch_cyclic(schedule, t, round_fn, state)

        return rebalance

    # --- the fused multi-iteration dispatch -----------------------------------

    def _make_run(self, window: int):
        """Build the K-fused dispatch (K = ``cfg.sync_every``) at one rung.

        Runs up to ``max_steps`` iterations in one XLA dispatch and stops
        early — remaining iterations become pass-throughs — as soon as any
        live slot's ``done`` flips on (decided from a psum of per-slot done
        masks, the fleet's single global sync point), so the host scheduler
        observes every collection at its exact iteration and can replay
        admission/eviction decisions identically to an unfused loop.  It also
        stops before an iteration whose widest live slot (across the mesh)
        has outgrown ``window``: the host then dispatches again at the rung
        that slot needs.
        """
        cfg = self.cfg
        n_dev = self.n_devices
        iter_fn = self._make_iter(window)
        rebalance_on = n_dev > 1 and cfg.rebalance != "off"
        rebalance = self._make_rebalance_round() if rebalance_on else None
        moved_rows = self.rebalance_cap if n_dev > 1 else 0
        dtype = self._dtype

        def no_moves():
            return jnp.full((moved_rows, 2), -1, jnp.int32)

        def zero_metrics(state: BatchState):
            B = state.occupied.shape[0]
            z = jnp.zeros
            return {
                "integral": z((B,), dtype),
                "error": z((B,), dtype),
                "n_active": z((B,), jnp.int32),
                "it": z((B,), jnp.int32),
                "n_evals": z((B,), dtype),
                "overflowed": z((B,), bool),
                "converged": z((B,), bool),
                "nonfinite": z((B,), bool),
                "done": z((B,), bool),
                "occupied": z((B,), bool),
                "window": z((), jnp.int32),
            }

        def run_body(state: BatchState, max_steps, tick):
            def one(carry, t):
                state, stop = carry
                widest = _widest_live(state)
                if n_dev > 1:
                    widest = jax.lax.pmax(widest, AXIS)
                go = (~stop) & (t < max_steps) & (widest <= window)

                def do(state):
                    state, metrics, n_new = iter_fn(state)
                    if n_dev > 1:
                        n_new = jax.lax.psum(n_new, AXIS)
                    if rebalance_on:
                        state, moved = rebalance(state, tick + t)
                    else:
                        moved = no_moves()
                    return state, metrics, moved, n_new > 0

                def skip(state):
                    return state, zero_metrics(state), no_moves(), jnp.asarray(True)

                state, m, moved, stop = jax.lax.cond(go, do, skip, state)
                return (state, stop), (m, moved, go)

            (state, _), (ms, moved, executed) = jax.lax.scan(
                one,
                (state, jnp.asarray(False)),
                jnp.arange(cfg.sync_every, dtype=jnp.int32),
            )
            # per-device eval window, shaped for the slot-axis out_spec
            ms = {**ms, "window": ms["window"][:, None]}
            return state, ms, executed, moved

        if self.mesh is None:
            return run_body
        # check_vma=False: scan carries start device-invariant and become
        # device-varying, which the static replication checker cannot express
        return jax.shard_map(
            run_body,
            mesh=self.mesh,
            in_specs=(P(AXIS), P(), P()),
            out_specs=(P(AXIS), P(None, AXIS), P(), P(None, AXIS, None)),
            check_vma=False,
        )

    backend = "cubature"

    def status_of(
        self,
        converged: bool,
        n_active: int,
        it: int,
        overflowed: bool,
        nonfinite: bool = False,
    ) -> str:
        """Terminal taxonomy for collected slots (scheduler hook; the MC
        engine pool supplies its own — MC has no region store, so no
        capacity/no_active statuses)."""
        return result_status(
            converged, n_active, it, self.cfg, overflowed, nonfinite
        )

    def run(self, state: BatchState, max_steps: int, tick: int):
        """Up to ``min(max_steps, cfg.sync_every)`` fused iterations.

        Returns ``(state, metrics, executed, moved)``:

        - ``metrics`` — per-slot arrays stacked over the fused iterations,
          shape ``(sync_every, batch_slots)`` (``window`` is per device);
        - ``executed`` — ``(sync_every,)`` prefix mask of iterations that
          actually ran; the first unexecuted row follows either the
          ``max_steps`` cap or an early exit on a done-flip, so the last
          executed row is where every newly finished slot finished;
        - ``moved`` — ``(sync_every, n_devices * rebalance_cap, 2)`` int32
          ``(src_slot, dst_slot)`` migration records per iteration (-1 =
          unused row); the host applies them to its slot -> request map in
          iteration order, after collecting that iteration's done slots.

        ``tick`` is the fleet-global iteration number of the first fused
        iteration (indexes the cyclic migration schedule).
        """
        args = (
            state,
            jnp.asarray(min(int(max_steps), self.cfg.sync_every), jnp.int32),
            jnp.asarray(tick, jnp.int32),
        )
        return self._runs(self._rung(state), *args)(*args)
