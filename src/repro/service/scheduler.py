"""Continuous-batching scheduler for the batch quadrature engine.

The host-side loop that turns the fixed-shape :class:`BatchEngine` into a
service: a FIFO request queue feeds ``cfg.batch_slots`` slots; every
``cfg.admit_every`` iterations freed slots are refilled from the queue
(mid-flight — the other slots keep refining through the same compiled step),
and finished slots are collected and yielded as :class:`QuadResult`\\ s as
soon as their ``done`` flag flips, in convergence order rather than
submission order.

The engine is driven through its fused :meth:`~BatchEngine.run` protocol:
up to ``cfg.sync_every`` iterations execute per dispatch and the dispatch
exits early — from an on-device psum of per-slot done masks — the moment any
slot finishes, so the host observes every collection at its exact iteration.
The scheduler additionally caps a dispatch so it cannot run past the next
``admit_every`` tick while an admission is pending.  Together these make the
fused loop replay the unfused per-iteration loop decision-for-decision:
results (including ``admitted_at`` / ``finished_at``) are bit-identical at
any ``sync_every`` and any device count.

On a sharded engine the scheduler is also mesh-aware: admissions target the
device that owns the freed slot (free slots are filled on the least-loaded
device first, so fresh problems spread across the mesh), and the migration
records the engine emits when its cyclic rebalancer moves a problem between
devices are replayed onto the host's slot -> request map in iteration order.

Termination taxonomy per request (mirrors ``AdaptiveResult.status``):

- ``converged`` — error estimate under the request's budget;
- ``capacity`` — the slot's region store saturated (``overflowed``) and
  stayed unconverged for ``cfg.evict_patience`` further iterations: the
  engine freezes it and the scheduler *evicts* it with its best-effort
  estimate so the slot can serve the rest of the queue instead of grinding
  a hopeless problem (transient saturation that converges within the grace
  period keeps exact parity with the serial driver);
- ``no_active`` / ``max_iters`` — degenerate population / iteration cap;
- ``nonfinite`` — the slot produced NaN/Inf estimates; the engine quarantined
  the offending regions (zeroed their contributions, deactivated them) the
  same iteration, so the rest of the fleet's psum'd reductions never see the
  poison, and the scheduler collects the slot with its best-effort estimate;
- ``deadline`` — the request's SLO (``deadline_s`` wall clock and/or
  ``max_evals`` evaluation budget) expired at a dispatch boundary: the
  scheduler evicts the slot with its best-effort partial estimate instead of
  letting one slow problem hold a slot indefinitely.

Graceful degradation on top of this taxonomy (fallback re-routing of
``capacity``/``nonfinite`` evictions to the VEGAS pool, looser-tolerance
retries) lives in :mod:`repro.service.routing`; service-level
checkpoint/resume in :mod:`repro.service.checkpoint`.

The scheduler is also elastic in the fleet-topology dimension: every
dispatch runs under a host-side watchdog (:class:`DispatchTimeout` /
:class:`DeviceLostError`, bounded retries with exponential backoff), and a
device declared permanently failed is evacuated — snapshot-covered slots
rewind to the newest service checkpoint, uncovered requests re-enter the
admission queue with provenance — before the engine is rebuilt on the
largest surviving sub-mesh and, once the device heals, regrown.  See
DESIGN.md §6 for the failure model and the bit-identity guarantees.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Any, Callable, Iterable, Iterator, Optional, Union

import jax
import numpy as np

from repro.core.config import QuadratureConfig
from repro.core.integrands import ParamIntegrand
from repro.service.batch_engine import BatchEngine, BatchState
from repro.telemetry import NULL, ServiceStats


class DeviceLostError(RuntimeError):
    """A device failed permanently (retries exhausted, or mesh not elastic).

    ``device`` is the failing device's global index in the engine's
    *original* mesh, or ``None`` when the watchdog could not attribute the
    fault to a specific device.  Raised by injectors
    (:class:`repro.service.faults.DeviceDown`) to simulate the loss, and
    re-raised by the scheduler only when recovery is impossible — a
    single-device engine has nowhere to evacuate to.
    """

    def __init__(self, device: Optional[int], message: str):
        super().__init__(message)
        self.device = device


class DispatchTimeout(RuntimeError):
    """A fused dispatch exceeded the watchdog's ``dispatch_timeout_s``.

    Unlike :class:`DeviceLostError` it carries no device attribution — a
    hang looks the same from the host regardless of which device wedged —
    so the scheduler falls back to the injector's ``healthy`` probe (or
    gives up) to pick the device to declare failed.  ``retryable`` is False
    when the wedged launch had already taken (donated) the fleet state.
    """

    def __init__(self, message: str, retryable: bool = True):
        super().__init__(message)
        self.retryable = retryable


class _Attempt:
    """One watched dispatch attempt, shared by the watchdog and its worker.

    The fused dispatch donates the fleet state, so once a launch has taken
    the state there is nothing left to retry or evacuate with.  The worker
    may take the state only while the watchdog has not given up on it
    (:meth:`take`), and a watchdog that gives up learns whether it already
    had (:meth:`abandon`) — check and act under one lock, so an abandoned
    attempt that wakes up later can never consume the state of its retry.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._abandoned = False
        self._taken = False

    def take(self) -> bool:
        with self._lock:
            if not self._abandoned:
                self._taken = True
            return self._taken

    def abandon(self) -> bool:
        with self._lock:
            self._abandoned = True
            return self._taken


def _call_with_timeout(fn: Callable, timeout_s: Optional[float]):
    """Run ``fn(attempt)`` under a wall-clock watchdog.

    With a timeout the call runs on a daemon thread and a ``join`` bounds
    the wait: a wedged dispatch raises :class:`DispatchTimeout` on the host
    and the stuck thread is abandoned.  ``fn`` must call ``attempt.take()``
    before it consumes the state and skip the dispatch when that returns
    False.  A timeout after the state was taken is not retryable
    (``DispatchTimeout.retryable`` is False): the wedged launch owns the
    donated buffers.  Exceptions from ``fn`` itself propagate unchanged.
    """
    attempt = _Attempt()
    if timeout_s is None:
        return fn(attempt)
    box: dict = {}

    def target():
        try:
            box["value"] = fn(attempt)
        except BaseException as err:  # noqa: BLE001 - re-raised on the host
            box["error"] = err

    worker = threading.Thread(target=target, daemon=True, name="dispatch-watchdog")
    worker.start()
    worker.join(timeout_s)
    if worker.is_alive():
        taken = attempt.abandon()
        raise DispatchTimeout(
            f"fused dispatch still running after {timeout_s}s watchdog"
            + ("; it had already taken the fleet state, so it cannot be "
               "retried (resume from the service checkpoint)" if taken else ""),
            retryable=not taken,
        )
    if "error" in box:
        raise box["error"]
    return box["value"]


def make_engine(
    cfg: QuadratureConfig,
    family: Union[ParamIntegrand, str, None] = None,
    mesh=None,
    devices=None,
    recorder=NULL,
):
    """Engine for ``cfg``'s resolved backend.

    The service fronts two engine pools behind one scheduler protocol
    (``init``/``admit``/``release``/fused ``run`` + ``status_of``): the
    deterministic cubature :class:`BatchEngine` and the Monte Carlo
    :class:`~repro.mc.engine.VegasBatchEngine` — ``backend="auto"`` picks by
    the problem dimension, so high-d fleets are admitted through MC instead
    of being rejected by region-store explosion.
    """
    if cfg.resolved_backend() == "vegas":
        from repro.mc.engine import VegasBatchEngine

        return VegasBatchEngine(
            cfg, family, mesh=mesh, devices=devices, recorder=recorder
        )
    return BatchEngine(cfg, family, mesh=mesh, devices=devices, recorder=recorder)


@dataclasses.dataclass(frozen=True)
class QuadRequest:
    """One integration problem: a theta of the engine's family + tolerances.

    ``deadline_s`` / ``max_evals`` are best-effort SLOs, checked at dispatch
    boundaries (the host only observes slot metrics between fused launches):
    once either budget is exhausted the slot is evicted with its current
    partial estimate and status ``deadline`` instead of holding the slot.
    ``max_evals`` is deterministic (counted in integrand evaluations);
    ``deadline_s`` is wall clock measured from admission.
    """

    req_id: int
    theta: Any  # pytree matching the family's theta_fields, leaves (d,)
    rel_tol: Optional[float] = None  # None -> cfg default
    abs_tol: Optional[float] = None
    deadline_s: Optional[float] = None  # wall-clock budget from admission
    max_evals: Optional[float] = None  # integrand-evaluation budget


@dataclasses.dataclass(frozen=True)
class QuadResult:
    """Terminal state of one request (statuses as in AdaptiveResult).

    ``backend``/``attempts``/``retried_from`` record attempt provenance:
    which engine pool produced this estimate, how many admissions the
    request consumed in total, and — for re-routed/retried requests — the
    terminal status of the attempt that triggered the re-route (see
    :class:`repro.service.routing.GracefulScheduler`).  ``evacuated``
    records device-loss provenance: ``"snapshot"`` when the request's slot
    was recovered from the newest service checkpoint after its device
    failed (its trajectory rewound and replayed, still bit-identical),
    ``"readmit"`` when no snapshot covered the slot and the request was
    re-admitted from scratch (``attempts`` bumps and ``retried_from`` is
    ``"device_lost"``), ``None`` for requests no device failure touched.
    """

    req_id: int
    integral: float
    error: float
    status: str  # converged | capacity | no_active | max_iters | nonfinite | deadline
    iterations: int  # per-slot adaptive iterations spent on this problem
    n_evals: float  # integrand evaluations spent on this problem
    admitted_at: int  # scheduler iteration at which the slot was filled
    finished_at: int  # scheduler iteration at which done flipped on
    backend: str = "cubature"  # engine pool that produced this estimate
    attempts: int = 1  # admissions consumed (1 = first attempt)
    retried_from: Optional[str] = None  # prior attempt's terminal status
    evacuated: Optional[str] = None  # device-loss recovery: snapshot | readmit

    def summary(self) -> str:
        via = f" via={self.backend}" if self.attempts > 1 else ""
        evac = f" evac={self.evacuated}" if self.evacuated else ""
        return (
            f"req={self.req_id} I={self.integral:.15e} eps={self.error:.3e} "
            f"[{self.status}] iters={self.iterations} evals={self.n_evals:.3g}"
            f"{via}{evac}"
        )


def encode_request(req: QuadRequest) -> dict:
    """JSON-able form of a request (theta leaves as float64 lists).

    ``json`` serialises float64 via ``repr``, which round-trips bit-exactly,
    so a decode of an encode reconstructs the identical problem — the
    service checkpoint's resume-parity argument rests on this.
    """
    return {
        "req_id": int(req.req_id),
        "theta": jax.tree.map(
            lambda x: np.asarray(x, np.float64).tolist(), req.theta
        ),
        "rel_tol": None if req.rel_tol is None else float(req.rel_tol),
        "abs_tol": None if req.abs_tol is None else float(req.abs_tol),
        "deadline_s": None if req.deadline_s is None else float(req.deadline_s),
        "max_evals": None if req.max_evals is None else float(req.max_evals),
    }


def decode_request(obj: dict, theta_template) -> QuadRequest:
    """Inverse of :func:`encode_request`.

    ``theta_template`` (the engine's) supplies the pytree structure so the
    stored nested lists land as leaves of the right shape rather than being
    re-flattened as pytrees themselves.
    """
    theta = jax.tree.map(
        lambda t, v: np.asarray(v, np.float64).reshape(np.shape(t)),
        theta_template,
        obj["theta"],
    )
    return QuadRequest(
        req_id=int(obj["req_id"]),
        theta=theta,
        rel_tol=obj.get("rel_tol"),
        abs_tol=obj.get("abs_tol"),
        deadline_s=obj.get("deadline_s"),
        max_evals=obj.get("max_evals"),
    )


class BatchScheduler:
    """Drives a :class:`BatchEngine` over an arbitrary stream of requests.

    After :meth:`serve` completes, :attr:`last_stats` is a dict view of the
    run's :class:`~repro.telemetry.ServiceStats` — ``iterations`` (fleet
    iterations), ``dispatches`` (fused engine launches), ``admissions``,
    ``collections``, ``migrations`` (problems moved between devices by the
    cyclic rebalancer), ``quarantines`` (slots collected with a
    ``nonfinite`` status), ``deadlines`` (slots evicted on an expired SLO),
    ``checkpoints``, and the elastic-fleet counters ``dispatch_retries``,
    ``evacuations``, ``mesh_shrinks`` and ``mesh_regrows``.

    ``recorder`` (a :class:`repro.telemetry.Recorder`; default the no-op
    :data:`~repro.telemetry.NULL`) receives the structured event stream:
    spans around compile/dispatch/admit/collect/checkpoint, per-device
    ``service.n_live`` occupancy gauges at every executed iteration, and
    flow events for slot migrations.  Everything is recorded host-side at
    dispatch boundaries, so telemetry on/off cannot change any result bit
    (see DESIGN.md §8).

    ``checkpointer`` (a :class:`repro.service.checkpoint.ServiceCheckpointer`)
    snapshots the stacked engine state + the slot -> request map every
    ``checkpoint_every`` admission ticks; ``serve(resume=True)`` restores the
    latest snapshot and replays from it — bit-identically for slots the
    crash did not touch.  ``on_tick(it, state, slot_req)`` is a host hook
    called at every dispatch boundary (fault injection, external monitoring);
    it may return a replacement state pytree or ``None``.

    **Elastic fleet resilience** (DESIGN.md §6): every dispatch runs under a
    host-side watchdog.  A :class:`DeviceLostError` from ``fault_injector``'s
    pre-dispatch hook (see :class:`repro.service.faults.DeviceDown`) or a
    :class:`DispatchTimeout` past ``dispatch_timeout_s`` is retried up to
    ``max_dispatch_retries`` times with exponential backoff
    (``retry_backoff_s * 2**attempt``) — transient faults recover with the
    run bit-identical to a fault-free one.  When retries exhaust, the device
    is declared failed: its slots are evacuated (recovered from the newest
    service snapshot when it covers them, else their requests re-admitted
    with ``attempts``/``retried_from``/``evacuated`` provenance), the engine
    is rebuilt on the largest surviving sub-mesh dividing ``batch_slots``,
    and the fleet keeps serving.  A later admission tick regrows the mesh
    when the injector reports the device healthy again.  All detection and
    recovery happens between dispatches — no traced code changes.
    """

    def __init__(
        self,
        cfg: QuadratureConfig,
        family: Union[ParamIntegrand, str, None] = None,
        engine: Optional[BatchEngine] = None,
        mesh=None,
        devices=None,
        checkpointer=None,
        checkpoint_every: int = 0,
        on_tick: Optional[Callable] = None,
        recorder=NULL,
        fault_injector=None,
        max_dispatch_retries: int = 2,
        dispatch_timeout_s: Optional[float] = None,
        retry_backoff_s: float = 0.1,
    ):
        self.recorder = recorder
        if engine is not None:
            if mesh is not None or devices is not None:
                raise ValueError(
                    "pass mesh/devices to the BatchEngine, not alongside an "
                    "explicit engine: the engine's mesh is fixed at "
                    "construction and a conflicting argument here would be "
                    "silently ignored"
                )
            self.engine = engine
        else:
            self.engine = make_engine(
                cfg, family, mesh=mesh, devices=devices, recorder=recorder
            )
        self.cfg = self.engine.cfg
        if checkpoint_every < 0:
            raise ValueError(f"checkpoint_every must be >= 0, got {checkpoint_every}")
        if checkpoint_every and checkpointer is None:
            raise ValueError("checkpoint_every > 0 requires a checkpointer")
        if max_dispatch_retries < 0:
            raise ValueError(
                f"max_dispatch_retries must be >= 0, got {max_dispatch_retries}"
            )
        if dispatch_timeout_s is not None and dispatch_timeout_s <= 0:
            raise ValueError(
                f"dispatch_timeout_s must be positive, got {dispatch_timeout_s}"
            )
        self.checkpointer = checkpointer
        self.checkpoint_every = checkpoint_every
        self.on_tick = on_tick
        self.fault_injector = fault_injector
        self.max_dispatch_retries = max_dispatch_retries
        self.dispatch_timeout_s = dispatch_timeout_s
        self.retry_backoff_s = retry_backoff_s
        self._stats = ServiceStats()
        self._warm = False  # first-ever dispatch traces + compiles the step
        # Elastic-mesh bookkeeping.  Devices are identified by their index in
        # the engine's ORIGINAL mesh for the whole scheduler lifetime —
        # injector device ids, telemetry attrs, and the regrow target all
        # speak this namespace.  A single-device engine is not elastic
        # (nowhere to evacuate to): _all_devices stays None and a permanent
        # device loss is fatal.
        mesh = getattr(self.engine, "mesh", None)
        self._all_devices = list(mesh.devices.flat) if mesh is not None else None
        self._current_devs = list(range(self.engine.n_devices))
        self._failed: set = set()

    @property
    def last_stats(self) -> dict:
        """Dict view of the latest run's :class:`ServiceStats` (compat)."""
        return self._stats.as_dict()

    # --- elastic-mesh plumbing -----------------------------------------------

    def _healthy_mesh(self) -> list:
        """Largest sub-mesh of healthy devices whose size divides the slot
        count, as original-mesh indices in original order.

        ``batch_slots % n_devices == 0`` is the engine's contiguous-block
        placement invariant, so losing one device out of e.g. 4 with 8 slots
        shrinks to 2 devices, idling one healthy device until a regrow.
        """
        healthy = [
            gi for gi in range(len(self._all_devices)) if gi not in self._failed
        ]
        if not healthy:
            raise DeviceLostError(None, "every device in the mesh has failed")
        B = self.engine.n_slots
        m = max(k for k in range(1, len(healthy) + 1) if B % k == 0)
        return healthy[:m]

    def _rebuild_engine(self, dev_indices: list):
        """Rebuild the engine on the given original-mesh device indices.

        The compiled step/admit/release are rebuilt for the new device count
        (``redistribution.make_schedule``/``ring_perms`` are re-derived from
        it inside the engine), so the next dispatch re-traces — the warm
        flag resets and the trace shows a fresh ``service.compile`` span.
        """
        devices = [self._all_devices[i] for i in dev_indices]
        self.engine = make_engine(
            self.cfg, self.engine.family, devices=devices, recorder=self.recorder
        )
        self._current_devs = list(dev_indices)
        self._warm = False
        return self.engine

    def _attribute_fault(self, err: Exception, it: int) -> Optional[int]:
        """Best-effort mapping of a dispatch fault to an original-mesh device
        index: the error's own attribution first, else the injector's
        ``healthy`` probe over the devices in the current mesh."""
        dev = getattr(err, "device", None)
        if dev is not None:
            return int(dev)
        probe = getattr(self.fault_injector, "healthy", None)
        if probe is not None:
            for gi in self._current_devs:
                if not probe(gi, it):
                    return gi
        return None

    def serve(
        self, requests: Iterable[QuadRequest], resume: bool = False
    ) -> Iterator[QuadResult]:
        """Run the fleet to completion, yielding results as slots converge.

        ``requests`` may be any iterable (including a generator — it is only
        pulled from when a slot is free, so an unbounded stream backpressures
        naturally).  Every request yields exactly one result.

        With ``resume=True`` the latest service checkpoint is restored first:
        in-flight slots resume mid-refinement, requests the crashed run had
        already pulled are skipped from ``requests`` (the caller re-supplies
        the same stream), and requests that finished *after* the restored
        snapshot are served again — deterministically, so the duplicates are
        bit-identical to the results the crashed run already yielded.
        """
        engine = self.engine
        cfg = self.cfg
        B = engine.n_slots
        pending = iter(requests)
        exhausted = False  # the iterator signalled StopIteration
        slot_req: list[Optional[QuadRequest]] = [None] * B
        slot_admitted = np.zeros(B, np.int64)
        slot_wall = [0.0] * B  # admission wall clock, for deadline_s
        pulled_ids: set[int] = set()
        skip_ids: set[int] = set()
        # Device-loss bookkeeping: requests bumped off a failed device wait
        # in retry_queue (served before the pending iterator, preserving
        # admission-order determinism), and the evac_* maps carry their
        # provenance into the eventual QuadResult.
        retry_queue: deque = deque()
        evac_attempts: dict = {}  # req_id -> extra admissions consumed
        evac_from: dict = {}  # req_id -> status that triggered the retry
        evac_kind: dict = {}  # req_id -> "snapshot" | "readmit"
        rec = self.recorder
        stats = ServiceStats()
        self._stats = stats

        def bump(counter: str, n: int = 1) -> None:
            # one typed schema + one event stream: every host-loop counter
            # bump lands in ServiceStats AND (when enabled) the recorder
            stats.add(counter, n)
            rec.count(f"service.{counter}", n)

        state = engine.init()
        it = 0
        ticks = 0  # admission passes completed (checkpoint cadence unit)
        rec.event(
            "service.start",
            backend=engine.backend,
            slots=B,
            devices=engine.n_devices,
            sync_every=cfg.sync_every,
            admit_every=cfg.admit_every,
            resume=resume,
        )

        if resume:
            if self.checkpointer is None:
                raise ValueError("resume=True requires a checkpointer")
            state, meta = self.checkpointer.restore(engine)
            it = int(meta["it"])
            ticks = int(meta["ticks"])
            stats.merge(ServiceStats.from_dict(meta["stats"]))
            pulled_ids = set(meta["pulled_ids"])
            skip_ids = set(pulled_ids)
            for entry in meta["slots"]:
                slot = int(entry["slot"])
                slot_req[slot] = decode_request(entry["req"], engine.theta_template)
                slot_admitted[slot] = int(entry["admitted_at"])
                slot_wall[slot] = time.monotonic()  # wall deadlines restart

        def pull() -> Optional[QuadRequest]:
            # Requests are pulled ONLY here, from admission passes — never
            # speculatively — so a generator that derives its next request
            # from results yielded so far sees exactly the per-iteration
            # loop's pull points, and an unbounded stream backpressures on
            # slot availability.  On resume, requests the crashed run had
            # already pulled are skipped so the replayed stream lines up
            # with the restored slot map.  Evacuated requests (device loss)
            # re-enter here, ahead of the never-admitted stream.
            nonlocal exhausted
            if retry_queue:
                return retry_queue.popleft()
            if exhausted:
                return None
            req = next(pending, None)
            while req is not None and req.req_id in skip_ids:
                req = next(pending, None)
            if req is None:
                exhausted = True
            else:
                pulled_ids.add(req.req_id)
            return req

        def admission_order() -> list[int]:
            """Free slots, least-loaded device first (plain slot order on one
            device, which is exactly the legacy single-device fill order)."""
            free = [s for s in range(B) if slot_req[s] is None]
            if engine.n_devices == 1:
                return free
            per_dev = engine.slots_per_device
            load = [0] * engine.n_devices
            for s in range(B):
                if slot_req[s] is not None:
                    load[s // per_dev] += 1
            # admitting onto a device raises its load for the next pick, so
            # a burst of admissions round-robins across the drained devices
            order: list[int] = []
            free_per_dev = [[s for s in free if s // per_dev == d] for d in range(engine.n_devices)]
            for _ in free:
                dev = min(
                    (d for d in range(engine.n_devices) if free_per_dev[d]),
                    key=lambda d: (load[d], d),
                )
                order.append(free_per_dev[dev].pop(0))
                load[dev] += 1
            return order

        def admit_free_slots(state: BatchState) -> BatchState:
            with rec.span("service.admit", it=it) as sp:
                n_admitted = 0
                for slot in admission_order():
                    req = pull()
                    if req is None:
                        break
                    state = engine.admit(
                        state, slot, req.theta, req.rel_tol, req.abs_tol
                    )
                    slot_req[slot] = req
                    slot_admitted[slot] = it
                    slot_wall[slot] = time.monotonic()
                    n_admitted += 1
                    bump("admissions")
                    rec.event(
                        "service.admission",
                        lane=slot // engine.slots_per_device,
                        req_id=req.req_id,
                        slot=slot,
                        it=it,
                    )
                sp["admitted"] = n_admitted
            return state

        def admission_tick(state: BatchState) -> BatchState:
            """One admission pass + the checkpoint cadence hanging off it.

            The snapshot is taken *after* the admissions so a resumed run
            continues from a tick boundary: the next host decision after
            restore is the next dispatch, exactly as in the original run.
            Mesh regrowth also hangs off the tick: a failed device that the
            injector reports healthy again rejoins here, before the
            admissions, so fresh admissions spread across the regrown mesh.
            """
            nonlocal engine, ticks
            probe = getattr(self.fault_injector, "healthy", None)
            if self._failed and probe is not None:
                restored = [gi for gi in sorted(self._failed) if probe(gi, it)]
                if restored:
                    self._failed.difference_update(restored)
                    target = self._healthy_mesh()
                    if len(target) > engine.n_devices:
                        with rec.span(
                            "service.mesh_regrow", it=it, devices=len(target)
                        ):
                            host = jax.tree.map(np.asarray, jax.device_get(state))
                            engine = self._rebuild_engine(target)
                            state = engine.place(host)
                        bump("mesh_regrows")
                        rec.event(
                            "service.mesh_regrow",
                            it=it,
                            devices=len(target),
                            restored=restored,
                        )
            state = admit_free_slots(state)
            ticks += 1
            if (
                self.checkpointer is not None
                and self.checkpoint_every > 0
                and ticks % self.checkpoint_every == 0
            ):
                meta = {
                    "it": it,
                    "ticks": ticks,
                    "stats": stats.as_dict(),
                    "pulled_ids": sorted(pulled_ids),
                    "slots": [
                        {
                            "slot": s,
                            "req": encode_request(slot_req[s]),
                            "admitted_at": int(slot_admitted[s]),
                        }
                        for s in range(B)
                        if slot_req[s] is not None
                    ],
                }
                with rec.span("service.checkpoint", it=it, ticks=ticks):
                    self.checkpointer.save(it, state, meta)
                bump("checkpoints")
            return state

        def apply_moves(rows: np.ndarray) -> None:
            """Replay one iteration's device-side migrations onto the host
            map.  Within a round sources (live slots) and destinations
            (previously free slots) are disjoint, so copy-then-clear is
            exact."""
            valid = [(int(s), int(d)) for s, d in rows if s >= 0]
            if not valid:
                return
            snapshot_req = list(slot_req)
            snapshot_adm = slot_admitted.copy()
            snapshot_wall = list(slot_wall)
            for src, dst in valid:
                assert snapshot_req[src] is not None, (src, dst)
                slot_req[dst] = snapshot_req[src]
                slot_admitted[dst] = snapshot_adm[src]
                slot_wall[dst] = snapshot_wall[src]
                slot_req[src] = None
                if rec.enabled:
                    rec.flow(
                        "service.migrate",
                        src // engine.slots_per_device,
                        dst // engine.slots_per_device,
                        req_id=snapshot_req[src].req_id,
                        src_slot=src,
                        dst_slot=dst,
                        it=it,
                    )
            bump("migrations", len(valid))

        def evacuate_and_shrink(state: BatchState, dev: int) -> BatchState:
            """Recover the failed device's slots and rebuild on the survivors.

            Evacuation ordering (DESIGN.md §6): snapshot-covered slots are
            rewound to the newest readable service snapshot (their replay is
            deterministic, so final values stay bit-identical); uncovered
            slots lose their progress and their requests re-enter the queue
            with ``attempts``/``retried_from``/``evacuated`` provenance.
            Surviving devices' slots are carried over untouched — their
            trajectories are placement-independent, so shrink cannot change
            their bits.
            """
            nonlocal engine
            if self._all_devices is None or engine.n_devices <= 1:
                raise DeviceLostError(
                    dev,
                    f"device {dev} lost permanently with no surviving "
                    "sub-mesh to evacuate to",
                )
            per_dev = engine.slots_per_device
            local = self._current_devs.index(dev)
            self._failed.add(dev)
            rec.event("service.device_lost", device=dev, it=it)
            target = self._healthy_mesh()
            new_per = B // len(target)
            with rec.span("service.evacuate", it=it, device=dev) as sp:
                # Host copy of the fleet state.  The fault fired before any
                # launch took the state (pre-dispatch hook, or a watchdog
                # timeout that _Attempt caught before the dispatch), so the
                # buffers were never donated and remain readable.  A real
                # device loss would lose the failed shard's rows — exactly
                # the rows rewritten or released below; surviving rows are
                # all that is trusted.
                host = jax.tree.map(np.array, jax.device_get(state))
                snap_state = snap_meta = None
                if self.checkpointer is not None:
                    try:
                        snap_state, snap_meta, _ = self.checkpointer.restore_host(
                            host
                        )
                    except FileNotFoundError:
                        pass
                snap_slots = {}
                if snap_meta is not None:
                    snap_state = jax.tree.map(np.asarray, snap_state)
                    snap_slots = {
                        int(e["slot"]): int(e["req"]["req_id"])
                        for e in snap_meta["slots"]
                    }
                recovered = readmitted = 0
                for s in range(local * per_dev, (local + 1) * per_dev):
                    req = slot_req[s]
                    if req is None:
                        continue
                    if snap_state is not None and snap_slots.get(s) == req.req_id:
                        # rewind the slot to the snapshot row-for-row
                        # (occupied/done flags included); the deterministic
                        # replay re-derives the lost refinement
                        jax.tree.map(lambda h, v: h.__setitem__(s, v[s]), host, snap_state)
                        evac_kind[req.req_id] = "snapshot"
                        slot_wall[s] = time.monotonic()  # wall SLO restarts
                        kind = "snapshot"
                        recovered += 1
                    else:
                        host.occupied[s] = False
                        host.done[s] = False
                        retry_queue.append(req)
                        evac_attempts[req.req_id] = evac_attempts.get(req.req_id, 0) + 1
                        evac_from[req.req_id] = "device_lost"
                        evac_kind[req.req_id] = "readmit"
                        slot_req[s] = None
                        kind = "readmit"
                        readmitted += 1
                    bump("evacuations")
                    if rec.enabled:
                        # lanes are original-mesh device indices: src is the
                        # failed device, dst the slot row's new owner
                        # "via", not "kind": attrs merge into the event
                        # envelope, whose own "kind" key is the event type
                        rec.flow(
                            "service.evacuate",
                            dev,
                            target[s // new_per],
                            req_id=req.req_id,
                            slot=s,
                            it=it,
                            via=kind,
                        )
                sp["recovered"] = recovered
                sp["readmitted"] = readmitted
            with rec.span(
                "service.mesh_shrink", it=it, devices=len(target), failed=dev
            ):
                engine = self._rebuild_engine(target)
                state = engine.place(host)
            bump("mesh_shrinks")
            rec.event(
                "service.mesh_shrink",
                it=it,
                devices=len(target),
                failed=sorted(self._failed),
            )
            return state

        # Dispatch-latency views (DESIGN.md §9): per-dispatch wall time and
        # the host-side queue wait between dispatches (admission, collection,
        # checkpointing, result consumption — everything the devices idle
        # through).  Recorded strictly at dispatch boundaries on the
        # recorder's clock, so recorder-off runs are bit-identical.
        last_dispatch_end: Optional[float] = None
        if not resume:
            # on resume the snapshot was taken at a tick boundary, right
            # after its admissions: the next host decision is the dispatch
            state = admission_tick(state)
        while any(r is not None for r in slot_req) or retry_queue:
            if not any(r is not None for r in slot_req):
                # an evacuation emptied the fleet with re-admissions
                # pending: refill before dispatching
                state = admission_tick(state)
                continue
            # A dispatch may not run past the next admit tick while an
            # admission may be pending (free slot + a queue not yet known to
            # be exhausted) — the tick is a host decision the device cannot
            # replay.  Whether the queue actually still holds a request is
            # only discovered AT the tick, preserving the unfused loop's
            # exact pull timing; once the iterator is exhausted, full-length
            # dispatches resume for the drain phase.
            max_steps = cfg.sync_every
            if (not exhausted or retry_queue) and any(r is None for r in slot_req):
                max_steps = min(max_steps, cfg.admit_every - it % cfg.admit_every)
            it0 = it

            def attempt_dispatch(attempt):
                # the injector hook fires first: an injected loss surfaces
                # before the engine consumes (donates) the state buffers,
                # so a retry or an evacuation reads intact state
                if self.fault_injector is not None:
                    self.fault_injector.pre_dispatch(it, tuple(self._current_devs))
                if not attempt.take():
                    return None  # the watchdog gave up on this attempt
                new_state, ms, executed, moved = engine.run(state, max_steps, it)
                ms, executed, moved = jax.device_get((ms, executed, moved))
                return new_state, ms, executed, moved

            # the first-ever dispatch traces + compiles the fused step, so
            # its span is the trace's "compile" lane entry
            evacuated = False
            if rec.enabled:
                t_dispatch0 = rec.clock()
                if last_dispatch_end is not None:
                    rec.observe(
                        "service.queue_wait_s",
                        t_dispatch0 - last_dispatch_end,
                        it=it,
                    )
            with rec.span(
                "service.dispatch" if self._warm else "service.compile",
                it=it,
                max_steps=max_steps,
            ) as sp:
                attempt = 0
                while True:
                    try:
                        state, ms, executed, moved = _call_with_timeout(
                            attempt_dispatch, self.dispatch_timeout_s
                        )
                        k = int(np.sum(executed))
                        break
                    except (DeviceLostError, DispatchTimeout) as err:
                        if isinstance(err, DispatchTimeout) and not err.retryable:
                            raise  # the state went with the wedged launch
                        dev = self._attribute_fault(err, it)
                        rec.event(
                            "service.dispatch_fault",
                            it=it,
                            device=dev,
                            attempt=attempt,
                            error=type(err).__name__,
                        )
                        if attempt < self.max_dispatch_retries:
                            # transient until proven permanent: bounded
                            # retries with exponential backoff
                            attempt += 1
                            bump("dispatch_retries")
                            if self.retry_backoff_s > 0:
                                time.sleep(
                                    self.retry_backoff_s * 2 ** (attempt - 1)
                                )
                            continue
                        if dev is None:
                            raise  # unattributable: nothing to evacuate
                        state = evacuate_and_shrink(state, dev)
                        evacuated = True
                        k = 0
                        break
                sp["executed"] = k
            if evacuated:
                # no iteration executed: loop back and dispatch the same
                # ``it`` on the shrunken mesh (re-admissions wait for their
                # admit tick, exactly like any other queued request).  No
                # wall-time sample either — the next successful dispatch's
                # queue wait absorbs the whole recovery gap, which is the
                # honest account of where the time went.
                continue
            if rec.enabled:
                last_dispatch_end = rec.clock()
                rec.observe(
                    "service.dispatch_wall_s",
                    last_dispatch_end - t_dispatch0,
                    it=it0,
                )
            self._warm = True
            assert k >= 1, "fused dispatch executed no iterations"
            bump("dispatches")
            bump("iterations", k)
            if rec.enabled:
                # Per-device live-slot occupancy at every executed iteration
                # (the Fig. 4b input) — derived purely from the read-back
                # metrics, after the dispatch returned: nothing here can
                # perturb the device computation.
                occ = np.asarray(ms["occupied"][:k]).reshape(
                    k, engine.n_devices, engine.slots_per_device
                )
                n_live = occ.sum(axis=2)
                for t in range(k):
                    for dev in range(engine.n_devices):
                        rec.gauge(
                            "service.n_live",
                            int(n_live[t, dev]),
                            lane=dev,
                            it=it0 + t + 1,
                        )
                if "window" in ms:  # eval-window rung (cubature engine)
                    rec.gauge(
                        "service.window",
                        int(np.max(ms["window"][k - 1])),
                        it=it0 + k,
                    )
            for t in range(k - 1):
                it += 1
                apply_moves(moved[t])
            it += 1
            done = ms["done"][k - 1]
            occupied = ms["occupied"][k - 1]
            finished = [
                (slot_req[s].req_id, s)
                for s in range(B)
                if done[s] and occupied[s] and slot_req[s] is not None
            ]
            # req_id order: deterministic across device counts (collection
            # within one iteration has no inherent slot order anyway).
            # Results are built inside the collect span and yielded after
            # it closes — a span held open across a generator yield would
            # measure the consumer, not the collection.
            collected: list[QuadResult] = []
            if finished:
                with rec.span("service.collect", it=it, n=len(finished)):
                    for req_id, slot in sorted(finished):
                        status = engine.status_of(
                            bool(ms["converged"][k - 1][slot]),
                            int(ms["n_active"][k - 1][slot]),
                            int(ms["it"][k - 1][slot]),
                            bool(ms["overflowed"][k - 1][slot]),
                            bool(ms["nonfinite"][k - 1][slot]),
                        )
                        bump("collections")
                        if status == "nonfinite":
                            bump("quarantines")
                        rec.event(
                            "service.collected",
                            lane=slot // engine.slots_per_device,
                            req_id=req_id,
                            slot=slot,
                            status=status,
                            it=it,
                        )
                        collected.append(
                            QuadResult(
                                req_id=req_id,
                                integral=float(ms["integral"][k - 1][slot]),
                                error=float(ms["error"][k - 1][slot]),
                                status=status,
                                iterations=int(ms["it"][k - 1][slot]),
                                n_evals=float(ms["n_evals"][k - 1][slot]),
                                admitted_at=int(slot_admitted[slot]),
                                finished_at=it,
                                backend=engine.backend,
                                attempts=1 + evac_attempts.pop(req_id, 0),
                                retried_from=evac_from.pop(req_id, None),
                                evacuated=evac_kind.pop(req_id, None),
                            )
                        )
            for res in collected:
                yield res
            # migrations of the final executed iteration happened *after* its
            # metrics snapshot (and done slots never migrate), so the map
            # update follows collection
            apply_moves(moved[k - 1])
            for _, slot in finished:
                state = engine.release(state, slot)
                slot_req[slot] = None
            # Deadline sweep: SLOs are enforced here, at the dispatch
            # boundary (the host cannot observe a slot mid-dispatch).  The
            # evicted slot's row-(k-1) metrics are its best-effort partial
            # estimate; releasing it only clears this slot's masks, so the
            # other slots' trajectories are untouched bit-for-bit.
            now = time.monotonic()
            for slot in range(B):
                req = slot_req[slot]
                if req is None or (req.deadline_s is None and req.max_evals is None):
                    continue
                over_wall = (
                    req.deadline_s is not None
                    and now - slot_wall[slot] > req.deadline_s
                )
                over_evals = (
                    req.max_evals is not None
                    and float(ms["n_evals"][k - 1][slot]) > req.max_evals
                )
                if not (over_wall or over_evals):
                    continue
                bump("deadlines")
                rec.event(
                    "service.deadline",
                    lane=slot // engine.slots_per_device,
                    req_id=req.req_id,
                    slot=slot,
                    it=it,
                    over_wall=over_wall,
                    over_evals=over_evals,
                )
                yield QuadResult(
                    req_id=req.req_id,
                    integral=float(ms["integral"][k - 1][slot]),
                    error=float(ms["error"][k - 1][slot]),
                    status="deadline",
                    iterations=int(ms["it"][k - 1][slot]),
                    n_evals=float(ms["n_evals"][k - 1][slot]),
                    admitted_at=int(slot_admitted[slot]),
                    finished_at=it,
                    backend=engine.backend,
                    attempts=1 + evac_attempts.pop(req.req_id, 0),
                    retried_from=evac_from.pop(req.req_id, None),
                    evacuated=evac_kind.pop(req.req_id, None),
                )
                state = engine.release(state, slot)
                slot_req[slot] = None
            # Admit on the configured cadence — but never let the fleet go
            # idle with work still queued: if every slot just drained we
            # admit immediately rather than spinning (or exiting) until the
            # next admit tick.
            if it % cfg.admit_every == 0 or all(r is None for r in slot_req):
                state = admission_tick(state)
            if self.on_tick is not None:
                replacement = self.on_tick(it, state, list(slot_req))
                if replacement is not None:
                    state = replacement
        # drain: nothing in flight, so nothing may remain unadmitted
        leftover = pull()
        if leftover is not None:  # pragma: no cover - invariant guard
            raise RuntimeError(
                f"scheduler exited with queued requests (req_id={leftover.req_id})"
            )
        rec.event("service.drain", it=it, **stats.as_dict())
        rec.flush()
