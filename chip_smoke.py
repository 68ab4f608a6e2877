#!/usr/bin/env python3
"""Smoke run of the quadrature system on a TPU.

Drives the main paths once, through the entry points a user calls, at state
sizes a user would call real, and checks every answer against its analytic
exact value.  All phases run in this one process, in order; any failure
exits non-zero.

1. device   -- the default backend must be a TPU (no CPU fallback);
2. integral -- ``repro.core.integrate`` (what ``python -m
   repro.launch.integrate`` runs): f4, d=5, rel_tol 1e-7, capacity 2^20,
   float64; converged, true relative error within rel_tol;
3. service  -- ``repro.service.serve`` (what ``launch/serve_quad.py`` runs):
   genz_gaussian, d=5, 64 slots x 2^16 regions, 64 requests with thetas
   drawn from ``--seed``, rel_tol 1e-5; every request converged within
   its tolerance;
4. VEGAS    -- genz_gaussian, d=15, 2^20 samples per iteration; converged,
   |estimate - exact| < 5 reported sigma;
5. kernel   -- the GM Pallas kernel through Mosaic: ``kernels.ops`` in
   float32 at d=5 and d=8 on 2^16 regions against ``kernels.ref``, and one
   ``integrate`` with ``use_kernel=True, dtype="float32"`` (genz_product_peak,
   d=5, rel_tol 1e-4).

``--four-chips`` runs only the multi-chip paths and what they are compared
with: the service sharded over 4 chips; ``integrate_distributed`` with ring
redistribution on 4 chips (f4, d=5, rel_tol 1e-7, 2^20 regions per chip)
against the exact value and a 1-chip ``integrate``; and non-zero bytes in
use on every chip while each runs.

Each phase prints its wall time, compile count and seconds, persistent
cache hits and the device's peak bytes in use.  The last line of stdout is
one JSON object: ``{"ok": true, "device": {...}}``.

Run from the repository root:
  python chip_smoke.py              # one chip
  python chip_smoke.py --four-chips # four chips
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))


class _Compiles:
    """Counts backend compiles (cache loads included) and cache hits.

    The drivers compile the next window rungs in background threads, so
    compile seconds are summed over threads and can exceed a phase's wall
    time."""

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self._lock = threading.Lock()

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.count += 1
                self.seconds += duration

    def on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1


def _fail(phase: str, msg: str) -> None:
    raise SystemExit(f"{phase} FAILED: {msg}")


@contextlib.contextmanager
def _phase(name: str, compiles: _Compiles, devices):
    """Print wall time, compiles, cache hits and peak device bytes of a phase."""
    c0, s0, h0 = compiles.count, compiles.seconds, compiles.cache_hits
    t0 = time.perf_counter()
    info: dict = {}
    yield info
    wall = time.perf_counter() - t0
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    fields = " ".join(f"{k}={v}" for k, v in info.items())
    print(
        f"{name}: {fields} wall_s={wall} compiles={compiles.count - c0} "
        f"compile_s={compiles.seconds - s0} cache_hits={compiles.cache_hits - h0} "
        f"peak_bytes_in_use={peaks}",
        flush=True,
    )


class _BytesInUse:
    """Telemetry sink: samples ``bytes_in_use`` on each device at every event
    (events fire between dispatches, while the run's state is live)."""

    def __init__(self, devices) -> None:
        self.devices = devices
        self.max_bytes = [0] * len(devices)

    def emit(self, event) -> None:
        for i, d in enumerate(self.devices):
            self.max_bytes[i] = max(
                self.max_bytes[i], d.memory_stats()["bytes_in_use"]
            )


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-300)


def phase_integral(compiles, devices) -> None:
    from repro.core import QuadratureConfig, integrate
    from repro.core.integrands import get

    cfg = QuadratureConfig(d=5, integrand="f4", rel_tol=1e-7, capacity=1 << 20)
    with _phase("phase2 integrate f4 d=5 capacity=2^20 float64", compiles, devices) as info:
        res = integrate(cfg)
        rel = _rel(res.integral, get("f4").exact(5))
        info.update(status=res.status, iterations=res.iterations,
                    evals=res.n_evals, integral=res.integral,
                    true_rel_err=rel, rel_tol=cfg.rel_tol)
    if res.status != "converged" or not rel <= cfg.rel_tol:
        _fail("phase2", f"status={res.status} true_rel_err={rel} > {cfg.rel_tol}")


def _service(cfg, n_requests: int, seed: int, phase: str, compiles, devices,
             recorder=None):
    import numpy as np

    from repro.core.integrands import get_param
    from repro.service import QuadRequest, serve

    family = get_param("genz_gaussian")
    rng = np.random.default_rng(seed)
    thetas = [family.sample_theta(cfg.d, rng) for _ in range(n_requests)]
    requests = [QuadRequest(req_id=i, theta=t) for i, t in enumerate(thetas)]
    kwargs = {} if recorder is None else {"recorder": recorder}
    with _phase(phase, compiles, devices) as info:
        results = list(serve(cfg, requests, family, **kwargs))
        rels = {r.req_id: _rel(r.integral, family.exact(cfg.d, thetas[r.req_id]))
                for r in results}
        converged = sum(r.status == "converged" for r in results)
        within = sum(rels[r.req_id] <= cfg.rel_tol for r in results)
        info.update(completed=f"{len(results)}/{n_requests}", converged=converged,
                    within_tol=within, worst_true_rel_err=max(rels.values()),
                    rel_tol=cfg.rel_tol)
    if not (len(results) == n_requests == converged == within):
        bad = [r.summary() for r in results
               if r.status != "converged" or rels[r.req_id] > cfg.rel_tol][:5]
        _fail(phase.split()[0], f"{len(results)} results, {converged} converged, "
              f"{within} within tolerance; first bad: {bad}")


def _service_cfg(**kw):
    from repro.core import QuadratureConfig

    # rel_tol 1e-5: at 1e-6 some seed-0 thetas need more than 2^16 regions
    # per slot and are (correctly) evicted with status "capacity"
    return QuadratureConfig(d=5, integrand="genz_gaussian", rel_tol=1e-5,
                            capacity=1 << 16, batch_slots=64, max_iters=300, **kw)


def phase_service(compiles, devices, seed: int) -> None:
    cfg = _service_cfg()
    _service(cfg, 64, seed, "phase3 serve genz_gaussian d=5 slots=64 capacity=2^16",
             compiles, devices)


def phase_vegas(compiles, devices, seed: int) -> None:
    import numpy as np

    from repro.core import QuadratureConfig
    from repro.core.integrands import bind, get_param
    from repro.mc import integrate_vegas

    family = get_param("genz_gaussian")
    bound = bind(family, family.sample_theta(15, np.random.default_rng(seed)))
    cfg = QuadratureConfig(d=15, integrand="genz_gaussian", backend="vegas",
                           rel_tol=1e-3, mc_samples=1 << 20)
    with _phase("phase4 vegas genz_gaussian d=15 samples=2^20", compiles, devices) as info:
        res = integrate_vegas(cfg, bound.fn)
        exact = bound.exact(15)
        sigmas = abs(res.integral - exact) / res.error
        info.update(status=res.status, iterations=res.iterations,
                    integral=res.integral, exact=exact, reported_err=res.error,
                    err_over_sigma=sigmas)
    if res.status != "converged" or not sigmas < 5.0:
        _fail("phase4", f"status={res.status} |I - exact| = {sigmas} sigma")


KERNEL_TOL = 1e-4  # f32 kernel vs f32 reference, relative to the largest value


def phase_kernel(compiles, devices, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import QuadratureConfig, integrate
    from repro.core.integrands import get
    from repro.kernels import ops
    from repro.kernels.ref import genz_malik_eval_soa_ref

    interpret = ops.resolve_interpret(None)
    if interpret:
        _fail("phase5", "interpret resolved to True on the chip")
    f = get("f4").fn
    rng = np.random.default_rng(seed)
    n = 1 << 16
    for d in (5, 8):
        with _phase(f"phase5 gm kernel f32 d={d} regions=2^16", compiles, devices) as info:
            c = jnp.asarray(rng.uniform(0.1, 0.9, (n, d)), jnp.float32)
            h = jnp.asarray(rng.uniform(0.01, 0.1, (n, d)), jnp.float32)
            kernel = jax.jit(lambda c, h: ops.genz_malik_eval(f, c, h)[:3])
            compiled = kernel.lower(c, h).compile()
            mosaic = "tpu_custom_call" in compiled.as_text()
            ref = jax.jit(lambda c, h: genz_malik_eval_soa_ref(f, c.T, h.T)[:3])
            gaps = [float(jnp.max(jnp.abs(k - r)) / jnp.max(jnp.abs(r)))
                    for k, r in zip(compiled(c, h), ref(c, h))]
            info.update(interpret=interpret, tpu_custom_call=mosaic,
                        max_gap=max(gaps), tol=KERNEL_TOL)
        if not mosaic or not max(gaps) <= KERNEL_TOL:
            _fail("phase5", f"d={d} tpu_custom_call={mosaic} max_gap={max(gaps)}")

    # An integrand whose integral is O(1e5): in float32 the classifier's
    # round-off floor (50 eps x region volume, absolute) exceeds f4's whole
    # integral (1.8e-6), so f4 cannot be certified at any useful tolerance.
    spec = "genz_product_peak:5,5,5,5,5:0.5,0.5,0.5,0.5,0.5"
    cfg = QuadratureConfig(d=5, integrand=spec, rel_tol=1e-4, capacity=1 << 16,
                           use_kernel=True, dtype="float32")
    with _phase("phase5 integrate use_kernel genz_product_peak d=5 float32",
                compiles, devices) as info:
        res = integrate(cfg)
        rel = _rel(res.integral, get(spec).exact(5))
        info.update(status=res.status, iterations=res.iterations,
                    integral=res.integral, true_rel_err=rel, rel_tol=cfg.rel_tol)
    if res.status != "converged" or not rel <= cfg.rel_tol:
        _fail("phase5", f"status={res.status} true_rel_err={rel} > {cfg.rel_tol}")


def four_chips(compiles, devices, seed: int) -> None:
    from repro.core import QuadratureConfig, integrate
    from repro.core.distributed import integrate_distributed
    from repro.core.integrands import get
    from repro.telemetry import Recorder

    # the service first: rungs its fleet never reaches, compiled ahead,
    # finish while the later phases run
    svc = _service_cfg(service_devices=4)
    sink = _BytesInUse(devices)
    _service(svc, 64, seed, "four_chips serve genz_gaussian d=5 slots=64 devices=4",
             compiles, devices, recorder=Recorder((sink,)))
    print(f"four_chips serve bytes_in_use_max={sink.max_bytes}", flush=True)
    if not all(b > 0 for b in sink.max_bytes):
        _fail("four_chips", f"service bytes_in_use per chip {sink.max_bytes}")

    cfg = QuadratureConfig(d=5, integrand="f4", rel_tol=1e-7, capacity=1 << 20,
                           redistribution="ring")
    exact = get("f4").exact(5)
    sink = _BytesInUse(devices)
    with _phase("four_chips integrate_distributed f4 d=5 capacity=2^20/chip ring",
                compiles, devices) as info:
        res = integrate_distributed(cfg, devices=devices, recorder=Recorder((sink,)))
        rel = _rel(res.integral, exact)
        info.update(devices=res.n_devices, status=res.status,
                    iterations=res.iterations, integral=res.integral,
                    true_rel_err=rel, mean_imbalance=res.mean_imbalance(),
                    evals_per_device=list(res.evals_per_device),
                    bytes_in_use_max=sink.max_bytes)
    if res.status != "converged" or not rel <= cfg.rel_tol:
        _fail("four_chips", f"distributed status={res.status} true_rel_err={rel}")
    if not all(b > 0 for b in sink.max_bytes):
        _fail("four_chips", f"distributed bytes_in_use per chip {sink.max_bytes}")

    with _phase("four_chips integrate f4 d=5 capacity=2^20 on 1 chip",
                compiles, devices) as info:
        one = integrate(cfg)
        rel1 = _rel(one.integral, exact)
        info.update(status=one.status, iterations=one.iterations,
                    integral=one.integral, true_rel_err=rel1,
                    rel_diff_vs_4_chips=_rel(res.integral, one.integral))
    if one.status != "converged" or not rel1 <= cfg.rel_tol:
        _fail("four_chips", f"1-chip status={one.status} true_rel_err={rel1}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip paths and their comparisons")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    want = 4 if args.four_chips else 1
    visible = jax.devices()
    dev = visible[0]
    print(f"phase1 device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(visible)}", flush=True)
    if dev.platform != "tpu":
        _fail("phase1", f"no TPU: JAX computes on {dev.platform!r}")
    if len(visible) < want:
        _fail("phase1", f"{want} chips needed, {len(visible)} visible")
    devices = visible[:want]

    jax.config.update("jax_enable_x64", True)
    from repro import compile_cache

    cache_dir = compile_cache.enable()
    print(f"compile cache: dir={cache_dir} "
          f"enabled={bool(jax.config.jax_enable_compilation_cache)}", flush=True)
    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles.on_duration)
    jax.monitoring.register_event_listener(compiles.on_event)

    if args.four_chips:
        four_chips(compiles, devices, args.seed)
    else:
        phase_integral(compiles, devices)
        phase_service(compiles, devices, args.seed)
        phase_vegas(compiles, devices, args.seed)
        phase_kernel(compiles, devices, args.seed)
    print(f"total: compiles={compiles.count} compile_s={compiles.seconds} "
          f"cache_hits={compiles.cache_hits}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(visible)}}))


if __name__ == "__main__":
    main()
