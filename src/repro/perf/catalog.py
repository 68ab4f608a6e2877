"""Kernel cost catalog: lower the real compiled programs, predict, measure.

For each kernel the repo actually dispatches — the GM rule evaluation at
each eval-window rung, the windowed advance at each advance rung, the VEGAS
iterate, and the fused sharded-service dispatch — this module:

1. builds a representative input (a region store with the window full of
   live regions, a warmed VEGAS state, an admitted fleet),
2. lowers and compiles the *same jitted function the drivers run* and reads
   XLA's ``cost_analysis()`` FLOPs / bytes-accessed plus
   ``memory_analysis()`` buffer sizes,
3. times the compiled executable (best-of-``reps`` wall clock), and
4. predicts a roofline bound from a machine file
   (:mod:`repro.perf.machine`): ``predicted_s = max(flops / peak_flops,
   bytes / mem_bw)`` and reports ``roofline_frac = predicted_s /
   measured_s`` — the fraction of the machine's roofline the kernel
   actually achieves (1.0 = running at the bound).

**Scan-body caveat** (same issue ``benchmarks/roofline.py`` documents for
the LM stack): ``HloCostAnalysis`` counts a ``lax.scan``/``while`` body
ONCE regardless of trip count.  The fused service dispatch scans
``sync_every`` iterations per call, so its raw HLO counts are scaled by
``scan_trips = sync_every`` before predicting; every other cataloged
kernel is scan-free at the top level (``scan_trips = 1``).  The VEGAS
iterate's internal ``_ordered_sum`` scan runs over already-reduced shard
partials — negligible against the per-sample work, so no correction is
applied (recorded trip count 1).

Timing calls the AOT-compiled executable directly (the lowered object from
step 2), so the measured program is *exactly* the costed program — not a
re-traced sibling.  The drivers donate their state buffers; the catalog
therefore threads each call's output state back in as the next call's
input, which keeps repeated timing valid under donation.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence

from repro.perf.machine import DEFAULT_PATH as MACHINE_PATH, resolve_machine

_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)

#: default catalog location, next to the machine file it was predicted from
DEFAULT_PATH = os.path.join(_REPO, "results", "perf", "kernel_catalog.json")

#: kernel names the catalog can produce (report + tests key off these)
KERNELS = ("gm_eval", "advance", "vegas_iterate", "service_dispatch")


def _cost_of(compiled) -> Dict[str, float]:
    """FLOPs / bytes / buffer sizes of a compiled executable."""
    cost = compiled.cost_analysis() or {}
    out = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "transcendentals": float(cost.get("transcendentals", 0.0)),
    }
    try:
        mem = compiled.memory_analysis()
        out["arg_bytes"] = float(mem.argument_size_in_bytes)
        out["out_bytes"] = float(mem.output_size_in_bytes)
        out["temp_bytes"] = float(mem.temp_size_in_bytes)
    except Exception:  # noqa: BLE001 — memory stats are best-effort
        pass
    return out


def _time_compiled(compiled, args: tuple, reps: int, state_index: Optional[int]) -> float:
    """Best-of-``reps`` wall time of one executable call.

    When ``state_index`` is given, output element ``state_index`` (or the
    whole output, for state->state kernels returning a single value) is fed
    back as the first argument of the next call — repeated timing stays
    valid when the platform donates the state buffers.
    """
    import jax

    def feed(out, cur_args):
        if state_index is None:
            return cur_args
        new_state = out if not isinstance(out, tuple) else out[state_index]
        return (new_state,) + cur_args[1:]

    out = compiled(*args)  # first dispatch (executable is already compiled)
    jax.block_until_ready(out)
    args = feed(out, args)
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        out = compiled(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
        args = feed(out, args)
    return best


def _entry(
    kernel: str,
    compiled,
    args: tuple,
    *,
    d: int,
    rung: Optional[int],
    reps: int,
    scan_trips: int = 1,
    state_index: Optional[int] = 0,
    **extra: Any,
) -> Dict[str, Any]:
    cost = _cost_of(compiled)
    measured = _time_compiled(compiled, args, reps, state_index)
    return {
        "kernel": kernel,
        "d": d,
        "rung": rung,
        "scan_trips": scan_trips,
        "measured_s": measured,
        **cost,
        **extra,
    }


def predict(entry: Dict[str, Any], machine: Dict[str, Any]) -> Dict[str, Any]:
    """Attach roofline predictions from ``machine`` to a measured entry.

    Returns a new dict; ``entry`` is not mutated.  ``flops_total`` /
    ``bytes_total`` are the HLO counts scaled by the scan trip count (see
    module docstring); ``roofline_frac`` is predicted/measured wall time.
    """
    trips = int(entry.get("scan_trips", 1))
    flops = entry["flops"] * trips
    byts = entry["bytes"] * trips
    compute_s = flops / machine["peak_flops"]
    memory_s = byts / machine["mem_bw"]
    predicted = max(compute_s, memory_s)
    measured = entry["measured_s"]
    return {
        **entry,
        "flops_total": flops,
        "bytes_total": byts,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "predicted_s": predicted,
        "dominant": "compute" if compute_s >= memory_s else "memory",
        "roofline_frac": (predicted / measured) if measured > 0 else 0.0,
        "achieved_gflops": flops / measured / 1e9 if measured > 0 else 0.0,
        "achieved_gbs": byts / measured / 1e9 if measured > 0 else 0.0,
    }


# --- representative inputs ----------------------------------------------------


def _populated_region_state(cfg, n_active: int, seed: int = 0):
    """A region store with ``n_active`` live+fresh synthetic regions.

    Same construction as ``benchmarks/eval_window.py``: random boxes well
    inside the unit domain, everything beyond ``n_active`` inactive — the
    compaction invariant's steady-state shape.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core import region_store

    rng = np.random.default_rng(seed)
    C, d = cfg.capacity, cfg.d
    centers = np.zeros((C, d))
    halfw = np.zeros((C, d))
    centers[:n_active] = rng.uniform(0.2, 0.8, (n_active, d))
    halfw[:n_active] = rng.uniform(0.01, 0.1, (n_active, d))
    mask = np.arange(C) < n_active
    return dataclasses.replace(
        region_store.empty_state(C, d, jnp.dtype(cfg.dtype)),
        centers=jnp.asarray(centers),
        halfw=jnp.asarray(halfw),
        active=jnp.asarray(mask),
        fresh=jnp.asarray(mask),
    )


def gm_eval_entries(cfg, reps: int) -> List[Dict[str, Any]]:
    """GM rule evaluation at every eval-window rung, window full of work."""
    import jax

    from repro.core.adaptive import eval_ladder, make_eval_step
    from repro.core.rules import make_rule

    rule = make_rule(cfg)
    out = []
    for w in eval_ladder(cfg):
        state = _populated_region_state(cfg, n_active=w)
        step = jax.jit(make_eval_step(cfg, rule, window=w), donate_argnums=0)
        compiled = step.lower(state).compile()
        out.append(
            _entry(
                "gm_eval",
                compiled,
                (state,),
                d=cfg.d,
                rung=w,
                reps=reps,
                regions=w,
                evals_per_region=rule.n_evals_per_region,
            )
        )
    return out


def advance_entries(cfg, reps: int) -> List[Dict[str, Any]]:
    """Windowed advance (classify + split + compact) at every advance rung.

    The representative population is ``rung // 2`` live regions — the
    largest count whose doubled advance target the rung still covers, i.e.
    the heaviest workload this rung is ever picked for.
    """
    import jax
    import numpy as np

    from repro.core.adaptive import advance_ladder, make_advance_step, make_eval_step
    from repro.core.rules import make_rule

    lo = np.asarray(cfg.lo(), np.float64)
    hi = np.asarray(cfg.hi(), np.float64)
    total_volume = float(np.prod(hi - lo))
    rule = make_rule(cfg)
    out = []
    for w in advance_ladder(cfg):
        n_active = max(w // 2, 1)
        state = _populated_region_state(cfg, n_active=n_active)
        # est/err/axis must hold real rule output for classify to threshold
        state = jax.jit(make_eval_step(cfg, rule, window=w))(state)
        adv = make_advance_step(cfg, total_volume, hi - lo, window=w)
        step = jax.jit(lambda s, _adv=adv: _adv(s), donate_argnums=0)
        compiled = step.lower(state).compile()
        out.append(
            _entry(
                "advance",
                compiled,
                (state,),
                d=cfg.d,
                rung=w,
                reps=reps,
                regions=n_active,
            )
        )
    return out


def vegas_entries(cfg, reps: int) -> List[Dict[str, Any]]:
    """The full VEGAS iterate: sample -> map -> integrand -> reduce -> adapt."""
    import jax

    from repro.core.integrands import get as get_integrand
    from repro.mc import engine as mc_engine

    fn = get_integrand(cfg.integrand).fn
    iterate = jax.jit(mc_engine.make_iterate(cfg, fn))
    state = mc_engine.init_state(cfg)
    compiled = iterate.lower(state).compile()
    return [
        _entry(
            "vegas_iterate",
            compiled,
            (state,),
            d=cfg.d,
            rung=None,
            reps=reps,
            samples=cfg.mc_samples,
        )
    ]


def dispatch_entries(cfg, reps: int) -> List[Dict[str, Any]]:
    """The fused sharded-service dispatch (``BatchEngine.run``).

    A full fleet is admitted at tolerances no slot can reach within one
    fused window, so the timed dispatch executes all ``sync_every``
    iterations (no early exit) — and the HLO scan-body counts are scaled by
    exactly that trip count (``scan_trips``, see module docstring).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.service.batch_engine import BatchEngine

    engine = BatchEngine(cfg)
    state = engine.init()
    rng = np.random.default_rng(0)
    for slot in range(engine.n_slots):
        theta = engine.family.sample_theta(cfg.d, rng)
        state = engine.admit(state, slot, theta, 1e-14, 1e-30)
    args = (
        state,
        jnp.asarray(cfg.sync_every, jnp.int32),
        jnp.asarray(0, jnp.int32),
    )
    # the program BatchEngine.run dispatches for this fleet's window rung
    run = jax.jit(engine._make_run(engine._rung(state)), donate_argnums=0)
    compiled = run.lower(*args).compile()
    return [
        _entry(
            "service_dispatch",
            compiled,
            args,
            d=cfg.d,
            rung=None,
            reps=reps,
            scan_trips=cfg.sync_every,
            slots=engine.n_slots,
            devices=engine.n_devices,
        )
    ]


# --- catalog assembly ---------------------------------------------------------


def default_configs(fast: bool = True) -> Dict[str, Any]:
    """The (kernel kind -> config) grid the standard catalog sweeps.

    Reduced shapes in ``fast`` mode so the CI perf-smoke job finishes in
    minutes; ``fast=False`` uses the benchmark-scale shapes.
    """
    from repro.core.config import QuadratureConfig

    cub = QuadratureConfig(
        d=5,
        integrand="f4",
        capacity=(1 << 11) if fast else (1 << 13),
    ).validate()
    veg = QuadratureConfig(
        d=8,
        integrand="f4",
        backend="vegas",
        mc_samples=8192 if fast else 65536,
        mc_shards=8,
    ).validate()
    svc = QuadratureConfig(
        d=3,
        integrand="genz_gaussian",
        capacity=(1 << 9) if fast else (1 << 11),
        batch_slots=4 if fast else 16,
        sync_every=4,
    ).validate()
    return {"gm_eval": cub, "advance": cub, "vegas_iterate": veg, "service_dispatch": svc}


def build_catalog(
    machine: Dict[str, Any],
    fast: bool = True,
    which: Optional[Sequence[str]] = None,
    reps: Optional[int] = None,
    configs: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Measure + predict every requested kernel; returns the catalog dict."""
    import jax

    jax.config.update("jax_enable_x64", True)
    cfgs = configs or default_configs(fast)
    which = tuple(which) if which else KERNELS
    unknown = set(which) - set(KERNELS)
    if unknown:
        raise ValueError(f"unknown kernels {sorted(unknown)}; known: {KERNELS}")
    n_reps = reps or (3 if fast else 10)
    builders = {
        "gm_eval": gm_eval_entries,
        "advance": advance_entries,
        "vegas_iterate": vegas_entries,
        "service_dispatch": dispatch_entries,
    }
    entries: List[Dict[str, Any]] = []
    for kernel in which:
        entries.extend(
            predict(e, machine) for e in builders[kernel](cfgs[kernel], n_reps)
        )
    return {
        "machine": {
            "name": machine.get("name"),
            "source": machine.get("source"),
            "peak_flops": machine["peak_flops"],
            "mem_bw": machine["mem_bw"],
        },
        "entries": entries,
    }


def save_catalog(catalog: Dict[str, Any], path: str = DEFAULT_PATH) -> str:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(catalog, f, indent=1)
        f.write("\n")
    return path


def load_catalog(path: str = DEFAULT_PATH) -> Optional[Dict[str, Any]]:
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def render_table(entries: Sequence[Dict[str, Any]]) -> str:
    """Markdown table of a catalog's entries (shared with the report)."""
    head = (
        "| kernel | rung | d | GFLOP | MB | measured | predicted | "
        "roofline frac | dominant |\n|---|---|---|---|---|---|---|---|---|"
    )
    rows = [head]
    for e in entries:
        rung = "—" if e.get("rung") is None else str(e["rung"])
        rows.append(
            f"| {e['kernel']} | {rung} | {e['d']} | "
            f"{e['flops_total'] / 1e9:.3f} | {e['bytes_total'] / 1e6:.1f} | "
            f"{e['measured_s'] * 1e3:.2f} ms | {e['predicted_s'] * 1e3:.2f} ms | "
            f"{e['roofline_frac']:.3f} | {e['dominant']} |"
        )
    return "\n".join(rows)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Lower, cost, and time the repo's real kernels."
    )
    ap.add_argument("--out", default=DEFAULT_PATH)
    ap.add_argument(
        "--machine",
        default=None,
        help=f"machine file to predict from (default: {MACHINE_PATH} if it "
        "was measured on this device kind, else the preset for the device)",
    )
    ap.add_argument("--full", action="store_true", help="benchmark-scale shapes")
    ap.add_argument(
        "--only", default=None, help=f"comma-separated subset of {KERNELS}"
    )
    args = ap.parse_args(argv)

    machine = resolve_machine(args.machine)
    which = args.only.split(",") if args.only else None
    catalog = build_catalog(machine, fast=not args.full, which=which)
    path = save_catalog(catalog, args.out)
    print(render_table(catalog["entries"]))
    print(f"\nwrote {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
