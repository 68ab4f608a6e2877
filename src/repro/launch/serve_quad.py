"""Batch quadrature service launcher (continuous batching over a request fleet).

Serve 64 random Genz-Gaussian problems through 16 batch slots:
  PYTHONPATH=src python -m repro.launch.serve_quad --family genz_gaussian \
      --d 3 --n-requests 64 --batch-slots 16
Shard the fleet across 4 devices with cyclic problem rebalancing:
  PYTHONPATH=src python -m repro.launch.serve_quad --d 3 --n-requests 64 \
      --batch-slots 16 --devices 4 --rebalance ring
Explicit problems (one family spec per --request, see integrands.from_spec):
  PYTHONPATH=src python -m repro.launch.serve_quad --d 2 \
      --request genz_gaussian:5,5:0.3,0.7 --request genz_gaussian:8,2:0.5,0.5
Graceful degradation + crash recovery (see DESIGN.md §6): re-route degraded
requests, snapshot every admission tick, resume after a crash:
  PYTHONPATH=src python -m repro.launch.serve_quad --d 3 --n-requests 64 \
      --graceful --checkpoint-dir /tmp/quad-ckpt
  PYTHONPATH=src python -m repro.launch.serve_quad --d 3 --n-requests 64 \
      --graceful --checkpoint-dir /tmp/quad-ckpt --resume
Observability (DESIGN.md §8): Chrome trace + metrics stream + summary:
  PYTHONPATH=src python -m repro.launch.serve_quad --d 3 --n-requests 64 \
      --devices 4 --trace /tmp/quad-trace.json --metrics /tmp/quad.jsonl \
      --telemetry-summary
Elastic resilience (DESIGN.md §6): kill device 2 at iteration 3, watch the
fleet evacuate its slots, shrink the mesh, and finish anyway:
  PYTHONPATH=src python -m repro.launch.serve_quad --d 3 --n-requests 64 \
      --devices 4 --chaos-fail-device 2:3 --strict
"""

import argparse
import time

from repro.telemetry.logutil import add_verbosity_flags, setup_logging


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--family", default="genz_gaussian")
    ap.add_argument("--d", type=int, default=3)
    ap.add_argument(
        "--n-requests", type=int, default=32, help="random problems to sample"
    )
    ap.add_argument(
        "--request",
        action="append",
        default=[],
        metavar="SPEC",
        help="explicit family spec (repeatable; overrides --n-requests)",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rel-tol", type=float, default=1e-6)
    ap.add_argument(
        "--rel-tols",
        default=None,
        metavar="TOL[,TOL...]",
        help="per-request tolerances, cycled over the fleet (e.g. "
        "'1e-2,1e-8' stripes easy/hard problems across slots — the "
        "load-imbalanced fleet that exercises ring rebalancing)",
    )
    ap.add_argument("--capacity", type=int, default=1 << 12)
    ap.add_argument("--batch-slots", type=int, default=16)
    ap.add_argument("--admit-every", type=int, default=1)
    ap.add_argument("--eval-window-min", type=int, default=256)
    ap.add_argument(
        "--advance-window",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="window the vmapped advance stage too (bit-identical)",
    )
    ap.add_argument(
        "--use-kernel",
        action="store_true",
        help="fused Pallas GM kernel (theta rides as a kernel operand)",
    )
    ap.add_argument(
        "--interpret",
        action=argparse.BooleanOptionalAction,
        default=None,
        help="Pallas interpret mode (default: from the platform — the "
        "interpreter on CPU, Mosaic on a TPU)",
    )
    ap.add_argument("--max-iters", type=int, default=300)
    ap.add_argument(
        "--backend",
        default="cubature",
        choices=["cubature", "vegas", "auto"],
        help="engine pool backing the fleet: deterministic cubature, the "
        "VEGAS Monte Carlo subsystem (high d), or auto (by dimension)",
    )
    ap.add_argument(
        "--mc-samples", type=int, default=8192, help="vegas samples per iteration"
    )
    ap.add_argument(
        "--mc-iters", type=int, default=100, help="vegas iteration cap"
    )
    ap.add_argument(
        "--mc-seed", type=int, default=0, help="vegas PRNG seed (deterministic)"
    )
    ap.add_argument("--sync-every", type=int, default=4)
    ap.add_argument(
        "--devices",
        type=int,
        default=1,
        help="mesh size the slot axis is sharded over (0 = all visible devices)",
    )
    ap.add_argument(
        "--rebalance",
        choices=("ring", "off"),
        default="ring",
        help="cyclic problem migration between ring partners when a device drains",
    )
    ap.add_argument(
        "--max-state-bytes",
        type=int,
        default=2 << 30,
        help="refuse fleets whose stacked region store exceeds this many bytes",
    )
    ap.add_argument(
        "--validate", action="store_true", help="print true error vs analytic exact"
    )
    ap.add_argument(
        "--graceful",
        action="store_true",
        help="serve through the graceful-degradation layer: capacity/"
        "nonfinite evictions are re-routed once to the VEGAS pool, "
        "tolerance-starved requests retried at a loosened tolerance "
        "(results carry attempt provenance)",
    )
    ap.add_argument(
        "--deadline-s",
        type=float,
        default=None,
        help="per-request wall-clock SLO in seconds (expired slots are "
        "evicted with a best-effort partial result, status 'deadline')",
    )
    ap.add_argument(
        "--max-evals",
        type=float,
        default=None,
        help="per-request integrand-evaluation SLO (deterministic analogue "
        "of --deadline-s)",
    )
    ap.add_argument(
        "--checkpoint-dir",
        default=None,
        help="directory for service snapshots (engine state + slot map)",
    )
    ap.add_argument(
        "--checkpoint-every",
        type=int,
        default=1,
        help="snapshot every N admission ticks (needs --checkpoint-dir)",
    )
    ap.add_argument(
        "--resume",
        action="store_true",
        help="restore the latest snapshot in --checkpoint-dir and replay: "
        "already-pulled requests are skipped, in-flight slots resume "
        "mid-refinement (bit-identical for slots the crash did not touch)",
    )
    ap.add_argument(
        "--trace",
        default=None,
        metavar="PATH",
        help="write a Chrome trace-event file (load in chrome://tracing or "
        "ui.perfetto.dev): one lane per device, spans for compile/dispatch/"
        "admit/collect/checkpoint, flow arrows for migrations and reroutes",
    )
    ap.add_argument(
        "--metrics",
        default=None,
        metavar="PATH",
        help="stream telemetry events to PATH as JSON Lines",
    )
    ap.add_argument(
        "--telemetry-summary",
        action="store_true",
        help="print the end-of-run counter/span summary table",
    )
    ap.add_argument(
        "--chaos-fail-device",
        default=None,
        metavar="DEV:TICK[:RESTORE]",
        help="inject a permanent device loss: device index DEV fails at "
        "iteration TICK (optionally healing at iteration RESTORE, so the "
        "mesh regrows) — exercises watchdog / evacuation / shrink, see "
        "DESIGN.md §6",
    )
    ap.add_argument(
        "--max-dispatch-retries",
        type=int,
        default=2,
        help="transient dispatch faults retried (with backoff) before the "
        "faulting device is declared permanently lost",
    )
    ap.add_argument(
        "--dispatch-timeout-s",
        type=float,
        default=None,
        help="watchdog timeout per fused dispatch: a wedged device surfaces "
        "as a DispatchTimeout instead of hanging the serve loop",
    )
    ap.add_argument(
        "--strict",
        action="store_true",
        help="exit non-zero unless every result is finite AND converged; "
        "runs that completed only via device-loss evacuation or retry "
        "exit 0 but log a degraded-mode warning with per-request "
        "provenance",
    )
    add_verbosity_flags(ap)
    args = ap.parse_args()
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir")
    log = setup_logging(quiet=args.quiet, verbose=args.verbose)

    import jax

    jax.config.update("jax_enable_x64", True)
    import numpy as np

    from repro import compile_cache
    from repro.core import QuadratureConfig
    from repro.core.integrands import get_param, parse_spec
    from repro.service import QuadRequest, serve
    from repro.service.batch_engine import estimate_state_bytes

    compile_cache.enable()
    family = get_param(args.family)
    cfg = QuadratureConfig(
        d=args.d,
        integrand=args.family,
        rel_tol=args.rel_tol,
        capacity=args.capacity,
        batch_slots=args.batch_slots,
        admit_every=args.admit_every,
        eval_window_min=args.eval_window_min,
        advance_window=args.advance_window,
        use_kernel=args.use_kernel,
        interpret=args.interpret,
        max_iters=args.max_iters,
        backend=args.backend,
        mc_samples=args.mc_samples,
        mc_max_iters=args.mc_iters,
        mc_seed=args.mc_seed,
        sync_every=args.sync_every,
        service_devices=args.devices,
        rebalance=args.rebalance,
    )
    vegas = cfg.resolved_backend() == "vegas"
    if vegas and args.devices not in (0, 1):
        raise SystemExit(
            "--backend vegas serves through a single-device vmapped pool "
            "(MC parallelism shards samples, not slots — see "
            "repro.mc.multi_device); drop --devices"
        )

    # Fail fast on fleets the region store cannot accommodate: the stacked
    # store allocates batch_slots x capacity regions up front, so an oversized
    # --batch-slots would otherwise die deep inside XLA allocation (or swap
    # the host to death) instead of telling the operator what to change.
    # (The vegas pool's state is a few KB of grid edges per slot — no check.)
    need = 0 if vegas else estimate_state_bytes(cfg, family)
    if need > args.max_state_bytes:
        raise SystemExit(
            f"--batch-slots {args.batch_slots} x --capacity {args.capacity} "
            f"needs ~{need / 2**30:.2f} GiB of region-store state, over the "
            f"{args.max_state_bytes / 2**30:.2f} GiB limit; lower "
            "--batch-slots or --capacity (or raise --max-state-bytes if the "
            "hardware really has the memory)"
        )
    n_devices = (
        1
        if vegas
        else len(jax.devices()) if args.devices == 0 else args.devices
    )
    if n_devices > len(jax.devices()):
        raise SystemExit(
            f"--devices {args.devices} but only {len(jax.devices())} devices "
            "are visible (set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "to emulate a mesh on CPU)"
        )
    if args.batch_slots % n_devices:
        raise SystemExit(
            f"--batch-slots {args.batch_slots} must be a multiple of "
            f"--devices ({n_devices}): each device owns a contiguous block "
            "of batch_slots / devices slots"
        )

    if args.request:
        thetas = []
        for spec in args.request:
            req_family, theta = parse_spec(spec)
            if req_family.name != family.name:
                raise SystemExit(
                    f"--request {spec!r} names family {req_family.name!r}, "
                    f"but --family is {args.family!r}"
                )
            thetas.append(theta)
    else:
        rng = np.random.default_rng(args.seed)
        thetas = [family.sample_theta(args.d, rng) for _ in range(args.n_requests)]

    rel_tols = None
    if args.rel_tols:
        rel_tols = [float(t) for t in args.rel_tols.split(",")]
    requests = [
        QuadRequest(
            req_id=i,
            theta=t,
            rel_tol=None if rel_tols is None else rel_tols[i % len(rel_tols)],
            deadline_s=args.deadline_s,
            max_evals=args.max_evals,
        )
        for i, t in enumerate(thetas)
    ]
    log.info(
        "serving %d x %s (d=%d) through %d slots on %d device(s) "
        "(rebalance=%s), rel_tol=%s",
        len(requests),
        family.name,
        args.d,
        cfg.batch_slots,
        n_devices,
        cfg.rebalance,
        args.rel_tols if rel_tols else f"{cfg.rel_tol:g}",
    )
    serve_kwargs = {
        "max_dispatch_retries": args.max_dispatch_retries,
        "dispatch_timeout_s": args.dispatch_timeout_s,
    }
    if args.checkpoint_dir:
        from repro.service import ServiceCheckpointer

        serve_kwargs["checkpointer"] = ServiceCheckpointer(args.checkpoint_dir)
        serve_kwargs["checkpoint_every"] = args.checkpoint_every
    if args.chaos_fail_device:
        from repro.service.faults import DeviceDown

        parts = args.chaos_fail_device.split(":")
        if len(parts) not in (2, 3):
            raise SystemExit(
                f"--chaos-fail-device {args.chaos_fail_device!r}: expected "
                "DEV:TICK or DEV:TICK:RESTORE"
            )
        dev, tick = int(parts[0]), int(parts[1])
        restore = int(parts[2]) if len(parts) == 3 else None
        if not 0 <= dev < n_devices:
            raise SystemExit(
                f"--chaos-fail-device device {dev} out of range for "
                f"{n_devices} device(s)"
            )
        if n_devices < 2:
            raise SystemExit(
                "--chaos-fail-device needs --devices >= 2: a single-device "
                "fleet has no surviving sub-mesh to evacuate onto"
            )
        serve_kwargs["fault_injector"] = DeviceDown(
            device=dev, at_tick=tick, restore_at_tick=restore
        )
        log.info(
            "chaos: device %d fails at iteration %d%s",
            dev,
            tick,
            "" if restore is None else f", heals at iteration {restore}",
        )

    from repro.telemetry import JsonlSink, MemorySink, Recorder, summary_table
    from repro.telemetry.trace import write_chrome_trace

    recorder = None
    trace_sink = None
    if args.trace or args.metrics or args.telemetry_summary:
        sinks = []
        if args.trace:
            trace_sink = MemorySink()
            sinks.append(trace_sink)
        if args.metrics:
            sinks.append(JsonlSink(args.metrics))
        recorder = Recorder(sinks=tuple(sinks))
        serve_kwargs["recorder"] = recorder

    t0 = time.perf_counter()
    results = []
    for res in serve(
        cfg,
        requests,
        family,
        graceful=args.graceful,
        resume=args.resume,
        **serve_kwargs,
    ):
        results.append(res)
        line = res.summary()
        if args.validate:
            exact = family.exact(args.d, thetas[res.req_id])
            rel = abs(res.integral - exact) / max(abs(exact), 1e-300)
            line += f" true_rel_err={rel:.2e}"
        log.info("[%d/%d] %s", len(results), len(requests), line)
    dt = time.perf_counter() - t0
    log.info(
        "done: %d problems in %.2fs (%.1f problems/sec)",
        len(requests),
        dt,
        len(requests) / dt,
    )
    if recorder is not None:
        recorder.close()
        if args.trace:
            write_chrome_trace(args.trace, trace_sink.events)
            log.info("wrote Chrome trace: %s (load in ui.perfetto.dev)", args.trace)
        if args.metrics:
            log.info("wrote metrics JSONL: %s", args.metrics)
        if args.telemetry_summary:
            log.info("telemetry summary:\n%s", summary_table(recorder))

    if args.strict:
        import math
        import sys

        hints = {
            "max_iters": "raise --max-iters (or --mc-iters for vegas), or "
            "loosen --rel-tol",
            "capacity": "raise --capacity or loosen --rel-tol",
            "nonfinite": "the integrand produced NaN/Inf on this domain; "
            "check the integrand/theta for poles or overflow",
            "deadline": "raise --deadline-s / --max-evals or loosen the "
            "tolerance",
            "no_active": "the region population collapsed; loosen --rel-tol",
        }
        problems = []
        for res in sorted(results, key=lambda r: r.req_id):
            if not (math.isfinite(res.integral) and math.isfinite(res.error)):
                problems.append(
                    f"req {res.req_id}: non-finite result "
                    f"(integral={res.integral!r}, error={res.error!r})"
                )
            elif res.status != "converged":
                hint = hints.get(res.status, "see the status taxonomy in DESIGN.md")
                problems.append(
                    f"req {res.req_id}: status={res.status!r} (hint: {hint})"
                )
        # Converged-but-degraded requests (device-loss evacuations, watchdog
        # or fallback retries) pass strict mode — the answer is correct, the
        # road there was not — but the degradation is loud, with provenance,
        # so a scripted caller can still grep for it.
        degraded = [
            r
            for r in sorted(results, key=lambda r: r.req_id)
            if (r.evacuated or r.attempts > 1)
            and not (
                not (math.isfinite(r.integral) and math.isfinite(r.error))
                or r.status != "converged"
            )
        ]
        for r in degraded:
            log.warning(
                "STRICT-DEGRADED: req %d converged after recovery "
                "(attempts=%d, retried_from=%s, evacuated=%s)",
                r.req_id,
                r.attempts,
                r.retried_from,
                r.evacuated,
            )
        if problems:
            # via logging, not print: serve_quad is print-free by contract
            # (tests/test_no_print.py) — errors ride the same stream -q
            # controls, and the non-zero exit is what scripted callers gate on
            log.error("STRICT: %s", "; ".join(problems))
            sys.exit(1)


if __name__ == "__main__":
    main()
