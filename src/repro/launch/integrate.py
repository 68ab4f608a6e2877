"""Quadrature launcher (the paper's solver as a CLI).

Single device:
  PYTHONPATH=src python -m repro.launch.integrate --integrand f4 --d 5 --rel-tol 1e-7
Distributed over the first N visible devices (on CPU, emulate them with
XLA_FLAGS=--xla_force_host_platform_device_count=N):
  PYTHONPATH=src python -m repro.launch.integrate --devices 4 --integrand f6 --d 5
High dimension via the VEGAS Monte Carlo backend (see DESIGN.md §7); a bare
family name samples a random theta from --theta-seed:
  PYTHONPATH=src python -m repro.launch.integrate --backend vegas --d 15 \
      --integrand genz_gaussian --rel-tol 1e-3
"""

import argparse
import sys


def select_devices(n: int) -> list:
    """The first ``n`` visible devices; an error if fewer are visible."""
    import jax

    visible = jax.devices()
    if n < 1 or n > len(visible):
        raise SystemExit(
            f"--devices {n} but {len(visible)} device(s) are visible (on CPU, "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={n} to "
            "emulate a mesh)"
        )
    return visible[:n]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--integrand", default="f4")
    ap.add_argument("--d", type=int, default=5)
    ap.add_argument("--rel-tol", type=float, default=1e-7)
    ap.add_argument("--capacity", type=int, default=1 << 15)
    ap.add_argument("--classifier", default="robust", choices=["robust", "aggressive"])
    ap.add_argument("--rule", default="genz_malik", choices=["genz_malik", "gauss_kronrod"])
    ap.add_argument("--use-kernel", action="store_true", help="Pallas GM kernel")
    ap.add_argument(
        "--interpret",
        action=argparse.BooleanOptionalAction,
        default=None,
        help="Pallas interpret mode (default: from the platform — the "
        "interpreter on CPU, Mosaic on a TPU)",
    )
    ap.add_argument(
        "--block-regions", type=int, default=0, help="kernel lanes per block (0 = default)"
    )
    ap.add_argument(
        "--eval-window",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="evaluate only the leading active window of the region store",
    )
    ap.add_argument(
        "--eval-window-min", type=int, default=256, help="smallest window ladder rung"
    )
    ap.add_argument(
        "--advance-window",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="window the advance stage (classify + split/compact + global "
        "reductions) as well — bit-identical, scales the whole iteration "
        "with the live population",
    )
    ap.add_argument("--max-iters", type=int, default=600)
    ap.add_argument(
        "--backend",
        default="cubature",
        choices=["cubature", "vegas", "auto"],
        help="cubature (deterministic subdivision), vegas (adaptive "
        "importance-sampling MC for high d), or auto (picks by dimension)",
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
    ap.add_argument(
        "--theta-seed",
        type=int,
        default=0,
        help="theta draw for a bare family-name --integrand (e.g. "
        "'genz_gaussian' without coefficients)",
    )
    ap.add_argument(
        "--devices",
        type=int,
        default=1,
        help="run distributed over the first N visible devices",
    )
    ap.add_argument("--message-cap", type=int, default=512)
    ap.add_argument(
        "--redistribution",
        default="ring",
        choices=["ring", "off"],
        help="distributed load redistribution policy",
    )
    ap.add_argument(
        "--sync-every",
        type=int,
        default=4,
        help="iterations fused per dispatch in the distributed driver",
    )
    ap.add_argument("--device-loop", action="store_true", help="lax.while_loop driver")
    ap.add_argument(
        "--trace",
        default=None,
        metavar="PATH",
        help="write a Chrome trace-event file of the run (Perfetto-loadable)",
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
        "--strict",
        action="store_true",
        help="exit non-zero unless the result is finite AND converged "
        "(for scripted runs: a NaN or a max_iters/capacity/nonfinite "
        "termination must fail the pipeline, not print and exit 0)",
    )
    args = ap.parse_args()

    import jax

    jax.config.update("jax_enable_x64", True)

    import numpy as np

    from repro import compile_cache

    compile_cache.enable()
    devices = select_devices(args.devices)

    from repro.core import QuadratureConfig, integrate, integrate_device
    from repro.core.distributed import integrate_distributed
    from repro.core.integrands import PARAM_REGISTRY, REGISTRY, bind, get

    # A bare family name (no ':'-separated coefficients) samples one theta
    # deterministically — the ergonomic path for "just integrate a d=15
    # genz_gaussian"; the bound integrand carries its analytic exact value.
    bound = None
    if args.integrand in PARAM_REGISTRY:
        family = PARAM_REGISTRY[args.integrand]
        theta = family.sample_theta(args.d, np.random.default_rng(args.theta_seed))
        bound = bind(family, theta)

    cfg = QuadratureConfig(
        d=args.d,
        integrand=args.integrand,
        rel_tol=args.rel_tol,
        capacity=args.capacity,
        classifier=args.classifier,
        rule=args.rule,
        use_kernel=args.use_kernel,
        interpret=args.interpret,
        block_regions=args.block_regions,
        eval_window=args.eval_window,
        eval_window_min=args.eval_window_min,
        advance_window=args.advance_window,
        max_iters=args.max_iters,
        backend=args.backend,
        mc_samples=args.mc_samples,
        mc_max_iters=args.mc_iters,
        mc_seed=args.mc_seed,
        message_cap=args.message_cap,
        redistribution=args.redistribution,
        sync_every=args.sync_every,
    )
    from repro.telemetry import (
        NULL,
        JsonlSink,
        MemorySink,
        Recorder,
        summary_table,
    )
    from repro.telemetry.trace import write_chrome_trace

    recorder = NULL
    trace_sink = None
    if args.trace or args.metrics or args.telemetry_summary:
        sinks = []
        if args.trace:
            trace_sink = MemorySink()
            sinks.append(trace_sink)
        if args.metrics:
            sinks.append(JsonlSink(args.metrics))
        recorder = Recorder(sinks=tuple(sinks))

    fn = bound.fn if bound is not None else None
    if cfg.resolved_backend() == "vegas":
        from repro.mc import integrate_vegas, integrate_vegas_distributed

        if args.devices > 1:
            res = integrate_vegas_distributed(
                cfg, fn, devices=devices, recorder=recorder
            )
            print(res.summary())
            print(f"devices={args.devices} (sample shards split across mesh)")
        else:
            res = integrate_vegas(cfg, fn, recorder=recorder)
            print(res.summary())
    elif args.devices > 1:
        res = integrate_distributed(cfg, fn, devices=devices, recorder=recorder)
        print(res.summary())
        print(f"devices={res.n_devices} mean_imbalance={res.mean_imbalance():.3f}")
    elif args.device_loop:
        res = integrate_device(cfg, fn, recorder=recorder)
        print(res.summary())
    else:
        res = integrate(cfg, fn, recorder=recorder)
        print(res.summary())

    if recorder is not NULL:
        recorder.close()
        if args.trace:
            write_chrome_trace(args.trace, trace_sink.events)
            print(f"wrote Chrome trace: {args.trace}")
        if args.metrics:
            print(f"wrote metrics JSONL: {args.metrics}")
        if args.telemetry_summary:
            print(summary_table(recorder))
    exact = None
    if bound is not None:
        exact = bound.exact(args.d)
    elif args.integrand in REGISTRY or ":" in args.integrand:
        # fixed registry entries and family specs (e.g. genz_gaussian:5,5:.3,.7)
        exact = get(args.integrand).exact(args.d)
    if exact is not None:
        rel = abs(res.integral - exact) / max(abs(exact), 1e-300)
        print(f"exact={exact:.15e} true_rel_err={rel:.3e}")

    if args.strict:
        import math

        problems = []
        if not (math.isfinite(res.integral) and math.isfinite(res.error)):
            problems.append(
                f"non-finite result (integral={res.integral!r}, "
                f"error={res.error!r})"
            )
        if res.status != "converged":
            hints = {
                "max_iters": "raise --max-iters (or --mc-iters for vegas), "
                "or loosen --rel-tol",
                "capacity": "raise --capacity or loosen --rel-tol",
                "nonfinite": "the integrand produced NaN/Inf on this domain; "
                "check the integrand/theta for poles or overflow",
                "no_active": "the region population collapsed; loosen "
                "--rel-tol",
            }
            hint = hints.get(res.status, "see the status taxonomy in DESIGN.md")
            problems.append(f"status={res.status!r} (hint: {hint})")
        if problems:
            print(
                "STRICT: " + "; ".join(problems),
                file=sys.stderr,
            )
            sys.exit(1)


if __name__ == "__main__":
    main()
