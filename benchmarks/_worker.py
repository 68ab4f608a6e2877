"""Subprocess worker for multi-device quadrature benchmarks.

Usage: python -m benchmarks._worker '<json spec>'
spec = {"n_devices": int, "cases": [{integrand, d, rel_tol, capacity,
        classifier, redistribution, max_iters, use_kernel}]}
Distributed cases run on the first ``n_devices`` visible devices (on CPU
the worker forces that many virtual devices).
Prints one line: RESULT_JSON:[...per-case records...]
"""

import json
import os
import sys
import time


def main() -> None:
    spec = json.loads(sys.argv[1])
    n_dev = int(spec.get("n_devices", 1))
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={max(n_dev, 1)} "
        + os.environ.get("XLA_FLAGS", "")
    )
    import jax

    jax.config.update("jax_enable_x64", True)

    from repro import compile_cache
    from repro.core import integrands
    from repro.core.adaptive import integrate
    from repro.core.config import QuadratureConfig
    from repro.core.distributed import integrate_distributed

    compile_cache.enable()
    devices = jax.devices()[: max(n_dev, 1)]
    if len(devices) < n_dev:
        raise SystemExit(f"{n_dev} devices requested, {len(devices)} visible")
    out = []
    for case in spec["cases"]:
        case = dict(case)
        distributed = case.pop("distributed", n_dev > 1)
        cfg = QuadratureConfig(**case)
        t0 = time.time()
        if cfg.resolved_backend() == "vegas":
            from repro.mc import integrate_vegas, integrate_vegas_distributed

            if distributed:
                res = integrate_vegas_distributed(cfg, devices=devices)
            else:
                res = integrate_vegas(cfg)
            extra = {"chi2_dof": res.chi2_dof}
        elif distributed:
            res = integrate_distributed(cfg, devices=devices)
            extra = {
                "mean_imbalance": res.mean_imbalance(),
                "evals_per_device": res.evals_per_device.tolist(),
                "history_tail": res.history[-3:],
            }
        else:
            res = integrate(cfg)
            extra = {}
        wall = time.time() - t0
        exact = integrands.get(cfg.integrand).exact(cfg.d)
        out.append(
            {
                **case,
                "n_devices": n_dev if distributed else 1,
                "integral": res.integral,
                "eps": res.error,
                "status": res.status,
                "iterations": res.iterations,
                "n_evals": res.n_evals,
                "wall_s": wall,
                "exact": exact,
                "rel_err": abs(res.integral - exact) / max(abs(exact), 1e-300),
                **extra,
            }
        )
    print("RESULT_JSON:" + json.dumps(out))


if __name__ == "__main__":
    main()
