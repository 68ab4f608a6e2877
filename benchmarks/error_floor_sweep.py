"""Claimed vs true error of the two-level estimator, for a shrink floor.

The two-level error estimate (``repro.core.error``) shrinks ``|I7 - I5|``
by ``sqrt(2 r)`` in the asymptotic regime, bounded below by
``error._SHRINK_MIN``.  This sweep sets that floor to ``--floor`` (0 is the
unbounded shrink) and runs the f1-f7 registry at d=5 and d=8
(``integrate``), the parameterised families at d=5..8 (the batch service,
``--thetas`` thetas each from ``--seed``), and the 64-request
genz_gaussian d=5 fleet of ``chip_smoke.py`` at rel_tol 1e-6.  It prints
one JSON line per run: status, claimed error, true error against the
analytic exact value, and evaluations; the last line sums them up: runs
that reported convergence with a true error over their tolerance, the
worst true/claimed ratio among converged runs, and total evaluations.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m benchmarks.error_floor_sweep --floor 0
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

REGISTRY_TOLS = {5: 1e-6, 8: 1e-4}
FAMILY_TOLS = {5: 1e-5, 6: 1e-4, 7: 1e-4, 8: 1e-3}


def _row(name, d, tol, status, integral, claimed, exact, evals):
    true = abs(integral - exact)
    return {
        "integrand": name,
        "d": d,
        "rel_tol": tol,
        "status": status,
        "claimed_err": claimed,
        "true_err": true,
        "true_over_claimed": true / claimed if claimed > 0 else float("inf"),
        "false_convergence": status == "converged" and true > tol * abs(exact),
        "evals": evals,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--floor", type=float, required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--thetas", type=int, default=16, help="thetas per family and d")
    args = ap.parse_args()

    jax.config.update("jax_enable_x64", True)
    from repro.core import QuadratureConfig, error, integrate
    from repro.core.integrands import REGISTRY, get, get_param
    from repro.service.api import integrate_batch

    error._SHRINK_MIN = args.floor  # read when the rule is traced
    rows = []

    def fleet(family, d, thetas, tol):
        fam = get_param(family)
        cfg = QuadratureConfig(d=d, integrand=family, rel_tol=tol,
                               capacity=1 << 16, batch_slots=len(thetas),
                               max_iters=300)
        for theta, res in zip(thetas, integrate_batch(cfg, thetas, fam)):
            rows.append(_row(family, d, tol, res.status, res.integral,
                             res.error, fam.exact(d, theta), res.n_evals))
            print(json.dumps(rows[-1]), flush=True)

    for name in sorted(n for n in REGISTRY if n[1:].isdigit()):
        for d, tol in REGISTRY_TOLS.items():
            cfg = QuadratureConfig(d=d, integrand=name, rel_tol=tol,
                                   capacity=1 << 16, max_iters=200)
            res = integrate(cfg)
            rows.append(_row(name, d, tol, res.status, res.integral,
                             res.error, get(name).exact(d), res.n_evals))
            print(json.dumps(rows[-1]), flush=True)
    rng = np.random.default_rng(args.seed)
    for family in ("genz_gaussian", "genz_product_peak", "monomial"):
        fam = get_param(family)
        for d, tol in FAMILY_TOLS.items():
            fleet(family, d, [fam.sample_theta(d, rng) for _ in range(args.thetas)], tol)
    # chip_smoke.py's service fleet (its thetas, from seed 0) one decade tighter
    fam = get_param("genz_gaussian")
    rng = np.random.default_rng(0)
    fleet("genz_gaussian", 5, [fam.sample_theta(5, rng) for _ in range(64)], 1e-6)
    converged = [r for r in rows if r["status"] == "converged"]
    print(json.dumps({
        "floor": args.floor,
        "runs": len(rows),
        "converged": len(converged),
        "false_convergence": sum(r["false_convergence"] for r in rows),
        "worst_true_over_claimed": max(r["true_over_claimed"] for r in converged),
        "evals": sum(r["evals"] for r in rows),
    }))


if __name__ == "__main__":
    main()
