"""High-dimensional feasibility: the cubature/VEGAS crossover.

The paper's deterministic rules pay O(2^d + 2 d^2 + 2 d + 1) integrand
evaluations per region, so past d ≈ 8-10 the region store (and eventually a
single rule evaluation) stops fitting in memory; the VEGAS backend's cost
per sample is dimension-independent.  This benchmark measures both backends
on the two Genz families at d ∈ {5, 8, 10, 15, 20} (fast: {5, 10, 15}) at
rel_tol 1e-3 and records status / true error / wall time, giving the
crossover the ``backend="auto"`` dimension threshold approximates.

Cubature cases whose *initial evaluation* alone would exceed the memory
guard are recorded as ``infeasible`` without being run (that is the point:
at d = 15 one Genz-Malik sweep of the initial partition needs ~TBs), as are
cases that time out in the worker subprocess.  A worker that crashes is
recorded as ``error``: a crash says nothing about feasibility.
"""

import subprocess

from benchmarks._common import run_worker, save_results

REL_TOL = 1e-3
# bytes of *one* (nodes, regions) value matrix of the initial partition,
# beyond which cubature is recorded infeasible without being attempted (the
# reference evaluator materialises several of these, so the real footprint
# is a small multiple — and past this size the sweep also times out)
OOM_GUARD_BYTES = 512 << 20


def _spec(family: str, d: int) -> str:
    a = ",".join(["5"] * d)
    u = ",".join(["0.5"] * d)
    return f"{family}:{a}:{u}"


def _cubature_est_bytes(d: int, capacity: int) -> int:
    from repro.core import genz_malik
    from repro.core.config import QuadratureConfig

    n_init = QuadratureConfig(d=d, capacity=capacity).resolved_n_init()
    # the reference evaluator materialises (nodes, regions) value matrices
    return genz_malik.n_nodes(d) * n_init * 8


def _run_case(case: dict, timeout: int) -> dict:
    try:
        (rec,) = run_worker({"n_devices": 1, "cases": [case]}, timeout=timeout)
        return rec
    except subprocess.TimeoutExpired:
        status, detail = "infeasible", "timeout"
    except RuntimeError as e:  # the worker exited non-zero
        status, detail = "error", str(e)[-500:]
    return {**case, "status": status, "rel_err": None, "wall_s": None,
            "detail": detail}


def run(fast: bool = True):
    dims = (5, 10, 15) if fast else (5, 8, 10, 15, 20)
    timeout = 300 if fast else 1200
    capacity = 1 << 14
    out = []
    for family in ("genz_gaussian", "genz_product_peak"):
        for d in dims:
            spec = _spec(family, d)
            for backend in ("cubature", "vegas"):
                case = {
                    "integrand": spec,
                    "d": d,
                    "rel_tol": REL_TOL,
                    "backend": backend,
                }
                if backend == "cubature":
                    case.update(capacity=capacity, max_iters=60 if fast else 200)
                    if _cubature_est_bytes(d, capacity) > OOM_GUARD_BYTES:
                        out.append(
                            {
                                **case,
                                "status": "infeasible",
                                "rel_err": None,
                                "wall_s": None,
                                "detail": "oom_guard",
                            }
                        )
                        continue
                else:
                    case.update(
                        mc_samples=16384, mc_max_iters=40 if fast else 100
                    )
                out.append(_run_case(case, timeout))
    save_results("highdim_feasibility", out)
    return out


def rows(recs):
    for r in recs:
        wall = r.get("wall_s")
        rel = r.get("rel_err")
        yield (
            f"highdim/{r['integrand'].split(':')[0]}_d{r['d']}_{r['backend']}",
            0.0 if wall is None else wall * 1e6,
            f"status={r['status']} rel_err={'n/a' if rel is None else f'{rel:.1e}'}",
        )


if __name__ == "__main__":
    for row in rows(run(fast=False)):
        print(",".join(str(x) for x in row))
