"""Sharded batch-service scaling: one fleet, 1..N devices, same results.

The batch service's slot axis shards over a device mesh (each device owns
``batch_slots / n_devices`` slots and runs the vmapped windowed step
locally; convergence is decided from a psum of per-slot done masks once per
fused ``sync_every`` dispatch, and drained devices pull whole problems from
their cyclic ring partner).  This harness serves the *same* request fleet
through meshes of increasing size and reports problems/sec, speedup over the
single-device service, and the migration count — while asserting the
sharded runs return bit-identical integrals to the single-device run (the
service's parity guarantee).

Each mesh size runs in a subprocess so ``--xla_force_host_platform_device_count``
can size the virtual CPU mesh; on real multi-GPU/TPU hardware the same code
measures true scaling.  Virtual CPU devices share the same cores, so CPU
"speedups" mainly reflect dispatch/fusion overheads — the record that
matters here is the parity column and the harness itself.
"""

import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _worker_main(spec: dict) -> None:
    n_dev = int(spec["n_devices"])
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={max(n_dev, 1)} "
        + os.environ.get("XLA_FLAGS", "")
    )
    import jax

    jax.config.update("jax_enable_x64", True)
    import numpy as np

    from repro import compile_cache
    from repro.core import QuadratureConfig
    from repro.core.integrands import get_param
    from repro.service import BatchScheduler, QuadRequest

    family = get_param("genz_gaussian")
    cfg = QuadratureConfig(
        d=spec["d"],
        integrand="genz_gaussian",
        rel_tol=spec["rel_tol"],
        capacity=spec["capacity"],
        batch_slots=spec["batch_slots"],
        max_iters=300,
        sync_every=spec.get("sync_every", 4),
        rebalance=spec.get("rebalance", "ring"),
    )
    rng = np.random.default_rng(spec["seed"])
    thetas = [family.sample_theta(cfg.d, rng) for _ in range(spec["n_requests"])]

    def fleet():
        return [QuadRequest(req_id=i, theta=t) for i, t in enumerate(thetas)]

    compile_cache.enable()
    devices = jax.devices()[:n_dev]
    if len(devices) < n_dev:
        raise SystemExit(f"{n_dev} devices requested, {len(devices)} visible")
    out = {}
    for label in ("cold", "warm"):  # cold pays every window-rung compile once
        sched = BatchScheduler(cfg, family, devices=devices)
        t0 = time.perf_counter()
        results = sorted(sched.serve(fleet()), key=lambda r: r.req_id)
        out[f"{label}_s"] = time.perf_counter() - t0
        out["stats"] = sched.last_stats
    out.update(
        n_devices=n_dev,
        statuses=sorted({r.status for r in results}),
        integrals=[r.integral.hex() for r in results],
        problems_per_s=spec["n_requests"] / out["warm_s"],
    )
    print("RESULT_JSON:" + json.dumps(out))


def run(fast: bool = True):
    import numpy as np  # noqa: F401  (parity of import environment)

    devs = (1, 2, 4) if fast else (1, 2, 4, 8)
    spec = dict(
        d=3,
        rel_tol=1e-6,
        capacity=1 << 11,
        batch_slots=16,
        n_requests=32 if fast else 64,
        seed=2026,
    )
    out = []
    ref_integrals = None
    for n in devs:
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(_REPO, "src") + ":" + _REPO
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [
                sys.executable,
                "-m",
                "benchmarks.sharded_service",
                "--worker",
                json.dumps({**spec, "n_devices": n}),
            ],
            capture_output=True,
            text=True,
            timeout=3600,
            cwd=_REPO,
            env=env,
        )
        if proc.returncode != 0:
            raise RuntimeError(proc.stderr[-3000:])
        line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT_JSON:")]
        rec = json.loads(line[-1][len("RESULT_JSON:") :])
        if ref_integrals is None:
            ref_integrals = rec["integrals"]
        parity = rec["integrals"] == ref_integrals
        assert parity, f"sharded service diverged from 1-device run at n={n}"
        rec.pop("integrals")
        out.append(
            {
                **{k: v for k, v in spec.items() if k != "seed"},
                **rec,
                "bit_parity_vs_1dev": parity,
            }
        )
    # saved once, after every child has exited: collecting the device meta
    # touches JAX, and this process must stay off the chip while they run
    from benchmarks._common import save_results

    save_results("sharded_service", out)
    return out


def rows(recs):
    base = next((r["warm_s"] for r in recs if r["n_devices"] == 1), None)
    for r in recs:
        speedup = (base or r["warm_s"]) / max(r["warm_s"], 1e-9)
        yield (
            f"sharded_service/dev{r['n_devices']}_slots{r['batch_slots']}",
            r["warm_s"] / r["n_requests"] * 1e6,
            f"problems_per_s={r['problems_per_s']:.2f};speedup={speedup:.2f};"
            f"migrations={r['stats']['migrations']};"
            f"parity={r['bit_parity_vs_1dev']}",
        )


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--worker":
        _worker_main(json.loads(sys.argv[2]))
    else:
        for row in rows(run(fast="--full" not in sys.argv)):
            print(",".join(str(x) for x in row))
