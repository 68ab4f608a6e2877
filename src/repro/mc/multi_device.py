"""Multi-device VEGAS: sample shards spread over the mesh.

MC is embarrassingly parallel, so the multi-device story is the clean
counterpoint to the cubature backend's region migration: no load balancing,
no payload exchange — each device evaluates ``mc_shards / n_devices`` of the
iteration's fixed sample shards under ``jax.shard_map``, the
per-shard partial sums are all-gathered (device order == shard order) and
combined in the engine's fixed left-to-right scan, and the grid/counts
refinement runs replicated on every device from the identical combined
accumulators.

Because shards — not raw sample ranges — are the unit of division, and every
cross-shard reduction happens after the gather in a fixed order, the
estimate is **bit-identical to the single-device engine at any device count
dividing ``mc_shards``**, with device-count-invariant sample totals
(``cfg.mc_samples`` per iteration regardless of the mesh).  That parity is
asserted by the ``__main__`` selftest below, run in a subprocess by
``tests/test_mc.py`` (same idiom as ``repro.core.dist_selftest``: all jax
imports are deferred so the selftest can force the virtual device count
before the backend initialises).
"""

from __future__ import annotations

from typing import Callable, Optional

from repro.core.config import QuadratureConfig

AXIS = "dev"


def integrate_vegas_distributed(
    cfg: QuadratureConfig,
    integrand: Optional[Callable] = None,
    devices=None,
    callback: Optional[Callable[[int, float, float, float], None]] = None,
    recorder=None,
):
    """VEGAS with the sample shards sharded across ``devices`` (default all).

    Requires ``cfg.mc_shards % n_devices == 0``.  The state is replicated
    (it is a few KB of grid edges and scalars); only the sample evaluation
    is divided, which is where all the time goes.  Returns a
    :class:`~repro.mc.engine.VegasResult`.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.mc.engine import (
        _resolve_serial_fn,
        drive,
        integrate_vegas,
        make_iterate,
    )

    from repro.telemetry import NULL

    recorder = NULL if recorder is None else recorder
    cfg = cfg.validate()
    devices = list(jax.devices() if devices is None else devices)
    n_dev = len(devices)
    fn = _resolve_serial_fn(cfg, integrand)
    if n_dev == 1:
        return integrate_vegas(cfg, fn, callback, recorder=recorder)
    if cfg.mc_shards % n_dev:
        raise ValueError(
            f"mc_shards={cfg.mc_shards} must be divisible by the device "
            f"count ({n_dev}); shards are the unit of sample division"
        )
    mesh = jax.make_mesh((n_dev,), (AXIS,), devices=devices)
    body = make_iterate(cfg, fn, axis_name=AXIS, n_devices=n_dev)
    iterate = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(),),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )
    return drive(cfg, iterate, callback, recorder=recorder)


def main() -> None:
    """Parity selftest: ``python -m repro.mc.multi_device [n_devices]``.

    Runs every case single-device and at each device count in
    ``{2, n_devices}``, asserting bit-identical integral/error and
    device-count-invariant eval totals; prints one JSON blob.
    """
    import json
    import os
    import sys

    n_dev = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_dev} "
        + os.environ.get("XLA_FLAGS", "")
    )
    import jax

    jax.config.update("jax_enable_x64", True)

    from repro.core.integrands import get as get_integrand
    from repro.mc.engine import integrate_vegas

    assert len(jax.devices()) == n_dev, jax.devices()
    counts = sorted({2, n_dev} - {1})

    out = {"n_devices": n_dev, "device_counts": [1] + counts, "cases": []}
    cases = [
        ("genz_gaussian:5,5,5:0.5,0.3,0.7", 3, 1e-4),
        ("f6", 3, 1e-3),
        ("f4", 5, 1e-3),
    ]
    for name, d, tol in cases:
        cfg = QuadratureConfig(
            d=d,
            integrand=name,
            rel_tol=tol,
            backend="vegas",
            mc_samples=4096,
            mc_max_iters=30,
        )
        single = integrate_vegas(cfg)
        rec = {
            "integrand": name,
            "d": d,
            "integral": single.integral,
            "error": single.error,
            "status": single.status,
            "n_evals": single.n_evals,
            "chi2_dof": single.chi2_dof,
            "parity": [],
        }
        exact = get_integrand(name).exact(d)
        rec["rel_err"] = abs(single.integral - exact) / max(abs(exact), 1e-300)
        for p in counts:
            dist = integrate_vegas_distributed(cfg, devices=jax.devices()[:p])
            bit_identical = (
                dist.integral == single.integral
                and dist.error == single.error
                and dist.n_evals == single.n_evals
                and dist.iterations == single.iterations
            )
            rec["parity"].append(
                {
                    "devices": p,
                    "integral": dist.integral,
                    "error": dist.error,
                    "bit_identical": bool(bit_identical),
                }
            )
            assert bit_identical, (
                f"{name} d={d}: {p}-device result diverged from single "
                f"device: {dist.integral!r} vs {single.integral!r} "
                f"(error {dist.error!r} vs {single.error!r})"
            )
        out["cases"].append(rec)
    print("RESULT_JSON:" + json.dumps(out))


if __name__ == "__main__":
    main()
