"""Distributed adaptive quadrature with round-robin load redistribution.

Integrates a discontinuous integrand whose work concentrates on a few ranks
over every visible device, and prints the per-device balance with
redistribution ON vs OFF.

Run on a multi-chip host:
  PYTHONPATH=src python examples/distributed_quadrature.py
On CPU, emulate a mesh first:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/distributed_quadrature.py
"""


def main() -> None:
    import jax

    jax.config.update("jax_enable_x64", True)
    import numpy as np

    from repro.core.config import QuadratureConfig
    from repro.core.distributed import integrate_distributed
    from repro.core.integrands import get

    devices = jax.devices()
    print(f"devices: {len(devices)}")
    base = dict(d=4, integrand="f6", rel_tol=1e-6, capacity=1 << 13, max_iters=200)
    for redis in ("ring", "off"):
        cfg = QuadratureConfig(redistribution=redis, **base)
        res = integrate_distributed(cfg, devices=devices)
        exact = get("f6").exact(4)
        share = res.evals_per_device / max(res.n_evals, 1)
        print(
            f"redistribution={redis:3}: {res.summary()}\n"
            f"   true rel err {abs(res.integral-exact)/exact:.2e}; "
            f"mean work imbalance {res.mean_imbalance():.3f}; "
            f"per-device eval share {np.array2string(share, precision=3)}"
        )


if __name__ == "__main__":
    main()
