"""Pallas GM kernel vs pure-jnp oracle: shape/dtype/block sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import integrands
from repro.kernels import ops
from repro.kernels.ref import genz_malik_eval_soa_ref


def _random_regions(rng, b, d, dtype):
    centers = rng.uniform(0.1, 0.9, (b, d)).astype(dtype)
    halfw = rng.uniform(0.01, 0.1, (b, d)).astype(dtype)
    return jnp.asarray(centers), jnp.asarray(halfw)


@pytest.mark.parametrize("d", [1, 2, 3, 5, 8])
@pytest.mark.parametrize("b", [64, 256])
def test_kernel_matches_ref_shapes(d, b):
    rng = np.random.default_rng(d * 100 + b)
    centers, halfw = _random_regions(rng, b, d, np.float64)
    f = integrands.get("f4").fn

    i7k, i5k, i3k, dk = ops.genz_malik_eval(f, centers, halfw, interpret=True)
    i7r, i5r, i3r, dr = genz_malik_eval_soa_ref(f, centers.T, halfw.T)

    np.testing.assert_allclose(i7k, i7r, rtol=1e-12, atol=1e-300)
    np.testing.assert_allclose(i5k, i5r, rtol=1e-12, atol=1e-300)
    np.testing.assert_allclose(i3k, i3r, rtol=1e-12, atol=1e-300)
    # fourth differences are differences of near-equal tiny numbers; compare
    # at a scale-relative absolute tolerance
    np.testing.assert_allclose(
        dk, dr.T, rtol=1e-8, atol=float(np.max(np.abs(dr))) * 1e-10
    )


@pytest.mark.parametrize("name", ["f1", "f2", "f3", "f5", "f6", "f7"])
def test_kernel_matches_ref_integrands(name):
    rng = np.random.default_rng(7)
    d, b = 4, 128
    centers, halfw = _random_regions(rng, b, d, np.float64)
    f = integrands.get(name).fn
    i7k, *_ = ops.genz_malik_eval(f, centers, halfw, interpret=True)
    i7r, *_ = genz_malik_eval_soa_ref(f, centers.T, halfw.T)
    np.testing.assert_allclose(i7k, i7r, rtol=1e-12, atol=1e-300)


@pytest.mark.parametrize("dtype,rtol", [(np.float32, 1e-3), (np.float64, 1e-12)])
def test_kernel_dtypes(dtype, rtol):
    rng = np.random.default_rng(3)
    d, b = 3, 128
    centers, halfw = _random_regions(rng, b, d, dtype)
    f = integrands.get("f1").fn
    i7k, *_ = ops.genz_malik_eval(f, centers, halfw, interpret=True)
    assert i7k.dtype == dtype
    # compare against the float64 oracle
    i7r, *_ = genz_malik_eval_soa_ref(
        f, centers.T.astype(np.float64), halfw.T.astype(np.float64)
    )
    np.testing.assert_allclose(i7k, i7r, rtol=rtol)


@pytest.mark.parametrize("block", [32, 64, 128, 512])
def test_kernel_block_sizes(block):
    rng = np.random.default_rng(11)
    d, b = 3, 192  # not a multiple of most blocks -> exercises padding
    centers, halfw = _random_regions(rng, b, d, np.float64)
    f = integrands.get("f3").fn
    i7k, i5k, _, dk = ops.genz_malik_eval(
        f, centers, halfw, block_regions=block, interpret=True
    )
    i7r, i5r, _, dr = genz_malik_eval_soa_ref(f, centers.T, halfw.T)
    np.testing.assert_allclose(i7k, i7r, rtol=1e-12)
    np.testing.assert_allclose(i5k, i5r, rtol=1e-12)
    assert dk.shape == (b, d)


def test_rule_with_kernel_integrates():
    """End-to-end: adaptive driver with the kernel path enabled."""
    from repro.core.adaptive import integrate
    from repro.core.config import QuadratureConfig

    cfg = QuadratureConfig(
        d=3, integrand="f4", rel_tol=1e-6, capacity=1 << 12, use_kernel=True
    )
    res = integrate(cfg)
    exact = integrands.get("f4").exact(3)
    assert res.status == "converged"
    assert abs(res.integral - exact) / abs(exact) <= 5e-6


# --- ParamIntegrand families through the theta-operand kernel path ------------


@pytest.mark.parametrize(
    "name", ["genz_gaussian", "genz_product_peak", "monomial"]
)
@pytest.mark.parametrize("d", [2, 3, 5])
def test_theta_kernel_matches_ref_families(name, d):
    """Kernel with theta as an operand vs the jnp oracle with theta closed
    over — agreement at the repo's kernel/oracle tolerance (the two are
    separately compiled programs, so last-ulp FMA-contraction differences
    are expected exactly as for the fixed integrands above)."""
    rng = np.random.default_rng(d * 10 + len(name))
    fam = integrands.get_param(name)
    theta = fam.sample_theta(d, rng)
    centers, halfw = _random_regions(rng, 192, d, np.float64)
    i7k, i5k, i3k, dk = ops.genz_malik_eval(
        fam.fn, centers, halfw, theta=theta, interpret=True
    )
    i7r, i5r, i3r, dr = genz_malik_eval_soa_ref(
        lambda x: fam.fn(x, theta), centers.T, halfw.T
    )
    np.testing.assert_allclose(i7k, i7r, rtol=1e-12, atol=1e-300)
    np.testing.assert_allclose(i5k, i5r, rtol=1e-12, atol=1e-300)
    np.testing.assert_allclose(i3k, i3r, rtol=1e-12, atol=1e-300)
    # fourth differences can sit entirely at rounding noise (low-degree
    # monomials are near-exact for the embedded rules): compare at a
    # scale-relative tolerance with an eps-level absolute floor
    np.testing.assert_allclose(
        dk,
        dr.T,
        rtol=1e-8,
        atol=float(np.max(np.abs(np.asarray(dr)))) * 1e-10 + 1e-14,
    )


def test_make_rule_accepts_family_spec_with_kernel():
    """The family-spec rejection is gone: the kernel path parses the spec
    and feeds theta through the operand protocol."""
    from repro.core.config import QuadratureConfig
    from repro.core.rules import make_rule

    cfg = QuadratureConfig(
        d=2, integrand="genz_gaussian:5,5:0.3,0.7", use_kernel=True
    )
    rule = make_rule(cfg)
    assert rule.theta is not None
    rng = np.random.default_rng(0)
    centers, halfw = _random_regions(rng, 64, 2, np.float64)
    est, err, axis = rule.eval_batch(centers, halfw)
    assert est.shape == (64,)
    assert np.all(np.asarray(err) >= 0)


def test_kernel_family_spec_integrates_to_exact():
    """End-to-end serial driver on a family spec with the fused kernel."""
    from repro.core.adaptive import integrate
    from repro.core.config import QuadratureConfig

    spec = "genz_gaussian:6,4:0.3,0.7"
    cfg = QuadratureConfig(
        d=2, integrand=spec, rel_tol=1e-7, capacity=1 << 10, use_kernel=True
    )
    res = integrate(cfg)
    exact = integrands.get(spec).exact(2)
    assert res.status == "converged"
    assert abs(res.integral - exact) / abs(exact) <= 5e-7


@pytest.mark.parametrize("name", ["genz_gaussian", "genz_product_peak", "monomial"])
def test_batch_engine_kernel_path_matches_serial(name):
    """The service's vmapped kernel path is bit-identical to the serial
    kernel driver (theta through the operand protocol on both sides) — the
    parity guarantee continuous batching promises, now for use_kernel=True."""
    from repro.core.adaptive import integrate
    from repro.core.config import QuadratureConfig
    from repro.service.api import integrate_batch

    fam = integrands.get_param(name)
    rng = np.random.default_rng(17)
    thetas = [fam.sample_theta(2, rng) for _ in range(3)]
    base = dict(
        d=2, integrand=name, rel_tol=1e-6, capacity=1 << 9, batch_slots=2,
        max_iters=80, use_kernel=True,
    )
    results = integrate_batch(QuadratureConfig(**base), thetas, fam)
    for theta, r in zip(thetas, results):
        spec = name + ":" + ":".join(
            ",".join(repr(float(v)) for v in theta[k]) for k in fam.theta_fields
        )
        serial = integrate(QuadratureConfig(**{**base, "integrand": spec}))
        assert r.status == serial.status
        assert r.iterations == serial.iterations
        assert r.integral == serial.integral
        assert r.error == serial.error
        assert r.n_evals == serial.n_evals


@pytest.mark.parametrize("platform,want", [("cpu", True), ("tpu", False)])
def test_interpret_resolves_from_platform(platform, want):
    """interpret=None means "interpret iff the rule runs on CPU", resolved
    once in make_rule; an explicit setting wins."""
    from repro.core.config import QuadratureConfig
    from repro.core.rules import make_rule

    assert ops.resolve_interpret(None, platform) is want
    assert ops.resolve_interpret(not want, platform) is (not want)
    cfg = QuadratureConfig(d=2, use_kernel=True, dtype="float32")
    assert cfg.interpret is None
    assert make_rule(cfg, platform=platform).interpret is want
