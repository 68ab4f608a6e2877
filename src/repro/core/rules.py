"""Pluggable quadrature-rule layer (paper: "accommodates multiple rules")."""

from __future__ import annotations

from typing import Callable, Optional, Protocol

import jax
import jax.numpy as jnp

from repro.core import gauss_kronrod, genz_malik
from repro.core.config import QuadratureConfig
from repro.core.error import two_level_error
from repro.core.integrands import get as get_integrand, parse_spec


class Rule(Protocol):
    n_evals_per_region: int

    def eval_batch(
        self, centers: jnp.ndarray, halfw: jnp.ndarray
    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """(B, d) regions -> (est, err, split_axis) each of shape (B,)."""
        ...


def _select_axis(diffs: jnp.ndarray, halfw: jnp.ndarray) -> jnp.ndarray:
    """argmax fourth-difference; fall back to widest axis when flat."""
    eps = jnp.finfo(diffs.dtype).eps
    best = jnp.argmax(diffs, axis=-1).astype(jnp.int32)
    widest = jnp.argmax(halfw, axis=-1).astype(jnp.int32)
    flat = jnp.max(diffs, axis=-1) <= eps * 100.0
    return jnp.where(flat, widest, best)


class GenzMalikRule:
    """Degree-7 GM rule + two-level error + fourth-difference axis choice.

    ``theta`` switches the rule into ParamIntegrand-family mode: ``integrand``
    is then a family function ``f(x, theta)`` and theta may be a traced value
    (the batch service vmaps it over the problem axis).  On the kernel path
    theta enters ``pallas_call`` as a broadcast operand (see ``kernels.ops``)
    rather than a closure, which is what makes the fused kernel usable for
    families at all.
    """

    def __init__(
        self,
        d: int,
        integrand: Callable[..., jnp.ndarray],
        noise_mult: float = 50.0,
        use_kernel: bool = False,
        interpret: Optional[bool] = None,  # None: kernels.ops.resolve_interpret
        block_regions: int = 0,  # 0 = kernels.ops.DEFAULT_BLOCK_REGIONS
        theta=None,
    ):
        self.d = d
        self.f = integrand
        self.theta = theta
        self.noise_mult = noise_mult
        self.use_kernel = use_kernel
        self.interpret = interpret
        self.block_regions = block_regions
        self.n_evals_per_region = genz_malik.n_nodes(d)

    def eval_batch(self, centers, halfw):
        if self.use_kernel:
            from repro.kernels import ops as kernel_ops

            i7, i5, i3, diffs = kernel_ops.genz_malik_eval(
                self.f,
                centers,
                halfw,
                theta=self.theta,
                interpret=self.interpret,
                block_regions=self.block_regions,
            )
        else:
            f = (
                self.f
                if self.theta is None
                else lambda x: self.f(x, self.theta)
            )
            i7, i5, i3, diffs = genz_malik.gm_eval_reference(f, centers, halfw)
        vol = jnp.prod(2.0 * halfw, axis=-1)
        maxdiff = jnp.max(diffs, axis=-1)
        err = two_level_error(i7, i5, i3, vol, maxdiff, self.noise_mult)
        axis = _select_axis(diffs, halfw)
        return i7, err, axis


class GaussKronrodRule:
    """Tensor-product (G7, K15); cost 15^d — low/moderate d only (paper)."""

    def __init__(
        self,
        d: int,
        integrand: Callable[[jnp.ndarray], jnp.ndarray],
        chunk: int = 512,
        safety: float = 1.0,
    ):
        if d > 6:
            raise ValueError(
                f"tensor Gauss-Kronrod is prohibitive for d={d} (15^d nodes); "
                "the paper restricts it to low/moderate dimensions"
            )
        self.d = d
        self.f = integrand
        self.chunk = chunk
        self.safety = safety
        self.n_evals_per_region = gauss_kronrod.n_nodes(d)

    def eval_batch(self, centers, halfw):
        i_k, i_g, axis_disc = gauss_kronrod.gk_eval_batch(
            self.f, centers, halfw, chunk=self.chunk
        )
        err = self.safety * jnp.abs(i_k - i_g)
        # round-off floor
        eps = jnp.finfo(i_k.dtype).eps
        vol = jnp.prod(2.0 * halfw, axis=-1)
        err = jnp.maximum(err, 50.0 * eps * (jnp.abs(i_k) + vol))
        axis = _select_axis(axis_disc, halfw)
        return i_k, err, axis


def make_rule(
    cfg: QuadratureConfig, integrand=None, theta=None, platform: Optional[str] = None
) -> Rule:
    """Build the configured rule.

    ``platform`` is that of the devices that run the rule (the default
    backend when not given).  It resolves ``cfg.interpret=None`` once, here:
    the Pallas interpreter on CPU, Mosaic elsewhere.  Mosaic has no float64,
    so the kernel in float64 on a TPU is refused with the fix in the message.

    ``integrand`` overrides the config-named integrand with a plain callable
    ``f(x)``; passing ``theta`` as well marks it a ParamIntegrand family
    function ``f(x, theta)`` whose coefficients may be traced values (the
    batch service's per-slot theta).  A config-named family spec (e.g.
    ``"genz_gaussian:5,5:0.3,0.7"``) on the kernel path is parsed into the
    same (family fn, theta) pair so the fused kernel receives theta as an
    operand instead of a rejected captured constant.
    """
    if theta is not None and integrand is None:
        raise ValueError("theta requires an explicit family integrand")
    if integrand is not None:
        f = integrand
    elif cfg.use_kernel and ":" in cfg.integrand:
        # Family specs close over theta coefficient arrays when bound via
        # integrands.get(); the kernel path instead feeds theta through the
        # operand protocol of kernels.ops.genz_malik_eval.
        family, theta = parse_spec(cfg.integrand)
        f = family.fn
    else:
        f = get_integrand(cfg.integrand).fn
    if cfg.rule == "genz_malik":
        interpret = cfg.interpret
        if cfg.use_kernel:
            from repro.kernels.ops import resolve_interpret

            platform = platform or jax.default_backend()
            interpret = resolve_interpret(interpret, platform)
            if (
                platform == "tpu"
                and not interpret
                and jnp.dtype(cfg.dtype) == jnp.float64
            ):
                raise ValueError(
                    "use_kernel=True compiles the GM kernel through Mosaic, "
                    "which has no float64: pass dtype=\"float32\" to keep "
                    "the kernel, or use_kernel=False to stay in float64"
                )
        return GenzMalikRule(
            cfg.d,
            f,
            noise_mult=cfg.noise_mult,
            use_kernel=cfg.use_kernel,
            interpret=interpret,
            block_regions=cfg.block_regions,
            theta=theta,
        )
    if cfg.rule == "gauss_kronrod":
        if theta is not None:
            fam_f = f
            bound_theta = theta
            f = lambda x: fam_f(x, bound_theta)  # noqa: E731
        return GaussKronrodRule(cfg.d, f)
    raise ValueError(f"unknown rule {cfg.rule!r}")
