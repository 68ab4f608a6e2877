"""Compile the main-path programs for a described TPU v5e, without a chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described rather than attached, so these tests catch what interpret-mode
tests cannot: a kernel Mosaic refuses to lower, or a program that does not
fit the chip's 16 GB.  Nothing runs, so nothing here is a timing.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports every test file.  All such compiles stay in this one file so the
worker that loads the library is the one that runs them.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import region_store
from repro.core.adaptive import make_eval_step
from repro.core.config import QuadratureConfig
from repro.core.integrands import get, get_param
from repro.core.rules import make_rule
from repro.kernels import ops
from repro.kernels.genz_malik_eval import genz_malik_eval_soa

HBM_BYTES = 16 * 10**9  # one v5e chip
REGIONS = 1 << 16
BLOCK = 512


@pytest.fixture(scope="module")
def v5e():
    """The devices of a described v5e 2x2 host (four chips)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e[0])


@pytest.fixture(scope="module")
def four_chips(v5e):
    from jax.sharding import Mesh

    return Mesh(np.array(v5e[:4]), ("dev",))


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("d", [5, 8])
def test_gm_kernel_lowers_through_mosaic(one_chip, d):
    f = get("f4").fn
    soa = _spec(one_chip, (d, REGIONS), jnp.float32)
    fn = jax.jit(
        lambda c, h: genz_malik_eval_soa(f, c, h, block_regions=BLOCK, interpret=False)
    )
    compiled = fn.lower(soa, soa).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("family", ["genz_gaussian", "genz_product_peak"])
def test_gm_kernel_theta_operand_lowers_through_mosaic(one_chip, family):
    d = 5
    fam = get_param(family)
    theta = fam.sample_theta(d, np.random.default_rng(0))
    theta_spec = jax.tree.map(
        lambda x: _spec(one_chip, np.shape(x), jnp.float32), theta
    )
    aos = _spec(one_chip, (REGIONS, d), jnp.float32)
    fn = jax.jit(
        lambda c, h, t: ops.genz_malik_eval(fam.fn, c, h, theta=t, interpret=False)
    )
    compiled = fn.lower(aos, aos, theta_spec).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_f64_eval_step_at_capacity_2_20_fits_one_chip(one_chip):
    """The single-integral eval step at its top rung, in float64 with the
    jnp rule (the chip's default path), compiles and fits in HBM."""
    cfg = QuadratureConfig(d=5, integrand="f4", capacity=1 << 20)
    rule = make_rule(cfg)
    state = jax.eval_shape(
        lambda: region_store.init_state(
            cfg.capacity, np.zeros(5), np.ones(5), cfg.resolved_n_init(), jnp.float64
        )
    )
    state = jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype), state)
    step = jax.jit(make_eval_step(cfg, rule, window=cfg.capacity), donate_argnums=0)
    assert 0 < _bytes_per_chip(step.lower(state).compile()) < HBM_BYTES


def _bytes_per_chip(compiled) -> int:
    mem = compiled.memory_analysis()
    return (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        - mem.alias_size_in_bytes
        + mem.temp_size_in_bytes
    )


def _sharded(tree, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    shard = NamedSharding(mesh, P("dev"))
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=shard), tree
    )


def test_distributed_rung_program_compiles_for_four_chips(four_chips):
    """The ring-redistributing SPMD step of ``integrate_distributed`` (f4,
    d=5, 2^20 regions per chip, float64) at its first rung: it compiles for
    a 2x2 mesh, with its collectives, and fits each chip."""
    from repro.core.distributed import _stacked_initial_state, dist_program

    cfg = QuadratureConfig(
        d=5, integrand="f4", rel_tol=1e-7, capacity=1 << 20, redistribution="ring"
    )
    state = jax.eval_shape(lambda: _stacked_initial_state(cfg, 4, jnp.float64))
    program = dist_program(
        cfg, make_rule(cfg, platform="tpu"), four_chips, 1.0, np.ones(5), 256
    )
    compiled = program.lower(_sharded(state, four_chips)).compile()
    text = compiled.as_text()
    assert "all-reduce" in text and "collective-permute" in text
    assert 0 < _bytes_per_chip(compiled) < HBM_BYTES


def test_sharded_service_run_compiles_for_four_chips(four_chips):
    """The fused dispatch of the service sharded over a 2x2 mesh (64 slots
    of 2^16 regions, genz_gaussian, d=5, float64) at its first rung."""
    from repro.service.batch_engine import BatchEngine

    cfg = QuadratureConfig(
        d=5, integrand="genz_gaussian", rel_tol=1e-5, capacity=1 << 16,
        batch_slots=64,
    )
    engine = BatchEngine(cfg, mesh=four_chips)
    state = _sharded(jax.eval_shape(engine.init), four_chips)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    run = jax.jit(engine._make_run(256), donate_argnums=0)
    compiled = run.lower(state, scalar, scalar).compile()
    assert "collective-permute" in compiled.as_text()  # slot migration
    assert 0 < _bytes_per_chip(compiled) < HBM_BYTES


def test_kernel_in_float64_on_tpu_is_refused(monkeypatch):
    """Mosaic has no float64: the rule refuses it up front, naming the fix,
    instead of failing deep inside the TPU compiler."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="float32"):
        make_rule(QuadratureConfig(d=5, use_kernel=True))
    # float32, or no kernel, is accepted on the same platform
    assert not make_rule(
        QuadratureConfig(d=5, use_kernel=True, dtype="float32")
    ).interpret
    make_rule(QuadratureConfig(d=5))
