"""GM Pallas kernel benchmark: kernel timing vs the pure-jnp oracle + the
analytic arithmetic intensity of the kernel against the running device's
ridge point (peaks from :func:`repro.perf.machine.resolve_machine`).

The kernel runs as the platform needs it (``kernels.ops.resolve_interpret``):
the Pallas interpreter on CPU, Mosaic on a TPU.  Interpreter timings say
nothing about the chip."""

import time

import jax
import numpy as np


def run(fast: bool = True):
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    from repro.core import integrands
    from repro.core.genz_malik import n_nodes
    from repro.kernels import ops
    from repro.kernels.ref import genz_malik_eval_soa_ref
    from repro.perf.machine import resolve_machine

    machine = resolve_machine()
    ridge = machine["peak_flops"] / machine["mem_bw"]  # flops/byte
    interpret = ops.resolve_interpret(None)
    # Mosaic has no float64
    dtype = jnp.float64 if interpret else jnp.float32
    out = []
    dims = (3, 5) if fast else (2, 3, 5, 8, 10)
    b = 1024 if fast else 4096
    rng = np.random.default_rng(0)
    f = integrands.get("f4").fn
    for d in dims:
        centers = jnp.asarray(rng.uniform(0.1, 0.9, (b, d)), dtype)
        halfw = jnp.asarray(rng.uniform(0.01, 0.1, (b, d)), dtype)

        k_fn = jax.jit(lambda c, h: ops.genz_malik_eval(f, c, h)[0])
        r_fn = jax.jit(lambda c, h: genz_malik_eval_soa_ref(f, c.T, h.T)[0])
        k_fn(centers, halfw).block_until_ready()
        r_fn(centers, halfw).block_until_ready()
        t0 = time.time(); k_fn(centers, halfw).block_until_ready(); tk = time.time() - t0
        t0 = time.time(); r_fn(centers, halfw).block_until_ready(); tr = time.time() - t0

        # analytic kernel arithmetic intensity vs the device's ridge point
        nodes = n_nodes(d)
        flops_per_region = nodes * (6 * d + 4) + 8 * nodes  # node gen + f4 + sums
        itemsize = jnp.dtype(dtype).itemsize
        bytes_per_region = (2 * d + 3 + d) * itemsize  # c,h in; i7,i5,i3,diffs out
        intensity = flops_per_region / bytes_per_region
        out.append(
            {
                "d": d,
                "batch": b,
                "n_nodes": nodes,
                "interpret": interpret,
                "dtype": str(jnp.dtype(dtype)),
                "kernel_us": tk * 1e6,
                "ref_us": tr * 1e6,
                "arith_intensity": intensity,
                "ridge_flops_per_byte": ridge,
                "compute_bound": intensity > ridge,
            }
        )
    from benchmarks._common import save_results

    save_results("kernel_bench", out)
    return out


def rows(recs):
    for r in recs:
        yield (
            f"kernel/gm_d{r['d']}_b{r['batch']}",
            r["kernel_us"],
            f"intensity={r['arith_intensity']:.0f};compute_bound={r['compute_bound']}"
            f";interpret={r['interpret']}",
        )


if __name__ == "__main__":
    for row in rows(run(fast=False)):
        print(",".join(str(x) for x in row))
