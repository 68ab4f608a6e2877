"""Machine profiler: micro-benchmark the current device into a machine file.

A *machine file* is the measured half of the roofline model (DESIGN.md §9):
a small committed-able JSON document recording what the device this
container actually runs on can sustain —

- ``peak_flops``  — FLOP/s from a timed dense matmul in the working dtype
  (float64 here: the quadrature stack runs the paper's tolerances in f64;
  an f32 probe is recorded alongside for reference);
- ``mem_bw``      — bytes/s from a timed saxpy sweep (``y = a*x + y``:
  two reads + one write per element, the classic STREAM triad shape);
- ``reduce_bw``   — bytes/s from a timed full reduction (one read per
  element; reductions are the advance stage's dominant access pattern);
- ``ici_bw``      — bytes/s per link from a timed ``ppermute`` ring rotate
  when more than one device is visible, else ``None``.  On virtual CPU
  meshes this measures a host memcpy, which is still the honest number for
  what collectives cost *here*.

Every probe takes the best of ``reps`` timed repetitions — peak numbers
answer "what can the hardware do", so interference should push estimates
down, never up.

:data:`PRESETS` carries published vendor-sheet peaks, keyed by the
``device_kind`` JAX reports.  A machine file is used only on a device of
the kind it was measured on; a device with neither is an error, never a
default (:func:`resolve_machine`).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, Optional

_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)

#: default machine-file location (committed so CI and the report generator
#: share one baseline; re-run ``python -m repro.perf.machine`` to refresh)
DEFAULT_PATH = os.path.join(_REPO, "results", "perf", "machine.json")

#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.  TPU v5e
#: (Google Cloud documentation, "TPU v5e"): 197 TFLOP/s bf16, 16 GB HBM at
#: 819 GB/s, 50 GB/s ICI per link.  ``benchmarks/roofline.py`` carries the
#: same constants, pinned to this entry by ``tests/test_perf.py``.
PRESETS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "name": "v5e-preset",
        "source": "preset",
        "reference": "Google Cloud documentation, TPU v5e",
        "peak_flops": 197e12,
        "mem_bw": 819e9,
        "reduce_bw": 819e9,
        "ici_bw": 50e9,
    },
}


def _best_time(fn: Callable[[], Any], reps: int) -> float:
    """Best-of-``reps`` wall time of ``fn`` (one warmup call first)."""
    import jax

    jax.block_until_ready(fn())  # warmup: trace + compile + first dispatch
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _probe_matmul(n: int, dtype, reps: int) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    a = jnp.ones((n, n), dtype)
    b = jnp.ones((n, n), dtype)
    f = jax.jit(lambda x, y: x @ y)
    t = _best_time(lambda: f(a, b), reps)
    return {"n": n, "seconds": t, "flops_per_s": 2.0 * n**3 / t}


def _probe_saxpy(n: int, dtype, reps: int) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    x = jnp.ones((n,), dtype)
    y = jnp.ones((n,), dtype)
    f = jax.jit(lambda a, b: 2.0 * a + b)
    t = _best_time(lambda: f(x, y), reps)
    itemsize = jnp.dtype(dtype).itemsize
    return {
        "n": n,
        "seconds": t,
        # two operand reads + one result write per element
        "bytes_per_s": 3.0 * n * itemsize / t,
    }


def _probe_reduction(n: int, dtype, reps: int) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    x = jnp.ones((n,), dtype)
    f = jax.jit(jnp.sum)
    t = _best_time(lambda: f(x), reps)
    return {"n": n, "seconds": t, "bytes_per_s": n * jnp.dtype(dtype).itemsize / t}


def _probe_ici(n: int, dtype, reps: int) -> Optional[Dict[str, float]]:
    """Ring-rotate an ``(n,)`` buffer across all visible devices.

    Returns ``None`` on a single device.  The per-link payload is the whole
    buffer (every device sends its shard to its neighbour simultaneously).
    """
    import jax
    import jax.numpy as jnp

    devs = jax.devices()
    if len(devs) < 2:
        return None
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((len(devs),), ("probe",), devices=devs)
    perm = [(i, (i + 1) % len(devs)) for i in range(len(devs))]

    def rotate(x):
        return jax.lax.ppermute(x, "probe", perm)

    f = jax.jit(
        jax.shard_map(rotate, mesh=mesh, in_specs=P("probe"), out_specs=P("probe"))
    )
    x = jnp.ones((n * len(devs),), dtype)
    t = _best_time(lambda: f(x), reps)
    itemsize = jnp.dtype(dtype).itemsize
    return {
        "n_per_device": n,
        "devices": len(devs),
        "seconds": t,
        "bytes_per_s": n * itemsize / t,
    }


def profile_machine(
    fast: bool = True,
    *,
    matmul_n: Optional[int] = None,
    stream_n: Optional[int] = None,
    reps: Optional[int] = None,
) -> Dict[str, Any]:
    """Measure the current device into a machine dict (see module docstring).

    ``fast`` picks probe sizes that finish in a few seconds on this CPU
    container; ``fast=False`` quadruples the working sets for steadier
    numbers.  The explicit size/rep overrides exist for tests.
    """
    import jax
    import jax.numpy as jnp

    mm_n = matmul_n or (768 if fast else 1536)
    st_n = stream_n or ((1 << 23) if fast else (1 << 25))
    n_reps = reps or (3 if fast else 10)

    f64 = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    matmul64 = _probe_matmul(mm_n, f64, n_reps)
    matmul32 = _probe_matmul(mm_n, jnp.float32, n_reps)
    saxpy = _probe_saxpy(st_n, f64, n_reps)
    reduction = _probe_reduction(st_n, f64, n_reps)
    ici = _probe_ici(min(st_n, 1 << 21), f64, n_reps)

    return {
        "name": "measured",
        "source": "measured",
        "meta": _collect_meta(),
        "working_dtype": str(jnp.dtype(f64)),
        "peak_flops": matmul64["flops_per_s"],
        "mem_bw": saxpy["bytes_per_s"],
        "reduce_bw": reduction["bytes_per_s"],
        "ici_bw": None if ici is None else ici["bytes_per_s"],
        "probes": {
            "matmul_f64": matmul64,
            "matmul_f32": matmul32,
            "saxpy": saxpy,
            "reduction": reduction,
            "ici_ppermute": ici,
        },
    }


def _collect_meta() -> Dict[str, Any]:
    """Provenance for a machine file (mirrors benchmarks/_common meta)."""
    meta: Dict[str, Any] = {
        "jax_version": None,
        "platform": None,
        "device_kind": None,
        "device_count": None,
    }
    try:
        import jax

        devices = jax.devices()
        meta["jax_version"] = jax.__version__
        meta["platform"] = devices[0].platform
        meta["device_kind"] = devices[0].device_kind
        meta["device_count"] = len(devices)
    except Exception:  # noqa: BLE001 — provenance must never fail a probe
        pass
    return meta


def save_machine(machine: Dict[str, Any], path: str = DEFAULT_PATH) -> str:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(machine, f, indent=1)
        f.write("\n")
    return path


def load_machine(path: str = DEFAULT_PATH) -> Optional[Dict[str, Any]]:
    """Load a machine file; ``None`` when absent (callers fall back)."""
    if not os.path.exists(path):
        return None
    with open(path) as f:
        machine = json.load(f)
    for key in ("peak_flops", "mem_bw"):
        if key not in machine:
            raise ValueError(
                f"{path} is not a machine file: missing {key!r} "
                "(regenerate with `python -m repro.perf.machine`)"
            )
    return machine


def _device_kind() -> str:
    """``device_kind`` of the device JAX computes on by default."""
    import jax

    return jax.devices()[0].device_kind


def resolve_machine(path: Optional[str] = None) -> Dict[str, Any]:
    """The machine terms to predict with on the running device.

    This is the single resolution rule shared by the catalog, the report,
    ``benchmarks/kernel_bench.py`` and ``benchmarks/roofline.py``.  A machine
    file counts only on the device kind it was measured on (its
    ``meta.device_kind``): an explicit ``path`` must exist and match; the
    committed default file is used when it matches and skipped otherwise.
    Without a matching file the :data:`PRESETS` entry for the running device
    kind is used, and a device with neither is an error — peaks assumed for
    another device would poison every prediction.
    """
    kind = _device_kind()
    if path is not None:
        machine = load_machine(path)
        if machine is None:
            raise FileNotFoundError(
                f"machine file {path} not found; generate one with "
                "`python -m repro.perf.machine --out " + path + "`"
            )
        measured_on = machine.get("meta", {}).get("device_kind")
        if measured_on != kind:
            raise ValueError(
                f"machine file {path} was measured on {measured_on!r}, but "
                f"this process runs on {kind!r}; measure this device with "
                "`python -m repro.perf.machine --out <path>`"
            )
        return machine
    machine = load_machine(DEFAULT_PATH)
    if machine is not None and machine.get("meta", {}).get("device_kind") == kind:
        return machine
    if kind in PRESETS:
        return dict(PRESETS[kind])
    raise ValueError(
        f"no machine file and no preset for device kind {kind!r}; measure "
        "it with `python -m repro.perf.machine`"
    )


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Micro-benchmark this device into a machine file."
    )
    ap.add_argument("--out", default=DEFAULT_PATH)
    ap.add_argument("--full", action="store_true", help="larger probe sizes")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_enable_x64", True)
    machine = profile_machine(fast=not args.full)
    path = save_machine(machine, args.out)
    ici = machine["ici_bw"]
    print(f"wrote {path}")
    print(
        f"  peak_flops = {machine['peak_flops']:.3e} FLOP/s  "
        f"mem_bw = {machine['mem_bw']:.3e} B/s  "
        f"reduce_bw = {machine['reduce_bw']:.3e} B/s  "
        f"ici_bw = {'n/a (1 device)' if ici is None else f'{ici:.3e} B/s'}"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
