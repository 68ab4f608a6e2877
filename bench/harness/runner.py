"""One run of one cell: set-up, the window, the metrics and the check.

:func:`run_cell` returns the result line as a dict, with the checks last.
``require_tpu=False`` is the dry path the harness's own tests take on the
CPU; a measurement always requires the chip.
"""

from __future__ import annotations

import sys

from harness import check as check_lib
from harness import loops, peaks
from harness.compiles import CompileCounter


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


def _devices(chips: int, require_tpu: bool):
    import jax

    visible = jax.devices()
    if require_tpu:
        if visible[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX computes on {visible[0].platform!r}")
        if len(visible) < chips:
            raise NoChip(f"the cell needs {chips} chips, {len(visible)} visible")
        peaks.lookup(visible[0].device_kind)  # an unknown chip is an error
    return visible[:chips]


def _peak_bytes(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def _metrics(metrics: list, run) -> dict:
    out = {}
    for m in metrics:
        value = m.reader.read(run)
        if value is not None:
            out[m.name] = {"value": value, "unit": m.unit}
    return out


def run_cell(
    cell,
    seed: int,
    seconds: float,
    trace: bool,
    t0: float,
    require_tpu: bool = True,
) -> dict:
    """Run ``cell`` once and return its result line.  The caller has
    enabled float64 (the configurations state it) and, for a measurement,
    the persistent compilation cache."""
    import jax

    if cell.config["quadrature"]["dtype"] == "float64" and not jax.config.read(
        "jax_enable_x64"
    ):
        raise RuntimeError("the configuration states float64 and x64 is off")
    devices = _devices(cell.chips, require_tpu)
    compiles = CompileCounter().install()

    run = loops.RunRecord(cell=cell, seed=seed, seconds=seconds,
                          device_kind=devices[0].device_kind)
    cell.loop.run(run, devices, trace, compiles, t0)
    memory_peak = _peak_bytes(devices)

    answers = cell.loop.answers(run)
    rel_tol = cell.config["quadrature"]["rel_tol"]
    checks = check_lib.compare(answers, rel_tol, cell.config["limits"])
    checks += getattr(cell.loop, "checks", lambda _: [])(run)
    print(f"bench: {check_lib.describe(answers)} window_compiles="
          f"{run.compiles_in_window} window_cache_loads={run.cache_loads_in_window}",
          file=sys.stderr)
    metrics = _metrics(cell.per_layer if trace else cell.end_to_end, run)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    result = {
        "correct": all(c.ok for c in checks),
        "attempted": len(answers),
        "failed": check_lib.failed(answers, rel_tol),
        "metrics": metrics,
        "device": device,
    }
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {
            "device_ops": run.trace.top_ops(10),
            "idle_gaps": run.trace.idle_by_host(10),
        }
    result["window"] = {
        "seconds": run.window_s,
        "compiles": run.compiles_in_window,
        "cache_loads": run.cache_loads_in_window,
        "solves_s": [s.end - s.start for s in run.solves],
    }
    result["checks"] = {
        c.name: {"value": c.value, "limit": c.limit} for c in checks
    }
    return result
