"""Shared helpers of the harness's tests: a copy of the benchmark in a
temporary directory, with tiny cells added only as files and entries."""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (BENCH, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

from harness.cells import Suite  # noqa: E402
from harness.runner import run_cell  # noqa: E402

#: tiny cells, each a configuration (a patch of a benchmark configuration,
#: or a whole file), a traffic mix and the metrics it reports
TINY = {
    "tiny_f4.solve": (
        "tiny_f4",
        {"base": "f4_d5", "quadrature": {"d": 3, "rel_tol": 1e-5, "capacity": 1 << 14}},
        "tiny_solve",
        {"loop": "solve"},
        ["solve_s", "solve.iterations", "solve.eval_s", "solve.advance_s"],
    ),
    "tiny_gauss.closed4": (
        "tiny_gauss",
        {"integrand": "genz_gaussian",
         "quadrature": {"d": 3, "integrand": "genz_gaussian", "rel_tol": 1e-4,
                        "dtype": "float64", "capacity": 1 << 12, "batch_slots": 4,
                        "max_iters": 300, "use_kernel": False},
         "theta": {"a": [3.0, 10.0], "u": [0.2, 0.8]},
         "limits": {"uncertified_share": 0.0}},
        "tiny_closed4",
        {"loop": "closed_fleet", "clients": 4, "warmup_requests": 4,
         "stall_seconds": 20, "drain_seconds": 20},
        ["fleet_req_per_s", "fleet_latency_p90_s", "fleet.occupancy"],
    ),
    "tiny_f4.ring4": (
        "tiny_f4",
        None,
        "tiny_ring4",
        {"loop": "solve_distributed", "quadrature": {"redistribution": "ring"}},
        ["solve_s", "solve.iterations"],
    ),
}

#: entries of the metrics whose readers no cell of ``BENCHMARK.json`` uses
#: yet (the fleet's and the four-chip ring's)
DRAFT_METRICS = {
    "fleet_req_per_s": {"unit": "req/s", "better": "higher", "bound": 0.25,
                        "source": "host_clock"},
    "fleet_latency_p90_s": {"unit": "s", "better": "lower", "bound": 0.25,
                            "source": "host_clock"},
    "fleet.occupancy": {"unit": "%", "better": "higher", "source": "host_clock",
                        "layer": "service", "moves": "fleet_req_per_s"},
    "fleet.device_idle": {"unit": "%", "better": "lower", "source": "device_trace",
                          "layer": "device", "moves": "fleet_req_per_s"},
    "ring4.collective_share": {"unit": "%", "better": "lower",
                               "source": "device_trace", "layer": "redistribution",
                               "moves": "solve_s"},
}


def tiny_suite(tmp) -> Suite:
    """The benchmark copied under ``tmp``, plus the tiny cells added as
    files and entries only."""
    root = str(tmp)
    os.makedirs(os.path.join(root, "bench"), exist_ok=True)
    for sub in ("configs", "traffic", "loops", "references", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(root, "bench", sub),
                        dirs_exist_ok=True)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    metrics = {m["name"]: m for m in spec["end_to_end"] + spec["per_layer"]}
    for name, entry in DRAFT_METRICS.items():
        if name not in metrics:
            metrics[name] = dict(entry, name=name, workloads=[])
            spec["end_to_end" if "bound" in entry else "per_layer"].append(metrics[name])
    for cell in TINY:
        config, body, traffic, traffic_body, reads = TINY[cell]
        if body is not None:
            if "base" in body:
                base = next(c for c in spec["configs"] if c["name"] == body["base"])
                with open(os.path.join(root, base["file"])) as f:
                    patched = json.load(f)
                patched["quadrature"].update(body["quadrature"])
                body = patched
            path = f"bench/configs/{config}.json"
            with open(os.path.join(root, path), "w") as f:
                json.dump(body, f)
            spec["configs"].append({"name": config, "source": "a tiny test size",
                                    "file": path, "reduced": [],
                                    "why": "a tiny cell of the harness's tests"})
        with open(os.path.join(root, "bench", "traffic", f"{traffic}.json"), "w") as f:
            json.dump(traffic_body, f)
        spec["workloads"].append(
            {"name": cell, "config": config, "traffic": traffic, "chips": 1,
             "why": "a tiny cell of the harness's tests"})
        for name in reads:
            metrics[name].setdefault("workloads", []).append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return Suite(root)


def run_dry(suite: Suite, cell: str, seconds: float = 0.5, trace: bool = False,
            seed: int = 2**31 + 11, quadrature=None, traffic=None) -> dict:
    """One run of ``cell`` on the CPU through the harness's dry path, with
    the configuration's and the traffic's fields overridden as given."""
    c = suite.cell(cell)
    c.config["quadrature"].update(quadrature or {})
    c.traffic.update(traffic or {})
    return run_cell(c, seed, seconds, trace, time.monotonic(), require_tpu=False)
