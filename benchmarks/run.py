"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes the normalized
``results/benchmarks/BENCH_summary.json`` the perf regression gate
(``python -m repro.perf.regress``) consumes.  Default mode runs reduced
grids; pass ``--full`` for the figure-scale grids and ``--roofline`` to
include the quadrature roofline sweep (:mod:`benchmarks.quad_roofline`:
measured machine terms + per-kernel cost catalog — not the retired LM sweep
in :mod:`benchmarks.roofline`).

Each module runs in a child process of its own (``--module NAME``), one at
a time, and this parent imports no JAX until every child has exited: a
chip belongs to one process, and several modules start children of their
own that need it.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
from datetime import datetime

MODULES = {
    "fig2a": "fig2a_runtime",
    "fig2b": "fig2b_accuracy",
    "fig3a": "fig3a_feasibility",
    "fig3b": "fig3b_speedup",
    "fig4a": "fig4a_scaling",
    "fig4b": "fig4b_idle",
    "kernel": "kernel_bench",
    "highdim": "highdim_feasibility",
    "eval_window": "eval_window",
    "iteration_window": "iteration_window",
    "batch_throughput": "batch_throughput",
    "sharded_service": "sharded_service",
}
_ROW = "ROW_JSON:"


def run_module(name: str, full: bool, run_date: str) -> None:
    """Child side: run one module in this process, print its rows."""
    import importlib

    from benchmarks import _common
    from repro import compile_cache

    compile_cache.enable()
    _common.RUN_DATE = run_date
    mod = importlib.import_module(f"benchmarks.{MODULES.get(name, name)}")
    for row in mod.rows(mod.run(fast=not full)):
        print(_ROW + json.dumps(list(row)), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None, help="comma-separated module names")
    ap.add_argument(
        "--roofline",
        action="store_true",
        help="include the quad_roofline sweep (machine probes + kernel "
        "cost catalog; refreshes results/perf/)",
    )
    ap.add_argument("--module", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--run-date", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.module:
        run_module(args.module, args.full, args.run_date)
        return

    # the runner owns the sweep timestamp: every module saved below carries
    # the same date in its results-file meta header
    run_date = datetime.now().astimezone().isoformat(timespec="seconds")
    names = list(MODULES) + (["quad_roofline"] if args.roofline else [])
    if args.only:
        keep = set(args.only.split(","))
        # --only quad_roofline works without also passing --roofline
        names = [n for n in [*MODULES, "quad_roofline"] if n in keep]

    print("name,us_per_call,derived")
    failures = 0
    summary: dict[str, float] = {}
    for name in names:
        cmd = [sys.executable, "-m", "benchmarks.run", "--module", name,
               "--run-date", run_date] + (["--full"] if args.full else [])
        proc = subprocess.run(cmd, stdout=subprocess.PIPE, text=True)
        for line in proc.stdout.splitlines():
            if not line.startswith(_ROW):
                continue
            row = json.loads(line[len(_ROW):])
            print(",".join(str(x) for x in row), flush=True)
            try:
                summary[str(row[0])] = float(row[1])
            except (TypeError, ValueError, IndexError):
                pass  # non-numeric wall column: skip from the gate
        if proc.returncode != 0:
            failures += 1
            print(f"{name},0,ERROR", flush=True)
    if summary:
        from benchmarks import _common

        _common.RUN_DATE = run_date
        path = _common.save_bench_summary(
            summary, meta={"modules": sorted(names), "full": args.full}
        )
        print(f"# BENCH_summary: {path}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
