#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json``.  The cell,
its configuration, traffic mix, loop, reference and metric readers are found by
name (``bench/harness/cells.py``).  With ``--trace 0`` the result carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read in
a run with the profiler and the program's spans on.  The numbers compared
for ``correct`` are printed beside their limits as the last lines on
standard error and under ``checks``, last, in the result.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.  JAX's persistent compilation cache is kept in
``.jax_cache`` at the root of the checkout, so only a cell's first run
there compiles.
"""

import time

T0 = time.monotonic()  # set-up is timed from here, before JAX loads

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None, quadrature=None) -> int:
    """Run the cell; ``quadrature`` overrides fields of its configuration's
    solver settings (``bench/control.py`` runs the float32 control so)."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import jax

    jax.config.update("jax_enable_x64", True)
    from harness.cells import Suite
    from harness.runner import NoChip, run_cell
    from repro import compile_cache

    compile_cache.enable()

    cell = Suite(ROOT).cell(args.workload)
    cell.config["quadrature"].update(quadrature or {})
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace), T0)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
