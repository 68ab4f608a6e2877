#!/usr/bin/env python3
"""Run a cell's control on the chip: the cell's own loop, size and load,
with the program computing in float32, the precision below the float64
that the configurations state.  Its result line must read
``"correct": false``; ``PERF.md`` gives the readings.

  python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

The benchmark's own runs never run it.
"""

import time

T0 = time.monotonic()

import sys  # noqa: E402

import run  # noqa: E402

if __name__ == "__main__":
    run.T0 = T0
    sys.exit(run.main(quadrature={"dtype": "float32"}))
