"""The benchmark's yardstick: cell lookup, traffic loops, trace reduction,
work counts, peak table and the correctness comparison.

Nothing in this package names a cell: a cell is an entry of
``BENCHMARK.json`` whose configuration, traffic mix, reference and metric
readers are files found by name (see :mod:`harness.cells`).
"""
