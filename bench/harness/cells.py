"""Finds a cell's parts by name.

``BENCHMARK.json`` at the root names each cell's configuration and traffic
mix, and each metric.  The parts are files:

- ``bench/configs/<name>.json`` (the path the configuration entry gives):
  the deployment, as :class:`repro.core.QuadratureConfig` fields under
  ``quadrature``, the integrand under ``integrand`` and, for a family of
  integrands, the ranges its parameters are drawn from under ``theta``;
- ``bench/traffic/<traffic>.json``: the traffic's parameters, and under
  ``loop`` the name of the loop that drives the program with them;
- ``bench/loops/<loop>.py``: that loop, a ``run`` and an ``answers`` (and
  optionally ``checks``), as :mod:`harness.loops` sets out;
- ``bench/references/<integrand>.py``: the plain reference, an analytic
  ``exact`` and the integrand's ``flops_per_point``;
- ``bench/metrics/<metric>.py``: a ``read(run)`` that returns the metric's
  value from a :class:`harness.loops.RunRecord`, or ``None`` where the run
  holds nothing to read.

A later cell or metric is added as files and entries; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

_SAFE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _safe(name: str) -> str:
    if not _SAFE.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def load_module(path: str, name: str):
    """Import the Python file at ``path`` under the module name ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot import {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    reader: object  # module with read(run)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    loop: object  # module with run(record, ...) and answers(record)
    reference: object  # module with exact(d, theta) and flops_per_point(d)
    end_to_end: list  # Metric
    per_layer: list  # Metric


class Suite:
    """``BENCHMARK.json`` under ``root`` and the files it names."""

    def __init__(self, root: str):
        self.root = root
        self.bench = os.path.join(root, "bench")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    def _json(self, *parts: str) -> dict:
        with open(os.path.join(*parts)) as f:
            return json.load(f)

    def _metric(self, entry: dict) -> Metric:
        name = _safe(entry["name"])
        path = os.path.join(self.bench, "metrics", f"{name}.py")
        module = load_module(path, "bench_metric_" + re.sub(r"\W", "_", name))
        return Metric(name, entry["unit"], module)

    def cell(self, name: str) -> Cell:
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
        w = cells[name]
        configs = {c["name"]: c for c in self.spec["configs"]}
        config = self._json(self.root, configs[w["config"]]["file"])
        traffic = self._json(self.bench, "traffic", f"{_safe(w['traffic'])}.json")
        loop = _safe(traffic["loop"])
        loop_module = load_module(
            os.path.join(self.bench, "loops", f"{loop}.py"), f"bench_loop_{loop}"
        )
        integrand = _safe(config["integrand"])
        reference = load_module(
            os.path.join(self.bench, "references", f"{integrand}.py"),
            f"bench_reference_{integrand}",
        )

        def applies(m: dict, reported: set) -> bool:
            # a metric lists its cells, or goes wherever what it moves goes
            if "workloads" in m:
                return name in m["workloads"]
            return m["moves"] in reported if "moves" in m else True

        e2e = [m for m in self.spec["end_to_end"] if applies(m, set())]
        reported = {m["name"] for m in e2e}
        per_layer = [m for m in self.spec["per_layer"] if applies(m, reported)]
        return Cell(
            name=name,
            chips=int(w["chips"]),
            config=config,
            traffic=traffic,
            loop=loop_module,
            reference=reference,
            end_to_end=[self._metric(m) for m in e2e],
            per_layer=[self._metric(m) for m in per_layer],
        )
