"""Dependency-free observability for the quadrature serving stack.

See DESIGN.md §8.  Nothing is recorded inside traced code: events are
recorded on the host, at dispatch boundaries, so telemetry on/off cannot
perturb any compiled computation (bit-parity is asserted in
``tests/test_telemetry.py``).  Named scopes (``jax.named_scope``), which
change only the compiled programs' metadata, are allowed.

- :mod:`~repro.telemetry.core` — :class:`Recorder` (counters, gauges,
  histograms, nestable spans, flows; each span also a profiler trace
  annotation) and the no-op :data:`NULL`;
- :mod:`~repro.telemetry.monitoring` — :func:`jax_events`, JAX's trace,
  lower, build and cache-load events as recorder counters;
- :mod:`~repro.telemetry.sinks` — JSONL / in-memory sinks, summary table;
- :mod:`~repro.telemetry.trace` — Chrome trace-event (Perfetto) export;
- :mod:`~repro.telemetry.loadview` — per-device occupancy / idle-fraction
  / Fig. 4b imbalance timelines derived from recorded events;
- :mod:`~repro.telemetry.stats` — the typed :class:`ServiceStats` schema;
- :mod:`~repro.telemetry.check` — artifact validator (CI smoke checker);
- :mod:`~repro.telemetry.logutil` — shared CLI logging setup.
"""

from repro.telemetry.core import NULL, NullRecorder, Recorder, quantile
from repro.telemetry.monitoring import jax_events
from repro.telemetry.sinks import JsonlSink, MemorySink, read_jsonl, summary_table
from repro.telemetry.stats import ServiceStats
from repro.telemetry.trace import to_chrome, write_chrome_trace

__all__ = [
    "NULL",
    "NullRecorder",
    "Recorder",
    "quantile",
    "jax_events",
    "JsonlSink",
    "MemorySink",
    "read_jsonl",
    "summary_table",
    "ServiceStats",
    "to_chrome",
    "write_chrome_trace",
]
