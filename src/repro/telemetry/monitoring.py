"""JAX's own program-building events, forwarded into a :class:`Recorder`.

JAX reports through ``jax.monitoring`` how long it spends building each
program: tracing the Python function to a jaxpr, lowering that to an MLIR
module, and the backend build, which compiles the program or loads it from
the persistent compilation cache (a load also reports a cache hit).
:func:`jax_events` forwards those events as counters while its ``with``
body runs:

====================  =============================================
counter               JAX event
====================  =============================================
``jax.trace_s``       ``/jax/core/compile/jaxpr_trace_duration``
``jax.lower_s``       ``/jax/core/compile/jaxpr_to_mlir_module_duration``
``jax.build_s``       ``/jax/core/compile/backend_compile_duration``
``jax.builds``        one per ``backend_compile_duration``
``jax.cache_loads``   ``/jax/compilation_cache/cache_hits``
====================  =============================================

The events nest: tracing a function traces every jitted function it calls
that is not traced yet, each an event of its own inside the outer one.  So
each seconds counter gets its event's time less that of the events nested
in it, and on one thread the three add up to the time spent building, each
second counted once.

Each counter event carries ``thread="driver"`` where JAX did the work on the
thread that entered the context (the driver's own, blocking build time) and
``thread="other"`` elsewhere: the drivers compile the next window rungs
ahead in background threads (:mod:`repro.core.programs`).  The listeners
are JAX's process-wide lists, so every program built anywhere in the
process while the context is open is counted.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Iterator

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: JAX event -> the counter its seconds are added to
SECONDS_COUNTERS = {
    TRACE_EVENT: "jax.trace_s",
    LOWER_EVENT: "jax.lower_s",
    BUILD_EVENT: "jax.build_s",
}


class _Forward:
    """The listeners of one :func:`jax_events` context.  The recorder's
    counters take a lock, so the driver and the ahead-compile threads may
    count at once; each thread keeps its own list of counted events."""

    def __init__(self, recorder) -> None:
        self.recorder = recorder
        self.driver = threading.get_ident()
        # thread -> the (start, end) of its counted events, in order: an
        # event that starts no later than the last ones encloses them
        self.counted: dict = {}

    def _thread(self) -> str:
        return "driver" if threading.get_ident() == self.driver else "other"

    def on_span(self, event: str, start: float, end: float, **_) -> None:
        name = SECONDS_COUNTERS.get(event)
        if name is None:
            return
        thread = self._thread()
        counted = self.counted.setdefault(threading.get_ident(), [])
        nested = 0.0
        while counted and counted[-1][0] >= start:
            s, e = counted.pop()
            nested += e - s
        counted.append((start, end))
        self.recorder.count(name, (end - start) - nested, thread=thread)
        if event == BUILD_EVENT:
            self.recorder.count("jax.builds", 1, thread=thread)

    def on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.recorder.count("jax.cache_loads", 1, thread=self._thread())


@contextmanager
def jax_events(recorder) -> Iterator[None]:
    """Forward JAX's trace, lower, build and cache-load events into
    ``recorder`` while the body runs (nothing for a disabled recorder)."""
    if not recorder.enabled:
        yield
        return
    import jax.monitoring

    fwd = _Forward(recorder)
    jax.monitoring.register_event_time_span_listener(fwd.on_span)
    jax.monitoring.register_event_listener(fwd.on_event)
    try:
        yield
    finally:
        jax.monitoring.unregister_event_time_span_listener(fwd.on_span)
        jax.monitoring.unregister_event_listener(fwd.on_event)
