"""Reduction of a profiler trace to device busy and idle time, device time
per program and per operation, collective time, and idle gaps named by what
the host was doing.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
:func:`load_xplane` turns it into plain :class:`Plane` records and
:func:`reduce_planes` does the arithmetic on those, so the reduction can be
checked on synthetic planes without a chip.

What a TPU trace holds (read from one on a v5e): a plane ``/device:TPU:<i>``
per chip with the lines ``XLA Modules`` (one event per program run, named
``jit_<fn>(<program id>)``) and ``XLA Ops`` (one event per operation run,
named by its whole HLO instruction, ``%fusion.11 = (f32[...]) fusion(...)``);
and a plane ``/host:CPU`` with a line per host thread.

Times are nanoseconds from the start of the profiling session; everything is
clipped to the traced window ``[0, window_ns)``.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import Counter

#: a device plane: "/device:TPU:0" (not the host, not a sub-core plane)
DEVICE_PLANE = re.compile(r"^/device:[A-Z_]+:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: XLA's names for the collective operations (and their async halves)
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)"
)
#: a module event's name carries its program id: "jit_step(12)"
_PROGRAM_ID = re.compile(r"\(\d+\)$")
#: the benchmark's own host annotations mark the thread that drives the chip
BENCH_ANNOTATION = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict  # line name -> list[Event]; host lines may share a name


@dataclasses.dataclass
class DeviceTime:
    """One chip's share of the traced window."""

    index: int
    busy_ns: float  # union of the intervals in which an operation ran
    collective_ns: float  # union of the collective operations' intervals
    programs: Counter  # program name -> device ns
    ops: Counter  # "program/operation" -> device ns
    gaps: list  # (start_ns, dur_ns) of every idle interval


@dataclasses.dataclass
class TraceSummary:
    window_ns: float
    devices: list  # DeviceTime, by chip index
    host: list  # Event of the host thread that drives the chip

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def program_s(self, prefix: str) -> float:
        """Device seconds of the programs whose name starts with ``prefix``,
        summed over the chips."""
        return sum(
            ns
            for d in self.devices
            for name, ns in d.programs.items()
            if name.startswith(prefix)
        ) / 1e9

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` operations with the most device time, averaged over
        the chips, as ``[program/operation, seconds]``."""
        total = Counter()
        for d in self.devices:
            total.update(d.ops)
        k = max(len(self.devices), 1)
        return [[name, ns / k / 1e9] for name, ns in total.most_common(n)]

    def idle_by_host(self, n: int = 10) -> list:
        """Idle seconds of the busiest chip, summed by what the host was
        doing at the middle of each gap, as ``[activity, seconds]``."""
        if not self.devices:
            return []
        dev = max(self.devices, key=lambda d: d.busy_ns)
        mids = [start + dur / 2 for start, dur in dev.gaps]
        by = Counter()
        for (_, dur), name in zip(dev.gaps, host_activity(self.host, mids)):
            by[name] += dur
        return [[name, ns / 1e9] for name, ns in by.most_common(n)]


def host_activity(host: list, times: list) -> list:
    """For each time, the name of the innermost host event that spans it,
    or ``"no host event"``.  Events of one thread nest, so a sweep with a
    stack of open events finds each in one pass."""
    order = sorted(range(len(times)), key=lambda i: times[i])
    events = sorted(host, key=lambda e: (e.start_ns, -e.dur_ns))
    out = ["no host event"] * len(times)
    stack: list = []
    k = 0
    for i in order:
        t = times[i]
        while k < len(events) and events[k].start_ns <= t:
            e = events[k]
            while stack and stack[-1].end_ns <= e.start_ns:
                stack.pop()
            stack.append(e)
            k += 1
        while stack and stack[-1].end_ns <= t:
            stack.pop()
        # an event not nested in the one below it may have ended already
        for e in reversed(stack):
            if e.end_ns > t:
                out[i] = e.name
                break
    return out


def _union(intervals: list, lo: float, hi: float) -> list:
    """Merged ``[start, end)`` intervals clipped to ``[lo, hi)``."""
    merged: list = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _length(merged: list) -> float:
    return sum(e - s for s, e in merged)


def _clipped(e: Event, window_ns: float) -> float:
    return max(0.0, min(e.end_ns, window_ns) - max(e.start_ns, 0.0))


def op_name(hlo: str) -> str:
    """``"%fusion.11 = (f32[...]) fusion(...)"`` -> ``"fusion.11"``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _device(index: int, ops: list, modules: list, window_ns: float) -> DeviceTime:
    busy = _union([(e.start_ns, e.end_ns) for e in (ops or modules)], 0.0, window_ns)
    gaps, t = [], 0.0
    for s, e in busy:
        if s > t:
            gaps.append((t, s - t))
        t = e
    if window_ns > t:
        gaps.append((t, window_ns - t))
    coll = _union(
        [(e.start_ns, e.end_ns) for e in ops if COLLECTIVE.match(op_name(e.name))],
        0.0,
        window_ns,
    )
    programs = Counter()
    modules = sorted(modules, key=lambda e: e.start_ns)
    starts = [e.start_ns for e in modules]
    for e in modules:
        programs[_PROGRAM_ID.sub("", e.name)] += _clipped(e, window_ns)
    op_ns = Counter()
    for e in ops:
        j = bisect.bisect_right(starts, e.start_ns) - 1
        inside = j >= 0 and e.start_ns < modules[j].end_ns
        program = _PROGRAM_ID.sub("", modules[j].name) if inside else "?"
        op_ns[f"{program}/{op_name(e.name)}"] += _clipped(e, window_ns)
    return DeviceTime(index, _length(busy), _length(coll), programs, op_ns, gaps)


def reduce_planes(planes: list, window_ns: float) -> TraceSummary:
    """Busy, collective and per-program time of each device plane, and the
    events of the host thread that drives the chips (the one that holds the
    benchmark's ``bench.`` annotations), over ``[0, window_ns)``."""
    devices = []
    host: list = []
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m is None:
            if plane.name == HOST_PLANE:
                for events in plane.lines.values():
                    if any(e.name.startswith(BENCH_ANNOTATION) for e in events):
                        host.extend(events)
            continue
        ops = plane.lines.get(OPS_LINE) or []
        modules = plane.lines.get(MODULES_LINE) or []
        if ops or modules:
            devices.append(_device(int(m.group(1)), ops, modules, window_ns))
    devices = [d for d in devices if d.busy_ns > 0]
    devices.sort(key=lambda d: d.index)
    return TraceSummary(window_ns, devices, host)


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` a profiling session wrote under ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {found}"
        )
    return found[0]


def load_xplane(path: str) -> list:
    """The device planes' op and module lines and the host's threads of a
    recorded trace, times in nanoseconds from the start of the session."""
    from jax.profiler import ProfileData

    planes = []
    for p in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(p.name) is not None
        if not device and p.name != HOST_PLANE:
            continue
        lines: dict = {}
        for i, line in enumerate(p.lines):
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            key = line.name if device else f"{line.name}#{i}"
            lines[key] = [
                Event(e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events
            ]
        planes.append(Plane(p.name, lines))
    return planes


def summarize(trace_dir: str, window_ns: float) -> TraceSummary:
    """Load and reduce the trace a session wrote under ``trace_dir``."""
    return reduce_planes(load_xplane(find_xplane(trace_dir)), window_ns)
