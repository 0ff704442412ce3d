"""From a profiler trace (``.xplane.pb``) to intervals on the host's clock.

``jax.profiler.ProfileData`` reads the file.  A device plane
(``/device:TPU:<n>``) has a line of XLA module executions (``XLA
Modules``), one event per compiled step run; the host plane
(``/host:CPU``) has the ``TraceAnnotation`` spans the benchmark opened.
The line of the operations inside the modules (``XLA Ops``) is not read:
a Mamba-2 cell puts millions of events there, and reading them took
minutes.  A ``TraceAnnotation`` named ``bench_sync``, which the harness opens
at a time it reads from ``time.monotonic``, ties the host's events to the
host's monotonic clock, on which the benchmark keeps its own stamps.  The
device's events run about a millisecond off the host's in the trace (a
module shown starting before the host dispatched it), so inside that
annotation the harness also runs a tiny step named ``bench_sync_step``,
whose start on the device ties the device's events to the same clock to
within the dispatch latency.

The reduction: busy time is the union of the module executions on a
device; the idle share of a window is the part of it that none covers; a
module's device time is the duration of its executions.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

__all__ = ["DeviceTrace", "reduce_profile", "read_xplane", "union",
           "intersect", "total", "gaps", "module_name", "SYNC", "SYNC_STEP"]

SYNC = "bench_sync"
SYNC_STEP = "bench_sync_step"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    """``jit_<name>(<id>)`` -> ``<name>``: the name the step was given."""
    name = _SUFFIX.sub("", event_name)
    return name[4:] if name.startswith("jit_") else name



@dataclass
class DeviceTrace:
    """Intervals in seconds on the host's monotonic clock."""
    modules: list = field(default_factory=list)     # (name, start, end)
    annotations: list = field(default_factory=list)  # (name, start, end)
    devices: int = 0

    def busy(self) -> list:
        """Union of device activity, over all devices (one chip here)."""
        return union([(s, e) for _, s, e in self.modules])


def union(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def intersect(a, b) -> list:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy, within) -> list:
    """Parts of ``within`` (sorted, disjoint) that ``busy`` does not
    cover."""
    out = []
    for lo, hi in within:
        t = lo
        for s, e in busy:
            if e <= t or s >= hi:
                continue
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < hi:
            out.append((t, hi))
    return out


def reduce_profile(planes, sync_mono_s: float) -> DeviceTrace:
    """Reduce planes (objects with ``name``/``lines``; lines with
    ``name``/``events``; events with ``name``/``start_ns``/
    ``duration_ns``) to a :class:`DeviceTrace` on the monotonic clock."""
    planes = list(planes)
    sync_ns = None
    host = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == SYNC and sync_ns is None:
                    sync_ns = ev.start_ns
                elif ev.name.startswith("body:"):
                    host.append((ev.name, ev.start_ns, ev.duration_ns))
    if sync_ns is None:
        raise ValueError(f"the trace has no {SYNC!r} annotation")

    def on_clock(name, start_ns, dur_ns, shift):
        s = start_ns * 1e-9 + shift
        return (name, s, s + dur_ns * 1e-9)

    host_shift = sync_mono_s - sync_ns * 1e-9
    out = DeviceTrace(annotations=[on_clock(*h, host_shift) for h in host])
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        line = next((line for line in plane.lines
                     if line.name == MODULES_LINE), None)
        if line is None:
            continue
        out.devices += 1
        events = list(line.events)
        shift = next((sync_mono_s - ev.start_ns * 1e-9 for ev in events
                      if module_name(ev.name) == SYNC_STEP), host_shift)
        for ev in events:
            out.modules.append(on_clock(module_name(ev.name), ev.start_ns,
                                        ev.duration_ns, shift))
    out.modules.sort(key=lambda m: m[1])
    out.annotations.sort(key=lambda m: m[1])
    return out


def read_xplane(path: str, sync_mono_s: float) -> DeviceTrace:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path).planes, sync_mono_s)
