"""Reduction of a profiler trace to device busy time, op times and gaps.

A TPU trace (``.xplane.pb``) holds one plane per device
(``/device:TPU:<n>``) whose ``XLA Ops`` line has one event per operation
run on the chip and whose ``XLA Modules`` line has one event per program
run, and host planes whose lines hold the host's spans, the harness's
``chipbench.*`` annotations among them. Times are nanoseconds on one
clock.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

WINDOW_SPAN = "chipbench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NAME_CHARS = 160


@dataclasses.dataclass
class Event:
    """One span: name, start and end in ns, its stats and where it ran."""

    name: str
    start: float
    end: float
    stats: dict
    where: str  # device plane or host thread



def _union(intervals: list) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


class Trace:
    """Device ops, program runs and host spans inside the traced window."""

    def __init__(self, ops: list, modules: list, host: list):
        spans = [h for h in host if h.name == WINDOW_SPAN]
        if spans:
            self.start, self.end = spans[0].start, spans[0].end
        else:
            times = [e for e in ops + modules]
            self.start = min((e.start for e in times), default=0.0)
            self.end = max((e.end for e in times), default=0.0)
        inside = lambda e: e.end > self.start and e.start < self.end  # noqa
        self.ops = [e for e in ops if inside(e)]
        self.modules = [e for e in modules if inside(e)]
        self.host = [e for e in host if inside(e) and e.name != WINDOW_SPAN]
        self.devices = sorted({e.where for e in self.ops}) or ["none"]

    def window_s(self) -> float:
        """Length of the traced window in seconds."""
        return (self.end - self.start) / 1e9

    def _busy(self, device: str) -> list:
        return _union([(max(e.start, self.start), min(e.end, self.end))
                       for e in self.ops if e.where == device])

    def busy_s(self) -> float:
        """Seconds with an op running on a device, averaged over devices."""
        total = sum(e - s for d in self.devices for s, e in self._busy(d))
        return total / len(self.devices) / 1e9

    def op_seconds(self, pattern: str) -> list:
        """Device seconds of each op whose name ``pattern`` matches
        (``re.search``)."""
        rx = re.compile(pattern)
        return [(e.end - e.start) / 1e9 for e in self.ops if rx.search(e.name)]

    def idle_gaps(self) -> list:
        """(start, end) of each gap between device ops inside the window,
        on the first device."""
        busy = self._busy(self.devices[0])
        edges = [self.start] + [x for iv in busy for x in iv] + [self.end]
        return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]

    def _host_during(self, s: float, e: float) -> str:
        best, name = 0.0, "no host span"
        for h in self.host:
            over = min(e, h.end) - max(s, h.start)
            if over > best:
                best, name = over, h.name
        return name

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time, and the longest idle gaps
        named by the host span that covered most of each. An op's name is
        its HLO text, cut to ``NAME_CHARS``."""
        by_name = defaultdict(float)
        for e in self.ops:
            by_name[e.name[:NAME_CHARS]] += (e.end - e.start) / 1e9
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[self._host_during(s, e), (e - s) / 1e9]
                              for s, e in gaps]}


def _events(plane, line, where):
    for ev in line.events:
        stats = {k: v for k, v in ev.stats}
        yield Event(ev.name, float(ev.start_ns),
                    float(ev.start_ns) + float(ev.duration_ns), stats, where)


def from_profile(profile) -> Trace:
    """A :class:`Trace` from a ``jax.profiler.ProfileData``."""
    ops, modules, host = [], [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(_events(plane, line, plane.name))
                elif line.name == MODULES_LINE:
                    modules.extend(_events(plane, line, plane.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(plane, line, line.name))
    return Trace(ops, modules, host)


def load(path: str) -> Trace:
    """Read a ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))
