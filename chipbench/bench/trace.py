"""Reduce a profiler trace to device busy time, program time and idle gaps.

``read_xplane`` takes the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
what the metrics need, as plain lists: the operations of each device's
``XLA Ops`` line and the compiled programs of its ``XLA Modules`` line as
``[name, start_ns, duration_ns]``, and the host spans whose name starts with
``chipbench.`` (the window's own span gives the interval every reduction is
clipped to). All times are on the trace's clock. The reductions below work
on those lists alone, so they can be checked on a recorded trace without a
chip.
"""

from __future__ import annotations

import glob
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"

Event = Tuple[str, float, float]  # name, start_ns, duration_ns


def find_xplane(logdir: str) -> Optional[str]:
    paths = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))
    return paths[-1] if paths else None


def read_xplane(path: str) -> Dict:
    """``{"devices": {plane: [op, ...]}, "modules": {plane: [program, ...]},
    "spans": {name: [start, dur]}}``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: Dict[str, Dict] = {"devices": {}, "modules": {}, "spans": {}}
    spans = out["spans"]
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                key = ("devices" if line.name == OPS_LINE else
                       "modules" if MODULES_LINE in line.name else None)
                if key:
                    out[key].setdefault(plane.name, []).extend(
                        [e.name, e.start_ns, e.duration_ns] for e in line.events)
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.setdefault(e.name, [e.start_ns, e.duration_ns])
    return out


def _clip(events: Iterable[Event], lo: float, hi: float):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def merged(events: Iterable[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The union of the events' intervals inside ``[lo, hi]``, in order."""
    out: List[List[float]] = []
    for _, a, b in sorted(_clip(events, lo, hi), key=lambda x: x[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in merged(events, lo, hi))


def matched_ns(events: Iterable[Event], patterns: Sequence[str],
              lo: float, hi: float) -> float:
    """Summed device time of the events whose name matches a pattern."""
    pats = [re.compile(p) for p in patterns]
    return sum(b - a for name, a, b in _clip(events, lo, hi)
               if any(p.search(name) for p in pats))


def top_ops(events: Iterable[Event], lo: float, hi: float,
            k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` operations that took most device time, in seconds."""
    tot: Dict[str, float] = {}
    for name, a, b in _clip(events, lo, hi):
        tot[name] = tot.get(name, 0.0) + (b - a)
    return [(n, t / 1e9) for n, t in
            sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(events: Iterable[Event], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    """Intervals of ``[lo, hi]`` in which no operation ran, longest first."""
    gaps, t = [], lo
    for a, b in merged(events, lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


class Reduced:
    """A traced window: device events, and the window's interval on the
    trace's clock."""

    def __init__(self, data: Dict):
        self.devices: Dict[str, List[Event]] = {
            k: [tuple(e) for e in v] for k, v in data["devices"].items()}
        self.modules: Dict[str, List[Event]] = {
            k: [tuple(e) for e in v] for k, v in data.get("modules", {}).items()}
        span = data["spans"].get(WINDOW_SPAN)
        if span is None:
            raise ValueError(f"trace has no {WINDOW_SPAN} span")
        self.lo, self.hi = span[0], span[0] + span[1]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_s(self) -> float:
        """Device busy seconds in the window, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(busy_ns(ev, self.lo, self.hi)
                   for ev in self.devices.values()) / len(self.devices) / 1e9

    def program_s(self, patterns: Sequence[str]) -> float:
        """Device seconds of the compiled programs whose name matches."""
        return sum(matched_ns(ev, patterns, self.lo, self.hi)
                   for ev in self.modules.values()) / 1e9

    def all_events(self) -> List[Event]:
        return [e for ev in self.devices.values() for e in ev]

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        return top_ops(self.all_events(), self.lo, self.hi, k)

    def idle_gaps(self) -> List[Tuple[float, float]]:
        """Idle intervals of the first device, on the trace's clock."""
        if not self.devices:
            return [(self.lo, self.hi)]
        first = sorted(self.devices)[0]
        return idle_gaps(self.devices[first], self.lo, self.hi)
