"""From a profiler trace to busy time, kernel time and idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``:

* device operations: the events of the ``XLA Ops`` line of each device
  plane (``/device:TPU:<i>``);
* host spans: the events named ``bench.<name>`` on the host plane, which
  ``bench/spans.py`` records around the benchmark's calls.

Everything is counted inside the host span ``bench.window`` (the measured
window) where the trace holds it, else over the trace's extent. Busy time
is the union of the operations' intervals, averaged over the devices that
ran any. An idle gap is a stretch of the window in which no operation ran
on a device; it is charged to the innermost host span open at its middle
(``(none)`` where no span is open).
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

from bench.spans import PREFIX

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "window"
TOP = 10


@dataclass(frozen=True)
class Op:
    label: str       # the event's name: the HLO instruction's text on a TPU
    start: float     # ns
    dur: float       # ns

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def name(self) -> str:
        """The instruction's name without its number: ``%fusion.12 = ...``
        reads ``fusion``."""
        head = self.label.split(" = ", 1)[0].lstrip("%")
        base, _, num = head.rpartition(".")
        return base if base and num.isdigit() else head


def self_times(ops) -> list[tuple["Op", float]]:
    """Each operation with its own time: its duration less that of the
    operations nested in it (a loop's body ops sit inside the loop's
    event on the same line)."""
    order = sorted(ops, key=lambda o: (o.start, -o.dur))
    own = {id(o): o.dur for o in order}
    stack: list[Op] = []
    for o in order:
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            own[id(stack[-1])] -= o.dur
        stack.append(o)
    return [(o, own[id(o)]) for o in order]


@dataclass(frozen=True)
class Span:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi] that ``busy`` (merged) leaves uncovered."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def innermost(spans: list[Span], t: float) -> str:
    """Name of the shortest span open at time t."""
    best = None
    for sp in spans:
        if sp.start <= t < sp.end and (best is None or sp.dur < best.dur):
            best = sp
    return best.name if best is not None else "(none)"


@dataclass
class Summary:
    devices: dict                 # plane name -> [Op] inside the window
    spans: list                   # [Span] of the benchmark, prefix removed
    lo: float
    hi: float
    busy: dict = field(default_factory=dict)   # plane -> merged intervals

    def __post_init__(self):
        for plane, ops in self.devices.items():
            self.busy[plane] = union(clip([(o.start, o.end) for o in ops],
                                          self.lo, self.hi))

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        if not self.busy:
            return 0.0
        return sum(length(b) for b in self.busy.values()) \
            / len(self.busy) / 1e9

    def idle_share_pct(self):
        if self.window_s <= 0 or not self.busy:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def kernel_s(self, kernels) -> float:
        """Device seconds of the operations that match any of ``kernels``
        (each a tuple of strings that must all occur in the operation's
        text), averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = 0.0
        for ops in self.devices.values():
            tot += sum(o.dur for o in ops
                       if any(all(p in o.label for p in k) for k in kernels))
        return tot / len(self.devices) / 1e9

    def op_seconds(self) -> dict:
        """Own device seconds by operation name, averaged over devices."""
        out: dict = defaultdict(float)
        for ops in self.devices.values():
            for o, own in self_times(ops):
                out[o.name] += own / 1e9 / len(self.devices)
        return dict(out)

    def gap_seconds(self) -> dict:
        """Idle seconds by the host span open in each gap, averaged over
        the devices."""
        out: dict = defaultdict(float)
        for busy in self.busy.values():
            for s, e in gaps(busy, self.lo, self.hi):
                out[innermost(self.spans, (s + e) / 2)] += \
                    (e - s) / 1e9 / len(self.busy)
        return dict(out)

    def breakdown(self) -> dict:
        def top(d):
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:TOP]]
        return {"device_ops": top(self.op_seconds()),
                "idle_gaps": top(self.gap_seconds())}


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return files[-1]


def read(path: str):
    """(device ops by plane, benchmark spans) from a trace file or dir."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    devices: dict = {}
    spans: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = [Op(ev.name, ev.start_ns, ev.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        spans.append(Span(ev.name[len(PREFIX):],
                                          ev.start_ns, ev.duration_ns))
    return devices, spans


def summarize(path: str) -> Summary:
    return reduce(*read(path))


def reduce(devices: dict, spans: list) -> Summary:
    """Restrict to the ``window`` span (the whole trace where it is
    missing) and summarise."""
    win = [s for s in spans if s.name == WINDOW]
    if win:
        lo, hi = win[0].start, win[0].end
    else:
        ends = [(o.start, o.end) for ops in devices.values() for o in ops] \
            + [(s.start, s.end) for s in spans]
        lo = min((s for s, _ in ends), default=0.0)
        hi = max((e for _, e in ends), default=0.0)
    inside = {p: [o for o in ops if o.end > lo and o.start < hi]
              for p, ops in devices.items()}
    return Summary(devices={p: o for p, o in inside.items() if o},
                   spans=[s for s in spans if s.name != WINDOW],
                   lo=lo, hi=hi)
