"""From the profiler's trace to device intervals, busy time and a
breakdown.

``read`` takes the ``.xplane.pb`` a ``jax.profiler`` trace wrote and
keeps three things, on the profiler's clock in nanoseconds: the device
planes' program executions (line ``XLA Modules``) and operations (line
``XLA Ops``), and the host spans the harness opened with
``jax.profiler.TraceAnnotation`` (names starting ``cb_``), among them
the two markers of the measured window.  The rest is arithmetic on
intervals, which the tests check on synthesised events.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "cb_"
WINDOW_START, WINDOW_END = "cb_window_start", "cb_window_end"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, object] = dataclasses.field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    """Device events per device plane, host spans, and the window."""
    modules: Dict[str, List[Event]]
    ops: Dict[str, List[Event]]
    host: List[Event]
    window: Tuple[float, float]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def devices(self) -> List[str]:
        return sorted(self.ops)

    def in_window(self, events: Sequence[Event]) -> List[Event]:
        """Events that start inside the window."""
        lo, hi = self.window
        return [e for e in events if lo <= e.start_ns < hi]


def read(trace_dir: str) -> Trace:
    """Parse the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    modules: Dict[str, List[Event]] = {}
    ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:") and "CPU" not in plane.name
        for line in plane.lines:
            if device and line.name in (MODULES_LINE, OPS_LINE):
                out = (modules if line.name == MODULES_LINE else ops)
                out[plane.name] = [Event(e.name, e.start_ns, e.duration_ns)
                                   for e in line.events]
            elif plane.name.startswith("/host:"):
                host.extend(Event(e.name, e.start_ns, e.duration_ns,
                                  dict(e.stats))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    for dev, evs in ops.items():
        attribute_modules(evs, modules.get(dev, []))
    host.sort(key=lambda e: e.start_ns)
    starts = [e.start_ns for e in host if e.name == WINDOW_START]
    ends = [e.start_ns for e in host if e.name == WINDOW_END]
    if not starts or not ends:
        raise ValueError("the trace holds no window markers")
    return Trace(modules, ops, host, (starts[0], ends[-1]))


def attribute_modules(ops: List[Event], modules: List[Event]) -> None:
    """Set each operation's ``module`` stat to the program execution
    (its name without the ``(id)`` suffix) whose interval holds the
    operation's start; the device trace gives operations no program."""
    mods = sorted(modules, key=lambda e: e.start_ns)
    starts = [m.start_ns for m in mods]
    for e in ops:
        i = bisect.bisect_right(starts, e.start_ns) - 1
        if i >= 0 and e.start_ns < mods[i].end_ns:
            e.stats["module"] = mods[i].name.split("(")[0]


_OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")
CONTAINERS = ("while", "conditional", "call")


def op_label(name: str) -> Tuple[str, str]:
    """(HLO name, opcode) of an ``XLA Ops`` event, whose name is the
    operation's HLO text: ``%copy.117 = bf16[...] copy(...)``."""
    head, _, rest = name.partition(" = ")
    m = _OPCODE.search(" " + rest) if rest else None
    return head, (m.group(1) if m else "")


def union(intervals: Sequence[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """The union of ``intervals`` clipped to [lo, hi], as disjoint
    sorted intervals."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in union([(e.start_ns, e.end_ns)
                                        for e in events], lo, hi))


def busy_s(trace: Trace) -> float:
    """Seconds in the window in which an operation ran, averaged over
    the device planes."""
    lo, hi = trace.window
    devs = trace.devices()
    if not devs:
        return 0.0
    return sum(busy_ns(trace.ops[d], lo, hi) for d in devs) / len(devs) / 1e9


def idle_gaps(events: Sequence[Event], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] in which no event ran."""
    gaps, t = [], lo
    for a, b in union([(e.start_ns, e.end_ns) for e in events], lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def host_activity(gap: Tuple[float, float], host: Sequence[Event]) -> str:
    """What the host was doing in ``gap``: the harness span that covers
    most of it, or 'no harness span'."""
    best, name = 0.0, "no harness span"
    for e in host:
        if e.name in (WINDOW_START, WINDOW_END):
            continue
        ov = _overlap(gap[0], gap[1], e.start_ns, e.end_ns)
        if ov > best:
            best, name = ov, e.name
    return name


def breakdown(trace: Trace, top: int = 10) -> Dict[str, list]:
    """The device operations that took most time in the window (summed
    over the device planes by program and operation; loops and calls
    are left out, their bodies' operations count) and the
    longest idle gaps of the first device, each named by the host span
    that covered most of it."""
    lo, hi = trace.window
    per_op: Dict[str, float] = collections.defaultdict(float)
    for d in trace.devices():
        for e in trace.in_window(trace.ops[d]):
            head, opcode = op_label(e.name)
            if opcode in CONTAINERS:      # its body's operations count
                continue
            module = str(e.stats.get("module", "?"))
            per_op[f"{module}/{head} {opcode}".rstrip()] += e.dur_ns / 1e9
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps: List[list] = []
    if trace.devices():
        dev = trace.devices()[0]
        longest = sorted(idle_gaps(trace.ops[dev], lo, hi),
                         key=lambda g: g[0] - g[1])[:top]
        gaps = [[f"idle while host in {host_activity(g, trace.host)}",
                 (g[1] - g[0]) / 1e9] for g in longest]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": gaps}


def module_time_s(trace: Trace, match) -> Tuple[float, int]:
    """Device seconds and count of the program executions in the
    window whose name satisfies ``match``, summed over device planes."""
    total, count = 0.0, 0
    for evs in trace.modules.values():
        for e in trace.in_window(evs):
            if match(e.name):
                total += e.dur_ns / 1e9
                count += 1
    return total, count


def ops_time_s(trace: Trace, match) -> Tuple[float, int]:
    """Device seconds and count of the operations in the window for
    which ``match(event)`` holds, summed over device planes."""
    total, count = 0.0, 0
    for evs in trace.ops.values():
        for e in trace.in_window(evs):
            if match(e):
                total += e.dur_ns / 1e9
                count += 1
    return total, count
