"""Reduction of a JAX profiler trace (``*.xplane.pb``).

``jax.profiler.ProfileData`` reads the file with nothing but JAX. On a TPU
every chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one
event per executed HLO operation and whose line ``XLA Modules`` holds one
event per executed program; the host's threads are lines of ``/host:CPU``,
where the harness's own spans (``bench.*``, written by
``jax.profiler.TraceAnnotation``) lie. All times here are seconds on the
trace's own clock.

The interval arithmetic is in plain functions over ``(start, end)`` pairs
so that the tests can hold it to hand-made cases.
"""

from __future__ import annotations

import dataclasses
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|ragged-all-to-all")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float
    text: str = ""        # a device operation's whole HLO line

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Device:
    index: int
    ops: list[Event]
    modules: list[Event]


@dataclasses.dataclass
class Trace:
    devices: list[Device]
    spans: list[Event]    # the harness's host spans

    @property
    def bounds(self) -> tuple[float, float]:
        """From the first to the last thing recorded: host span or device
        operation."""
        ev = [e for d in self.devices for e in d.ops] + self.spans
        return min(e.start for e in ev), max(e.end for e in ev)


# -- interval arithmetic ----------------------------------------------------


def merge(intervals) -> list[tuple[float, float]]:
    """The union of ``(start, end)`` intervals as sorted disjoint ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(intervals, holes) -> list[tuple[float, float]]:
    """The parts of merged ``intervals`` that no merged ``holes`` cover."""
    out = []
    holes = merge(holes)
    for s, e in merge(intervals):
        cur = s
        for hs, he in holes:
            if he <= cur or hs >= e:
                continue
            if hs > cur:
                out.append((cur, hs))
            cur = max(cur, he)
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle stretches of ``[lo, hi]`` between merged ``busy`` ones."""
    return subtract([(lo, hi)], busy)


# -- reductions ---------------------------------------------------------------


def busy_intervals(dev: Device, lo=None, hi=None):
    iv = merge((e.start, e.end) for e in dev.ops)
    return iv if lo is None else clip(iv, lo, hi)


def busy_seconds(trace: Trace, lo=None, hi=None) -> float:
    """Seconds in which an operation ran on the device, averaged over the
    devices."""
    per = [total(busy_intervals(d, lo, hi)) for d in trace.devices]
    return sum(per) / len(per)


def idle_share(trace: Trace) -> float:
    """The share of the traced stretch in which no operation ran on the
    device, mean over the devices."""
    lo, hi = trace.bounds
    return 1.0 - busy_seconds(trace) / (hi - lo)


def seconds_by_name(events) -> dict[str, float]:
    """Total seconds per operation name; ``fusion.12`` and ``fusion.7``
    stay apart, which is what a breakdown wants."""
    out: dict[str, float] = {}
    for e in events:
        out[e.name] = out.get(e.name, 0.0) + e.seconds
    return out


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The device operations that took most time, summed over devices and
    divided by their number."""
    acc: dict[str, float] = {}
    for d in trace.devices:
        for name, s in seconds_by_name(d.ops).items():
            acc[name] = acc.get(name, 0.0) + s / len(trace.devices)
    return [[k, v] for k, v in sorted(acc.items(),
                                      key=lambda kv: -kv[1])[:n]]


def attribute_gaps(idle, spans) -> dict[str, float]:
    """Idle seconds by the harness span the host was in: each idle stretch
    is cut at span boundaries and every piece goes to the innermost
    (shortest) span that covers it, or to ``(no span)``."""
    cuts = sorted({t for s in spans for t in (s.start, s.end)})
    out: dict[str, float] = {}
    for lo, hi in idle:
        edges = [lo] + [t for t in cuts if lo < t < hi] + [hi]
        for a, b in zip(edges, edges[1:]):
            mid = (a + b) / 2
            cover = [s for s in spans if s.start <= mid < s.end]
            name = (min(cover, key=lambda s: s.seconds).name if cover
                    else "(no span)")
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def idle_by_span(trace: Trace, n: int = 10) -> list[list]:
    lo, hi = trace.bounds
    acc: dict[str, float] = {}
    for d in trace.devices:
        for name, s in attribute_gaps(gaps(busy_intervals(d), lo, hi),
                                      trace.spans).items():
            acc[name] = acc.get(name, 0.0) + s / len(trace.devices)
    return [[k, v] for k, v in sorted(acc.items(),
                                      key=lambda kv: -kv[1])[:n]]


def exposed_collective_seconds(dev: Device) -> float:
    """Seconds in which a collective ran on the device and no other
    operation did."""
    coll = [(e.start, e.end) for e in dev.ops if COLLECTIVE.search(e.name)]
    rest = [(e.start, e.end) for e in dev.ops
            if not COLLECTIVE.search(e.name)]
    return total(subtract(coll, rest))


def module_runs(dev: Device, pattern: str) -> list[Event]:
    """The executions of the programs whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return [m for m in dev.modules if rx.search(m.name)]


def ops_within(dev: Device, runs) -> list[Event]:
    """The device operations that ran inside any of ``runs`` (program
    executions), by time."""
    spans = merge((r.start, r.end) for r in runs)
    out, i = [], 0
    for e in sorted(dev.ops, key=lambda e: e.start):
        while i < len(spans) and spans[i][1] <= e.start:
            i += 1
        if i < len(spans) and spans[i][0] <= e.start < spans[i][1]:
            out.append(e)
    return out


OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")
# control flow: its event spans the operations inside it, which the line
# also holds one by one, so it says nothing about whether the chip worked
CONTAINERS = {"while", "conditional", "call"}


def op_kind(text: str) -> str:
    """The HLO opcode of a device operation's line (``fusion``, ``copy``,
    ``while``, ...), or ``""`` where the line is not HLO text."""
    m = OPCODE.search(text)
    return m.group(1) if m else ""


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: the trace
    names a device operation by its whole HLO line."""
    return text.split(" = ", 1)[0].lstrip("%")


# -- reading ------------------------------------------------------------------


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        if op_kind(ev.name) in CONTAINERS:
                            continue
                        ops.append(Event(
                            op_name(ev.name), ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9, ev.name))
                elif line.name == MODULES_LINE:
                    modules = [Event(ev.name, ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9)
                               for ev in line.events]
            devices.append(Device(int(m.group(1)), ops, modules))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Event(
                            ev.name, ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9))
    devices.sort(key=lambda d: d.index)
    if not devices or not any(d.ops for d in devices):
        raise SystemExit(f"bench_cells: no device operation in the trace "
                         f"{path}: nothing ran on a TPU while it was taken")
    return Trace(devices, spans)
