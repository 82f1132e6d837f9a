"""The program's own spans (``telemetry/tracing.py``'s recorder, reached by
import), cut to the window and joined with the harness's records and with
the profiler's trace.

A span is whatever the recorder holds: ``name``, ``start_ns`` / ``end_ns``
on ``time.perf_counter_ns()`` (the clock the harness stamps its ticks and
window with), ``id``, ``parent``, ``attrs``. A program without the recorder
(one older than it) gives :func:`recorder` ``None`` and every reader built
on this file then reports nothing; with it, a partial reading is an error
like a kernel that is not found: a tick that emitted a token and left no
``engine.tick`` span, or a ring that evicted spans of the window, ends the
run with no result line.
"""

from __future__ import annotations

import bisect
import statistics

from bench_cells.reduce import xplane

TICK = "engine.tick"
NO_SPAN = "(no span)"
ENGINE_STEP = "bench.serve.engine_step"


def recorder():
    """The program's span recorder, or ``None`` where it has none."""
    try:
        from simple_distributed_machine_learning_tpu.telemetry import tracing
    except ImportError:
        return None
    current = getattr(tracing, "current", None)
    return None if current is None else current()


def seconds(span) -> float:
    return (span.end_ns - span.start_ns) * 1e-9


def window_spans(records: dict, tracer) -> list:
    """The recorder's spans that lie inside the window, oldest first; an
    error where the ring has evicted a span that ended after the window
    began."""
    lo = records["t0"]
    hi = lo + records["window_s"]
    if tracer.evicted_until_ns * 1e-9 > lo:
        raise SystemExit(
            f"bench_cells: the program's span ring evicted spans of the "
            f"window ({tracer.dropped} dropped in all); raise its capacity")
    return [s for s in tracer.spans()
            if s.start_ns * 1e-9 >= lo and s.end_ns * 1e-9 <= hi]


def children_of(spans) -> dict:
    """Span id -> its direct children, in the order they closed."""
    out: dict = {}
    for s in spans:
        if s.parent is not None:
            out.setdefault(s.parent, []).append(s)
    return out


def descendants(span, kids: dict) -> list:
    out = []
    for c in kids.get(span.id, ()):
        out.append(c)
        out.extend(descendants(c, kids))
    return out


def self_seconds(span, kids: dict) -> float:
    """A span less what its direct children cover (their union: children
    recorded from a duration, such as ``jax.compile``, may overlap)."""
    lo, hi = span.start_ns * 1e-9, span.end_ns * 1e-9
    covered = xplane.clip(xplane.merge(
        (c.start_ns * 1e-9, c.end_ns * 1e-9)
        for c in kids.get(span.id, ())), lo, hi)
    return (hi - lo) - xplane.total(covered)


def window_ticks(records: dict, spans) -> list:
    """One entry per harness tick of the window, in order: its
    ``engine.tick`` span, or ``None`` for a call that found the engine
    idle. More than one span inside a harness tick, or none where the tick
    emitted a token, is an error."""
    ticks = sorted((s for s in spans if s.name == TICK),
                   key=lambda s: s.start_ns)
    out, i = [], 0
    for n, (ts, te, emitted) in enumerate(records["ticks"]):
        while i < len(ticks) and ticks[i].start_ns * 1e-9 < ts:
            i += 1
        inside = []
        while i < len(ticks) and ticks[i].end_ns * 1e-9 <= te:
            inside.append(ticks[i])
            i += 1
        if len(inside) > 1 or (emitted and not inside):
            raise SystemExit(
                f"bench_cells: the harness's tick {n} (emitted {emitted}) "
                f"holds {len(inside)} {TICK} spans of the program; "
                f"expected {'exactly' if emitted else 'at most'} one")
        out.append(inside[0] if inside else None)
    return out


class Window:
    """The window's spans as the metric readers want them."""

    def __init__(self, records: dict, tracer) -> None:
        self.spans = window_spans(records, tracer)
        self.kids = children_of(self.spans)
        self.ticks = [t for t in window_ticks(records, self.spans)
                      if t is not None]
        if not self.ticks:
            raise SystemExit(f"bench_cells: no {TICK} span of the program "
                             f"inside the window")

    def per_tick(self, names, less=()) -> list[float]:
        """Per tick, the seconds its direct children called ``names`` took,
        less their own children whose name starts with one of ``less``."""
        out, less = [], tuple(less)
        for t in self.ticks:
            total = 0.0
            for c in self.kids.get(t.id, ()):
                if c.name in names:
                    total += seconds(c) - sum(
                        seconds(g) for g in self.kids.get(c.id, ())
                        if g.name.startswith(less))
            out.append(total)
        return out

    def uncovered_share(self) -> float:
        """The share of all tick time that lies in no child span."""
        whole = sum(seconds(t) for t in self.ticks)
        return sum(self_seconds(t, self.kids) for t in self.ticks) / whole


def serve_window(run):
    """The serving cell's window, or ``None`` where there is nothing to
    read: another kind of cell, or a program without the recorder."""
    if run["records"].get("kind") != "serve":
        return None
    tracer = recorder()
    return None if tracer is None else Window(run["records"], tracer)


def mean_ms_per_tick(run, names, less=()):
    """What most of the ``engine.host_*`` readers are: the mean over the
    window's ticks of the named children's time, in milliseconds."""
    w = serve_window(run)
    if w is None:
        return None
    return 1e3 * statistics.fmean(w.per_tick(names, less))


# -- the join with the profiler's trace ---------------------------------------


def align(records: dict, trace) -> float:
    """Seconds to add to a ``perf_counter`` reading to get the trace's
    clock: the median, over the traced ticks, of the harness span's start
    in the trace less that tick's start stamp. The trace must hold exactly
    the traced ticks' harness spans."""
    first, last = records["traced_ticks"]
    steps = sorted((e for e in trace.spans if e.name == ENGINE_STEP),
                   key=lambda e: e.start)
    if last is None or len(steps) != last - first or not steps:
        raise SystemExit(
            f"bench_cells: the trace holds {len(steps)} {ENGINE_STEP} "
            f"spans for the traced ticks {first}..{last}")
    return statistics.median(
        e.start - records["ticks"][first + k][0]
        for k, e in enumerate(steps))


def _overlaps(a, b):
    """The pieces two sorted lists of disjoint intervals share."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _innermost(spans, lo: float):
    """Nested ``(name, start, end)`` spans of one thread as disjoint
    ``(name, start, end)`` pieces, each named after the innermost span that
    covers it. A span recorded from a duration (``jax.compile``) may reach
    back over its elder sibling or its parent's start: it is cut to what
    is still free."""
    pieces, stack, at = [], [], lo

    def piece(name, a, b):
        if b > a:
            pieces.append((name, a, b))

    def close(until):
        nonlocal at
        while stack and stack[-1][2] <= until:
            name, _, end = stack.pop()
            piece(name, at, end)
            at = max(at, end)

    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        close(a)
        a = max(a, at)
        if stack:
            piece(stack[-1][0], at, a)
            b = min(b, stack[-1][2])
        if b > a:
            stack.append((name, a, b))
            at = a
    close(float("inf"))
    return pieces


def idle_by_span(records: dict, trace, tracer) -> dict[str, float]:
    """The device's idle seconds inside the traced ticks by the program
    span the host was in, mean over the devices: the idle stretches of each
    device between the first traced tick's start and the last one's end,
    cut to the ticks, on the trace's clock. A stretch goes to the innermost
    span below ``engine.tick`` that covers it (a collector pause inside a
    wait is ``py.gc``, the rest of the wait stays the wait's), and to
    ``(no span)`` where only the tick or nothing does. (Not
    ``xplane.attribute_gaps``: that one scans every span for every idle
    stretch, and a traced serve window has some 10**5 stretches between
    device operations.)"""
    offset = align(records, trace)
    first, last = records["traced_ticks"]
    ticks = [(ts + offset, te + offset)
             for ts, te, _ in records["ticks"][first:last]]
    lo, hi = ticks[0][0], ticks[-1][1]
    pieces = _innermost(
        [(s.name, s.start_ns * 1e-9 + offset, s.end_ns * 1e-9 + offset)
         for s in tracer.spans() if s.name != TICK
         and lo <= s.start_ns * 1e-9 + offset
         and s.end_ns * 1e-9 + offset <= hi], lo)
    starts = [a for _, a, _ in pieces]
    out: dict[str, float] = {}
    for dev in trace.devices:
        idle = xplane.gaps(xplane.busy_intervals(dev, lo, hi), lo, hi)
        for a, b in _overlaps(idle, ticks):
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            rest = b - a
            while i < len(pieces) and pieces[i][1] < b:
                name, pa, pb = pieces[i]
                shared = min(b, pb) - max(a, pa)
                if shared > 0:
                    out[name] = out.get(name, 0.0) + shared
                    rest -= shared
                i += 1
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + rest
    return {k: v / len(trace.devices) for k, v in out.items()}
