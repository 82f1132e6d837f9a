"""Program runs on the device joined with the program's spans that asked
for them and read them back.

Since the tick is dispatched ahead, a decode is launched in one tick and
waited for in the next, so position in the tick pairs nothing. The engine
numbers every program run it launches: its ``engine.decode.dispatch`` /
``engine.prefill.dispatch`` span carries ``run`` and ``program``
(``"decode"`` / ``"chunk"``), and the ``*.wait`` span that reads the run's
tokens carries the same ``run``. A run whose tokens were dropped (its slots
preempted or cancelled since the dispatch) has a dispatch and no wait.

The device's half is device 0's ``XLA Modules`` line: the k-th traced run of
a program is the (``at`` + k)-th dispatch of that program, and ``at``, the
shift, is the unknown. Two orders hold for the right shift and for no other:
a run starts on the device after its dispatch span began, and ends before
the ``*.wait`` that read it ended. The device plane's stamps do not sit on
the host's clock to the millisecond (``program_spans.align`` aligns HOST
spans; the device's lay 1-2 ms early in the traces caught), so the right
shift may break an order by that skew on the few pairs whose run started
or was read at once, while a wrong shift breaks one of them on EVERY pair
by the better part of a program run's length. So every shift that fits
(``0 <= at``, ``at + traced <= dispatches``) is scored by the seconds its
pairs break the two orders, summed (a run nobody waited for binds on its
start alone), and the least is taken where it is clearly least: under
``CLEAR`` (a tenth) of the runner-up's, or, where one shift alone fits, of
the time the paired runs took on the device, which is the size of what a
wrong shift breaks. No single device stamp against a single host stamp
decides anything.

Where no shift is clearly least, where the trace holds more runs than the
spans dispatches, where more than ``CUT`` dispatches of the stretch never
ran, or where the device's waits do not add up to its idle, the trace is
UNPAIRED: one line on stderr that starts ``bench_cells: unpaired:`` says
why, :func:`join` gives ``None`` as for an untraced run, and the three
readers of the device's half report nothing (``engine.tick_max_wait_pct``
reads host stamps alone and still does). That costs four diagnostics,
never the run's result. A program whose spans carry no ``run`` (one older
than the numbering) gives every reader nothing; a wait that names no
earlier dispatch of its program says the PROGRAM is wrong and is an error
like a kernel that is not found.
"""

from __future__ import annotations

import bisect
import dataclasses
import sys

from bench_cells import program_spans
from bench_cells.reduce import xplane

# program -> (its pattern's key in the mix, its dispatch span, its wait span)
PROGRAMS = {
    "decode": ("decode_tick", "engine.decode.dispatch", "engine.decode.wait"),
    "chunk": ("prefill_chunk", "engine.prefill.dispatch",
              "engine.prefill.wait"),
}
_DISPATCH = {d: p for p, (_, d, _) in PROGRAMS.items()}
_WAIT = {w: p for p, (_, _, w) in PROGRAMS.items()}
CUT = 2        # runs a stretch's end may cut: dispatched inside, run after
CLEAR = 0.1    # the least score is taken under this share of the runner-up's


class Unpaired(Exception):
    """The trace and the spans cannot be paired; the text says why."""


@dataclasses.dataclass
class Run:
    run: int
    program: str
    dispatch: object                 # the span that asked for it
    wait: object | None = None       # the span that read it, if any did
    start: float | None = None       # on the device, the trace's clock, s
    end: float | None = None


def _say(text: str) -> None:
    print(text, file=sys.stderr, flush=True)


def span_runs(spans) -> dict[int, Run]:
    """Run number -> :class:`Run` from the spans that carry one. A wait
    must name a run that a dispatch of its own program named earlier, and
    no run is waited for twice."""
    runs: dict[int, Run] = {}
    for s in sorted(spans, key=lambda s: s.start_ns):
        n = s.attrs.get("run")
        if n is None:
            continue
        if s.name in _DISPATCH:
            runs[n] = Run(n, s.attrs["program"], s)
        elif s.name in _WAIT:
            r = runs.get(n)
            if r is None or r.program != _WAIT[s.name] or r.wait is not None:
                raise SystemExit(
                    f"bench_cells: {s.name} names run {n}, which "
                    + ("no dispatch span named earlier" if r is None else
                       f"a {r.program} dispatch named"
                       if r.program != _WAIT[s.name]
                       else "another wait has read"))
            r.wait = s
    return runs


@dataclasses.dataclass
class Shift:
    """How one program's traced runs were laid on its dispatches."""
    at: int                          # the first traced run's dispatch
    score: float                     # s its pairs break the two orders by
    runner_up: float | None          # the next shift's; None: one fitted
    tried: int                       # shifts that fitted


@dataclasses.dataclass
class Joined:
    runs: dict[int, Run]
    offset: float                    # perf_counter -> the trace's clock, s
    error: float                     # a tick's own offset from it, at most
    ticks: list                      # the traced ticks, the trace's clock
    unrun: list                      # dispatched in the stretch, run after
    shifts: dict[str, Shift]         # program -> how its runs were paired
    waits: dict | None = None        # what device_waits found

    def on_trace(self, ns: int) -> float:
        return ns * 1e-9 + self.offset

    @property
    def traced(self) -> list[Run]:
        """The runs the trace holds, in the order the device ran them."""
        return sorted((r for r in self.runs.values() if r.start is not None),
                      key=lambda r: r.start)

    @property
    def skew(self) -> float | None:
        """The smallest ``wait.end - run.end`` over the traced runs whose
        wait slept on the device (``ready`` 0): the read-back at its
        quickest plus however far the device's stamps lie before the
        host's clock (less for stamps that lie after it)."""
        return min((self.on_trace(r.wait.end_ns) - r.end
                    for r in self.runs.values() if r.start is not None
                    and r.wait is not None and not r.wait.attrs.get("ready")),
                   default=None)


def window_runs(run):
    """The window's runs by number, or ``None`` where there is nothing to
    read: not a serving cell, a program without the recorder, or one whose
    spans carry no ``run``."""
    w = program_spans.serve_window(run)
    if w is None:
        return None, None
    return w, span_runs(w.spans) or None


_joined = None      # the last (trace, records, recorder) joined, and the join


def join(run):
    """The window's runs, those of the traced stretch with their time on
    the device, or ``None`` where there is nothing to read: an untraced
    run, or a trace that cannot be paired (which says so once on stderr).
    Every reader of a run asks; the trace is parsed, sorted and paired, or
    found unpaired, for the first."""
    global _joined
    if run["trace"] is None:
        return None
    of = (run["trace"], run["records"], program_spans.recorder())
    if _joined is None or any(a is not b for a, b in zip(_joined[0], of)):
        try:
            j = _join(run)
        except Unpaired as why:
            _say(f"bench_cells: unpaired: {why}")
            j = None
        _joined = (of, j)
    return _joined[1]


def _pairs(begun, ended, traced, at: int):
    """Per pair of the k-th traced run with the (``at`` + k)-th dispatch:
    the seconds the run starts BEFORE its dispatch began and the seconds
    it ends AFTER its wait ended (negative where it keeps the order)."""
    for k, ev in enumerate(traced):
        yield begun[at + k] - ev.start, ev.end - ended[at + k]


def _breaks(begun, ended, traced, at: int, stop: float) -> float:
    """A shift's score: the seconds its pairs break the two orders by,
    summed, and given up once past ``stop``."""
    total = 0.0
    for early, late in _pairs(begun, ended, traced, at):
        total += max(early, 0.0) + max(late, 0.0)
        if total > stop:
            break
    return total


def find_shift(program, pattern, asked, begun, ended, traced) -> Shift:
    """The shift at which ``traced`` (a program's runs on the device, in
    order) lie on ``asked`` (its dispatches, in order, begun at ``begun``,
    their waits ended at ``ended``, ``inf`` where nobody waited): every
    shift that fits is scored (:func:`_breaks`) and the least taken where
    it is under ``CLEAR`` of the runner-up's."""
    fits = len(asked) - len(traced) + 1
    if fits < 1:
        raise Unpaired(
            f"the trace holds {len(traced)} runs of {pattern!r} and the "
            f"spans {len(asked)} {program} dispatches: no shift fits")
    inf = float("inf")
    best, second = (inf, -1), (inf, -1)
    for at in range(fits):
        got = (_breaks(begun, ended, traced, at, second[0]), at)
        if got < best:
            best, second = got, best
        elif got < second:
            second = got
    (score, at), lone = best, fits == 1
    bar = sum(ev.end - ev.start for ev in traced) if lone else second[0]
    if traced and not score < CLEAR * bar:
        k, (early, late) = max(enumerate(_pairs(begun, ended, traced, at)),
                               key=lambda pair: max(pair[1]))
        r, ev = asked[at + k], traced[k]
        raise Unpaired(
            f"the {len(traced)} traced runs of {pattern!r} on the "
            f"{len(asked)} {program} dispatches break the two orders by "
            f"{score:.6f} s at shift {at}, the least of {fits} tried, and by "
            + (f"{second[0]:.6f} s at shift {second[1]}, the runner-up"
               if not lone else f"no other (the runs took {bar:.6f} s)")
            + f": not under {CLEAR} of it; at shift {at} {program} run "
            f"{r.run} would lie on the device at {ev.start:.6f}.."
            f"{ev.end:.6f} s, {1e3 * max(early, late):.3f} ms "
            + ("after the wait that read it ended" if late > early else
               "before its dispatch began"))
    return Shift(at, score, None if lone else second[0], fits)


def _join(run):
    records, trace = run["records"], run["trace"]
    _, runs = window_runs(run)
    if runs is None:
        return None
    offset = program_spans.align(records, trace)
    first, last = records["traced_ticks"]
    steps = sorted((e for e in trace.spans
                    if e.name == program_spans.ENGINE_STEP),
                   key=lambda e: e.start)
    error = max(abs(e.start - records["ticks"][first + k][0] - offset)
                for k, e in enumerate(steps))
    ticks = [(ts + offset, te + offset)
             for ts, te, _ in records["ticks"][first:last]]
    j = Joined(runs, offset, error, ticks, [], {})
    lo, hi = ticks[0][0], ticks[-1][1]
    dev = trace.devices[0]
    for program, (key, _, _) in PROGRAMS.items():
        pattern = run["mix"]["programs"][key]
        traced = sorted(xplane.module_runs(dev, pattern),
                        key=lambda e: e.start)
        asked = sorted((r for r in runs.values() if r.program == program),
                       key=lambda r: r.dispatch.start_ns)
        begun = [j.on_trace(r.dispatch.start_ns) for r in asked]
        ended = [float("inf") if r.wait is None
                 else j.on_trace(r.wait.end_ns) for r in asked]
        shift = find_shift(program, pattern, asked, begun, ended, traced)
        j.shifts[program] = shift
        for r, ev in zip(asked[shift.at:], traced):
            r.start, r.end = ev.start, ev.end
        after = shift.at + len(traced)
        cut = [r for r, b in zip(asked[after:], begun[after:])
               if lo <= b <= hi]
        if len(cut) > CUT:
            raise Unpaired(
                f"{len(cut)} {program} dispatches of the traced stretch "
                f"have no run of {pattern!r} in the trace ({len(traced)} "
                f"runs); at most {CUT} may be cut at its end")
        j.unrun.extend(cut)
    j.unrun.sort(key=lambda r: r.dispatch.start_ns)
    j.waits = device_waits(j, trace)
    return j


def device_waits(j: Joined, trace) -> dict[str, float]:
    """Device 0's idle seconds inside the traced ticks, three ways: inside
    program runs (``inside``), and between the end of one run and the start
    of the next, split where the next run's dispatch span began: before it
    the device had nothing asked of it (``host``), after it the call, the
    runtime and the chip (``launch``). With ``idle``, their sum as the
    device's operations alone give it; where the two do not agree within
    2 % the trace is unpaired."""
    lo, hi = j.ticks[0][0], j.ticks[-1][1]
    dev = trace.devices[0]
    idle = program_spans._overlaps(
        xplane.gaps(xplane.busy_intervals(dev, lo, hi), lo, hi), j.ticks)
    traced = j.traced
    ran = xplane.merge((r.start, r.end) for r in traced)
    out = {"idle": xplane.total(idle), "host": 0.0, "launch": 0.0,
           "inside": xplane.total(program_spans._overlaps(idle, ran))}
    starts = [r.start for r in traced]
    for a, b in program_spans._overlaps(idle, xplane.gaps(ran, lo, hi)):
        i = bisect.bisect_left(starts, b - 1e-9)
        # past the last traced run the next one is the first the stretch's
        # end cut (the device runs them in the order they were asked for)
        nxt = traced[i] if i < len(traced) else next(iter(j.unrun), None)
        asked = b if nxt is None else j.on_trace(nxt.dispatch.start_ns)
        out["host"] += max(min(b, asked) - a, 0.0)
        out["launch"] += max(b - max(a, asked), 0.0)
    parts = out["host"] + out["launch"] + out["inside"]
    if abs(parts - out["idle"]) > 0.02 * out["idle"] + 1e-9:
        raise Unpaired(
            f"the device's waits for the host {out['host']:.6f} s and for "
            f"the launch {out['launch']:.6f} s and its idle inside runs "
            f"{out['inside']:.6f} s do not add up to its idle inside the "
            f"traced ticks, {out['idle']:.6f} s")
    return out


def read_device_wait(run, part: str):
    """What the two ``engine.device_wait_*_ms_per_tick`` readers are: the
    mean a traced tick of ``part`` (``host`` / ``launch``), in ms; the
    ``host`` reader says on stderr what the three parts were and how the
    runs were paired."""
    j = join(run)
    if j is None:
        return None
    w = j.waits
    n = len(j.ticks)
    if part == "host":
        skew = j.skew
        _say(f"device 0 idle inside the {n} traced ticks: "
             f"{w['idle']:.6f} s = waiting for the host {w['host']:.6f} + "
             f"for the launch {w['launch']:.6f} + inside program runs "
             f"{w['inside']:.6f}; {len(j.traced)} runs paired, "
             f"{len(j.unrun)} cut at the end; the join's error (a traced "
             f"tick's own offset from the median) {1e6 * j.error:.1f} us; "
             + "; ".join(
                 f"{p} shift {s.at} of {s.tried} breaks the orders by "
                 f"{s.score:.6f} s, the runner-up by "
                 + ("-" if s.runner_up is None else f"{s.runner_up:.6f}")
                 for p, s in j.shifts.items())
             + "; the smallest wait.end - run.end over waits that slept "
             + ("-" if skew is None else f"{1e3 * skew:.3f} ms"))
    return 1e3 * w[part] / n


def readbacks(j: Joined) -> list[tuple[float, Run]]:
    """Per traced run that was waited for: seconds from the later of the
    wait's start and the run's end on the device to the wait's end."""
    return [(j.on_trace(r.wait.end_ns)
             - max(j.on_trace(r.wait.start_ns), r.end), r)
            for r in j.traced if r.wait is not None]


def tick_of(span, window):
    """The ``engine.tick`` span a child span lies in."""
    return next((t for t in window.ticks if t.id == span.parent), None)


def longest_tick(run, window):
    """The window's longest ``engine.tick`` and the one tick left out of
    the choice (``None`` in an untraced run): the tick that follows the
    traced stretch, whose first call into the runtime after
    ``jax.profiler.stop_trace()`` holds 135-155 ms in half the traced
    windows (``PERF.md`` section 6, PR 37): the profiler's, in no measured
    stretch."""
    records, after = run["records"], None
    last = (records.get("traced_ticks") or (None, None))[1]
    if last is not None and last < len(records["ticks"]):
        ts, te, _ = records["ticks"][last]
        after = next((t for t in window.ticks if ts <= t.start_ns * 1e-9
                      and t.end_ns * 1e-9 <= te), None)
    return max((t for t in window.ticks if t is not after),
               key=program_spans.seconds, default=after), after


def wait_share(tick, window) -> float:
    """The share of ``tick`` that lies inside its ``*.wait`` children, by
    the spans' own stamps alone."""
    waits = sum(program_spans.seconds(c) for c in window.kids.get(tick.id, ())
                if c.name in _WAIT)
    return waits / program_spans.seconds(tick)


def stall_record(window, j: Joined | None, tick=None, left_out=None) -> str:
    """One line on ``tick``, the window's longest by default: its length
    and what the scheduler did to the thread in it, each child in order
    with its ms (and the stretch before it that lies in no child, where
    over 0.1 ms) and whatever stall nobody called for it holds (``py.gc``,
    ``jax.*``), for each wait its run, ``ready`` and ``cpu_ns``; where
    the tick lies in the traced stretch, each of its runs on the device
    relative to the tick's start; how many ticks exceeded four times the
    median; and the tick ``left_out`` of the choice, if one was."""
    longest = tick or max(window.ticks, key=program_spans.seconds)
    a = longest.attrs
    # ``-``: the reading is absent (no ``schedstat`` on this kernel)
    ms = lambda ns: "-" if ns is None else f"{ns / 1e6:.3f}"  # noqa: E731
    parts, at = [], longest.start_ns
    kids = sorted(window.kids.get(longest.id, ()), key=lambda s: s.start_ns)
    for c in kids:
        if c.start_ns - at > 100_000:
            parts.append(f"(no span) {ms(c.start_ns - at)}")
        text = f"{c.name} {ms(c.end_ns - c.start_ns)}"
        if c.name in _WAIT:
            text += (f" [run {c.attrs.get('run')} ready "
                     f"{c.attrs.get('ready')} cpu_ms "
                     f"{ms(c.attrs.get('cpu_ns', 0))} runq_ms "
                     f"{ms(c.attrs.get('runq_ns'))} nvcsw "
                     f"{c.attrs.get('nvcsw')} nivcsw {c.attrs.get('nivcsw')}]")
        elif c.name in _DISPATCH:
            text += f" [run {c.attrs.get('run')}]"
        text += "".join(
            f" {{{d.name} {ms(d.end_ns - d.start_ns)}}}"
            for d in program_spans.descendants(c, window.kids))
        parts.append(text)
        at = max(at, c.end_ns)
    if longest.end_ns - at > 100_000:
        parts.append(f"(no span) {ms(longest.end_ns - at)}")
    on_device = ""
    if j is not None:
        t0 = j.on_trace(longest.start_ns)
        seen = {}
        for r in (j.runs.get(c.attrs.get("run")) for c in kids):
            if r is not None and r.start is not None:
                seen[r.run] = (f"{r.program} {r.run} "
                               f"{1e3 * (r.start - t0):.3f}.."
                               f"{1e3 * (r.end - t0):.3f}")
        on_device = ("; its runs on the device (ms from the tick's start): "
                     + (", ".join(seen.values()) or "not in the traced "
                        "stretch"))
    lengths = sorted(program_spans.seconds(t) for t in window.ticks)
    median = lengths[len(lengths) // 2]
    over = sum(1 for x in lengths if x > 4 * median)
    return (f"stall record: tick {a.get('tick')} "
            f"{1e3 * program_spans.seconds(longest):.3f} ms (median "
            f"{1e3 * median:.3f}), cpu_ms {ms(a.get('cpu_ns', 0))} runq_ms "
            f"{ms(a.get('runq_ns'))} nvcsw {a.get('nvcsw')} nivcsw "
            f"{a.get('nivcsw')}, chunk {a.get('chunk')} decoding "
            f"{a.get('decoding')} runs {a.get('runs')}; children (ms): "
            + ", ".join(parts) + on_device
            + f"; {over} of {len(lengths)} ticks of the window exceeded 4 x "
            f"the median"
            + ("" if left_out is None else
               f"; left out, the tick after the traced stretch: tick "
               f"{left_out.attrs.get('tick')} "
               f"{1e3 * program_spans.seconds(left_out):.3f} ms"))
