"""The join of program runs on the device with the spans that asked for them
and read them (``bench_cells/program_runs.py``) and its four readers, held
to hand-made spans and a hand-made trace; then the toy serve cell on the
CPU, whose real recorder gives the host's half.

All times are ms after the window's start; the trace's clock runs ``SKEW``
ahead of ``perf_counter``.

Three traced ticks. Tick 1 (100-200) dispatches decode run 1, chunk run 2
and, ahead, decode run 3, then reads runs 1 and 2; tick 2 (300-400)
dispatches run 4 ahead and reads run 3, whose bytes were there; tick 3
(500-650, the window's longest) dispatches run 5, which the stretch's end
cuts, and reads run 4. On the device: run 1 116-150 with a hole at 130-132,
run 2 152-185, run 3 185-250, another program's copy 303-305, run 4
330-635.
"""

import bisect
import dataclasses
import importlib
import json
import os
import re

import pytest

from bench_cells import harness, manifest, program_runs, program_spans
from bench_cells.reduce import xplane

MS = 1_000_000
T0 = 100.0
SKEW = 1000.0
MIX = {"programs": {"decode_tick": "^jit_step", "prefill_chunk": "^jit_chunk"}}
SCHED = {"cpu_ns": 1 * MS, "runq_ns": 0, "nvcsw": 1, "nivcsw": 0}
NEW = ("engine.device_wait_host_ms_per_tick",
       "engine.device_wait_launch_ms_per_tick", "engine.readback_ms_p50",
       "engine.tick_max_wait_pct")


@dataclasses.dataclass
class Span:
    name: str
    start_ns: int
    end_ns: int
    id: int
    parent: int | None = None
    attrs: dict = dataclasses.field(default_factory=dict)


class Recorder:
    evicted_until_ns = 0
    dropped = 0

    def __init__(self, spans):
        self._spans = spans

    def spans(self):
        return list(self._spans)


def at(ms: float) -> int:
    return int(T0 * 1e9) + int(round(ms * MS))


def dispatch(program, a, b, run, id_, parent):
    name = program_runs.PROGRAMS[program][1]
    return Span(name, at(a), at(b), id_, parent,
                {"run": run, "program": program})


def wait(program, a, b, run, ready, id_, parent):
    name = program_runs.PROGRAMS[program][2]
    return Span(name, at(a), at(b), id_, parent,
                dict(SCHED, run=run, ready=ready))


def hand_made():
    tick = lambda a, b, n, id_, cpu, runs: Span(  # noqa: E731
        "engine.tick", at(a), at(b), id_, None,
        dict(SCHED, tick=n, chunk=int(n == 1), decoding=2, runs=runs,
             cpu_ns=cpu * MS))
    spans = [
        dispatch("decode", 110, 115, 1, 11, 10),
        dispatch("chunk", 120, 125, 2, 12, 10),
        dispatch("decode", 130, 135, 3, 13, 10),
        wait("decode", 140, 160, 1, 0, 14, 10),
        wait("chunk", 160, 190, 2, 0, 15, 10),
        tick(100, 200, 1, 10, 12, 3),
        dispatch("decode", 310, 315, 4, 21, 20),
        wait("decode", 320, 330, 3, 1, 22, 20),
        tick(300, 400, 2, 20, 8, 1),
        dispatch("decode", 510, 515, 5, 31, 30),
        wait("decode", 520, 640, 4, 0, 32, 30),
        tick(500, 650, 3, 30, 15, 1),
    ]
    records = {
        "kind": "serve", "t0": T0, "window_s": 1.0,
        "ticks": [(T0 + 0.100, T0 + 0.200, 2), (T0 + 0.300, T0 + 0.400, 1),
                  (T0 + 0.500, T0 + 0.660, 1)],
        "traced_ticks": [0, 3],
    }
    return records, spans


def trace_of(runs, ops=None):
    """``runs``: ``(module name, start, end)`` on device 0; its operations
    are the runs themselves unless ``ops`` says otherwise."""
    ev = lambda name, a, b: xplane.Event(  # noqa: E731
        name, T0 + SKEW + a / 1e3, T0 + SKEW + b / 1e3)
    modules = [ev(*r) for r in runs]
    ops = [ev(f"fusion.{i}", a, b) for i, (a, b) in enumerate(
        ops if ops is not None else [r[1:] for r in runs])]
    steps = [ev("bench.serve.engine_step", a, b)
             for a, b in ((100, 200), (300, 400), (500, 660))]
    return xplane.Trace([xplane.Device(0, ops, modules)], steps)


RUNS = [("jit_step_paged_decode(1)", 116, 150),
        ("jit_chunk_paged_prefill(2)", 152, 185),
        ("jit_step_paged_decode(1)", 185, 250),
        ("jit_step_paged_decode(1)", 330, 635)]
OPS = [(116, 130), (132, 150), (152, 185), (185, 250), (303, 305),
       (330, 635)]


@pytest.fixture
def run(monkeypatch):
    records, spans = hand_made()
    recorder = Recorder(spans)
    monkeypatch.setattr(program_spans, "recorder", lambda: recorder)
    return {"records": records, "trace": trace_of(RUNS, OPS), "mix": MIX,
            "recorder": recorder}


def read(name, run):
    return manifest.load_reader(name)(run)


def _ms(seconds):
    return round(seconds * 1e3, 6)


# -- the pairing ------------------------------------------------------------------


def test_every_traced_run_meets_its_dispatch_and_its_wait(run):
    j = program_runs.join(run)
    got = {r.run: (r.program, r.wait and r.wait.attrs["run"],
                   r.start and _ms(r.start - SKEW - T0),
                   r.end and _ms(r.end - SKEW - T0))
           for r in j.runs.values()}
    assert got == {1: ("decode", 1, 116, 150), 2: ("chunk", 2, 152, 185),
                   3: ("decode", 3, 185, 250), 4: ("decode", 4, 330, 635),
                   5: ("decode", None, None, None)}
    assert [r.run for r in j.traced] == [1, 2, 3, 4]
    assert [r.run for r in j.unrun] == [5]          # cut at the stretch's end
    assert j.offset == pytest.approx(SKEW) and j.error < 1e-6
    # the decode waited for in tick 2 was dispatched in tick 1
    w, runs = program_runs.window_runs(run)
    assert program_runs.tick_of(runs[3].dispatch, w).attrs["tick"] == 1
    assert program_runs.tick_of(runs[3].wait, w).attrs["tick"] == 2


def test_the_first_traced_run_may_have_been_asked_for_before_the_stretch(
        run):
    """The trace opens on tick 2: its first decode run is run 3, dispatched
    a tick earlier; run 2's and run 1's dispatches lie before it."""
    run["records"]["traced_ticks"] = [1, 3]
    run["trace"] = trace_of(RUNS[2:], OPS[3:])
    run["trace"].spans = run["trace"].spans[1:]
    j = program_runs.join(run)
    assert [r.run for r in j.traced] == [3, 4]
    assert [r.run for r in j.unrun] == [5]


# -- the device's waits -----------------------------------------------------------


def test_gaps_before_after_and_cut_by_the_next_dispatch(run):
    """Idle inside the ticks: 100-116 cut by run 1's dispatch at 110 (host
    10, launch 6); 130-132 inside run 1; 150-152 wholly after run 2's
    dispatch (launch 2); 300-303 wholly before run 4's at 310 (host 3);
    305-330 cut by it (host 5, launch 20); 635-660, to the end of the
    harness's tick, waits for run 5, asked for at 510 (launch 25)."""
    w = program_runs.device_waits(program_runs.join(run), run["trace"])
    assert {k: _ms(v) for k, v in w.items()} == {
        "idle": 73, "inside": 2, "host": 18, "launch": 53}


@pytest.mark.parametrize("name,value", [
    ("engine.device_wait_host_ms_per_tick", 18 / 3),
    ("engine.device_wait_launch_ms_per_tick", 53 / 3),
    ("engine.readback_ms_p50", 7.5),          # 10, 5, 10 (a copy), 5
    ("engine.tick_max_wait_pct", 80.0),       # 120 of tick 3's 150 ms
])
def test_each_reader_on_the_hand_made_join(run, name, value, capsys):
    assert read(name, run) == pytest.approx(value)
    err = capsys.readouterr().err
    if name == "engine.device_wait_launch_ms_per_tick":
        assert not err                    # its sibling says the three parts
    if name == "engine.device_wait_host_ms_per_tick":
        assert ("idle inside the 3 traced ticks: 0.073000 s = waiting for "
                "the host 0.018000 + for the launch 0.053000 + inside "
                "program runs 0.002000; 4 runs paired, 1 cut") in err
        assert err.rstrip().endswith(
            "decode shift 0 of 2 breaks the orders by 0.000000 s, the "
            "runner-up by 0.319000; chunk shift 0 of 1 breaks the orders by "
            "0.000000 s, the runner-up by -; the smallest wait.end - run.end "
            "over waits that slept 5.000 ms")
    if name == "engine.readback_ms_p50":
        assert ("the largest 10.000 ms: decode run 1 in tick 1, ready 0, "
                "the wait 20.000 ms") in err
    if name == "engine.tick_max_wait_pct":
        assert err.count("\n") == 1
        for piece in ("stall record: tick 3 150.000 ms (median 100.000), "
                      "cpu_ms 15.000 runq_ms 0.000 nvcsw 1 nivcsw 0",
                      "(no span) 10.000, engine.decode.dispatch 5.000 "
                      "[run 5], (no span) 5.000, engine.decode.wait 120.000 "
                      "[run 4 ready 0 cpu_ms 1.000",
                      "decode 4 -170.000..135.000",
                      "0 of 3 ticks of the window exceeded 4 x the median"):
            assert piece in err, piece


def test_a_run_without_a_wait_is_left_out_of_the_read_back(run):
    """Run 3's slots were preempted after its dispatch: nothing read it."""
    run["recorder"]._spans = [s for s in run["recorder"]._spans
                              if s.id != 22]
    assert [r.run for r in program_runs.join(run).traced] == [1, 2, 3, 4]
    assert read("engine.readback_ms_p50", run) == pytest.approx(5.0)
    assert read("engine.device_wait_host_ms_per_tick", run) \
        == pytest.approx(6.0)


def test_untraced_run_reads_the_hosts_half_alone(run, capsys):
    run["trace"] = None
    assert [read(n, run) for n in NEW[:3]] == [None] * 3
    assert read(NEW[3], run) == pytest.approx(80.0)
    err = capsys.readouterr().err
    record = stall_record(err)
    assert "stall record: tick 3" in record and "on the device" not in record
    assert "left out" not in err
    assert "runq_ms 0.000" in err
    # a collection nobody called for, inside the dispatch: named with it
    run["recorder"]._spans.append(
        Span("py.gc", at(511), at(514), 33, 31, {"generation": 2}))
    read(NEW[3], run)
    assert ("engine.decode.dispatch 5.000 [run 5] {py.gc 3.000}, "
            in capsys.readouterr().err)
    # a kernel without ``schedstat``: the reading is absent, not zero
    for s in run["recorder"]._spans:
        s.attrs.pop("runq_ns", None)
    assert read(NEW[3], run) == pytest.approx(80.0)
    err = capsys.readouterr().err
    assert "cpu_ms 15.000 runq_ms - nvcsw 1" in err
    assert "0.000 nvcsw" not in err
    # nor does a sandboxed kernel count switches: the share needs neither
    for s in run["recorder"]._spans:
        s.attrs.pop("nvcsw", None), s.attrs.pop("nivcsw", None)
    assert read(NEW[3], run) == pytest.approx(80.0)
    assert "runq_ms - nvcsw None nivcsw None" in capsys.readouterr().err


def test_the_tick_after_the_traced_stretch_is_not_the_longest(run, capsys):
    """The first call into the runtime after ``stop_trace`` is the
    profiler's: the trace closed on tick 2, tick 3 is left out of the
    choice and named, and tick 1 (100 ms, 50 of them in its two waits) is
    the longest of the rest (tick 2's 100 ms come second in order)."""
    run["records"]["traced_ticks"] = [0, 2]
    run["trace"] = trace_of(RUNS[:3], OPS[:4])
    run["trace"].spans = run["trace"].spans[:2]
    assert read(NEW[3], run) == pytest.approx(50.0)
    err = capsys.readouterr().err
    assert "stall record: tick 1 100.000 ms" in err
    assert err.rstrip().endswith("left out, the tick after the traced "
                                 "stretch: tick 3 150.000 ms")
    # a trace that ran to the window's end is followed by no tick
    run["records"]["traced_ticks"] = [0, 3]
    run["trace"] = trace_of(RUNS, OPS)
    assert read(NEW[3], run) == pytest.approx(80.0)
    assert "left out" not in capsys.readouterr().err


def test_the_join_is_made_once_a_run(run, monkeypatch):
    made = []
    real = program_runs._join
    monkeypatch.setattr(program_runs, "_join",
                        lambda r: made.append(1) or real(r))
    for name in NEW:
        read(name, run)
    assert made == [1]
    run["trace"] = trace_of(RUNS, OPS)           # another run's trace
    read(NEW[0], run)
    assert made == [1, 1]


def test_a_program_without_run_numbers_reads_nothing(run):
    """The parent of the PR that numbered the runs: the same spans with no
    ``run``, ``program`` or scheduler readings."""
    for s in run["recorder"]._spans:
        s.attrs = {k: v for k, v in s.attrs.items()
                   if k in ("tick", "chunk", "decoding", "rid")}
    assert [read(n, run) for n in NEW] == [None] * 4
    for kind in ("train",):
        ctx = dict(run, records=dict(run["records"], kind=kind))
        assert [read(n, ctx) for n in NEW] == [None] * 4


# -- a stretch as the chip gives it, and the device's clock off by a skew -----------


def stretch(skew=0.0, stall=None, first=4, last=14, n=18):
    """A window of ``n`` ticks from the numbers of the trace that PR 39
    caught (``git show 3a5e92a:PERF.md``, Open question 14; closed by PR
    41), ms: every tick dispatches a
    chunk run (22.05 on the device) and, ahead, a decode run (19.55) that
    the NEXT tick reads; the device runs them in order and never idles, so
    a run starts 15-17 ms after its dispatch, behind the run before it; a
    wait ends 1.3 after its run's end. ``start_trace`` holds the host for
    47 ms before tick ``first``: the device runs dry, and the stretch's
    first chunk run starts 0.83 after its dispatch span begins;
    ``stop_trace`` holds it for 12 s before tick ``last``, writing the
    trace (46-50 ms and 11.5-12.5 s in 24 traced runs of the latent-expert
    cell; my chip runs, PR 41). ``stall`` names a traced tick before which
    the host stands for 100 ms more (a pause of the runtime, in 5 of those
    24): the device runs dry mid-stretch too. The trace
    holds ticks ``first``..``last`` and every run asked for in them, its
    device stamps ``skew`` ms late (early where negative). Returns the
    reader's ``run`` and, per program, run numbers in the order traced."""
    spans, ticks, dev, free, t = [], [], {}, 0.0, 100.0
    prev_decode, run_no, ids = None, 0, iter(range(1, 10 ** 6))

    def launch(program, a, b, tick_id):
        nonlocal free, run_no
        run_no += 1
        spans.append(dispatch(program, a, b, run_no, next(ids), tick_id))
        start = max(free, a + 0.83)
        free = start + (22.05 if program == "chunk" else 19.55)
        dev[run_no] = (program, start, free)
        return run_no

    def read_back(program, a, r, tick_id):
        b = max(a + 0.13, dev[r][2] + 1.3)
        spans.append(wait(program, a, b, r, int(b == a + 0.13), next(ids),
                          tick_id))
        return b

    for i in range(n):
        t += {first: 47.0, last: 12000.0, stall: 100.0}.get(i, 0.0)
        t0, tick_id = t, next(ids)
        chunk = launch("chunk", t + 0.4, t + 1.3, tick_id)
        decode = launch("decode", t + 2.4, t + 3.2, tick_id)
        t += 3.25
        if prev_decode is not None:
            t = read_back("decode", t, prev_decode, tick_id) + 0.4
        t = read_back("chunk", t, chunk, tick_id) + 0.3
        spans.append(Span("engine.tick", at(t0), at(t), tick_id, None,
                          dict(SCHED, tick=i, chunk=1, decoding=96, runs=2)))
        ticks.append((t0, t))
        prev_decode, t = decode, t + 0.05
    lo, hi = ticks[first][0], ticks[last - 1][1]
    ev = lambda name, a, b, by=0.0: xplane.Event(  # noqa: E731
        name, T0 + SKEW + (a + by) / 1e3, T0 + SKEW + (b + by) / 1e3)
    # every run asked for in the stretch is in the trace: the last ones end
    # while ``stop_trace`` is still writing
    traced = [(r, p, a, b) for r, (p, a, b) in dev.items()
              if lo <= a <= hi + 100.0]
    modules = [ev("jit_step_x(1)" if p == "decode" else "jit_chunk_x(2)",
                  a, b, skew) for _, p, a, b in traced]
    # the harness's tick holds the program's, 10 us on either side
    outer = [(a - 0.01, b + 0.01) for a, b in ticks]
    trace = xplane.Trace(
        [device_of(modules)],
        [ev("bench.serve.engine_step", a, b) for a, b in outer[first:last]])
    records = {"kind": "serve", "t0": T0, "window_s": t / 1e3 + 1.0,
               "ticks": [(T0 + a / 1e3, T0 + b / 1e3, 96) for a, b in outer],
               "traced_ticks": [first, last]}
    order = {p: [r for r, q, _, _ in traced if q == p]
             for p in program_runs.PROGRAMS}
    return ({"records": records, "trace": trace, "mix": MIX,
             "recorder": Recorder(spans)}, order)


def device_of(modules):
    """Device 0 with ``modules`` as its program runs and, as its
    operations, the runs themselves."""
    return xplane.Device(0, [xplane.Event(f"fusion.{i}", m.start, m.end)
                             for i, m in enumerate(modules)], modules)


def one_stamps_guess(j, program):
    """Where the join until PR 41 took a program's first traced run to have
    been dispatched: the last dispatch that began no later than the run
    did, give or take the join's error and 50 us."""
    asked = sorted((r for r in j.runs.values() if r.program == program),
                   key=lambda r: r.dispatch.start_ns)
    first = min(r.start for r in asked if r.start is not None)
    begun = [j.on_trace(r.dispatch.start_ns) for r in asked]
    return bisect.bisect_right(begun, first + j.error + 50e-6) - 1


def stall_record(err):
    record, = (x for x in err.splitlines() if x.startswith("stall record"))
    return record


def _paired(j):
    return {p: [r.run for r in j.traced if r.program == p]
            for p in program_runs.PROGRAMS}


SKEWS = (-3.0, -2.0, -1.1, -0.5, 0.0, 0.5, 1.1, 2.0, 3.0)


@pytest.mark.parametrize("stall", [None, 9], ids=["opening", "mid-stretch"])
@pytest.mark.parametrize("skew", SKEWS)
def test_every_skew_of_the_devices_clock_gives_the_pairs_of_none(
        monkeypatch, skew, stall):
    """The device's stamps 1.1 ms early put the stretch's first chunk run
    0.27 ms BEFORE its dispatch span and every wait's end 2.4 ms after its
    run's (the trace PR 39 caught); a run that found the device idle
    mid-stretch lies before its dispatch alike (the trace PR 40 left: "a
    run before its dispatch began"); stamps that lie late put a run's end
    after its wait's. Whatever the skew, the runs meet the dispatches that
    asked for them."""
    run, order = stretch(skew, stall)
    monkeypatch.setattr(program_spans, "recorder", lambda: run["recorder"])
    j = program_runs.join(run)
    assert j is not None
    assert _paired(j) == order
    assert len(order["chunk"]) >= 8 and len(order["decode"]) >= 8


def test_the_caught_trace_lies_one_dispatch_offone_stamps_guess(monkeypatch):
    """What the join did until PR 41, on the caught numbers: the last
    dispatch that began no later than the first traced run did is the
    chunk of the tick BEFORE (the event lies 0.27 ms before its own
    dispatch), one dispatch early; every pair then ends tens of ms after
    the wait it is given."""
    run, order = stretch(-1.1)
    monkeypatch.setattr(program_spans, "recorder", lambda: run["recorder"])
    j = program_runs.join(run)
    s = j.shifts["chunk"]
    assert one_stamps_guess(j, "chunk") == s.at - 1
    assert one_stamps_guess(j, "decode") == j.shifts["decode"].at
    first = j.runs[order["chunk"][0]]
    assert first.start == min(r.start for r in j.traced)
    assert j.on_trace(first.dispatch.start_ns) - first.start \
        == pytest.approx(0.27e-3, abs=1e-6)
    assert s.score == pytest.approx(0.27e-3, abs=1e-6)
    assert s.runner_up > 100 * s.score
    assert 1e3 * j.skew == pytest.approx(2.4, abs=1e-6)


# -- two traces as the chip gave them -------------------------------------------------


def _caught(name):
    """A reader's ``run`` from one of the two inputs kept under ``data/``:
    what the join read in one traced run on the chip (my chip runs, PR 41;
    cut by a builder's aid to the traced stretch, 1.5 s before it and a
    second of what follows ``stop_trace``; the device's operations are the
    program runs themselves). Also what the old join made of it."""
    path = os.path.join(os.path.dirname(__file__), "data", name)
    with open(path, encoding="utf-8") as f:
        d = json.load(f)
    assert os.path.getsize(path) < 200_000
    names = d["span_names"]
    spans = []
    for n, a, b, id_, parent, r, ready, tick in d["spans"]:
        name = names[n]
        attrs = {"tick": tick} if name == "engine.tick" else {"run": r}
        if name in program_runs._DISPATCH:
            attrs["program"] = program_runs._DISPATCH[name]
        elif name in program_runs._WAIT:
            attrs["ready"] = ready
        spans.append(Span(name, a, b, id_, parent, attrs))
    modules = [xplane.Event(f"{pattern[1:]}_x({k})", a, b)
               for k, pattern in d["programs"].items()
               for a, b in d["modules"][k]]
    trace = xplane.Trace(
        [device_of(modules)],
        [xplane.Event(program_spans.ENGINE_STEP, a, b)
         for a, b in d["steps"]])
    records = {"kind": "serve", "t0": d["t0"], "window_s": d["window_s"],
               "ticks": [tuple(t) for t in d["ticks"]],
               "traced_ticks": d["traced_ticks"]}
    return ({"records": records, "trace": trace,
             "mix": {"programs": d["programs"]},
             "recorder": Recorder(spans)}, d)


def test_a_trace_the_old_join_paired_gives_the_same_pairs(monkeypatch):
    run, d = _caught("join_paired_by_the_old.json")
    monkeypatch.setattr(program_spans, "recorder", lambda: run["recorder"])
    assert d["old_verdict"] == "paired"
    j = program_runs.join(run)
    got = {str(r.run): [r.start, r.end] for r in j.traced}
    assert got == d["old_pairs"] and len(got) > 200
    for program, s in j.shifts.items():
        assert s.at == one_stamps_guess(j, program)
        assert s.score < 1e-3 and s.runner_up > 100 * s.score, program


def test_a_trace_the_old_join_refused_is_paired(monkeypatch, capsys):
    """The one of 24 traced runs of the latent-expert cell that the join
    as it was refused (my chip runs, PR 41; the machine's first traced
    process, its device stamps 2.0 ms before the host's clock where the 23
    others read 1.0-1.2): the one stamp's guess was right for both
    programs, but a pause of the runtime had let the device run dry
    mid-stretch, and the decode run that found it idle lies 0.56 ms BEFORE
    the span that asked for it: "before its dispatch began", as the trace
    PR 40 left. (The trace PR 39 caught, one dispatch off the guess at the
    stretch's opening, did not come again in 24 runs: its numbers are the
    hand-made stretch's above.)"""
    run, d = _caught("join_refused_by_the_old.json")
    monkeypatch.setattr(program_spans, "recorder", lambda: run["recorder"])
    assert d["old_verdict"] == (
        "refused: bench_cells: decode run 666 would lie on the device at "
        "1.376632..1.396170 s, before its dispatch began: the trace cannot "
        "be paired")
    j = program_runs.join(run)
    assert {str(r.run): [r.start, r.end] for r in j.traced} == d["new_pairs"]
    assert len(j.traced) == 242 and not j.unrun
    for program, s in j.shifts.items():
        assert s.at == one_stamps_guess(j, program)
        assert s.score < 1e-3 and s.runner_up > 100 * s.score, program
    # all that breaks an order is run 666, by how far it lies before its span
    early = j.on_trace(j.runs[666].dispatch.start_ns) - j.runs[666].start
    assert early == pytest.approx(0.563e-3, abs=1e-6)
    assert j.shifts["decode"].score == pytest.approx(early)
    assert j.shifts["chunk"].score == 0
    assert 1e3 * j.skew == pytest.approx(2.021, abs=1e-3)
    # and the three readers read
    assert all(read(n, run) > 0 for n in NEW[:3])
    capsys.readouterr()


# -- what the program got wrong is an error; what cannot be paired reads nothing ------


def test_a_wait_must_name_an_earlier_dispatch_of_its_program(run):
    spans = run["recorder"]._spans
    spans[7].attrs["run"] = 9
    with pytest.raises(SystemExit, match="names run 9, which no dispatch"):
        read("engine.tick_max_wait_pct", run)
    spans[7].attrs["run"] = 2               # a chunk's run, a decode's wait
    with pytest.raises(SystemExit, match="run 2, which a chunk dispatch"):
        read("engine.readback_ms_p50", run)
    spans[7].attrs["run"] = 1               # read already, in tick 1
    with pytest.raises(SystemExit, match="run 1, which another wait"):
        read("engine.readback_ms_p50", run)


def _swap_two_waits(run):
    """Run 4 was dispatched at 310 and is read at 320, run 3 at 520: fine
    by the spans, but run 4 still ran when its wait ended."""
    spans = run["recorder"]._spans
    spans[7].attrs["run"] = 4
    spans[10].attrs["run"] = 3


@pytest.mark.parametrize("runs,match", [
    # a decode run before any dispatch began: the one shift that fits lays
    # every run before its dispatch
    ([("jit_step_x", 101, 105)] + RUNS,
     "break the two orders by 0.328000 s at shift 0, the least of 1 tried, "
     "and by no other .*decode run 5 would lie on the device at "
     r"1100.330000..1100.635000 s, 180.000 ms before its dispatch began"),
    # more runs than dispatches
    (RUNS + [("jit_step_x", 640, 645), ("jit_step_x", 646, 648)],
     "the trace holds 5 runs of '.jit_step' and the spans 4 decode "
     "dispatches: no shift fits"),
    # three decodes asked for in the stretch and never run
    (RUNS[:2], "3 decode dispatches of the traced stretch have no run"),
    # two waits that read each other's runs: no shift is clearly least
    (_swap_two_waits,
     "by 0.305000 s at shift 0, the least of 2 tried, and by 0.319000 s at "
     "shift 1, the runner-up: not under 0.1 of it; at shift 0 decode run 4 "
     r"would lie on the device at 1100.330000..1100.635000 s, 305.000 ms "
     "after the wait that read it ended"),
    # no chunk run at all is one cut run, which the end may cut: paired
    ([r for r in RUNS if "chunk" not in r[0]], None),
])
def test_a_trace_that_cannot_be_paired_reads_nothing_and_says_why(
        run, runs, match, capsys):
    if callable(runs):
        runs(run)
    else:
        run["trace"] = trace_of(runs)
    if match is None:
        assert [r.run for r in program_runs.join(run).unrun] == [2, 5]
        return
    assert program_runs.join(run) is None
    assert [read(n, run) for n in NEW[:3]] == [None] * 3
    # the host's half needs no pairing: the longest tick's share, and its
    # stall record without the device's half, as in an untraced run
    assert read(NEW[3], run) == pytest.approx(80.0)
    err = capsys.readouterr().err
    said = [line for line in err.splitlines()
            if line.startswith("bench_cells: unpaired: ")]
    assert len(said) == 1 and re.search(match, said[0]), err
    record = stall_record(err)
    assert "stall record: tick 3" in record and "on the device" not in record


def test_shifts_that_break_nothing_alike_leave_the_trace_unpaired(
        run, capsys):
    """One decode run after every dispatch, and nobody waited for any: all
    four shifts keep both orders, so none is clearly least."""
    run["recorder"]._spans = [s for s in run["recorder"]._spans
                              if s.name not in program_runs._WAIT]
    run["trace"] = trace_of([("jit_step_x", 700, 710)])
    assert program_runs.join(run) is None
    assert ("break the two orders by 0.000000 s at shift 0, the least of 4 "
            "tried, and by 0.000000 s at shift 1, the runner-up: not under "
            "0.1 of it") in capsys.readouterr().err


def test_manifest_lists_the_four_readers_for_the_cells_that_can_take_them():
    """Entries of the serve engine's layer, listed for every serving cell
    (the block-diffusion cell since PR 41: its block programs number their
    runs too). By name and by membership: a metric or a cell appended
    later trips nothing."""
    entries = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    cells = {"gpt2-large.serve-closed", "jamba2-3b.serve-reason-closed",
             "nemotron3-super-120b-a12b.serve-agent-closed",
             "sdar-30b-a3b.serve-diffuse-closed"}
    for name in NEW:
        m = entries[name]
        assert cells <= set(m["workloads"]) and m["layer"] == "serve engine"
        assert m["source"] == ("program_span" if "tick_max" in name
                               else "device_trace")
    assert entries[NEW[2]]["moves"] == "tpot_p95_ms"
    assert {entries[n]["moves"] for n in NEW if n != NEW[2]} \
        == {"serve_tokens_per_s"}


# -- against the real recorder, at toy size ---------------------------------------


def test_toy_serve_cell_reads_the_longest_ticks_wait_share(capsys):
    from simple_distributed_machine_learning_tpu.telemetry import tracing

    toy = importlib.import_module("test_bench_cells_run")
    previous = tracing.install(tracing.Tracer())
    try:
        cell = toy.serve_cell()
        runner = importlib.import_module("bench_cells.runners.serve")
        r = runner.Run(cell, 2 ** 31 + 11, harness.Spans())
        r.setup()
        r.window(1.0, toy._NoTrace())
        ctx = {"records": r.records, "setup": {}, "trace": None,
               "peaks": toy.PEAKS, "chips": 1, "gpt": toy.GPT,
               "mix": cell.traffic}
        capsys.readouterr()
        value = read("engine.tick_max_wait_pct", ctx)
        err = capsys.readouterr().err
        w, runs = program_runs.window_runs(ctx)
        assert [read(n, ctx) for n in NEW[:3]] == [None] * 3
    finally:
        tracing.install(previous)
    assert 0 < value <= 100
    assert err.startswith("stall record: tick ") and err.count("\n") == 1
    assert "engine.decode.wait" in err and " ready " in err
    # every run the window waited for was dispatched in it, a tick earlier
    # for the decodes; the runs are numbered as the engine launched them
    waited = [x for x in runs.values() if x.wait is not None]
    assert len(waited) > 50
    ahead = [x for x in waited if x.program == "decode"
             and program_runs.tick_of(x.wait, w) is not
             program_runs.tick_of(x.dispatch, w)]
    assert len(ahead) > len(waited) // 4
    assert sorted(runs) == list(range(min(runs), max(runs) + 1))
    assert sum(t.attrs["runs"] for t in w.ticks) == len(runs)


def test_an_unpaired_trace_costs_four_metrics_never_the_result_line(
        monkeypatch, capsys):
    """A whole run past the look for a chip, traced, at toy size: the
    profiler is a stand-in whose trace holds the traced ticks' harness
    spans and ONE decode run that lies seconds before any dispatch, so no
    shift is clearly least. The result comes out with every other metric,
    the keys it always has and one ``bench_cells: unpaired:`` line."""
    import time

    from bench_cells import run as benchrun
    from simple_distributed_machine_learning_tpu.telemetry import tracing

    toy = importlib.import_module("test_bench_cells_run")
    made = {}

    class Spans(harness.Spans):
        def __init__(self):
            super().__init__()
            made["spans"] = self

    class Tracer(harness.Tracer):
        def start(self):
            if self.enabled and self.dir is None:
                self.dir, self.started_at = "nowhere", time.perf_counter()

        def stop(self):
            if self.started_at is not None and self.window_s is None:
                self.window_s = time.perf_counter() - self.started_at

        def xplane_path(self):
            made["tracer"] = self
            return "nowhere"

        def cleanup(self):
            pass

    def load(_path):
        began = made["tracer"].started_at
        steps = [xplane.Event(name, a + SKEW, b + SKEW)
                 for name, a, b in made["spans"].rows
                 if name == program_spans.ENGINE_STEP and began <= a
                 and b <= began + made["tracer"].window_s]
        lo = steps[0].start
        return xplane.Trace(
            [xplane.Device(0, [xplane.Event("fusion.1", lo + 1e-3, lo + 2e-3)],
                           [xplane.Event("jit_step_x", lo - 5.0, lo - 4.9)])],
            steps)

    monkeypatch.setattr(harness, "Spans", Spans)
    monkeypatch.setattr(harness, "Tracer", Tracer)
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: 1)
    monkeypatch.setattr(xplane, "load", load)
    cell = toy.serve_cell()
    others = ("engine.host_ms_per_tick", "engine.tick_ms_p50",
              "engine.chunk_ticks_pct")
    cell = dataclasses.replace(cell, per_layer=tuple(
        m for m in cell.per_layer if m["name"] in NEW + others))
    assert {m["name"] for m in cell.per_layer} == set(NEW + others)
    previous = tracing.install(tracing.Tracer())
    try:
        result = benchrun.run_cell(cell, 2 ** 31 + 13, 2.0, True, toy.DEVICE,
                                   toy.PEAKS)
    finally:
        tracing.install(previous)
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {NEW[3], *others}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "breakdown", "setup_split", "compared"]
    assert result["device"]["busy_s"] > 0
    json.dumps(result)
    err = capsys.readouterr().err
    said = [x for x in err.splitlines()
            if x.startswith("bench_cells: unpaired: ")]
    assert len(said) == 1, err
    assert "1 traced runs of '^jit_step'" in said[0]
    assert "before its dispatch began" in said[0]
    assert "on the device" not in stall_record(err)
