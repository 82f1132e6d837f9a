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

import dataclasses
import importlib

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
    assert "stall record: tick 3" in err and "on the device" not in err
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


# -- what cannot be paired is an error ------------------------------------------------


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
    spans[7].attrs["run"] = 4               # dispatched at 310, read at 320:
    spans[10].attrs["run"] = 3              # fine by the spans, but run 4
    with pytest.raises(SystemExit,          # still ran when its wait ended
                       match="after the wait that read it ended"):
        read("engine.readback_ms_p50", run)


@pytest.mark.parametrize("runs,match", [
    # a decode run before any dispatch began
    ([("jit_step_x", 101, 105)] + RUNS, "0 of them before the first"),
    # more runs than dispatches
    (RUNS + [("jit_step_x", 640, 645), ("jit_step_x", 646, 648)],
     "cannot be paired"),
    # three decodes asked for in the stretch and never run
    (RUNS[:2], "3 decode dispatches of the traced stretch have no run"),
    # no chunk run at all is one cut run, which the end may cut: no error
    ([r for r in RUNS if "chunk" not in r[0]], None),
])
def test_a_trace_that_cannot_be_paired_is_an_error(run, runs, match):
    run["trace"] = trace_of(runs)
    if match is None:
        assert [r.run for r in program_runs.join(run).unrun] == [2, 5]
        return
    with pytest.raises(SystemExit, match=match):
        program_runs.join(run)


def test_manifest_lists_the_four_readers_for_the_cells_that_can_take_them():
    """Appended entries of the serve engine's layer. The block-diffusion
    cell is left out: ``test_bench_cells_sdar.py`` pins that cell's set of
    per-layer metrics, and only a ``benchmark`` PR may edit it."""
    entries = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    cells = ["gpt2-large.serve-closed", "jamba2-3b.serve-reason-closed",
             "nemotron3-super-120b-a12b.serve-agent-closed"]
    for name in NEW:
        m = entries[name]
        assert m["workloads"] == cells and m["layer"] == "serve engine"
        assert m["source"] == ("program_span" if "tick_max" in name
                               else "device_trace")
    assert list(entries)[-4:] == list(NEW)
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
