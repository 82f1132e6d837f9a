"""The readers of the program's own spans (``bench_cells/program_spans.py``
and the ``engine.host_*`` family of metric files), held to hand-made spans
and a hand-made trace; then once against the real recorder on toy cells;
and the traffic files' program patterns against the program's names.
"""

import dataclasses
import glob
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_cells import harness, manifest, program_spans
from bench_cells.reduce import xplane

MS = 1_000_000          # nanoseconds
T0 = 100.0              # the window's start on the perf_counter clock, s


@dataclasses.dataclass
class Span:
    name: str
    start_ns: int
    end_ns: int
    id: int
    parent: int | None = None
    attrs: dict = dataclasses.field(default_factory=dict)


class Recorder:
    def __init__(self, spans, evicted_until_ns=0, dropped=0):
        self._spans = spans
        self.evicted_until_ns = evicted_until_ns
        self.dropped = dropped

    def spans(self):
        return list(self._spans)


def at(ms: float) -> int:
    """``ms`` milliseconds after the window's start, in nanoseconds."""
    return int(T0 * 1e9) + int(round(ms * MS))


def hand_made():
    """Three busy ticks and an idle call, as the harness stamps them and as
    the program records them; all times in ms after the window's start.

    tick 1 (110-190, a first chunk of request 7, whose dispatch compiles):
    admit 2, prefill.prepare 3, prefill.dispatch 10 of which jax.compile 6,
    prefill.wait 20, prefill.emit 1, decode.prepare 2, decode.dispatch 2,
    decode.wait 35, decode.emit 4: 79 of the tick's 80.
    tick 2 (310-440, request 7's second chunk): admit 1, prefill.prepare
    0.5, decode.prepare 3.5, decode.dispatch 5, decode.wait 100 of which
    py.gc 10, decode.emit 20.
    tick 3 (610-690, decode only): decode.prepare 2, decode.dispatch 3,
    decode.wait 70, decode.emit 5.
    """
    S = Span
    spans = [
        # before the window, and one that straddles its end: both cut
        S("engine.tick", at(-500), at(-400), 900, None,
          {"tick": 1, "chunk": 1, "decoding": 0, "emitted": 0, "queue": 0}),
        S("engine.submit", at(50), at(51), 1, None, {"rid": 7}),
        S("engine.submit", at(240), at(241), 2, None, {"rid": 9}),
        S("engine.admit", at(110), at(112), 11, 10, {"boarded": 1}),
        S("engine.prefill.prepare", at(112), at(115), 12, 10,
          {"rid": 7, "p0": 0, "n": 4}),
        S("jax.compile", at(117), at(123), 131, 13),
        S("engine.prefill.dispatch", at(115), at(125), 13, 10, {"rid": 7}),
        S("engine.prefill.wait", at(125), at(145), 14, 10, {"rid": 7}),
        S("engine.prefill.emit", at(145), at(146), 15, 10, {"rid": 7}),
        S("engine.decode.prepare", at(146), at(148), 16, 10),
        S("engine.decode.dispatch", at(148), at(150), 17, 10),
        S("engine.decode.wait", at(150), at(185), 18, 10),
        S("engine.decode.emit", at(185), at(189), 19, 10),
        S("engine.tick", at(110), at(190), 10, None,
          {"tick": 2, "chunk": 1, "decoding": 2, "emitted": 3, "queue": 1}),
        S("engine.admit", at(310), at(311), 21, 20, {"boarded": 0}),
        S("engine.prefill.prepare", at(311), at(311.5), 22, 20,
          {"rid": 7, "p0": 4, "n": 4}),
        S("engine.decode.prepare", at(311.5), at(315), 23, 20),
        S("engine.decode.dispatch", at(315), at(320), 24, 20),
        S("py.gc", at(350), at(360), 251, 25, {"generation": 1}),
        S("engine.decode.wait", at(320), at(420), 25, 20),
        S("engine.decode.emit", at(420), at(440), 26, 20),
        S("engine.tick", at(310), at(440), 20, None,
          {"tick": 3, "chunk": 1, "decoding": 2, "emitted": 2, "queue": 0}),
        S("engine.decode.prepare", at(610), at(612), 31, 30),
        S("engine.decode.dispatch", at(612), at(615), 32, 30),
        S("engine.decode.wait", at(615), at(685), 33, 30),
        S("engine.decode.emit", at(685), at(690), 34, 30),
        S("engine.tick", at(610), at(690), 30, None,
          {"tick": 4, "chunk": 0, "decoding": 3, "emitted": 3, "queue": 0}),
        S("engine.submit", at(990), at(1010), 3, None, {"rid": 11}),
    ]
    records = {
        "kind": "serve", "t0": T0, "window_s": 1.0,
        "ticks": [(T0 + 0.100, T0 + 0.200, 3), (T0 + 0.300, T0 + 0.450, 2),
                  (T0 + 0.500, T0 + 0.50001, 0), (T0 + 0.600, T0 + 0.700, 3)],
        "traced_ticks": [0, 1],
    }
    return records, spans


@pytest.fixture
def run(monkeypatch):
    records, spans = hand_made()
    recorder = Recorder(spans)
    monkeypatch.setattr(program_spans, "recorder", lambda: recorder)
    return {"records": records, "trace": None, "recorder": recorder}


def read(name, run):
    return manifest.load_reader(name)(run)


# -- the arithmetic -------------------------------------------------------------


def test_window_cut_and_ticks(run):
    w = program_spans.Window(run["records"], run["recorder"])
    assert [s.id for s in w.spans if s.name == "engine.submit"] == [1, 2]
    assert [t.id for t in w.ticks] == [10, 20, 30]      # not the early one
    matched = program_spans.window_ticks(run["records"], w.spans)
    assert [t and t.id for t in matched] == [10, 20, None, 30]


def test_self_time_is_a_span_less_its_childrens_union(run):
    w = program_spans.Window(run["records"], run["recorder"])
    tick1, tick2, tick3 = w.ticks
    assert program_spans.self_seconds(tick1, w.kids) == pytest.approx(1e-3)
    assert program_spans.self_seconds(tick2, w.kids) == pytest.approx(0.0)
    dispatch = next(s for s in w.spans if s.id == 13)
    assert program_spans.self_seconds(dispatch, w.kids) == pytest.approx(4e-3)
    # overlapping children count once
    kids = {1: [Span("a", 0, 6 * MS, 2, 1), Span("b", 4 * MS, 8 * MS, 3, 1)]}
    assert program_spans.self_seconds(Span("p", 0, 10 * MS, 1), kids) \
        == pytest.approx(2e-3)
    assert w.uncovered_share() == pytest.approx(1 / (80 + 130 + 80))
    assert [s.id for s in program_spans.descendants(tick1, w.kids)][:4] \
        == [11, 12, 13, 131]


@pytest.mark.parametrize("name,value", [
    ("engine.host_ms_per_tick", 25.0),          # median of 25, 30, 10
    ("engine.host_admit_ms", 1.0),              # (2 + 1 + 0) / 3
    ("engine.host_prepare_ms", 11.0 / 3),       # 3+2, 0.5+3.5, 2
    ("engine.host_dispatch_ms", 14.0 / 3),      # 10-6+2, 5, 3
    ("engine.host_emit_ms", 10.0),              # 1+4, 20, 5
    ("engine.ttft_queue_ms_p50", 62.0),         # rid 7: 112 - 50; 9 not yet
    ("engine.chunk_ticks_pct", 200.0 / 3),
    ("engine.tick_ms_max", 130.0),
])
def test_each_metric_on_the_hand_made_spans(run, name, value, capsys):
    assert read(name, run) == pytest.approx(value)
    err = capsys.readouterr().err
    if name == "engine.tick_ms_max":
        assert "py.gc 10.00" in err and "'tick': 3" in err
    if name == "engine.host_ms_per_tick":
        assert "3 ticks, 0.34 % of tick time in no child span" in err


def test_no_recorder_no_reading(run, monkeypatch):
    """A program older than the recorder: every reader reports nothing and
    none raises."""
    monkeypatch.setattr(program_spans, "recorder", lambda: None)
    bench = manifest.load_manifest()
    new = [m["name"] for m in bench["per_layer"]
           if m["name"].startswith("engine.host_")
           or m["name"] in ("engine.ttft_queue_ms_p50", "pipeline.init_s",
                            "engine.chunk_ticks_pct", "engine.tick_ms_max",
                            "engine.idle_explained_pct")]
    assert len(new) == 10
    for kind in ("serve", "train"):
        ctx = dict(run, records=dict(run["records"], kind=kind),
                   trace=object())
        assert [read(n, ctx) for n in new] == [None] * 10


def test_recorder_lookup_survives_a_program_without_one(monkeypatch):
    from simple_distributed_machine_learning_tpu.telemetry import tracing

    assert program_spans.recorder() is tracing.current()
    monkeypatch.delattr(tracing, "current")
    assert program_spans.recorder() is None


# -- partial readings are errors ------------------------------------------------


def test_tick_that_emitted_and_left_no_span_is_an_error(run):
    run["records"]["ticks"].append((T0 + 0.80, T0 + 0.85, 1))
    with pytest.raises(SystemExit, match=r"tick 4 \(emitted 1\) holds 0"):
        read("engine.host_ms_per_tick", run)


def test_two_spans_inside_one_harness_tick_is_an_error(run):
    run["records"]["ticks"][0] = (T0 + 0.100, T0 + 0.450, 5)
    with pytest.raises(SystemExit, match="holds 2 engine.tick spans"):
        read("engine.chunk_ticks_pct", run)


def test_evicted_window_spans_are_an_error(run):
    run["recorder"].evicted_until_ns = at(-1)       # before the window: fine
    run["recorder"].dropped = 5
    assert read("engine.tick_ms_max", run) == pytest.approx(130.0)
    run["recorder"].evicted_until_ns = at(200)
    with pytest.raises(SystemExit, match="evicted spans of the window"):
        read("engine.tick_ms_max", run)


def test_window_without_a_tick_span_is_an_error(run):
    run["recorder"]._spans = [s for s in run["recorder"]._spans
                              if s.name != "engine.tick"]
    run["records"]["ticks"] = [(ts, te, 0)
                               for ts, te, _ in run["records"]["ticks"]]
    with pytest.raises(SystemExit, match="no engine.tick span"):
        read("engine.host_admit_ms", run)


# -- the join with the trace ------------------------------------------------------

SKEW = 1000.0           # the trace's clock less perf_counter, s


def trace_of(busy_ms, steps_ms):
    ev = xplane.Event
    ops = [ev(f"fusion.{i}", T0 + SKEW + a / 1e3, T0 + SKEW + b / 1e3)
           for i, (a, b) in enumerate(busy_ms)]
    spans = [ev("bench.serve.engine_step", T0 + SKEW + a / 1e3,
                T0 + SKEW + b / 1e3) for a, b in steps_ms]
    spans.append(ev("bench.serve.clients", T0 + SKEW, T0 + SKEW + 1e-3))
    return xplane.Trace([xplane.Device(0, ops, [])], spans)


def test_align_is_the_median_start_offset_over_the_traced_ticks(run):
    r = dict(run["records"], traced_ticks=[0, 4])
    trace = trace_of([(1, 2)], [(100.01, 200), (300.01, 450),
                                (500.03, 500.04), (600.02, 700)])
    assert program_spans.align(r, trace) == pytest.approx(SKEW + 15e-6)
    for bad in ([0, 3], [1, 4], [0, None]):
        with pytest.raises(SystemExit, match="engine_step"):
            program_spans.align(dict(r, traced_ticks=bad), trace)


def test_idle_inside_the_traced_tick_goes_to_the_innermost_span(run,
                                                                capsys):
    """Tick 1, the device busy 126-144 and 151-184: idle 100-126 (10 before
    the tick span, admit 2, prepare 3, the dispatch's own 4, its compile 6,
    the wait 1), 144-151 (wait 1, emit 1, prepare 2, dispatch 2, wait 1)
    and 184-200 (wait 1, emit 4, 1 of the tick alone, 10 after it)."""
    run["trace"] = trace_of([(126, 144), (151, 184), (250, 260)],
                            [(100, 200)])
    idle = program_spans.idle_by_span(run["records"], run["trace"],
                                      run["recorder"])
    want = {"(no span)": 21, "engine.admit": 2, "engine.prefill.prepare": 3,
            "engine.prefill.dispatch": 4, "jax.compile": 6,
            "engine.prefill.wait": 2, "engine.prefill.emit": 1,
            "engine.decode.prepare": 2, "engine.decode.dispatch": 2,
            "engine.decode.wait": 2, "engine.decode.emit": 4}
    assert {k: round(v * 1e3, 6) for k, v in idle.items()} == want
    assert read("engine.idle_explained_pct", run) == pytest.approx(
        100.0 * 28 / 49)
    err = capsys.readouterr().err
    assert "the 1 traced ticks: 0.0490 s" in err and "(no span) 0.0210" in err
    # an untraced run has no device idle time to explain
    assert read("engine.idle_explained_pct", dict(run, trace=None)) is None


def test_innermost_cuts_a_span_that_reaches_back():
    pieces = program_spans._innermost(
        [("a", 0.0, 4.0), ("b", 3.0, 6.0), ("c", 6.5, 7.0)], 0.0)
    assert pieces == [("a", 0.0, 3.0), ("b", 3.0, 4.0), ("c", 6.5, 7.0)]


# -- against the real recorder, at toy size ---------------------------------------


def _toy():
    return importlib.import_module("test_bench_cells_run")


def _ctx(cell, run, toy):
    return {"records": run.records, "setup": {}, "trace": None,
            "peaks": toy.PEAKS, "chips": 1, "gpt": toy.GPT,
            "mix": cell.traffic}


def test_toy_serve_cell_reads_every_span_metric():
    from simple_distributed_machine_learning_tpu.telemetry import tracing

    toy = _toy()
    previous = tracing.install(tracing.Tracer())
    try:
        cell = toy.serve_cell()
        runner = importlib.import_module("bench_cells.runners.serve")
        run = runner.Run(cell, 2 ** 31 + 7, harness.Spans())
        run.setup()
        run.window(1.5, toy._NoTrace())
        ctx = _ctx(cell, run, toy)
        got = {m["name"]: read(m["name"], ctx) for m in cell.per_layer
               if m["source"] in ("program_span", "program_counter")}
    finally:
        tracing.install(previous)
    assert got["engine.tick_ms_p50"] > got["engine.host_ms_per_tick"] > 0
    parts = sum(got[k] for k in ("engine.host_admit_ms",
                                 "engine.host_prepare_ms",
                                 "engine.host_dispatch_ms",
                                 "engine.host_emit_ms"))
    # the host's part of a tick is its phases (means beside a median)
    assert 0.5 * got["engine.host_ms_per_tick"] < parts \
        < 2 * got["engine.host_ms_per_tick"]
    assert 0 < got["engine.chunk_ticks_pct"] <= 100
    assert got["engine.tick_ms_max"] >= got["engine.tick_ms_p50"]
    ttft = read("engine.ttft_p50_ms", ctx)
    assert 0 < got["engine.ttft_queue_ms_p50"] < ttft
    # the harness's own count of the same thing, from outside
    ticks = [te - ts for ts, te, _ in run.records["ticks"]]
    assert got["engine.tick_ms_max"] <= 1e3 * max(ticks)


def test_toy_train_cell_reads_pipeline_init(capsys):
    from simple_distributed_machine_learning_tpu.telemetry import tracing

    toy = _toy()
    previous = tracing.install(tracing.Tracer())
    try:
        cell = toy.train_cell()
        runner = importlib.import_module("bench_cells.runners.train")
        run = runner.Run(cell, 5, harness.Spans())
        split = run.setup()
        run.records = {"kind": "train"}
        value = read("pipeline.init_s", _ctx(cell, run, toy))
        assert 0 < value < split["pipeline_pack_s"]
        err = capsys.readouterr().err
        assert "pipeline.pack" in err and "pipeline.to_host" in err
        assert "'bytes':" in err
        # a recorder that lost the span is an error, not a zero
        tracing.install(tracing.Tracer())
        with pytest.raises(SystemExit, match="0 pipeline.init spans"):
            read("pipeline.init_s", _ctx(cell, run, toy))
    finally:
        tracing.install(previous)


# -- the names the traffic files find the programs by ----------------------------


def _module_name(jitted, *args) -> str:
    text = jitted.lower(*args).as_text()
    return re.search(r"module @(\S+)", text).group(1)


@pytest.fixture(scope="module")
def program_names():
    """What the trace calls each program a traffic file looks for: the
    module name of its lowering (CPU, toy size)."""
    from simple_distributed_machine_learning_tpu.models.gpt import (
        SEAT_SAMPLE,
        GPTConfig,
        make_gpt_stages,
    )
    from simple_distributed_machine_learning_tpu.parallel.mesh import (
        make_mesh,
    )
    from simple_distributed_machine_learning_tpu.parallel.pipeline import (
        Pipeline,
    )
    from simple_distributed_machine_learning_tpu.serve import InferenceEngine
    from simple_distributed_machine_learning_tpu.train.optimizer import adamw
    from simple_distributed_machine_learning_tpu.train.step import (
        make_train_step,
    )

    cfg = GPTConfig(vocab=32, seq_len=16, d_model=32, n_heads=2, n_layers=2)
    stages, wire_dim, out_shape = make_gpt_stages(jax.random.key(0), cfg, 1)
    eng = InferenceEngine(stages, cfg, n_slots=2, block_size=4,
                          prefill_chunk=4, attn_kernel="fused")
    # the signature since PR 31: the state (every slot's newest token and
    # key) after the pool; the decode told which slots are live, the chunk
    # its slot and what to seat
    S, nb, pool = 2, eng.pool.blocks_per_seq, eng.pool
    decode = _module_name(
        eng._decode, eng.params, pool.kc, pool.vc, pool.state,
        np.zeros(S, np.int32), np.zeros((S, nb), np.int32),
        np.zeros(S, bool), np.zeros(S, np.float32),
        np.zeros(S, np.int32), np.full(S, 2.0, np.float32))
    chunk = _module_name(
        eng._chunk_prefill, eng.params, pool.kc, pool.vc, pool.state,
        np.zeros((1, 4), np.int32), np.int32(0), pool.device_table(0),
        np.int32(0), np.int32(SEAT_SAMPLE), np.zeros(2, np.uint32),
        np.float32(0), np.int32(0), np.float32(2))
    pipe = Pipeline(stages, make_mesh(n_stages=1, n_data=1), wire_dim,
                    out_shape)
    opt = adamw(1e-3)
    buf = pipe.init_params()
    train = _module_name(
        make_train_step(pipe, opt), buf, opt.init(buf),
        jnp.zeros((2, 16), jnp.float32), jnp.zeros((2, 16), jnp.int32),
        jax.random.key(0))
    return {"decode_tick": decode, "prefill_chunk": chunk,
            "train_step": train}


def test_every_program_pattern_still_finds_its_program(program_names):
    assert program_names == {"decode_tick": "jit_step_paged_decode",
                             "prefill_chunk": "jit_chunk_paged_prefill",
                             "train_step": "jit_step_train"}
    seen = set()
    for path in sorted(glob.glob(os.path.join(manifest.HERE, "traffic",
                                              "*.json"))):
        with open(path, encoding="utf-8") as f:
            mix = json.load(f)
        for key, pattern in mix["programs"].items():
            mine = program_names[key]
            assert re.search(pattern, mine), (path, key, pattern)
            # and no other program of the same cell: the decode tick's
            # pattern must not take the prefill chunk's runs for its own
            others = [v for k, v in program_names.items()
                      if k != key and k in mix["programs"]]
            assert not any(re.search(pattern, o) for o in others), (
                path, key, pattern)
            seen.add(key)
    assert seen == set(program_names)
