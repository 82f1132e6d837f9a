"""The span recorder (``telemetry/tracing.py``) and the spans the serve
engine writes into it: parents, the ring, the disabled path, the stalls
nobody called for, the clock, what the scheduler did to a span's thread
(``sched=True``); then, on a toy paged engine, one ``engine.tick`` per busy
``step()`` whose children cover it and whose attributes say what the tick
did, and every program run numbered from its dispatch to its wait; and a
virtual-clock scenario whose report does not depend on the recorder.
"""

import collections
import dataclasses
import gc
import json
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simple_distributed_machine_learning_tpu.models.gpt import (
    GPTConfig,
    make_gpt_stages,
)
from simple_distributed_machine_learning_tpu.resilience import faults
from simple_distributed_machine_learning_tpu.resilience.scenarios import (
    run_scenario,
)
from simple_distributed_machine_learning_tpu.serve import InferenceEngine
from simple_distributed_machine_learning_tpu.telemetry import (
    Telemetry,
    tracing,
)


@pytest.fixture
def tracer():
    """A recorder of the test's own, the process's while the test runs."""
    mine = tracing.Tracer()
    previous = tracing.install(mine)
    yield mine
    tracing.install(previous)


def _by_name(tracer):
    out = {}
    for s in tracer.spans():
        out.setdefault(s.name, []).append(s)
    return out


# -- the recorder -------------------------------------------------------------


def test_parent_is_the_span_open_on_the_same_thread(tracer):
    with tracing.span("outer", k=1) as outer:
        with tracing.span("inner") as inner:
            with tracing.span("leaf") as leaf:
                pass
        with tracing.span("second") as second:
            pass
        outer.set(done=True)
    assert tracing.current() is tracer
    assert outer.parent is None
    assert inner.parent == outer.id and second.parent == outer.id
    assert leaf.parent == inner.id
    assert len({outer.id, inner.id, leaf.id, second.id}) == 4
    assert outer.attrs == {"k": 1, "done": True}
    # in the order they closed, each inside its parent
    assert [s.name for s in tracer.spans()] == ["leaf", "inner", "second",
                                                "outer"]
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns


def test_parents_do_not_cross_threads(tracer):
    go, held = threading.Event(), threading.Event()
    seen = {}

    def other():
        held.wait(timeout=10)
        with tracing.span("other.outer") as a:
            with tracing.span("other.inner") as b:
                seen.update(a=a, b=b)
        go.set()

    th = threading.Thread(target=other)
    th.start()
    with tracing.span("main.outer") as mine:
        held.set()                    # the other thread's spans open while
        assert go.wait(timeout=10)    # this one is
    th.join(timeout=10)
    assert not th.is_alive()
    assert seen["a"].parent is None and seen["b"].parent == seen["a"].id
    assert seen["a"].tid != mine.tid
    assert mine.start_ns < seen["a"].start_ns < mine.end_ns


def test_ring_is_bounded_and_counts_what_it_evicted():
    tr = tracing.Tracer(capacity=8)
    assert tr.dropped == 0 and tr.evicted_until_ns == 0
    for i in range(20):
        with tr.span("s", i=i):
            pass
    kept = tr.spans()
    assert [s.attrs["i"] for s in kept] == list(range(12, 20))
    assert tr.dropped == 12
    # everything evicted ended before anything kept did
    assert 0 < tr.evicted_until_ns <= kept[0].end_ns
    assert len(tr.to_chrome_trace()["traceEvents"]) == 1 + 8


def test_disabled_recorder_hands_out_one_no_op(tracer):
    tracer.enabled = False
    a = tracing.span("a", rid=1)
    b = tracer.span("b")
    assert a is b is tracing.NO_SPAN
    with a as sp:
        sp.set(x=1)                       # accepted, kept nowhere
        jax.jit(_long_chain)(jnp.ones(3)).block_until_ready()
        del _junk()[:]
        gc.collect()
    assert tracer.spans() == [] and tracer.dropped == 0
    tracer.enabled = True
    with tracing.span("c"):
        pass
    assert [s.name for s in tracer.spans()] == ["c"]


def _long_chain(x):
    for _ in range(200):                  # long enough to trace in > 1 ms
        x = jnp.sin(x) * 2 + 1
    return x


def _junk(n=300_000):
    """Cycles for the collector to find, so that a full collection takes
    more than the floor under which it is no stall."""
    out = []
    for _ in range(n):
        a = []
        a.append(a)
        out.append(a)
    return out


def test_compiles_and_collections_are_spans_under_the_open_one(tracer):
    with tracing.span("work") as work:
        t0 = time.perf_counter_ns()
        jax.jit(_long_chain)(jnp.ones(5)).block_until_ready()
        del _junk()[:]
        gc.collect()
        t1 = time.perf_counter_ns()
    by = _by_name(tracer)
    for name in ("jax.trace", "jax.lower", "jax.compile", "py.gc"):
        assert by.get(name), (name, sorted(by))
        assert all(s.parent == work.id for s in by[name])
    full = [s for s in by["py.gc"] if s.attrs["generation"] == 2]
    assert full and all(t0 <= s.start_ns <= s.end_ns <= t1 for s in full)
    # a duration event ends when it is heard: inside the open span
    assert all(work.start_ns <= s.end_ns <= work.end_ns
               and s.end_ns > s.start_ns for s in by["jax.compile"])
    # with no span open they hang from nothing
    del _junk()[:]
    gc.collect()
    assert _by_name(tracer)["py.gc"][-1].parent is None


def test_an_event_under_the_floor_is_no_stall(tracer):
    """Tracing a deep model fires a trace event for every inner jitted
    helper, microseconds each: they would flush the ring."""
    event = "/jax/core/compile/jaxpr_trace_duration"
    tracing._on_jax_duration(event, 20e-6)
    tracing._on_jax_duration("/jax/some/other/duration", 5.0)
    assert tracer.spans() == []
    tracing._on_jax_duration(event, 2.5e-3)
    (s,) = tracer.spans()
    assert s.name == "jax.trace"
    assert s.end_ns - s.start_ns == 2_500_000
    with tracing.span("quiet"):
        for _ in range(3):
            gc.collect(0)                 # a young collection: microseconds
    assert [s.name for s in tracer.spans()] == ["jax.trace", "quiet"]


def test_stamps_are_absolute_perf_counter_readings(tracer):
    a = time.perf_counter()
    with tracing.span("timed"):
        time.sleep(0.01)
    b = time.perf_counter()
    (s,) = tracer.spans()
    assert a <= s.start_ns * 1e-9 <= s.end_ns * 1e-9 <= b
    assert 0.01 <= s.seconds <= b - a
    # the Chrome export stays relative: microseconds since the tracer was made
    ev = tracer.to_chrome_trace()["traceEvents"][1]
    assert ev["name"] == "timed" and 0 <= ev["ts"] < 60e6
    assert ev["dur"] == pytest.approx(s.seconds * 1e6)


# -- what the scheduler did to the thread -------------------------------------

SCHED = {"cpu_ns", "nvcsw", "nivcsw", "runq_ns"}


def test_sched_span_holds_the_four_readings_and_a_plain_span_none(tracer):
    with tracing.span("with", sched=True, k=1) as sp:
        sp.set(late=2)
    with tracing.span("without", k=1):
        pass
    with_, without = tracer.spans()
    assert set(with_.attrs) == SCHED | {"k", "late"}
    assert all(isinstance(with_.attrs[k], int) and with_.attrs[k] >= 0
               for k in SCHED)
    assert set(without.attrs) == {"k"}
    # an operator reads them in trace.json as the event's args
    ev, = (e for e in tracer.to_chrome_trace()["traceEvents"]
           if e["name"] == "with")
    assert SCHED <= set(ev["args"])


def _spin(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


@pytest.mark.parametrize("what", ["busy", "asleep"])
def test_cpu_ns_is_a_busy_loops_length_and_nothing_of_a_sleep(tracer, what):
    with tracing.span(what, sched=True) as sp:
        (_spin if what == "busy" else time.sleep)(0.05)
    length = sp.end_ns - sp.start_ns
    a = sp.attrs
    # read just outside the span's stamps: over its length by a reading's
    # own time at most
    assert length >= 50_000_000 and 0 <= a["cpu_ns"] <= length + 1_000_000
    if what == "busy":
        # what a loaded machine took from the loop it says too: under six
        # test workers the loop has had a fifth of its 50 ms on a CPU
        assert a["cpu_ns"] + a.get("runq_ns", 0) >= 0.8 * length, a
        assert a["cpu_ns"] > 0, a
    else:
        assert a["cpu_ns"] <= 0.1 * length, a
        assert a["nvcsw"] >= 1, a


def test_where_the_readings_lie_against_the_spans_own_stamps(tracer,
                                                            monkeypatch):
    """What a reading costs has to be some span's time. A span with a
    parent reads inside its own stamps (the parent's children still cover
    the parent: the engine's waits under ``engine.tick``); one without
    reads outside them (``engine.tick`` itself: its time is nobody
    else's)."""
    real, taken = tracing._sched_now, []

    def slow():
        taken.append(time.perf_counter_ns())
        time.sleep(0.005)
        return real()

    monkeypatch.setattr(tracing, "_sched_now", slow)
    with tracing.span("root", sched=True) as root:
        with tracing.span("child", sched=True) as child:
            pass
    assert len(taken) == 4
    assert taken[0] < root.start_ns - 5_000_000
    assert root.start_ns <= child.start_ns <= taken[1] < taken[2]
    assert taken[2] < child.end_ns - 5_000_000
    assert child.end_ns <= root.end_ns <= taken[3]
    covered = (child.end_ns - child.start_ns) / (root.end_ns - root.start_ns)
    assert covered > 0.95, covered


def test_nvcsw_grows_with_every_sleep(tracer):
    with tracing.span("three", sched=True) as three:
        for _ in range(3):
            time.sleep(0.002)
    with tracing.span("none", sched=True) as none:
        pass
    assert three.attrs["nvcsw"] >= 3 > none.attrs["nvcsw"]


def test_runq_ns_is_absent_where_the_kernel_gives_no_such_file(
        tracer, monkeypatch):
    """Another kernel, a container without ``/proc``: the other three
    readings stand, nothing is raised, and the open is tried once a
    thread."""
    monkeypatch.setattr(tracing, "SCHEDSTAT", "/nonexistent/schedstat")
    opened = []
    real_open = open

    def counted(path, *a, **k):
        opened.append(path)
        return real_open(path, *a, **k)

    monkeypatch.setattr("builtins.open", counted)

    def work():                   # a thread that has opened nothing yet
        for _ in range(3):
            with tracing.span("s", sched=True):
                pass

    t = threading.Thread(target=work)
    t.start()
    t.join(10)
    assert not t.is_alive()
    spans = tracer.spans()
    assert len(spans) == 3
    assert all(set(s.attrs) == SCHED - {"runq_ns"} for s in spans)
    assert opened == ["/nonexistent/schedstat"]


class _Counted:
    """A module whose every attribute read is counted."""

    def __init__(self, module, reads):
        self._module, self._reads = module, reads

    def __getattr__(self, name):
        self._reads[f"{self._module.__name__}.{name}"] += 1
        return getattr(self._module, name)


@pytest.fixture
def reads(monkeypatch):
    """What the recorder reads of ``time``, ``resource`` and ``os``."""
    reads = collections.Counter()
    for name in ("time", "resource", "os"):
        monkeypatch.setattr(tracing, name,
                            _Counted(getattr(tracing, name), reads))
    return reads


def test_switch_counts_are_left_out_where_a_sleep_does_not_move_them(
        tracer, monkeypatch, reads):
    """A sandboxed kernel (the chip's host) answers ``getrusage`` and counts
    no switch: asked once a process across one sleep, then never again."""
    dead = types.SimpleNamespace(ru_nvcsw=0, ru_nivcsw=0)
    monkeypatch.setattr(tracing, "_rusage_counts", None)
    monkeypatch.setattr(tracing.resource._module, "getrusage",
                        lambda _who: dead)
    for _ in range(3):
        with tracing.span("s", sched=True):
            time.sleep(0.001)
    assert reads["resource.getrusage"] == 2      # the one probe
    assert tracing._rusage_counts is False
    for s in tracer.spans():
        assert not {"nvcsw", "nivcsw"} & set(s.attrs)
        assert "cpu_ns" in s.attrs


def test_disabled_recorder_reads_no_clock_no_rusage_and_no_file(tracer,
                                                                reads):
    assert tracing._rusage_live()                # asked once a process
    reads.clear()
    with tracing.span("on", sched=True):
        pass
    assert reads["time.perf_counter_ns"] == 2
    assert reads["time.thread_time_ns"] == 2
    assert reads["resource.getrusage"] == 2 and reads["os.pread"] == 2
    tracer.enabled = False
    reads.clear()
    with tracing.span("off", sched=True, k=1) as sp:
        sp.set(late=2)
    assert sp is tracing.NO_SPAN and not reads
    assert [s.name for s in tracer.spans()] == ["on"]


def test_telemetry_installs_its_tracer_as_the_process_recorder(tmp_path):
    previous = tracing.current()
    try:
        tele = Telemetry(str(tmp_path))
        assert tracing.current() is tele.tracer
        with tracing.span("engine.tick", tick=1):
            pass
        tele.flush()
        doc = json.load(open(tmp_path / "trace.json"))
        tick = [e for e in doc["traceEvents"] if e["name"] == "engine.tick"]
        assert tick and tick[0]["args"] == {"tick": 1}
    finally:
        tracing.install(previous)


# -- the serve engine's spans ---------------------------------------------------

# wide enough that a tick takes milliseconds on the CPU: the children's
# share of a tick is then a statement about the spans, not about the few
# microseconds each of them costs
CFG = GPTConfig(vocab=256, seq_len=64, d_model=128, n_heads=4, n_layers=4)


@pytest.fixture(scope="module")
def stages():
    return make_gpt_stages(jax.random.key(0), CFG, 1)[0]


def _prompt(n, seed):
    return np.asarray(
        jax.random.randint(jax.random.key(seed), (n,), 0, CFG.vocab),
        np.int32)


def _engine(stages, **kw):
    return InferenceEngine(stages, CFG, n_slots=3, block_size=4,
                           prefill_chunk=4, **kw)


PHASES = {"engine.admit", "engine.prefill.prepare", "engine.prefill.dispatch",
          "engine.prefill.wait", "engine.prefill.emit",
          "engine.decode.prepare", "engine.decode.dispatch",
          "engine.decode.wait", "engine.decode.emit"}


def test_one_tick_span_per_busy_step_and_none_for_an_idle_one(stages,
                                                               tracer):
    eng = _engine(stages)
    assert eng.step() == 0                       # idle: no span
    assert "engine.tick" not in _by_name(tracer)
    # what each step() did, witnessed from outside the spans: the chunk
    # program's calls, and every token with its place in its request (a
    # request's first token comes from its prefill, the others from the
    # batched decode, one a decoding slot)
    did, tokens, chunks = [], [], []
    run_chunk = eng._chunk_prefill

    def counted(*args):
        chunks.append(len(did))
        return run_chunk(*args)

    eng._chunk_prefill = counted
    for i in range(4):
        eng.submit(_prompt(6 + 3 * i, i), 5,
                   on_token=lambda r, _t: tokens.append(
                       (len(did), len(r.tokens))))
    while eng.busy:
        did.append(eng.step())
    assert eng.step() == 0
    by = _by_name(tracer)
    ticks = by["engine.tick"]
    assert len(ticks) == len(did)
    assert [t.attrs["tick"] for t in ticks] == list(range(1, len(did) + 1))
    assert [s.attrs["rid"] for s in by["engine.submit"]] == [0, 1, 2, 3]
    kids = {}
    for s in tracer.spans():
        kids.setdefault(s.parent, []).append(s)
    for n, t in enumerate(ticks):
        assert t.attrs["emitted"] == did[n]
        assert t.attrs["emitted"] == sum(1 for k, _ in tokens if k == n)
        assert t.attrs["decoding"] == sum(1 for k, place in tokens
                                          if k == n and place > 1)
        assert t.attrs["chunk"] == chunks.count(n) <= 1
        assert t.attrs["queue"] >= 0
        assert {c.name for c in kids[t.id]
                if c.name.startswith("engine.")} <= PHASES
    assert 0 < sum(t.attrs["chunk"] for t in ticks) < len(ticks)
    # every prefill span names its request
    for name in PHASES:
        if name.startswith("engine.prefill."):
            assert all("rid" in s.attrs for s in by[name]), name
            assert len(by[name]) == len(chunks)
    (p,) = [s for s in by["engine.prefill.prepare"]
            if s.attrs["rid"] == 0 and s.attrs["p0"] == 0]
    assert p.attrs["n"] == 4
    assert sum(s.attrs["boarded"] for s in by["engine.admit"]) == 4


@pytest.mark.parametrize("temps", [(0.0, 0.0, 0.0, 0.0),
                                   (0.0, 0.9, 0.0, 1.2),
                                   (0.7, 0.9, 1.1, 0.5)],
                         ids=["none", "some", "all"])
@pytest.mark.parametrize("tick", ["ahead", "speculative"])
def test_tick_counts_the_decoding_slots_that_sample(stages, tracer, tick,
                                                    temps):
    """``sampling``: of the slots of the tick's decode, those whose
    temperature is above 0 (where it is 0 the programs' sampler sorted
    nothing, ``models/serving.py::sample_slots``); witnessed from outside by
    which requests got a token past their first in the tick."""
    kw = {}
    if tick == "speculative":
        draft_cfg = dataclasses.replace(CFG, n_layers=1)
        kw = dict(draft_cfg=draft_cfg, spec_k=3, draft_stages=make_gpt_stages(
            jax.random.key(9), draft_cfg, 1)[0])
    eng = _engine(stages, **kw)
    assert eng._dispatch_ahead == (tick == "ahead")
    did, decoded = [], []
    for i, t in enumerate(temps):
        eng.submit(_prompt(6 + 3 * i, i), 5, temperature=t, seed=i,
                   on_token=lambda r, _t: len(r.tokens) > 1 and decoded.append(
                       (len(did), r.rid, r.temperature > 0)))
    while eng.busy:
        did.append(eng.step())
    ticks = _by_name(tracer)["engine.tick"]
    assert len(ticks) == len(did)
    for n, t in enumerate(ticks):
        slots = {(rid, hot) for k, rid, hot in decoded if k == n}
        assert t.attrs["decoding"] == len(slots)
        assert t.attrs["sampling"] == sum(hot for _, hot in slots)
    seen = {t.attrs["sampling"] for t in ticks if t.attrs["decoding"]}
    hot = sum(t > 0 for t in temps)
    assert {0: seen == {0}, 2: 1 in seen and max(seen) <= 2,
            4: 0 not in seen}[hot]
    assert all(t.attrs["sampling"] == 0 for t in ticks
               if not t.attrs["decoding"])


def test_children_cover_the_tick(stages, tracer):
    """At least 95 % of a tick lies inside its children. A client's callback
    that takes 2 ms (it runs inside the ``*.emit`` spans) makes a CPU tick
    of this toy several milliseconds, so that the few microseconds between
    two spans, which is all that may lie outside them, do not decide. What
    a loaded test machine took from the thread there is not the program's:
    a tick's uncovered time is forgiven up to what the tick says it stood
    runnable and without a CPU (``runq_ns``, where the kernel gives it)."""
    eng = _engine(stages)

    def serve():
        for i in range(4):
            eng.submit(_prompt(6 + 3 * i, i), 5,
                       on_token=lambda _r, _t: time.sleep(0.002))
        eng.drain()

    serve()                                      # warm every shape
    fresh = tracing.Tracer()
    tracing.install(fresh)                       # the fixture restores
    serve()
    spans = fresh.spans()
    ticks = [s for s in spans if s.name == "engine.tick"]
    assert len(ticks) > 5
    whole = sum(t.end_ns - t.start_ns for t in ticks)
    inside = 0
    for t in ticks:
        covered = sum(s.end_ns - s.start_ns for s in spans
                      if s.parent == t.id)
        inside += covered + min(t.attrs.get("runq_ns", 0),
                                t.end_ns - t.start_ns - covered)
    assert inside / whole >= 0.95, inside / whole


def test_bookkeeping_span_only_with_metrics_or_flight_attached(stages,
                                                                tracer):
    from simple_distributed_machine_learning_tpu.serve import ServeMetrics

    eng = _engine(stages, metrics=ServeMetrics())
    eng.submit(_prompt(6, 0), 3)
    eng.drain()
    by = _by_name(tracer)
    assert len(by["engine.bookkeeping"]) == len(by["engine.tick"])
    assert {s.parent for s in by["engine.bookkeeping"]} == {
        t.id for t in by["engine.tick"]}


# -- every program run, from its dispatch to its wait -------------------------

DISPATCHES = {"engine.decode.dispatch": "decode",
              "engine.prefill.dispatch": "chunk"}
WAITS = {"engine.decode.wait": "decode", "engine.prefill.wait": "chunk"}


def _runs(tracer):
    """``(dispatch spans by run, wait spans by run)``, after the checks
    every drive must pass: runs are numbered in the order they were
    launched, a dispatch says which program, a wait names a run that a
    dispatch of its program named earlier and that nothing else read, and
    says whether the bytes were there."""
    spans = sorted(tracer.spans(), key=lambda s: s.start_ns)
    asked = {s.attrs["run"]: s for s in spans if s.name in DISPATCHES}
    assert list(asked) == list(range(1, len(asked) + 1))
    read = {}
    for s in spans:
        if s.name in DISPATCHES:
            assert s.attrs["program"] == DISPATCHES[s.name]
        if s.name in WAITS:
            d = asked[s.attrs["run"]]
            assert d.attrs["program"] == WAITS[s.name]
            assert d.end_ns <= s.start_ns
            assert s.attrs["run"] not in read
            assert s.attrs["ready"] in (0, 1)
            assert SCHED <= set(s.attrs)
            read[s.attrs["run"]] = s
        elif s.name != "engine.tick":
            assert not SCHED & set(s.attrs), s.name
    ticks = {s.id: s for s in spans if s.name == "engine.tick"}
    for t in ticks.values():
        assert SCHED <= set(t.attrs)
        assert t.attrs["runs"] == sum(1 for d in asked.values()
                                      if d.parent == t.id)
    return asked, read, ticks


def _drive(eng, prompt=_prompt):
    for i in range(4):
        eng.submit(prompt(6 + 3 * i, i), 5)
    eng.drain()


def test_a_decode_is_waited_for_in_the_tick_after_its_dispatch(stages,
                                                               tracer):
    eng = _engine(stages)
    assert eng._dispatch_ahead
    _drive(eng)
    asked, read, ticks = _runs(tracer)
    assert set(read) == set(asked)              # nothing dropped: all read
    crossed = 0
    for run, w in read.items():
        d = asked[run]
        a, b = ticks[d.parent].attrs["tick"], ticks[w.parent].attrs["tick"]
        if d.attrs["program"] == "chunk":
            assert a == b and w.attrs["rid"] == d.attrs["rid"]
        else:
            assert b - a in (0, 1)
            crossed += b - a
    decodes = sum(1 for d in asked.values() if d.attrs["program"] == "decode")
    assert crossed >= decodes - 2 > 5           # but for the first tick's
    assert eng._runs == len(asked)


def test_a_speculative_tick_reads_its_run_in_the_same_tick(tracer):
    draft_cfg = dataclasses.replace(CFG, n_layers=1)
    eng = _engine(make_gpt_stages(jax.random.key(0), CFG, 1)[0],
                  draft_stages=make_gpt_stages(jax.random.key(9), draft_cfg,
                                               1)[0],
                  draft_cfg=draft_cfg, spec_k=3)
    assert eng.speculative and not eng._dispatch_ahead
    _drive(eng)
    asked, read, _ = _runs(tracer)
    assert set(read) == set(asked) and len(asked) > 5
    assert all(asked[r].parent == w.parent for r, w in read.items())


def test_block_steps_number_their_runs_too(tracer):
    from simple_distributed_machine_learning_tpu.models.sdar import (
        SdarConfig,
        make_sdar_stages,
    )

    cfg = SdarConfig()
    eng = InferenceEngine(make_sdar_stages(jax.random.key(0), cfg)[0], cfg,
                          n_slots=2, max_len=64, block_size=8,
                          prefill_chunk=8, attn_kernel="fused")
    for i in range(3):
        eng.submit(np.arange(5 + 4 * i, dtype=np.int32) % cfg.vocab, 6)
    eng.drain()
    asked, read, ticks = _runs(tracer)
    assert set(read) == set(asked)
    ahead = [r for r, w in read.items() if asked[r].parent != w.parent]
    assert ahead and all(asked[r].attrs["program"] == "decode"
                         for r in ahead)


@pytest.mark.parametrize("leave", ["preempt", "cancel"])
def test_a_run_whose_slot_left_has_a_dispatch_and_no_wait(stages, tracer,
                                                          leave):
    eng = _engine(stages)
    h = eng.submit(_prompt(6, 0), 8)
    while len(h.tokens) < 3:
        eng.step()
    assert eng._ahead is not None and h.rid in eng._ahead[0]
    dropped = eng._ahead[1][3]
    getattr(eng, leave)(h.rid)
    eng.drain()
    asked, read, _ = _runs(tracer)
    assert dropped in asked and dropped not in read
    assert set(asked) - set(read) == {dropped}
    assert len(h.tokens) == (8 if leave == "preempt" else 3)


def test_disabled_recorder_costs_the_engine_no_reading(stages, tracer,
                                                       reads):
    """The hot path with the operator's switch off: no clock of the
    recorder's, no ``getrusage``, no ``/proc`` file, and the awaited array
    is not asked whether it is ready."""
    from simple_distributed_machine_learning_tpu.serve import engine

    tracer.enabled = False
    eng = _engine(stages)
    _drive(eng)
    assert not reads and not tracer.spans()
    engine._ready(tracing.NO_SPAN, object())    # has no is_ready to ask


# -- the engine's own clock never sees the recorder ----------------------------

TOY = GPTConfig(vocab=32, seq_len=48, d_model=32, n_heads=2, n_layers=2)


@pytest.mark.parametrize("name", ["burst-interactive", "crash-serve",
                                  "overload-shed"])
def test_virtual_clock_report_is_the_same_with_the_recorder_off(name,
                                                                tracer):
    """The spans read ``perf_counter``, never the engine's clock (under the
    virtual clock every read moves time): the scenario's report is byte for
    byte the same with the recorder enabled and disabled."""
    toy = make_gpt_stages(jax.random.key(0), TOY, 2)[0]
    faults.uninstall()
    try:
        on = run_scenario(name, toy, TOY)
        assert any(s.name == "engine.tick" for s in tracer.spans())
        tracer.enabled = False
        n = len(tracer.spans())
        off = run_scenario(name, toy, TOY)
        assert len(tracer.spans()) == n
    finally:
        faults.uninstall()
    assert json.dumps(on, sort_keys=True) == json.dumps(off, sort_keys=True)
