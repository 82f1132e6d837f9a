"""The span recorder (``telemetry/tracing.py``) and the spans the serve
engine writes into it: parents, the ring, the disabled path, the stalls
nobody called for, the clock; then, on a toy paged engine, one
``engine.tick`` per busy ``step()`` whose children cover it and whose
attributes say what the tick did; and a virtual-clock scenario whose report
does not depend on the recorder.
"""

import gc
import json
import threading
import time

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


def test_children_cover_the_tick(stages, tracer):
    """At least 95 % of a tick lies inside its children. A client's callback
    that takes 2 ms (it runs inside the ``*.emit`` spans) makes a CPU tick
    of this toy several milliseconds, so that the few microseconds between
    two spans, which is all that may lie outside them, do not decide."""
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
    ids = {t.id for t in ticks}
    inside = sum(s.end_ns - s.start_ns for s in spans if s.parent in ids)
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


# -- the engine's own clock never sees the recorder ----------------------------

TOY = GPTConfig(vocab=32, seq_len=48, d_model=32, n_heads=2, n_layers=2)


@pytest.mark.parametrize("name", ["burst-interactive", "crash-serve"])
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
