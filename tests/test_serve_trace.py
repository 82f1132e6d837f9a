"""Request-scoped serve tracing, flight recorder, post-mortem bundles,
KV-drift gauges and the report CLI (ISSUE 12).

The acceptance pins:

- a crash-serve run produces per-request traces that SPAN the restart
  (submit -> crash -> re-admit -> completion under one rid, both
  incarnations visible) with no orphan end events;
- the virtual-clock scenario trace is byte-identical across two runs, and
  every exact-pinned scenario number is unchanged with tracing enabled
  (the recorder never reads a clock);
- the supervisor dumps a parseable post-mortem bundle on every restart,
  on DrainTimeout and on a shed burst, whose rows join the journal on the
  monotonic tick;
- the KV-drift gauge reads exactly 0 on clean runs (the
  PR-8 live-gauge == analyzer-prediction parity promoted to a runtime
  invariant), and old journals without the tick field stay recoverable.
"""

import hashlib
import json
import os

import jax
import numpy as np
import pytest

from simple_distributed_machine_learning_tpu.models.gpt import (
    GPTConfig,
    make_gpt_stages,
)
from simple_distributed_machine_learning_tpu.resilience import faults
from simple_distributed_machine_learning_tpu.resilience.scenarios import (
    VirtualClock,
    run_scenario,
)
from simple_distributed_machine_learning_tpu.serve import (
    DrainTimeout,
    FlightRecorder,
    InferenceEngine,
    ServeMetrics,
    ServeSupervisor,
    ServeTrace,
    engine_factory,
)
from simple_distributed_machine_learning_tpu.serve.flight import write_bundle
from simple_distributed_machine_learning_tpu.serve.journal import (
    RequestJournal,
    read_journal,
    recover_state,
)

CFG = GPTConfig(vocab=32, seq_len=48, d_model=32, n_heads=2, n_layers=2)
_STAGES = None


def _model():
    global _STAGES
    if _STAGES is None:
        _STAGES = make_gpt_stages(jax.random.key(0), CFG, 2)[0]
    return _STAGES


def _prompt(n, seed, first=None):
    p = np.array(jax.random.randint(jax.random.key(seed), (n,), 0,
                                    CFG.vocab), np.int32)
    if first is not None:
        p[0] = first            # distinct first tokens -> no prefix sharing
    return p


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    faults.uninstall()
    yield
    faults.uninstall()


def _span_balance(events):
    """(orphan_ends, unclosed) over the async b/e events of a Chrome
    trace — the well-formedness invariant."""
    open_count: dict = {}
    orphans = []
    for e in events:
        key = (e.get("cat"), e.get("id"), e["name"])
        if e["ph"] == "b":
            open_count[key] = open_count.get(key, 0) + 1
        elif e["ph"] == "e":
            if open_count.get(key, 0) < 1:
                orphans.append(e)
            else:
                open_count[key] -= 1
    return orphans, {k: v for k, v in open_count.items() if v}


# ---------------------------------------------------------------------------
# trace well-formedness on a plain engine


def test_engine_trace_covers_request_lifecycle(tmp_path):
    stages = _model()
    trace = ServeTrace(outdir=str(tmp_path))
    eng = InferenceEngine(stages, CFG, n_slots=2, block_size=4,
                          prefill_chunk=3, trace=trace)
    h = eng.submit(_prompt(7, 1, first=0), max_new_tokens=4, seed=1)
    eng.submit(_prompt(5, 2, first=1), max_new_tokens=3, seed=2)
    eng.drain()
    trace.close()
    rows = [json.loads(line)
            for line in open(tmp_path / "request_timeline.jsonl")]
    evs_h = [r["ev"] for r in rows if r.get("rid") == h.rid]
    # the full ladder, in order: submit -> admit -> chunks -> first token
    # -> decode ticks -> done
    assert evs_h[0] == "submit" and evs_h[-1] == "done"
    assert "admit" in evs_h and "first_token" in evs_h
    assert evs_h.count("prefill_chunk") == 3          # ceil(7/3)
    assert evs_h.count("tick") == 3                   # tokens 2..4
    # timestamps non-decreasing within a request's timeline
    ts = [r["t"] for r in rows if r.get("rid") == h.rid]
    assert ts == sorted(ts)
    doc = json.load(open(tmp_path / "serve_trace.json"))
    orphans, unclosed = _span_balance(doc["traceEvents"])
    assert not orphans and not unclosed
    # chrome trace is pid-pinned (byte-identical across machines)
    assert all(e["pid"] == 0 for e in doc["traceEvents"])


def test_trace_preempt_resume_and_shed_events():
    from simple_distributed_machine_learning_tpu.serve import (
        PriorityScheduler,
    )
    stages = _model()
    trace = ServeTrace()
    eng = InferenceEngine(stages, CFG, n_slots=1, block_size=4,
                          scheduler=PriorityScheduler, trace=trace)
    low = eng.submit(_prompt(4, 1, first=0), max_new_tokens=10, seed=1,
                     cls="batch", priority=0)
    for _ in range(3):
        eng.step()
    eng.submit(_prompt(4, 2, first=1), max_new_tokens=3, seed=2,
               cls="interactive", priority=2)
    for _ in range(6):
        eng.step()
    eng.cancel(low.rid, "deadline")
    eng.drain()
    evs = [(r["ev"], r.get("rid")) for r in trace.rows]
    assert ("preempt", low.rid) in evs
    assert ("shed", low.rid) in evs
    orphans, unclosed = _span_balance(
        trace.to_chrome_trace()["traceEvents"])
    assert not orphans and not unclosed


def test_tracing_does_not_perturb_virtual_clock_metrics():
    """THE no-clock-reads pin: the same virtual-clock workload produces
    identical latency metrics with tracing on and off — a recorder that
    read the clock even once would shift every subsequent timestamp."""
    stages = _model()

    def run(trace):
        clock = VirtualClock()
        metrics = ServeMetrics(clock=clock)
        eng = InferenceEngine(stages, CFG, n_slots=2, block_size=4,
                              prefill_chunk=3, metrics=metrics,
                              clock=clock, trace=trace)
        for i in range(4):
            eng.submit(_prompt(5 + i, i, first=i), max_new_tokens=5,
                       seed=i)
        eng.drain()
        return metrics.summary()

    assert run(None) == run(ServeTrace())


# ---------------------------------------------------------------------------
# crash-serve: spans join across the restart (satellite 4)


def test_crash_serve_trace_spans_the_restart(tmp_path):
    """Spans for a recovered request cover submit -> crash -> re-admit ->
    completion across >= 1 restart, keyed by ONE rid; no orphan end
    events; and the exact-pinned scenario numbers hold with tracing ON."""
    stages = _model()
    trace = ServeTrace(outdir=str(tmp_path), suffix="-crash-serve")
    rep = run_scenario("crash-serve", stages, CFG, trace=trace)
    # tracing enabled must not move a single pinned number
    assert rep["slo_ok"] and rep["all_completed"] and rep["restarts"] == 1
    assert rep["slo"]["interactive"]["ttft_ms_p95"] == 23.16
    assert rep["trace_events"] == trace.n_events > 0
    rows = trace.rows
    crashed_rids = {r["rid"] for r in rows if r["ev"] == "crash"}
    assert crashed_rids, "the injected crash must show in the timeline"
    rid = sorted(crashed_rids)[0]
    evs = [r["ev"] for r in rows if r.get("rid") == rid]
    # the joined lifecycle under one trace id
    for needle in ("submit", "crash", "readmit", "done"):
        assert needle in evs, (rid, evs)
    assert evs.index("submit") < evs.index("crash") \
        < evs.index("readmit") < evs.index("done")
    # both engine incarnations visible on the one timeline
    incs = {r["inc"] for r in rows if r.get("rid") == rid}
    assert incs == {0, 1}
    orphans, unclosed = _span_balance(
        trace.to_chrome_trace()["traceEvents"])
    assert not orphans and not unclosed


def test_virtual_clock_trace_byte_identical_across_runs(tmp_path):
    stages = _model()
    digests = []
    for run_dir in ("a", "b"):
        d = tmp_path / run_dir
        run_scenario("crash-serve", stages, CFG, outdir=str(d), trace=True)
        digests.append(tuple(
            hashlib.sha256(
                open(d / name, "rb").read()).hexdigest()
            for name in ("serve_trace-crash-serve.json",
                         "request_timeline-crash-serve.jsonl")))
    assert digests[0] == digests[1]


def test_cold_restart_timeline_appends_under_same_rid(tmp_path):
    """Cold restart join: a NEW process's recorder (fresh=False) appends
    the recovered rid's events after the dead process's — one key, two
    engine incarnations' worth of history in one timeline file."""
    stages = _model()
    jpath = str(tmp_path / "journal.jsonl")
    trace1 = ServeTrace(outdir=str(tmp_path))
    sup = ServeSupervisor(engine_factory(stages, CFG, n_slots=2,
                                         block_size=4, prefill_chunk=3),
                          jpath, trace=trace1)
    h = sup.submit(_prompt(5, 1, first=0), max_new_tokens=6, seed=1)
    for _ in range(4):
        sup.step()
    mid_tokens = list(h.tokens)
    assert 0 < len(mid_tokens) < 6
    sup.close()         # process "dies" with the request in flight
    trace1.close()

    trace2 = ServeTrace(outdir=str(tmp_path), fresh=False)
    sup2 = ServeSupervisor(engine_factory(stages, CFG, n_slots=2,
                                          block_size=4, prefill_chunk=3),
                           jpath, trace=trace2)
    sup2.drain()
    sup2.close()
    trace2.close()
    rows = [json.loads(line)
            for line in open(tmp_path / "request_timeline.jsonl")]
    evs = [r["ev"] for r in rows if r.get("rid") == h.rid]
    assert evs[0] == "submit" and "readmit" in evs and evs[-1] == "done"
    # the recovered stream is the continuation, not a replay
    assert sup2.requests[h.rid].tokens[:len(mid_tokens)] == mid_tokens


# ---------------------------------------------------------------------------
# flight recorder + post-mortem bundles


def test_flight_recorder_ring_bounds():
    fr = FlightRecorder(capacity=3)
    for i in range(7):
        fr.record({"tick": i})
    assert fr.ticks_recorded == 7
    assert [r["tick"] for r in fr.rows()] == [4, 5, 6]
    with pytest.raises(ValueError, match="capacity"):
        FlightRecorder(capacity=0)


def test_restart_writes_postmortem_bundle_that_joins_journal(tmp_path):
    """One bundle per restart: parses, carries the dead incarnation's
    flight rows, request states and the journal tail — and bundle rows
    join journal records exactly on the monotonic tick."""
    stages = _model()
    faults.install(faults.FaultPlan.parse("engine-crash@serve.tick=3"))
    sup = ServeSupervisor(
        engine_factory(stages, CFG, n_slots=2, block_size=4,
                       prefill_chunk=3),
        str(tmp_path / "journal.jsonl"),
        postmortem_dir=str(tmp_path))
    for i in range(3):
        sup.submit(_prompt(5, i, first=i), max_new_tokens=6, seed=i)
    sup.drain()
    sup.close()
    assert sup.restarts == 1 and len(sup.postmortems) == 1
    bundle = json.load(open(sup.postmortems[0]))
    assert bundle["kind"] == "postmortem"
    assert bundle["trigger"] == "restart"
    assert "EngineCrash" in bundle["cause"]
    assert bundle["flight"], "the dead incarnation's flight rows"
    assert bundle["requests"] and bundle["journal_tail"]
    # the forensic join: flight ticks and journal ticks share one counter
    flight_ticks = {row["tick"] for row in bundle["flight"]}
    journal_ticks = {ev["tick"] for ev in bundle["journal_tail"]
                     if "tick" in ev}
    assert flight_ticks & journal_ticks
    assert bundle["tick"] >= max(flight_ticks)
    # every journal record written by the supervisor carries the tick
    events, _ = read_journal(str(tmp_path / "journal.jsonl"))
    assert events and all("tick" in ev for ev in events)
    ticks = [ev["tick"] for ev in events]
    assert ticks == sorted(ticks), "monotonic across the restart"


def test_drain_timeout_dumps_bundle_before_raising(tmp_path):
    stages = _model()
    sup = ServeSupervisor(
        engine_factory(stages, CFG, n_slots=1, block_size=4),
        str(tmp_path / "journal.jsonl"), postmortem_dir=str(tmp_path))
    sup.submit(_prompt(4, 1), max_new_tokens=12, seed=1)
    sup.submit(_prompt(4, 2), max_new_tokens=12, seed=2)
    with pytest.raises(DrainTimeout):
        sup.drain(max_ticks=2)
    assert len(sup.postmortems) == 1
    bundle = json.load(open(sup.postmortems[0]))
    assert bundle["trigger"] == "drain_timeout"
    live = [r for r in bundle["requests"]
            if r["state"] in ("queued", "active")]
    assert live, "the abandoned work is in the bundle"
    sup.close()


def test_shed_burst_dumps_bundle(tmp_path):
    """A tick that sheds >= shed_burst requests is a forensic event: the
    deadline mass-expiry here sheds every queued request at once."""
    stages = _model()
    clock = VirtualClock()
    sup = ServeSupervisor(
        engine_factory(stages, CFG, n_slots=1, block_size=4, clock=clock),
        str(tmp_path / "journal.jsonl"), clock=clock,
        postmortem_dir=str(tmp_path), shed_burst=3,
        default_ttft_deadline_s=0.004)
    for i in range(5):
        sup.submit(_prompt(4, i, first=i), max_new_tokens=4, seed=i)
    clock.sleep(1.0)            # every TTFT deadline expires
    sup.step()
    assert any("shed_burst" in p for p in sup.postmortems), sup.postmortems
    bundle = json.load(open(sup.postmortems[0]))
    assert bundle["trigger"] == "shed_burst"
    sup.close()


def test_write_bundle_atomic_and_complete(tmp_path):
    fr = FlightRecorder()
    fr.record({"tick": 1})
    path = write_bundle(str(tmp_path / "b.json"), trigger="restart",
                        cause="x", tick=1, flight=fr, requests={})
    b = json.load(open(path))
    assert b["flight"] == [{"tick": 1}] and b["requests"] == []
    assert not [p for p in os.listdir(tmp_path) if ".tmp." in p]


# ---------------------------------------------------------------------------
# journal tick satellite: old journals stay recoverable


def test_recover_state_tolerates_records_without_tick(tmp_path):
    """Regression pin for the journal format extension: a journal written
    BEFORE the tick field existed (hand-built here in the old grammar)
    recovers identically — cold restarts over old journals keep working."""
    path = str(tmp_path / "old.jsonl")
    old_records = [
        {"ev": "submit", "rid": 0, "prompt": [1, 2, 3], "max_new": 4,
         "temp": 0.0, "top_k": None, "top_p": None, "eos": None,
         "seed": 0, "cls": None, "prio": 0, "ttft_dl": None, "dl": None,
         "t": 1.0},
        {"ev": "tok", "rid": 0, "tok": 7, "kd": [1, 2], "dkd": None,
         "t": 2.0},
    ]
    with open(path, "w") as f:
        for rec in old_records:
            f.write(json.dumps(rec) + "\n")
    events, valid = read_journal(path)
    assert len(events) == 2 and valid == os.path.getsize(path)
    snaps = recover_state(events)
    assert snaps[0].tokens == [7] and snaps[0].state == "queued"
    # and the journal reopens for append over the old-format prefix
    j = RequestJournal(path, sync=False)
    j.log_done(rid=0, reason="length", t=3.0, tick=9)
    j.close()
    events2, _ = read_journal(path)
    assert events2[-1] == {"ev": "done", "rid": 0, "reason": "length",
                           "t": 3.0, "tick": 9}
    assert "tick" not in events2[0]


# ---------------------------------------------------------------------------
# KV drift: the PR-8 parity as a runtime invariant


def test_kv_drift_zero_every_tick_clean_paged_run():
    """THE drift acceptance pin (paged): with no prefix sharing, the live
    gauge equals the analyzer prediction at EVERY tick of the run."""
    stages = _model()
    metrics = ServeMetrics()
    eng = InferenceEngine(stages, CFG, n_slots=3, block_size=4,
                          prefill_chunk=3, metrics=metrics)
    for i in range(5):
        eng.submit(_prompt(5 + i, i, first=i), max_new_tokens=6, seed=i)
    while eng.busy:
        eng.step()
        live, predicted = eng.kv_drift()
        assert live == predicted, (live, predicted)
        assert metrics.kv_drift_bytes.value == 0
    s = metrics.summary()
    assert s["kv_drift_bytes"] == 0 and "kv_bytes_predicted" in s


def test_kv_drift_negative_under_prefix_sharing_never_positive():
    """Shared blocks make the live gauge SMALLER than the no-sharing
    model — drift <= 0 always; a positive drift would be a block leak."""
    stages = _model()
    metrics = ServeMetrics()
    eng = InferenceEngine(stages, CFG, n_slots=2, block_size=4,
                          prefill_chunk=None, metrics=metrics)
    shared = _prompt(8, 99)
    eng.submit(shared.copy(), max_new_tokens=8, seed=0)
    # the first request must have REGISTERED its prompt blocks (prefill
    # done) and still be decoding when the duplicate binds — concurrent
    # sharing is what makes live < predicted
    eng.step()
    eng.step()
    saw_sharing = False
    eng.submit(shared.copy(), max_new_tokens=8, seed=1)
    while eng.busy:
        eng.step()
        live, predicted = eng.kv_drift()
        assert live <= predicted, (live, predicted)
        saw_sharing |= live < predicted
    assert saw_sharing, "identical prompts must actually share blocks"


# ---------------------------------------------------------------------------
# the report CLI


def test_report_cli_renders_and_exits_zero(tmp_path, capsys):
    from simple_distributed_machine_learning_tpu.telemetry import report

    stages = _model()
    d = str(tmp_path / "run")
    rep = run_scenario("crash-serve", stages, CFG, outdir=d, trace=True)
    assert rep["postmortem_bundles"] == 1
    rc = report.main(["--dir", d])
    out = capsys.readouterr().out
    assert rc == 0
    assert "scenario crash-serve [PASS]" in out
    assert "restart #1" in out and "postmortem" in out
    assert "kv drift" in out and "[OK]" in out
    assert "timeline" in out and "2 incarnation(s)" in out
    rc = report.main(["--dir", d, "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert doc["serve"]["requests_completed"] == 16
    assert doc["postmortems"][0]["trigger"] == "restart"


def test_report_cli_exit_codes(tmp_path, capsys):
    from simple_distributed_machine_learning_tpu.telemetry import report

    assert report.main(["--dir", str(tmp_path / "missing")]) == 2
    empty = tmp_path / "empty"
    empty.mkdir()
    assert report.main(["--dir", str(empty)]) == 2
    capsys.readouterr()


def test_report_json_schema_pinned(tmp_path, capsys):
    """The ``--json`` document's top-level keys are an interface other
    tooling parses — pinned EXACTLY (a new artifact must land here, and
    the ISSUE-19 ``slo_alerts``/``attribution`` blocks are always
    present, never conditionally spliced in)."""
    from simple_distributed_machine_learning_tpu.telemetry import report

    stages = _model()
    d = str(tmp_path / "run")
    run_scenario("overload-shed", stages, CFG, outdir=d, trace=True)
    assert report.main(["--dir", d, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert set(doc) == {
        "dir", "serve", "scenarios", "slo_alerts", "attribution",
        "epochs", "last_epoch", "sentinel", "journals", "timelines",
        "traces", "postmortems"}
    assert [(r["tick"], r["to"]) for r in doc["slo_alerts"]] == [
        (32, "pending"), (33, "firing"), (56, "resolved"),
        (57, "inactive")]
    att = doc["attribution"]["overload-shed"]
    assert att["requests"] == 9 and att["top_slow"][0]["rid"] == 26
    # the text renderer shows the same two blocks: alert transitions and
    # the top-K slow-request autopsy table
    assert report.main(["--dir", d]) == 0
    out = capsys.readouterr().out
    assert "alert slo_burn{class=interactive}: pending -> firing" in out
    assert "top slow requests (TTFT autopsy):" in out
