"""Streaming SLO engine (ISSUE 19): windowed quantiles, multi-window
burn-rate alerts, and the alert -> fleet feedback loop.

The acceptance pins: the alert state machine transitions exactly as the
SRE diagram says (one transition per evaluation, tick-stamped, never a
clock read); the overload-shed scenario fires and resolves
``slo_burn{class=interactive}`` at EXACT virtual-clock ticks; per-token
TPOT samples stay out of the burn series (the request-level SLI — a shed
storm must not be diluted by hundreds of good token observations); and a
replica whose burn alert fires demonstrably loses the router's affinity
preference while firing and regains it after resolve, with hysteresis.
"""

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
    ServeMetrics,
    engine_factory,
)
from simple_distributed_machine_learning_tpu.serve.fleet import (
    AutoscalePolicy,
    ServeFleet,
)
from simple_distributed_machine_learning_tpu.telemetry.alerts import (
    Alert,
    AlertBook,
)
from simple_distributed_machine_learning_tpu.telemetry.slo import (
    SLOEngine,
    SLOObjective,
    WindowHistogram,
)

CFG = GPTConfig(vocab=32, seq_len=48, d_model=32, n_heads=2, n_layers=2)
_STAGES = None


def _model():
    global _STAGES
    if _STAGES is None:
        _STAGES = make_gpt_stages(jax.random.key(0), CFG, 2)[0]
    return _STAGES


def _prompt(n, seed):
    return np.asarray(
        jax.random.randint(jax.random.key(seed), (n,), 0, CFG.vocab),
        np.int32)


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    faults.uninstall()
    yield
    faults.uninstall()


# ---------------------------------------------------------------------------
# the alert state machine (telemetry/alerts.py) — pure, no jax, no clock


def test_alert_full_cycle_one_transition_per_evaluation():
    a = Alert("k", pending_ticks=2, resolve_ticks=3)
    assert a.evaluate(1, True) == ("inactive", "pending")
    assert a.evaluate(2, True) == ("pending", "firing")
    assert a.fired_at == 2 and a.firing
    assert a.evaluate(3, True) is None            # firing stays firing
    # the un-flap hysteresis: resolve needs resolve_ticks CONSECUTIVE
    # clear evaluations — a mid-streak breach resets it
    assert a.evaluate(4, False) is None
    assert a.evaluate(5, True) is None
    assert a.evaluate(6, False) is None
    assert a.evaluate(7, False) is None
    assert a.evaluate(8, False) == ("firing", "resolved")
    assert a.resolved_at == 8 and not a.firing
    # resolved is a ONE-evaluation state: the explicit "just cleared" row
    assert a.evaluate(9, False) == ("resolved", "inactive")


def test_alert_blip_never_pages_and_resolved_can_retrip():
    a = Alert("k", pending_ticks=2, resolve_ticks=2)
    # a single-tick blip: pending decays straight back, never firing
    assert a.evaluate(1, True) == ("inactive", "pending")
    assert a.evaluate(2, False) == ("pending", "inactive")
    # drive to resolved, then re-trip: resolved -> pending (not firing —
    # the page needs a fresh pending_ticks streak)
    for t, b in ((3, True), (4, True), (5, False), (6, False)):
        a.evaluate(t, b)
    assert a.state == "resolved"
    assert a.evaluate(7, True) == ("resolved", "pending")


def test_alert_validation():
    with pytest.raises(ValueError):
        Alert("k", pending_ticks=0)
    with pytest.raises(ValueError):
        Alert("k", resolve_ticks=0)


def test_alert_book_journals_context_and_replays_active_at():
    book = AlertBook(pending_ticks=1, resolve_ticks=1)
    assert book.evaluate("a", 1, True, burn_fast=2.0) == {
        "tick": 1, "alert": "a", "from": "inactive", "to": "pending",
        "burn_fast": 2.0}
    book.evaluate("a", 2, True, burn_fast=3.0)
    book.evaluate("b", 2, True)
    assert book.firing() == ["a"]
    assert book.states() == {"a": "firing", "b": "pending"}
    # journal replay reconstructs the firing set as of any tick — the
    # flight-row/bundle tick-join contract
    assert book.active_at(1) == []
    assert book.active_at(2) == ["a"] == book.active_at(2.5)
    book.evaluate("a", 3, False)                  # firing -> resolved
    assert book.active_at(2) == ["a"]
    assert book.active_at(3) == [] == book.firing()


# ---------------------------------------------------------------------------
# windowed quantiles — static buckets, deterministic by construction


def test_window_histogram_quantiles_are_bucket_upper_bounds():
    h = WindowHistogram(bounds=(1.0, 2.0, 5.0, 10.0), window=2)
    for v in (0.5, 1.5, 7.0):
        h.observe(v)
    h.roll()
    assert h.n == 3
    assert h.quantile(0.5) == 2.0                 # nearest rank, never
    assert h.quantile(1.0) == 10.0                # an interpolation
    h.observe(100.0)                              # overflow clamps to the
    h.roll()                                      # last bound
    assert h.quantile(1.0) == 10.0
    # the window slides: two fresh empty ticks evict everything
    h.roll()
    h.roll()
    assert h.n == 0 and h.quantile(0.5) is None


def test_window_histogram_validation():
    with pytest.raises(ValueError):
        WindowHistogram(window=0)
    with pytest.raises(ValueError):
        WindowHistogram(bounds=(5.0, 1.0))
    with pytest.raises(ValueError):
        WindowHistogram(bounds=(1.0, 1.0))


# ---------------------------------------------------------------------------
# the engine: objectives, burn math, the request-level SLI


def test_objective_and_engine_validation():
    with pytest.raises(ValueError):
        SLOObjective("x", ttft_slo_ms=10.0, target=1.0)
    with pytest.raises(ValueError):
        SLOObjective("x")                         # tracks nothing
    obj = SLOObjective("x", ttft_slo_ms=10.0)
    assert obj.budget == pytest.approx(0.1)
    with pytest.raises(ValueError):
        SLOEngine([obj], fast_window=4, slow_window=2)
    with pytest.raises(ValueError):
        SLOEngine([obj, SLOObjective("x", tpot_slo_ms=5.0)])
    with pytest.raises(ValueError):
        SLOEngine([obj], min_count=0)


def test_from_classes_none_when_nothing_to_track():
    class TC:
        def __init__(self, name, ttft=None, tpot=None):
            self.name, self.ttft_slo_ms, self.tpot_slo_ms = name, ttft, tpot

    assert SLOEngine.from_classes([TC("a"), TC("b")]) is None
    eng = SLOEngine.from_classes([TC("a"), TC("b", ttft=50.0)])
    assert set(eng.objectives) == {"b"}


def test_tpot_samples_stay_out_of_the_burn_series():
    """The request-level SLI: per-token TPOT observations feed the
    quantile window only — a flood of them (every one violating its
    target!) must not move the burn rate, else a shed storm would be
    diluted into invisibility by the surviving requests' token streams."""
    eng = SLOEngine([SLOObjective("x", ttft_slo_ms=10.0, tpot_slo_ms=1.0)],
                    fast_window=2, slow_window=4)
    for _ in range(100):
        eng.observe_tpot("x", 99.0)               # all violate the target
    assert eng.evaluate(1) == []
    assert eng.burn_rates() == {"x": 0.0}
    assert eng.window_quantiles()["x_tpot_p95_ms"] == 100.0
    # one violating TTFT is one bad request: burn = (1/1) / 0.1
    eng.observe_ttft("x", 99.0)
    eng.evaluate(2)
    assert eng.burn_rates() == {"x": pytest.approx(10.0)}
    # a shed is a violated observation by definition
    eng.observe_shed("x")
    eng.observe_ttft("x", 1.0)
    eng.evaluate(3)
    assert eng.burn_rates() == {"x": pytest.approx((2 / 3) / 0.1)}
    # unknown classes are ignored, never KeyError
    eng.observe_ttft("ghost", 1.0)
    eng.observe_shed("ghost")


def test_multi_window_condition_needs_both_windows():
    """Fast window alone is flappy: one hot fast window over a clean slow
    window must NOT breach (the SRE multi-window point)."""
    eng = SLOEngine([SLOObjective("x", ttft_slo_ms=10.0)],
                    fast_window=1, slow_window=32, pending_ticks=1)
    for t in range(1, 20):                        # long clean history
        eng.observe_ttft("x", 1.0)
        eng.evaluate(t)
    eng.observe_ttft("x", 99.0)                   # one hot tick
    assert eng.evaluate(20) == []                 # fast=10, slow=.5: holds


# ---------------------------------------------------------------------------
# the scenario pins: exact fire/resolve ticks under the virtual clock


def test_overload_shed_burn_alert_trajectory_pinned():
    """THE alert determinism pin: the shed storm fires
    ``slo_burn{class=interactive}`` and drains it at exact ticks — every
    transition, both burn rates, byte-for-byte."""
    rep = run_scenario("overload-shed", _model(), CFG)
    alerts = rep["slo_alerts"]
    assert alerts["tick"] == 69
    assert alerts["windows"] == {"fast": 8, "slow": 32,
                                 "burn_threshold": 1.0}
    key = "slo_burn{class=interactive}"
    assert alerts["transitions"] == [
        {"tick": 32, "alert": key, "from": "inactive", "to": "pending",
         "burn_fast": 3.3333, "burn_slow": 2.0},
        {"tick": 33, "alert": key, "from": "pending", "to": "firing",
         "burn_fast": 5.0, "burn_slow": 2.0},
        {"tick": 56, "alert": key, "from": "firing", "to": "resolved",
         "burn_fast": 0.0, "burn_slow": 3.75},
        {"tick": 57, "alert": key, "from": "resolved", "to": "inactive",
         "burn_fast": 0.0, "burn_slow": 4.2857},
    ]
    # fired AND resolved within the run: nothing left active at the end
    assert alerts["firing"] == []
    assert alerts["states"] == {key: "inactive"}
    # the pre-existing overload pins must survive the SLO engine riding
    # along (it observes, never steers the supervised run)
    assert rep["completed"] == 9 and rep["shed"] == 27
    assert rep["slo"]["interactive"]["ttft_ms_p95"] == 69.143


def test_crash_serve_burns_no_budget():
    """A crash the supervisor absorbs within SLO (attainment 1.0) must
    fire NOTHING — alerts are for burn, not for restarts."""
    rep = run_scenario("crash-serve", _model(), CFG)
    assert rep["slo_alerts"]["transitions"] == []
    assert rep["slo_alerts"]["states"] == {
        "slo_burn{class=interactive}": "inactive"}
    # windowed quantiles are pinned bucket bounds, not interpolations
    assert rep["slo_alerts"]["window_quantiles"] == {
        "interactive_tpot_p95_ms": 5.0, "interactive_ttft_p95_ms": 20.0}
    assert rep["restarts"] == 1


def test_slo_blocks_deterministic_across_runs():
    r1 = run_scenario("overload-shed", _model(), CFG)
    r2 = run_scenario("overload-shed", _model(), CFG)
    assert (json.dumps(r1["slo_alerts"], sort_keys=True)
            == json.dumps(r2["slo_alerts"], sort_keys=True))


def test_slo_alert_records_land_in_metrics_jsonl(tmp_path):
    """The CI chaos drill's grep target: one ``kind: "slo_alert"`` record
    per journaled transition, joinable on tick."""
    d = str(tmp_path / "run")
    run_scenario("overload-shed", _model(), CFG, outdir=d)
    with open(os.path.join(d, "metrics.jsonl")) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    alerts = [r for r in recs if r.get("kind") == "slo_alert"]
    assert [(r["tick"], r["to"]) for r in alerts] == [
        (32, "pending"), (33, "firing"), (56, "resolved"), (57, "inactive")]
    assert all(r["scenario"] == "overload-shed" for r in alerts)
    scen = next(r for r in recs if r.get("kind") == "scenario")
    assert scen["slo_alerts"]["transitions"] == 4


# ---------------------------------------------------------------------------
# the flight-recorder / bundle tick-join contract, extended to alerts


def test_flight_rows_join_alert_journal(tmp_path):
    """Every flight row's ``active_alerts`` snapshot must agree with the
    alert journal replayed to the same tick — the bundle/journal
    tick-join contract, extended to alerts (both are stamped with the
    supervisor's monotonic tick, evaluation strictly before the snap)."""
    from simple_distributed_machine_learning_tpu.serve import ServeSupervisor
    from simple_distributed_machine_learning_tpu.serve.flight import (
        FlightRecorder,
    )

    metrics = ServeMetrics()
    slo = SLOEngine([SLOObjective("interactive", ttft_slo_ms=1e-6)],
                    fast_window=2, slow_window=4, pending_ticks=2,
                    resolve_ticks=2)
    flight = FlightRecorder()
    sup = ServeSupervisor(
        engine_factory(_model(), CFG, n_slots=2, block_size=4,
                       prefill_chunk=3, metrics=metrics),
        os.path.join(str(tmp_path), "journal.jsonl"), metrics=metrics,
        flight=flight, slo=slo)
    for i in range(4):                 # every TTFT violates the 1ns target
        sup.submit(_prompt(5, i), max_new_tokens=3, cls="interactive")
    sup.drain()
    for _ in range(10):                # idle ticks: the alert drains too
        sup.step()
    sup.close()
    tos = [t["to"] for t in slo.alerts.journal]
    assert "firing" in tos and "resolved" in tos
    rows = flight.rows()
    assert any(r["active_alerts"] for r in rows)
    for r in rows:
        assert r["active_alerts"] == slo.alerts.active_at(r["tick"]), \
            r["tick"]


def test_postmortem_bundle_carries_active_alert_set(tmp_path):
    """The shed-burst bundle overload-shed dumps records the firing set
    at its trigger tick AND per flight row — all joinable against the
    journaled transitions. (A hotter storm than the catalog's: at its
    burst factor 5 no tick sheds the four requests that dump a bundle
    since the engine's tick dispatches its decode ahead, three at most.)"""
    import dataclasses
    import glob

    from simple_distributed_machine_learning_tpu.resilience.scenarios import (
        SCENARIOS,
    )
    d = str(tmp_path / "run")
    storm = SCENARIOS["overload-shed"]
    storm = dataclasses.replace(
        storm, sim=dataclasses.replace(storm.sim, burst_factor=7.0))
    run_scenario(storm, _model(), CFG, outdir=d)
    with open(os.path.join(d, "metrics.jsonl")) as f:
        journal = [json.loads(ln) for ln in f if ln.strip()
                   and json.loads(ln).get("kind") == "slo_alert"]

    def active_at(tick):
        state = {}
        for row in journal:
            if row["tick"] > tick:
                break
            state[row["alert"]] = row["to"]
        return sorted(k for k, s in state.items() if s == "firing")

    paths = glob.glob(os.path.join(d, "postmortem-*.json"))
    assert paths
    for p in paths:
        with open(p) as f:
            b = json.load(f)
        assert b["active_alerts"] == active_at(b["tick"])
        for row in b["flight"]:
            assert row["active_alerts"] == active_at(row["tick"])


# ---------------------------------------------------------------------------
# the closed loop: firing replica loses affinity, hysteresis re-entry


def _fleet(tmp_path, slo, **fleet_kw):
    clock = VirtualClock(per_call_s=0.001)
    metrics = ServeMetrics()
    fleet = ServeFleet(
        engine_factory(_model(), CFG, n_slots=2, block_size=4,
                       prefill_chunk=3, clock=clock, metrics=metrics),
        os.path.join(str(tmp_path), "fleet"), n_replicas=2,
        journal_sync=False, clock=clock, metrics=metrics, slo=slo,
        **fleet_kw)
    return fleet, metrics


def test_firing_replica_loses_affinity_then_reenters(tmp_path):
    slo = SLOEngine([SLOObjective("synthetic", ttft_slo_ms=10.0)],
                    fast_window=2, slow_window=4, pending_ticks=2,
                    resolve_ticks=2)
    fleet, metrics = _fleet(tmp_path, slo, alert_recover_ticks=2)
    try:
        # warm the hot prefix onto one replica (8 tokens = 2 full blocks)
        hot = _prompt(8, 7)
        h = fleet.submit(hot.copy(), max_new_tokens=4, seed=1)
        home = fleet._home[h.rid]
        fleet.drain()
        rep2, hit = fleet.router.route(hot, fleet._alive())
        assert rep2.idx == home and hit            # affinity established
        # burn the home replica's budget: one violating request-level
        # observation per fleet tick, attributed to ITS index
        for _ in range(2):
            slo.observe_ttft("synthetic", 999.0, replica=home)
            fleet.step()
        assert slo.firing_replicas() == {home}
        assert fleet._alert_demoted == {home}
        assert [e["replica"] for e in fleet.replica_log
                if e["event"] == "alert-demote"] == [home]
        # the demoted replica keeps its longer prefix but the router must
        # not PREFER it: the hot prompt lands on the other replica and the
        # suppression is counted
        h2 = fleet.submit(hot.copy(), max_new_tokens=4, seed=2)
        assert fleet._home[h2.rid] != home
        assert fleet.router.last_suppressed
        assert metrics.route_alert_demotions.value == 1
        fleet.drain()
        # recovery: clean ticks resolve the alert (resolve_ticks), then
        # the fleet's OWN hysteresis (alert_recover_ticks) re-enters it —
        # two separate debounces, both must elapse
        for _ in range(8):
            fleet.step()
        assert slo.firing_replicas() == set()
        assert fleet._alert_demoted == set()
        assert [e["replica"] for e in fleet.replica_log
                if e["event"] == "alert-re-enter"] == [home]
        h3 = fleet.submit(hot.copy(), max_new_tokens=4, seed=3)
        assert fleet._home[h3.rid] == home         # preference restored
        assert metrics.route_alert_demotions.value == 1
        assert metrics.summary()["route_alert_demotions"] == 1
    finally:
        fleet.close()


def test_fleet_validation_and_demotion_never_empties_candidates(tmp_path):
    with pytest.raises(ValueError):
        AutoscalePolicy(scale_out_burn_rate=0.0)
    slo = SLOEngine([SLOObjective("synthetic", ttft_slo_ms=10.0)],
                    fast_window=2, slow_window=4, pending_ticks=1)
    with pytest.raises(ValueError):
        _fleet(tmp_path, slo, alert_recover_ticks=0)
    # every replica firing: demotion deprioritizes but the fleet still
    # routes (a demoted replica serves — it just stops attracting)
    fleet, metrics = _fleet(tmp_path, slo)
    try:
        for _ in range(2):
            for idx in range(2):
                slo.observe_ttft("synthetic", 999.0, replica=idx)
            fleet.step()
        assert slo.firing_replicas() == {0, 1}
        assert fleet._alert_demoted == {0, 1}
        h = fleet.submit(_prompt(5, 3), max_new_tokens=3, seed=4)
        fleet.drain()
        assert h.state == "done"
    finally:
        fleet.close()


def test_burn_rate_feeds_autoscaler_scale_out(tmp_path):
    """The optional scale-out trigger: sustained burn counts toward the
    same backlog streak as queue depth — capacity arrives on latency
    pressure before the queue-depth watermark trips."""
    clock = VirtualClock(per_call_s=0.001)
    metrics = ServeMetrics()
    slo = SLOEngine([SLOObjective("synthetic", ttft_slo_ms=10.0)],
                    fast_window=2, slow_window=4)
    fleet = ServeFleet(
        engine_factory(_model(), CFG, n_slots=2, block_size=4,
                       prefill_chunk=3, clock=clock, metrics=metrics),
        os.path.join(str(tmp_path), "fleet"), n_replicas=1,
        journal_sync=False, clock=clock, metrics=metrics, slo=slo,
        autoscale=AutoscalePolicy(min_replicas=1, max_replicas=2,
                                  scale_out_queue_depth=999,
                                  scale_out_ticks=2, retire_idle_s=60.0,
                                  scale_out_burn_rate=1.0))
    try:
        assert fleet.n_alive == 1
        for _ in range(2):
            slo.observe_ttft("synthetic", 999.0)
            fleet.step()
        assert fleet.n_alive == 2
        assert any(e["event"] == "scale-out" for e in fleet.replica_log)
    finally:
        fleet.close()
