"""Multi-replica fleet: router, cross-replica migration, autoscaler, journal
rotation.

The load-bearing claims (ISSUE 13 acceptance):

- **Bit-exact cross-replica migration** — killing a whole replica
  mid-decode (``replica-kill@fleet.tick``) re-admits its in-flight
  requests onto the survivors from its journal ALONE, and every migrated
  request's full token stream equals the uninterrupted run's — which
  equals the solo ``make_cached_decoder`` stream — across a double
  replica loss and a loss landing during another replica's crash
  recovery. The adopting replica's journal is self-contained: crashing
  the ADOPTER after a migration still recovers the adoptee bit-exact.
- **Routing** — affinity routes to the replica whose paged pool already
  holds the prompt's registered prefix (hot-prefix-skew pins affinity's
  prefix-hit counters STRICTLY above round-robin's on exact numbers);
  rids are fleet-unique; unhealthy replicas drain out of rotation and
  re-enter with hysteresis.
- **Autoscaler** — the diurnal scenario's exact virtual-clock trajectory:
  scale-out ticks at the first peak, drain-then-retire ticks in the
  trough, scale-out again at the second peak.
- **Journal rotation** (satellite) — ``RequestJournal.rotate()`` compacts
  to per-request ``snap`` records; recovery after rotation is
  byte-identical to recovery from the unrotated journal.
- **No mutable-default aliasing** (satellite) — one ``OverloadPolicy``
  shared by N replicas keeps PER-REPLICA token-bucket fills: one
  replica's debit never appears in another's.
- **SHED stays shed** (satellite) — ``recover_state`` over a journal with
  shed/cancelled records interleaved with restarts never re-admits a
  shed request.
"""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from simple_distributed_machine_learning_tpu.models.gpt import (
    GPTConfig,
    make_cached_decoder,
    make_gpt_stages,
)
from simple_distributed_machine_learning_tpu.resilience import faults
from simple_distributed_machine_learning_tpu.resilience.scenarios import (
    SCENARIOS,
    VirtualClock,
    run_scenario,
)
from simple_distributed_machine_learning_tpu.serve import (
    AutoscalePolicy,
    FleetRouter,
    OverloadPolicy,
    RequestJournal,
    ServeFleet,
    ServeSupervisor,
    engine_factory,
)
from simple_distributed_machine_learning_tpu.serve.journal import (
    read_journal,
    recover_state,
)
from simple_distributed_machine_learning_tpu.serve.request import (
    DONE,
    QUEUED,
    SHED,
)

CFG = GPTConfig(vocab=32, seq_len=48, d_model=32, n_heads=2, n_layers=2)
_STAGES = None


def _model():
    global _STAGES
    if _STAGES is None:
        _STAGES = make_gpt_stages(jax.random.key(0), CFG, 2)[0]
    return _STAGES, [s.params for s in _STAGES]


def _solo(stages, params, prompt, n_new, seed, temperature=0.0, top_k=None):
    dec = make_cached_decoder(stages, CFG, len(prompt), n_new,
                              temperature=temperature, top_k=top_k)
    out = dec(params, np.asarray(prompt, np.int32)[None],
              jax.random.key(seed))
    return np.asarray(out)[0, len(prompt):]


def _prompt(n, seed):
    return np.asarray(
        jax.random.randint(jax.random.key(seed), (n,), 0, CFG.vocab),
        np.int32)


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    faults.uninstall()
    yield
    faults.uninstall()


def _fleet(tmp_path, name, clock=None, metrics=None, n_replicas=3,
           engine_kw=None, **fleet_kw):
    stages, _ = _model()
    kw = dict(engine_kw or {})
    kw.setdefault("n_slots", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 3)
    if clock is not None:
        kw["clock"] = clock
        fleet_kw["clock"] = clock
    if metrics is not None:
        kw["metrics"] = metrics
        fleet_kw["metrics"] = metrics
    return ServeFleet(engine_factory(stages, CFG, **kw),
                      os.path.join(str(tmp_path), name),
                      n_replicas=n_replicas, journal_sync=False,
                      **fleet_kw)


_SPECS = [
    dict(prompt_seed=1, prompt_len=5, max_new_tokens=8, seed=11),
    dict(prompt_seed=2, prompt_len=9, max_new_tokens=6, seed=12,
         temperature=0.8, top_k=5),
    dict(prompt_seed=3, prompt_len=3, max_new_tokens=7, seed=13),
    dict(prompt_seed=4, prompt_len=7, max_new_tokens=5, seed=14,
         temperature=1.1, top_k=4),
]


def _fixed_run(tmp_path, name, chaos, **fleet_kw):
    """The mixed workload (greedy + sampled, varied prompt lengths) over a
    3-replica fleet — optionally under a chaos schedule. Returns the
    fleet and each request's final tokens in rid order."""
    if chaos:
        faults.install(faults.FaultPlan.parse(chaos))
    fleet = _fleet(tmp_path, name, **fleet_kw)
    handles = []
    for s in _SPECS:
        s = dict(s)
        prompt = _prompt(s.pop("prompt_len"), s.pop("prompt_seed"))
        handles.append(fleet.submit(prompt, **s))
    fleet.drain()
    fleet.close()
    faults.uninstall()
    return fleet, [list(h.tokens) for h in handles]


# ---------------------------------------------------------------------------
# bit-exact cross-replica migration


def test_replica_loss_migrates_bitexact():
    """THE acceptance pin: a whole replica killed mid-decode migrates its
    in-flight requests onto the survivors from its journal alone, and
    every stream equals the uninterrupted fleet run's — which equals each
    request's solo decode."""
    import tempfile

    stages, params = _model()
    base_dir = tempfile.TemporaryDirectory()
    kill_dir = tempfile.TemporaryDirectory()
    _, base = _fixed_run(base_dir.name, "b", None)
    fleet, killed = _fixed_run(kill_dir.name, "k",
                               "replica-kill@fleet.tick=3")
    assert fleet.replica_losses == 1 and fleet.migrations >= 1
    assert killed == base
    for toks, s in zip(killed, _SPECS):
        np.testing.assert_array_equal(
            toks, _solo(stages, params,
                        _prompt(s["prompt_len"], s["prompt_seed"]),
                        s["max_new_tokens"], s["seed"],
                        temperature=s.get("temperature", 0.0),
                        top_k=s.get("top_k")))
    assert all(r.state == DONE for r in fleet.requests.values())
    base_dir.cleanup()
    kill_dir.cleanup()


def test_double_replica_loss_bitexact(tmp_path):
    """Two replicas die at the same fleet tick: the first loss migrates
    onto a replica the second loss then kills — the adoptee recovers AGAIN
    from the adopter's journal (the snap record makes it self-contained)
    and the streams still match the uninterrupted run."""
    _, base = _fixed_run(tmp_path / "base", "b", None)
    fleet, killed = _fixed_run(tmp_path / "kill", "k",
                               "replica-kill@fleet.tick=3,times=2")
    assert fleet.replica_losses == 2
    assert fleet.migrations >= 2
    assert killed == base


def test_replica_loss_during_another_replicas_recovery(tmp_path):
    """An engine-crash puts one replica into its post-recovery re-prefill
    (out of rotation, restart consumed); a replica-kill lands on ANOTHER
    replica one tick later — migration routes around the recovering
    replica and every stream stays bit-exact."""
    _, base = _fixed_run(tmp_path / "base", "b", None)
    fleet, crashed = _fixed_run(
        tmp_path / "kill", "k",
        "engine-crash@serve.tick=3;replica-kill@fleet.tick=4,rank=1")
    assert fleet.replica_losses == 1
    assert sum(r.supervisor.restarts for r in fleet.replicas) == 1
    assert crashed == base


def test_adopter_crash_after_migration_bitexact(tmp_path):
    """The adopting replica's journal is self-contained: crash the
    ADOPTER's engine after it adopted migrated work — supervisor-level
    journal recovery replays the snap record plus the tokens appended
    after it, and the streams still equal the uninterrupted run's."""
    _, base = _fixed_run(tmp_path / "base", "b", None)
    fleet, crashed = _fixed_run(
        tmp_path / "kill", "k",
        "replica-kill@fleet.tick=3;engine-crash@serve.tick,after=8")
    assert fleet.replica_losses == 1
    assert sum(r.supervisor.restarts for r in fleet.replicas) >= 1
    assert crashed == base


def test_fleet_rids_are_globally_unique(tmp_path):
    """The fleet owns the rid space: requests routed to different
    replicas never collide on a rid (journals, traces and metrics join on
    it)."""
    fleet = _fleet(tmp_path, "rids", n_replicas=3,
                   route="round-robin")
    hs = [fleet.submit(_prompt(4, i), max_new_tokens=2, seed=i)
          for i in range(6)]
    assert [h.rid for h in hs] == list(range(6))
    homes = {fleet._home[h.rid] for h in hs}
    assert len(homes) == 3          # round-robin actually spread the load
    fleet.drain()
    fleet.close()
    assert all(h.state == DONE for h in hs)


# ---------------------------------------------------------------------------
# health-aware rotation


def test_crash_recovered_replica_reenters_with_hysteresis(tmp_path):
    """A replica that consumed a restart drains out of rotation the same
    tick and re-enters only after ``health_recover_ticks`` consecutive
    healthy ticks — the drain/re-enter transitions land in the
    replica_log."""
    faults.install(faults.FaultPlan.parse("engine-crash@serve.tick=2"))
    fleet = _fleet(tmp_path, "hyst", n_replicas=2,
                   health_recover_ticks=3)
    for s in _SPECS:
        s = dict(s)
        fleet.submit(_prompt(s.pop("prompt_len"), s.pop("prompt_seed")),
                     **s)
    fleet.drain()
    fleet.close()
    faults.uninstall()
    events = [(e["event"], e["replica"]) for e in fleet.replica_log]
    assert ("drain", 0) in events and ("re-enter", 0) in events
    drain_t = next(e["tick"] for e in fleet.replica_log
                   if e["event"] == "drain")
    reenter_t = next(e["tick"] for e in fleet.replica_log
                     if e["event"] == "re-enter")
    assert reenter_t - drain_t >= 3          # the hysteresis actually held
    assert all(r.state == DONE for r in fleet.requests.values())


def test_restart_budget_exhaustion_is_a_replica_loss(tmp_path):
    """A replica whose supervisor exhausts its restart budget is a LOST
    replica, not a fleet crash: its in-flight work migrates and the run
    completes."""
    # every tick of replica 0's engine crashes; with max_restarts=1 the
    # second crash exhausts its budget and the fleet absorbs the loss
    faults.install(faults.FaultPlan.parse(
        "engine-crash@serve.tick,times=2"))
    fleet = _fleet(tmp_path, "budget", n_replicas=2, max_restarts=1)
    h = fleet.submit(_prompt(5, 1), max_new_tokens=4, seed=21)
    fleet.drain()
    fleet.close()
    faults.uninstall()
    assert fleet.replica_losses == 1 and fleet.n_alive == 1
    assert h.state == DONE
    stages, params = _model()
    np.testing.assert_array_equal(
        h.tokens, _solo(stages, params, h.prompt, 4, 21))


# ---------------------------------------------------------------------------
# routing: hot-prefix skew (exact pins)


def test_hot_prefix_affinity_beats_round_robin_pinned():
    """The hot-prefix-skew scenario on both routing policies: affinity
    concentrates the shared prefix on one replica (17 prefix-share hits —
    every request after the first) while round-robin re-prefills it on
    every replica (5 hits) — strictly above, on exact pinned numbers."""
    stages, _ = _model()
    aff = run_scenario("hot-prefix-skew", stages, CFG)
    rr = run_scenario("hot-prefix-skew", stages, CFG, route="round-robin")
    assert aff["slo_ok"] is True and rr["completed"] == 18
    assert aff["prefix_hit_blocks"] == 17
    assert rr["prefix_hit_blocks"] == 5
    assert aff["prefix_hit_blocks"] > rr["prefix_hit_blocks"]
    assert aff["fleet"]["affinity_hits"] == 17
    assert rr["fleet"]["affinity_hits"] == 0


def test_affinity_routes_to_prefix_holder(tmp_path):
    """Unit form of the affinity signal: once a replica registered a
    prompt's blocks, a request sharing that prefix routes to THAT replica
    even when another is less loaded."""
    clock = VirtualClock(0.001)
    fleet = _fleet(tmp_path, "aff", clock=clock, n_replicas=2,
                   engine_kw={"n_slots": 2, "block_size": 4,
                              "prefill_chunk": None})
    p = _prompt(8, 7)
    h0 = fleet.submit(p, max_new_tokens=2, seed=1)
    fleet.drain()                     # registers p's blocks on h0's home
    h1 = fleet.submit(np.concatenate([p, _prompt(3, 8)]),
                      max_new_tokens=2, seed=2)
    assert fleet._home[h1.rid] == fleet._home[h0.rid]
    fleet.drain()
    fleet.close()


# ---------------------------------------------------------------------------
# autoscaler: the diurnal trajectory (exact pins)


def test_diurnal_autoscale_trajectory_pinned():
    """The fleet-autoscale-diurnal scenario walks the whole autoscaler
    state machine in one virtual-clock run, and the trajectory is EXACT:
    scale-out to 3 at the first peak (ticks 33/39), drain-then-retire
    back to 1 in the trough (tick 68), scale-out again at the second
    peak (ticks 85/87)."""
    stages, _ = _model()
    report = run_scenario("fleet-autoscale-diurnal", stages, CFG)
    assert report["slo_ok"] is True
    assert report["completed"] == 50
    log = [(e["event"], e["replica"], e["tick"], e["alive"])
           for e in report["fleet"]["replica_log"]]
    assert log == [
        ("scale-out", 1, 33, 2),
        ("scale-out", 2, 39, 3),
        ("retire", 2, 68, 2),
        ("retire", 1, 68, 1),
        ("scale-out", 3, 85, 2),
        ("scale-out", 4, 87, 3),
    ]
    assert report["fleet"]["scale_outs"] == 4
    assert report["fleet"]["retired"] == 2


def test_budget_exhaustion_during_admission_is_a_replica_loss(tmp_path):
    """An admission crash (serve.admit) on a replica whose restart budget
    is already spent must lose THAT replica and migrate the journaled
    submission onto a survivor — never crash the whole fleet out of
    submit()."""
    stages, params = _model()
    faults.install(faults.FaultPlan.parse("engine-crash@serve.admit=1"))
    fleet = _fleet(tmp_path, "admitloss", n_replicas=2, max_restarts=0)
    h0 = fleet.submit(_prompt(5, 1), max_new_tokens=4, seed=21)
    h1 = fleet.submit(_prompt(4, 2), max_new_tokens=4, seed=22)  # crashes
    faults.uninstall()
    assert fleet.replica_losses == 1 and fleet.n_alive == 1
    assert h1.rid == 1 and h1.state == QUEUED
    fleet.drain()
    fleet.close()
    for h in (h0, h1):
        assert h.state == DONE
        np.testing.assert_array_equal(
            h.tokens, _solo(stages, params, h.prompt, 4, h.seed))


def test_wall_clock_idle_retire_anchored_at_observation(tmp_path):
    """Regression: on a wall-style clock (absolute monotonic values, not
    a virtual clock starting at 0) the autoscaler must NOT retire the
    initial replicas the moment it learns the first real timestamp —
    idleness is anchored at the first idle OBSERVATION, so the clock base
    cancels out."""
    class OffsetClock(VirtualClock):
        def __init__(self):
            super().__init__(0.001)
            self._t = 50_000.0               # monotonic-style absolute base

    clock = OffsetClock()
    fleet = _fleet(tmp_path, "wall", clock=clock, n_replicas=2,
                   autoscale=AutoscalePolicy(min_replicas=1,
                                             max_replicas=2,
                                             retire_idle_s=0.5))
    h = fleet.submit(_prompt(4, 1), max_new_tokens=2, seed=1,
                     arrival_time=50_000.5)
    # the huge absolute timestamp must not read as 50k seconds of idleness
    assert fleet.n_alive == 2
    fleet.drain()
    assert h.state == DONE
    # genuine idleness still retires: observe idle, then advance past the
    # threshold via a later arrival
    h2 = fleet.submit(_prompt(4, 2), max_new_tokens=2, seed=2,
                      arrival_time=50_010.0)
    assert fleet.n_alive == 1
    fleet.drain()
    fleet.close()
    assert h2.state == DONE


def test_autoscale_floor_replaces_lost_replica(tmp_path):
    """An autoscaled fleet losing a replica below min_replicas replaces
    it on the next tick — the floor binds on the loss side, not only
    against retirement."""
    faults.install(faults.FaultPlan.parse("replica-kill@fleet.tick=2"))
    fleet = _fleet(tmp_path, "floor", n_replicas=2,
                   autoscale=AutoscalePolicy(min_replicas=2,
                                             max_replicas=3))
    h = fleet.submit(_prompt(5, 1), max_new_tokens=4, seed=1)
    fleet.drain()
    fleet.close()
    faults.uninstall()
    assert fleet.replica_losses == 1 and fleet.n_alive == 2
    assert [(e["event"], e["replica"]) for e in fleet.replica_log] == \
        [("loss", 0), ("replace", 2)]
    assert h.state == DONE


def test_fleet_replica_restart_writes_tagged_postmortem(tmp_path):
    """An in-place replica restart under a fleet keeps the PR-11 crash
    forensics: the bundle lands in the shared dir with the replica tag in
    its name, so N replicas never overwrite each other's bundles."""
    faults.install(faults.FaultPlan.parse("engine-crash@serve.tick=2"))
    fleet = _fleet(tmp_path, "pm", n_replicas=2,
                   postmortem_dir=str(tmp_path))
    h = fleet.submit(_prompt(5, 1), max_new_tokens=4, seed=1)
    fleet.drain()
    fleet.close()
    faults.uninstall()
    assert h.state == DONE
    bundles = sorted(p.name for p in tmp_path.glob("postmortem-*.json"))
    assert bundles and all("-r" in b for b in bundles), bundles


# ---------------------------------------------------------------------------
# the fleet-replica-loss scenario gate


def test_fleet_replica_loss_scenario_gate():
    """The catalog entry: all requests complete through the loss, at
    least one migration actually happened, SLOs held."""
    stages, _ = _model()
    report = run_scenario("fleet-replica-loss", stages, CFG)
    assert report["slo_ok"] is True
    assert report["completed"] == 16
    assert report["fleet"]["replica_losses"] == 1
    assert report["fleet"]["migrations"] >= 1


def test_fleet_scenario_gate_requires_migrations():
    """The vacuous-pass guard: the same scenario with its fault stripped
    must FAIL the gate (min_migrations unmet), not pass because nothing
    went wrong."""
    stages, _ = _model()
    quiet = dataclasses.replace(SCENARIOS["fleet-replica-loss"],
                                name="fleet-no-kill", chaos=None)
    report = run_scenario(quiet, stages, CFG)
    assert report["completed"] == 16          # nothing wrong with the run
    assert report["fleet"]["migrations"] == 0
    assert report["slo_ok"] is False          # the gate caught the silence


def test_fleet_scenario_emits_gateable_record(tmp_path):
    """With an outdir, the scenario lands its fleet block in the
    metrics.jsonl record CI re-asserts from, and the per-replica journals
    sit next to it."""
    stages, _ = _model()
    report = run_scenario("fleet-replica-loss", stages, CFG,
                          outdir=str(tmp_path))
    assert report["slo_ok"] is True
    recs = [json.loads(ln) for ln in open(tmp_path / "metrics.jsonl")]
    scen = [r for r in recs if r.get("kind") == "scenario"][-1]
    assert scen["fleet"]["migrations"] >= 1
    assert scen["fleet"]["replica_losses"] == 1
    serve = [r for r in recs if r.get("kind") == "serve"][-1]
    assert serve["fleet_migrations"] == scen["fleet"]["migrations"]
    journals = sorted(p.name for p in tmp_path.glob(
        "journal-fleet-replica-loss-r*.jsonl"))
    assert len(journals) == 3
    prom = open(tmp_path / "metrics.prom").read()
    for name in ("serve_fleet_replicas", "serve_fleet_migrations_total",
                 "serve_route_affinity_hits_total"):
        assert f"# HELP {name}" in prom, name


# ---------------------------------------------------------------------------
# journal rotation (satellite)


def test_journal_rotation_recovery_byte_identical(tmp_path):
    """The satellite pin: rotate() compacts a real run's journal to snap
    records, reclaims bytes, and recovery from the rotated journal is
    byte-identical to recovery from the unrotated one."""
    stages, _ = _model()
    path = str(tmp_path / "rot.jsonl")
    sup = ServeSupervisor(
        engine_factory(stages, CFG, n_slots=2, block_size=4,
                       prefill_chunk=3),
        RequestJournal(path, sync=False))
    h1 = sup.submit(_prompt(5, 1), max_new_tokens=8, seed=31)
    h2 = sup.submit(_prompt(7, 2), max_new_tokens=6, seed=32,
                    temperature=0.9, top_k=4)
    for _ in range(6):
        sup.step()
    assert 0 < len(h1.tokens) < 8            # genuinely mid-flight

    def snap_key(snaps):
        return {rid: (r.state, r.finish_reason, list(r.tokens),
                      None if r.key_data is None
                      else [int(x) for x in np.asarray(r.key_data)],
                      None if r.draft_key_data is None
                      else [int(x) for x in np.asarray(r.draft_key_data)],
                      r.submit_time, r.first_token_time, r.done_time,
                      [int(x) for x in np.asarray(r.prompt)],
                      r.max_new_tokens, r.seed, r.temperature, r.top_k)
                for rid, r in snaps.items()}

    before = snap_key(sup.journal.recovered_state())
    pre_bytes = sup.journal.bytes
    reclaimed = sup.journal.rotate()
    assert reclaimed > 0 and sup.journal.bytes < pre_bytes
    assert snap_key(sup.journal.recovered_state()) == before
    # the live supervisor keeps appending cleanly after the rotation, and
    # a cold restart over the rotated journal continues bit-exact
    sup.drain()
    sup.close()
    done = [list(h1.tokens), list(h2.tokens)]
    sup2 = ServeSupervisor(
        engine_factory(stages, CFG, n_slots=2, block_size=4,
                       prefill_chunk=3),
        RequestJournal(path, sync=False))
    assert not sup2.busy                     # everything recovered DONE
    assert [list(sup2.requests[h1.rid].tokens),
            list(sup2.requests[h2.rid].tokens)] == done
    sup2.close()


def test_journal_rotation_shrinks_long_history(tmp_path):
    """The motivating case: a long token history compacts to one snap
    line per request — the cold-restart replay stops re-reading every
    token record."""
    stages, _ = _model()
    path = str(tmp_path / "long.jsonl")
    sup = ServeSupervisor(
        engine_factory(stages, CFG, n_slots=2, block_size=4,
                       prefill_chunk=3),
        RequestJournal(path, sync=False))
    for i in range(4):
        sup.submit(_prompt(4, i), max_new_tokens=16, seed=40 + i)
    sup.drain()
    n_events_before = len(read_journal(path)[0])
    reclaimed = sup.journal.rotate()
    events_after = read_journal(path)[0]
    assert reclaimed > 0
    assert len(events_after) == 4            # one snap per request
    assert {e["ev"] for e in events_after} == {"snap"}
    assert n_events_before > 4 * 16          # it really was a long history
    sup.close()


# ---------------------------------------------------------------------------
# overload-policy aliasing (satellite bugfix pin)


def test_token_bucket_not_shared_across_replicas(tmp_path):
    """ONE OverloadPolicy instance shared by a two-replica fleet: replica
    A's token-bucket debit must not appear in replica B's. Round-robin
    routing pins which replica each submission lands on."""
    clock = VirtualClock(0.001)
    policy = OverloadPolicy(class_rates={"batch": (0.1, 1)})
    fleet = _fleet(tmp_path, "buckets", clock=clock, n_replicas=2,
                   route="round-robin", overload=policy)
    a = fleet.submit(_prompt(4, 1), max_new_tokens=2, seed=1, cls="batch",
                     arrival_time=0.001)
    b = fleet.submit(_prompt(4, 2), max_new_tokens=2, seed=2, cls="batch",
                     arrival_time=0.002)
    assert fleet._home[a.rid] != fleet._home[b.rid]
    # A's burst-1 bucket is spent on a; b landed on B's OWN full bucket
    assert a.state == QUEUED and b.state == QUEUED
    # a third arrival cycles back to replica A, whose bucket IS spent
    c = fleet.submit(_prompt(4, 3), max_new_tokens=2, seed=3, cls="batch",
                     arrival_time=0.003)
    assert c.state == SHED and c.finish_reason == "class"
    fleet.drain()
    fleet.close()


def test_overload_policy_class_rates_defensively_copied():
    """The aliasing fix itself: the policy snapshots class_rates at
    construction — mutating the caller's dict afterwards cannot retune
    (or couple) the replicas that share the policy."""
    rates = {"batch": (1.0, 2)}
    policy = OverloadPolicy(class_rates=rates)
    rates["batch"] = (1000.0, 99)
    rates["new"] = (1.0, 1)
    assert policy.class_rates == {"batch": (1.0, 2.0)}


# ---------------------------------------------------------------------------
# recover_state: shed/cancelled interleaved with restarts (satellite)


def test_recover_state_shed_and_cancelled_stay_shed(tmp_path):
    """The fleet re-admit path feeds recover_state journals with SHED and
    cancelled records interleaved with restarts — shed requests must stay
    shed, never re-admitted."""
    path = str(tmp_path / "shed.jsonl")
    j = RequestJournal(path, sync=False)
    base = dict(temp=0.0, top_k=None, top_p=None, seed=0, cls=None,
                prio=0, ttft_dl=None, dl=None)
    j.log_submit(rid=0, prompt=[1, 2], max_new=8, eos=None, t=1.0, **base)
    j.append({"ev": "tok", "rid": 0, "tok": 5, "kd": [1, 1], "dkd": None})
    j.log_shed(rid=0, reason="deadline", t=1.5, tick=2)
    j.log_restart(1, False, "EngineCrash", tick=3)
    j.log_submit(rid=1, prompt=[3, 4], max_new=4, eos=None, t=2.0, **base)
    j.log_shed(rid=1, reason="cancelled", t=2.2, tick=4)
    j.log_submit(rid=2, prompt=[5, 6], max_new=4, eos=None, t=2.5, **base)
    j.append({"ev": "tok", "rid": 2, "tok": 7, "kd": [2, 2], "dkd": None})
    j.log_restart(2, False, "ReplicaLost", tick=5)
    j.log_submit(rid=3, prompt=[7], max_new=2, eos=None, t=3.0, **base)
    j.log_shed(rid=3, reason="backpressure", t=3.1, tick=6)
    j.close()
    snap = recover_state(read_journal(path)[0])
    assert snap[0].state == SHED and snap[0].finish_reason == "deadline"
    assert snap[0].tokens == [5]             # partial stream kept readable
    assert snap[1].state == SHED and snap[1].finish_reason == "cancelled"
    assert snap[3].state == SHED
    assert snap[2].state == QUEUED and snap[2].tokens == [7]
    # end to end: a supervisor over this journal re-admits ONLY rid 2
    stages, _ = _model()
    sup = ServeSupervisor(
        engine_factory(stages, CFG, n_slots=2, block_size=4,
                       prefill_chunk=3),
        RequestJournal(path, sync=False))
    assert sorted(sup._open) == [2]
    assert sup.requests[0].state == SHED
    assert sup.requests[1].state == SHED
    assert sup.requests[3].state == SHED
    sup.drain()
    assert sup.requests[2].state == DONE
    assert sup.requests[0].state == SHED     # still shed after the drain
    sup.close()


# ---------------------------------------------------------------------------
# validation + fault plumbing


def test_router_and_autoscale_validation():
    with pytest.raises(ValueError, match="route policy"):
        FleetRouter("fastest")
    with pytest.raises(ValueError, match="min_replicas"):
        AutoscalePolicy(min_replicas=0)
    with pytest.raises(ValueError, match="max_replicas"):
        AutoscalePolicy(min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError, match="retire_idle_s"):
        AutoscalePolicy(retire_idle_s=0)
    with pytest.raises(ValueError, match="kv_frac_high"):
        AutoscalePolicy(kv_frac_high=1.5)


def test_fleet_constructor_validation(tmp_path):
    with pytest.raises(ValueError, match="n_replicas"):
        _fleet(tmp_path, "v1", n_replicas=0)
    with pytest.raises(ValueError, match="autoscale bounds"):
        _fleet(tmp_path, "v2", n_replicas=5,
               autoscale=AutoscalePolicy(min_replicas=1, max_replicas=3))


def test_scenario_fleet_field_validation():
    from simple_distributed_machine_learning_tpu.resilience.scenarios import (
        Scenario,
    )
    base = SCENARIOS["fleet-replica-loss"]
    with pytest.raises(ValueError, match="drop supervised"):
        dataclasses.replace(base, supervised=True)
    with pytest.raises(ValueError, match="fleet knobs"):
        dataclasses.replace(SCENARIOS["steady"], min_migrations=1)
    with pytest.raises(ValueError, match="route"):
        dataclasses.replace(base, route="fastest")
    assert isinstance(base, Scenario)


def test_replica_kill_fault_kind_plumbing():
    """The new kind/site parse and the bare-maybe_fire effect: a plan
    outside a fleet still fails loudly instead of silently no-opping."""
    from simple_distributed_machine_learning_tpu.resilience.faults import (
        ReplicaLost,
    )
    plan = faults.FaultPlan.parse("replica-kill@fleet.tick=2,rank=1")
    [spec] = plan.specs
    assert (spec.kind, spec.site, spec.step, spec.rank) == \
        ("replica-kill", "fleet.tick", 2, 1)
    faults.install(plan)
    assert faults.maybe_fire("fleet.tick", step=1, rank=1) == []
    with pytest.raises(ReplicaLost):
        faults.maybe_fire("fleet.tick", step=2, rank=1)
    faults.uninstall()
    with pytest.raises(ValueError, match="unknown fault site"):
        faults.FaultPlan.parse("replica-kill@fleet.tock=2")
    # the kind<->site pairing: any crossed combination would match and
    # count as fired without ever taking effect — refused at parse time
    with pytest.raises(ValueError, match="only interprets"):
        faults.FaultPlan.parse("engine-crash@fleet.tick=2")
    with pytest.raises(ValueError, match="only pairs with"):
        faults.FaultPlan.parse("replica-kill@serve.tick=2")
    # ...but the secondary interpreting site (the adopt/seal race probe
    # in _handoff_step) is a valid pairing
    [spec] = faults.FaultPlan.parse("replica-kill@fleet.handoff,rank=0").specs
    assert (spec.kind, spec.site, spec.rank) == \
        ("replica-kill", "fleet.handoff", 0)


# ---------------------------------------------------------------------------
# bench + CLI surface


def test_bench_fleet_availability_under_replica_loss():
    """The bench fleet availability row: a replica loss costs a
    migration, never a completion — availability pins at 1.0."""
    import jax as _jax

    from bench import _measure_fleet_availability
    from simple_distributed_machine_learning_tpu.models.gpt import (
        make_gpt_stages as _mk,
    )

    stages = _mk(_jax.random.key(0), CFG, n_stages=1)[0]
    [row] = _measure_fleet_availability(stages, CFG, n_requests=8,
                                        max_new=6, prompt_lens=(4, 8),
                                        block_size=4, slots=2)
    assert row["availability"] == 1.0 and row["completed"] == 8
    assert row["replica_losses"] == 1 and row["faults_fired"] == 1
    assert row["migrations"] >= 1 and row["shed_deadline"] == 0


def test_serve_replicas_cli(tmp_path, capsys):
    """--serve-replicas end to end: a replica killed mid-serve migrates
    its work, every request completes, exit 0, and the fleet counters
    land in the serve metrics record + Prometheus exposition."""
    from simple_distributed_machine_learning_tpu.cli import main

    tele = str(tmp_path / "tele")
    main(["--rank", "0", "--world_size", "1", "--model", "gpt",
          "--serve-sim", "6", "--serve-rate", "100", "--serve-slots", "2",
          "--serve-max-new", "4", "--serve-block-size", "4",
          "--serve-prefill-chunk", "3", "--serve-replicas", "3",
          "--serve-chaos", "replica-kill@fleet.tick=4",
          "--telemetry-dir", tele])
    out = capsys.readouterr().out
    assert "| serve: 6/6 requests completed" in out
    assert "1 replica loss(es)" in out
    recs = [json.loads(ln) for ln in
            open(os.path.join(tele, "metrics.jsonl"))]
    r = [x for x in recs if x.get("kind") == "serve"][-1]
    assert r["completed"] == 6
    assert r["fleet_replica_losses"] == 1 and r["fleet_migrations"] >= 1
    prom = open(os.path.join(tele, "metrics.prom")).read()
    assert "serve_fleet_replica_losses_total 1" in prom
    journals = sorted(f for f in os.listdir(tele)
                      if f.startswith("journal-r"))
    assert len(journals) == 3


def test_serve_fleet_cli_flag_validation():
    from simple_distributed_machine_learning_tpu.cli import main

    base = ["--rank", "0", "--world_size", "1", "--model", "gpt",
            "--serve-sim", "2"]
    with pytest.raises(SystemExit, match="serve-replicas"):
        main(base + ["--serve-replicas", "-1"])
    with pytest.raises(SystemExit, match="serve-autoscale"):
        main(base + ["--serve-autoscale", "1,3"])
    with pytest.raises(SystemExit, match="bad --serve-autoscale"):
        main(base + ["--serve-replicas", "2", "--serve-autoscale", "x"])
    with pytest.raises(SystemExit, match="outside the"):
        main(base + ["--serve-replicas", "5", "--serve-autoscale", "1,3"])
    with pytest.raises(SystemExit, match="needs --serve-replicas"):
        # a fleet.tick chaos spec without a fleet would never fire: the
        # drill must refuse, not pass vacuously
        main(base + ["--serve-chaos", "replica-kill@fleet.tick=5"])
    with pytest.raises(SystemExit, match="serve-route needs"):
        # a non-default route without a fleet would be silently ignored
        main(base + ["--serve-route", "round-robin"])
