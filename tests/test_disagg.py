"""Disaggregated prefill/decode pools + tiered host-offload KV cache.

The load-bearing claims (ISSUE 17 acceptance):

- **Bit-exact prefill→decode handoff** — a disaggregated fleet serves the
  mixed greedy/sampled workload token-identical to the symmetric fleet
  (which equals each request's solo decode), across paged f32 AND int8
  caches, and with a decode-replica kill racing the handoffs (the
  handed-off request migrates AGAIN off the dead adopter's journal).
- **Journal grammar** (satellite) — ``snap`` records carry a ``why``
  (``"failure"`` vs ``"handoff"``), the terminal ``handoff`` event makes
  the SOURCE journal never re-admit a handed-off request, and journals
  written before the field (``why`` stripped) still recover identically.
- **Async prefetch** — a routing-time affinity hit on a host-resident
  prefix starts the upload AT SUBMIT; a request boarding before the
  upload completes must BLOCK (never read half-uploaded rows) and its
  final stream equals the solo decode.
- **Analyzer drift == 0** — ``predict_host_kv_bytes`` /
  ``predict_transfer_bytes`` equal the live host-tier gauges on every
  tick of a disaggregated+offload run, observed on at least the
  mid-handoff, post-demote and prefetch-in-flight shapes.
- **Scenario gates, both sides pinned** — disagg TTFT p95 beats the
  symmetric fleet on the prefill-heavy mix; the host tier's prefix-hit
  blocks strictly exceed the HBM-only fleet's under cache churn; the
  decode-replica kill mid-handoff still completes everything.
"""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from simple_distributed_machine_learning_tpu.analysis.programs import (
    engine_spec,
    predict_host_kv_bytes,
    predict_transfer_bytes,
)
from simple_distributed_machine_learning_tpu.models.gpt import (
    GPTConfig,
    make_cached_decoder,
    make_gpt_stages,
)
from simple_distributed_machine_learning_tpu.resilience import faults
from simple_distributed_machine_learning_tpu.resilience.scenarios import (
    SCENARIOS,
    run_scenario,
)
from simple_distributed_machine_learning_tpu.serve import (
    RequestJournal,
    ServeFleet,
    ServeSupervisor,
    engine_factory,
)
from simple_distributed_machine_learning_tpu.serve.flight import (
    FlightRecorder,
)
from simple_distributed_machine_learning_tpu.serve.journal import (
    read_journal,
    recover_state,
)
from simple_distributed_machine_learning_tpu.serve.request import DONE

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


def _fleet(tmp_path, name, n_replicas=3, engine_kw=None, **fleet_kw):
    stages, _ = _model()
    kw = dict(engine_kw or {})
    kw.setdefault("n_slots", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 3)
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
    """The mixed greedy/sampled workload over a 3-replica fleet —
    symmetric or disaggregated, optionally under chaos. Returns the
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
# bit-exact prefill->decode handoff


def test_handoff_bitexact_vs_symmetric_f32(tmp_path):
    """THE tentpole pin (paged f32, greedy + sampled): every request
    crosses the prefill->decode handoff and its stream equals the
    symmetric single-pool fleet's — which equals the solo decode."""
    stages, params = _model()
    _, base = _fixed_run(tmp_path / "sym", "b", None)
    fleet, moved = _fixed_run(tmp_path / "dis", "d", None,
                              prefill_replicas=1)
    assert fleet.disaggregated and fleet.handoffs == len(_SPECS)
    assert {r.role for r in fleet.replicas} == {"prefill", "decode"}
    assert moved == base
    for toks, s in zip(moved, _SPECS):
        np.testing.assert_array_equal(
            toks, _solo(stages, params,
                        _prompt(s["prompt_len"], s["prompt_seed"]),
                        s["max_new_tokens"], s["seed"],
                        temperature=s.get("temperature", 0.0),
                        top_k=s.get("top_k")))
    assert all(r.state == DONE for r in fleet.requests.values())


def test_handoff_bitexact_vs_symmetric_int8(tmp_path):
    """The quantized twin: int8 paged caches hand off bit-exact too (the
    snapshot replays tokens, not cache bytes, so the adopted stream's
    quantization state is rebuilt identically)."""
    kw = dict(cache_dtype="int8")
    _, base = _fixed_run(tmp_path / "sym", "b", None, engine_kw=kw)
    fleet, moved = _fixed_run(tmp_path / "dis", "d", None,
                              prefill_replicas=1, engine_kw=kw)
    assert fleet.handoffs == len(_SPECS)
    assert moved == base


def test_handoff_racing_replica_loss_bitexact(tmp_path):
    """A decode replica dies while handoffs are landing on it: the
    handed-off requests migrate AGAIN off the dead adopter's journal
    (the handoff snap made it self-contained) and every stream still
    equals the symmetric uninterrupted run's."""
    _, base = _fixed_run(tmp_path / "sym", "b", None)
    fleet, moved = _fixed_run(tmp_path / "dis", "d",
                              "replica-kill@fleet.tick=3,rank=1",
                              prefill_replicas=1)
    assert fleet.handoffs >= len(_SPECS)          # every request moved
    assert fleet.replica_losses == 1 and fleet.migrations >= 1
    assert moved == base


# ---------------------------------------------------------------------------
# journal grammar (satellite): snap why + terminal handoff + tolerance


def test_handoff_journal_grammar_and_old_journal_tolerance(tmp_path):
    """Three pins on one run's journals: (1) the SOURCE journal's
    terminal ``handoff`` event means recovery never re-admits a
    handed-off request (no double-serve if the prefill replica dies
    later); (2) the adopter's snap records say ``why: handoff`` (vs
    ``failure`` for loss migration); (3) stripping ``why`` — the
    pre-field journal format — recovers byte-identically modulo the
    cause annotation."""
    fleet, _ = _fixed_run(tmp_path, "g", None, prefill_replicas=1)
    src_path = fleet.replicas[0].journal_path          # the prefill pool
    events, _ = read_journal(src_path)
    handoffs = [e for e in events if e["ev"] == "handoff"]
    assert len(handoffs) == len(_SPECS)
    assert recover_state(events) == {}      # terminal: nothing re-admits

    # the adopters' journals carry the cause
    snaps = []
    for rep in fleet.replicas[1:]:
        evs, _ = read_journal(rep.journal_path)
        snaps += [e for e in evs if e["ev"] == "snap"]
    assert snaps and all(e["why"] == "handoff" for e in snaps)
    rec = recover_state(snaps + [])
    assert all(r.snap_reason == "handoff" for r in rec.values())

    # reason-less old journals: strip the field, recovery still parses
    # and carries the same streams (snap_reason degrades to None)
    stripped = [{k: v for k, v in e.items() if k != "why"} for e in snaps]
    old = recover_state(stripped)
    assert set(old) == set(rec)
    for rid in rec:
        assert list(old[rid].tokens) == list(rec[rid].tokens)
        assert old[rid].snap_reason is None


def test_failure_migration_snap_says_failure(tmp_path):
    """The other half of the cause split: a plain (symmetric) replica
    loss stamps ``why: failure`` on the adoption snaps."""
    fleet, _ = _fixed_run(tmp_path, "f", "replica-kill@fleet.tick=3")
    assert fleet.replica_losses == 1 and fleet.migrations >= 1
    whys = []
    for rep in fleet.replicas:
        if not os.path.exists(rep.journal_path):
            continue
        evs, _ = read_journal(rep.journal_path)
        whys += [e["why"] for e in evs if e["ev"] == "snap"]
    assert whys and set(whys) == {"failure"}


# ---------------------------------------------------------------------------
# async prefetch: routing-time start, boarding blocks until the upload lands


def _offload_fleet(tmp_path, name, n_replicas=1, prefill_replicas=0,
                   prefetch_ticks=3):
    return _fleet(tmp_path, name, n_replicas=n_replicas,
                  prefill_replicas=prefill_replicas,
                  engine_kw=dict(n_slots=2, block_size=4, n_blocks=6,
                                 max_len=24, prefill_chunk=4,
                                 host_cache_blocks=8,
                                 prefetch_ticks=prefetch_ticks))


def test_prefetch_on_affinity_hit_starts_at_submit_and_blocks_boarding(
        tmp_path):
    """The satellite pin: demote a hot prefix to host, re-submit a
    request carrying it — the upload starts AT routing time (in-flight
    blocks visible before any tick), the request does NOT board while
    the upload flies, and once landed its stream equals the solo decode
    (a stale read would diverge)."""
    stages, params = _model()
    fleet = _offload_fleet(tmp_path, "p", prefetch_ticks=3)
    pool = fleet.replicas[0].supervisor.pool
    # 9 tokens: positions 0..7 are cacheable full blocks (the last prompt
    # token always decodes live), so TWO blocks register and demote
    p = _prompt(9, 1)

    # 1) register the prefix in HBM, then churn it out with a
    #    prefix-less scan that needs the whole pool
    fleet.submit(p, max_new_tokens=4, seed=21)
    fleet.drain()
    fleet.submit(_prompt(16, 7), max_new_tokens=8, seed=22)
    fleet.drain()
    st = pool.stats()
    assert st["host_demotes_total"] >= 2    # the prefix lives on host now
    assert pool.host_prefix_len(p) == 8 and pool.shared_prefix_len(p) == 0

    # 2) routing-time prefetch: in flight BEFORE any tick runs
    h = fleet.submit(p, max_new_tokens=4, seed=23)
    st = pool.stats()
    assert st["host_prefetch_hits_total"] == 1
    assert st["host_inflight_blocks"] == 2

    # 3) boarding blocks while the upload flies (prefetch_ticks=3): after
    #    one tick the request has NOT seated and emitted nothing
    fleet.step()
    assert h.slot is None and not h.tokens
    assert pool.stats()["host_inflight_blocks"] == 2
    assert pool.prefetch_blocked(h)

    # 4) drain: the upload lands, the request boards as a prefix HIT on
    #    the promoted blocks and the stream equals the solo decode
    fleet.drain()
    fleet.close()
    st = pool.stats()
    assert st["host_promotes_total"] == 2
    assert st["host_inflight_blocks"] == 0
    np.testing.assert_array_equal(
        h.tokens, _solo(stages, params, p, 4, 23))


def test_prefetch_misses_are_counted_not_fatal(tmp_path):
    """A prompt with no host-resident prefix past the device registry is
    a MISS: counted, no upload, boarding unaffected."""
    fleet = _offload_fleet(tmp_path, "m")
    pool = fleet.replicas[0].supervisor.pool
    assert pool.prefetch(_prompt(8, 9)) is False
    assert pool.stats()["host_prefetch_misses_total"] == 1
    h = fleet.submit(_prompt(8, 9), max_new_tokens=2, seed=31)
    fleet.drain()
    fleet.close()
    assert h.state == DONE and len(h.tokens) == 2


# ---------------------------------------------------------------------------
# analyzer host-tier predictions: drift == 0 on every observed shape


def test_host_tier_analyzer_drift_zero_across_shapes(tmp_path):
    """``predict_host_kv_bytes`` / ``predict_transfer_bytes`` equal the
    live gauges on EVERY tick of a disaggregated+offload run — and the
    run demonstrably passes through all three required shapes:
    mid-handoff, post-demote, and prefetch-in-flight."""
    fleet = _offload_fleet(tmp_path, "a", n_replicas=2,
                           prefill_replicas=1, prefetch_ticks=2)
    seen = {"mid_handoff": False, "post_demote": False,
            "prefetch_inflight": False}

    def check():
        for rep in fleet.replicas:
            if not rep.alive:
                continue
            pool = rep.supervisor.pool
            spec = engine_spec(rep.supervisor.engine)
            st = pool.stats()
            assert predict_host_kv_bytes(spec, st["host_blocks"]) \
                == st["host_bytes_resident"]
            moves = (st["host_demotes_total"] + st["host_promotes_total"])
            assert predict_transfer_bytes(spec, moves) \
                == st["host_transfer_bytes_total"]
            if st["host_demotes_total"]:
                seen["post_demote"] = True
            if st["host_inflight_blocks"]:
                seen["prefetch_inflight"] = True

    def run(submits):
        last = fleet.handoffs
        for prompt, max_new, seed in submits:
            fleet.submit(prompt, max_new_tokens=max_new, seed=seed)
            check()
        while fleet.busy:
            fleet.step()
            if fleet.handoffs > last:
                seen["mid_handoff"] = True
                last = fleet.handoffs
            check()

    p = _prompt(8, 1)
    run([(p, 4, 41)])                           # registers the prefix
    run([(_prompt(16, 7), 8, 42)])              # churns it out -> demote
    run([(p, 4, 43)])                           # prefetch-in-flight
    fleet.close()
    assert fleet.handoffs >= 3
    assert all(seen.values()), seen


# ---------------------------------------------------------------------------
# flight-recorder rows (satellite): pool role + host-tier stats per tick


def test_flight_rows_carry_pool_role_and_host_stats(tmp_path):
    """Per-tick forensics rows stamp which pool the replica serves and
    the full host-tier stats block — a post-mortem can tell WHERE a
    request was and what the offload tier held that tick."""
    fleet = _offload_fleet(tmp_path, "fl", n_replicas=2,
                           prefill_replicas=1)
    for rep in fleet.replicas:
        rep.supervisor.flight = FlightRecorder()
    fleet.submit(_prompt(8, 1), max_new_tokens=4, seed=51)
    fleet.submit(_prompt(16, 7), max_new_tokens=6, seed=52)
    fleet.drain()
    fleet.close()
    roles = {}
    for rep in fleet.replicas:
        rows = rep.supervisor.flight.rows()
        assert rows
        for row in rows:
            assert row["pool_role"] == rep.role
            assert "host_blocks" in row["blocks"]
            assert "host_inflight_blocks" in row["blocks"]
        roles[rep.role] = True
    assert set(roles) == {"prefill", "decode"}


# ---------------------------------------------------------------------------
# scenario gates — exact virtual-clock numbers, BOTH sides pinned


def test_disagg_prefill_heavy_scenario_pinned():
    """The headline TTFT gate: on the bursty prefill-heavy mix the 2+2
    disaggregated fleet's interactive TTFT p95 beats the same-size
    symmetric fleet's by ~3.0x — exact numbers on the virtual clock."""
    stages, _ = _model()
    rep = run_scenario("disagg-prefill-heavy", stages, CFG)
    assert rep["slo_ok"] and rep["completed"] == 16
    assert rep["fleet"]["prefill_replicas"] == 2
    assert rep["fleet"]["handoffs"] == 16
    assert rep["slo"]["interactive"]["ttft_ms_p95"] == 67.719

    sym = dataclasses.replace(SCENARIOS["disagg-prefill-heavy"],
                              name="disagg-symmetric",
                              prefill_replicas=0, min_handoffs=0)
    base = run_scenario(sym, stages, CFG)
    assert base["completed"] == 16
    assert base["slo"]["interactive"]["ttft_ms_p95"] == 203.719
    assert rep["slo"]["interactive"]["ttft_ms_p95"] * 2 \
        < base["slo"]["interactive"]["ttft_ms_p95"]


def test_offload_churn_scenario_pinned(tmp_path):
    """The headline offload gate: under hot-prefix churn the host tier's
    prefix-hit blocks STRICTLY exceed the HBM-only fleet's, with the
    demote/promote/prefetch cycle pinned exactly — plus the gateable
    record and the metric-catalog HELP lines CI re-asserts."""
    stages, _ = _model()
    rep = run_scenario("offload-churn", stages, CFG, outdir=str(tmp_path))
    assert rep["slo_ok"] and rep["completed"] == 24
    ht = rep["host_tier"]
    assert ht == {"host_cache_blocks": 12, "demotes": 66, "promotes": 5,
                  "prefetch_hits": 3, "prefetch_misses": 0,
                  "host_evictions": 53, "transfer_bytes": 145408}

    recs = [json.loads(ln) for ln in open(tmp_path / "metrics.jsonl")]
    serve = [r for r in recs if r.get("kind") == "serve"][-1]
    assert serve["prefix_hit_blocks"] == 16
    assert serve["host_demotes"] == 66 and serve["host_promotes"] == 5
    assert serve["host_transfer_bytes"] == 145408
    assert serve["kv_drift_bytes"] == 0
    prom = open(tmp_path / "metrics.prom").read()
    for name in ("serve_host_blocks", "serve_host_bytes_resident",
                 "serve_host_inflight_blocks", "serve_host_demotes_total",
                 "serve_host_promotes_total", "serve_host_evictions_total",
                 "serve_host_prefetch_hits_total",
                 "serve_host_prefetch_misses_total",
                 "serve_host_transfer_bytes_total"):
        assert f"# HELP {name}" in prom, name

    hbm = dataclasses.replace(SCENARIOS["offload-churn"],
                              name="offload-hbm-only", host_cache_blocks=0,
                              min_host_demotes=0, min_host_prefetch_hits=0)
    base = run_scenario(hbm, stages, CFG, outdir=str(tmp_path / "hbm"))
    assert base["completed"] == 24 and "host_tier" not in base
    recs = [json.loads(ln)
            for ln in open(tmp_path / "hbm" / "metrics.jsonl")]
    bserve = [r for r in recs if r.get("kind") == "serve"][-1]
    assert bserve["prefix_hit_blocks"] == 10     # strictly below 16
    assert serve["prefix_hit_blocks"] > bserve["prefix_hit_blocks"]


def test_handoff_replica_loss_scenario_pinned(tmp_path):
    """The chaos drill: a decode replica dies at fleet tick 6 with
    handoffs in flight — everything completes, the loss migrates, the
    handoff counter and catalog rows land in the gateable artifacts."""
    stages, _ = _model()
    rep = run_scenario("handoff-replica-loss", stages, CFG,
                       outdir=str(tmp_path))
    assert rep["slo_ok"] and rep["completed"] == 16
    assert rep["fleet"]["prefill_replicas"] == 1
    assert rep["fleet"]["handoffs"] == 16
    assert rep["fleet"]["replica_losses"] == 1
    assert rep["fleet"]["migrations"] >= 1
    recs = [json.loads(ln) for ln in open(tmp_path / "metrics.jsonl")]
    serve = [r for r in recs if r.get("kind") == "serve"][-1]
    assert serve["fleet_handoffs"] == 16
    assert serve["pools"]["prefill"]["replicas"] == 1
    prom = open(tmp_path / "metrics.prom").read()
    assert "serve_fleet_handoffs_total 16" in prom
    for name in ("serve_fleet_handoffs_total", "serve_pool_replicas",
                 "serve_pool_queue_depth", "serve_pool_slots_active"):
        assert f"# HELP {name}" in prom, name
    whys = set()
    for p in tmp_path.glob("journal-handoff-replica-loss-r*.jsonl"):
        evs, _ = read_journal(str(p))
        whys |= {e["why"] for e in evs if e["ev"] == "snap"}
    assert whys == {"handoff", "failure"}


def test_handoff_gate_requires_handoffs():
    """The vacuous-pass guard: the disagg scenario with its pools
    flattened must FAIL its gate (min_handoffs unmet), not pass because
    nothing moved — and min_handoffs without pools is refused outright."""
    from simple_distributed_machine_learning_tpu.resilience.scenarios import (
        Scenario,
    )

    stages, _ = _model()
    # flattening the pools while keeping the gate is refused outright
    with pytest.raises(ValueError, match="min_handoffs"):
        dataclasses.replace(SCENARIOS["handoff-replica-loss"],
                            name="no-pools", prefill_replicas=0,
                            chaos=None, min_migrations=0)
    # and a gate the run cannot meet fails slo_ok instead of passing
    starved = dataclasses.replace(SCENARIOS["handoff-replica-loss"],
                                  name="starved", chaos=None,
                                  min_migrations=0, min_handoffs=17)
    rep = run_scenario(starved, stages, CFG)
    assert rep["completed"] == 16           # nothing wrong with the run
    assert rep["fleet"]["handoffs"] == 16   # one short of the gate
    assert not rep["slo_ok"]                # the gate caught it
    with pytest.raises(ValueError, match="min_handoffs"):
        Scenario(name="x", description="", sim=SCENARIOS["steady"].sim,
                 replicas=2, min_handoffs=1)
    with pytest.raises(ValueError, match="min_host_demotes"):
        Scenario(name="x", description="", sim=SCENARIOS["steady"].sim,
                 min_host_demotes=1)


# ---------------------------------------------------------------------------
# bench + CLI surface


def test_bench_disaggregation_row():
    """The bench comparison rows exist and their deterministic fields
    pin: every request hands off exactly once, both fleets complete
    everything (the latency gap itself is gated in the virtual-clock
    scenario, not on wall time)."""
    from bench import _measure_disaggregation

    stages, _ = _model()
    [row] = _measure_disaggregation(stages, CFG, n_requests=8, max_new=8,
                                    prompt_lens=(8, 12), block_size=4)
    assert row["config"] == "gpt_serve_disagg_prefill_decode"
    assert row["handoffs"] == 8
    assert row["completed"] == 8 and row["completed_symmetric"] == 8
    assert row["ttft_ms_p95"] > 0 and row["ttft_ms_p95_symmetric"] > 0


def test_bench_host_offload_row():
    """The host-offload bench row: with the tier the churned prefix
    survives as host hits; the HBM-only fleet re-prefills from scratch
    (counter-based, so exact despite wall-clock timing)."""
    from bench import _measure_host_offload

    stages, _ = _model()
    [row] = _measure_host_offload(stages, CFG, n_requests=8, block_size=4)
    assert row["config"] == "gpt_serve_host_offload_prefix"
    assert row["prefix_hit_blocks"] == 6
    assert row["prefix_hit_blocks_hbm_only"] == 0
    assert row["host_demotes"] == 24 and row["host_promotes"] == 6
    assert row["host_prefetch_hits"] == 3
    assert row["host_transfer_bytes"] == 61440


def test_serve_disagg_cli(tmp_path, capsys):
    """--serve-prefill-replicas / --serve-host-blocks end to end: the
    disaggregated fleet serves the sim, the handoff/pool/host blocks
    land in stdout, the metrics record and the Prom exposition."""
    from simple_distributed_machine_learning_tpu.cli import main

    tele = str(tmp_path / "tele")
    main(["--rank", "0", "--world_size", "1", "--model", "gpt",
          "--serve-sim", "6", "--serve-rate", "100", "--serve-slots", "2",
          "--serve-max-new", "4", "--serve-block-size", "4",
          "--serve-prefill-chunk", "3", "--serve-replicas", "3",
          "--serve-prefill-replicas", "1", "--serve-host-blocks", "8",
          "--telemetry-dir", tele])
    out = capsys.readouterr().out
    assert "| serve: 6/6 requests completed" in out
    assert "disaggregated 1 prefill + 2 decode" in out
    assert "prefill->decode handoff(s)" in out
    assert "host tier" in out
    recs = [json.loads(ln) for ln in
            open(os.path.join(tele, "metrics.jsonl"))]
    r = [x for x in recs if x.get("kind") == "serve"][-1]
    assert r["completed"] == 6 and r["fleet_handoffs"] == 6
    assert r["pools"]["prefill"]["replicas"] == 1
    assert r["pools"]["decode"]["replicas"] == 2
    assert "host_blocks" in r
    prom = open(os.path.join(tele, "metrics.prom")).read()
    assert "serve_fleet_handoffs_total 6" in prom


def test_serve_disagg_cli_flag_validation():
    from simple_distributed_machine_learning_tpu.cli import main

    base = ["--rank", "0", "--world_size", "1", "--model", "gpt",
            "--serve-sim", "2"]
    with pytest.raises(SystemExit, match="needs"):
        main(base + ["--serve-prefill-replicas", "1"])
    with pytest.raises(SystemExit, match="at least one decode"):
        main(base + ["--serve-replicas", "2",
                     "--serve-prefill-replicas", "2"])
    with pytest.raises(SystemExit, match="autoscale"):
        main(base + ["--serve-replicas", "3", "--serve-autoscale", "2,4",
                     "--serve-prefill-replicas", "1"])
    with pytest.raises(SystemExit, match="host-blocks"):
        main(base + ["--serve-host-blocks", "-1"])
    with pytest.raises(SystemExit, match="prefetch-ticks"):
        main(base + ["--serve-host-blocks", "4",
                     "--serve-prefetch-ticks", "0"])
