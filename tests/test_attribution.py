"""Per-request TTFT attribution (ISSUE 19): the additive fold.

The acceptance pins: every component decomposition sums EXACTLY to the
request's journaled TTFT (reconciliation drift beyond float rounding is
an :class:`AttributionError`, i.e. a test failure), across the
queue-heavy, host-prefetch-gate, chunked-prefill, preemption,
crash-restart and fleet-handoff paths — including one recovered rid whose
timeline spans two engine incarnations — and the aggregated scenario
blocks are deterministic enough to pin byte-identically.
"""

import json

import jax
import pytest

from simple_distributed_machine_learning_tpu.models.gpt import (
    GPTConfig,
    make_gpt_stages,
)
from simple_distributed_machine_learning_tpu.resilience import faults
from simple_distributed_machine_learning_tpu.resilience.scenarios import (
    run_scenario,
)
from simple_distributed_machine_learning_tpu.serve.tracing import ServeTrace
from simple_distributed_machine_learning_tpu.telemetry.attribution import (
    DRIFT_TOL_MS,
    AttributionError,
    attribute,
    fold_request,
)
from simple_distributed_machine_learning_tpu.telemetry.registry import (
    MetricsRegistry,
)

CFG = GPTConfig(vocab=32, seq_len=48, d_model=32, n_heads=2, n_layers=2)
_STAGES = None


def _model():
    global _STAGES
    if _STAGES is None:
        _STAGES = make_gpt_stages(jax.random.key(0), CFG, 2)[0]
    return _STAGES


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    faults.uninstall()
    yield
    faults.uninstall()


def _row(ev, t, rid=0, inc=0, **kw):
    return {"ev": ev, "t": t, "rid": rid, "inc": inc, **kw}


# ---------------------------------------------------------------------------
# the fold: synthetic timelines, every span→component edge


def test_fold_simple_queue_prefill_decode():
    att = fold_request([
        _row("submit", 0.0, cls="x", prompt_len=4),
        _row("admit", 0.010),
        _row("first_token", 0.030, ttft_ms=30.0),
        _row("tick", 0.040),
        _row("done", 0.050, tokens=3, reason="length"),
    ])
    assert att["components_ms"] == {"queue": 10.0, "prefill": 20.0}
    assert att["ttft_ms"] == 30.0 and att["drift_ms"] == 0.0
    assert att["cls"] == "x" and att["prompt_len"] == 4
    assert att["incarnations"] == [0] and not att["recovered"]
    # the decode side aggregates separately (the TPOT block)
    assert att["decode_ms"] == 20.0
    assert att["decode_components_ms"] == {"decode": 20.0}
    assert att["tokens"] == 3 and att["finish"] == "length"


def test_fold_prefetch_gate_chunks_and_preemption():
    """Host-prefetch gate wait, chunked prefill (inter-chunk spans stay
    prefill), a preemption with readmission: the full pre-TTFT map."""
    att = fold_request([
        _row("submit", 0.0, cls="x", prompt_len=8),
        _row("gate", 0.005),                 # blocked on host->HBM upload
        _row("admit", 0.009),
        _row("prefill_chunk", 0.012),
        _row("preempt", 0.020),              # evicted mid-prefill
        _row("readmit", 0.024),              # re-boards: the wait after
        _row("admit", 0.026),                # readmission is queue again
        _row("first_token", 0.040, ttft_ms=40.0),
    ])
    assert att["components_ms"] == {
        "queue": 7.0, "prefetch": 4.0, "prefill": 25.0, "preempt": 4.0}
    assert sum(att["components_ms"].values()) == att["ttft_ms"] == 40.0


def test_fold_crash_spans_incarnations():
    """A recovered rid: the crash->readmit->board gap stays ``crash``
    (readmit does NOT flip it to queue — the outage caused the wait), and
    the rid-less restart row never breaks the cursor walk."""
    att = fold_request([
        _row("submit", 0.0, cls="x", prompt_len=4),
        _row("admit", 0.002),
        _row("crash", 0.010),
        {"ev": "restart", "t": 0.011, "inc": 1},
        _row("readmit", 0.015, inc=1),
        _row("admit", 0.016, inc=1),
        _row("first_token", 0.020, inc=1, ttft_ms=20.0),
    ])
    assert att["components_ms"] == {
        "queue": 2.0, "prefill": 12.0, "crash": 6.0}
    assert att["incarnations"] == [0, 1] and att["recovered"]


def test_fold_handoff_migration():
    att = fold_request([
        _row("submit", 0.0, cls="x", prompt_len=4),
        _row("admit", 0.004),
        _row("migrate", 0.010),
        _row("readmit", 0.012),              # still the handoff gap
        _row("admit", 0.013),
        _row("first_token", 0.020, ttft_ms=20.0),
    ])
    assert att["components_ms"] == {
        "queue": 4.0, "prefill": 13.0, "handoff": 3.0}


def test_fold_drift_raises_and_shed_returns_none():
    rows = [
        _row("submit", 0.0, cls="x", prompt_len=4),
        _row("admit", 0.010),
        _row("first_token", 0.030, ttft_ms=99.0),   # timeline disagrees
    ]
    with pytest.raises(AttributionError):
        fold_request(rows)
    # nothing to decompose: never reached a first token
    assert fold_request([
        _row("submit", 0.0, cls="x", prompt_len=4),
        _row("shed", 0.001, reason="deadline"),
    ]) is None


def test_attribute_aggregates_and_registers_histograms():
    reg = MetricsRegistry()
    rows = [
        _row("submit", 0.0, rid=0, cls="a", prompt_len=4),
        _row("admit", 0.010, rid=0),
        _row("first_token", 0.030, rid=0, ttft_ms=30.0),
        _row("submit", 0.001, rid=1, cls="a", prompt_len=4),
        _row("admit", 0.002, rid=1),
        _row("first_token", 0.041, rid=1, ttft_ms=40.0),
        _row("submit", 0.002, rid=2, cls="b", prompt_len=4),
        _row("shed", 0.003, rid=2, reason="class"),
    ]
    out = attribute(rows, registry=reg)
    assert out["requests"] == 2 and out["recovered"] == 0
    assert out["by_class"]["a"]["n"] == 2
    assert out["by_class"]["a"]["ttft_ms_mean"] == 35.0
    assert out["by_class"]["a"]["components_ms_mean"] == {
        "queue": 5.5, "prefill": 29.5}
    # slowest first, rid ascending on ties
    assert [a["rid"] for a in out["top_slow"]] == [1, 0]
    assert out["max_abs_drift_ms"] <= DRIFT_TOL_MS
    prom = reg.prometheus_text()
    assert 'serve_ttft_component_ms_count{component="queue"} 2' in prom
    assert 'serve_ttft_component_ms_count{component="prefill"} 2' in prom


# ---------------------------------------------------------------------------
# the scenario pins: reconciliation on every real path, exact numbers


def test_attribution_reconciles_across_every_serving_path():
    """One assertion per acceptance path: queue-heavy shed storm,
    crash-restart, host-offload prefetch, fleet handoff, disaggregated
    chunked prefill — every fold reconciles (drift within float
    rounding), with the per-scenario request counts pinned."""
    expected_requests = {
        "overload-shed": 9,           # queue-heavy: only completions fold
        "crash-serve": 16,
        "offload-churn": 24,          # host-prefetch gate path
        "handoff-replica-loss": 16,   # fleet handoff path
        "disagg-prefill-heavy": 16,   # chunked-prefill pools
    }
    for name, n in expected_requests.items():
        rep = run_scenario(name, _model(), CFG, trace=True)
        att = rep["attribution"]
        assert att["requests"] == n, name
        assert att["max_abs_drift_ms"] <= DRIFT_TOL_MS, name
        for a in att["top_slow"]:
            assert sum(a["components_ms"].values()) == pytest.approx(
                a["ttft_ms"], abs=DRIFT_TOL_MS), (name, a["rid"])


def test_crash_serve_autopsy_pinned_with_recovered_rid():
    """The crash-restart pin, exact virtual-clock numbers: the slowest
    request's autopsy and the one rid whose timeline spans both engine
    incarnations (recovered through the journal)."""
    tr = ServeTrace()
    rep = run_scenario("crash-serve", _model(), CFG, trace=tr)
    att = rep["attribution"]
    assert att["requests"] == 16 and att["recovered"] == 1
    assert att["max_abs_drift_ms"] == 0.0
    top = att["top_slow"][0]
    assert top["rid"] == 3 and top["ttft_ms"] == 23.16
    assert top["components_ms"] == {"queue": 1.16, "prefill": 22.0}
    # the recovered rid, folded straight from its two-incarnation rows
    rows0 = [r for r in tr.rows if r.get("rid") == 0]
    a0 = fold_request(rows0)
    assert a0["incarnations"] == [0, 1] and a0["recovered"]
    assert sum(a0["components_ms"].values()) == pytest.approx(
        a0["ttft_ms"], abs=DRIFT_TOL_MS)
    # the pre-existing crash pins survive attribution riding along
    assert rep["restarts"] == 1 and rep["slo_ok"]


def test_overload_shed_autopsy_pinned():
    rep = run_scenario("overload-shed", _model(), CFG, trace=True)
    att = rep["attribution"]
    assert att["requests"] == 9
    top = att["top_slow"][0]
    assert top["rid"] == 26 and top["cls"] == "interactive"
    assert top["ttft_ms"] == 69.143
    assert top["components_ms"] == {"queue": 61.144, "prefill": 8.0}


def test_attribution_block_deterministic():
    r1 = run_scenario("crash-serve", _model(), CFG, trace=True)
    r2 = run_scenario("crash-serve", _model(), CFG, trace=True)
    assert (json.dumps(r1["attribution"], sort_keys=True)
            == json.dumps(r2["attribution"], sort_keys=True))
