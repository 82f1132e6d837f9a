"""Resilience (resilience/): fault injection, checkpoint store, supervisor.

The elastic acceptance pin lives here: a training run killed mid-epoch by
an injected host-loss fault auto-restores the latest VALID checkpoint,
repacks it onto a different stage count, and resumes to completion with
loss continuing from the restored step (vs the uninterrupted run). Plus:
the deterministic fault-plan semantics, the checksum-validated store never
selecting a corrupt checkpoint, write-crash and budget-exhaustion recovery
paths, async-save error surfacing, and bench.py's rc-17 wedged-device
detection with retry + the structured device_unhealthy row.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from simple_distributed_machine_learning_tpu.data.mnist import Dataset
from simple_distributed_machine_learning_tpu.models.mlp import make_mlp_stages
from simple_distributed_machine_learning_tpu.parallel.mesh import make_mesh
from simple_distributed_machine_learning_tpu.parallel.pipeline import Pipeline
from simple_distributed_machine_learning_tpu.resilience import (
    CheckpointStore,
    RestartBudgetExceeded,
    RestartPolicy,
    faults,
    make_elastic_trainer,
    supervise,
)
from simple_distributed_machine_learning_tpu.resilience.supervisor import (
    PeerLost,
)
from simple_distributed_machine_learning_tpu.train.trainer import (
    TrainConfig,
    Trainer,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    """Every test starts and ends with no active fault plan."""
    faults.uninstall()
    yield
    faults.uninstall()


# ---------------------------------------------------------------------------
# fault plans


def test_fault_plan_parse_grammar():
    p = faults.FaultPlan.parse(
        "host-kill@train.step=6;"
        "slow-tick@serve.tick,dur=0.01,after=2,times=3;"
        "frozen-peer@watchdog.heartbeat,rank=1")
    kinds = [(s.kind, s.site, s.step, s.rank) for s in p.specs]
    assert kinds == [("host-kill", "train.step", 6, None),
                     ("slow-tick", "serve.tick", None, None),
                     ("frozen-peer", "watchdog.heartbeat", None, 1)]
    assert p.specs[1].dur == 0.01 and p.specs[1].after == 2
    for bad in ("explode@train.step", "host-kill", "host-kill@x,zzz=1",
                "", "host-kill@train.step,dur=-1",
                # a typo'd site must be rejected, not silently never fire
                # (a vacuously-green chaos drill is worse than none)
                "host-kill@train.steps=6"):
        with pytest.raises(ValueError):
            faults.FaultPlan.parse(bad)


def test_fault_step_match_fires_once_and_counts():
    plan = faults.install(faults.FaultPlan.parse("host-kill@train.step=3"))
    assert faults.maybe_fire("train.step", step=2) == []
    with pytest.raises(faults.HostLost):
        faults.maybe_fire("train.step", step=3)
    # times=1 default: the same step on a later attempt does NOT re-fire —
    # that is what lets a supervised retry run clean past the kill point
    assert faults.maybe_fire("train.step", step=3) == []
    assert plan.stats()["total_fired"] == 1


def test_fault_after_times_and_sleep_routing():
    slept = []
    plan = faults.FaultPlan.parse("slow-tick@serve.tick,dur=0.5,after=1,"
                                  "times=2", sleep=slept.append)
    faults.install(plan)
    for i in range(5):
        faults.maybe_fire("serve.tick", step=i)
    assert slept == [0.5, 0.5]          # skipped first, fired twice, capped


def test_fault_noop_without_plan_and_check_has_no_effects():
    assert faults.maybe_fire("train.step", step=0) == []
    faults.install(faults.FaultPlan.parse("host-kill@train.step=0"))
    # check() matches and counts but never raises — the watchdog's entry
    fired = faults.check("train.step", step=0)
    assert [f.kind for f in fired] == ["host-kill"]
    assert faults.check("train.step", step=0) == []   # times exhausted


def test_fault_random_schedule_deterministic():
    a = faults.FaultPlan.random(7, n=4, max_step=50)
    b = faults.FaultPlan.random(7, n=4, max_step=50)
    assert ([(s.kind, s.site, s.step) for s in a.specs]
            == [(s.kind, s.site, s.step) for s in b.specs])
    c = faults.FaultPlan.random(8, n=4, max_step=50)
    assert ([(s.kind, s.step) for s in a.specs]
            != [(s.kind, s.step) for s in c.specs])


def test_install_from_env(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "wedged-device@bench.probe=0")
    plan = faults.install_from_env()
    assert plan is faults.active()
    assert plan.specs[0].kind == "wedged-device"
    monkeypatch.delenv(faults.ENV_VAR)
    faults.uninstall()
    assert faults.install_from_env() is None


# ---------------------------------------------------------------------------
# checkpoint store


def _store_state(seed=0):
    rng = np.random.RandomState(seed)
    return rng.randn(4, 8).astype(np.float32), [rng.randn(4, 8)]


def test_store_save_validate_latest(tmp_path):
    store = CheckpointStore(str(tmp_path), keep=3)
    buf, opt = _store_state()
    for step in (4, 8):
        store.save(buf, opt, step, extra={"epoch": step // 4, "n_stages": 2})
    entries = store.entries()
    assert [e["step"] for e in entries] == [4, 8]
    assert all(store.validate(e) for e in entries)
    latest = store.latest_valid()
    assert latest["step"] == 8 and latest["extra"]["n_stages"] == 2
    assert os.path.exists(latest["path"])


def test_store_never_selects_corrupt_checkpoint(tmp_path, capfd):
    """The acceptance invariant: a corrupt checkpoint is NEVER selected —
    the newest generation is truncated on disk and latest_valid falls back
    to the previous one, loudly."""
    store = CheckpointStore(str(tmp_path), keep=3)
    buf, opt = _store_state()
    store.save(buf, opt, 4, extra={"epoch": 1})
    store.save(buf, opt, 8, extra={"epoch": 2})
    newest = os.path.join(str(tmp_path), store.entries()[-1]["file"])
    with open(newest, "r+b") as f:        # torn write / bad disk
        f.truncate(os.path.getsize(newest) // 2)
    latest = store.latest_valid()
    assert latest["step"] == 4
    assert "skipping corrupt" in capfd.readouterr().err
    # every generation corrupt -> None, not a bad pick
    with open(os.path.join(str(tmp_path), latest["file"]), "wb") as f:
        f.write(b"garbage")
    assert store.latest_valid() is None


def test_store_gc_keeps_newest(tmp_path):
    store = CheckpointStore(str(tmp_path), keep=2)
    buf, opt = _store_state()
    for step in (1, 2, 3, 4):
        store.save(buf, opt, step)
    assert [e["step"] for e in store.entries()] == [3, 4]
    files = {f for f in os.listdir(str(tmp_path)) if f.endswith(".npz")}
    assert files == {"ckpt-00000003.npz", "ckpt-00000004.npz"}


def test_store_resave_same_step_supersedes_and_gc_keeps_live_file(tmp_path):
    """A restarted attempt re-saving a step it already saved (the corrupt-
    newest-generation fallback path) must SUPERSEDE the stale manifest
    entry, and GC must never unlink a file a live entry still references —
    the duplicate-entry case where position-based GC would delete the
    newest valid checkpoint out from under its own manifest line."""
    store = CheckpointStore(str(tmp_path), keep=2)
    buf, opt = _store_state()
    store.save(buf, opt, 4, extra={"epoch": 1})
    store.save(buf, opt, 8, extra={"epoch": 2})
    store.save(buf, opt, 8, extra={"epoch": 2})   # re-run of epoch 2
    entries = store.entries()
    assert [e["step"] for e in entries] == [4, 8]  # one entry per file
    store.save(buf, opt, 12, extra={"epoch": 3})   # triggers GC (keep=2)
    assert [e["step"] for e in store.entries()] == [8, 12]
    latest = store.latest_valid()
    assert latest["step"] == 12
    # the step-8 file survived GC and still validates
    assert store.validate(store.entries()[0])


def test_store_manifest_tolerates_torn_line(tmp_path):
    store = CheckpointStore(str(tmp_path), keep=3)
    buf, opt = _store_state()
    store.save(buf, opt, 4)
    with open(os.path.join(str(tmp_path), "MANIFEST.jsonl"), "a") as f:
        f.write('{"file": "ckpt-trunc')   # crash mid-append
    assert [e["step"] for e in store.entries()] == [4]
    assert store.latest_valid()["step"] == 4


# ---------------------------------------------------------------------------
# elastic supervisor (stub-level semantics)


class _StubTrainer:
    def __init__(self, outcomes, n_stages):
        self._outcomes = outcomes
        self.n_stages = n_stages
        self._step_count = 0
        self.start_epoch = 1
        self.history = []

    def fit(self):
        out = self._outcomes.pop(0)
        if out is not None:
            raise out


def _host_lost():
    return faults.HostLost(
        faults.FaultSpec(kind="host-kill", site="train.step"), "train.step")


def test_supervise_shrinks_on_peer_loss_with_exponential_backoff():
    outcomes = [PeerLost("peer 1 vanished"), _host_lost(), None]
    built, sleeps = [], []

    def build(n):
        built.append(n)
        return _StubTrainer(outcomes, n)

    report = supervise(build, (4, 2, 1),
                       policy=RestartPolicy(max_restarts=3,
                                            base_backoff_s=0.1,
                                            backoff_factor=2.0,
                                            max_backoff_s=10.0),
                       sleep=sleeps.append)
    assert built == [4, 2, 1]            # one rung down per host/peer loss
    assert report["completed"] and report["restarts"] == 2
    assert sleeps == [0.1, 0.2]          # exponential
    assert [t[0] for t in report["transitions"]] == [
        "RUNNING", "RESTORING", "RUNNING", "RESTORING", "RUNNING", "DONE"]


def test_supervise_budget_exhaustion_fails_loudly():
    outcomes = [_host_lost(), _host_lost(), _host_lost()]

    def build(n):
        return _StubTrainer(outcomes, n)

    with pytest.raises(RestartBudgetExceeded):
        supervise(build, (2, 1),
                  policy=RestartPolicy(max_restarts=2, base_backoff_s=0.0),
                  sleep=lambda s: None)


def test_supervise_propagates_real_bugs():
    def build(n):
        return _StubTrainer([ValueError("a real bug")], n)

    with pytest.raises(ValueError, match="a real bug"):
        supervise(build, (1,), sleep=lambda s: None)


# ---------------------------------------------------------------------------
# elastic supervisor (real training, the acceptance pin)


def _tiny_ds():
    rng = np.random.RandomState(0)
    return Dataset(rng.randn(120, 12).astype(np.float32),
                   rng.randint(0, 10, 120))


_DIMS = [12, 16, 14, 16, 10]


def _build_pipe(n):
    stages, wd, od = make_mlp_stages(jax.random.key(0), _DIMS, n)
    return Pipeline(stages, make_mesh(n_stages=n, n_data=1,
                                      devices=jax.devices()[:n]), wd, od)


def test_elastic_host_kill_restores_repacks_and_loss_continues(tmp_path):
    """THE acceptance pin: host-kill at step 6 (mid-epoch 2 of a 4-step-
    per-epoch run) -> the supervisor restores the epoch-1 checkpoint
    (step 4), repacks it from 2 pipeline stages onto 1, and resumes to
    completion — with every post-restore epoch loss matching the
    uninterrupted 2-stage run (identical state => identical trajectory to
    cross-topology float tolerance)."""
    ds = _tiny_ds()
    cfg = TrainConfig(epochs=4, batch_size=30, print_throughput=False)

    ref = Trainer(_build_pipe(2), ds, ds, cfg)
    ref_losses = []
    ref._log_metrics = lambda rec: ref_losses.append(rec["train_loss"])
    ref.fit()

    store = CheckpointStore(str(tmp_path), keep=8)
    faults.install(faults.FaultPlan.parse("host-kill@train.step=6"))
    sleeps = []
    report = supervise(
        lambda n: make_elastic_trainer(_build_pipe, n, store, ds, ds, cfg),
        (2, 1), policy=RestartPolicy(max_restarts=3),
        sleep=sleeps.append)

    assert report["completed"] and report["restarts"] == 1
    a1, a2 = report["attempts"]
    assert (a1["n_stages"], a1["outcome"], a1["fault"]) == (2, "fault",
                                                            "HostLost")
    # the kill hit mid-epoch 2: only epoch 1 finished before it
    assert [h["epoch"] for h in a1["history"]] == [1]
    assert a1["history"][0]["train_loss"] == ref_losses[0]
    # restored the latest valid checkpoint (epoch 1 / step 4), repacked 2->1
    assert a2["n_stages"] == 1
    assert a2["resumed_step"] == 4 and a2["start_epoch"] == 2
    assert a2["outcome"] == "completed"
    # loss CONTINUES from the restored step: epochs 2..4 match the
    # uninterrupted run (cross-stage-count float tolerance, the bound
    # test_checkpoint's repack trajectory test established)
    np.testing.assert_allclose([h["train_loss"] for h in a2["history"]],
                               ref_losses[1:], rtol=3e-5, atol=3e-5)
    assert sleeps == [0.05]
    # the manifest recorded the source topology the repack keyed off
    assert store.latest_valid()["extra"]["n_stages"] == 1
    assert [t[0] for t in report["transitions"]] == [
        "RUNNING", "RESTORING", "RUNNING", "DONE"]


def test_elastic_write_crash_retries_in_place(tmp_path):
    """A checkpoint-write crash is recoverable but NOT topology-shrinking:
    the supervisor restarts at the same stage count; the fault's times=1
    schedule lets the retry save cleanly and complete."""
    ds = _tiny_ds()
    cfg = TrainConfig(epochs=2, batch_size=30, print_throughput=False)
    store = CheckpointStore(str(tmp_path), keep=4)
    faults.install(faults.FaultPlan.parse("ckpt-write-crash@ckpt.write"))
    report = supervise(
        lambda n: make_elastic_trainer(_build_pipe, n, store, ds, ds, cfg),
        (2, 1), policy=RestartPolicy(max_restarts=2),
        sleep=lambda s: None)
    assert report["completed"] and report["restarts"] == 1
    a1, a2 = report["attempts"]
    assert a1["fault"] == "CheckpointWriteCrash"
    assert a2["n_stages"] == 2            # same rung: nothing was lost
    assert store.latest_valid() is not None


def test_elastic_trainer_rejects_checkpoint_dir_config(tmp_path):
    ds = _tiny_ds()
    cfg = TrainConfig(epochs=1, batch_size=30,
                      checkpoint_dir=str(tmp_path / "clash"))
    with pytest.raises(ValueError, match="CheckpointStore"):
        make_elastic_trainer(_build_pipe, 1,
                             CheckpointStore(str(tmp_path)), ds, ds, cfg)


# ---------------------------------------------------------------------------
# async checkpoint error surfacing (satellite)


def test_async_write_crash_surfaces_from_fit(tmp_path, capfd):
    """An async checkpoint write that dies on the writer thread must fail
    the RUN (original exception type, surfaced at the next wait point) —
    not vanish while training reports success with no checkpoint."""
    ds = _tiny_ds()
    cfg = TrainConfig(epochs=2, batch_size=30, print_throughput=False,
                      checkpoint_dir=str(tmp_path), async_checkpoint=True)
    tr = Trainer(_build_pipe(1), ds, ds, cfg)
    faults.install(faults.FaultPlan.parse("ckpt-write-crash@ckpt.write"))
    with pytest.raises(faults.CheckpointWriteCrash):
        tr.fit()
    assert "async write" in capfd.readouterr().err


# ---------------------------------------------------------------------------
# bench: rc-17 wedged-device detection + retry + structured row (satellite)


def _bench():
    sys.path.insert(0, REPO)
    import bench
    return bench


def _raise_exit(rc):
    raise SystemExit(rc)


def test_bench_supervised_smoke_retry_then_recover(capsys):
    """First probe is unresponsive (rc 17), the retry succeeds: one backoff
    sleep, a plain return, no device_unhealthy row."""
    bench = _bench()
    rcs, sleeps = [17, 0], []
    bench._supervised_smoke(probe=lambda a, t: rcs.pop(0), backoff_s=3.0,
                            sleep=sleeps.append, exit=_raise_exit)
    assert rcs == [] and sleeps == [3.0]
    assert "device_unhealthy" not in capsys.readouterr().out


def test_bench_supervised_smoke_emits_device_unhealthy_row(capsys):
    """Persistently unresponsive: retry once with backoff, print the
    structured row, then EXIT rc 17 — an unreachable device is a failure,
    never an exit-0 'measurement'."""
    bench = _bench()
    sleeps = []
    with pytest.raises(SystemExit) as ei:
        bench._supervised_smoke(probe=lambda a, t: 17, backoff_s=2.0,
                                sleep=sleeps.append, exit=_raise_exit)
    assert ei.value.code == bench.WEDGED_RC == 17 and sleeps == [2.0]
    rows = [json.loads(line) for line in
            capsys.readouterr().out.strip().splitlines()]
    row = rows[-1]
    assert row["metric"] == "device_unhealthy"
    assert row["rc"] == 17 and row["attempts"] == 2


def test_bench_supervised_smoke_non_wedge_rc_stays_fatal():
    bench = _bench()
    with pytest.raises(SystemExit) as ei:
        bench._supervised_smoke(probe=lambda a, t: 3, sleep=lambda s: None,
                                exit=_raise_exit)
    assert ei.value.code == 3


def test_bench_serve_round_exits_nonzero_and_writes_no_artifact(
        tmp_path, monkeypatch, capsys):
    """A --serve round on a persistently unresponsive device exits rc 17
    after the device_unhealthy row and leaves benchmarks/serving.json
    untouched: the artifact only ever holds measured rows."""
    bench = _bench()
    (tmp_path / "benchmarks").mkdir()
    art = tmp_path / "benchmarks" / "serving.json"
    art.write_text('{"rows": ["measured earlier"]}')
    monkeypatch.setattr(bench, "REPO", str(tmp_path))
    monkeypatch.setattr(bench, "_probe", lambda a, t: 17)
    monkeypatch.setattr(bench, "_hard_exit", _raise_exit)
    monkeypatch.setenv("SDML_BENCH_PROBE_BACKOFF", "0")
    monkeypatch.setattr(sys, "argv", ["bench.py", "--serve"])
    with pytest.raises(SystemExit) as ei:
        bench.main()
    assert ei.value.code == 17
    assert "device_unhealthy" in capsys.readouterr().out
    assert json.loads(art.read_text()) == {"rows": ["measured earlier"]}


def test_bench_probe_wedge_signature():
    """The in-process probe: an injected wedged-device fault at the
    bench.probe site produces exactly the rc-17 signature (and only on the
    attempt the plan names)."""
    bench = _bench()
    faults.install(faults.FaultPlan.parse("wedged-device@bench.probe=0"))
    assert bench._probe(0, timeout_s=60) == 17
    assert bench._probe(1, timeout_s=60) == 0


def test_bench_probe_healthy_cpu():
    """The unwedged probe end-to-end, in this process (a child would take
    the chip from a parent about to measure on it): a constant materializes
    on the CPU backend, rc 0."""
    assert _bench()._probe(0, timeout_s=120) == 0
