"""Epoch driver: train/eval loops with the reference's console surface.

Replaces ``run_master`` and its inner ``train``/``test`` closures
(``/root/reference/simple_distributed.py:86-136``). Print formats are
byte-identical to the reference (``:114-117`` train, ``:130-132`` test) so
logs are directly comparable; an additional per-epoch throughput line covers
the north-star metric the reference never measured (SURVEY §6).

MPMD→SPMD note (SURVEY §7 hard part (c)): the reference's loops run only on
the master process while workers idle in an RPC serve loop. Here every process
runs the same loop; on multi-process runs each host feeds only its data-axis
rows of every batch (``_feed`` → ``data/sharding.py``), and only process 0
prints (``is_main``).
"""

from __future__ import annotations

import dataclasses
import sys

import jax
import numpy as np

from simple_distributed_machine_learning_tpu.data.mnist import (
    Dataset,
    batches,
    prefetch_batches,
)
from simple_distributed_machine_learning_tpu.parallel.pipeline import Pipeline
from simple_distributed_machine_learning_tpu.resilience.faults import (
    active as faults_active,
    check as faults_check,
    maybe_fire,
)
from simple_distributed_machine_learning_tpu.telemetry import tracing
from simple_distributed_machine_learning_tpu.train.optimizer import (
    Optimizer,
    sgd,
    shard_opt_state_zero1,
)
from simple_distributed_machine_learning_tpu.train.step import (
    make_eval_step,
    make_train_step,
)
from simple_distributed_machine_learning_tpu.utils.metrics import Throughput

# Reference hyperparameters (simple_distributed.py:18-22)
BATCH_SIZE = 60
EPOCHS = 10
LEARNING_RATE = 0.1
MOMENTUM = 0.5
LOG_INTERVAL = 10


@dataclasses.dataclass
class TrainConfig:
    epochs: int = EPOCHS
    batch_size: int = BATCH_SIZE
    learning_rate: float = LEARNING_RATE
    momentum: float = MOMENTUM
    log_interval: int = LOG_INTERVAL
    seed: int = 0
    print_throughput: bool = True
    # persistence (absent from the reference, SURVEY §5.4): a checkpoint is
    # written after every epoch and auto-resumed from on construction
    checkpoint_dir: str | None = None
    resume: bool = True
    # overlap the checkpoint FILE WRITE with the next epoch's compute (the
    # device->host gather stays synchronous — it is a collective)
    async_checkpoint: bool = False
    # ZeRO-1: shard optimizer state over the data axis (pure sharding
    # annotation; GSPMD inserts the collectives — optimizer.py)
    zero1: bool = False
    # seeded per-epoch shuffle of the train set (the reference trains in
    # fixed order, simple_distributed.py:94-95 — kept as the default for
    # loss-curve parity)
    shuffle: bool = False
    # per-host input sharding (multi-process runs): each process feeds only
    # its data-axis rows of every batch instead of materializing the global
    # batch on every host (data/sharding.py). On a single process this is a
    # no-op path and the plain numpy feed is used.
    shard_inputs: bool = True
    # machine-readable training log: one JSON line per epoch (epoch, step,
    # train_loss, samples_per_sec, eval_loss, accuracy — plus the raw
    # correct/n_eval counts the accuracy is computed from) appended to this
    # path by process 0. The console surface stays byte-identical to the
    # reference; this is the structured counterpart (SURVEY §5.5). Records
    # are written through the telemetry registry and carry "schema": 2.
    metrics_json: str | None = None
    # smoke/dryrun mode (cli.py --dryrun): train at most this many batches
    # per epoch. None = the full dataset, the reference's behavior.
    max_steps_per_epoch: int | None = None
    # self-healing training (resilience/sentinel.py): check every step's
    # loss/grad-norm for NaN/Inf and EWMA loss spikes, keep a bounded
    # in-memory ring of host snapshots, and on an anomaly roll back to the
    # newest pre-anomaly snapshot, quarantine the offending batch (recorded
    # in <checkpoint_dir>/quarantine.jsonl and deterministically skipped
    # from then on) and replay forward — bit-exact vs a run that never saw
    # the fault. Cost when on: one device→host scalar sync per step and a
    # host gather every sentinel_snapshot_every steps.
    sentinel: bool = False
    sentinel_window: int = 16        # EWMA horizon + escalation window
    sentinel_snapshot_every: int = 4
    sentinel_ring: int = 4           # retained snapshots (memory bound)
    sentinel_spike_factor: float = 3.0


class Trainer:
    """Drives a :class:`Pipeline` over a dataset, reference-style."""

    def __init__(self, pipe: Pipeline, train_ds: Dataset, test_ds: Dataset,
                 config: TrainConfig | None = None,
                 opt: Optimizer | None = None, telemetry=None) -> None:
        self.pipe = pipe
        self.train_ds = train_ds
        self.test_ds = test_ds
        self.config = config or TrainConfig()
        # the observability hook (telemetry/session.py): per-step latency
        # sampling, feed/step/eval host spans, per-epoch metric emission.
        # None = reference behavior (console + optional metrics_json only).
        self.telemetry = telemetry
        # LM datasets have [N, T] targets: telemetry reports tokens/sec
        # alongside examples/sec (0 = classifier, no token throughput)
        self._tokens_per_sample = (int(np.prod(train_ds.y.shape[1:]))
                                   if np.ndim(train_ds.y) > 1 else 0)
        self._registry = telemetry.registry if telemetry is not None else None
        self.opt = opt or sgd(self.config.learning_rate, self.config.momentum)
        self.buf = pipe.init_params()
        self.opt_state = self.opt.init(self.buf)
        if self.config.zero1:
            self.opt_state = shard_opt_state_zero1(
                self.opt_state, pipe.mesh, pipe.param_spec())
        self._train_step = make_train_step(
            pipe, self.opt, with_grad_norm=self.config.sentinel)
        self._eval_step = make_eval_step(pipe)
        self._key = jax.random.key(self.config.seed)
        self._step_count = 0
        self._last_samples_per_sec = 0.0
        self._pending_save = None
        self.start_epoch = 1
        self.is_main = jax.process_index() == 0
        self._shard_inputs = (self.config.shard_inputs
                              and jax.process_count() > 1)
        self._shard_announced = False
        self._host_rows_cache: dict[int, tuple[int, int]] = {}
        # graceful preemption (SIGTERM / injected preempt@train.sigterm):
        # finish the in-flight step, synchronous checkpoint with the data
        # cursor, quarantine-journal flush, clean return
        self._stop_requested = False
        self._stop_signal: int | None = None
        self._preempt_cursor: int | None = None
        self._resume_batch_idx = 0
        self.preempted = False
        self.preempt_persisted = False
        self._sentinel = None
        if self.config.sentinel:
            import os

            from simple_distributed_machine_learning_tpu.resilience.sentinel import (  # noqa: E501
                Sentinel,
                SentinelConfig,
            )
            jdir = self._sentinel_dir()
            self._sentinel = Sentinel(
                SentinelConfig(
                    window=self.config.sentinel_window,
                    snapshot_every=self.config.sentinel_snapshot_every,
                    ring_size=self.config.sentinel_ring,
                    spike_factor=self.config.sentinel_spike_factor),
                registry=self._registry,
                journal_path=(os.path.join(jdir, "quarantine.jsonl")
                              if jdir else None),
                # rank-0 writes the shared journal; every rank still loads
                # it and skips identically (the checkpoint writers' rule)
                journal_write_ok=self.is_main)
        if self.config.checkpoint_dir and self.config.resume:
            self._maybe_resume()

    # -- persistence (reference has none: SURVEY §5.4) --------------------

    def _ckpt_path(self) -> str:
        import os
        return os.path.join(self.config.checkpoint_dir, "state.npz")

    def _maybe_resume(self) -> None:
        import os
        path = self._ckpt_path()
        found = os.path.exists(path)
        if jax.process_count() > 1:
            # all processes must agree on whether/where to resume, or they
            # would issue different numbers of collective steps and hang
            # (e.g. checkpoint_dir on a non-shared filesystem)
            from jax.experimental import multihost_utils
            founds = multihost_utils.process_allgather(
                np.asarray([1 if found else 0], np.int32))
            if int(founds.min()) != int(founds.max()):
                raise RuntimeError(
                    f"checkpoint {path} visible on only some processes — "
                    "checkpoint_dir must be a shared filesystem for "
                    "multi-process resume")
        if not found:
            return
        from simple_distributed_machine_learning_tpu.train.checkpoint import (
            restore_checkpoint,
        )
        st = restore_checkpoint(path, pipe=self.pipe,
                                opt_treedef_like=self.opt_state)
        if tuple(st["params"].shape) != tuple(self.buf.shape):
            raise ValueError(
                f"checkpoint {path} does not match the model: packed param "
                f"buffer is {tuple(st['params'].shape)}, model expects "
                f"{tuple(self.buf.shape)} (different model/topology "
                f"config?). A checkpoint from a different contiguous stage "
                f"split of the SAME model can be rewritten with "
                f"train.checkpoint.repack_checkpoint (or restored with "
                f"restore_checkpoint(..., src_pipe=<source pipeline>)).")
        self.buf, self.opt_state = st["params"], st["opt_state"]
        self._step_count = st["step"]
        self.start_epoch = int(st["extra"].get("epoch", 0)) + 1
        # a graceful-preemption checkpoint carries the mid-epoch data
        # cursor: the saved epoch is the last COMPLETED one, next_batch is
        # where the interrupted epoch re-enters
        self._resume_batch_idx = int(st["extra"].get("next_batch", 0))
        if self._sentinel is not None and "sentinel" in st["extra"]:
            self._sentinel.restore_detector(st["extra"]["sentinel"])
        self._print(f"| resumed from {path} at epoch {self.start_epoch} "
                    f"(step {self._step_count})"
                    + (f" (batch {self._resume_batch_idx})"
                       if self._resume_batch_idx else ""))

    def _save_extra(self, epoch: int, cursor: int | None) -> dict:
        """Checkpoint ``extra`` metadata. A completed epoch records itself;
        a graceful-preemption save mid-epoch records the last COMPLETED
        epoch plus the ``next_batch`` data cursor, so resume re-enters the
        interrupted epoch at the exact batch (same steps, same keys —
        bit-identical to the uninterrupted run). With the sentinel on, the
        EWMA detector state rides along so the resumed run's spike
        threshold matches the uninterrupted run's."""
        extra = ({"epoch": epoch} if cursor is None
                 else {"epoch": epoch - 1, "next_batch": int(cursor)})
        if self._sentinel is not None:
            extra["sentinel"] = self._sentinel.detector_state()
        return extra

    def _save(self, epoch: int, cursor: int | None = None,
              sync: bool = False) -> None:
        if not self.config.checkpoint_dir:
            return
        from simple_distributed_machine_learning_tpu.train.checkpoint import (
            save_checkpoint,
            save_checkpoint_async,
        )
        extra = self._save_extra(epoch, cursor)
        # every process participates: gathering non-addressable shards is a
        # collective inside save_checkpoint; only process 0 writes the file
        if self.config.async_checkpoint and not sync:
            if self._pending_save is not None:
                self._wait_pending()         # one write in flight at a time
            self._pending_save = save_checkpoint_async(
                self._ckpt_path(), self.buf, self.opt_state,
                self._step_count, extra=extra)
        else:
            save_checkpoint(self._ckpt_path(), self.buf, self.opt_state,
                            self._step_count, extra=extra)

    def _wait_pending(self) -> None:
        """Drain the in-flight async checkpoint write, SURFACING a failed
        write: ``AsyncSave.wait`` re-raises the writer thread's exception
        (original type and traceback — the supervisor's recoverability
        dispatch depends on the type) after a loud diagnostic, instead of
        letting a dead checkpoint pass silently as training success."""
        pending, self._pending_save = self._pending_save, None
        try:
            pending.wait()
        except BaseException as e:
            sys.stderr.write(
                f"[checkpoint] async write to {self._ckpt_path()} FAILED "
                f"({type(e).__name__}: {e}) — surfacing the writer "
                f"thread's error; the previously committed checkpoint is "
                f"intact\n")
            sys.stderr.flush()
            raise

    # -- self-healing training (resilience/sentinel.py) --------------------

    def _sentinel_dir(self) -> str | None:
        """Directory for the quarantine journal (``quarantine.jsonl``);
        None = in-memory journal. ``ElasticTrainer`` overrides this to its
        checkpoint store's directory."""
        return self.config.checkpoint_dir

    @property
    def sentinel(self):
        return self._sentinel

    def sentinel_stats(self) -> dict | None:
        """Cumulative sentinel counters (None when the sentinel is off) —
        the per-epoch metric record and the supervisor's attempt report
        both embed this."""
        return (None if self._sentinel is None
                else self._sentinel.stats())

    def request_stop(self, signum: int | None = None) -> None:
        """Graceful preemption (the CLI's SIGTERM/SIGINT handler calls
        this): the in-flight step finishes, then ``fit`` writes a
        synchronous checkpoint carrying the data cursor, flushes the
        quarantine journal and telemetry, and returns cleanly."""
        self._stop_requested = True
        self._stop_signal = signum

    def _restore_snapshot(self, snap) -> None:
        """Micro-rollback: re-place a ring snapshot's host state onto the
        live shardings (the mirror of ``restore_checkpoint``'s placement —
        mesh-sharded leaves via device_put, scalar optimizer leaves left as
        host values so jit replicates them)."""
        from jax.sharding import NamedSharding
        self.buf = jax.device_put(
            snap.params, NamedSharding(self.pipe.mesh,
                                       self.pipe.param_spec()))
        treedef = jax.tree.structure(self.opt_state)
        live = jax.tree.leaves(self.opt_state)
        leaves = []
        for ref, arr in zip(live, snap.opt_leaves):
            sh = getattr(ref, "sharding", None)
            leaves.append(jax.device_put(arr, sh)
                          if isinstance(sh, NamedSharding) else arr)
        self.opt_state = jax.tree.unflatten(treedef, leaves)
        self._step_count = snap.step

    def _epoch_stream(self, shuffle_seed: int | None, start_idx: int):
        """The epoch's ``(batch_idx, Batch)`` stream from ``start_idx``
        (0 = the whole epoch). Rollback and mid-epoch resume both re-enter
        here: batch order is deterministic per (epoch, seed), so skipping
        forward replays the exact same data the first pass saw."""
        stream = prefetch_batches(self.train_ds, self.config.batch_size,
                                  shuffle_seed=shuffle_seed)
        try:
            for i, b in enumerate(stream):
                if i < start_idx:
                    continue
                yield i, b
        finally:
            stream.close()

    def _apply_numeric_faults(self, x, step: int):
        """Interpret the sentinel's seeded numeric fault kinds
        (``resilience/faults.py``) on the RAW host batch, before any
        feed/sharding: nan-grad scales the inputs by NaN (the backward
        produces NaN gradients and the donated update destroys the
        params), corrupt-batch overflows them to non-finite, loss-spike
        scales them 100x (a large but finite excursion for the EWMA
        detector — f32-safe, unlike corrupt-batch's overflow). Without the sentinel the same sites fire the standard
        effect — a raised NumericFault — so a drill can never pass
        vacuously against an undefended trainer."""
        if faults_active() is None:
            return x
        if self._sentinel is None:
            maybe_fire("train.grad", step=step)
            maybe_fire("data.batch", step=step)
            return x
        fired = (faults_check("train.grad", step=step)
                 + faults_check("data.batch", step=step)
                 + faults_check("train.step", step=step,
                                only=("loss-spike",)))
        for spec in fired:
            if spec.kind == "nan-grad":
                x = np.asarray(x) * np.float32("nan")
            elif spec.kind == "corrupt-batch":
                x = np.asarray(x) * np.float32(1e30)
            elif spec.kind == "loss-spike":
                x = np.asarray(x) * np.float32(100.0)
        return x

    # -- reference console surface (simple_distributed.py:114-117,:130-132) --

    def _print(self, msg: str) -> None:
        if self.is_main:
            print(msg)

    def _feed(self, x, y, w):
        """Batch feed: per-host data-axis slices assembled into global
        arrays on multi-process runs, plain numpy otherwise.

        The slice is taken host-side BEFORE any device transfer, so each
        host's memory traffic is rows/dp, not the global batch — the correct
        multi-host mapping of the reference's master-only loading
        (simple_distributed.py:87-95, SURVEY §7 hard part (c))."""
        if not self._shard_inputs:
            return x, y, w
        import os
        import sys

        from simple_distributed_machine_learning_tpu.data.sharding import (
            host_rows,
            make_global_batch,
        )
        B = len(x)
        # (mesh, B) -> rows is run-invariant; don't pay the sharding-map
        # query on every hot-loop step (train and eval batches are padded to
        # a constant size, so this caches exactly one or two entries)
        lo_hi = self._host_rows_cache.get(B)
        if lo_hi is None:
            lo_hi = self._host_rows_cache[B] = host_rows(self.pipe.mesh, B)
        lo, hi = lo_hi
        if not self._shard_announced:
            self._shard_announced = True
            if os.environ.get("SDML_DEBUG_SHARDING"):
                # stderr + every rank: diagnostics must not touch the
                # reference-format (rank-0-only) stdout surface
                print(f"| host {jax.process_index()}: input rows "
                      f"[{lo},{hi}) of {B}", file=sys.stderr, flush=True)
        mesh = self.pipe.mesh
        xg = make_global_batch(mesh, x[lo:hi], B)
        yg = make_global_batch(mesh, y[lo:hi], B)
        wg = None if w is None else make_global_batch(mesh, w[lo:hi], B)
        return xg, yg, wg

    def train_epoch(self, epoch: int) -> float:
        cfg = self.config
        tele = self.telemetry
        sent = self._sentinel
        meter = Throughput()
        n_total = len(self.train_ds.x)
        n_batches = max(1, (n_total + cfg.batch_size - 1) // cfg.batch_size)
        loss = 0.0
        # batch assembly on the native C++ prefetcher thread when available
        # (transparent python fallback), overlapped with the device step
        shuffle_seed = (cfg.seed * 100003 + epoch) if cfg.shuffle else None
        # mid-epoch resume cursor (graceful-preemption checkpoints only):
        # consumed once, by the first epoch the run re-enters
        start_idx = (self._resume_batch_idx if epoch == self.start_epoch
                     else 0)
        self._resume_batch_idx = 0
        if tele is not None:
            tele.mark()                  # window start = loop entry, not init
        if sent is not None:
            sent.begin_epoch(epoch)      # fresh ring + forced entry snapshot
        stream = self._epoch_stream(shuffle_seed, start_idx)
        first = True                     # first EXECUTED batch of the epoch
        try:
            # explicit next() rather than `for ... in stream`: a rollback
            # REPLACES the stream mid-loop (rewound to the snapshot's data
            # cursor), which a for-loop's captured iterator would ignore
            while True:
                nxt = next(stream, None)
                if nxt is None:
                    break
                batch_idx, b = nxt
                if (cfg.max_steps_per_epoch is not None
                        and batch_idx >= cfg.max_steps_per_epoch):
                    break
                if sent is not None and sent.quarantined(epoch, batch_idx):
                    continue             # deterministic corrupt-batch skip
                step = self._step_count
                # graceful-preemption probe (injected preempt@train.sigterm
                # — the SIGTERM drill's deterministic in-process twin) plus
                # the async SIGTERM/SIGINT flag: checked BEFORE the next
                # step starts, so the in-flight one always finishes
                if faults_check("train.sigterm", step=step):
                    self._stop_requested = True
                if self._stop_requested:
                    self._preempt_cursor = batch_idx
                    break
                if sent is not None:
                    # pre-step snapshot: captured before the (possibly
                    # poisoned) update, so this very step's state is a
                    # valid rollback target
                    sent.maybe_snapshot(step, epoch, batch_idx, self.buf,
                                        self.opt_state)
                # fault-injection site (resilience/faults.py): a scheduled
                # host-kill raises HostLost here (mid-epoch, between steps —
                # the supervisor restores from disk), slow-tick stalls the
                # step; one `is None` check when no plan is installed.
                # loss-spike is the sentinel's kind: interpreted via
                # _apply_numeric_faults below, excluded here
                maybe_fire("train.step", step=step,
                           exclude=(("loss-spike",) if sent is not None
                                    else ()))
                key = jax.random.fold_in(self._key, step)
                # ragged final batch: zero-padded, masked out of the loss
                # mean (the reference just trains on the short batch,
                # :108-113; the weighted mean gives the identical gradient)
                w = None
                if b.n_valid < len(b.x):
                    w = (np.arange(len(b.x)) < b.n_valid).astype(np.float32)
                bx = self._apply_numeric_faults(b.x, step)
                with tracing.span("feed"):
                    x, y, w = self._feed(bx, b.y, w)
                if (tele is not None and first
                        and epoch == self.start_epoch):
                    # register the exact step + shapes for the static
                    # ICI-bytes gauge (trace-only; shapes captured BEFORE
                    # donation). Keyed on the run's first batch — not
                    # _step_count, which a checkpoint resume starts nonzero
                    from simple_distributed_machine_learning_tpu.analysis import (  # noqa: E501
                        abstractify,
                    )
                    tele.set_step_probe(
                        self._train_step, abstractify(self.buf),
                        abstractify(self.opt_state), abstractify(x),
                        abstractify(y), abstractify(key),
                        abstractify(w) if w is not None else None,
                        mesh=self.pipe.mesh)
                gnorm = None
                with tracing.span("step"):
                    if sent is not None:
                        self.buf, self.opt_state, loss, gnorm = \
                            self._train_step(self.buf, self.opt_state,
                                             x, y, key, w)
                    else:
                        self.buf, self.opt_state, loss = self._train_step(
                            self.buf, self.opt_state, x, y, key, w)
                self._step_count += 1
                if sent is not None:
                    # ONE host sync fetches both scalars — the sentinel's
                    # per-step cost (detection cannot be async)
                    loss_f, gnorm_f = (float(v) for v in
                                       jax.device_get((loss, gnorm)))
                    anomaly = sent.observe(step, epoch, batch_idx,
                                           loss_f, gnorm_f)
                    if anomaly is not None:
                        # micro-rollback: restore the newest pre-anomaly
                        # snapshot (params/opt/step/EWMA), rewind the batch
                        # stream to its data cursor and replay forward —
                        # the quarantined batch is skipped on the way
                        # through. Raises SentinelExhausted (supervisor-
                        # recoverable) when anomalies repeat faster than
                        # the ring can absorb.
                        snap = sent.rollback(anomaly)
                        self._restore_snapshot(snap)
                        self._print(
                            f"| sentinel: {anomaly.kind} at step "
                            f"{anomaly.step} (epoch {epoch} batch "
                            f"{anomaly.batch_idx}) — rolled back to step "
                            f"{snap.step}, batch quarantined, replaying")
                        stream.close()
                        stream = self._epoch_stream(shuffle_seed,
                                                    snap.batch_idx)
                        if tele is not None:
                            tele.mark()  # the poisoned window is not a step
                        continue
                meter.update(b.n_valid)
                if tele is not None:
                    # the first batch of the run is forced: that window is
                    # the compile window and the StepTimer keeps it split
                    tele.on_step(
                        loss, examples=b.n_valid,
                        tokens=b.n_valid * self._tokens_per_sample,
                        force_fence=first)
                if first:
                    # first step includes trace+compile; keep it out of the
                    # throughput window (the metric is chip throughput)
                    jax.block_until_ready(loss)
                    meter.reset()
                    first = False
                if batch_idx % cfg.log_interval == 0:
                    self._print(
                        'Train Epoch: {} [{}/{} ({:.0f}%)]\tLoss: '
                        '{:.6f}'.format(
                            epoch, batch_idx * len(b.x), n_total,
                            100.0 * batch_idx / n_batches, float(loss)))
        finally:
            stream.close()
        jax.block_until_ready(self.buf)      # drain async-dispatched steps
        self._last_samples_per_sec = meter.samples_per_sec
        if cfg.print_throughput:
            self._print('| epoch {}: {:.1f} samples/sec'.format(
                epoch, meter.samples_per_sec))
        return float(loss)

    def evaluate(self) -> tuple[float, int]:
        cfg = self.config
        total_loss = 0.0
        correct = 0
        # prediction units: samples for classifiers (y: [N]), tokens for
        # language models (y: [N, T]) — y.size covers both
        n = int(self.test_ds.y.size)
        for b in batches(self.test_ds, cfg.batch_size, pad_last=True):
            with tracing.span("eval"):
                x, y, _ = self._feed(b.x, b.y, None)
                sl, c = self._eval_step(self.buf, x, y, self._key,
                                        np.int32(b.n_valid))
                total_loss += float(sl)      # host read closes the span at
                correct += int(c)            # the batch's true end
        avg = total_loss / n
        self._print(
            '\nTest set: Average loss: {:.4f}, Accuracy: {}/{} ({:.0f}%)\n'
            .format(avg, correct, n, 100.0 * correct / n))
        return avg, correct

    def _log_metrics(self, record: dict) -> None:
        """Per-epoch metrics through the telemetry registry.

        Every field is mirrored into registry instruments (monotonic
        counters for step/correct counts, gauges for the rest) so the same
        numbers ride the Prometheus exposition when telemetry is on; the
        JSONL line keeps every documented key (``accuracy`` is the headline)
        and is now schema-versioned (``"schema": 2`` — schema 1 was the bare
        unversioned record).
        """
        from simple_distributed_machine_learning_tpu.telemetry.registry import (
            append_jsonl,
        )
        reg = self._registry
        if reg is not None:
            # a Telemetry session is attached: its registry (and thus the
            # Prometheus exposition) carries the training series too
            steps = reg.counter("train_steps_total")
            steps.inc(record["step"] - steps.value)
            if record["correct"] is not None:
                reg.counter("eval_correct_total").inc(record["correct"])
            for key in ("train_loss", "eval_loss", "accuracy",
                        "samples_per_sec"):
                if record.get(key) is not None:
                    reg.gauge(key).set(record[key])
        if not (self.config.metrics_json and self.is_main):
            return
        append_jsonl(self.config.metrics_json, record, schema=2)

    def fit(self) -> None:
        """The reference's epoch driver (``simple_distributed.py:134-136``),
        plus per-epoch checkpointing when ``checkpoint_dir`` is set and a
        JSONL metrics record per epoch when ``metrics_json`` is set.

        Graceful preemption (SIGTERM via :meth:`request_stop`, or the
        injected ``preempt@train.sigterm`` fault): the in-flight step
        finishes, a SYNCHRONOUS checkpoint carrying the mid-epoch data
        cursor is written, the quarantine journal and telemetry flush, and
        ``fit`` returns cleanly with ``self.preempted`` set — resume
        re-enters the interrupted epoch at the exact next batch and the
        trajectory is bit-identical to the uninterrupted run."""
        for epoch in range(self.start_epoch, self.config.epochs + 1):
            train_loss = self.train_epoch(epoch)
            if self._stop_requested:
                self._finish_preempt(epoch)
                return
            eval_loss, correct = self.evaluate()
            n_eval = int(self.test_ds.y.size)
            record = {
                "epoch": epoch,
                "step": self._step_count,
                "train_loss": round(train_loss, 6),
                "samples_per_sec": round(self._last_samples_per_sec, 1),
                "eval_loss": round(eval_loss, 6),
                # accuracy is the documented key (--metrics-json help); the
                # raw counts stay so consumers can re-aggregate across epochs
                "accuracy": round(correct / n_eval, 6) if n_eval else None,
                "correct": correct,
                "n_eval": n_eval,
            }
            if self._sentinel is not None:
                # the self-healing block rides every epoch record (and the
                # telemetry epoch record below), so a drill can re-assert
                # rollbacks from metrics.jsonl — not the exit code alone
                record.update(self.sentinel_stats())
                record["anomaly_events"] = self._sentinel.drain_events()
            self._log_metrics(record)
            if self.telemetry is not None:
                # the full per-epoch telemetry record: step-latency
                # quantiles, throughput, memory, bubble estimate, ICI bytes
                # — with the training record's fields riding along
                self.telemetry.on_epoch(epoch, pipe=self.pipe, extra=record)
            self._save(epoch)
        if self._pending_save is not None:
            self._wait_pending()
        if self.telemetry is not None:
            self.telemetry.close()

    def _finish_preempt(self, epoch: int) -> None:
        """The graceful-preemption epilogue: synchronous checkpoint (with
        the data cursor when the stop hit mid-epoch), quarantine-journal
        flush (each quarantine already flushed on append — this is the
        gauge + report), telemetry close, clean return."""
        if self._pending_save is not None:
            self._wait_pending()         # never orphan an in-flight write
        self._save(epoch, cursor=self._preempt_cursor, sync=True)
        # the interrupted epoch's metrics record still lands: a drill that
        # preempts after an anomaly must be able to re-assert rollbacks
        # from metrics.jsonl, and the drained anomaly_events would
        # otherwise be lost with the process
        record: dict = {"epoch": epoch, "step": self._step_count,
                        "preempted": True, "correct": None}
        if self._sentinel is not None:
            record.update(self.sentinel_stats())
            record["anomaly_events"] = self._sentinel.drain_events()
        self._log_metrics(record)
        if self.telemetry is not None:
            self.telemetry.on_epoch(epoch, pipe=self.pipe, extra=record)
        if self._registry is not None:
            self._registry.gauge("train_preempt_graceful").set(1)
        self.preempted = True
        sig = (f"signal {self._stop_signal}"
               if self._stop_signal is not None else "preempt notice")
        where = (f"batch {self._preempt_cursor} of epoch {epoch}"
                 if self._preempt_cursor is not None
                 else f"end of epoch {epoch}")
        # the single source of truth for "did the stop persist anything" —
        # the CLI's closing hint reads this instead of re-deriving it
        self.preempt_persisted = bool(
            self.config.checkpoint_dir
            or getattr(self, "store", None) is not None)
        self._print(
            f"| preempt: graceful stop on {sig} at step "
            f"{self._step_count} ({where}) — "
            + ("synchronous checkpoint + quarantine-journal flush"
               if self.preempt_persisted
               else "no checkpoint_dir configured, state NOT persisted "
               "(quarantine journal flushed)"))
        if self.telemetry is not None:
            self.telemetry.close()
