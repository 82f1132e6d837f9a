"""Reference-compatible CLI and process bootstrap.

Drop-in replacement for the reference's ``__main__`` block
(``/root/reference/simple_distributed.py:138-186``): the same flags launch TPU
hosts instead of RPC processes —

    python -m simple_distributed_machine_learning_tpu.cli --rank=0 --world_size=2 \
        --master_addr=10.0.0.1 --master_port=29500

Flag mapping (north star, BASELINE.json): ``--rank`` → process_id,
``--world_size`` → num_processes, ``--master_addr``/``--master_port`` →
coordinator address for ``jax.distributed.initialize``; ``--interface`` is
accepted for compatibility (the reference exports it as GLOO/TP_SOCKET_IFNAME,
``:164-165``; ICI needs no ifname pinning).

Semantic shift (MPMD → SPMD): in the reference, rank 0 runs the whole trainer
and other ranks idle serving RPCs (``:176-184``). Here every rank runs the
same program on the same data; sharding places each pipeline stage's compute
on its owning devices, and only process 0 prints. There is no shutdown
barrier to call — collectives in the compiled step are the synchronization.

Extensions beyond the reference CLI (hyperparameters surfaced as flags,
model/topology selection) are listed under "framework options".
"""

from __future__ import annotations

import argparse

import jax

from simple_distributed_machine_learning_tpu.utils.compile_cache import (
    enable_compile_cache,
)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="Distributed Machine Learning (TPU-native)",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    # -- reference-compatible flags (simple_distributed.py:144-156) --
    p.add_argument('--rank', type=int, metavar='R',
                   help="Number of rank")
    p.add_argument('--world_size', type=int, default=1, metavar='N',
                   help="Number of workers (processes)")
    p.add_argument('--interface', type=str, default="eth0", metavar='I',
                   help="Accepted for reference compatibility; unused on TPU "
                        "(ICI/DCN need no socket ifname pinning)")
    p.add_argument('--master_addr', type=str, default="localhost", metavar='MA',
                   help="Address of the coordinator (master)")
    p.add_argument('--master_port', type=str, default="29500", metavar='MP',
                   help="Port the coordinator is listening on")
    # -- framework options --
    g = p.add_argument_group("framework options")
    g.add_argument('--model', choices=("lenet", "mlp", "gpt"), default="lenet",
                   help="model family (lenet = the reference's workload)")
    g.add_argument('--stages', type=int, default=None,
                   help="pipeline stages (default: 2 if enough devices else 1)")
    g.add_argument('--microbatches', type=int, default=1,
                   help="GPipe microbatches per step (1 = reference's "
                        "sequential schedule)")
    g.add_argument('--schedule', choices=("gpipe", "1f1b"), default="gpipe",
                   help="pipeline schedule: gpipe = scanned fwd sweep + "
                        "autodiff backward (activation memory grows with "
                        "microbatches); 1f1b = one-forward-one-backward "
                        "(PipeDream-flush) with recompute (memory bounded by "
                        "the stage count; composes with --dp/--tp/--sp/--ep)")
    g.add_argument('--dp', type=int, default=1,
                   help="data-parallel mesh width (batch must divide by "
                        "dp * microbatches)")
    g.add_argument('--tp', type=int, default=1,
                   help="tensor-parallel width: for --model=mlp each stage "
                        "becomes a column->row sharded pair (needs exactly "
                        "2*stages layers in --mlp-dims, hidden widths "
                        "divisible by tp); for --model=gpt every block's "
                        "QKV/O and MLP shard Megatron-style over a 'model' "
                        "mesh axis (n_heads and 4*d_model divisible by tp)")
    g.add_argument('--overlap', choices=("none", "ring"), default="none",
                   help="collective schedule for the tensor-parallel "
                        "all-reduces and the expert-parallel dispatch: none "
                        "= monolithic psum/all_to_all (the chip blocks for "
                        "the whole collective); ring = ppermute-chunked "
                        "latency-hiding collective matmuls "
                        "(parallel/overlap.py) — each chunk's ICI hop hides "
                        "under another chunk's compute, same losses to "
                        "float tolerance")
    g.add_argument('--epochs', type=int, default=10)
    g.add_argument('--batch-size', type=int, default=60)
    g.add_argument('--lr', type=float, default=0.1)
    g.add_argument('--momentum', type=float, default=0.5)
    g.add_argument('--optimizer', choices=("sgd", "adamw"), default="sgd",
                   help="sgd = the reference's SGD(momentum); adamw = "
                        "torch-semantics decoupled weight decay")
    g.add_argument('--weight-decay', type=float, default=0.01,
                   help="weight decay for --optimizer adamw")
    g.add_argument('--lr-schedule',
                   choices=("constant", "cosine", "warmup-cosine", "step"),
                   default="constant",
                   help="learning-rate schedule over the whole run "
                        "(epochs * batches steps); evaluated inside the "
                        "compiled step")
    g.add_argument('--warmup-steps', type=int, default=0,
                   help="linear-warmup steps for --lr-schedule warmup-cosine")
    g.add_argument('--lr-step-size', type=int, default=100,
                   help="steps between decays for --lr-schedule step")
    g.add_argument('--lr-gamma', type=float, default=0.1,
                   help="decay factor for --lr-schedule step")
    g.add_argument('--clip-norm', type=float, default=0.0,
                   help="clip gradients to this global L2 norm before the "
                        "update (torch clip_grad_norm_ semantics; 0 "
                        "disables); replication-corrected on tp/ep meshes")
    g.add_argument('--zero1', action='store_true',
                   help="ZeRO-1: shard optimizer state over the data axis "
                        "(cuts its memory by dp; GSPMD inserts the "
                        "collectives)")
    g.add_argument('--data-root', type=str, default="data",
                   help="directory with MNIST IDX files (synthetic fallback "
                        "if absent)")
    g.add_argument('--seed', type=int, default=0)
    g.add_argument('--shuffle', action='store_true',
                   help="seeded per-epoch shuffle of the train set (off by "
                        "default: the reference trains in fixed order)")
    g.add_argument('--mlp-dims', type=str, default="784,512,10",
                   help="comma-separated layer widths for --model=mlp")
    g.add_argument('--checkpoint-dir', type=str, default=None,
                   help="write a checkpoint after every epoch and auto-resume "
                        "from it on restart (the reference loses all progress "
                        "on a crash)")
    g.add_argument('--no-resume', action='store_true',
                   help="with --checkpoint-dir: start fresh, ignore an "
                        "existing checkpoint")
    g.add_argument('--async-checkpoint', action='store_true',
                   help="overlap the checkpoint file write with the next "
                        "epoch (the sharded gather stays synchronous)")
    g.add_argument('--eval-only', action='store_true',
                   help="skip training: evaluate the checkpoint-restored "
                        "(or fresh-initialized) params on the test set and "
                        "exit")
    g.add_argument('--experts', type=int, default=0,
                   help="for --model=gpt: replace each block's MLP with a "
                        "top-2-routed mixture of this many experts (0 = dense)")
    g.add_argument('--sp', type=int, default=1,
                   help="sequence-parallel width for --model=gpt: shards the "
                        "token axis over a 'seq' mesh axis (requires "
                        "--attn ring or ulysses)")
    g.add_argument('--ep', type=int, default=1,
                   help="expert-parallel width for --model=gpt with "
                        "--experts: shards expert weights over an 'expert' "
                        "mesh axis with all-to-all dispatch")
    g.add_argument('--generate', type=int, default=0, metavar="N",
                   help="for --model=gpt: after training, decode N tokens "
                        "from the trained model (KV-cache, straight from "
                        "the live param buffer) and print them on rank 0 — "
                        "with --text-corpus, decoded bytes as text")
    g.add_argument('--serve-sim', type=int, default=0, metavar="N",
                   help="for --model=gpt: skip training and serve N "
                        "simulated requests through the continuous-batching "
                        "inference engine (serve/): seeded Poisson arrivals, "
                        "FCFS admission into a block-table paged KV-cache "
                        "pool (prefix sharing + copy-on-write + chunked "
                        "prefill), EOS/budget retirement freeing memory "
                        "mid-flight; "
                        "params restore from --checkpoint-dir when a "
                        "checkpoint exists, else fresh init; TTFT/TPOT and "
                        "occupancy metrics land in --telemetry-dir")
    g.add_argument('--serve-rate', type=float, default=8.0, metavar="R",
                   help="with --serve-sim: mean request arrival rate "
                        "(req/s) of the open-loop Poisson trace")
    g.add_argument('--serve-slots', type=int, default=4, metavar="S",
                   help="with --serve-sim: KV-cache pool slots (the "
                        "continuous batch's max occupancy)")
    g.add_argument('--serve-max-new', type=int, default=16, metavar="T",
                   help="with --serve-sim: tokens generated per request "
                        "(EOS may retire a request earlier)")
    g.add_argument('--serve-block-size', type=int, default=16, metavar="B",
                   help="with --serve-sim: positions per K/V block of the "
                        "paged cache pool (serve/slots.py PagedKVPool) — "
                        "smaller blocks waste less tail memory and share "
                        "prefixes at finer grain, larger blocks gather "
                        "fewer pages per attention step")
    g.add_argument('--serve-prefill-chunk', type=int, default=0, metavar="C",
                   help="with --serve-sim: prompt positions prefilled per "
                        "engine tick (chunked prefill — each tick runs at "
                        "most one chunk, then the batched decode step, so "
                        "a long prompt cannot stall in-flight decodes); "
                        "0 = whole prompt in one chunk")
    g.add_argument('--serve-shared-prefix', type=int, default=0, metavar="N",
                   help="with --serve-sim: prepend ONE seeded common "
                        "N-token prefix to every simulated prompt (the "
                        "system-prompt case) — the paged pool serves the "
                        "prefix from shared physical blocks, copy-on-write "
                        "at divergence")
    g.add_argument('--serve-tp', type=int, default=1, metavar="T",
                   help="with --serve-sim: tensor-parallel width of the "
                        "serving programs — every tick runs head-sharded "
                        "QKV/O + collective-matmul MLP over T chips of the "
                        "mesh's model axis and the K/V pool shards its "
                        "head axis, so per-chip KV bytes drop by T "
                        "(needs T devices; T must divide n_heads)")
    g.add_argument('--serve-spec-k', type=int, default=0, metavar="K",
                   help="with --serve-sim: speculative decoding — a small "
                        "draft model (half the target's layers, fresh "
                        "init) proposes K tokens per slot per tick and "
                        "the target verifies all K in ONE batched step, "
                        "emitting 1..K tokens; greedy streams stay "
                        "bit-exact vs solo decode. 0 = plain one-token "
                        "decode; K >= 2 enables the draft/verify tick")
    g.add_argument('--serve-chaos', type=str, default=None, metavar='SPEC',
                   help="with --serve-sim: serve under a deterministic "
                        "fault schedule through the crash-restartable "
                        "serve supervisor (serve/supervisor.py) — on an "
                        "injected engine-crash/wedged-device the engine "
                        "is rebuilt and every in-flight request recovers "
                        "BIT-EXACT from the fsync'd request journal "
                        "(resume from the last journaled token, key "
                        "stream intact). Same grammar as --chaos, e.g. "
                        "'engine-crash@serve.tick=5'; sites serve.tick "
                        "and serve.admit")
    g.add_argument('--serve-deadline-ms', type=float, default=0.0,
                   metavar='D',
                   help="with --serve-sim: per-request completion "
                        "deadline in ms, enforced by the serve "
                        "supervisor at tick boundaries — an expired "
                        "request is SHED with a structured rejection and "
                        "its slot/block budget refunded (0 = no "
                        "deadline). The run exits 0 when every request "
                        "either completed or was structurally shed")
    g.add_argument('--serve-max-restarts', type=int, default=3,
                   help="with --serve-chaos: engine-rebuild budget before "
                        "the serve supervisor fails the run loudly")
    g.add_argument('--serve-replicas', type=int, default=0, metavar='N',
                   help="with --serve-sim: serve through a FLEET of N "
                        "supervised engine replicas behind a health-aware "
                        "router (serve/fleet.py) — prefix-cache-affinity "
                        "routing, per-replica journals "
                        "(journal-r<i>.jsonl), and journal-backed "
                        "cross-replica migration: killing a whole replica "
                        "(--serve-chaos 'replica-kill@fleet.tick=5') "
                        "re-admits its in-flight requests onto the "
                        "survivors bit-exact from its journal alone. "
                        "0 = the single-engine paths above")
    g.add_argument('--serve-route',
                   choices=("affinity", "least-loaded", "round-robin"),
                   default="affinity",
                   help="with --serve-replicas: routing policy — "
                        "affinity routes to the replica whose paged pool "
                        "already holds the prompt's registered prefix "
                        "blocks (least-loaded fallback); least-loaded "
                        "orders by queue depth then occupancy; "
                        "round-robin is the affinity-blind baseline")
    g.add_argument('--serve-autoscale', type=str, default=None,
                   metavar='MIN,MAX',
                   help="with --serve-replicas: enable the fleet "
                        "autoscaler between MIN and MAX replicas — "
                        "scale-out on sustained queue backlog (or paged "
                        "KV residency), drain-then-retire on idle "
                        "(serve/fleet.py::AutoscalePolicy)")
    g.add_argument('--serve-prefill-replicas', type=int, default=0,
                   metavar='N',
                   help="with --serve-replicas: DISAGGREGATE the fleet — "
                        "the first N replicas form the prefill pool (new "
                        "requests board there only) and the rest the "
                        "decode pool; every request hands off at "
                        "end-of-prefill by the journal snap/adopt move "
                        "(serve/fleet.py). Mutually exclusive with "
                        "--serve-autoscale")
    g.add_argument('--serve-host-blocks', type=int, default=0, metavar='N',
                   help="with --serve-sim: host-RAM offload tier of N "
                        "blocks per replica behind the paged KV pool — "
                        "LRU-evicted prefix blocks demote to host instead "
                        "of dying, and a router affinity hit on a "
                        "host-resident prefix starts the async prefetch "
                        "upload at routing time (serve/slots.py)")
    g.add_argument('--serve-prefetch-ticks', type=int, default=1,
                   metavar='T',
                   help="with --serve-host-blocks: engine ticks one "
                        "host->HBM prefetch upload takes (the modeled "
                        "PCIe/DMA latency; boarding blocks until the "
                        "upload lands)")
    g.add_argument('--serve-adapters', type=int, default=0, metavar='N',
                   help="with --serve-sim: multi-tenant LoRA serving "
                        "(serve/adapters.py) — register N per-tenant "
                        "low-rank adapters (tenant-0..tenant-N-1) over "
                        "the SHARED base weights and split arrivals "
                        "evenly across them; each decode tick gathers "
                        "per-slot adapter rows from one device-resident "
                        "bank, so ONE compiled program serves any tenant "
                        "mix (no per-tenant retrace, no merged weight "
                        "copies). With --serve-replicas the router "
                        "prefers a replica where the request's adapter "
                        "is already resident (adapter-affinity)")
    g.add_argument('--serve-adapter-rank', type=int, default=4,
                   metavar='R',
                   help="with --serve-adapters: the low-rank dimension r "
                        "of every adapter's A/B factors (bank HBM scales "
                        "linearly with r; see models/lora.py bank_bytes)")
    g.add_argument('--serve-trace', action='store_true',
                   help="with --serve-sim/--scenario and --telemetry-dir: "
                        "request-scoped tracing (serve/tracing.py) — a "
                        "per-rid async span timeline (queue wait, prefill "
                        "chunks, decode/spec ticks, preempt/resume, crash "
                        "re-admission) written as serve_trace*.json "
                        "(chrome://tracing / Perfetto) plus a "
                        "request_timeline*.jsonl the report CLI reads "
                        "(python -m ...telemetry.report). Off by default: "
                        "the hot path pays nothing when disabled, and "
                        "spans join across supervisor restarts (the "
                        "journal rid is the trace id)")
    g.add_argument('--text-corpus', default=None, metavar="PATH",
                   help="for --model=gpt: train on the BYTES of this local "
                        "file (vocab=256, next-byte LM, contiguous "
                        "train/test split) instead of the synthetic Markov "
                        "stream — the reference's real-data-first sourcing "
                        "mapped to a zero-egress environment")
    g.add_argument('--attn', choices=("dense", "flash", "ring", "ulysses"),
                   default="dense",
                   help="attention implementation for --model=gpt (flash = "
                        "Pallas fused kernel; ring/ulysses = sequence-"
                        "parallel collectives, used with --sp)")
    g.add_argument('--flash-blocks', type=str, default=None, metavar='Q,K',
                   help="with --attn flash: kernel block sizes, e.g. "
                        "512,512 (defaults 128,128; tune with "
                        "benchmarks/flash_tune.py)")
    g.add_argument('--bf16', action='store_true',
                   help="bfloat16 compute (float32 master params and loss): "
                        "doubles MXU throughput, halves HBM traffic")
    g.add_argument('--remat', action='store_true',
                   help="rematerialize stage activations in backward "
                        "(jax.checkpoint): trades FLOPs for memory")
    g.add_argument('--metrics-json', type=str, default=None, metavar='PATH',
                   help='append one JSON line of metrics per epoch (epoch, '
                        'step, train_loss, samples_per_sec, eval_loss, '
                        'accuracy, plus the raw correct/n_eval counts) — '
                        'the machine-readable counterpart of the '
                        'reference-format console output')
    g.add_argument('--profile', type=str, default=None, metavar='DIR',
                   help="capture an XProf/TensorBoard trace of the whole run "
                        "into DIR")
    g.add_argument('--telemetry-dir', type=str, default=None, metavar='DIR',
                   help="structured run telemetry (telemetry/): per-epoch "
                        "metrics.jsonl (step-latency p50/p95, examples/sec "
                        "and tokens/sec, live-array bytes, pipeline bubble "
                        "fraction, expected ICI bytes/step), trace.json "
                        "(Chrome-trace host spans for feed/step/eval — open "
                        "in chrome://tracing or ui.perfetto.dev, no XProf "
                        "needed) and metrics.prom (Prometheus text "
                        "exposition) written into DIR")
    g.add_argument('--telemetry-every', type=int, default=1, metavar='N',
                   help="with --telemetry-dir: fence the device and sample "
                        "step latency every Nth step; 1 = exact per-step "
                        "latency, larger N keeps async dispatch overlapped "
                        "and attributes each fenced window to its N steps")
    g.add_argument('--max-steps-per-epoch', type=int, default=None,
                   metavar='N',
                   help="cap every training epoch at N batches (full "
                        "epochs by default) — the knob short CI runs and "
                        "the --chaos smoke use to keep multi-epoch runs "
                        "cheap without collapsing them to one epoch like "
                        "--dryrun does")
    g.add_argument('--sentinel', action='store_true',
                   help="self-healing training (resilience/sentinel.py): "
                        "check every step's loss/grad-norm for NaN/Inf and "
                        "EWMA loss spikes, keep a bounded in-memory ring of "
                        "host snapshots, and on an anomaly roll back to the "
                        "newest pre-anomaly snapshot, quarantine the "
                        "offending batch (appended to quarantine.jsonl "
                        "under --checkpoint-dir and deterministically "
                        "skipped from then on) and replay forward — "
                        "bit-exact vs a run that never saw the fault. "
                        "Repeated anomalies escalate to the --chaos elastic "
                        "supervisor (full disk restore). Also arms the "
                        "numeric fault sites nan-grad@train.grad, "
                        "corrupt-batch@data.batch, loss-spike@train.step "
                        "for --chaos drills")
    g.add_argument('--sentinel-window', type=int, default=16, metavar='W',
                   help="with --sentinel: EWMA horizon for the loss-spike "
                        "detector AND the escalation window (more than "
                        "ring-size anomalies within W steps raise to the "
                        "supervisor)")
    g.add_argument('--sentinel-snapshot-every', type=int, default=4,
                   metavar='K',
                   help="with --sentinel: steps between in-memory snapshot-"
                        "ring entries (rollback replays at most K-1 steps; "
                        "smaller K = cheaper recovery, more frequent host "
                        "gathers)")
    g.add_argument('--chaos', type=str, default=None, metavar='SPEC',
                   help="resilience drill (resilience/): train under a "
                        "deterministic fault-injection schedule with the "
                        "elastic checkpoint-restart supervisor — on an "
                        "injected host-kill (or other recoverable fault) "
                        "the run restores the latest VALID checkpoint from "
                        "--checkpoint-dir (checksum-verified manifest), "
                        "repacks it onto the surviving stage count and "
                        "resumes. SPEC grammar: 'kind@site[=step]"
                        "[,key=val...]' entries joined by ';' — e.g. "
                        "'host-kill@train.step=6'; kinds: host-kill, "
                        "frozen-peer, slow-tick, ckpt-write-crash, "
                        "wedged-device. Requires --checkpoint-dir; "
                        "--model mlp or gpt")
    g.add_argument('--chaos-stages', type=str, default=None, metavar='S1,S2',
                   help="with --chaos: the stage-count ladder the "
                        "supervisor falls back through on host/peer loss "
                        "(largest first, e.g. 2,1 = restart-and-repack "
                        "onto 1 stage after losing a host at 2); default: "
                        "stay at the launch stage count")
    g.add_argument('--chaos-max-restarts', type=int, default=3,
                   help="with --chaos: recoverable-failure restart budget "
                        "before the run FAILS loudly")
    g.add_argument('--scenario', type=str, default=None, metavar='NAME',
                   help="run one SLO-gated serving scenario "
                        "(resilience/scenarios.py): deterministic bursty/"
                        "diurnal/multi-tenant traffic with per-class "
                        "TTFT/TPOT targets through the continuous-batching "
                        "engine on a virtual clock; priority scheduling "
                        "with prefill preemption protects interactive "
                        "traffic. Exits nonzero unless every class attains "
                        "its SLOs and every request completes; per-class "
                        "attainment lands in --telemetry-dir. NAME 'list' "
                        "prints the catalog")
    g.add_argument('--dryrun', type=int, default=0, metavar='N',
                   help="smoke mode: train only N batches of a single epoch "
                        "(then the normal eval) and exit — the cheap "
                        "end-to-end check CI pairs with --telemetry-dir")
    g.add_argument('--lint', action='store_true',
                   help="static-analysis preflight (analysis/): trace the "
                        "exact compiled steps this run is about to execute "
                        "and lint them before any device executes one — "
                        "train+eval steps for a training run (ppermute "
                        "deadlocks, unreduced gradients, mesh-axis "
                        "validity, dtype drift, donation hazards); the "
                        "whole serving-program registry for --serve-sim "
                        "(KV scatter-bounds, donated-buffer flow through "
                        "the tick, retrace policy, HBM bytes/tick); abort "
                        "on ERROR findings")
    g.add_argument('--lint-only', action='store_true',
                   help="run the --lint preflight and exit without "
                        "training/serving (exit 0 clean, 2 on ERROR "
                        "findings)")
    g.add_argument('--peer-timeout', type=float, default=60.0,
                   help="multi-process dead-peer watchdog: abort with a "
                        "nonzero exit if a peer crashes or stops "
                        "heartbeating for this many seconds (0 disables; "
                        "the reference hangs forever on a dead peer)")
    g.add_argument('--heartbeat-port', type=int, default=None,
                   help="TCP port for the dead-peer watchdog "
                        "(default: master_port + 1)")
    return p


def main(argv: list[str] | None = None) -> None:
    enable_compile_cache()
    args = build_parser().parse_args(argv)
    assert args.rank is not None or args.world_size == 1, \
        "Must provide rank argument."  # reference :160

    from simple_distributed_machine_learning_tpu.parallel.mesh import (
        bootstrap_distributed,
    )

    bootstrap_distributed(args.rank or 0, args.world_size,
                          args.master_addr, args.master_port)

    watchdog = None
    if args.world_size > 1 and args.peer_timeout > 0:
        from simple_distributed_machine_learning_tpu.utils.failure import (
            spawn_watchdog,
        )
        hb_port = (args.heartbeat_port if args.heartbeat_port is not None
                   else int(args.master_port) + 1)
        # a SUBPROCESS, not threads: in-process watchdog threads freeze when
        # the main thread blocks in a native collective holding the GIL
        # (utils/failure.py module docstring)
        watchdog = spawn_watchdog(
            args.rank or 0, args.world_size, args.master_addr, hb_port,
            timeout=args.peer_timeout)

    try:
        _dispatch(args)
    except BaseException:
        # crash path: kill the monitor abruptly (no goodbye — peers must
        # read the disconnect as a failure) and disarm its kill_parent, so
        # a programmatic main() caller that catches this exception is not
        # SIGKILLed by an orphaned monitor minutes later
        if watchdog is not None:
            watchdog.abort()
        raise
    # goodbye ONLY on success
    if watchdog is not None:
        watchdog.stop()


def _dispatch(args) -> None:
    n_dev = len(jax.devices())
    n_stages = args.stages if args.stages is not None else (2 if n_dev >= 2 else 1)

    key = jax.random.key(args.seed)
    if args.dryrun < 0:
        raise SystemExit(f"--dryrun needs a non-negative step count, got "
                         f"{args.dryrun}")
    if args.tp > 1 and args.model not in ("mlp", "gpt"):
        raise SystemExit("--tp is only supported with --model=mlp or gpt")
    if args.sp > 1 and args.model != "gpt":
        raise SystemExit("--sp is only supported with --model=gpt")
    if args.ep > 1 and (args.model != "gpt" or args.experts < 1):
        raise SystemExit("--ep needs --model=gpt with --experts > 0")
    if args.generate > 0 and args.model != "gpt":
        raise SystemExit("--generate is only supported with --model=gpt")
    if args.max_steps_per_epoch is not None and args.max_steps_per_epoch < 1:
        raise SystemExit(f"--max-steps-per-epoch must be >= 1, got "
                         f"{args.max_steps_per_epoch}")
    if args.sentinel_window < 2:
        raise SystemExit(f"--sentinel-window must be >= 2, got "
                         f"{args.sentinel_window}")
    if args.sentinel_snapshot_every < 1:
        raise SystemExit(f"--sentinel-snapshot-every must be >= 1, got "
                         f"{args.sentinel_snapshot_every}")
    if args.scenario is not None:
        _run_scenario(args, n_stages, key)
        return
    if args.chaos is not None:
        _run_chaos(args, n_stages, key)
        return
    if args.serve_sim > 0:
        if args.model != "gpt":
            raise SystemExit("--serve-sim is only supported with "
                             "--model=gpt")
        if args.experts > 0 or args.sp > 1 or args.tp > 1 or args.ep > 1:
            raise SystemExit(
                "--serve-sim serves a dense single-device build (the "
                "make_cached_decoder restrictions): drop "
                "--experts/--sp/--tp/--ep")
        _run_serve(args, n_stages, key)
        return
    if args.model == "gpt":
        _run_gpt(args, n_stages, key)
        return
    if args.model == "lenet":
        from simple_distributed_machine_learning_tpu.models.lenet import (
            make_lenet_stages,
        )
        stages, wire_dim, out_dim = make_lenet_stages(key, n_stages)
        in_is_image = True
    elif args.tp > 1:
        from simple_distributed_machine_learning_tpu.parallel.tensor import (
            make_mlp_tp_stages,
        )
        dims = [int(d) for d in args.mlp_dims.split(",")]
        stages, wire_dim, out_dim = make_mlp_tp_stages(key, dims, n_stages,
                                                       args.tp,
                                                       overlap=args.overlap)
        in_is_image = False
    else:
        from simple_distributed_machine_learning_tpu.models.mlp import (
            make_mlp_stages,
        )
        dims = [int(d) for d in args.mlp_dims.split(",")]
        stages, wire_dim, out_dim = make_mlp_stages(key, dims, n_stages)
        in_is_image = False

    from simple_distributed_machine_learning_tpu.data.mnist import (
        Dataset,
        load_mnist,
    )
    train_ds, test_ds = load_mnist(args.data_root)
    if not in_is_image:
        train_ds = Dataset(train_ds.x.reshape(len(train_ds.x), -1), train_ds.y)
        test_ds = Dataset(test_ds.x.reshape(len(test_ds.x), -1), test_ds.y)

    from simple_distributed_machine_learning_tpu.parallel.mesh import make_mesh
    from simple_distributed_machine_learning_tpu.parallel.pipeline import Pipeline
    from simple_distributed_machine_learning_tpu.train.trainer import (
        Trainer,
    )

    mesh = make_mesh(n_stages=n_stages, n_data=args.dp, n_model=args.tp)
    pipe = Pipeline(stages, mesh, wire_dim, out_dim,
                    n_microbatches=args.microbatches,
                    compute_dtype=_compute_dtype(args), remat=args.remat,
                    schedule=args.schedule, overlap=args.overlap)
    config = _train_config(args)
    _fit(args, Trainer(pipe, train_ds, test_ds, config,
                       opt=_make_opt(args, _total_steps(args, train_ds),
                                     pipe),
                       telemetry=_telemetry(args)))


def _compute_dtype(args):
    if not args.bf16:
        return None
    import jax.numpy as jnp
    return jnp.bfloat16


def _train_config(args):
    from simple_distributed_machine_learning_tpu.train.trainer import (
        TrainConfig,
    )
    return TrainConfig(
        # --dryrun N: N batches of one epoch, the cheap end-to-end smoke;
        # --max-steps-per-epoch caps every epoch without collapsing to one
        epochs=1 if args.dryrun else args.epochs,
        max_steps_per_epoch=args.dryrun or args.max_steps_per_epoch,
        batch_size=args.batch_size,
        learning_rate=args.lr, momentum=args.momentum,
        seed=args.seed, checkpoint_dir=args.checkpoint_dir,
        resume=not args.no_resume, zero1=args.zero1,
        async_checkpoint=args.async_checkpoint,
        shuffle=args.shuffle,
        metrics_json=args.metrics_json,
        sentinel=args.sentinel,
        sentinel_window=args.sentinel_window,
        sentinel_snapshot_every=args.sentinel_snapshot_every)


def _telemetry(args):
    if not args.telemetry_dir:
        return None
    if args.telemetry_every < 1:
        raise SystemExit(f"--telemetry-every must be >= 1, got "
                         f"{args.telemetry_every}")
    from simple_distributed_machine_learning_tpu.telemetry import Telemetry
    return Telemetry(args.telemetry_dir, every=args.telemetry_every)


def _make_opt(args, total_steps: int, pipe=None):
    from simple_distributed_machine_learning_tpu.train.optimizer import (
        adamw,
        clip_by_global_norm,
        sgd,
    )
    from simple_distributed_machine_learning_tpu.train import schedules

    if args.lr_schedule == "cosine":
        lr = schedules.cosine(args.lr, total_steps)
    elif args.lr_schedule == "warmup-cosine":
        lr = schedules.warmup_cosine(args.lr, args.warmup_steps, total_steps)
    elif args.lr_schedule == "step":
        lr = schedules.step_decay(args.lr, args.lr_step_size, args.lr_gamma)
    else:
        lr = args.lr
    if args.optimizer == "adamw":
        opt = adamw(lr, weight_decay=args.weight_decay)
    else:
        opt = sgd(lr, args.momentum)
    if args.clip_norm > 0:
        weights = pipe.replication_weights() if pipe is not None else None
        opt = clip_by_global_norm(opt, args.clip_norm, weights)
    return opt


def _total_steps(args, train_ds) -> int:
    """The LR-schedule horizon: steps the run will actually execute —
    honoring --max-steps-per-epoch, so a capped run's cosine/warmup
    schedule sweeps its full range instead of idling at the initial LR."""
    per_epoch = max(1, -(-len(train_ds.x) // args.batch_size))
    if args.max_steps_per_epoch is not None:
        per_epoch = min(per_epoch, args.max_steps_per_epoch)
    return args.epochs * per_epoch


def _fit(args, trainer) -> None:
    if args.lint or args.lint_only:
        # the preflight gate: lint the EXACT compiled steps this trainer is
        # about to execute (same pipeline, optimizer, donation and batch
        # shapes) — zero FLOPs, no device buffers touched
        from simple_distributed_machine_learning_tpu.analysis.preflight import (
            lint_trainer,
        )
        report = lint_trainer(trainer)
        trainer._print(report.format(costs=True))
        if not report.ok():
            raise SystemExit(2)
        trainer._print("| --lint: preflight clean")
        if args.lint_only:
            return
    if args.eval_only:
        # evaluate the restored (or fresh-init, if no checkpoint) params
        # without training — the companion to --checkpoint-dir resume
        if args.checkpoint_dir and trainer.start_epoch == 1:
            trainer._print("| --eval-only: no checkpoint found, evaluating "
                           "fresh-initialized params")
        trainer.evaluate()
        if trainer.telemetry is not None:
            trainer.telemetry.close()    # eval spans -> trace.json
        return
    # graceful preemption: SIGTERM/SIGINT finish the in-flight step, write
    # a synchronous checkpoint carrying the mid-epoch data cursor, flush
    # the quarantine journal + telemetry and exit 0 — the training mirror
    # of the --serve-sim handler (a rollout must not look like a fault)
    import signal

    def _on_signal(signum, frame):
        trainer.request_stop(signum)

    old_handlers = {}
    try:
        for s in (signal.SIGTERM, signal.SIGINT):
            old_handlers[s] = signal.signal(s, _on_signal)
    except ValueError:
        old_handlers = {}              # not the main thread: no handlers
    try:
        if args.profile:
            from simple_distributed_machine_learning_tpu.utils.profiler import (
                trace,
            )
            with trace(args.profile):
                trainer.fit()
        else:
            trainer.fit()
    finally:
        for s, h in old_handlers.items():
            signal.signal(s, h)
    stats = trainer.sentinel_stats()
    if stats is not None:
        trainer._print(
            f"| sentinel: absorbed {stats['anomalies']} anomal"
            f"{'y' if stats['anomalies'] == 1 else 'ies'} "
            f"({stats['rollbacks']} rollback(s), "
            f"{stats['quarantined_batches']} quarantined batch(es), "
            f"ring {stats['snapshot_ring_bytes']} bytes)")
    if trainer.preempted:
        trainer._print(
            "| train: graceful shutdown complete — "
            + ("resume with the same --checkpoint-dir to continue "
               "bit-exact" if trainer.preempt_persisted
               else "no --checkpoint-dir was configured, so the "
               "interrupted progress was NOT persisted"))


def _run_gpt(args, n_stages: int, key) -> None:
    """--model gpt: tiny-GPT LM on a synthetic Markov token stream
    (BASELINE.json config 5), same trainer/console surface."""
    import numpy as np

    from simple_distributed_machine_learning_tpu.data.mnist import Dataset
    from simple_distributed_machine_learning_tpu.data.text import synthetic_tokens
    from simple_distributed_machine_learning_tpu.models.gpt import (
        GPTConfig,
        make_gpt_stages,
    )
    from simple_distributed_machine_learning_tpu.parallel.mesh import make_mesh
    from simple_distributed_machine_learning_tpu.parallel.pipeline import Pipeline
    from simple_distributed_machine_learning_tpu.train.trainer import (
        Trainer,
    )

    fb = {}
    if args.flash_blocks:
        if args.attn != "flash":
            raise SystemExit("--flash-blocks needs --attn flash")
        try:
            bq, bk = (int(v) for v in args.flash_blocks.split(","))
        except ValueError:
            raise SystemExit(
                f"--flash-blocks expects Q,K integers, got "
                f"{args.flash_blocks!r}") from None
        fb = {"flash_block_q": bq, "flash_block_k": bk}
    cfg = GPTConfig(vocab=256 if args.text_corpus else 128,
                    n_experts=args.experts,
                    moe_top_k=min(2, max(1, args.experts)),
                    attn_impl=args.attn, n_seq=args.sp,
                    n_expert_parallel=args.ep,
                    n_tensor_parallel=args.tp, overlap=args.overlap, **fb)
    stages, wire_dim, out_shape = make_gpt_stages(key, cfg, n_stages)
    def as_ds(x, y):
        return Dataset(x.astype(np.float32), y)

    if args.text_corpus:
        # real data: next-byte LM over a local file (data/text.py)
        from simple_distributed_machine_learning_tpu.data.text import (
            byte_corpus,
        )
        tr, te = byte_corpus(args.text_corpus, cfg.seq_len)
        train_ds, test_ds = as_ds(*tr), as_ds(*te)
    else:
        # one Markov chain, disjoint train/test sequences (a different seed
        # would regenerate a different transition matrix — nothing would
        # transfer)
        all_data = synthetic_tokens(7000, cfg.seq_len, cfg.vocab,
                                    seed=args.seed)
        train_ds = as_ds(all_data.x[:6000], all_data.y[:6000])
        test_ds = as_ds(all_data.x[6000:], all_data.y[6000:])

    mesh = make_mesh(n_stages=n_stages, n_data=args.dp, n_model=args.tp,
                     n_seq=args.sp, n_expert=args.ep)
    pipe = Pipeline(stages, mesh, wire_dim, out_shape,
                    n_microbatches=args.microbatches,
                    compute_dtype=_compute_dtype(args), remat=args.remat,
                    schedule=args.schedule, overlap=args.overlap)
    config = _train_config(args)
    trainer = Trainer(pipe, train_ds, test_ds, config,
                      opt=_make_opt(args, _total_steps(args, train_ds),
                                    pipe),
                      telemetry=_telemetry(args))
    _fit(args, trainer)
    if args.generate > 0:
        _print_sample(args, trainer, cfg, test_ds)


def _run_serve(args, n_stages: int, key) -> None:
    """--serve-sim N: continuous-batching inference over a simulated
    open-loop Poisson trace (serve/). Params come from --checkpoint-dir
    when a checkpoint exists (the same build the training run wrote),
    otherwise fresh init; no training happens. Exits nonzero if any
    request fails to complete."""
    import os

    from simple_distributed_machine_learning_tpu.models.gpt import (
        GPTConfig,
        make_gpt_stages,
    )
    from simple_distributed_machine_learning_tpu.serve import (
        InferenceEngine,
        ServeMetrics,
        SimConfig,
        TrafficClass,
        simulate,
    )

    if args.serve_slots < 1:
        raise SystemExit(f"--serve-slots must be >= 1, got "
                         f"{args.serve_slots}")
    if args.serve_max_new < 1:
        raise SystemExit(f"--serve-max-new must be >= 1, got "
                         f"{args.serve_max_new}")
    if args.serve_block_size < 1:
        raise SystemExit(f"--serve-block-size must be >= 1, got "
                         f"{args.serve_block_size}")
    if args.serve_prefill_chunk < 0:
        raise SystemExit(f"--serve-prefill-chunk must be >= 1 (or 0 for "
                         f"whole-prompt chunks), got "
                         f"{args.serve_prefill_chunk}")
    if args.serve_shared_prefix < 0:
        raise SystemExit(f"--serve-shared-prefix must be >= 0, got "
                         f"{args.serve_shared_prefix}")
    if args.serve_tp < 1:
        raise SystemExit(f"--serve-tp must be >= 1, got {args.serve_tp}")
    if args.serve_spec_k == 1 or args.serve_spec_k < 0:
        raise SystemExit(f"--serve-spec-k must be 0 (plain decode) or "
                         f">= 2, got {args.serve_spec_k}")
    if args.serve_deadline_ms < 0:
        raise SystemExit(f"--serve-deadline-ms must be >= 0 (0 = none), "
                         f"got {args.serve_deadline_ms}")
    if args.serve_max_restarts < 0:
        raise SystemExit(f"--serve-max-restarts must be >= 0, got "
                         f"{args.serve_max_restarts}")
    if args.serve_replicas < 0:
        raise SystemExit(f"--serve-replicas must be >= 0 (0 = single "
                         f"engine), got {args.serve_replicas}")
    if args.serve_route != "affinity" and not args.serve_replicas:
        raise SystemExit("--serve-route needs --serve-replicas (a single "
                         "engine has nothing to route between)")
    autoscale = None
    if args.serve_autoscale:
        if not args.serve_replicas:
            raise SystemExit("--serve-autoscale needs --serve-replicas")
        from simple_distributed_machine_learning_tpu.serve import (
            AutoscalePolicy,
        )
        try:
            lo, hi = (int(v) for v in args.serve_autoscale.split(","))
            autoscale = AutoscalePolicy(min_replicas=lo, max_replicas=hi)
        except ValueError as e:
            raise SystemExit(f"bad --serve-autoscale (expected MIN,MAX "
                             f"integers): {e}") from None
        if not lo <= args.serve_replicas <= hi:
            raise SystemExit(
                f"--serve-replicas {args.serve_replicas} outside the "
                f"--serve-autoscale bounds [{lo}, {hi}]")
    if args.serve_prefill_replicas:
        if not args.serve_replicas:
            raise SystemExit("--serve-prefill-replicas needs "
                             "--serve-replicas (pools split a fleet)")
        if not 0 < args.serve_prefill_replicas < args.serve_replicas:
            raise SystemExit(
                f"--serve-prefill-replicas must leave at least one decode "
                f"replica (0 < N < {args.serve_replicas}), got "
                f"{args.serve_prefill_replicas}")
        if args.serve_autoscale:
            raise SystemExit("--serve-prefill-replicas and "
                             "--serve-autoscale are mutually exclusive "
                             "(the autoscaler assumes one symmetric pool)")
    if args.serve_host_blocks < 0:
        raise SystemExit(f"--serve-host-blocks must be >= 0 (0 = no host "
                         f"tier), got {args.serve_host_blocks}")
    if args.serve_adapters < 0:
        raise SystemExit(f"--serve-adapters must be >= 0 (0 = base model "
                         f"only), got {args.serve_adapters}")
    if args.serve_adapters and args.serve_adapter_rank < 1:
        raise SystemExit(f"--serve-adapter-rank must be >= 1, got "
                         f"{args.serve_adapter_rank}")
    if args.serve_prefetch_ticks < 1:
        raise SystemExit(f"--serve-prefetch-ticks must be >= 1, got "
                         f"{args.serve_prefetch_ticks}")
    serve_plan = None
    if args.serve_chaos:
        from simple_distributed_machine_learning_tpu.resilience import (
            faults,
        )
        try:
            serve_plan = faults.FaultPlan.parse(args.serve_chaos)
        except ValueError as e:
            raise SystemExit(f"bad --serve-chaos spec: {e}") from None
        if not args.serve_replicas and any(
                s.site == "fleet.tick" for s in serve_plan.specs):
            # only the fleet probes fleet.tick: without replicas the spec
            # would never fire and the drill would pass vacuously — the
            # FaultSpec typo'd-site rule's CLI twin
            raise SystemExit(
                "--serve-chaos at site fleet.tick needs --serve-replicas "
                "(a single engine never probes the fleet site, so the "
                "fault would never fire)")
    fleet_mode = args.serve_replicas > 0
    supervised = (not fleet_mode
                  and bool(args.serve_chaos or args.serve_deadline_ms))
    cfg = GPTConfig(vocab=256 if args.text_corpus else 128)
    if cfg.n_heads % args.serve_tp:
        raise SystemExit(f"--serve-tp {args.serve_tp} must divide the "
                         f"model's head count ({cfg.n_heads})")
    longest = args.serve_shared_prefix + max(GPT_SERVE_PROMPTS)
    if longest + 1 > cfg.seq_len:
        raise SystemExit(
            f"--serve-shared-prefix {args.serve_shared_prefix} leaves no "
            f"room to generate: prefix + longest simulated prompt "
            f"({max(GPT_SERVE_PROMPTS)}) + 1 token must fit seq_len "
            f"{cfg.seq_len}")
    stages, wire_dim, out_shape = make_gpt_stages(key, cfg, n_stages)
    # the serving deployment shape: stages stay the dense unsharded build
    # (the engine slices per shard itself), the serve cfg carries the TP
    # width and the mesh binds the model axis the shard_map programs need
    serve_cfg = cfg
    mesh = None
    if args.serve_tp > 1:
        import dataclasses as _dc

        import jax as _jax

        from simple_distributed_machine_learning_tpu.parallel.mesh import (
            make_mesh,
        )
        if len(_jax.devices()) < args.serve_tp:
            raise SystemExit(
                f"--serve-tp {args.serve_tp} needs {args.serve_tp} "
                f"devices, have {len(_jax.devices())} (on CPU: "
                f"XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{args.serve_tp})")
        serve_cfg = _dc.replace(cfg, n_tensor_parallel=args.serve_tp)
        mesh = make_mesh(n_stages=1, n_data=1, n_model=args.serve_tp)
    draft_stages = draft_cfg = None
    if args.serve_spec_k:
        # the draft: same config family at half the layers, fresh init off
        # a folded key — proposals only steer which tokens get verified,
        # so an untrained draft costs acceptance rate, never correctness
        import dataclasses as _dc

        import jax as _jax
        draft_cfg = _dc.replace(cfg,
                                n_layers=max(1, cfg.n_layers // 2))
        draft_stages, _dw, _do = make_gpt_stages(
            _jax.random.fold_in(key, 1), draft_cfg, 1)
    if args.lint or args.lint_only:
        # the serve-path preflight gate: trace and lint the EXACT compiled
        # programs the ticks below will execute (block/position contracts
        # via the scatter-bounds interval pass, donated-buffer flow through
        # the composite tick, retrace policy against the simulator's
        # prompt buckets, HBM-bytes-per-tick table) — zero FLOPs, nothing
        # allocated yet
        from simple_distributed_machine_learning_tpu.analysis.programs import (
            ServeSpec,
            lint_serve,
        )
        buckets = tuple(args.serve_shared_prefix + p
                        for p in GPT_SERVE_PROMPTS)
        report = lint_serve(stages, ServeSpec(
            serve_cfg, n_slots=args.serve_slots,
            block_size=args.serve_block_size,
            prefill_chunk=(args.serve_prefill_chunk or None),
            prompt_lens=buckets, spec_k=args.serve_spec_k,
            draft_cfg=draft_cfg,
            # the engine's AdapterStore sizes the bank n_slots + 1 (row 0
            # = the zero base row), so the linted layouts are the EXACT
            # programs the adapter ticks below will execute
            n_adapters=(args.serve_slots + 1 if args.serve_adapters
                        else 0),
            adapter_rank=(args.serve_adapter_rank if args.serve_adapters
                          else 0)), mesh=mesh, draft_stages=draft_stages)
        print(report.format(costs=True))
        if not report.ok():
            raise SystemExit(2)
        # the protocol gate rides the same preflight: bounded model check
        # of the fleet snap/adopt/handoff discipline (pure stdlib, <1s) —
        # a serving stack whose PROTOCOL double-serves is as broken as one
        # whose kernels scatter out of bounds
        from simple_distributed_machine_learning_tpu.analysis.protocol import (
            check_protocol,
        )
        proto = check_protocol()
        print(f"| serve --lint protocol: {proto.verdict}")
        if not proto.ok():
            print(proto.format(costs=False))
            raise SystemExit(2)
        print("| serve --lint: preflight clean")
        if args.lint_only:
            return
    params = None
    ckpt = (os.path.join(args.checkpoint_dir, "state.npz")
            if args.checkpoint_dir else None)
    if ckpt and os.path.exists(ckpt):
        # restore the TRAINED params: same build (model flags + --stages +
        # --seed) the training run used, unpacked from the packed buffer
        from simple_distributed_machine_learning_tpu.parallel.mesh import (
            make_mesh,
        )
        from simple_distributed_machine_learning_tpu.parallel.pipeline import (
            Pipeline,
        )
        from simple_distributed_machine_learning_tpu.train.checkpoint import (
            restore_checkpoint,
        )
        pipe = Pipeline(stages, make_mesh(n_stages=n_stages), wire_dim,
                        out_shape)
        st = restore_checkpoint(ckpt, pipe=pipe)
        params = pipe.unpack(st["params"])
        print(f"| serve: restored params from {ckpt} "
              f"(step {st['step']})")
    else:
        print("| serve: fresh-initialized params"
              + (f" (no checkpoint at {ckpt})" if ckpt else ""))
    metrics = ServeMetrics(outdir=args.telemetry_dir)
    trace = None
    if args.serve_trace:
        if not args.telemetry_dir:
            raise SystemExit("--serve-trace needs --telemetry-dir (the "
                             "trace artifacts land next to metrics.jsonl)")
        from simple_distributed_machine_learning_tpu.serve import (
            ServeTrace,
        )
        trace = ServeTrace(outdir=args.telemetry_dir)
    engine_kw = dict(
        params=params, n_slots=args.serve_slots,
        block_size=args.serve_block_size,
        prefill_chunk=(args.serve_prefill_chunk or None),
        host_cache_blocks=args.serve_host_blocks,
        prefetch_ticks=args.serve_prefetch_ticks,
        metrics=metrics, mesh=mesh, draft_stages=draft_stages,
        draft_cfg=draft_cfg, spec_k=args.serve_spec_k)
    if args.serve_adapters:
        if fleet_mode or supervised:
            # the engine factory builds (and rebuilds, after a crash)
            # each engine's AdapterStore over one shared host dict
            engine_kw["adapter_rank"] = args.serve_adapter_rank
        else:
            from simple_distributed_machine_learning_tpu.serve.adapters import (  # noqa: E501
                AdapterStore,
            )
            engine_kw["adapters"] = AdapterStore(
                serve_cfg, args.serve_adapter_rank, args.serve_slots)
    tmpdir = None
    if fleet_mode:
        # the multi-replica path: N supervised engines behind the
        # health-aware router — fleet-unique rids, per-replica journals,
        # journal-backed cross-replica migration on replica loss
        import tempfile

        from simple_distributed_machine_learning_tpu.serve import (
            ServeFleet,
            engine_factory,
        )
        if args.telemetry_dir:
            journal_dir = args.telemetry_dir
        else:
            tmpdir = tempfile.TemporaryDirectory(prefix="sdml-fleet-")
            journal_dir = tmpdir.name
        engine = ServeFleet(
            engine_factory(stages, serve_cfg, **engine_kw), journal_dir,
            n_replicas=args.serve_replicas,
            prefill_replicas=args.serve_prefill_replicas,
            route=args.serve_route,
            metrics=metrics, autoscale=autoscale,
            max_restarts=args.serve_max_restarts,
            default_deadline_s=(args.serve_deadline_ms / 1e3
                                if args.serve_deadline_ms else None),
            trace=trace,
            # crash forensics whenever artifacts are kept, like the
            # single-supervisor path: bundles are tagged -r<idx> so the
            # replicas sharing this dir never collide
            postmortem_dir=args.telemetry_dir or None)
        print(f"| serve: fleet of {args.serve_replicas} replica(s), "
              f"route {args.serve_route} (journals "
              f"{journal_dir}/journal-r*.jsonl"
              + (f", disaggregated {args.serve_prefill_replicas} prefill "
                 f"+ {args.serve_replicas - args.serve_prefill_replicas} "
                 f"decode" if args.serve_prefill_replicas else "")
              + (f", autoscale [{autoscale.min_replicas}, "
                 f"{autoscale.max_replicas}]" if autoscale else "")
              + (f", chaos {args.serve_chaos!r}" if args.serve_chaos
                 else "") + ")")
    elif supervised:
        # the crash-restartable path: the engine lives behind the serve
        # supervisor — journaled submissions/tokens, engine rebuild +
        # journal recovery on injected faults, deadline shedding
        import tempfile

        from simple_distributed_machine_learning_tpu.serve import (
            ServeSupervisor,
            engine_factory,
        )
        if args.telemetry_dir:
            journal_path = os.path.join(args.telemetry_dir,
                                        "journal.jsonl")
            if os.path.exists(journal_path):
                os.unlink(journal_path)        # each --serve-sim run is fresh
        else:
            tmpdir = tempfile.TemporaryDirectory(prefix="sdml-serve-")
            journal_path = os.path.join(tmpdir.name, "journal.jsonl")
        engine = ServeSupervisor(
            engine_factory(stages, serve_cfg, **engine_kw), journal_path,
            metrics=metrics, max_restarts=args.serve_max_restarts,
            default_deadline_s=(args.serve_deadline_ms / 1e3
                                if args.serve_deadline_ms else None),
            trace=trace,
            # crash forensics whenever artifacts are kept: a post-mortem
            # bundle per restart / drain-timeout / shed burst next to the
            # journal (serve/flight.py)
            postmortem_dir=args.telemetry_dir or None)
        print(f"| serve: supervised (journal {journal_path}"
              + (f", chaos {args.serve_chaos!r}" if args.serve_chaos
                 else "")
              + (f", deadline {args.serve_deadline_ms:g} ms"
                 if args.serve_deadline_ms else "") + ")")
    else:
        engine = InferenceEngine(stages, serve_cfg, trace=trace,
                                 **engine_kw)
    if args.serve_adapters:
        # seeded per-tenant weights off the run key: register on the
        # serving target (engine / supervisor / fleet — one call shape);
        # device rows upload lazily at each replica's admission ticks
        import jax as _jax

        from simple_distributed_machine_learning_tpu.models import lora
        for k in range(args.serve_adapters):
            engine.register_adapter(
                f"tenant-{k}",
                lora.init_lora_adapter(_jax.random.fold_in(key, 7000 + k),
                                       serve_cfg,
                                       args.serve_adapter_rank))
        print(f"| serve: {args.serve_adapters} LoRA tenant(s) rank "
              f"{args.serve_adapter_rank} over shared base weights")
    max_new = min(args.serve_max_new, cfg.seq_len - longest)
    if max_new < args.serve_max_new:
        print(f"| serve: --serve-max-new {args.serve_max_new} clamped to "
              f"{max_new} (seq_len {cfg.seq_len} minus the longest "
              f"{longest}-token simulated prompt)")
    sim = SimConfig(n_requests=args.serve_sim, rate=args.serve_rate,
                    seed=args.seed, prompt_lens=GPT_SERVE_PROMPTS,
                    max_new_tokens=max_new,
                    shared_prefix_len=args.serve_shared_prefix,
                    # multi-tenant adapters: arrivals split evenly across
                    # the tenants, each request decoding its own adapter
                    classes=tuple(
                        TrafficClass(name=f"tenant-{k}",
                                     adapter=f"tenant-{k}")
                        for k in range(args.serve_adapters)))
    # graceful shutdown: SIGTERM/SIGINT stop admission, drain in-flight
    # requests, flush metrics + journal and exit 0 — the operational
    # complement of crash recovery (a rollout must not look like a fault)
    import signal

    stop = {"sig": None}

    def _on_signal(signum, frame):
        stop["sig"] = signum

    old_handlers = {}
    try:
        for s in (signal.SIGTERM, signal.SIGINT):
            old_handlers[s] = signal.signal(s, _on_signal)
    except ValueError:
        old_handlers = {}              # not the main thread: no handlers
    if serve_plan is not None:
        from simple_distributed_machine_learning_tpu.resilience import (
            faults,
        )
        faults.install(serve_plan)
    try:
        report = simulate(engine, sim,
                          should_stop=lambda: stop["sig"] is not None)
    finally:
        if serve_plan is not None:
            faults.uninstall()
        for s, h in old_handlers.items():
            signal.signal(s, h)
        if supervised or fleet_mode:
            engine.close()             # journal(s) flushed + closed
        if trace is not None:
            trace.close()              # chrome trace + timeline flushed
    s = metrics.summary()
    print(f"| serve: {report['completed']}/{report['n_requests']} requests "
          f"completed, {s['tokens_generated']} tokens, "
          f"{s['tokens_per_sec']} tok/s, "
          f"ttft p50/p95 {s['ttft_ms_p50']}/{s['ttft_ms_p95']} ms, "
          f"tpot p50/p95 {s['tpot_ms_p50']}/{s['tpot_ms_p95']} ms, "
          f"occupancy {s['slot_occupancy_mean']}")
    if fleet_mode:
        print(f"| serve: fleet {engine.n_alive} alive "
              f"({engine.n_in_rotation} in rotation), "
              f"{engine.replica_losses} replica loss(es), "
              f"{engine.migrations} migration(s), "
              f"{s.get('route_affinity_hits', 0)} affinity hit(s), "
              f"{s.get('fleet_scale_outs', 0)} scale-out(s), "
              f"{s.get('fleet_retired', 0)} retired, "
              f"{s.get('restarts', 0)} in-place restart(s), "
              f"journals {s.get('journal_bytes', 0)} bytes")
        if args.serve_prefill_replicas:
            print(f"| serve: disaggregated — {engine.handoffs} "
                  f"prefill->decode handoff(s), pools "
                  + ", ".join(
                      f"{p}[{b['replicas']} replica(s), queue "
                      f"{b['queue_depth']}, {b['slots_active']} active]"
                      for p, b in sorted((s.get("pools") or {}).items())))
        if args.serve_host_blocks:
            print(f"| serve: host tier {s.get('host_blocks', 0)} block(s) "
                  f"resident ({s.get('host_bytes_resident', 0)} bytes), "
                  f"{s.get('host_demotes', 0)} demote(s), "
                  f"{s.get('host_promotes', 0)} promote(s), prefetch "
                  f"{s.get('host_prefetch_hits', 0)} hit(s)/"
                  f"{s.get('host_prefetch_misses', 0)} miss(es), "
                  f"{s.get('host_transfer_bytes', 0)} bytes transferred")
    if supervised:
        print(f"| serve: supervisor {engine.state}, "
              f"{s.get('restarts', 0)} restart(s), "
              f"{s.get('recovered_requests', 0)} recovered, "
              f"{report['shed']} shed {s.get('shed_by_reason', {})}, "
              f"journal {s.get('journal_bytes', 0)} bytes")
        if engine.postmortems:
            print(f"| serve: {len(engine.postmortems)} post-mortem "
                  f"bundle(s): "
                  f"{[os.path.basename(p) for p in engine.postmortems]}")
    if args.serve_adapters:
        print(f"| serve: adapters — "
              f"{s.get('adapter_resident_bytes', 0)} bank bytes "
              f"resident, {s.get('adapter_swaps', 0)} bank upload(s), "
              f"{s.get('route_adapter_affinity_hits', 0)} "
              f"adapter-affinity hit(s), per-tenant completed "
              f"{s.get('per_adapter_completed', {})}")
    if "kv_drift_bytes" in s:
        print(f"| serve: kv drift {s['kv_drift_bytes']} bytes vs the "
              f"analyzer model (predicted {s['kv_bytes_predicted']})")
    if trace is not None:
        print(f"| serve: trace {trace.n_events} events -> "
              f"{trace.trace_file} + {trace.timeline_file}")
    if report["stopped"]:
        print(f"| serve: graceful shutdown on signal {stop['sig']} — "
              f"admission stopped, {report['submitted']} submitted "
              f"request(s) drained, metrics/journal flushed")
    print(f"| serve: paged pool {s['blocks_in_use']}/{s['blocks_total']} "
          f"blocks in use ({s['blocks_cached']} cached), "
          f"{s['kv_bytes_resident']} KV bytes resident, "
          f"{s['prefix_hit_blocks']} prefix-share hits, "
          f"{s['cow_copies']} CoW copies, "
          f"prefill chunk p50/p95 {s['prefill_chunk_ms_p50']}/"
          f"{s['prefill_chunk_ms_p95']} ms")
    if args.serve_tp > 1 or args.serve_spec_k:
        spec = (f", spec_k {s.get('spec_k', 0)} accept_rate "
                f"{s.get('spec_accept_rate')} "
                f"({s.get('spec_accepted_tokens', 0)}/"
                f"{s.get('spec_proposed_tokens', 0)} draft tokens)"
                if args.serve_spec_k else "")
        print(f"| serve: tp {args.serve_tp}{spec}")
    if args.telemetry_dir:
        metrics.emit(extra={"rate": sim.rate, "n_slots": args.serve_slots,
                            "block_size": args.serve_block_size,
                            "shared_prefix": args.serve_shared_prefix,
                            "completed": report["completed"]})
    if tmpdir is not None:
        tmpdir.cleanup()
    # success = every SUBMITTED request accounted for: completed, or (a
    # deadline run) structurally shed — a silently lost request fails.
    # A graceful shutdown judges only what was admitted before the signal.
    expected = (report["submitted"] if report["stopped"]
                else report["n_requests"])
    if report["completed"] + report["shed"] != expected:
        raise SystemExit(1)


# prompt-length buckets of the simulated serving workload (each bucket is
# one compiled prefill shape)
GPT_SERVE_PROMPTS = (4, 8, 12)


def _run_scenario(args, n_stages: int, key) -> None:
    """--scenario NAME: one SLO-gated serving scenario (resilience/
    scenarios.py) on a fresh-init GPT build; exits nonzero unless every
    gated class attains its TTFT/TPOT targets and all requests complete."""
    from simple_distributed_machine_learning_tpu.resilience.scenarios import (
        SCENARIOS,
        run_scenario,
    )

    if args.scenario == "list":
        for s in SCENARIOS.values():
            print(f"| {s.name}: {s.description}")
        return
    if args.scenario not in SCENARIOS:
        raise SystemExit(
            f"unknown --scenario {args.scenario!r}; available: "
            f"{', '.join(sorted(SCENARIOS))} (or 'list')")
    if args.serve_sim > 0 or args.chaos is not None:
        raise SystemExit("--scenario runs alone (drop --serve-sim/--chaos)")
    from simple_distributed_machine_learning_tpu.models.gpt import (
        GPTConfig,
        make_gpt_stages,
    )
    cfg = GPTConfig()
    stages, _wd, _os = make_gpt_stages(key, cfg, n_stages)
    report = run_scenario(args.scenario, stages, cfg,
                          outdir=args.telemetry_dir,
                          trace=bool(args.serve_trace))
    print(f"| scenario {report['scenario']} ({report['scheduler']}"
          + (", supervised" if report.get("supervised") else "") + "): "
          f"{report['completed']}/{report['n_requests']} completed, "
          f"{report['shed']} shed, "
          f"{report.get('preemptions', 0)} preemptions, "
          + (f"{report['restarts']} restart(s), "
             if report.get("supervised") else "")
          + f"faults fired: "
          f"{report.get('faults', {}).get('total_fired', 0)}")
    fl = report.get("fleet")
    if fl:
        print(f"| scenario: fleet {fl['replicas']} replica(s) "
              f"(route {fl['route']}): {fl['replica_losses']} loss(es), "
              f"{fl['migrations']} migration(s), "
              f"{fl['affinity_hits']} affinity hit(s), "
              f"{fl['scale_outs']} scale-out(s), {fl['retired']} retired")
        for ev in fl["replica_log"]:
            print(f"| scenario:   fleet {ev['event']} replica "
                  f"{ev['replica']} @tick {ev['tick']} "
                  f"(t={ev['t']:g}, {ev['alive']} alive)")
    for cls, att in sorted(report["slo"].items()):
        parts = []
        if "ttft_attainment" in att:
            a = att["ttft_attainment"]
            parts.append(f"ttft p95 {att['ttft_ms_p95']} vms vs SLO "
                         f"{att['ttft_slo_ms']} "
                         f"({'-' if a is None else round(a, 3)})")
        if "tpot_attainment" in att:
            a = att["tpot_attainment"]
            parts.append(f"tpot p95 {att['tpot_ms_p95']} vms vs SLO "
                         f"{att['tpot_slo_ms']} "
                         f"({'-' if a is None else round(a, 3)})")
        print(f"| scenario:   {cls} "
              f"[{'OK' if att['ok'] else 'VIOLATED'}] " + "; ".join(parts))
    sa = report.get("slo_alerts")
    if sa:
        for tr in sa["transitions"]:
            print(f"| scenario:   alert {tr['alert']} {tr['from']} -> "
                  f"{tr['to']} @tick {tr['tick']} (burn fast/slow "
                  f"{tr.get('burn_fast', 0)}/{tr.get('burn_slow', 0)})")
        if not sa["transitions"]:
            print("| scenario:   alerts: no burn-rate transitions "
                  "(error budget never breached)")
    att_blk = report.get("attribution")
    if att_blk:
        print(f"| scenario: attribution {att_blk['requests']} request(s) "
              f"folded, {att_blk['recovered']} recovered, max drift "
              f"{att_blk['max_abs_drift_ms']} ms")
        for a in att_blk["top_slow"]:
            comps = " ".join(f"{c}={v}"
                             for c, v in a["components_ms"].items())
            print(f"| scenario:   slow rid {a['rid']} ({a['cls']}) ttft "
                  f"{a['ttft_ms']} vms: {comps}"
                  + (" [recovered]" if a.get("recovered") else ""))
    if report.get("postmortem_bundles"):
        print(f"| scenario: {report['postmortem_bundles']} post-mortem "
              f"bundle(s) under {args.telemetry_dir}")
    if report.get("trace_events"):
        print(f"| scenario: trace {report['trace_events']} events"
              + (f" under {args.telemetry_dir}" if args.telemetry_dir
                 else " (in-memory; add --telemetry-dir to keep them)"))
    print(f"| scenario: SLO {'ATTAINED' if report['slo_ok'] else 'MISSED'}")
    if not report["slo_ok"]:
        raise SystemExit(1)


def _run_chaos(args, n_stages: int, key) -> None:
    """--chaos SPEC: training under a deterministic fault schedule with the
    elastic checkpoint-restart supervisor (resilience/supervisor.py).

    The supervisor rebuilds the trainer from scratch after every
    recoverable failure — nothing in-memory survives an attempt — restoring
    the latest checksum-valid checkpoint from the store in --checkpoint-dir
    and repacking it onto the surviving stage count from the
    --chaos-stages ladder. Exits 0 only when training ran to completion
    within the restart budget.
    """
    import dataclasses

    import numpy as np

    from simple_distributed_machine_learning_tpu.resilience import (
        CheckpointStore,
        RestartPolicy,
        faults,
        make_elastic_trainer,
        supervise,
    )

    if args.model not in ("mlp", "gpt"):
        raise SystemExit(
            "--chaos supports --model mlp or gpt (the contiguous-split "
            "families repack_checkpoint can rewrite across stage counts; "
            "lenet's conv|fc split is a structural rename)")
    if args.experts > 0 or args.sp > 1 or args.tp > 1 or args.ep > 1 \
            or args.serve_sim > 0:
        raise SystemExit(
            "--chaos drills the pipeline-parallel training path: drop "
            "--experts/--sp/--tp/--ep/--serve-sim")
    if args.world_size > 1:
        raise SystemExit(
            "--chaos supervises in-process (single-process elastic "
            "restart); multi-process peer loss is the watchdog's domain "
            "(--peer-timeout)")
    if not args.checkpoint_dir:
        raise SystemExit("--chaos needs --checkpoint-dir (the supervisor "
                         "restores from its checkpoint store)")
    if args.chaos_max_restarts < 0:
        raise SystemExit(f"--chaos-max-restarts must be >= 0, got "
                         f"{args.chaos_max_restarts}")
    try:
        plan = faults.FaultPlan.parse(args.chaos)
    except ValueError as e:
        raise SystemExit(f"bad --chaos spec: {e}") from None
    from simple_distributed_machine_learning_tpu.resilience.faults import (
        SENTINEL_KINDS,
    )
    numeric = sorted({s.kind for s in plan.specs
                      if s.kind in SENTINEL_KINDS})
    if numeric and not args.sentinel:
        # without the sentinel a numeric fault's standard effect is a
        # raised NumericFault the supervisor treats as a real bug — the
        # drill would fail confusingly instead of being absorbed
        raise SystemExit(
            f"--chaos plan contains sentinel-interpreted kinds "
            f"({', '.join(numeric)}): add --sentinel so the trainer "
            f"absorbs them")
    if args.chaos_stages:
        try:
            topologies = [int(s) for s in args.chaos_stages.split(",")]
        except ValueError:
            raise SystemExit(f"--chaos-stages expects a comma list of "
                             f"stage counts, got {args.chaos_stages!r}"
                             ) from None
        if any(t < 1 for t in topologies):
            raise SystemExit(f"--chaos-stages entries must be >= 1, got "
                             f"{topologies}")
    else:
        topologies = [n_stages]

    from simple_distributed_machine_learning_tpu.data.mnist import Dataset
    from simple_distributed_machine_learning_tpu.parallel.mesh import (
        make_mesh,
    )
    from simple_distributed_machine_learning_tpu.parallel.pipeline import (
        Pipeline,
    )

    if args.model == "gpt":
        from simple_distributed_machine_learning_tpu.data.text import (
            synthetic_tokens,
        )
        from simple_distributed_machine_learning_tpu.models.gpt import (
            GPTConfig,
            make_gpt_stages,
        )
        cfg = GPTConfig(vocab=256 if args.text_corpus else 128)
        all_data = synthetic_tokens(7000, cfg.seq_len, cfg.vocab,
                                    seed=args.seed)
        train_ds = Dataset(all_data.x[:6000].astype(np.float32),
                           all_data.y[:6000])
        test_ds = Dataset(all_data.x[6000:].astype(np.float32),
                          all_data.y[6000:])

        def build_pipe(n):
            stages, wd, osh = make_gpt_stages(key, cfg, n)
            mesh = make_mesh(n_stages=n, n_data=args.dp,
                             devices=jax.devices()[:n * args.dp])
            return Pipeline(stages, mesh, wd, osh,
                            n_microbatches=args.microbatches,
                            compute_dtype=_compute_dtype(args),
                            remat=args.remat, schedule=args.schedule)
    else:
        from simple_distributed_machine_learning_tpu.data.mnist import (
            load_mnist,
        )
        from simple_distributed_machine_learning_tpu.models.mlp import (
            make_mlp_stages,
        )
        dims = [int(d) for d in args.mlp_dims.split(",")]
        tr, te = load_mnist(args.data_root)
        train_ds = Dataset(tr.x.reshape(len(tr.x), -1), tr.y)
        test_ds = Dataset(te.x.reshape(len(te.x), -1), te.y)

        def build_pipe(n):
            stages, wd, od = make_mlp_stages(key, dims, n)
            mesh = make_mesh(n_stages=n, n_data=args.dp,
                             devices=jax.devices()[:n * args.dp])
            return Pipeline(stages, mesh, wd, od,
                            n_microbatches=args.microbatches,
                            compute_dtype=_compute_dtype(args),
                            remat=args.remat, schedule=args.schedule)

    store = CheckpointStore(args.checkpoint_dir, keep=5)
    # the store owns persistence: the Trainer's own state.npz path stays off
    config = dataclasses.replace(_train_config(args), checkpoint_dir=None)
    total = _total_steps(args, train_ds)

    def build_trainer(n):
        # opt_factory: the optimizer must see the ATTEMPT's pipeline
        # (replication-weighted --clip-norm depends on the topology)
        return make_elastic_trainer(
            build_pipe, n, store, train_ds, test_ds, config,
            opt_factory=lambda pipe: _make_opt(args, total, pipe))

    faults.install(plan)
    try:
        report = supervise(
            build_trainer, topologies,
            policy=RestartPolicy(max_restarts=args.chaos_max_restarts))
    finally:
        faults.uninstall()
    print(f"| chaos: completed after {report['restarts']} restart(s); "
          f"attempts: "
          + " -> ".join(f"{a['n_stages']}st/{a['outcome']}"
                        f"{'(' + a['fault'] + ')' if 'fault' in a else ''}"
                        for a in report["attempts"])
          + f"; faults fired: {plan.stats()['total_fired']}")
    if args.sentinel:
        tot = {"anomalies": 0, "rollbacks": 0}
        quarantined = 0
        for a in report["attempts"]:
            s = a.get("sentinel") or {}
            tot["anomalies"] += s.get("anomalies", 0)
            tot["rollbacks"] += s.get("rollbacks", 0)
            # the journal is cumulative across attempts (loaded from disk):
            # the last attempt's count is the total
            quarantined = s.get("quarantined_batches", quarantined)
        print(f"| chaos: sentinel absorbed {tot['anomalies']} anomal"
              f"{'y' if tot['anomalies'] == 1 else 'ies'} "
              f"({tot['rollbacks']} rollback(s), {quarantined} "
              f"quarantined batch(es))")
    if plan.stats()["total_fired"] == 0:
        # the min_anomalies-style anti-vacuous gate: a chaos drill whose
        # schedule never fired proves nothing — fail it instead of letting
        # a typo'd step number pass green
        raise SystemExit(
            "--chaos plan never fired (scheduled step beyond the run?) — "
            "the drill is vacuous; fix the schedule")


def _print_sample(args, trainer, cfg, test_ds) -> None:
    """--generate N: decode N tokens from the trained model (KV-cache path,
    straight from the live packed buffer) and print them on rank 0 — for a
    --text-corpus run this is the model writing text."""
    import jax

    import numpy as np

    from simple_distributed_machine_learning_tpu.models.gpt import (
        decoder_from_pipeline,
    )

    n_new = min(args.generate, cfg.seq_len - 1)
    t0 = max(1, min(cfg.seq_len - n_new, 16))
    pipe = trainer.pipe
    if cfg.n_experts > 0 or cfg.n_seq > 1 or cfg.n_tensor_parallel > 1:
        trainer._print("| --generate: skipped (MoE/seq-/tensor-parallel "
                       "builds decode via models.make_decoder)")
        return
    if pipe.n_stages >= 2:
        # pipeline-parallel decode: stage-sharded params stay put, so this
        # works on multi-process meshes too (every rank participates; the
        # batch shards over the data axis, hence B = n_data prompts)
        from simple_distributed_machine_learning_tpu.models.pp_decode import (
            make_pp_decoder,
        )
        B = pipe.n_data
        if len(test_ds.x) < B:
            trainer._print("| --generate: skipped (test set smaller than "
                           "the data-parallel width)")
            return
        prompt = np.asarray(test_ds.x[:B, :t0], np.int32)
        dec = make_pp_decoder(pipe, cfg, t0, n_new,
                              cache_dtype=_compute_dtype(args))
    else:
        if jax.process_count() > 1:
            # a 1-stage multi-process buffer is not host-gatherable here
            trainer._print("| --generate: skipped (single-stage multi-"
                           "process run; decode from a checkpoint instead)")
            return
        prompt = np.asarray(test_ds.x[:1, :t0], np.int32)
        dec = decoder_from_pipeline(pipe, cfg, t0, n_new,
                                    cache_dtype=_compute_dtype(args))
    toks = _decode_timed(args, trainer, dec, prompt, n_new)[0]
    if args.text_corpus:
        text = bytes(int(t) for t in toks).decode("latin-1")
        trainer._print(f"| sample ({t0}-byte prompt + {n_new} generated):\n"
                       f"{text!r}")
    else:
        trainer._print(f"| sample tokens (prompt {t0} + {n_new} generated): "
                       f"{toks.tolist()}")


def _decode_timed(args, trainer, dec, prompt, n_new):
    """Run the --generate decode; with --telemetry-dir attached, route its
    timing through the telemetry StepTimer/registry so decode latency and
    tokens/sec land in metrics.jsonl (+ the Prometheus exposition) instead
    of being print-only. The first call is the compile window (StepTimer
    splits it out); a second, different-key decode measures the steady
    latency — distinct inputs so a result-cached re-dispatch cannot fake
    the number (bench.py's measure_decode discipline)."""
    import time as _time

    import jax

    from simple_distributed_machine_learning_tpu.train.checkpoint import (
        _to_host,
    )

    tele = trainer.telemetry
    key = jax.random.key(args.seed)
    if tele is None:
        return _to_host(dec(trainer.buf, prompt, key))
    from simple_distributed_machine_learning_tpu.telemetry.registry import (
        append_jsonl,
    )
    from simple_distributed_machine_learning_tpu.telemetry.timer import (
        StepTimer,
    )
    timer = StepTimer(registry=tele.registry, name="decode_time_ms")
    b, n_tok = prompt.shape[0], prompt.shape[0] * n_new
    t0 = _time.perf_counter()
    toks = _to_host(dec(trainer.buf, prompt, key))
    timer.record_window(_time.perf_counter() - t0, steps=1)   # compile window
    t0 = _time.perf_counter()
    jax.block_until_ready(dec(trainer.buf, prompt,
                              jax.random.fold_in(key, 1)))
    timer.record_window(_time.perf_counter() - t0, steps=1, tokens=n_tok)
    if trainer.is_main:
        import os
        rec = {"kind": "decode", "batch": int(b), "n_new": int(n_new),
               **timer.summary()}
        append_jsonl(os.path.join(tele.outdir, "metrics.jsonl"), rec)
        tele.flush()                     # decode series -> metrics.prom
    return toks


if __name__ == "__main__":
    main()
