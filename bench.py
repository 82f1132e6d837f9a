"""Benchmarks: samples/sec/chip + MFU for every BASELINE.json config.

Default invocation prints ONE JSON line (the headline config — the 2-stage
MLP of BASELINE.json configs 1-2):
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "mfu": ...}

``--all`` additionally measures the 4-stage MLP (config 3), LeNet (config 4),
the tiny GPipe GPT (config 5), and a bf16 GPT sized to load the MXU, printing
one JSON line per row and writing ``benchmarks/results_all.json``.

Measurement: the epoch-compiled train step (``lax.scan`` over batches) with a
small resident POOL of input batches (``pool_steps`` in
``train/step.py``) — one dispatch per window, so the number reflects chip
throughput, not host dispatch latency, without pinning GBs of inputs.
Two-point timing (one window vs two back-to-back windows, each closed with a
forced host read) cancels every fixed cost: dispatch and the host read.

MFU: closed-form training FLOPs (fwd matmul FLOPs x3 — the standard
approximation; backward costs 2x forward) divided by the chip's peak. Peaks
are the published bf16 matmul numbers per device kind; f32 rows are still
divided by the bf16 peak (TPU MXUs execute f32 matmuls via bf16 passes at
default precision), so f32 MFU is an honest "fraction of the chip" figure.

``vs_baseline`` divides by the stored CPU baseline (benchmarks/
baseline_cpu.json): the torch.distributed.rpc 2-process CPU implementation of
the same workload (the reference's architecture, measured by
benchmarks/torch_rpc_baseline.py) — i.e. "ours on TPU vs theirs on CPU",
which is the north-star comparison (BASELINE.json config 1 vs 2). Regenerate
with ``python bench.py --measure-baseline``.

Single-chip note: with one device the pipeline degenerates to the fused
single-stage model (``Pipeline.loss_and_logits``'s fast path) — the same
math, no ppermute. The multi-stage shard_map engine is covered on virtual
CPU meshes (tests/) and by ``chip_smoke.py --chips 4`` on a four-chip
host; its on-chip throughput has not been measured.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
BASELINE_PATH = os.path.join(REPO, "benchmarks", "baseline_cpu.json")
RESULTS_PATH = os.path.join(REPO, "benchmarks", "results_all.json")

# published peak bf16 matmul FLOP/s per chip, by jax device_kind
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,      # v5e
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,      # v6e / Trillium
}


def _peak_flops(kind: str) -> float:
    """The published peak of ``kind``; a device this table does not know is
    an error, never an ``mfu: null`` row."""
    if kind not in PEAK_FLOPS:
        raise SystemExit(
            f"bench: no published peak FLOP/s for device_kind {kind!r} — "
            f"MFU is never defaulted; train rows are measured on a chip "
            f"PEAK_FLOPS lists ({', '.join(PEAK_FLOPS)})")
    return PEAK_FLOPS[kind]

POOL = 16                       # resident input batches per window


def _mlp_flops(dims):
    """Per-sample training FLOPs of an MLP: 3 x fwd, fwd = 2*sum(d_i*d_i+1)."""
    return 6 * sum(dims[i] * dims[i + 1] for i in range(len(dims) - 1))


def _lenet_flops():
    """LeNet per-sample training FLOPs (convs dominate; pools/bias dropped).

    conv1 1->10 k5 on 28x28 (out 24x24), conv2 10->20 k5 on 12x12 (out 8x8),
    fc 320->50->10 — the reference's exact architecture
    (/root/reference/simple_distributed.py:26-95).
    """
    conv1 = 2 * 24 * 24 * 10 * (5 * 5 * 1)
    conv2 = 2 * 8 * 8 * 20 * (5 * 5 * 10)
    fc = 2 * (320 * 50 + 50 * 10)
    return 3 * (conv1 + conv2 + fc)


def _gpt_flops(cfg):
    """Per-sample training FLOPs of the GPT (3 x fwd matmul FLOPs).

    Per token per layer: qkvo projections 8d^2, attention scores+values 4Td,
    MLP (ratio r) 2*2*r*d^2; head 2dV per token. Causal masking's 2x saving
    on the score matmuls is NOT credited (XLA computes the full product).
    """
    d, T, L, V, r = (cfg.d_model, cfg.seq_len, cfg.n_layers, cfg.vocab,
                     cfg.mlp_ratio)
    per_tok = L * (8 * d * d + 4 * T * d + 4 * r * d * d) + 2 * d * V
    return 3 * T * per_tok


def _build_mlp(dims, n_dev):
    import jax

    from simple_distributed_machine_learning_tpu.models.mlp import (
        make_mlp_stages,
    )
    want = len(dims) - 1
    # degrade gracefully: as many pipeline stages as there are devices
    # (still a real multi-stage pipeline on 2-3 chips, fused only on 1);
    # n_chips in the output row records what actually ran
    n_stages = want if n_dev >= want else (2 if n_dev >= 2 else 1)
    stages, wire_dim, out_dim = make_mlp_stages(jax.random.key(0), dims,
                                                n_stages)
    return stages, wire_dim, out_dim, n_stages


def _data_mlp(dims, batch, pool):
    import jax
    key = jax.random.key(1)
    xs = jax.random.normal(key, (pool, batch, dims[0]))
    ts = jax.random.randint(key, (pool, batch), 0, dims[-1])
    return xs, ts


def _data_img(batch, pool):
    import jax
    key = jax.random.key(1)
    xs = jax.random.normal(key, (pool, batch, 28, 28, 1))
    ts = jax.random.randint(key, (pool, batch), 0, 10)
    return xs, ts


def _data_gpt(cfg, batch, pool):
    import jax
    key = jax.random.key(1)
    xs = jax.random.randint(key, (pool, batch, cfg.seq_len), 0,
                            cfg.vocab).astype("float32")
    ts = jax.random.randint(jax.random.key(2), (pool, batch, cfg.seq_len), 0,
                            cfg.vocab)
    return xs, ts


def _configs():
    """name -> spec. Built lazily so jax only imports inside measure()."""
    from simple_distributed_machine_learning_tpu.models.gpt import GPTConfig

    mlp2 = [784, 512, 10]
    mlp4 = [784, 512, 512, 512, 10]
    tiny_gpt = GPTConfig(vocab=128, seq_len=64, d_model=128, n_heads=4,
                         n_layers=2)
    big_gpt = GPTConfig(vocab=8192, seq_len=256, d_model=512, n_heads=8,
                        n_layers=4)
    return {
        # BASELINE.json config 2 (headline; config 1 is the torch-RPC CPU
        # baseline of the same workload)
        # steps are sized so one compiled window is >= ~200 ms of chip time,
        # well above host dispatch jitter (a window of a few ms lets the
        # jitter swamp the two-point difference)
        "mlp2": dict(kind="mlp", dims=mlp2, batch=60, n_micro=1,
                     steps=30000, flops=_mlp_flops(mlp2), dtype=None),
        # config 3: 4-layer MLP -> 4-stage pipeline, microbatch=1
        "mlp4": dict(kind="mlp", dims=mlp4, batch=60, n_micro=1,
                     steps=20000, flops=_mlp_flops(mlp4), dtype=None),
        # config 4: LeNet split conv<->fc (the reference's own workload)
        "lenet": dict(kind="lenet", batch=60, n_micro=1, steps=4000,
                      flops=_lenet_flops(), dtype=None),
        # config 5: 2-layer tiny-GPT (d=128) with GPipe microbatching
        "gpt": dict(kind="gpt", cfg=tiny_gpt, batch=32, n_micro=4,
                    steps=1000, flops=_gpt_flops(tiny_gpt), dtype=None),
        # MXU-sized bf16 GPT: the MFU row (not a BASELINE config; sized so
        # the matmuls are large enough for the systolic array to matter).
        # bf16 rows train with AdamW: SGD at the f32 rows' lr=0.1 diverges
        # to NaN in half precision (observed r4), and a NaN final_loss means
        # the throughput was measured on garbage values
        "gpt_bf16": dict(kind="gpt", cfg=big_gpt, batch=16, n_micro=1,
                         steps=100, flops=_gpt_flops(big_gpt),
                         dtype="bfloat16", opt="adamw"),
        "mlp2_bf16": dict(kind="mlp", dims=mlp2, batch=60, n_micro=1,
                          steps=15000, flops=_mlp_flops(mlp2),
                          dtype="bfloat16", opt="adamw"),
    }


def _xl_config():
    """MXU-stretch bf16 GPT (d=1024, T=512): not part of ``--all`` (slower
    compile + more HBM than the sweep budget wants); run explicitly with
    ``python bench.py --config gpt_bf16_xl`` to probe peak MFU."""
    from simple_distributed_machine_learning_tpu.models.gpt import GPTConfig

    xl = GPTConfig(vocab=8192, seq_len=512, d_model=1024, n_heads=16,
                   n_layers=4)
    return dict(kind="gpt", cfg=xl, batch=8, n_micro=1, steps=24,
                flops=_gpt_flops(xl), dtype="bfloat16", opt="adamw")


# the unresponsive-accelerator exit code: bench.py exits with it, after a
# structured ``device_unhealthy`` row, when a small constant never
# materializes — an unreachable chip is a failure, not a measurement
WEDGED_RC = 17


def _smoke_check(timeout_s: float = 90.0) -> int:
    """The in-process device watchdog: 0 when a small constant materializes
    on the default backend within ``timeout_s``, ``WEDGED_RC`` when it does
    not (the first dispatch blocks forever on a hung device; without this
    the whole bench hangs until an outer timeout with no clue in the
    output). A probe that RAISES (plugin/init error) is not a hang: the real
    exception propagates."""
    import threading

    import jax.numpy as jnp

    done = threading.Event()
    err: list[BaseException] = []

    def probe():
        try:
            jnp.ones((128, 128)).block_until_ready()
        except BaseException as e:  # noqa: BLE001 - re-raised in main thread
            err.append(e)
        finally:
            done.set()

    t = threading.Thread(target=probe, daemon=True)
    t.start()
    if done.wait(timeout_s):
        if err:
            raise err[0]
        return 0
    # NO jax calls from here on: with the device hung even
    # jax.default_backend() blocks on the backend-init lock the probe
    # thread is stuck holding
    sys.stderr.write(
        f"bench: accelerator unresponsive - a 128x128 constant did not "
        f"materialize within {timeout_s:.0f}s; no measurement possible\n")
    sys.stderr.flush()
    return WEDGED_RC


def _probe(attempt: int, timeout_s: float) -> int:
    """One device probe, in this process (a child would take the chip from
    the parent that is about to measure on it). Consults the active fault
    plan at the ``bench.probe`` site first, so a scheduled ``wedged-device``
    fault wedges exactly the attempts it names without touching jax."""
    from simple_distributed_machine_learning_tpu.resilience.faults import (
        check as _check_fault,
    )
    if any(f.kind == "wedged-device"
           for f in _check_fault("bench.probe", step=attempt)):
        sys.stderr.write(
            "bench: accelerator unresponsive - injected wedged-device "
            "fault (resilience/faults.py)\n")
        sys.stderr.flush()
        return WEDGED_RC
    return _smoke_check(timeout_s)


def _hard_exit(rc: int) -> None:
    # os._exit, not sys.exit: with the device hung, normal interpreter exit
    # hangs too (jax's atexit backend finalization blocks on the same lock)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)


def _supervised_smoke(probe=None, retries: int = 1,
                      backoff_s: float | None = None,
                      sleep=time.sleep, exit=None) -> None:
    """The accelerator preflight: probe, retry once with backoff on the
    unresponsive signature, and on a persistent one print the structured
    ``{"metric": "device_unhealthy", ...}`` row and EXIT ``WEDGED_RC`` —
    nothing is measured and no artifact is written. Returns only when the
    device answered; other probe failures exit with their own code."""
    probe = probe or _probe
    exit = exit or _hard_exit
    if backoff_s is None:
        backoff_s = float(os.environ.get("SDML_BENCH_PROBE_BACKOFF", "10"))
    timeout_s = float(os.environ.get("SDML_BENCH_PROBE_TIMEOUT", "150"))
    for attempt in range(retries + 1):
        rc = probe(attempt, timeout_s)
        if rc == 0:
            return
        if rc != WEDGED_RC:
            sys.stderr.write(f"bench: device probe failed with rc={rc} "
                             f"(not the unresponsive signature) — "
                             f"aborting\n")
            sys.exit(rc or 1)
        if attempt < retries:
            sys.stderr.write(
                f"bench: accelerator unresponsive (rc {WEDGED_RC}), attempt "
                f"{attempt + 1}/{retries + 1} — retrying in "
                f"{backoff_s:.0f}s\n")
            sys.stderr.flush()
            sleep(backoff_s)
            backoff_s *= 2
    print(json.dumps({
        "metric": "device_unhealthy",
        "rc": WEDGED_RC,
        "attempts": retries + 1,
        "detail": "accelerator unresponsive; no throughput measurement "
                  "possible",
    }))
    exit(WEDGED_RC)


def measure(name: str, spec: dict, windows: int = 5,
            schedule: str = "gpipe", lint: bool = False) -> dict:
    """One train row with its MFU. Needs a ``device_kind`` that
    ``PEAK_FLOPS`` knows, checked before any work: an unknown kind is an
    error, not an mfu-less row."""
    import jax

    peak = _peak_flops(jax.devices()[0].device_kind)
    row, flops_per_chip = _throughput_row(name, spec, windows, schedule, lint)
    # model-FLOPs utilization of the chips that ran: aggregate FLOP/s over
    # aggregate peak
    row["mfu"] = round(flops_per_chip / peak, 4)
    return row


def _throughput_row(name: str, spec: dict, windows: int = 5,
                    schedule: str = "gpipe", lint: bool = False
                    ) -> tuple[dict, float]:
    """The timed train row without MFU, and its achieved FLOP/s per chip
    (the CPU baseline child reads ``samples_per_sec`` from the row alone)."""
    import jax
    import jax.numpy as jnp

    from simple_distributed_machine_learning_tpu.parallel.mesh import make_mesh
    from simple_distributed_machine_learning_tpu.parallel.pipeline import (
        Pipeline,
    )
    from simple_distributed_machine_learning_tpu.train.optimizer import (
        adamw,
        sgd,
    )
    from simple_distributed_machine_learning_tpu.train.step import (
        make_scanned_train_step,
    )

    n_dev = len(jax.devices())
    kind = jax.devices()[0].device_kind
    batch, n_micro = spec["batch"], spec["n_micro"]
    steps = spec.get("steps_override") or spec["steps"]

    if spec["kind"] == "mlp":
        stages, wire_dim, out_dim, n_stages = _build_mlp(spec["dims"], n_dev)
        xs, ts = _data_mlp(spec["dims"], batch, POOL)
    elif spec["kind"] == "lenet":
        from simple_distributed_machine_learning_tpu.models.lenet import (
            make_lenet_stages,
        )
        n_stages = 2 if n_dev >= 2 else 1
        stages, wire_dim, out_dim = make_lenet_stages(jax.random.key(0),
                                                      n_stages)
        xs, ts = _data_img(batch, POOL)
    else:
        from simple_distributed_machine_learning_tpu.models.gpt import (
            make_gpt_stages,
        )
        import dataclasses as _dc
        cfg = spec["cfg"]
        if spec.get("attn"):
            fb = spec.get("flash_blocks") or (128, 128)
            cfg = _dc.replace(cfg, attn_impl=spec["attn"],
                              flash_block_q=fb[0], flash_block_k=fb[1])
        tp = spec.get("tp") or 1
        if tp > 1 or spec.get("overlap"):
            # full spec validation through the analyzer preflight: device
            # count, head/hidden divisibility, and the ring-overlap chunk
            # counts — one clear message instead of a trace-time stack
            from simple_distributed_machine_learning_tpu.analysis.preflight import (
                validate_tp_overlap,
            )
            errors, warns = validate_tp_overlap(
                tp, spec.get("overlap") or "none", n_dev, cfg,
                batch=batch, n_micro=n_micro)
            for w in warns:
                sys.stderr.write(f"bench: {name}: {w}\n")
            if errors:
                raise SystemExit(f"bench: {name}: invalid --tp/--overlap "
                                 f"spec:\n  " + "\n  ".join(errors))
        if tp > 1:
            # the TP sweep measures the collective schedule, so the whole
            # mesh goes to the model axis (one stage). This also keeps the
            # ring's ppermutes out of divergent lax.switch branches, whose
            # global collective-permute rendezvous deadlocks on XLA:CPU
            # smoke runs (on TPU the permutes are independent ICI DMAs)
            cfg = _dc.replace(cfg, n_tensor_parallel=tp,
                              overlap=spec.get("overlap") or "none")
            n_stages = 1
        else:
            n_stages = 2 if n_dev >= 2 else 1
        stages, wire_dim, out_dim = make_gpt_stages(jax.random.key(0), cfg,
                                                    n_stages)
        xs, ts = _data_gpt(cfg, batch, POOL)

    n_model = (spec.get("tp") or 1) if spec["kind"] == "gpt" else 1
    mesh = make_mesh(n_stages=n_stages, n_data=1, n_model=n_model)
    dtype = jnp.bfloat16 if spec["dtype"] == "bfloat16" else None
    # 1f1b needs >= 2 stages; on a single chip the pipeline degenerates to
    # the fused path either way
    sched = schedule if n_stages >= 2 else "gpipe"
    pipe = Pipeline(stages, mesh, wire_dim, out_dim, n_microbatches=n_micro,
                    compute_dtype=dtype, schedule=sched,
                    overlap=spec.get("overlap") or "none")
    buf = pipe.init_params()
    lr = spec.get("lr")
    if spec.get("opt") == "adamw":
        opt = adamw(1e-3 if lr is None else lr)
    else:
        opt = sgd(0.1 if lr is None else lr, momentum=0.5)
    opt_state = opt.init(buf)
    step = make_scanned_train_step(pipe, opt, pool_steps=steps)
    key = jax.random.key(0)
    # abstract shapes of the exact step being timed, captured BEFORE any
    # donation: the static ICI-bytes gauge (telemetry/ici.py) traces on these
    from simple_distributed_machine_learning_tpu.analysis import abstractify
    step_sds = (abstractify(buf), abstractify(opt_state), abstractify(xs),
                abstractify(ts), abstractify(key))
    lint_report = None
    if lint:
        # preflight the EXACT scanned step about to be timed (same spec,
        # schedule, overlap, donation) — abstract trace only, no FLOPs
        from simple_distributed_machine_learning_tpu.analysis import analyze
        lint_report = analyze(step, *step_sds, mesh=mesh,
                              name=f"bench:{name}")
        print(lint_report.format(costs=True))
        if not lint_report.ok():
            raise SystemExit(2)
    jax.block_until_ready((xs, ts))

    def timed(reps, buf, opt_state):
        t0 = time.perf_counter()
        for r in range(reps):
            buf, opt_state, losses = step(buf, opt_state, xs, ts,
                                          jax.random.fold_in(key, r))
        final_loss = float(losses[-1])            # forced device->host sync
        return time.perf_counter() - t0, final_loss, buf, opt_state

    t_compile, _, buf, opt_state = timed(1, buf, opt_state)  # compile + warm
    # paired two-point windows: (3 dispatches - 1 dispatch)/2 cancels every
    # fixed cost (dispatch, the host read) within the SAME
    # pair; the median over pairs rejects host-jitter outliers (taking
    # separate mins of t1/t2 across windows is biased when jitter ~ window)
    #
    # every per-window estimate also feeds a StepTimer histogram so rows
    # report p50/p95/max per-step latency, not just the median-derived mean
    from simple_distributed_machine_learning_tpu.telemetry.timer import (
        StepTimer,
    )
    timer = StepTimer()
    timer.record_window(t_compile, steps=1)      # the compile window
    diffs = []
    for _ in range(windows):
        d1, final_loss, buf, opt_state = timed(1, buf, opt_state)
        d3, final_loss, buf, opt_state = timed(3, buf, opt_state)
        diffs.append((d3 - d1) / 2)
        if diffs[-1] > 0:                # negative = jitter swamped the pair
            timer.record_window(diffs[-1], steps=steps,
                                examples=steps * batch)
    diffs.sort()
    dt = diffs[len(diffs) // 2]
    if dt <= 0:
        raise RuntimeError(
            f"{name}: two-point timing collapsed (median diff {dt:.4f}s) - "
            f"dispatch noise exceeds one {steps}-step window; raise --steps")
    sps = steps * batch / dt

    achieved = sps * spec["flops"]     # aggregate FLOP/s across the pipeline
    n_chips = n_stages * n_model

    # observability columns (telemetry/): per-step latency quantiles from
    # the window histogram, the schedule-model pipeline bubble, and the
    # statically expected collective bytes per step — bytes/step next to
    # ms/step. All additive keys: the row schema only ever grows.
    from simple_distributed_machine_learning_tpu.telemetry.bubble import (
        schedule_bubble_fraction,
    )
    from simple_distributed_machine_learning_tpu.telemetry.ici import (
        expected_ici_bytes,
        from_report,
    )
    tstats = timer.summary()
    # --lint already traced this exact step: reuse its cost table instead of
    # paying the jaxpr trace a second time
    ici_info = (from_report(lint_report, steps=steps) if lint_report is not None
                else expected_ici_bytes(step, *step_sds, mesh=mesh,
                                        name=f"bench:{name}", steps=steps))
    return {
        "config": name,
        "samples_per_sec": round(sps, 1),
        "samples_per_sec_per_chip": round(sps / n_chips, 1),
        "n_chips": n_chips,
        "dtype": spec["dtype"] or "float32",
        "flops_per_sample": spec["flops"],
        "achieved_tflops": round(achieved / 1e12, 2),
        "device_kind": kind,
        "backend": jax.default_backend(),
        "optimizer": spec.get("opt", "sgd"),
        "lr": (spec["lr"] if spec.get("lr") is not None
               else (1e-3 if spec.get("opt") == "adamw" else 0.1)),
        "schedule": sched,
        "attn": (spec.get("attn", "dense") if spec["kind"] == "gpt"
                 else None),
        "tp": (spec.get("tp") or 1) if spec["kind"] == "gpt" else None,
        "overlap": ((spec.get("overlap") or "none")
                    if spec["kind"] == "gpt" else None),
        "final_loss": round(final_loss, 4),
        "step_ms_p50": tstats["step_time_ms_p50"],
        "step_ms_p95": tstats["step_time_ms_p95"],
        "step_ms_max": tstats["step_time_ms_max"],
        "compile_s": round(t_compile, 3),
        # schedule-model bubble of what actually RAN (pipe.n_stages and the
        # degraded sched, not the requested flags); non-interleaved 1F1B
        # shares GPipe's (S-1)/(M+S-1) — its win is activation memory
        "bubble_fraction": round(schedule_bubble_fraction(
            pipe.n_stages, pipe.n_microbatches, sched), 4),
        "ici_bytes_per_step": (ici_info["ici_bytes_per_step"]
                               if ici_info else None),
    }, achieved / n_chips


def measure_decode(windows: int = 5, cfg=None, prompt_len: int = 32,
                   b: int = 8, extra_batches: tuple = (1, 32)) -> dict:
    """Decode throughput: KV-cache vs full-prefix-recompute decoders.

    Default shape: the MXU-sized GPT (d=512, L=4, V=8192) generating 224
    tokens from a 32-token prompt, batch 8; ``cfg``/``prompt_len``/``b``
    exist so CPU smoke-drives can run the identical harness on a tiny
    model (n_new is always ``cfg.seq_len - prompt_len``). The recompute decoder re-forwards the whole
    T=256 buffer every step (O(T²) per sequence, models/gpt.py:make_decoder);
    the cached decoder pushes one token against per-layer K/V buffers
    (make_cached_decoder).

    Measurement discipline: every dispatch gets a DISTINCT prompt from a
    resident pool and is closed by a forced host read of the output tokens,
    so neither a result cache keyed on (executable, inputs) nor an async
    handle that ``block_until_ready`` does not force can fake a time.
    Paired two-point windows (1 vs 3 back-to-back dispatches) then cancel
    the per-dispatch fixed cost exactly as in :func:`measure`.
    """
    import jax

    from simple_distributed_machine_learning_tpu.models.gpt import (
        GPTConfig,
        make_cached_decoder,
        make_decoder,
        make_gpt_stages,
    )

    default_shape = cfg is None and prompt_len == 32 and b == 8
    cfg = cfg or GPTConfig(vocab=8192, seq_len=256, d_model=512, n_heads=8,
                           n_layers=4)
    t0 = prompt_len
    n_new = cfg.seq_len - t0
    stages, _, _ = make_gpt_stages(jax.random.key(0), cfg, n_stages=1)
    params = [s.params for s in stages]
    n_disp = 1 + windows * 4            # warm + (1+3) dispatches per window

    def prompt_pool(bb):
        return jax.block_until_ready(jax.random.randint(
            jax.random.key(1), (n_disp, bb, t0), 0, cfg.vocab))

    prompts = prompt_pool(b)
    key = jax.random.key(2)

    def timed(fn, prompts=prompts):
        it = iter(range(n_disp))

        def one():
            out = fn(params, prompts[next(it)], key)
            int(jax.device_get(out[0, -1]))          # forced host read

        one()                                        # compile + warm
        diffs = []
        for _ in range(windows):
            t_start = time.perf_counter()
            one()
            d1 = time.perf_counter() - t_start
            t_start = time.perf_counter()
            one()
            one()
            one()
            d3 = time.perf_counter() - t_start
            diffs.append((d3 - d1) / 2)
        diffs.sort()
        dt = diffs[len(diffs) // 2]
        if dt <= 0:
            raise RuntimeError(
                "decode two-point timing collapsed (median diff "
                f"{dt:.6f}s) - dispatch noise exceeds one decode window")
        return dt

    cached_s = timed(make_cached_decoder(stages, cfg, t0, n_new))
    recompute_s = timed(make_decoder(stages, t0, n_new))
    row = {
        "config": "gpt_decode",
        "prompt_len": t0, "n_new": n_new, "batch": b,
        "tokens_per_sec_cached": round(b * n_new / cached_s, 1),
        "tokens_per_sec_recompute": round(b * n_new / recompute_s, 1),
        "speedup": round(recompute_s / cached_s, 2),
        "device_kind": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
    }
    # batched-decode columns (additive): the cached decoder at other batch
    # sizes — the per-batch-size baseline the serving sweep (--serve) is
    # judged against (a continuous batch of K slots should approach the
    # B=K one-shot column, and beat the B=1 sequential one)
    for bb in extra_batches:
        if bb == b:
            continue
        bs = timed(make_cached_decoder(stages, cfg, t0, n_new),
                   prompts=prompt_pool(bb))
        row[f"tokens_per_sec_cached_b{bb}"] = round(bb * n_new / bs, 1)
    if default_shape:
        # only the benchmark shape owns the artifact — CPU smoke-drives on
        # tiny cfgs must not clobber it
        with open(os.path.join(REPO, "benchmarks", "decode_timing.json"),
                  "w") as f:
            json.dump(row, f, indent=2)
    return row


def measure_serving(rates: tuple = (2.0, 8.0, 32.0), n_requests: int = 24,
                    slots: int = 8, max_new: int = 24, cfg=None,
                    prompt_lens: tuple = (8, 16, 32), block_size: int = 16,
                    compare: bool = True, lint: bool = False,
                    attn_kernel: str = "dense") -> list[dict]:
    """Offered-load sweep of the continuous-batching engine (serve/).

    One row per Poisson arrival rate through an ``slots``-slot engine, plus
    the ``gpt_serve_sequential`` baseline: the SAME workload at the top
    rate through a 1-slot engine — literal one-request-at-a-time decoding,
    which continuous batching must beat on aggregate tokens/sec (that gap
    is the whole subsystem's reason to exist; asserted in
    tests/test_serve.py on the CPU smoke shape). Each row reports
    throughput, TTFT/TPOT p50/p95 and mean slot occupancy — TTFT includes
    genuine queue wait once the offered load exceeds slot capacity.

    With ``compare=True`` the comparison rows ride along (speculative vs
    plain, fused vs gather kernel, quantized pool, availability, fleet,
    disaggregation, host offload, adapters, observability overhead).

    Engines are warmed (every prefill bucket + the decode tick compiled)
    before the trace runs, so latency columns measure serving, not XLA
    compilation. ``cfg``/shape params exist so CPU smoke-drives can run the
    identical harness on a tiny model; only the default (MXU-sized) shape
    writes the ``benchmarks/serving.json`` artifact.
    """
    import jax
    import numpy as np

    from simple_distributed_machine_learning_tpu.models.gpt import (
        GPTConfig,
        make_gpt_stages,
    )
    from simple_distributed_machine_learning_tpu.serve import (
        InferenceEngine,
        ServeMetrics,
        SimConfig,
        simulate,
    )

    default_shape = (cfg is None and slots == 8 and n_requests == 24
                     and max_new == 24 and rates == (2.0, 8.0, 32.0)
                     and prompt_lens == (8, 16, 32) and block_size == 16
                     and attn_kernel == "dense")
    cfg = cfg or GPTConfig(vocab=8192, seq_len=256, d_model=512, n_heads=8,
                           n_layers=4)
    if max(prompt_lens) + max_new > cfg.seq_len:
        raise ValueError(
            f"prompt {max(prompt_lens)} + max_new {max_new} exceeds "
            f"seq_len {cfg.seq_len}")
    stages, _, _ = make_gpt_stages(jax.random.key(0), cfg, n_stages=1)
    if lint:
        # --serve --lint: preflight the EXACT serving programs this sweep
        # is about to time — the sweep engines (including the 1-slot
        # sequential baseline) AND, with compare=True, the comparison
        # engines, whose n_slots/prefill_chunk are traced shapes and
        # contract bounds, i.e. DIFFERENT compiled programs — abort before
        # any compile/timing work on ERROR findings
        from simple_distributed_machine_learning_tpu.analysis.programs import (
            ServeSpec,
            lint_serve,
        )
        sspecs = [
            # the sweep rows and the 1-slot sequential baseline (n_slots is
            # a traced shape: different compiled programs)
            ServeSpec(cfg, n_slots=slots,
                      block_size=block_size, prompt_lens=prompt_lens,
                      attn_kernel=attn_kernel),
            ServeSpec(cfg, n_slots=1,
                      block_size=block_size, prompt_lens=prompt_lens,
                      attn_kernel=attn_kernel),
            # the kernel-comparison engines (both attention paths) and the
            # int8 pool the quantized fixed-mem rows build — each a
            # distinct compiled program family
            ServeSpec(cfg, n_slots=slots,
                      block_size=block_size, prompt_lens=prompt_lens,
                      attn_kernel="fused"),
            ServeSpec(cfg, n_slots=slots,
                      block_size=block_size, prompt_lens=prompt_lens,
                      cache_dtype="int8"),
            # the speculative comparison engines (draft == target): the
            # propose scan, the batched verify and the fused tick are
            # DIFFERENT compiled programs from the plain sweep's
            ServeSpec(cfg, n_slots=min(slots, 4),
                      block_size=block_size, prompt_lens=prompt_lens,
                      spec_k=SPEC_BENCH_K, draft_cfg=cfg)]
        if compare:
            # the availability row's supervised engine (chunked prefill =
            # block_size bounds its recovery-retrace shapes) — a distinct
            # compiled geometry, so it preflights too
            sspecs.append(ServeSpec(cfg, n_slots=min(slots, 4),
                                    block_size=block_size,
                                    prefill_chunk=block_size,
                                    prompt_lens=prompt_lens))
        seen = []
        for sspec in sspecs:
            if sspec in seen:
                continue
            seen.append(sspec)
            rep = lint_serve(stages, sspec,
                             draft_stages=(stages if sspec.spec_k else None))
            print(rep.format(costs=False))
            if not rep.ok():
                raise SystemExit("bench --serve: serve-program preflight "
                                 "found ERROR findings")
        print(f"bench --serve: lint preflight clean "
              f"({len(seen)} deployments)")

    def run(rate, n_slots, label):
        engine = InferenceEngine(stages, cfg, n_slots=n_slots,
                                 block_size=block_size,
                                 attn_kernel=attn_kernel)
        # warm every compiled shape OUTSIDE the measured trace: one tiny
        # request per prompt-length bucket (prefill shapes) + decode ticks
        for t0 in prompt_lens:
            engine.submit(np.zeros(t0, np.int32), max_new_tokens=2)
        engine.drain()
        engine.metrics = metrics = ServeMetrics()
        rep = simulate(engine, SimConfig(
            n_requests=n_requests, rate=rate, seed=0,
            prompt_lens=prompt_lens, max_new_tokens=max_new))
        s = metrics.summary()
        return {
            "config": label, "rate": rate, "n_slots": n_slots,
            "n_requests": n_requests, "max_new_tokens": max_new,
            "completed": rep["completed"], "wall_s": rep["wall_s"],
            "tokens_per_sec": s["tokens_per_sec"],
            "ttft_ms_p50": s["ttft_ms_p50"], "ttft_ms_p95": s["ttft_ms_p95"],
            "tpot_ms_p50": s["tpot_ms_p50"], "tpot_ms_p95": s["tpot_ms_p95"],
            "slot_occupancy_mean": s["slot_occupancy_mean"],
            "tp": s.get("tp", 1), "spec_k": s.get("spec_k", 0),
            "accept_rate": s.get("spec_accept_rate"),
            "device_kind": jax.devices()[0].device_kind,
            "backend": jax.default_backend(),
        }

    rows = [run(max(rates), 1, "gpt_serve_sequential")]
    rows += [run(r, slots, "gpt_serve") for r in rates]
    if compare:
        rows += _measure_spec_vs_plain(stages, cfg, slots=min(slots, 4),
                                       n_requests=n_requests,
                                       max_new=max_new,
                                       prompt_lens=prompt_lens,
                                       block_size=block_size)
        # the ISSUE-15 rows: fused-kernel vs dense per-tick HBM bytes +
        # ticks/sec, and the int8 pool's fixed-KV-bytes concurrency win
        rows += _measure_kernel_and_quant(stages, cfg, slots=min(slots, 4),
                                          n_requests=n_requests,
                                          max_new=max_new,
                                          prompt_lens=prompt_lens,
                                          block_size=block_size)
        # the availability row: completed-within-deadline fraction while a
        # mid-flight engine crash restarts through the serve supervisor
        rows += _measure_availability(stages, cfg, slots=min(slots, 4),
                                      n_requests=n_requests,
                                      max_new=max_new,
                                      prompt_lens=prompt_lens,
                                      block_size=block_size)
        # the fleet availability row: same question one level up — a whole
        # replica killed mid-decode, its in-flight requests migrated onto
        # the survivors from its journal alone (serve/fleet.py). The
        # per-replica engine geometry matches the availability row's, so
        # the --lint preflight and the build cache already cover it
        rows += _measure_fleet_availability(stages, cfg,
                                            slots=min(slots, 4),
                                            n_requests=n_requests,
                                            max_new=max_new,
                                            prompt_lens=prompt_lens,
                                            block_size=block_size)
        # the ISSUE-17 rows: disaggregated prefill/decode pools vs the
        # symmetric fleet (same burst, same replica count), and the host
        # offload tier's prefix-cache win under HBM pressure
        rows += _measure_disaggregation(stages, cfg,
                                        n_requests=n_requests,
                                        max_new=max_new,
                                        prompt_lens=prompt_lens,
                                        block_size=block_size)
        rows += _measure_host_offload(stages, cfg,
                                      n_requests=min(n_requests, 12),
                                      block_size=block_size)
        # the ISSUE-20 row: N LoRA tenants batched through one engine's
        # adapter bank vs N sequential dedicated merged-dense engines
        rows += _measure_multi_adapter(stages, cfg, slots=min(slots, 4),
                                       n_requests=min(n_requests, 12),
                                       max_new=max_new,
                                       prompt_lens=prompt_lens,
                                       block_size=block_size)
        # the ISSUE-19 row: what the always-on observability pipeline
        # (SLO engine + trace + TTFT attribution) costs per tick
        rows += _measure_slo_overhead(stages, cfg, slots=min(slots, 4),
                                      n_requests=n_requests,
                                      max_new=max_new,
                                      prompt_lens=prompt_lens,
                                      block_size=block_size)
    if default_shape:
        with open(os.path.join(REPO, "benchmarks", "serving.json"),
                  "w") as f:
            json.dump({"device": rows[0]["device_kind"],
                       "backend": rows[0]["backend"], "rows": rows},
                      f, indent=2)
    return rows


def _drain_burst(engine, specs):
    """Submit everything at t=0 and drive to empty — the one burst-drain
    helper every comparison row family measures with. Returns
    ``(handles, ticks, tokens, peak concurrent active, completed,
    wall_s)``."""
    import time as _time

    handles = [engine.submit(**sp) for sp in specs]
    ticks, toks, peak = 0, 0, 0
    t0 = _time.perf_counter()
    while engine.busy:
        toks += engine.step()
        ticks += 1
        peak = max(peak, engine.pool.n_active)
    wall = _time.perf_counter() - t0
    done = sum(1 for h in handles if h.state == "done")
    return handles, ticks, toks, peak, done, wall


def _measure_kernel_and_quant(stages, cfg, slots: int, n_requests: int,
                              max_new: int, prompt_lens: tuple,
                              block_size: int) -> list[dict]:
    """The ISSUE-15 serve-path rows: the fused Pallas paged-attention
    kernel vs the gather-then-dense path, and the int8-quantized pool vs
    bf16 at fixed KV bytes.

    1. ``paged_attention_kernel`` (one row per kernel path) — the SAME
       burst workload drained through ``attn_kernel="dense"`` and
       ``"fused"`` engines: measured ticks/sec and tokens/sec ride along,
       and each row carries the ANALYZER's per-tick decode K/V bytes
       (``hbm_tick_costs`` over ``engine_spec`` — the exact deployment,
       not a parallel description). The dense row's bytes include the
       ``decode.kv_attn_reread`` pass the kernel eliminates, so
       ``hbm_reduction`` on the fused row is the single-pass win (2x);
       greedy token streams are asserted IDENTICAL across the two engines
       (the bit-exactness anchor, run on every bench round).

    2. ``gpt_serve_quantized_fixed_mem`` (one row per cache dtype) — a
       bf16 pool and an int8 pool sized from the SAME byte budget
       (``n_blocks_for_bytes``, scale planes billed), drained under an
       all-at-once burst; ``max_concurrent`` is the resident-request
       count the quantized pool exists to multiply. The int8 row carries
       ``resident_ratio`` vs bf16 (the >= 2x gate the CI smoke and
       tests/test_paged_attention.py assert).
    """
    import jax
    import numpy as np

    from simple_distributed_machine_learning_tpu.analysis.programs import (
        engine_spec,
        hbm_tick_costs,
    )
    from simple_distributed_machine_learning_tpu.serve import (
        InferenceEngine,
    )
    from simple_distributed_machine_learning_tpu.serve.slots import (
        kv_block_bytes,
        n_blocks_for_bytes,
    )

    dev = {"device_kind": jax.devices()[0].device_kind,
           "backend": jax.default_backend()}
    rng = np.random.default_rng(11)

    def _specs(n, seed0=0):
        return [dict(prompt=rng.integers(
                         0, cfg.vocab,
                         prompt_lens[i % len(prompt_lens)]).astype(np.int32),
                     max_new_tokens=max_new, seed=seed0 + i)
                for i in range(n)]

    out = []
    # -- 1. dense vs fused kernel path -------------------------------------
    streams = {}
    burst = _specs(n_requests)
    for kernel in ("dense", "fused"):
        engine = InferenceEngine(stages, cfg, n_slots=slots,
                                 block_size=block_size, attn_kernel=kernel)
        for t0 in prompt_lens:       # warm every compiled shape
            engine.submit(np.zeros(t0, np.int32), max_new_tokens=2)
        engine.drain()
        handles, ticks, toks, _peak, done, wall = _drain_burst(
            engine, [dict(sp) for sp in burst])
        streams[kernel] = [list(h.tokens) for h in handles]
        costs = {h.op: h.bytes_per_tick
                 for h in hbm_tick_costs(engine_spec(engine),
                                         n_layers=engine._n_layers)}
        decode_bytes = (costs["decode.kv_gather"]
                        + costs.get("decode.kv_attn_reread", 0))
        out.append({
            "config": "paged_attention_kernel", "kernel": kernel,
            "n_slots": slots, "n_requests": n_requests,
            "completed": done, "ticks": ticks,
            "ticks_per_sec": round(ticks / wall, 1),
            "tokens_per_sec": round(toks / wall, 1),
            "decode_kv_bytes_per_tick": decode_bytes, **dev,
        })
    # the bit-exactness anchor, REPORTED rather than raised: on a real
    # accelerator the kernel's different accumulation order may flip a
    # genuine near-tie argmax (the tests/tolerances.py budget), and a
    # measurement round must record that, not abort. Sparse flips within
    # the near-tie budget report bit_exact false with the fraction; a
    # wholesale divergence (a real math bug) still fails loudly
    flat_d = [t for s_ in streams["dense"] for t in s_]
    flat_f = [t for s_ in streams["fused"] for t in s_]
    mismatch = (sum(a != b for a, b in zip(flat_d, flat_f))
                / max(len(flat_d), 1))
    if mismatch > 0.25:    # pragma: no cover - gate
        raise AssertionError(
            f"bench: fused-kernel greedy streams diverged {mismatch:.0%} "
            f"from the dense path — beyond any near-tie budget, the "
            f"parity anchor is broken")
    dense_b = out[-2]["decode_kv_bytes_per_tick"]
    fused_b = out[-1]["decode_kv_bytes_per_tick"]
    out[-1]["hbm_reduction"] = round(dense_b / fused_b, 2)
    out[-1]["streams_bit_exact"] = mismatch == 0
    if mismatch:           # pragma: no cover - near-tie corner on-chip
        out[-1]["stream_mismatch_fraction"] = round(mismatch, 4)
        sys.stderr.write(
            f"bench: fused streams flipped {mismatch:.2%} of tokens "
            f"(near-tie argmax under reordered accumulation)\n")

    # -- 2. int8 vs bf16 resident requests at fixed KV bytes ---------------
    L = sum(len(p["blocks"]) for p in (s.params for s in stages))
    dh = cfg.d_model // cfg.n_heads
    # cap the pools' per-sequence budget at the workload's footprint (the
    # pool refuses a capacity that cannot hold one full sequence, and the
    # comparison is about RESIDENT REQUESTS, not unreachable headroom)
    ml_q = max(prompt_lens) + max_new
    bpr = -(-ml_q // block_size)         # == the pools' blocks_per_seq
    # a realistic non-divisible budget: 2 requests' worth of bf16 blocks
    # plus one stranded block (fixed budgets never divide evenly)
    budget = (2 * bpr + 1) * kv_block_bytes(L, cfg.n_heads, block_size, dh,
                                            "bfloat16")
    base_concurrent = None
    for cd in ("bfloat16", "int8"):
        nb = n_blocks_for_bytes(budget, L, cfg.n_heads, block_size, dh, cd)
        n_slots_q = min(32, max(2, nb // bpr + 1))
        engine = InferenceEngine(stages, cfg, n_slots=n_slots_q,
                                 max_len=ml_q, block_size=block_size,
                                 n_blocks=nb, cache_dtype=cd)
        for t0 in prompt_lens:
            engine.submit(np.zeros(t0, np.int32), max_new_tokens=2)
        engine.drain()
        # every request the longest shape: the budget maths above sized
        # the pool for exactly this per-request footprint
        specs = [dict(prompt=rng.integers(0, cfg.vocab,
                                          max(prompt_lens)).astype(np.int32),
                      max_new_tokens=max_new, seed=700 + i)
                 for i in range(max(n_requests, 3 * n_slots_q))]
        _h, _ticks, toks, peak, done, wall = _drain_burst(engine, specs)
        row = {
            "config": "gpt_serve_quantized_fixed_mem", "cache_dtype": cd,
            "kv_budget_bytes": int(budget), "n_blocks": nb,
            "n_slots": n_slots_q, "bytes_per_block": engine.pool.
            bytes_per_block, "n_requests": len(specs), "completed": done,
            "max_concurrent": peak,
            "tokens_per_sec": round(toks / wall, 1), **dev,
        }
        if base_concurrent is None:
            base_concurrent = peak
        else:
            row["resident_ratio"] = round(peak / base_concurrent, 2)
        out.append(row)
    return out


# verify width of the speculative bench comparison (and its lint spec):
# draft == target makes every greedy proposal accepted, so the tick emits
# exactly SPEC_BENCH_K tokens per slot — the amortization ceiling
SPEC_BENCH_K = 4


def _measure_spec_vs_plain(stages, cfg, slots: int, n_requests: int,
                           max_new: int, prompt_lens: tuple,
                           block_size: int, spec_k: int = SPEC_BENCH_K
                           ) -> list:
    """Speculative-vs-plain aggregate throughput on the SAME workload with
    ``draft == target`` — every greedy proposal verifies, so acceptance
    pins at 1.0 and each speculative tick emits ``spec_k`` tokens per
    decoding slot (the amortization ceiling, isolated from draft quality).

    The GATED numbers are tokens per engine TICK, measured by draining the
    identical all-submitted-up-front workload through both engines and
    counting ``engine.step()`` calls: a tick is one fixed program-dispatch
    round (the launch + weight/KV-stream cost speculative decoding exists
    to amortize), and the tick counts are fully deterministic — the same
    on every machine — so tests/CI can assert the >= 2x amortization bar
    without flaking on a loaded box. Real wall tokens/sec for both modes
    ride along as informational columns (on real accelerators the wall
    speedup is what the per-tick cost argument predicts; on a tiny CPU
    smoke shape wall time is host-noise-dominated, which is exactly why
    the gate counts ticks)."""
    import time as _time

    import jax
    import numpy as np

    from simple_distributed_machine_learning_tpu.serve import (
        InferenceEngine,
        ServeMetrics,
    )

    def run(spec: bool) -> dict:
        kw = dict(block_size=block_size)
        if spec:
            kw.update(draft_stages=stages, draft_cfg=cfg, spec_k=spec_k)
        engine = InferenceEngine(stages, cfg, n_slots=slots, **kw)
        for t0 in prompt_lens:    # warm every compiled shape
            engine.submit(np.zeros(t0, np.int32), max_new_tokens=2)
        engine.drain()
        engine.metrics = metrics = ServeMetrics()
        rng = np.random.default_rng(0)
        t0w = _time.perf_counter()
        for i in range(n_requests):
            engine.submit(
                rng.integers(0, cfg.vocab,
                             prompt_lens[i % len(prompt_lens)]).astype(
                                 np.int32),
                max_new_tokens=max_new)
        ticks = 0
        while engine.busy:
            engine.step()
            ticks += 1
        wall = _time.perf_counter() - t0w
        s = metrics.summary()
        tokens = n_requests * max_new
        return {"ticks": ticks, "tokens_per_tick": round(tokens / ticks, 3),
                "wall_tokens_per_sec": round(tokens / wall, 1),
                "accept_rate": s.get("spec_accept_rate")}

    sr, pr = run(True), run(False)
    return [{
        "config": "gpt_serve_spec_vs_plain", "n_slots": slots,
        "n_requests": n_requests, "max_new_tokens": max_new,
        "spec_k": spec_k, "accept_rate": sr["accept_rate"],
        # the deterministic gate columns: same workload, counted ticks
        "ticks_spec": sr["ticks"], "ticks_plain": pr["ticks"],
        "tokens_per_tick_spec": sr["tokens_per_tick"],
        "tokens_per_tick_plain": pr["tokens_per_tick"],
        "speedup_vs_plain": round(sr["tokens_per_tick"]
                                  / pr["tokens_per_tick"], 2),
        # informational wall-clock columns
        "wall_tokens_per_sec_spec": sr["wall_tokens_per_sec"],
        "wall_tokens_per_sec_plain": pr["wall_tokens_per_sec"],
        "device_kind": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
    }]


def _measure_availability(stages, cfg, slots: int, n_requests: int,
                          max_new: int, prompt_lens: tuple,
                          block_size: int, deadline_s: float = 120.0,
                          crash_tick: int = 5, max_restarts: int = 3
                          ) -> list:
    """Serving availability under an injected engine crash: the fraction
    of requests that complete WITHIN their deadline while the serve
    supervisor (``serve/supervisor.py``) rebuilds the crashed engine and
    recovers every in-flight request from the journal.

    One ``engine-crash@serve.tick`` fires mid-flight; the row reports
    ``availability`` = completed-within-deadline / submitted (requests the
    supervisor shed on an expired deadline count AGAINST availability —
    that is the metric's point), the restart count, and how many requests
    were recovered from the journal.  With the default generous deadline
    the smoke shape pins availability == 1.0 and restarts >= 1
    (tests/test_serve_supervisor.py): a crash costs a restart, not
    completions.  Tightening ``deadline_s`` turns the same harness into a
    recovery-latency budget measurement."""
    import tempfile
    import time as _time

    import jax
    import numpy as np

    from simple_distributed_machine_learning_tpu.resilience import faults
    from simple_distributed_machine_learning_tpu.serve import (
        ServeMetrics,
        ServeSupervisor,
        engine_factory,
    )

    metrics = ServeMetrics()
    plan = faults.install(faults.FaultPlan.parse(
        f"engine-crash@serve.tick={crash_tick}"))
    tmpdir = tempfile.TemporaryDirectory(prefix="sdml-bench-journal-")
    try:
        sup = ServeSupervisor(
            # chunked prefill bounds the recovery re-prefill to chunk-sized
            # compiled shapes (the engine.preempt compile-cost note)
            engine_factory(stages, cfg, n_slots=slots,
                           block_size=block_size, prefill_chunk=block_size,
                           metrics=metrics),
            os.path.join(tmpdir.name, "journal.jsonl"), metrics=metrics,
            max_restarts=max_restarts, default_deadline_s=deadline_s,
            # the crash forensics ride along: the injected restart must
            # leave a post-mortem bundle (flight rows + request states +
            # journal tail), and the row reports how many were written
            postmortem_dir=tmpdir.name)
        rng = np.random.default_rng(0)
        t0w = _time.perf_counter()
        for i in range(n_requests):
            sup.submit(
                rng.integers(0, cfg.vocab,
                             prompt_lens[i % len(prompt_lens)]).astype(
                                 np.int32),
                max_new_tokens=max_new)
        sup.drain()
        sup.close()
        wall = _time.perf_counter() - t0w
        postmortems = len(sup.postmortems)
    finally:
        faults.uninstall()
        tmpdir.cleanup()
    s = metrics.summary()
    completed = sum(1 for r in sup.requests.values() if r.state == "done")
    return [{
        "config": "gpt_serve_availability_crash", "n_slots": slots,
        "n_requests": n_requests, "max_new_tokens": max_new,
        "deadline_s": deadline_s, "crash_tick": crash_tick,
        # the headline: completed-within-deadline fraction under the crash
        "availability": round(completed / n_requests, 4),
        "completed": completed,
        "shed_deadline": s.get("shed_by_reason", {}).get("deadline", 0),
        "restarts": s.get("restarts", 0),
        "recovered_requests": s.get("recovered_requests", 0),
        "postmortem_bundles": postmortems,
        "faults_fired": plan.stats()["total_fired"],
        "wall_s": round(wall, 3),
        "device_kind": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
    }]


def _measure_fleet_availability(stages, cfg, n_requests: int, max_new: int,
                                prompt_lens: tuple, block_size: int,
                                replicas: int = 3, slots: int = 4,
                                deadline_s: float = 120.0,
                                kill_tick: int = 5) -> list:
    """Serving availability under a WHOLE-REPLICA loss: a 3-replica fleet
    (``serve/fleet.py``) loses one replica mid-decode
    (``replica-kill@fleet.tick``) and must migrate its in-flight requests
    onto the survivors from the dead replica's journal alone.

    ``availability`` = completed-within-deadline / submitted, like
    :func:`_measure_availability` one level down — with the default
    generous deadline the smoke shape pins availability == 1.0 with
    ``replica_losses == 1`` and ``migrations >= 1``
    (tests/test_fleet.py): losing a replica costs a migration, never a
    completion."""
    import tempfile
    import time as _time

    import jax
    import numpy as np

    from simple_distributed_machine_learning_tpu.resilience import faults
    from simple_distributed_machine_learning_tpu.serve import (
        ServeFleet,
        ServeMetrics,
        engine_factory,
    )

    metrics = ServeMetrics()
    plan = faults.install(faults.FaultPlan.parse(
        f"replica-kill@fleet.tick={kill_tick}"))
    tmpdir = tempfile.TemporaryDirectory(prefix="sdml-bench-fleet-")
    try:
        fleet = ServeFleet(
            engine_factory(stages, cfg, n_slots=slots,
                           block_size=block_size, prefill_chunk=block_size,
                           metrics=metrics),
            tmpdir.name, n_replicas=replicas, metrics=metrics,
            default_deadline_s=deadline_s)
        rng = np.random.default_rng(0)
        t0w = _time.perf_counter()
        for i in range(n_requests):
            fleet.submit(
                rng.integers(0, cfg.vocab,
                             prompt_lens[i % len(prompt_lens)]).astype(
                                 np.int32),
                max_new_tokens=max_new)
        fleet.drain()
        fleet.close()
        wall = _time.perf_counter() - t0w
    finally:
        faults.uninstall()
        tmpdir.cleanup()
    s = metrics.summary()
    completed = sum(1 for r in fleet.requests.values()
                    if r.state == "done")
    return [{
        "config": "gpt_serve_fleet_availability_replica_loss",
        "replicas": replicas, "n_slots": slots,
        "n_requests": n_requests, "max_new_tokens": max_new,
        "deadline_s": deadline_s, "kill_tick": kill_tick,
        # the headline: completed-within-deadline fraction under the loss
        "availability": round(completed / n_requests, 4),
        "completed": completed,
        "shed_deadline": s.get("shed_by_reason", {}).get("deadline", 0),
        "replica_losses": fleet.replica_losses,
        "migrations": fleet.migrations,
        "affinity_hits": s.get("route_affinity_hits", 0),
        "faults_fired": plan.stats()["total_fired"],
        "wall_s": round(wall, 3),
        "device_kind": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
    }]


def _measure_disaggregation(stages, cfg, n_requests: int, max_new: int,
                            prompt_lens: tuple, block_size: int,
                            replicas: int = 4, prefill_replicas: int = 2,
                            slots: int = 2) -> list:
    """Disaggregated prefill/decode pools vs the symmetric fleet
    (``serve/fleet.py``, ISSUE 17): the SAME burst of requests through the
    same replica count both ways. In the symmetric fleet every slot is
    shared between prefilling new arrivals and decoding old ones, so
    lingering decodes block fresh prefills; disaggregated, the prefill
    pool's slots free at end-of-prefill (the journal snap/adopt handoff
    moves the request to the decode pool) and TTFT tracks prefill-pool
    turnover only. The row reports TTFT p95 both ways plus the handoff
    count; the exact-pinned virtual-clock gate lives in
    ``resilience/scenarios.py::disagg-prefill-heavy``."""
    import tempfile
    import time as _time

    import jax
    import numpy as np

    from simple_distributed_machine_learning_tpu.serve import (
        ServeFleet,
        ServeMetrics,
        engine_factory,
    )

    def run(n_prefill):
        metrics = ServeMetrics()
        tmpdir = tempfile.TemporaryDirectory(prefix="sdml-bench-disagg-")
        try:
            fleet = ServeFleet(
                engine_factory(stages, cfg, n_slots=slots,
                               block_size=block_size,
                               prefill_chunk=block_size, metrics=metrics),
                tmpdir.name, n_replicas=replicas,
                prefill_replicas=n_prefill, metrics=metrics)
            rng = np.random.default_rng(0)
            t0 = _time.perf_counter()
            for i in range(n_requests):
                fleet.submit(
                    rng.integers(0, cfg.vocab,
                                 prompt_lens[i % len(prompt_lens)]).astype(
                                     np.int32),
                    max_new_tokens=max_new)
            fleet.drain()
            fleet.close()
            wall = _time.perf_counter() - t0
        finally:
            tmpdir.cleanup()
        completed = sum(1 for r in fleet.requests.values()
                        if r.state == "done")
        return metrics.summary(), wall, fleet.handoffs, completed

    sym, sym_wall, _, sym_done = run(0)
    dis, dis_wall, handoffs, dis_done = run(prefill_replicas)
    return [{
        "config": "gpt_serve_disagg_prefill_decode",
        "replicas": replicas, "prefill_replicas": prefill_replicas,
        "n_slots": slots, "n_requests": n_requests,
        "max_new_tokens": max_new,
        "completed": dis_done, "completed_symmetric": sym_done,
        "handoffs": handoffs,
        "ttft_ms_p95": dis.get("ttft_ms_p95"),
        "ttft_ms_p95_symmetric": sym.get("ttft_ms_p95"),
        "tokens_per_sec": dis.get("tokens_per_sec"),
        "tokens_per_sec_symmetric": sym.get("tokens_per_sec"),
        "wall_s": round(dis_wall, 3),
        "wall_s_symmetric": round(sym_wall, 3),
        "device_kind": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
    }]


def _measure_host_offload(stages, cfg, n_requests: int,
                          block_size: int, slots: int = 2) -> list:
    """The host offload tier's prefix-cache win under HBM pressure
    (``serve/slots.py``, ISSUE 17): alternate hot-prefix requests with
    prefix-less scans through a pool sized to ONE full sequence, with and
    without the host tier. Each scan evicts the idle shared prefix; the
    HBM-only pool discards it (the next hot request re-prefills from
    scratch) while the tiered pool demotes it to host RAM and the router's
    affinity probe starts the prefetch upload back at submit time. The
    row pins the mechanism end to end: demotions, promotions, prefetch
    hits and the device prefix-hit gap over the HBM-only baseline."""
    import tempfile
    import time as _time

    import jax
    import numpy as np

    from simple_distributed_machine_learning_tpu.serve import (
        ServeFleet,
        ServeMetrics,
        engine_factory,
    )

    bs = block_size
    prefix = np.arange(2 * bs, dtype=np.int32) % cfg.vocab
    max_len = 6 * bs                   # the scan's full extent
    n_blocks = 6                       # exactly one full sequence: maximal
    #                                    pressure, every scan evicts

    def run(host_blocks):
        metrics = ServeMetrics()
        tmpdir = tempfile.TemporaryDirectory(prefix="sdml-bench-host-")
        try:
            fleet = ServeFleet(
                engine_factory(stages, cfg, n_slots=slots,
                               block_size=bs,
                               n_blocks=n_blocks, max_len=max_len,
                               prefill_chunk=bs,
                               host_cache_blocks=host_blocks,
                               metrics=metrics),
                tmpdir.name, n_replicas=1, metrics=metrics)
            rng = np.random.default_rng(0)
            t0 = _time.perf_counter()
            for i in range(n_requests):
                if i % 2 == 0:         # hot: shared prefix + unique tail
                    prompt = np.concatenate(
                        [prefix,
                         rng.integers(0, cfg.vocab, bs).astype(np.int32)])
                    fleet.submit(prompt, max_new_tokens=bs)
                else:                  # scan: prefix-less, pool-filling
                    fleet.submit(
                        rng.integers(0, cfg.vocab, 4 * bs).astype(np.int32),
                        max_new_tokens=2 * bs)
                fleet.drain()          # sequential: each scan's eviction
                #                        lands before the next hot arrival
            fleet.close()
            wall = _time.perf_counter() - t0
        finally:
            tmpdir.cleanup()
        return metrics.summary(), wall

    base, base_wall = run(0)
    tier, tier_wall = run(n_blocks)
    return [{
        "config": "gpt_serve_host_offload_prefix",
        "n_slots": slots, "n_requests": n_requests,
        "block_size": bs, "n_blocks": n_blocks,
        "host_cache_blocks": n_blocks,
        "prefix_hit_blocks": tier.get("prefix_hit_blocks", 0),
        "prefix_hit_blocks_hbm_only": base.get("prefix_hit_blocks", 0),
        "host_demotes": tier.get("host_demotes", 0),
        "host_promotes": tier.get("host_promotes", 0),
        "host_prefetch_hits": tier.get("host_prefetch_hits", 0),
        "host_transfer_bytes": tier.get("host_transfer_bytes", 0),
        "wall_s": round(tier_wall, 3),
        "wall_s_hbm_only": round(base_wall, 3),
        "device_kind": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
    }]


def _measure_multi_adapter(stages, cfg, slots: int, n_requests: int,
                           max_new: int, prompt_lens: tuple,
                           block_size: int, n_adapters: int = 3,
                           rank: int = 4) -> list:
    """Multi-tenant LoRA serving's consolidation claim (ISSUE 20),
    measured head to head: N tenants through ONE engine — shared base
    weights plus a gathered adapter bank, every tick batching whatever
    tenant mix is resident — vs the dedicated deployment, N engines each
    serving its tenant's merged ``W + A @ B`` weights one after the
    other. Same prompts, same decode lengths, same total request count.
    The row reports tokens/sec both ways and the memory story: the
    bank's resident bytes vs the ``N - 1`` extra full parameter copies
    the dedicated deployment pays (the adapter path keeps ONE base
    copy)."""
    import dataclasses as _dc
    import time as _time

    import jax
    import numpy as np

    from simple_distributed_machine_learning_tpu.models import lora
    from simple_distributed_machine_learning_tpu.serve import (
        InferenceEngine,
    )
    from simple_distributed_machine_learning_tpu.serve.adapters import (
        AdapterStore,
    )

    rng = np.random.default_rng(11)
    names = [f"tenant-{k}" for k in range(n_adapters)]
    adapters = {name: lora.init_lora_adapter(jax.random.key(100 + k),
                                             cfg, rank)
                for k, name in enumerate(names)}
    prompts = [rng.integers(0, cfg.vocab,
                            prompt_lens[i % len(prompt_lens)])
               .astype(np.int32) for i in range(n_requests)]
    tenant_of = [names[i % n_adapters] for i in range(n_requests)]
    params_list = [s.params for s in stages]
    base_bytes = int(sum(x.nbytes for x in jax.tree.leaves(params_list)))

    def _warm(engine, adapter=None):
        # compile every shape outside the timed window (both sides pay
        # their tracing up front, so the row measures steady-state ticks)
        for t0 in sorted(set(len(p) for p in prompts)):
            engine.submit(rng.integers(0, cfg.vocab, t0).astype(np.int32),
                          max_new_tokens=2, adapter=adapter)
        engine.drain()

    # -- one engine, N tenants batched through the adapter bank ----------
    store = AdapterStore(cfg, rank, slots)
    for name in names:
        store.register(name, adapters[name])
    multi = InferenceEngine(stages, cfg, n_slots=slots,
                            block_size=block_size, adapters=store)
    _warm(multi, adapter=names[0])
    handles = []
    t0 = _time.perf_counter()
    for i, prompt in enumerate(prompts):
        handles.append(multi.submit(prompt, max_new_tokens=max_new,
                                    seed=2000 + i,
                                    adapter=tenant_of[i]))
    toks = 0
    while multi.busy:
        toks += multi.step()
    multi_wall = _time.perf_counter() - t0
    multi_done = sum(1 for h in handles if h.state == "done")

    # -- the dedicated baseline: one merged-dense engine per tenant ------
    merged_wall, merged_done, merged_toks = 0.0, 0, 0
    for name in names:
        merged = [_dc.replace(s, params=p) for s, p in
                  zip(stages, lora.merge_adapter(params_list,
                                                 adapters[name]))]
        engine = InferenceEngine(merged, cfg, n_slots=slots,
                                 block_size=block_size)
        _warm(engine)
        mine = [i for i in range(n_requests) if tenant_of[i] == name]
        t0 = _time.perf_counter()
        hs = [engine.submit(prompts[i], max_new_tokens=max_new,
                            seed=2000 + i) for i in mine]
        while engine.busy:
            merged_toks += engine.step()
        merged_wall += _time.perf_counter() - t0
        merged_done += sum(1 for h in hs if h.state == "done")

    return [{
        "config": "gpt_serve_multi_adapter",
        "n_adapters": n_adapters, "adapter_rank": rank,
        "n_slots": slots, "n_requests": n_requests,
        "max_new_tokens": max_new,
        "completed": multi_done,
        "completed_merged_sequential": merged_done,
        "tokens_per_sec": round(toks / multi_wall, 1),
        "tokens_per_sec_merged_sequential": round(
            merged_toks / merged_wall, 1),
        "adapter_resident_bytes": store.resident_bytes,
        "adapter_swaps": store.swaps_total,
        "base_param_bytes": base_bytes,
        "merged_param_bytes_total": n_adapters * base_bytes,
        "param_bytes_saved": (n_adapters - 1) * base_bytes
        - store.resident_bytes,
        "device_kind": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
    }]


def _measure_slo_overhead(stages, cfg, slots: int, n_requests: int,
                          max_new: int, prompt_lens: tuple,
                          block_size: int) -> list:
    """Cost of the ISSUE-19 observability pipeline: the identical
    supervised serve run with the SLO engine + request trace +
    TTFT attribution ON vs OFF, reported as ticks/sec both ways.

    The ON side binds an :class:`~telemetry.slo.SLOEngine` (windowed
    quantile histograms + per-tick burn-rate alert evaluation) and an
    in-memory :class:`~serve.tracing.ServeTrace`, then folds every
    request through :func:`~telemetry.attribution.attribute` after the
    drain — the full always-on production telemetry path.  The OFF side
    is the bare supervisor.  Both sides share engine geometry (and so
    the decode build cache and every compiled shape), and a warmup pass
    runs first so neither measured side pays compile time."""
    import tempfile
    import time as _time

    import jax
    import numpy as np

    from simple_distributed_machine_learning_tpu.serve import (
        ServeMetrics,
        ServeSupervisor,
        engine_factory,
    )
    from simple_distributed_machine_learning_tpu.serve.tracing import (
        ServeTrace,
    )
    from simple_distributed_machine_learning_tpu.telemetry.attribution import (
        attribute,
    )
    from simple_distributed_machine_learning_tpu.telemetry.slo import (
        SLOEngine,
        SLOObjective,
    )

    def run(with_slo: bool, n: int):
        metrics = ServeMetrics()
        slo = (SLOEngine([SLOObjective("bench", ttft_slo_ms=50.0,
                                       tpot_slo_ms=20.0)],
                         registry=metrics.registry) if with_slo else None)
        trace = ServeTrace() if with_slo else None
        tmpdir = tempfile.TemporaryDirectory(prefix="sdml-bench-slo-")
        try:
            sup = ServeSupervisor(
                engine_factory(stages, cfg, n_slots=slots,
                               block_size=block_size,
                               prefill_chunk=block_size, metrics=metrics),
                os.path.join(tmpdir.name, "journal.jsonl"),
                metrics=metrics, trace=trace, slo=slo)
            rng = np.random.default_rng(0)
            t0 = _time.perf_counter()
            for i in range(n):
                sup.submit(
                    rng.integers(0, cfg.vocab,
                                 prompt_lens[i % len(prompt_lens)]).astype(
                                     np.int32),
                    max_new_tokens=max_new, cls="bench")
            sup.drain()
            att = (attribute(trace.rows, registry=metrics.registry)
                   if with_slo else None)
            wall = _time.perf_counter() - t0
            ticks = sup.tick
            sup.close()
        finally:
            tmpdir.cleanup()
        return ticks, wall, att, slo

    run(False, min(n_requests, len(prompt_lens)))   # warmup: compile shapes
    off_ticks, off_wall, _, _ = run(False, n_requests)
    on_ticks, on_wall, att, slo = run(True, n_requests)
    return [{
        "config": "gpt_serve_slo_overhead",
        "n_slots": slots, "n_requests": n_requests,
        "max_new_tokens": max_new,
        "ticks": on_ticks, "ticks_off": off_ticks,
        "ticks_per_sec": round(on_ticks / on_wall, 1) if on_wall else None,
        "ticks_per_sec_off": (round(off_ticks / off_wall, 1)
                              if off_wall else None),
        "wall_s": round(on_wall, 3), "wall_s_off": round(off_wall, 3),
        "overhead_frac": (round(on_wall / off_wall - 1.0, 4)
                          if off_wall else None),
        "slo_evaluations": slo.evaluations,
        "alert_transitions": len(slo.alerts.journal),
        "attributed_requests": att["requests"],
        "attribution_max_drift_ms": att["max_abs_drift_ms"],
        "device_kind": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
    }]


def _measure_sentinel(n_steps: int = 48, fault_step: int = 30,
                      snapshot_every: int = 4) -> list:
    """Self-healing training cost and recovery (``resilience/sentinel.py``).

    Two rows from the same small MLP workload:

    - ``train_sentinel_overhead``: steady steps/sec with the sentinel OFF
      vs ON (no faults) — the price of the per-step host sync + the
      every-K-steps snapshot gather.
    - ``train_sentinel_recovery``: an injected ``nan-grad`` at a fixed
      step; the row pins recovered == True (run completes, >= 1 rollback,
      the fault actually fired — the anti-vacuous gate) and reports the
      replayed-step budget (at most ``snapshot_every - 1`` by
      construction), quarantined batches and the ring's resident bytes.
    """
    import time as _time

    import jax
    import numpy as np

    from simple_distributed_machine_learning_tpu.data.mnist import Dataset
    from simple_distributed_machine_learning_tpu.models.mlp import (
        make_mlp_stages,
    )
    from simple_distributed_machine_learning_tpu.parallel.mesh import (
        make_mesh,
    )
    from simple_distributed_machine_learning_tpu.parallel.pipeline import (
        Pipeline,
    )
    from simple_distributed_machine_learning_tpu.resilience import faults
    from simple_distributed_machine_learning_tpu.train.trainer import (
        TrainConfig,
        Trainer,
    )

    rng = np.random.default_rng(0)
    batch, n_batches = 64, 12
    ds = Dataset(rng.standard_normal((batch * n_batches, 64),
                                     dtype=np.float32),
                 rng.integers(0, 10, batch * n_batches).astype(np.int32))
    epochs = max(1, n_steps // n_batches)

    def run(sentinel: bool, plan: str | None = None):
        stages, wd, od = make_mlp_stages(jax.random.key(0),
                                         [64, 128, 64, 10], 1)
        pipe = Pipeline(stages, make_mesh(n_stages=1, n_data=1,
                                          devices=jax.devices()[:1]),
                        wd, od)
        cfg = TrainConfig(epochs=epochs, batch_size=batch,
                          print_throughput=False, sentinel=sentinel,
                          sentinel_snapshot_every=snapshot_every)
        tr = Trainer(pipe, ds, ds, cfg)
        tr._print = lambda msg: None     # keep bench stdout row-clean
        installed = (faults.install(faults.FaultPlan.parse(plan))
                     if plan else None)
        t0 = _time.perf_counter()
        try:
            tr.fit()
        finally:
            # only uninstall what THIS run installed: a bare baseline run
            # must not clobber the SDML_CHAOS env plan main() installed
            # for the wedged-probe drill
            if installed is not None:
                faults.uninstall()
        wall = _time.perf_counter() - t0
        fired = installed.stats()["total_fired"] if installed else 0
        return tr, wall, fired

    _, wall_off, _ = run(sentinel=False)
    tr_on, wall_on, _ = run(sentinel=True)
    steps = epochs * n_batches
    tr_rec, _, fired = run(sentinel=True,
                           plan=f"nan-grad@train.grad={fault_step}")
    stats = tr_rec.sentinel_stats()
    return [{
        "config": "train_sentinel_overhead",
        "steps": steps,
        "steps_per_sec_off": round(steps / wall_off, 2),
        "steps_per_sec_on": round(steps / wall_on, 2),
        "overhead_frac": round(max(0.0, 1.0 - wall_off / wall_on), 4),
        "snapshot_every": snapshot_every,
        "ring_bytes": tr_on.sentinel_stats()["snapshot_ring_bytes"],
        "device_kind": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
    }, {
        "config": "train_sentinel_recovery",
        "fault": f"nan-grad@train.grad={fault_step}",
        "faults_fired": fired,
        "anomalies": stats["anomalies"],
        "rollbacks": stats["rollbacks"],
        "quarantined_batches": stats["quarantined_batches"],
        # replay budget: rollback lands on the newest pre-anomaly snapshot
        "max_replayed_steps": snapshot_every - 1,
        "recovered": bool(fired >= 1 and stats["rollbacks"] >= 1
                          and not tr_rec.preempted),
        "device_kind": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
    }]


def _measure_jax_cpu_baseline() -> float:
    """Our own pipeline on 2 virtual CPU devices (BASELINE config 1 analog).
    A child process held to the CPU by its environment: only safe while this
    parent has initialised no backend (``--measure-baseline`` runs it first)."""
    code = (
        "import sys; sys.path.insert(0, %r);"
        "from bench import _throughput_row, _configs;"
        "import json; spec = dict(_configs()['mlp2'], steps_override=2000);"
        "print('RESULT'+json.dumps(_throughput_row('mlp2', spec, "
        "windows=2)[0]))" % REPO)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=2"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, cwd=REPO, env=env)
    for line in out.stdout.splitlines():
        if line.startswith("RESULT"):
            return json.loads(line[len("RESULT"):])["samples_per_sec"]
    raise RuntimeError(f"jax cpu baseline failed: {out.stderr[-2000:]}")


def _measure_torch_rpc_baseline() -> float:
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "torch_rpc_baseline.py")],
        capture_output=True, text=True, timeout=900, cwd=REPO)
    for line in out.stdout.splitlines():
        if line.startswith("RESULT"):
            return json.loads(line[len("RESULT"):])["samples_per_sec"]
    raise RuntimeError(f"torch rpc baseline failed: {out.stderr[-2000:]}")


def main() -> None:
    from simple_distributed_machine_learning_tpu.utils.compile_cache import (
        enable_compile_cache,
    )
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--measure-baseline", action="store_true",
                    help="re-measure CPU baselines and rewrite "
                         "benchmarks/baseline_cpu.json")
    ap.add_argument("--all", action="store_true",
                    help="measure every config, one JSON line each, and "
                         "write benchmarks/results_all.json")
    ap.add_argument("--config", default=None,
                    choices=list(_configs()) + ["gpt_bf16_xl"],
                    help="single config to measure (default: headline mlp2; "
                         "with --decode and no --config, decode only)")
    ap.add_argument("--steps", type=int, default=None,
                    help="override the per-config scan-window length (use "
                         "when dispatch noise exceeds the window)")
    ap.add_argument("--schedule", choices=("gpipe", "1f1b"),
                    default="gpipe",
                    help="pipeline schedule to bench (1f1b engages only "
                         "with >= 2 pipeline stages, i.e. >= 2 chips)")
    ap.add_argument("--decode", action="store_true",
                    help="measure KV-cache vs recompute decode tokens/sec "
                         "(also runs as part of --all)")
    ap.add_argument("--serve", action="store_true",
                    help="offered-load serving sweep (serve/): continuous-"
                         "batching tokens/sec + TTFT/TPOT p50/p95 per "
                         "Poisson arrival rate, vs the 1-slot sequential "
                         "baseline; writes benchmarks/serving.json")
    ap.add_argument("--serve-kernel", choices=("dense", "fused"),
                    default="dense",
                    help="with --serve: the sweep engines' paged-attention "
                         "path — dense gather-then-dense (parity anchor) "
                         "or the fused Pallas flash-decode kernel; the "
                         "kernel comparison rows always measure both")
    ap.add_argument("--opt", choices=("sgd", "adamw"), default=None,
                    help="override the per-config optimizer (experiment "
                         "rows only; results_all.json is not rewritten "
                         "under an override)")
    ap.add_argument("--attn", choices=("dense", "flash"), default=None,
                    help="override the GPT rows' attention implementation "
                         "(whole-model flash-vs-dense comparison; "
                         "experiment rows only, like --opt)")
    ap.add_argument("--flash-blocks", type=str, default=None, metavar="Q,K",
                    help="with --attn flash: kernel block sizes")
    ap.add_argument("--lr", type=float, default=None,
                    help="override the optimizer learning rate (with "
                         "--opt sgd keeps momentum=0.5; experiment rows "
                         "only, like --opt)")
    ap.add_argument("--tp", type=int, default=None,
                    help="shard the GPT rows' blocks tensor-parallel over "
                         "this many devices (Megatron QKV/O + MLP; one "
                         "pipeline stage, the whole mesh to the model "
                         "axis; experiment rows only, like --opt)")
    ap.add_argument("--overlap", choices=("none", "ring"), default=None,
                    help="collective schedule for the GPT rows' tensor-"
                         "parallel all-reduces: none = monolithic psum, "
                         "ring = latency-hiding ppermute-chunked collective "
                         "matmuls (parallel/overlap.py); pair with --tp; "
                         "experiment rows only, like --opt")
    ap.add_argument("--sentinel", action="store_true",
                    help="self-healing training rows (resilience/"
                         "sentinel.py): sentinel on/off steps-per-sec "
                         "overhead plus a nan-grad recovery drill "
                         "(rollback + quarantine, anti-vacuous "
                         "faults_fired gate)")
    ap.add_argument("--lint", action="store_true",
                    help="static-analysis preflight (analysis/): lint the "
                         "exact scanned step of every row before timing it "
                         "(with --serve, the whole serving-program registry "
                         "on both KV layouts) and abort on ERROR findings")
    args = ap.parse_args()
    # mirror cli.py's validation instead of silently ignoring the flag or
    # dumping a raw ValueError traceback from the int parse
    if args.flash_blocks and args.attn != "flash":
        raise SystemExit("--flash-blocks needs --attn flash")
    if args.flash_blocks:
        raw = args.flash_blocks
        try:
            bq, bk = (int(v) for v in raw.split(","))
        except ValueError:
            raise SystemExit(
                f"--flash-blocks expects Q,K integers, got {raw!r}"
            ) from None
        args.flash_blocks = (bq, bk)
    if args.overlap == "ring" and args.tp is None:
        args.tp = 2          # smallest sharded row: the ring schedule
        #                      measures a collective, which needs a shard
    if args.tp is not None or args.overlap is not None:
        # flag-level spec validation through the analyzer preflight (device
        # count and model-shape divisibility re-checked per row in measure())
        from simple_distributed_machine_learning_tpu.analysis.preflight import (
            validate_tp_overlap,
        )
        errors, _ = validate_tp_overlap(args.tp if args.tp is not None else 1,
                                        args.overlap or "none")
        if errors:
            raise SystemExit("bench: invalid --tp/--overlap spec:\n  "
                             + "\n  ".join(errors))
    if (args.tp or args.overlap) and args.config is None and not args.all:
        args.config = "gpt"  # the TP/overlap axes are GPT-row knobs

    if args.measure_baseline:
        # children held to the CPU; this parent has touched no backend yet
        baselines = {
            "torch_rpc_cpu_samples_per_sec": _measure_torch_rpc_baseline(),
            "jax_cpu_pipeline_samples_per_sec": _measure_jax_cpu_baseline(),
        }
        os.makedirs(os.path.dirname(BASELINE_PATH), exist_ok=True)
        with open(BASELINE_PATH, "w") as f:
            json.dump(baselines, f, indent=2)
    elif not os.path.exists(BASELINE_PATH):
        raise SystemExit(
            f"bench: {BASELINE_PATH} is missing. It is committed: restore "
            f"it (git checkout), or re-measure it explicitly with "
            f"--measure-baseline — it is never re-measured silently")
    else:
        with open(BASELINE_PATH) as f:
            baselines = json.load(f)
    base = baselines.get("torch_rpc_cpu_samples_per_sec") or \
        baselines.get("jax_cpu_pipeline_samples_per_sec")

    configs = _configs()
    if args.config == "gpt_bf16_xl" and not args.all:
        # explicit opt-in only: never joins the --all sweep (slow compile,
        # heavy HBM; _xl_config's contract)
        configs["gpt_bf16_xl"] = _xl_config()
    # --decode is additive: an explicit --config still runs; only a bare
    # --decode (no --all, no --config) measures decode alone
    if args.all:
        names = list(configs)
    elif args.config is not None:
        names = [args.config]
    else:
        names = [] if (args.decode or args.serve or args.sentinel) \
            else ["mlp2"]
    # device preflight (SDML_CHAOS can inject wedged-device faults): retry
    # once with backoff; a persistently unresponsive device prints the
    # structured device_unhealthy row and exits WEDGED_RC — nothing is
    # measured and no artifact is touched
    from simple_distributed_machine_learning_tpu.resilience.faults import (
        install_from_env,
    )
    install_from_env()
    _supervised_smoke()

    def _run_decode() -> None:
        drow = measure_decode()
        print(json.dumps({
            "metric": "gpt_decode_tokens_per_sec",
            "value": drow["tokens_per_sec_cached"],
            "unit": "tokens/sec",
            "vs_recompute": drow["speedup"],
        }))

    if args.decode and not args.all:
        _run_decode()
    if args.sentinel:
        for srow in _measure_sentinel():
            print(json.dumps({"metric": srow.pop("config"), **srow}))
        if not names and not args.serve:
            return
    if args.serve:
        for srow in measure_serving(lint=args.lint,
                                    attn_kernel=args.serve_kernel):
            line = {"metric": srow["config"], "n_slots": srow["n_slots"]}
            # sweep rows report throughput+latency; the paged-vs-dense
            # comparison rows report concurrency / tick-latency instead
            for k in ("tokens_per_sec", "rate", "ttft_ms_p50",
                      "ttft_ms_p95", "tpot_ms_p50", "tpot_ms_p95",
                      "slot_occupancy_mean", "kv_bytes", "max_concurrent",
                      "long_prompt_len", "tick_ms_p50", "tick_ms_p95",
                      "tick_ms_max", "tp", "spec_k", "accept_rate",
                      "tokens_per_tick_spec", "tokens_per_tick_plain",
                      "speedup_vs_plain", "wall_tokens_per_sec_spec",
                      "wall_tokens_per_sec_plain", "kernel",
                      "ticks_per_sec", "decode_kv_bytes_per_tick",
                      "hbm_reduction", "streams_bit_exact", "cache_dtype",
                      "kv_budget_bytes", "n_blocks", "resident_ratio"):
                if srow.get(k) is not None:
                    line[k] = srow[k]
            print(json.dumps(line))
        if not names:
            return
    rows = []

    def _write_results(partial: bool) -> None:
        # the authoritative GPipe artifact — a 1f1b sweep writes its own
        # file instead of silently overwriting it with rows that used to be
        # indistinguishable. Both the filename and the top-level field
        # reflect what actually RAN, not what was requested: on one chip a
        # --schedule 1f1b sweep degenerates to gpipe rows (measure()'s
        # n_stages < 2 fallback) and is recorded as such. Written after
        # EVERY row (partial=True) so a late-row failure on flaky hardware
        # cannot cost the rows already measured.
        if not rows:
            return
        ran = {r["schedule"] for r in rows}
        sched_actual = ran.pop() if len(ran) == 1 else "mixed"
        if not partial and sched_actual != args.schedule:
            sys.stderr.write(
                f"bench: requested --schedule {args.schedule} but rows ran "
                f"{sched_actual} (single-chip fallback?); recording "
                f"{sched_actual}\n")
        path = (RESULTS_PATH if sched_actual == "gpipe" else
                RESULTS_PATH.replace(".json", f"_{sched_actual}.json"))
        # never let a CPU-backend sweep silently clobber the authoritative
        # TPU artifact (easy to do from a dev shell with JAX_PLATFORMS=cpu)
        if rows[0]["backend"] != "tpu" and os.path.exists(path):
            try:
                with open(path) as f:
                    prev = json.load(f)
            except Exception:
                prev = {}
            if prev.get("backend") == "tpu":
                path = path.replace(".json", f"_{rows[0]['backend']}.json")
                if partial is False:
                    sys.stderr.write(
                        f"bench: existing artifact is from TPU; this "
                        f"{rows[0]['backend']} sweep written to {path}\n")
        payload = {"device": rows[0]["device_kind"],
                   "backend": rows[0]["backend"],
                   "schedule": sched_actual,
                   "rows": rows}
        if partial:
            payload["partial"] = True
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)

    write_artifact = (args.all and args.opt is None and args.lr is None
                      and args.attn is None and args.tp is None
                      and args.overlap is None)
    for name in names:
        spec = (dict(configs[name], steps_override=args.steps)
                if args.steps else configs[name])
        if (args.opt is not None or args.lr is not None
                or args.attn is not None or args.tp is not None
                or args.overlap is not None):
            spec = dict(spec)
            if args.opt is not None:
                spec["opt"] = args.opt
            if args.lr is not None:
                spec["lr"] = args.lr
            if args.attn is not None and spec["kind"] == "gpt":
                spec["attn"] = args.attn
                if args.flash_blocks:
                    spec["flash_blocks"] = args.flash_blocks
            if spec["kind"] == "gpt":
                if args.tp is not None:
                    spec["tp"] = args.tp
                if args.overlap is not None:
                    spec["overlap"] = args.overlap
        res = measure(name, spec, schedule=args.schedule, lint=args.lint)
        # vs_baseline only for the headline: the torch-RPC baseline runs the
        # 2-stage MLP workload, not the others
        vs = (round(res["samples_per_sec"] / base, 2)
              if base and name in ("mlp2", "mlp2_bf16") else None)
        rows.append(dict(res, vs_baseline=vs))
        print(json.dumps({
            "metric": f"{name}_samples_per_sec_per_chip"
                      if name != "mlp2" else
                      "2stage_mlp_pipeline_samples_per_sec_per_chip",
            "value": res["samples_per_sec_per_chip"],
            "unit": "samples/sec/chip",
            "vs_baseline": vs,
            "mfu": res["mfu"],
            "achieved_tflops": res["achieved_tflops"],
            "dtype": res["dtype"],
            "n_chips": res["n_chips"],
            "schedule": res["schedule"],
            "optimizer": res["optimizer"],
            "tp": res["tp"],
            "overlap": res["overlap"],
            # latency quantiles + bubble (telemetry/): p50/p95 say more than
            # a mean on a shared host; bubble ranks schedule headroom
            "step_ms_p50": res["step_ms_p50"],
            "step_ms_p95": res["step_ms_p95"],
            "bubble_fraction": res["bubble_fraction"],
            "ici_bytes_per_step": res["ici_bytes_per_step"],
        }))
        if write_artifact:
            _write_results(partial=True)
    if args.all:
        _run_decode()
    if args.all and not write_artifact:
        sys.stderr.write(
            "bench: --opt/--lr override active - results_all.json NOT "
            "rewritten (experiment rows only)\n")
    elif write_artifact:
        _write_results(partial=False)


if __name__ == "__main__":
    main()
