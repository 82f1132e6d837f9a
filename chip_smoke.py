#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` needs exactly one TPU chip and drives the main path
once through the entry points a user calls, at the widest model the repository
names (``bench.py::_xl_config``: vocab 8192, T 512, d_model 1024, 16 heads,
4 layers, bf16 compute, AdamW, batch 8), weights random from ``--seed``:

1. **device** — one ``tpu`` device, or failure (never a CPU run);
2. **train**  — optimizer steps through ``make_gpt_stages`` -> ``Pipeline`` ->
   ``make_train_step`` with dense and with flash attention: finite, falling
   losses on a fixed seeded batch pool;
3. **serve**  — ``InferenceEngine`` over the trained parameters, paged pool,
   chunked prefill: dense, fused (Pallas) and int8 runs to completion,
   ``kv_drift`` 0, fused attention output against dense on the same pool, and
   a ``tpu_custom_call`` in the fused tick's lowered program;
4. **cli**    — ``cli.main`` in-process at its built-in size (train + serve).

``python chip_smoke.py --chips 4`` needs four chips and runs only the
cross-chip path and its comparison: the train steps on a 2-stage x 2-data
mesh (GPipe, 4 microbatches) against the same steps on one device, parameter
bytes on all four devices, and where a 2-replica serve fleet puts its pools.

One process, no children (a child could not have the chip). Any failed phase
exits non-zero with the phase's name and prints no result line. The last line
of stdout on success is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Timings printed on the way are smoke timings, not benchmark results.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import statistics
import sys
import tempfile
import time

import numpy as np

import jax
import jax.numpy as jnp

from simple_distributed_machine_learning_tpu import cli
from simple_distributed_machine_learning_tpu.analysis import ArgSpec
from simple_distributed_machine_learning_tpu.analysis.programs import (
    build_registry,
    engine_spec,
)
from simple_distributed_machine_learning_tpu.models.gpt import (
    GPTConfig,
    make_gpt_stages,
)
from simple_distributed_machine_learning_tpu.models.serving import QuantKV
from simple_distributed_machine_learning_tpu.ops.paged_attention import (
    paged_attention,
)
from simple_distributed_machine_learning_tpu.parallel.mesh import make_mesh
from simple_distributed_machine_learning_tpu.parallel.pipeline import Pipeline
from simple_distributed_machine_learning_tpu.serve import (
    InferenceEngine,
    ServeFleet,
    engine_factory,
)
from simple_distributed_machine_learning_tpu.train.optimizer import adamw
from simple_distributed_machine_learning_tpu.train.step import make_train_step
from simple_distributed_machine_learning_tpu.utils.compile_cache import (
    enable_compile_cache,
)
from simple_distributed_machine_learning_tpu.utils.tolerances import attn_tol

#: bench.py::_xl_config — the widest model the repository names
XL = GPTConfig(vocab=8192, seq_len=512, d_model=1024, n_heads=16, n_layers=4)
BATCH = 8
TRAIN_STEPS = 6
POOL = 2            # seeded batches cycled by the train phase
SERVE_SLOTS = 8
SERVE_BLOCK = 16    # a deployment-sized paged block (vLLM's default)
SERVE_CHUNK = 128   # chunked prefill
SERVE_REQUESTS = 12
SERVE_PROMPTS = (64, 384)
SERVE_NEW = 32


class SmokeFailure(RuntimeError):
    pass


@contextlib.contextmanager
def phase(name: str):
    print(f"== phase {name}", flush=True)
    t0 = time.perf_counter()
    try:
        yield
    except BaseException:
        print(f"chip_smoke: phase {name} FAILED", file=sys.stderr, flush=True)
        raise
    print(f"== phase {name} ok ({time.perf_counter() - t0:.1f} s)", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def loss_atol() -> float:
    """The pin for two bf16 runs of the same steps (dense vs flash, four
    devices vs one): the bf16 pin's absolute term alone. Its relative term
    on losses near 7-9 would admit +-0.45, and a gradient-scale error with
    it."""
    return attn_tol(jnp.bfloat16)[1]


# -- phase 1: device --------------------------------------------------------


def require_tpu(count: int) -> dict:
    """Exactly ``count`` devices of platform ``tpu``; anything else fails."""
    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == "tpu",
          f"need a TPU, JAX found platform {d0.platform!r} "
          f"({d0.device_kind}); this script never runs on another backend")
    check(len(devs) == count, f"need exactly {count} TPU device(s), "
                              f"JAX found {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def phase_device(count: int, cache_dir: str) -> dict:
    device = require_tpu(count)
    import jaxlib
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 - version lookup only, never the check
        libtpu = "unknown"
    print(f"device: {device['platform']} / {device['kind']} x "
          f"{device['count']}; jax {jax.__version__}, jaxlib "
          f"{jaxlib.__version__}, libtpu {libtpu}")
    print(f"compile cache: {cache_dir} ({cache_entries(cache_dir)} entries "
          f"at start)")
    return device


# -- phase 2: train ---------------------------------------------------------


def token_pool(cfg: GPTConfig, batch: int, pool: int, seed: int):
    """``pool`` seeded batches of Zipf-distributed token ids (next-token
    targets), so a handful of steps has a frequency skew to learn."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, cfg.vocab + 1)
    toks = rng.choice(cfg.vocab, size=(pool, batch, cfg.seq_len + 1),
                      p=p / p.sum())
    return (jnp.asarray(toks[..., :-1], jnp.float32),
            jnp.asarray(toks[..., 1:], jnp.int32))


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def train_steps(cfg: GPTConfig, mesh, n_stages: int, n_micro: int, xs, ts,
                steps: int, seed: int, label: str):
    """``steps`` AdamW steps in bf16 through the public build path; returns
    ``(losses, pipe, buf, compile seconds)``. Compile time is taken apart
    from step time."""
    stages, wire_dim, out_shape = make_gpt_stages(jax.random.key(seed), cfg,
                                                  n_stages)
    pipe = Pipeline(stages, mesh, wire_dim, out_shape,
                    n_microbatches=n_micro, compute_dtype=jnp.bfloat16)
    buf = pipe.init_params()
    opt = adamw(1e-3)
    state = opt.init(buf)
    step = make_train_step(pipe, opt)
    key = jax.random.key(seed + 1)
    t0 = time.perf_counter()
    lowered = step.lower(buf, state, xs[0], ts[0], key)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    losses, times = [], []
    for i in range(steps):
        t = time.perf_counter()
        buf, state, loss = compiled(buf, state, xs[i % len(xs)],
                                    ts[i % len(ts)],
                                    jax.random.fold_in(key, i))
        jax.block_until_ready((buf, loss))
        times.append(time.perf_counter() - t)
        losses.append(float(loss))
    check(all(np.isfinite(losses)), f"{label}: non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"{label}: loss did not fall: {losses}")
    peak = peak_bytes()
    print(f"train[{label}]: losses {[round(l, 4) for l in losses]}")
    print(f"train[{label}]: trace+lower {t1 - t0:.2f} s, compile "
          f"{t2 - t1:.2f} s, step median {statistics.median(times) * 1e3:.2f}"
          f" ms (host clock, block_until_ready), peak_bytes_in_use "
          f"{'not reported' if peak is None else peak}")
    return losses, pipe, buf, round(t2 - t1, 2)


def phase_train(cfg: GPTConfig, batch: int, steps: int, seed: int):
    """Dense then flash attention on a one-device mesh; returns the stage
    list, the dense run's trained per-stage parameters for phase 3, and the
    two compile times."""
    mesh = make_mesh(n_stages=1, n_data=1, devices=jax.devices()[:1])
    xs, ts = token_pool(cfg, batch, POOL, seed)
    dense, pipe, buf, c_dense = train_steps(cfg, mesh, 1, 1, xs, ts, steps,
                                            seed, "attn=dense")
    flash, _, _, c_flash = train_steps(
        dataclasses.replace(cfg, attn_impl="flash"), mesh, 1, 1, xs, ts,
        steps, seed, "attn=flash")
    # same seed, same batches: flash must track dense
    np.testing.assert_allclose(flash, dense, rtol=0, atol=loss_atol(),
                               err_msg="flash losses left dense losses")
    print(f"train: max |loss diff| flash vs dense: "
          f"{max(abs(a - b) for a, b in zip(flash, dense)):.3e} (pin atol "
          f"{loss_atol()}, rtol 0)")
    return (pipe.stages, pipe.unpack(buf),
            {"attn=dense": c_dense, "attn=flash": c_flash})


# -- phase 3: serve ---------------------------------------------------------


def assert_kernel_compiled(lowered_text: str, what: str) -> None:
    """The proof the Pallas kernel ran compiled through Mosaic, not
    interpreted or replaced: its custom call is in the lowered program."""
    check("tpu_custom_call" in lowered_text,
          f"{what}: no tpu_custom_call in the lowered program — the fused "
          f"kernel was interpreted or replaced")


def serve_prompts(cfg: GPTConfig, n: int, seed: int) -> list[np.ndarray]:
    """Seeded Zipf prompts whose lengths are multiples of half a prefill
    chunk: the engine compiles one prefill program per distinct final-chunk
    length, so free lengths would spend the run compiling."""
    rng = np.random.default_rng(seed + 7)
    q = max(SERVE_CHUNK // 2, 1)
    lo, hi = SERVE_PROMPTS
    hi = min(hi, cfg.seq_len - SERVE_NEW)
    lengths = [l for l in range(q, hi + 1, q) if l >= min(lo, hi)] or [hi]
    p = 1.0 / np.arange(1, cfg.vocab + 1)
    return [rng.choice(cfg.vocab, size=int(rng.choice(lengths)),
                       p=p / p.sum()).astype(np.int32) for _ in range(n)]


def serve_run(stages, cfg, params, prompts, *, attn_kernel: str,
              cache_dtype, label: str):
    """Serve every prompt to completion (greedy); returns ``(engine, token
    streams)``."""
    eng = InferenceEngine(stages, cfg, params=params, n_slots=SERVE_SLOTS,
                          block_size=SERVE_BLOCK, prefill_chunk=SERVE_CHUNK,
                          attn_kernel=attn_kernel, cache_dtype=cache_dtype)
    handles = [eng.submit(p, SERVE_NEW) for p in prompts]
    t0 = time.perf_counter()
    ticks, first_tick = 0, None
    while eng.busy:
        t = time.perf_counter()
        eng.step()
        dt = time.perf_counter() - t
        first_tick = dt if first_tick is None else first_tick
        ticks += 1
        live, predicted = eng.kv_drift()
        check(live - predicted == 0,
              f"serve[{label}]: kv drift {live - predicted} bytes at tick "
              f"{ticks}")
        check(ticks < 20_000, f"serve[{label}]: no completion in 20000 ticks")
    wall = time.perf_counter() - t0
    done = [h for h in handles if len(h.tokens) == SERVE_NEW]
    check(len(done) == len(prompts),
          f"serve[{label}]: {len(done)}/{len(prompts)} requests completed")
    pool_bytes = sum(a.nbytes for a in jax.tree.leaves((eng.pool.kc,
                                                        eng.pool.vc)))
    print(f"serve[{label}]: {len(done)}/{len(prompts)} requests completed, "
          f"{ticks} ticks, {wall:.2f} s wall incl. compiles (first tick "
          f"{first_tick:.2f} s), kv drift 0 bytes, pool "
          f"{pool_bytes} bytes")
    return eng, [list(h.tokens) for h in handles]


def lowered_decode_tick(eng) -> str:
    """The engine's decode tick, lowered at one tick's argument shapes: the
    ``paged_decode`` entry of the registry ``InferenceEngine(lint=True)``
    lints (the builders are memoized, so it is the engine's own program)."""
    programs, _ = build_registry(eng.stages, engine_spec(eng), mesh=eng.mesh)
    tick, = (p for p in programs if p.name == "paged_decode")
    args = jax.tree.map(lambda a: a.sds if isinstance(a, ArgSpec) else a,
                        tick.args, is_leaf=lambda a: isinstance(a, ArgSpec))
    return tick.fn.lower(*args).as_text()


def gathered_rows(cache, tables, n_heads: int) -> jax.Array:
    """Layer 0's rows under every slot's table, ``[S, H, NB * bs, dh]`` f32:
    a plain gather (dequantized for a :class:`QuantKV` pool), written here so
    the reference shares no code with the paths it checks."""
    layer = cache[0]                 # [n_blocks+1, bs, H*dh]: heads in a row
    quant = isinstance(layer, QuantKV)
    rows = (layer.data if quant else layer)[tables].astype(jnp.float32)
    S, NB, bs, _ = rows.shape
    rows = rows.reshape(S, NB * bs, n_heads, -1)
    if quant:
        rows = rows * layer.scale[tables].reshape(S, NB * bs, n_heads, 1)
    return jnp.moveaxis(rows, 2, 1)


def attention_parity(eng, cache_dtype, seed: int, label: str) -> None:
    """Fused against dense attention on the engine's own pool (layer 0, the
    K/V the run just wrote): every slot reads a random table of written
    blocks at its own position.

    On a TPU each side rounds an f32 matmul as its compiler defaults (XLA:
    one bf16 pass), so the pair is compared twice: with both sides at
    ``highest`` matmul precision, held to the pool dtype's pin — that is the
    kernel's correctness — and as the ticks run them (default precision),
    held to the bf16 pin and printed."""
    pool = eng.pool
    S, NB, bs = pool.n_slots, pool.blocks_per_seq, SERVE_BLOCK
    H, dh = eng.cfg.n_heads, eng.cfg.d_model // eng.cfg.n_heads
    rng = np.random.default_rng(seed + 11)
    n_phys = pool.n_blocks + 1
    # physical blocks 1.. (0 is the trash block) hold what the run wrote
    tables = jnp.asarray(rng.integers(1, n_phys, size=(S, NB)), jnp.int32)
    pos = jnp.asarray(rng.integers(bs, NB * bs, size=(S,)), jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, H, 1, dh)), jnp.float32)

    def both(kc, vc):
        if isinstance(kc[0], QuantKV):
            fused = paged_attention(q, kc[0].data, vc[0].data, tables,
                                    pos[:, None], block_size=bs,
                                    kscale=kc[0].scale, vscale=vc[0].scale)
        else:
            fused = paged_attention(q, kc[0], vc[0], tables, pos[:, None],
                                    block_size=bs)
        krow = gathered_rows(kc, tables, H)
        vrow = gathered_rows(vc, tables, H)
        live = (jnp.arange(NB * bs)[None, None, None, :]
                <= pos[:, None, None, None])
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, krow) / np.sqrt(dh)
        scores = jnp.where(live, scores, -jnp.inf)
        dense = jnp.einsum("bhqk,bhkd->bhqd",
                           jax.nn.softmax(scores, axis=-1), vrow)
        return fused, dense

    pins = {"highest": attn_tol(jnp.float32 if cache_dtype is None
                                else cache_dtype),
            "default": attn_tol(jnp.bfloat16)}
    for prec, (rtol, atol) in pins.items():
        with jax.default_matmul_precision(
                "highest" if prec == "highest" else None):
            fused, dense = jax.jit(both)(pool.kc, pool.vc)
        fused, dense = np.asarray(fused), np.asarray(dense, np.float32)
        check(np.isfinite(fused).all(), f"{label}: fused attention not finite")
        check(np.abs(dense).max() > 0, f"{label}: the pool read back zeros")
        print(f"serve[{label}]: fused vs dense attention on the same pool, "
              f"{prec} matmul precision: max |diff| "
              f"{float(np.abs(fused - dense).max()):.3e} (pin rtol {rtol} "
              f"atol {atol}, {fused.shape} f32)", flush=True)
        np.testing.assert_allclose(
            fused, dense, rtol=rtol, atol=atol,
            err_msg=f"{label}: fused attention left dense ({prec})")


def agree_share(a: list[list[int]], b: list[list[int]]) -> float:
    same = sum(x == y for s, t in zip(a, b) for x, y in zip(s, t))
    return same / sum(len(s) for s in a)


def phase_serve(stages, cfg: GPTConfig, params, seed: int) -> None:
    prompts = serve_prompts(cfg, SERVE_REQUESTS, seed)
    print(f"serve: {len(prompts)} requests, prompts "
          f"{min(map(len, prompts))}-{max(map(len, prompts))} tokens, "
          f"{SERVE_NEW} new each, {SERVE_SLOTS} slots, block {SERVE_BLOCK}, "
          f"prefill chunk {SERVE_CHUNK}")
    _, dense_toks = serve_run(stages, cfg, params, prompts,
                              attn_kernel="dense", cache_dtype=None,
                              label="dense f32")
    eng, fused_toks = serve_run(stages, cfg, params, prompts,
                                attn_kernel="fused", cache_dtype=None,
                                label="fused f32")
    assert_kernel_compiled(lowered_decode_tick(eng), "fused decode tick")
    attention_parity(eng, None, seed, "fused f32")
    print(f"serve: greedy tokens on which dense and fused streams agree "
          f"(f32 pool): {100 * agree_share(dense_toks, fused_toks):.2f}%")
    eng8, int8_toks = serve_run(stages, cfg, params, prompts,
                                attn_kernel="fused", cache_dtype="int8",
                                label="fused int8")
    assert_kernel_compiled(lowered_decode_tick(eng8),
                           "fused int8 decode tick")
    attention_parity(eng8, "int8", seed, "fused int8")
    print(f"serve: greedy tokens on which f32 and int8 fused streams agree: "
          f"{100 * agree_share(fused_toks, int8_toks):.2f}%")


# -- phase 4: cli -----------------------------------------------------------


def run_cli(argv: list[str]) -> None:
    print(f"cli: {' '.join(argv)}", flush=True)
    try:
        cli.main(argv)
    except SystemExit as e:
        check(e.code in (None, 0), f"cli exited with {e.code!r}: {argv}")


CLI_TRAIN = ["--rank", "0", "--model", "gpt", "--dryrun", "3"]
CLI_SERVE = ["--rank", "0", "--model", "gpt", "--serve-sim", "6",
             "--serve-slots", "3", "--serve-max-new", "5",
             "--serve-block-size", "4"]


def phase_cli() -> None:
    run_cli(CLI_TRAIN)
    run_cli(CLI_SERVE)


# -- phase 5 (--chips 4): the cross-chip path -------------------------------


def device_bytes(arr) -> dict:
    out: dict = {}
    for leaf in jax.tree.leaves(arr):
        for sh in leaf.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return out


def phase_pipeline_4(cfg: GPTConfig, batch: int, steps: int,
                     seed: int) -> dict:
    """2 stages x 2 data over four devices (GPipe, 4 microbatches) against
    the same steps on ``devices[:1]``."""
    devs = jax.devices()
    xs, ts = token_pool(cfg, batch, POOL, seed)
    one, _, _, c_one = train_steps(
        cfg, make_mesh(n_stages=1, n_data=1, devices=devs[:1]),
        1, 1, xs, ts, steps, seed, "1 device")
    four, _, buf, c_four = train_steps(
        cfg, make_mesh(n_stages=2, n_data=2, devices=devs[:4]),
        2, 4, xs, ts, steps, seed, "2 stages x 2 data, gpipe M=4")
    np.testing.assert_allclose(four, one, rtol=0, atol=loss_atol(),
                               err_msg="4-device losses left 1-device losses")
    print(f"pipeline: max |loss diff| 4 devices vs 1: "
          f"{max(abs(a - b) for a, b in zip(four, one)):.3e} (pin atol "
          f"{loss_atol()}, rtol 0)")
    held = device_bytes(buf)
    print(f"pipeline: parameter bytes per device after training: {held}")
    check(sorted(held) == sorted(d.id for d in devs[:4])
          and all(v > 0 for v in held.values()),
          f"not every device holds parameter bytes: {held}")
    return {"1 device": c_one, "2x2 gpipe": c_four}


def phase_replica_placement(seed: int) -> None:
    """``--serve-replicas 2`` through the CLI, then the same fleet through
    the library to see which device each replica's pool lives on. A report,
    not a check: nothing in serve/ names a device (ROADMAP R4)."""
    run_cli(CLI_SERVE + ["--serve-replicas", "2"])
    cfg = GPTConfig()
    stages, _, _ = make_gpt_stages(jax.random.key(seed), cfg, 1)
    with tempfile.TemporaryDirectory(prefix="sdml-smoke-") as jd:
        fleet = ServeFleet(engine_factory(stages, cfg, n_slots=3,
                                          block_size=4), jd, n_replicas=2)
        rng = np.random.default_rng(seed)
        handles = [fleet.submit(rng.integers(0, cfg.vocab, size=6), 5)
                   for _ in range(6)]
        fleet.drain(max_ticks=2000)
        check(all(len(h.tokens) == 5 for h in handles),
              "fleet: not every request completed")
        where = {}
        for rep in fleet.replicas:
            kc = rep.supervisor.engine.pool.kc
            where[rep.idx] = sorted(
                d.id for leaf in jax.tree.leaves(kc) for d in leaf.devices())
        fleet.close()
    print(f"fleet: 2 replicas over {len(jax.devices())} devices; KV pool of "
          f"replica -> device ids: {where}")
    if len({tuple(v) for v in where.values()}) == 1:
        print("fleet: FINDING every replica's pool is on the same device — "
              "serve/ places nothing; R4's one-chip replicas need placement")


# -- main -------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default, what the driver runs): device, train, "
                         "serve, cli. 4: only the 2-stage x 2-data pipeline, "
                         "its 1-device comparison and replica placement")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    with phase("device"):
        device = phase_device(args.chips, cache_dir)
    if args.chips == 4:
        with phase("pipeline-4"):
            compile_s = phase_pipeline_4(XL, BATCH, TRAIN_STEPS, args.seed)
        with phase("replica-placement"):
            phase_replica_placement(args.seed)
    else:
        with phase("train"):
            stages, params, compile_s = phase_train(XL, BATCH, TRAIN_STEPS,
                                                    args.seed)
        with phase("serve"):
            phase_serve(stages, XL, params, args.seed)
        with phase("cli"):
            phase_cli()
    # a run that found its programs in the cache reads a smaller number here
    # than the run that put them there (compare two runs' lines)
    print(f"compile: train-step compile seconds {compile_s}; cache "
          f"{cache_entries(cache_dir)} entries at end")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
