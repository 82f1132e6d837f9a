"""The compiled-program registry: one call lints everything a launch runs.

PR 3's preflight covered the train/eval step; since the serving subsystem
landed, the riskiest compiled code is the DECODE path — the paged pool's
programs (``models/gpt.py``: ``make_paged_prefill_chunk``/
``make_paged_decode_step``/``make_paged_block_copy``, plus
``make_cached_decoder``, the solo-parity anchor) whose failure modes are
silent: an out-of-range block-table index scatters K/V into another
request's blocks, a CoW copy reads a buffer the prefill already donated, a
per-prompt-length retrace explodes the trace cache under real traffic. This module enumerates those
entry points with ABSTRACT-ARG BUILDERS — each argument carries the value
contract the host side (``serve/slots.py``) maintains, declared via
``analysis.spec`` — so ``lint_serve`` traces and lints the exact programs a
serve tick will execute, plus a composite tick that threads donated buffers
across program boundaries the way ``serve/engine.py`` does.

What runs per program:

- the full PR-3 rule walk (donation incl. double-donation, mesh-axis,
  dtype-drift — serving is single-device, so collective families are
  vacuous here but the walk still guards regressions);
- the ``scatter-bounds`` interval pass (``analysis/bounds.py``) against the
  declared contracts — block-table gathers proven within ``n_blocks + 1``,
  position counters within ``block_size``/``max_len``: the trash-page and
  trailing-zero disciplines ``serve/slots.py`` argues in prose,
  machine-checked against the compiled artifact;
- the ``retrace-explosion`` policy checks (builders memoized through
  ``_DECODE_BUILD_CACHE``; trace keys with unbounded runtime shapes
  flagged unless the deployment bounds them — prompt-length buckets or a
  ``prefill_chunk``);
- the HBM-bytes-per-tick cost model (:class:`~.report.HBMCost`): the
  serving twin of the ICI table — K/V bytes gathered/scattered per decode
  tick as a function of block size and slot count, plus
  :func:`predict_kv_bytes_resident`, cross-checked against the pool's
  ``serve_kv_bytes_resident`` gauge in tests.

Since ISSUE 9 the registry also covers sharded + speculative serving: with
``cfg.n_tensor_parallel > 1`` (pass the live ``mesh``) every serving
program is rebuilt as its exact ``shard_map`` twin — head-sharded pool,
packed Megatron weights — and the mesh-axis + scatter-bounds rules walk
the sharded block gathers; with ``spec_k >= 2`` (pass the draft build) the
draft's prefill and propose scan (over its own slot rows), the batched
verify step and a composite speculative tick join the registry, and the
HBM model reports PER-SHARD bytes plus the verify/propose streams.

Entry points::

    spec = ServeSpec(cfg, n_slots=4, block_size=16, prefill_chunk=8,
                     prompt_lens=(4, 8, 12))
    report = lint_serve(stages, spec)         # one Report, all programs
    report = lint_engine(engine)              # a live engine's exact knobs

``SDML_LINT_INJECT=<tag>`` (environment) appends one seeded ERROR finding
to every ``lint_serve`` report — the resilience-style drill that proves the
``--lint`` gates actually exit nonzero (CI and tests use it; never set it
in a real launch).
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, Callable

from simple_distributed_machine_learning_tpu.analysis import (
    Report,
    abstractify,
    analyze,
    spec,
)
from simple_distributed_machine_learning_tpu.analysis.report import (
    Finding,
    HBMCost,
    Severity,
)


@dataclasses.dataclass(frozen=True)
class ServeSpec:
    """Static description of one serving deployment — what the registry
    needs to rebuild the exact compiled programs and their contracts.

    ``prompt_lens`` declares the deployment's prompt-length buckets (the
    simulator's ``GPT_SERVE_PROMPTS``, a real frontend's bucketing): the
    retrace-explosion rule treats a prompt-shaped trace key as bounded iff
    buckets are declared or chunked prefill bounds the shapes.

    Tensor parallelism rides in ``cfg.n_tensor_parallel`` (the engine's
    own knob — :attr:`tp` reads it); ``lint_serve`` then needs the live
    ``mesh`` to rebuild the sharded programs. ``spec_k``/``draft_cfg``
    declare speculative decoding (``lint_serve`` additionally needs the
    ``draft_stages`` build to trace the propose/verify pair)."""
    cfg: Any
    n_slots: int = 4
    max_len: int | None = None          # None -> cfg.seq_len
    block_size: int = 16
    n_blocks: int | None = None         # None -> every slot reaches max_len
    prefill_chunk: int | None = None
    cache_dtype: Any = None
    prompt_lens: tuple | None = None
    spec_k: int = 0                     # 0 -> plain decode (no draft)
    draft_cfg: Any = None
    # the decode/verify attention path: "dense" gather-then-dense (two
    # passes over resident K/V per tick) or "fused" (the Pallas
    # paged-attention kernel's single pass) — the HBM model's per-tick
    # rows and the registry's built programs both key off it
    attn_kernel: str = "dense"
    # the host-RAM offload tier (serve/slots.py): evicted
    # prefix blocks demote to a host-side LRU of this many blocks instead
    # of dying; 0 disables the tier (and the host rows of the HBM model).
    # prefetch_ticks is the async host->HBM upload latency in engine ticks
    host_cache_blocks: int = 0
    prefetch_ticks: int = 1
    # multi-tenant LoRA serving (ISSUE 20): ``n_adapters`` is the device
    # adapter bank's TOTAL row count (the engine's rule is n_slots + 1;
    # row 0 is the pinned all-zero base row) and ``adapter_rank`` the
    # low-rank width of every row; 0 disables adapters. When on, every
    # decode-path program is rebuilt as its ``adapters=True`` twin —
    # trailing traced ``(bank, aid[s])`` args — and the bank-row upload
    # program joins the registry.
    n_adapters: int = 0
    adapter_rank: int = 0

    @property
    def adapters_on(self) -> bool:
        return self.n_adapters > 0 and self.adapter_rank > 0

    @property
    def tp(self) -> int:
        """Tensor-parallel width — the cfg's own knob, surfaced so the
        HBM model and per-shard byte accounting read one source."""
        return int(getattr(self.cfg, "n_tensor_parallel", 1))

    @property
    def ml(self) -> int:
        return int(self.max_len if self.max_len is not None
                   else self.cfg.seq_len)

    @property
    def blocks_per_seq(self) -> int:
        return math.ceil(self.ml / self.block_size)

    @property
    def nb(self) -> int:
        """Resolved pool capacity in blocks (the engine's default rule)."""
        if self.n_blocks is not None:
            return int(self.n_blocks)
        return self.n_slots * self.blocks_per_seq

    @property
    def resolved_chunk(self) -> int:
        """The prefill-chunk length the compiled program actually traces
        for this deployment: the declared chunk, else the largest prompt
        bucket (whole-remaining-prompt chunks compile per prompt shape),
        else 8; clamped to [1, ml-1]. The HBM model MUST use this same
        rule — a table row for a chunk the registry never built would
        mis-state the linted program's bytes."""
        c = self.prefill_chunk
        if c is None:
            c = int(max(self.prompt_lens)) if self.prompt_lens else 8
        return max(1, min(int(c), self.ml - 1))


@dataclasses.dataclass(frozen=True)
class Program:
    """One registry entry: a built (memoized) callable plus the abstract
    args — with declared contracts — that one serve tick would feed it."""
    name: str
    fn: Callable
    args: tuple


def check_builder_memo(name: str, build: Callable[[], Any]) -> list[Finding]:
    """The ``_DECODE_BUILD_CACHE`` contract, machine-checked: calling a
    decode-path builder twice with identical static config must return the
    SAME callable (and therefore the same compiled executables). A builder
    that returns fresh objects recompiles per engine/test instance — the
    retrace-explosion failure mode at the build level."""
    first, second = build(), build()
    if first is second:
        return []
    return [Finding(
        rule="retrace-explosion.unmemoized-builder", severity=Severity.ERROR,
        message=(f"builder '{name}' returned a DIFFERENT callable for an "
                 f"identical static config — every engine (and every test) "
                 f"constructing it pays a fresh trace + XLA compile"),
        where=name,
        hint="route the build through models.serving._DECODE_BUILD_CACHE "
             "(memo_build) keyed on the static config")]


def _retrace_finding(name: str, axis: str, sspec: ServeSpec) -> list[Finding]:
    """Flag a builder whose trace key includes an unbounded runtime value
    (a per-prompt-length retrace) unless the deployment bounds it."""
    if sspec.prompt_lens is not None:
        return []
    return [Finding(
        rule="retrace-explosion.unbounded-trace-key",
        severity=Severity.WARNING,
        message=(f"'{name}' retraces per distinct {axis}, and this "
                 f"deployment declares no bound on it — under real traffic "
                 f"every new length is a fresh trace + XLA compile (the "
                 f"trace cache grows without limit)"),
        where=name,
        hint="bucket prompt lengths (ServeSpec.prompt_lens / the "
             "simulator's buckets) or serve with a prefill_chunk, which "
             "bounds the target's prefill shapes to the chunk size")]


# -- abstract-arg builders -------------------------------------------------

def _key_sds():
    import jax
    return jax.ShapeDtypeStruct((), jax.random.key(0).dtype)


def _sds(shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _cache_sds(n_layers, n_phys, bs, n_heads, head_dim, cache_dtype):
    """Abstract paged pool: one buffer a layer, ``[n_phys, bs, H*dh]``, as
    a plain struct or the QuantKV (data + per-row scale plane) pytree a
    quantized pool actually threads through every tick program
    (``serve/slots.py::PagedKVPool``)."""
    import numpy as np

    from simple_distributed_machine_learning_tpu.models.serving import (
        QuantKV,
        is_quantized_dtype,
        storage_dtype,
    )
    layer = _sds((n_phys, bs, n_heads * head_dim), storage_dtype(cache_dtype))
    if is_quantized_dtype(cache_dtype):
        layer = QuantKV(layer, _sds((n_phys, bs, n_heads), np.float32))
    return (layer,) * n_layers


def build_registry(stages, sspec: ServeSpec, mesh=None, draft_stages=None
                   ) -> tuple[list[Program], list[Finding]]:
    """Build every compiled program of ``sspec``'s serve path with its
    abstract args + contracts; returns (programs, policy findings) where
    the findings are the retrace/memo checks that are not jaxpr rules.

    With ``sspec.tp > 1`` pass the live ``mesh`` — the registry then
    builds the EXACT shard_map programs a TP engine runs (head-sharded
    pool, packed Megatron weights). With ``sspec.spec_k >= 2`` pass the
    ``draft_stages`` build — the draft's prefill and propose scan, the
    batched verify and a composite speculative tick join the registry."""
    import numpy as np

    from simple_distributed_machine_learning_tpu.models.gpt import (
        make_cached_decoder,
        make_paged_block_copy,
        make_paged_decode_step,
        make_paged_prefill_chunk,
        pack_tp_serve_params,
    )
    from simple_distributed_machine_learning_tpu.models.serving import (
        SEAT_NONE,
    )

    cfg = sspec.cfg
    S, ml, bs = sspec.n_slots, sspec.ml, sspec.block_size
    V = cfg.vocab
    H = cfg.n_heads
    dh = cfg.d_model // H
    L = cfg.n_layers
    NB = sspec.blocks_per_seq
    n_blocks = sspec.nb
    dense_params = [s.params for s in stages]
    if sspec.tp > 1:
        # the TP serving layout: stacked Megatron block slices + replicated
        # embed/head (what the engine actually feeds the shard_map programs)
        params = abstractify(pack_tp_serve_params(dense_params, sspec.tp))
    else:
        params = abstractify(dense_params)

    f32 = _sds((), np.float32)
    f32S = _sds((S,), np.float32)
    kd1 = _sds((2,), np.uint32)
    kdS = _sds((S, 2), np.uint32)
    toks = spec((S,), np.int32, 0, V - 1)
    pos = spec((S,), np.int32, 0, ml - 1)
    top_ks = spec((S,), np.int32, 0, V)
    top_k1 = spec((), np.int32, 0, V)

    programs: list[Program] = []
    findings: list[Finding] = []

    # the cached decoder: the solo-parity anchor every served request is
    # bit-exact against — linted at one representative bucket (always the
    # dense single-device build, whatever the serving tp)
    t0 = int(min(sspec.prompt_lens)) if sspec.prompt_lens else min(4, ml - 1)
    t0 = max(1, min(t0, ml - 1))
    n_new = ml - t0
    # the solo anchor decodes contiguous rows: a quantized serving dtype
    # widens to f32 there (quantized pools are judged against it at
    # pinned tolerance, not bit-exactness)
    from simple_distributed_machine_learning_tpu.models.serving import (
        is_quantized_dtype as _is_q,
    )
    anchor_cd = None if _is_q(sspec.cache_dtype) else sspec.cache_dtype
    findings += check_builder_memo(
        "make_cached_decoder",
        lambda: make_cached_decoder(stages, cfg_dense(cfg), t0, n_new,
                                    cache_dtype=anchor_cd))
    findings += _retrace_finding("make_cached_decoder",
                                 "(prompt_len, n_new) pair", sspec)
    programs.append(Program(
        "cached_decoder",
        make_cached_decoder(stages, cfg_dense(cfg), t0, n_new,
                            cache_dtype=anchor_cd),
        (abstractify(dense_params), spec((1, t0), np.int32, 0, V - 1),
         _key_sds())))

    K = int(sspec.spec_k)
    speculative = K >= 2 and draft_stages is not None
    valid_n = spec((S,), np.int32, 0, K) if speculative else None
    drafts_a = spec((S, K), np.int32, 0, V - 1) if speculative else None
    qrows_a = _sds((S, K, V), np.float32) if speculative else None

    # the multi-tenant adapter bank and its index contracts: the bank is
    # TRACED data (hot-swap never retraces), the per-slot adapter ids are
    # gathers into [0, n_adapters) — the scatter-bounds pass proves the
    # bank-row gathers and the upload's row scatter stay inside the bank
    bank = aid1 = aids = None
    if sspec.adapters_on:
        from simple_distributed_machine_learning_tpu.models.gpt import (
            make_adapter_bank_update,
        )
        N, r, d = sspec.n_adapters, sspec.adapter_rank, cfg.d_model
        bank = {"aq": _sds((N, L, d, r), np.float32),
                "bq": _sds((N, L, r, d), np.float32),
                "av": _sds((N, L, d, r), np.float32),
                "bv": _sds((N, L, r, d), np.float32)}
        row_a = {"aq": _sds((L, d, r), np.float32),
                 "bq": _sds((L, r, d), np.float32),
                 "av": _sds((L, d, r), np.float32),
                 "bv": _sds((L, r, d), np.float32)}
        aid1 = spec((), np.int32, 0, N - 1)
        aids = spec((S,), np.int32, 0, N - 1)
        findings += check_builder_memo("make_adapter_bank_update",
                                       make_adapter_bank_update)
        programs.append(Program(
            "adapter_bank_update", make_adapter_bank_update(),
            (bank, spec((), np.int32, 0, N - 1), row_a)))

    kc = _cache_sds(L, n_blocks + 1, bs, H, dh, sspec.cache_dtype)
    kernel = sspec.attn_kernel
    tables = spec((S, NB), np.int32, 0, n_blocks)
    table1 = spec((NB,), np.int32, 0, n_blocks)
    c = sspec.resolved_chunk
    chunk = make_paged_prefill_chunk(stages, cfg, ml, bs,
                                     sspec.cache_dtype, mesh=mesh)
    decode = make_paged_decode_step(stages, cfg, ml, bs,
                                    sspec.cache_dtype, mesh=mesh,
                                    kernel=kernel)
    copy = make_paged_block_copy()
    findings += check_builder_memo(
        "make_paged_prefill_chunk",
        lambda: make_paged_prefill_chunk(stages, cfg, ml, bs,
                                         sspec.cache_dtype, mesh=mesh))
    findings += check_builder_memo(
        "make_paged_decode_step",
        lambda: make_paged_decode_step(stages, cfg, ml, bs,
                                       sspec.cache_dtype, mesh=mesh,
                                       kernel=kernel))
    findings += check_builder_memo("make_paged_block_copy",
                                   make_paged_block_copy)
    if sspec.prefill_chunk is None:
        findings += _retrace_finding("make_paged_prefill_chunk",
                                     "chunk (= whole-prompt) length", sspec)

    # every slot's newest token and key, which both programs keep on the
    # device beside the pool (PagedServing): the chunk seats its
    # slot's (``seat``: SEAT_NONE, SEAT_SAMPLE or a token), the decode
    # reads its inputs there and writes the ``live`` slots' back
    state = ((toks, kdS),)
    slot1 = spec((), np.int32, 0, S - 1)
    seat1 = spec((), np.int32, SEAT_NONE, V - 1)
    live = _sds((S,), np.bool_)
    chunk_args = (params, kc, kc, state, spec((1, c), np.int32, 0, V - 1),
                  spec((), np.int32, 0, ml - 1 - c), table1, slot1, seat1,
                  kd1, f32, top_k1, f32)
    decode_args = (params, kc, kc, state, pos, tables, live, f32S, top_ks,
                   f32S)
    copy_args = (kc, kc, spec((), np.int32, 1, n_blocks),
                 spec((), np.int32, 0, n_blocks))
    programs.append(Program("paged_prefill_chunk", chunk, chunk_args))
    programs.append(Program("paged_decode", decode, decode_args))
    programs.append(Program("paged_block_copy", copy, copy_args))

    if sspec.adapters_on:
        findings += check_builder_memo(
            "make_paged_prefill_chunk[adapters]",
            lambda: make_paged_prefill_chunk(stages, cfg, ml, bs,
                                             sspec.cache_dtype, mesh=mesh,
                                             adapters=True))
        findings += check_builder_memo(
            "make_paged_decode_step[adapters]",
            lambda: make_paged_decode_step(stages, cfg, ml, bs,
                                           sspec.cache_dtype, mesh=mesh,
                                           kernel=kernel, adapters=True))
        programs.append(Program(
            "paged_prefill_chunk_adapter",
            make_paged_prefill_chunk(stages, cfg, ml, bs,
                                     sspec.cache_dtype, mesh=mesh,
                                     adapters=True),
            chunk_args + (bank, aid1)))
        programs.append(Program(
            "paged_decode_adapter",
            make_paged_decode_step(stages, cfg, ml, bs, sspec.cache_dtype,
                                   mesh=mesh, kernel=kernel,
                                   adapters=True),
            decode_args + (bank, aids)))

    # the composite tick: chunk -> CoW copy -> decode, pool and state
    # buffers threaded exactly as engine.step/_ensure_writable_range
    # thread them. A read of the pre-call buffer after any stage donated
    # it is the cross-program read-after-donate the donation rules exist
    # for.
    def paged_tick(params, kc, vc, state, tokens, p0, table, slot, seat,
                   kd_1, t1, k1, p1, dst, src, pos, tables, live, temps,
                   tks, tps):
        kc, vc, state, tok, kd_1 = chunk(params, kc, vc, state, tokens, p0,
                                         table, slot, seat, kd_1, t1, k1,
                                         p1)
        kc, vc = copy(kc, vc, dst, src)
        kc, vc, state, toks2, kds2 = decode(params, kc, vc, state, pos,
                                            tables, live, temps, tks, tps)
        return kc, vc, state, tok, toks2, kds2

    programs.append(Program(
        "paged_tick", paged_tick,
        chunk_args + copy_args[2:] + decode_args[4:]))

    if speculative:
        from simple_distributed_machine_learning_tpu.models.gpt import (
            make_paged_verify_step,
            make_slot_prefill,
            make_slot_propose,
        )
        from simple_distributed_machine_learning_tpu.models.serving import (
            is_quantized_dtype,
            storage_dtype,
        )
        # the draft's programs over its own slot rows (one max_len row a
        # slot; a quantized TARGET dtype falls back to f32 for the draft:
        # the engine's rule — trace the programs it actually runs)
        dcfg = sspec.draft_cfg
        dL = sum(len(p["blocks"]) for p in (s.params for s in draft_stages))
        draft_cd = (None if is_quantized_dtype(sspec.cache_dtype)
                    else sspec.cache_dtype)
        dkc = _sds((dL, S, dcfg.n_heads, ml,
                    dcfg.d_model // dcfg.n_heads), storage_dtype(draft_cd))
        dparams = abstractify([s.params for s in draft_stages])
        draft_prefill = make_slot_prefill(draft_stages, dcfg, ml, draft_cd)
        findings += check_builder_memo(
            "make_slot_prefill",
            lambda: make_slot_prefill(draft_stages, dcfg, ml, draft_cd))
        # the draft prefills a request's whole sequence at once
        findings += _retrace_finding("make_slot_prefill", "prompt length",
                                     sspec)
        programs.append(Program(
            "draft_prefill", draft_prefill,
            (dparams, dkc, dkc, spec((1, t0), np.int32, 0, V - 1),
             spec((), np.int32, 0, S - 1), kd1, f32, top_k1, f32)))
        propose = make_slot_propose(draft_stages, dcfg, ml, K, draft_cd)
        findings += check_builder_memo(
            "make_slot_propose",
            lambda: make_slot_propose(draft_stages, dcfg, ml, K, draft_cd))
        propose_args = (dparams, dkc, dkc, toks, pos, kdS, f32S, top_ks,
                        f32S)
        verify = make_paged_verify_step(stages, cfg, ml, bs, K,
                                        sspec.cache_dtype, mesh=mesh,
                                        kernel=kernel)
        findings += check_builder_memo(
            "make_paged_verify_step",
            lambda: make_paged_verify_step(stages, cfg, ml, bs, K,
                                           sspec.cache_dtype, mesh=mesh,
                                           kernel=kernel))
        verify_args = (params, kc, kc, toks, pos, drafts_a, qrows_a,
                       valid_n, tables, kdS, f32S, top_ks, f32S)
        programs.append(Program("paged_propose", propose, propose_args))
        programs.append(Program("paged_verify", verify, verify_args))

        # the composite speculative tick: propose (draft rows) -> verify
        # (target pool), proposals flowing between on device. Single-device
        # targets execute this as the engine's FUSED make_paged_spec_tick
        # program — lint exactly that build; a TP engine dispatches the two
        # halves separately, so the closure composition below IS its tick
        if sspec.tp == 1:
            from simple_distributed_machine_learning_tpu.models.gpt import (
                make_paged_spec_tick,
            )
            paged_spec_tick = make_paged_spec_tick(
                stages, cfg, draft_stages, dcfg, ml, bs, K,
                sspec.cache_dtype, kernel=kernel)
            findings += check_builder_memo(
                "make_paged_spec_tick",
                lambda: make_paged_spec_tick(stages, cfg, draft_stages,
                                             dcfg, ml, bs, K,
                                             sspec.cache_dtype,
                                             kernel=kernel))
        else:
            def paged_spec_tick(dparams, dkc, dvc, params, kc, vc, toks,
                                pos, valid, tables, dkds, kds, temps, tks,
                                tps):
                dkc, dvc, drafts, qrows, dkds2 = propose(
                    dparams, dkc, dvc, toks, pos, dkds, temps, tks, tps)
                kc, vc, toks2, n_acc, kds2 = verify(
                    params, kc, vc, toks, pos, drafts, qrows, valid,
                    tables, kds, temps, tks, tps)
                return dkc, dvc, kc, vc, toks2, n_acc, kds2, dkds2

        programs.append(Program(
            "paged_spec_tick", paged_spec_tick,
            propose_args[:3] + (params, kc, kc, toks, pos, valid_n,
                                tables, kdS, kdS, f32S, top_ks, f32S)))
    return programs, findings


def cfg_dense(cfg):
    """The single-device twin of a (possibly TP) serving config — what the
    solo-parity anchor decodes with."""
    if getattr(cfg, "n_tensor_parallel", 1) == 1:
        return cfg
    return dataclasses.replace(cfg, n_tensor_parallel=1)


def degraded_spec(sspec: ServeSpec) -> ServeSpec:
    """The serve supervisor's degraded-fallback deployment for ``sspec`` —
    the SAME transform ``serve/supervisor.py::engine_factory`` applies when
    rebuilding past ``degrade_after`` restarts: speculation off, tensor
    parallelism off, the fused kernel, a quantized cache and the host tier
    off; everything else (the paged pool's block size, block count and
    prefill chunk, the adapter bank: tenants keep serving on the worst day)
    kept. One function, so the registry sweep
    (:func:`default_registry_reports`) lints the exact layout a
    chaos-stressed supervisor will rebuild into — a fallback that only
    exists on the worst day must be proven clean on every PR."""
    from simple_distributed_machine_learning_tpu.models.serving import (
        is_quantized_dtype,
    )
    return dataclasses.replace(
        sspec, cfg=cfg_dense(sspec.cfg), spec_k=0, draft_cfg=None,
        attn_kernel="dense", host_cache_blocks=0, prefetch_ticks=1,
        cache_dtype=(None if is_quantized_dtype(sspec.cache_dtype)
                     else sspec.cache_dtype))


# -- the HBM-bytes-per-tick model ------------------------------------------

def hbm_tick_costs(sspec: ServeSpec, n_layers: int | None = None
                   ) -> list[HBMCost]:
    """Static K/V traffic per serve tick, the serving mirror of the ICI
    cost table. Shapes are static — the batched decode gathers EVERY
    slot's full table span every tick regardless of occupancy (that is the
    design: one compiled program serves every tick), so the per-tick
    stream sizes depend on block geometry and slot count only; what
    occupancy changes is the RESIDENT bytes
    (:func:`predict_kv_bytes_resident`)."""
    from simple_distributed_machine_learning_tpu.serve.slots import (
        kv_block_bytes,
    )
    cfg = sspec.cfg
    L = n_layers if n_layers is not None else cfg.n_layers
    H, dh = cfg.n_heads, cfg.d_model // cfg.n_heads
    S, ml = sspec.n_slots, sspec.ml
    tp = sspec.tp
    # K + V, one position, 1 layer — PER SHARD (the TP serving programs
    # split the head axis tp ways, so each chip streams H/tp heads).
    # Derived from kv_block_bytes so it IS the pool's bytes_per_block per
    # row — which makes quantized caches automatic: int8/fp8 data plus the
    # per-row f32 scale planes the kernel (and the dense-path dequant
    # gather) actually stream
    row = kv_block_bytes(1, H // tp, 1, dh, sspec.cache_dtype)
    shard = f" (per {tp}-way shard)" if tp > 1 else ""
    fused = sspec.attn_kernel == "fused"
    out: list[HBMCost] = []
    K = int(sspec.spec_k)
    span = sspec.blocks_per_seq * sspec.block_size
    out.append(HBMCost(
        "decode.kv_gather", "paged_decode", S * L * span * row,
        note=f"{S} slots x {L} layers x {span}-row table span{shard}"
             + (" — the fused kernel's single pass" if fused else "")))
    if not fused:
        # gather-then-dense materializes the gathered span and the
        # attention einsums read it back: a SECOND full pass of
        # resident K/V per tick — exactly what kernel='fused'
        # (ops/paged_attention.py) eliminates
        out.append(HBMCost(
            "decode.kv_attn_reread", "paged_decode",
            S * L * span * row,
            note=f"dense-math path rereads the materialized "
                 f"span{shard}; eliminated by kernel='fused'"))
    out.append(HBMCost(
        "decode.kv_scatter", "paged_decode", S * L * row,
        note=f"one position per slot per layer{shard}"))
    c = sspec.resolved_chunk
    out.append(HBMCost(
        "prefill.kv_scatter", "paged_prefill_chunk", c * L * row,
        note=f"{c}-token chunk{shard}"))
    out.append(HBMCost(
        "prefill.kv_gather", "paged_prefill_chunk", L * span * row,
        note=f"the chunk attends the gathered table span{shard}"))
    out.append(HBMCost(
        "cow.block_copy", "paged_block_copy",
        L * sspec.block_size * row,
        note=f"per copy-on-write divergence, all layers{shard}"))
    if sspec.host_cache_blocks:
        # the host offload tier's transfer-bandwidth bill: one whole
        # block (all layers, K+V, plus quantized scale planes — it IS
        # the pool's bytes_per_block) crosses the HBM<->host boundary
        # per demotion and per prefetch promotion. The pool's
        # host_transfer_bytes_total counter advances by exactly this
        # per move — predict_transfer_bytes reconciles it to zero
        # drift (tests/test_disagg.py)
        blk = kv_block_bytes(L, H // tp, sspec.block_size, dh,
                             sspec.cache_dtype)
        out.append(HBMCost(
            "offload.demote_copy", "host_offload", blk,
            note=f"per HBM->host demotion: the evicted block, all "
                 f"layers{shard} — an eviction that would otherwise "
                 f"discard the prefix"))
        out.append(HBMCost(
            "offload.prefetch_upload", "host_offload", blk,
            note=f"per host->HBM promotion: one async-prefetched "
                 f"block, all layers{shard}, spread over "
                 f"{sspec.prefetch_ticks} tick(s)"))
    if K >= 2:
        out.append(HBMCost(
            "verify.kv_scatter", "paged_verify", S * L * K * row,
            note=f"{K} speculated positions per slot per layer{shard}"))
        out.append(HBMCost(
            "verify.kv_gather", "paged_verify", S * L * span * row,
            note=f"the verify queries attend the table span{shard}"
                 + (" — the fused kernel's single pass" if fused
                    else "")))
        if not fused:
            out.append(HBMCost(
                "verify.kv_attn_reread", "paged_verify",
                S * L * span * row,
                note=f"dense-math path rereads the materialized "
                     f"span{shard}; eliminated by kernel='fused'"))
    if sspec.adapters_on:
        # the adapter bank's per-tick traffic: each slot gathers its
        # tenant's whole A/B row (4 planes x L layers, f32) per decode
        # dispatch, prefill gathers one row, and every hot-swap/first
        # admission scatters one row back. Billed with the SAME formula
        # as the resident gauge (models/lora.py::bank_bytes) so the rows
        # and predict_adapter_bytes can never disagree on a row's size.
        # Under TP the bq/bv planes are column-sliced per shard but the
        # aq/av gathers replicate — billed at the replicated full row.
        from simple_distributed_machine_learning_tpu.models import lora
        row_b = lora.bank_bytes(1, L, cfg.d_model, sspec.adapter_rank)
        out.append(HBMCost(
            "decode.adapter_gather", "paged_decode", S * row_b,
            note=f"{S} slots x one bank row ({L} layers, 4 low-rank "
                 f"planes, rank {sspec.adapter_rank}) — row 0 (base) "
                 f"gathers the same bytes of zeros"))
        out.append(HBMCost(
            "prefill.adapter_gather", "paged_prefill_chunk", row_b,
            note="the boarding request's one bank row"))
        out.append(HBMCost(
            "adapter.bank_upload", "adapter_bank_update", row_b,
            note="per hot-swap / first admission: one donated bank-row "
                 "rewrite (serve_adapter_swaps_total advances by 1)"))
    if K >= 2 and sspec.draft_cfg is not None:
        from simple_distributed_machine_learning_tpu.models.serving import (
            is_quantized_dtype,
        )
        dcfg = sspec.draft_cfg
        # the draft keeps one max_len row a slot; a quantized TARGET dtype
        # falls back to f32 for the draft (the engine's rule)
        draft_cd = (None if is_quantized_dtype(sspec.cache_dtype)
                    else sspec.cache_dtype)
        drow = kv_block_bytes(1, dcfg.n_heads, 1,
                              dcfg.d_model // dcfg.n_heads, draft_cd)
        dL = dcfg.n_layers
        out.append(HBMCost(
            "propose.kv_read", "slot_propose", K * S * dL * ml * drow,
            note=f"{K} draft steps x {S} rows x {dL} draft layers x "
                 f"max_len={ml} (replicated draft)"))
        out.append(HBMCost(
            "propose.kv_scatter", "slot_propose", K * S * dL * drow,
            note="one position per draft step per slot per draft layer"))
    return out


def predict_kv_bytes_resident(sspec: ServeSpec, rows_per_seq,
                              n_layers: int | None = None,
                              kv_heads: int | None = None,
                              head_dim: int | None = None,
                              streams: int = 2) -> int:
    """Model of the pool's ``serve_kv_bytes_resident`` gauge: bytes the
    given live sequences pin, where each entry of ``rows_per_seq`` is one
    sequence's written-row count (``prompt_len + tokens_emitted - 1`` once
    decoding). Assumes no prefix sharing between the sequences — shared
    blocks make the true gauge strictly smaller, never larger, which is
    what makes the runtime KV-drift gauge (``serve_kv_drift_bytes`` =
    live − predicted) a leak detector: 0 without sharing, ≤ 0 with it,
    and > 0 only if the pool pins blocks the model says it cannot need.
    PER SHARD under TP — the pool's gauge reports per-chip bytes (heads
    split ``tp`` ways), and this model must agree with it EXACTLY
    (tests/test_analysis_serve.py). The pool's row is the CACHE's:
    ``kv_heads`` heads of ``head_dim`` lanes in ``streams`` buffers a layer
    (``PagedServing.kv_heads`` / ``head_dim`` / ``value_lanes``), each by
    default what GPT's config gives (as many heads as the queries', ``d_model
    / n_heads`` lanes, a key and a value buffer)."""
    from simple_distributed_machine_learning_tpu.serve.slots import (
        kv_block_bytes,
    )
    cfg = sspec.cfg
    L = n_layers if n_layers is not None else cfg.n_layers
    per_block = kv_block_bytes(
        L, (cfg.n_heads if kv_heads is None else kv_heads) // sspec.tp,
        sspec.block_size,
        cfg.d_model // cfg.n_heads if head_dim is None else head_dim,
        sspec.cache_dtype, streams)
    blocks = sum(math.ceil(r / sspec.block_size) for r in rows_per_seq)
    return blocks * per_block


def predict_adapter_bytes(sspec: ServeSpec,
                          n_layers: int | None = None) -> int:
    """Model of the AdapterStore's ``serve_adapter_resident_bytes`` gauge:
    HBM the device adapter bank pins — the whole static allocation (every
    row, resident or not; the bank never reallocates). Computed with the
    store's OWN formula (:func:`~..models.lora.bank_bytes`), so the parity
    pin is exact by construction: any drift means the deployment spec and
    the live store describe different banks
    (tests/test_adapters.py pins predicted == live)."""
    if not sspec.adapters_on:
        return 0
    from simple_distributed_machine_learning_tpu.models import lora
    cfg = sspec.cfg
    L = n_layers if n_layers is not None else cfg.n_layers
    return lora.bank_bytes(sspec.n_adapters, L, cfg.d_model,
                           sspec.adapter_rank)


def _host_block_bytes(sspec: ServeSpec, n_layers: int | None = None) -> int:
    """One paged block's bytes for ``sspec`` — the pool's own
    ``bytes_per_block`` (per shard; quantized scale planes included), the
    unit both host-tier predictors below bill in."""
    from simple_distributed_machine_learning_tpu.serve.slots import (
        kv_block_bytes,
    )
    cfg = sspec.cfg
    L = n_layers if n_layers is not None else cfg.n_layers
    return kv_block_bytes(L, cfg.n_heads // sspec.tp, sspec.block_size,
                          cfg.d_model // cfg.n_heads, sspec.cache_dtype)


def predict_host_kv_bytes(sspec: ServeSpec, n_host_blocks: int,
                          n_layers: int | None = None) -> int:
    """Model of the pool's ``serve_host_bytes_resident`` gauge: bytes the
    host-RAM offload tier pins for ``n_host_blocks`` demoted blocks. The
    host tier stores whole blocks (the exact device layout, numpy-side),
    so the model is blocks x ``bytes_per_block`` — and like
    ``predict_kv_bytes_resident`` it must agree with the live gauge
    EXACTLY: any drift is an offload-tier accounting leak
    (tests/test_disagg.py pins drift == 0 mid-handoff, post-demote and
    with a prefetch in flight)."""
    return n_host_blocks * _host_block_bytes(sspec, n_layers)


def predict_transfer_bytes(sspec: ServeSpec, n_blocks: int,
                           n_layers: int | None = None) -> int:
    """Model of the pool's ``serve_host_transfer_bytes_total`` counter:
    every block crossing the HBM↔host boundary — demotions down,
    prefetch promotions up — moves exactly ``bytes_per_block``
    (quantized caches move the narrow data planes plus their f32 scales,
    so int8 blocks cross at roughly half the f32 bill). ``n_blocks`` is
    the move count (``host_demotes_total + host_promotes_total``); the
    prediction must equal the live counter exactly, same discipline as
    ``serve_kv_drift_bytes``."""
    return n_blocks * _host_block_bytes(sspec, n_layers)


# -- the one-call preflights -----------------------------------------------

def jnp_dtype_name(cache_dtype) -> str:
    import jax.numpy as jnp
    return jnp.dtype(cache_dtype).name


def _injected_findings() -> list[Finding]:
    tag = os.environ.get("SDML_LINT_INJECT")
    if not tag:
        return []
    return [Finding(
        rule=f"injected.{tag}", severity=Severity.ERROR,
        message="seeded ERROR finding injected via SDML_LINT_INJECT — the "
                "gate drill proving --lint preflights actually fail",
        where="SDML_LINT_INJECT", hint="unset SDML_LINT_INJECT")]


def lint_serve(stages, sspec: ServeSpec, name: str | None = None,
               mesh=None, draft_stages=None) -> Report:
    """Trace and lint every compiled program of one serving deployment;
    returns a single merged :class:`Report` carrying the findings of all
    rule families, the retrace/memo policy checks and the
    HBM-bytes-per-tick table. Pass the live ``mesh`` for a TP deployment
    (``sspec.tp > 1``) and the ``draft_stages`` build for a speculative
    one (``sspec.spec_k >= 2``)."""
    if sspec.tp > 1 and mesh is None:
        raise ValueError(
            f"lint_serve: sspec.cfg.n_tensor_parallel={sspec.tp} needs the "
            f"deployment's mesh to rebuild the sharded programs")
    if sspec.spec_k >= 2 and draft_stages is None:
        raise ValueError(
            f"lint_serve: sspec.spec_k={sspec.spec_k} needs the "
            f"draft_stages build to trace the propose/verify pair")
    programs, policy = build_registry(stages, sspec, mesh=mesh,
                                      draft_stages=draft_stages)
    n_layers = sum(len(p["blocks"]) for p in (s.params for s in stages))
    label = name or (f"serve[slots={sspec.n_slots} max_len={sspec.ml}"
                     f" block={sspec.block_size}"
                     f" chunk={sspec.prefill_chunk}"
                     + (" kernel=fused" if sspec.attn_kernel == "fused"
                        else "")
                     + (f" cache={jnp_dtype_name(sspec.cache_dtype)}"
                        if sspec.cache_dtype is not None else "")
                     + (f" tp={sspec.tp}" if sspec.tp > 1 else "")
                     + (f" spec_k={sspec.spec_k}" if sspec.spec_k
                        else "")
                     + (f" adapters={sspec.n_adapters}"
                        f"r{sspec.adapter_rank}"
                        if sspec.adapters_on else "") + "]")
    report = Report(name=label, findings=list(policy))
    kernel_rows: list[HBMCost] = []
    for prog in programs:
        sub = analyze(prog.fn, *prog.args, mesh=mesh,
                      name=f"{label}:{prog.name}")
        for f in sub.findings:
            report.findings.append(dataclasses.replace(
                f, where=f"{prog.name}: {f.where}" if f.where
                else prog.name))
        report.costs.extend(sub.costs)
        # kernel-derived HBM rows (analysis/kernels.py): what the traced
        # pallas_calls' own BlockSpecs say the program streams
        kernel_rows.extend(dataclasses.replace(h, program=prog.name)
                           for h in sub.hbm)
    report.hbm.extend(kernel_rows)
    model_rows = hbm_tick_costs(sspec, n_layers=n_layers)
    report.hbm.extend(model_rows)
    report.findings.extend(
        _reconcile_kernel_hbm(kernel_rows, model_rows, sspec))
    report.findings.extend(_injected_findings())
    return report


def _reconcile_kernel_hbm(kernel_rows: list[HBMCost],
                          model_rows: list[HBMCost],
                          sspec: ServeSpec) -> list[Finding]:
    """Cross-check the kernel-DERIVED K/V stream bytes (block shapes x the
    grid trips each index map depends on, from the traced pallas_calls)
    against the hand-built tick model's gather rows. The fused kernel's
    whole value claim — it deletes the 2x ``kv_attn_reread`` pass, reading
    resident K/V exactly once per tick — must be computed from the
    kernel's own BlockSpecs, not asserted: the two totals agree EXACTLY or
    the registry gate fails."""
    if sspec.attn_kernel != "fused":
        return []
    derived: dict[str, int] = {}
    for h in kernel_rows:
        if h.op == "kernel.kv_stream":
            derived[h.program] = derived.get(h.program, 0) + h.bytes_per_tick
    model = {(m.program, m.op): m.bytes_per_tick for m in model_rows}
    out: list[Finding] = []
    for prog, op in (("paged_decode", "decode.kv_gather"),
                     ("paged_verify", "verify.kv_gather")):
        want = model.get((prog, op))
        if want is None:
            continue
        got = derived.get(prog)
        if got is None:
            out.append(Finding(
                rule="kernel-hbm.mismatch", severity=Severity.ERROR,
                message=(f"attn_kernel='fused' but no pallas_call K/V "
                         f"stream was traced in {prog} — the registry "
                         f"linted a program that is not running the "
                         f"kernel it claims"),
                where=prog,
                hint="the engine/registry builder dropped the fused "
                     "kernel path; rebuild with kernel='fused' plumbed "
                     "through"))
        elif got != want:
            out.append(Finding(
                rule="kernel-hbm.mismatch", severity=Severity.ERROR,
                message=(f"{prog}: the traced kernels' BlockSpecs stream "
                         f"{got} K/V bytes/tick but the HBM tick model's "
                         f"{op} row says {want} — the fused single-pass "
                         f"claim (the deleted kv_attn_reread) no longer "
                         f"matches the kernel itself"),
                where=prog,
                hint="hbm_tick_costs and the kernel BlockSpecs are one "
                     "contract: fix whichever drifted"))
    return out


def default_registry_reports() -> list[Report]:
    """The CI lint gate's serve-program sweep: one tiny GPT build linted
    at two block/chunk shapes, under the fused kernel over a quantized
    pool, with adapters, speculative and as the degraded fallback, all
    with the simulator's prompt buckets declared — every report must
    be ERROR-free for the gate to pass (``--serve`` in the analysis
    CLI)."""
    import jax

    from simple_distributed_machine_learning_tpu.models.gpt import (
        GPTConfig,
        make_gpt_stages,
    )
    cfg = GPTConfig(vocab=32, seq_len=24, d_model=16, n_heads=2, n_layers=2)
    stages, _, _ = make_gpt_stages(jax.random.key(0), cfg, 1)
    import dataclasses as _dc
    draft_cfg = _dc.replace(cfg, n_layers=1)
    draft_stages, _, _ = make_gpt_stages(jax.random.key(1), draft_cfg, 1)
    buckets = (4, 8, 12)
    # the speculative deployment runs the FUSED verify kernel (the
    # K-token variant of paged attention) so the registry sweep lints —
    # and HBM-reconciles — both fused tick shapes, not just K=1 decode
    spec_paged = ServeSpec(cfg, n_slots=4, block_size=4, prefill_chunk=3,
                           prompt_lens=buckets, spec_k=4,
                           draft_cfg=draft_cfg, attn_kernel="fused")
    specs = [
        ServeSpec(cfg, n_slots=4, block_size=4, prefill_chunk=3,
                  prompt_lens=buckets),
        ServeSpec(cfg, n_slots=4, block_size=8, prefill_chunk=None,
                  prompt_lens=buckets),
        # the fused Pallas paged-attention kernel over an int8-quantized
        # pool (interpret mode off-TPU): the serving hot path's kernel
        # variant is linted exactly like the dense-math programs
        ServeSpec(cfg, n_slots=4, block_size=4, prefill_chunk=3,
                  prompt_lens=buckets, cache_dtype="int8",
                  attn_kernel="fused"),
        # the multi-tenant adapter layouts (ISSUE 20): every decode-path
        # program's adapters=True twin plus the bank-row upload program,
        # bank sized by the engine's n_slots + 1 rule
        ServeSpec(cfg, n_slots=4, block_size=4, prefill_chunk=3,
                  prompt_lens=buckets, n_adapters=5, adapter_rank=2),
        # the speculative programs (draft prefill + propose, batched verify,
        # composite tick) — TP deployments need a live multi-device mesh,
        # so the CLI/tests cover those where devices exist
        spec_paged,
    ]
    reports = [lint_serve(stages, s, draft_stages=(draft_stages
                                                   if s.spec_k else None))
               for s in specs]
    # the serve supervisor's degraded-fallback layout, derived from the
    # full speculative deployment by the SAME rule engine_factory applies
    # on a chaos-driven rebuild — explicitly named so the gate output
    # shows the fallback was proven, not assumed
    reports.append(lint_serve(
        stages, degraded_spec(spec_paged),
        name=f"serve[degraded fallback of spec_k={spec_paged.spec_k} "
             f"kernel=fused: slots={spec_paged.n_slots} "
             f"block={spec_paged.block_size} tp=1 spec_k=0]"))
    return reports


def engine_spec(engine, prompt_lens: tuple | None = None) -> ServeSpec:
    """The :class:`ServeSpec` of a LIVE engine — the one engine->spec
    mapping (block geometry, chunk size, cache dtype, spec/draft
    shape) shared by the lint preflight and the runtime KV-drift gauge,
    so the two can never describe different deployments."""
    pool = engine.pool
    return ServeSpec(
        cfg=engine.cfg, n_slots=pool.n_slots, max_len=engine.max_len,
        block_size=pool.block_size, n_blocks=pool.n_blocks,
        prefill_chunk=engine.prefill_chunk,
        # the storage dtype (a quantized pool's is its narrow one, which
        # round-trips through storage_dtype)
        cache_dtype=pool.cache_dtype, prompt_lens=prompt_lens,
        spec_k=engine.spec_k if engine.speculative else 0,
        draft_cfg=engine.draft_cfg,
        attn_kernel=engine.attn_kernel,
        host_cache_blocks=pool.host_cache_blocks,
        prefetch_ticks=pool.prefetch_ticks,
        n_adapters=(0 if getattr(engine, "_adapters", None) is None
                    else engine._adapters.n_rows),
        adapter_rank=(0 if getattr(engine, "_adapters", None) is None
                      else engine._adapters.rank))


def lint_engine(engine, prompt_lens: tuple | None = None) -> Report:
    """Preflight a live :class:`~..serve.engine.InferenceEngine`'s EXACT
    programs — same block geometry, chunk size and cache dtype the
    engine constructed (``InferenceEngine(lint=True)`` calls this at
    construction)."""
    return lint_serve(engine.stages, engine_spec(engine, prompt_lens),
                      mesh=engine.mesh, draft_stages=engine.draft_stages)
