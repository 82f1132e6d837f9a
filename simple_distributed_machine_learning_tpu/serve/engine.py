"""The continuous-batching inference engine: submit / step / drain.

:class:`InferenceEngine` is the serving API over a model's serving
programs, the paged KV-cache pool and the FCFS scheduler. It takes the
cache's shape and its two compiled programs from the model
(``cfg.paged_serving(...)`` -> ``models/serving.py::PagedServing``) and
nowhere else: GPT (``models/gpt.py``) is attention in every block and nothing
else; a model with state-space layers (``models/jamba.py``) also has a
recurrent buffer per slot; a model that generates by diffusion over blocks
(``models/sdar.py``, ``PagedServing.block > 1``) keeps each slot's block in
progress. Each keeps per-slot buffers in the pool (``pool.state``: every
slot's newest token and key, and the recurrent buffers or the block where
there are any), which ride beside the K/V blocks through both programs.

- ``submit(prompt, ...) -> Request`` enqueues one sequence with its own
  sampling params and seeded key stream, and returns the live handle
  (``handle.tokens`` grows as the engine runs; ``on_token`` streams);
- ``step()`` is one *tick*; ``drain()`` ticks until queue and slots are
  empty.

One KV-cache layout: the block-table paged pool (``serve/slots.py::
PagedKVPool``) with prefix sharing, copy-on-write and CHUNKED prefill.
Each tick runs at most one prefill chunk (``prefill_chunk`` prompt
positions of the oldest still-prefilling request) and then ONE batched
block-gather decode step over every decoding slot — a long prompt does not
freeze in-flight requests, and admission is gated on free BLOCKS (the
request's worst-case footprint after prefix sharing), not free rows.
Non-decoding slots' tick writes are routed to the pool's trash block (see
the stale-write note in ``serve/slots.py``). Only a speculative DRAFT
model keeps one contiguous row per slot (:meth:`InferenceEngine.
_init_draft_pool`).

Device state is exactly the pool's buffers; everything else (positions,
block tables, request lifecycle, and the host's copy of last tokens and
key streams) is host-side numpy assembled into each tick's inputs — the
scheduler stays plain Python while every FLOP runs inside the compiled
programs. Every model's programs keep every slot's newest token and key on
the device (``models/serving.py::PagedServing``), so the tick is
:meth:`InferenceEngine._tick_ahead`'s: decode, then the chunk, and the
NEXT tick's decode launched before this tick's tokens are read, so the
device works through the host's share of the tick. A request's tokens are
the same; the slot a chunk seats decodes from the tick after. Speculative
decoding keeps the plain tick (chunk, then :meth:`InferenceEngine.
_spec_tick`): its draft and its token budget need the host's tokens every
tick.

Block steps (``PagedServing.block = B > 1``): a decoding slot's tick is one
forward of its block of ``B`` positions. A denoising forward emits nothing
and leaves nothing that lasts; a committing forward emits up to ``B`` tokens
at once, all stamped when the tick is read, and advances the slot by ``B``.
The last prefill chunk emits no token: it seats the first block. Which
phase a slot is in follows from counts the host keeps (``pool.block_fwd`` /
``block_total``: the static schedule), so the dispatch ahead holds.

Correctness anchor: a request's tokens are bit-exact vs decoding it alone
via ``make_cached_decoder`` with the same seed (tests/test_serve.py) —
admission order, co-residents, occupancy, paged blocks, SHARED prefixes and
chunk boundaries cannot change anyone's output.
"""

from __future__ import annotations

import collections
import functools
import time

import numpy as np

from simple_distributed_machine_learning_tpu.resilience.faults import (
    maybe_fire,
)
from simple_distributed_machine_learning_tpu.serve.metrics import ServeMetrics
from simple_distributed_machine_learning_tpu.serve.request import (
    ACTIVE,
    DONE,
    QUEUED,
    SHED,
    Request,
    validate_request,
)
from simple_distributed_machine_learning_tpu.serve.scheduler import (
    FCFSScheduler,
)
from simple_distributed_machine_learning_tpu.serve.slots import PagedKVPool
from simple_distributed_machine_learning_tpu.telemetry import tracing

# sampling-param sentinels (models/serving.py::sample_dyn): 0 disables
# top-k, anything > 1 disables top-p
_NO_TOP_K = 0
_NO_TOP_P = 2.0


class DrainTimeout(RuntimeError):
    """``drain(max_ticks=...)`` hit its cap with requests still in flight.

    Carries the abandoned work: ``unfinished`` is the list of live
    :class:`Request` handles (queued + active) at the moment the cap hit,
    so a caller can requeue, shed or report them instead of silently
    losing whatever the return value didn't include."""

    def __init__(self, max_ticks: int, unfinished: list):
        states = collections.Counter(r.state for r in unfinished)
        super().__init__(
            f"drain exceeded {max_ticks} ticks with {len(unfinished)} "
            f"unfinished request(s) ({dict(states)}) — rids "
            f"{[r.rid for r in unfinished]}")
        self.max_ticks = max_ticks
        self.unfinished = unfinished


def _ready(wait, awaited) -> None:
    """Tell a ``*.wait`` span, as it begins, whether the bytes it reads
    back were already there (``ready`` 1: the wait is a copy; 0: the host
    goes to sleep on the device). A disabled recorder asks nothing."""
    if wait is not tracing.NO_SPAN:
        wait.set(ready=int(awaited.is_ready()))


@functools.cache
def _host_device():
    """The host's own CPU device, or ``None`` where JAX is held to another
    platform."""
    import jax

    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        return None


def _seed_key_data(seed: int, fold: int | None = None) -> np.ndarray:
    """``jax.random.key(seed)``'s key data (folded with ``fold``, where
    given), made on the host's CPU: on the accelerator the few integer
    operations would queue behind the tick in flight, and ``submit`` would
    wait a whole decode for two words (my chip run, PR 28: 1.7 ms of
    device idle a request)."""
    import jax

    with jax.default_device(_host_device()):
        key = jax.random.key(seed)
        if fold is not None:
            key = jax.random.fold_in(key, fold)
        return np.asarray(jax.random.key_data(key))


# What was built for K/V blocks alone, for blocks that live as long as their
# request or for one token a step is refused by name where the model declares
# another trait, rather than half-done: (trait, option) -> reason, the trait
# in the words that end "a model that ...". (``mesh``, a quantized
# ``cache_dtype`` and, but for window layers, ``adapters`` reach the model's
# own ``paged_serving`` and the pool, which refuse them in the same words.)
_RECURRENT = "has recurrent state"
_BLOCK_STEPS = "generates by diffusion over blocks"
_WINDOWS = "has window layers"
_HOST, _DRAFT = "host_cache_blocks", "draft_stages (speculative decoding)"
_ADAPTERS, _LINT = "adapters", "lint=True"
_GPT_REGISTRY = ("the analyzer's program registry (analysis/programs.py) "
                 "builds GPT's programs")
_REFUSALS = {
    (_RECURRENT, _HOST):
        "the host offload tier demotes prefix BLOCKS, and a block without "
        "the recurrent state that goes with it is no prefix",
    (_RECURRENT, _DRAFT):
        "a rejected draft token cannot be taken back out of the recurrent "
        "state without a snapshot of it",
    (_RECURRENT, _LINT): _GPT_REGISTRY,
    (_BLOCK_STEPS, _HOST):
        "the host offload tier demotes and uploads prefix blocks of any "
        "fill, and a row here is valid only with its whole block",
    (_BLOCK_STEPS, _DRAFT):
        "a tick already decides several positions at once, by the model's "
        "own confidence and not by a draft's proposals",
    (_BLOCK_STEPS, _LINT): _GPT_REGISTRY,
    (_WINDOWS, _HOST):
        "the host offload tier demotes prefix blocks, and a window layer "
        "has handed its share of a prefix back",
    (_WINDOWS, _DRAFT):
        "a rejected draft token's window blocks may already have been "
        "handed back behind it",
    (_WINDOWS, _ADAPTERS):
        "the LoRA bank rides GPT's wq / wv (models/lora.py)",
    (_WINDOWS, _LINT): _GPT_REGISTRY,
}


def _refuse(declared: dict, asked: dict) -> None:
    """Raise for the first option of ``asked`` (name -> whether it was
    asked for) that :data:`_REFUSALS` holds against a trait the model has
    (``declared``: trait -> whether it has it), in the order given."""
    for trait, has in declared.items():
        for option, on in asked.items():
            if has and on and (trait, option) in _REFUSALS:
                raise ValueError(
                    f"{option} is not available with a model that {trait}: "
                    f"{_REFUSALS[trait, option]}")


class InferenceEngine:
    """Continuous-batching serving over a single-device model build.

    ``stages``/``cfg``: a ``make_gpt_stages`` build (dense-MLP, unsharded —
    the ``make_cached_decoder`` restrictions). ``params`` overrides the
    stages' init weights (e.g. checkpoint-restored trees from
    ``Pipeline.unpack``); where the model's programs read a layout of their
    own (``PagedServing.serve_params``) the engine makes it of either, once,
    and ``self.params`` is that. ``max_len`` caps each slot's prompt+generation
    budget (defaults to ``cfg.seq_len``); ``cache_dtype`` is the pool's
    storage dtype (bf16 halves pool memory, the ``storage_dtype`` rule).

    Pool knobs: ``block_size`` positions per K/V block; ``n_blocks`` pool
    capacity (default ``n_slots * ceil(max_len/block_size)``: every slot
    can reach ``max_len`` — shrink it to serve more slots than the memory
    could back at full length); ``prefill_chunk`` prompt positions
    per prefill chunk (``None`` = the whole remaining prompt in one chunk);
    ``attn_kernel`` the decode/verify attention path — ``"dense"``
    (gather-then-dense, the parity anchor) or ``"fused"`` (the Pallas
    paged-attention kernel: block gather + online-softmax attention in one
    HBM pass, ``ops/paged_attention.py``; greedy token streams stay
    bit-exact vs ``"dense"``). A QUANTIZED ``cache_dtype`` (``"int8"``, or
    fp8 where the jnp build has it) stores paged blocks narrow with
    per-row f32 scales (``models/serving.py::QuantKV``): roughly 3.6x more
    resident requests per byte than f32 at pinned-tolerance logits, with
    dequantize fused into both attention paths. ``host_cache_blocks > 0``
    enables the LRU host-RAM offload tier (evicted prefix blocks demote to
    host; a router affinity hit on a host-resident prefix starts an async
    upload landing after ``prefetch_ticks`` ticks — ``serve/slots.py``
    "Host offload tier").

    Tensor parallelism: build ``cfg`` with ``n_tensor_parallel = tp > 1``
    (the stages stay the UNSHARDED dense build) and pass a ``mesh`` whose
    ``model`` axis is exactly ``tp``. The engine slices the dense weights
    into the Megatron serving layout (``pack_tp_serve_params``) and places
    the K/V pool sharded over its HEAD axis, so every tick's compiled
    program runs head-sharded QKV/O + collective-matmul MLP over ``tp``
    chips and per-chip KV bytes drop by ``tp`` (the pool's
    ``serve_kv_bytes_resident`` gauge reports PER-SHARD bytes).

    Speculative decoding: pass ``draft_stages``/``draft_cfg`` (a smaller
    dense single-device build sharing the target's vocab) and
    ``spec_k >= 2``. Each tick then runs ONE draft propose scan plus ONE
    batched target verify instead of a one-token decode, emitting 1..
    ``spec_k`` tokens per slot; greedy requests stay bit-exact vs their
    solo decode (the models/gpt.py speculative-section contract). The
    draft keeps its own K/V buffers, one contiguous row per slot, and its
    own per-request key stream.

    Multi-tenant adapters: pass ``adapters`` (a
    :class:`~.adapters.AdapterStore` built for this engine's ``n_slots``)
    and every decode-path program is built with trailing adapter-bank
    args — each slot gathers its adapter's low-rank rows by a per-slot
    index, so one compiled program serves any adapter mix per tick and a
    hot-swap never retraces. ``submit(..., adapter="tenant")`` pins a
    request to a registered adapter; ``adapter=None`` rides bank row 0
    (the all-zero base row — its stream is identical to an engine with
    no adapter subsystem). The admission gate uploads/refcounts bank
    rows at tick boundaries; the paged prefix cache is namespaced per
    adapter so tenants can never share K/V computed under a different
    model.

    Recurrent state: a ``cfg`` with ``recurrent_state`` (a hybrid of
    state-space and attention layers) serves through the same paged pool,
    chunked prefill and ``attn_kernel`` — its per-slot state buffers live
    in the pool beside the blocks. It binds with no prefix match and
    registers none; preemption, journal recovery and fleet handoff
    recompute ``resume_seq`` from position 0, which rebuilds the state.
    What is built for K/V blocks alone is REFUSED at construction with
    such a model, by name: ``host_cache_blocks``,
    ``draft_stages`` (speculation), ``adapters``, ``mesh`` (tensor
    parallelism), ``lint=True`` (the analyzer's registry builds GPT's
    programs) and a quantized ``cache_dtype``.

    Block steps: a ``cfg`` whose ``paged_serving`` gives ``block = B > 1``
    (generation by diffusion over blocks) serves through the same pool,
    chunked prefill and dispatch ahead; ``submit(..., denoising_steps=)``
    sets a request's steps (1..B, default ``cfg.denoising_steps``).
    ``prefill_chunk``, ``block_size`` and ``max_len`` must be multiples of
    ``B``. Prefix sharing is KEPT, at whole pool blocks (a row depends on
    the tokens up to the end of its block of ``B``). A request preempted or
    restored mid-block starts that block again from masks; its committed
    tokens stay. Refused by name: the same six options.

    A model whose attention layers are of several KINDS
    (``PagedServing.windows``, ``models/cohere2.py``: window layers beside
    full ones) is served by the pool's groups (``serve/slots.py``, "Layer
    kinds"): ``n_window_blocks`` is a window group's block count (default:
    every slot can hold its window, a prefill chunk and one block more),
    the programs are handed every group's table side by side
    (``pool.device_table``), the blocks of a program's rows are asked for
    with the oldest of them (``ensure_writable(oldest=)``: what lies behind
    that query's window is handed back first), and every ``engine.tick``
    span carries ``kv_window_positions``, ``kv_window_blocks`` and
    ``kv_full_blocks``, ``engine.admit`` ``window_released``. Refused by
    name ("window layers"): the host tier, drafts, adapters, ``lint=True``
    here, ``mesh`` and a quantized ``cache_dtype`` by the model and the
    pool; no prefix is shared.
    """

    def __init__(self, stages, cfg, *, params=None, n_slots: int = 4,
                 max_len: int | None = None, cache_dtype=None,
                 block_size: int = 16,
                 n_blocks: int | None = None, prefill_chunk: int | None = None,
                 n_window_blocks: int | None = None,
                 host_cache_blocks: int = 0, prefetch_ticks: int = 1,
                 attn_kernel: str = "dense",
                 metrics: ServeMetrics | None = None,
                 scheduler: FCFSScheduler | None = None,
                 clock=time.monotonic, lint: bool = False,
                 mesh=None, draft_stages=None, draft_cfg=None,
                 spec_k: int = 0, trace=None, flight=None,
                 adapters=None) -> None:
        if attn_kernel not in ("dense", "fused"):
            raise ValueError(
                f"attn_kernel must be 'dense' (gather-then-dense "
                f"attention) or 'fused' (the Pallas paged-attention "
                f"kernel), got {attn_kernel!r}")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1 (or None for whole-prompt "
                f"chunks), got {prefill_chunk}")
        if (draft_stages is None) != (draft_cfg is None):
            raise ValueError(
                "speculative decoding needs BOTH draft_stages and "
                "draft_cfg (the draft build's config)")
        if draft_stages is not None and spec_k < 2:
            raise ValueError(
                f"speculative decoding needs spec_k >= 2 (got {spec_k}); "
                f"spec_k=1 is plain one-token decode — drop the draft")
        if draft_stages is None and spec_k:
            raise ValueError(
                f"spec_k={spec_k} without draft_stages/draft_cfg — the "
                f"draft model is what proposes the speculated tokens")
        if adapters is not None and adapters.n_rows != n_slots + 1:
            raise ValueError(
                f"AdapterStore has {adapters.n_rows} bank rows but this "
                f"engine needs n_slots + 1 = {n_slots + 1} (base row + one "
                f"per slot — the never-refuse sizing)")
        asked = {_HOST: bool(host_cache_blocks),
                 _DRAFT: draft_stages is not None,
                 _ADAPTERS: adapters is not None, _LINT: bool(lint)}
        _refuse({_RECURRENT: cfg.recurrent_state}, asked)
        self._adapters = adapters
        self.cfg = cfg
        self.stages = stages       # kept for the analyzer's program registry
        self.attn_kernel = attn_kernel
        self.prefill_chunk = prefill_chunk
        self.params = (params if params is not None
                       else [s.params for s in stages])
        self.max_len = int(max_len if max_len is not None else cfg.seq_len)
        self.tp = int(cfg.n_tensor_parallel)
        self.mesh = mesh if self.tp > 1 else None
        self.spec_k = int(spec_k)
        self.speculative = draft_stages is not None
        self.draft_stages = draft_stages   # for the analyzer's registry
        self.draft_cfg = draft_cfg
        adp = adapters is not None
        # the model's cache layout and its two programs (models/serving.py
        # ::PagedServing); everything below the pool is the model's
        serving = cfg.paged_serving(
            stages, self.max_len, block_size, cache_dtype, mesh=mesh,
            kernel=attn_kernel, adapters=adp)
        # the layout the programs read, where it is not the stages' own
        # (PagedServing.serve_params): made once, here
        if serving.serve_params is not None:
            self.params = serving.serve_params(self.params)
        self._n_layers = serving.kv_layers
        # positions a slot's step works on (PagedServing.block)
        self._block = int(serving.block)
        _refuse({_WINDOWS: any(w is not None for w in serving.windows),
                 _BLOCK_STEPS: self._block > 1}, asked)
        if self._block > 1:
            if prefill_chunk is not None and prefill_chunk % self._block:
                raise ValueError(
                    f"prefill_chunk={prefill_chunk} must be a multiple of "
                    f"the model's block of {self._block} positions: a chunk "
                    f"holds whole blocks")
        self._block_forwards = serving.block_forwards
        self._unpack_block = serving.unpack_rows
        # what a decode run counts of itself (PagedServing.counters): the
        # names, and the last run's counts (engine.tick's attrs)
        self._counter_names = tuple(serving.counters)
        self._counted = dict.fromkeys(self._counter_names, 0)
        # of the slots of the decode this tick read, those that sample
        # (temperature > 0): where 0 the programs' sampler took its argmax
        # branch and sorted nothing (models/serving.py::sample_slots)
        self._sampling = 0
        # the summed lengths of those slots: the K/V positions a layer of
        # that decode read
        self._kv_positions = 0
        # and, of those, what a window layer read: each slot's length or
        # the window, whichever is less (0 without a window group)
        self._kv_window_positions = 0
        self.pool = PagedKVPool(self._n_layers, n_slots, serving.kv_heads,
                                self.max_len, serving.head_dim, cache_dtype,
                                block_size=block_size, n_blocks=n_blocks,
                                tp=self.tp,
                                host_cache_blocks=host_cache_blocks,
                                prefetch_ticks=prefetch_ticks,
                                state_shapes=serving.state_shapes,
                                recurrent=cfg.recurrent_state,
                                step_rows=self._block,
                                windows=serving.windows,
                                n_window_blocks=n_window_blocks,
                                chunk_rows=prefill_chunk,
                                value_lanes=serving.value_lanes)
        # the pool's narrowest window group, where it has one: what the
        # tick's window counts are of
        self._window_group = (self.pool.window_groups[0]
                              if self.pool.windowed else None)
        self._window_released = 0
        self._chunk_prefill = serving.chunk_prefill
        self._decode = serving.decode
        self._pack_chunk = serving.pack_chunk
        self._pack_decode = serving.pack_decode
        # the model's programs keep the newest tokens on the device
        # (PagedServing: its chunks are told what to seat): the tick is
        # _tick_ahead's, and _ahead the decode it has dispatched for the
        # next one. Not under speculation, whose tick reads the host's
        # tokens (the pair its chunks seat is then never read)
        self._dispatch_ahead = not self.speculative
        self._ahead = None
        # program runs launched in the engine's life (decode, chunk,
        # speculative and block programs alike): a run's number is on its
        # ``*.dispatch`` span and on the ``*.wait`` span that reads it
        self._runs = 0
        from simple_distributed_machine_learning_tpu.models.gpt import (
            make_paged_block_copy,
        )
        from simple_distributed_machine_learning_tpu.models.serving import (
            SEAT_NONE,
            SEAT_SAMPLE,
            is_quantized_dtype,
        )
        self._copy_block = make_paged_block_copy()
        self._seat_none, self._seat_sample = SEAT_NONE, SEAT_SAMPLE
        if self.speculative:
            if draft_cfg.vocab != cfg.vocab:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab} != target vocab "
                    f"{cfg.vocab} — the draft proposes target token ids")
            # the draft keeps one row per slot (no per-block scales), so a
            # quantized TARGET dtype falls back to f32 for the draft — the
            # draft cache is small by design, and its rows feed proposals
            # only (acceptance always re-scores on the target)
            from simple_distributed_machine_learning_tpu.models.gpt import (
                make_paged_spec_tick,
                make_paged_verify_step,
                make_slot_prefill,
                make_slot_propose,
            )
            self._draft_cache_dtype = (None if is_quantized_dtype(
                cache_dtype) else cache_dtype)
            self._draft_prefill = make_slot_prefill(
                draft_stages, draft_cfg, self.max_len,
                self._draft_cache_dtype)
            self._propose = make_slot_propose(
                draft_stages, draft_cfg, self.max_len, spec_k,
                self._draft_cache_dtype)
            if self.tp == 1:
                # single-device targets run the FUSED tick: one dispatch
                # per speculative tick, draft rows never leave the device
                self._spec_fused = make_paged_spec_tick(
                    stages, cfg, draft_stages, draft_cfg, self.max_len,
                    block_size, spec_k, cache_dtype, kernel=attn_kernel,
                    adapters=adp)
            else:
                # a TP target verifies in a shard_map program while the
                # draft stays replicated single-device — two dispatches
                self._spec_fused = None
                self._verify = make_paged_verify_step(
                    stages, cfg, self.max_len, block_size, spec_k,
                    cache_dtype, mesh=mesh, kernel=attn_kernel,
                    adapters=adp)
            self._draft_params = [s.params for s in draft_stages]
            self._init_draft_pool(n_slots)
        if self.tp > 1:
            self._place_tp(mesh)
        if scheduler is None:
            scheduler = FCFSScheduler(self.pool)
        elif not isinstance(scheduler, FCFSScheduler) and callable(scheduler):
            # a scheduler CLASS/factory: the pool is engine-built, so the
            # caller cannot construct the instance up front
            scheduler = scheduler(self.pool)
        self.scheduler = scheduler
        self.scheduler.attach(self)
        if lint:
            # preflight the EXACT compiled programs this engine just built
            # (analysis/programs.py registry: scatter-bounds over the
            # block/position contracts, donation flow through the tick,
            # retrace policy) — trace-only, no FLOPs; construction fails
            # loudly on any ERROR finding rather than serving corruptable
            # programs
            from simple_distributed_machine_learning_tpu.analysis.programs import (  # noqa: E501
                lint_engine,
            )
            report = lint_engine(self)
            if not report.ok():
                raise RuntimeError(
                    "InferenceEngine(lint=True): the serve-program "
                    "preflight found ERROR findings:\n" + report.format())
        self.metrics = metrics
        # request-scoped tracing (serve/tracing.py) and the tick flight
        # recorder (serve/flight.py): both None by default — the hot path
        # pays exactly one `is None` test per site when disabled, and the
        # trace recorder is only ever handed timestamps this engine
        # already read (never a fresh clock read), so enabling it cannot
        # perturb virtual-clock scenario numbers
        self.trace = trace
        self.flight = flight
        self._predict = None     # lazy (ServeSpec, predict_fn) for kv drift
        self._clock = clock
        # the engine's most recent clock reading — what trace events with
        # no clock read of their own (paged admission, preemption, crash)
        # are stamped with; updated at every site that reads the clock
        # anyway, NEVER by an extra read
        self._now = 0.0
        self._next_rid = 0
        self._tick_count = 0
        self.requests: dict[int, Request] = {}
        # rids admitted but not yet fully prefilled, admission order (the
        # chunked-prefill work queue)
        self._prefilling: collections.deque[int] = collections.deque()
        # per-request last-emit timestamps for TPOT accounting
        self._last_emit: dict[int, float] = {}
        # rids whose current prefetch-gate episode already traced a
        # ``gate`` row (trace-only bookkeeping; cleared on boarding)
        self._gated: set[int] = set()

    def _init_draft_pool(self, n_slots: int) -> None:
        """The draft model's K/V buffers: one ``max_len`` row per slot —
        the draft is small by design, so paging it buys nothing, and a
        rejected tail's rows are overwritten before they can be attended
        (the trailing-write argument, models/gpt.py's speculative
        section)."""
        import jax.numpy as jnp

        from simple_distributed_machine_learning_tpu.models.serving import (
            storage_dtype,
        )
        dcfg = self.draft_cfg
        dL = sum(len(p["blocks"]) for p in self._draft_params)
        ddh = dcfg.d_model // dcfg.n_heads
        cd = storage_dtype(self._draft_cache_dtype)
        shape = (dL, n_slots, dcfg.n_heads, self.max_len, ddh)
        self._dkc = jnp.zeros(shape, cd)
        self._dvc = jnp.zeros(shape, cd)

    def _place_tp(self, mesh) -> None:
        """Shard the serving state for the TP programs: the K/V pool
        buffers split over their head axis (per-chip KV drops by ``tp``),
        the per-slot state (newest tokens and keys) replicated, the dense
        stage weights sliced into the Megatron serving layout
        (``pack_tp_serve_params``) with block shards on the model axis and
        embed/head replicated. One placement at construction; donation
        keeps the pool buffers where they were placed across ticks."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from simple_distributed_machine_learning_tpu.models.gpt import (
            pack_tp_serve_params,
        )
        from simple_distributed_machine_learning_tpu.parallel.mesh import (
            MODEL_AXIS,
        )
        # the head axis is dim 2 in every pool leaf — a layer's
        # [n_blocks+1, bs, H*dh] (a shard's heads are contiguous lanes) AND
        # (for quantized pools) its QuantKV scale plane — so one spec
        # places the whole pytree per-shard
        cache_sh = NamedSharding(mesh, P(None, None, MODEL_AXIS))
        self.pool.kc = jax.tree.map(
            lambda leaf: jax.device_put(leaf, cache_sh), self.pool.kc)
        self.pool.vc = jax.tree.map(
            lambda leaf: jax.device_put(leaf, cache_sh), self.pool.vc)
        stacked, rep = pack_tp_serve_params(self.params, self.tp)
        blk_sh = NamedSharding(mesh, P(MODEL_AXIS))
        rep_sh = NamedSharding(mesh, P())
        self.pool.state = jax.tree.map(
            lambda leaf: jax.device_put(leaf, rep_sh), self.pool.state)
        self.params = (
            [jax.tree.map(lambda leaf: jax.device_put(leaf, blk_sh), bp)
             for bp in stacked],
            jax.tree.map(lambda leaf: jax.device_put(leaf, rep_sh), rep))

    # -- public API --------------------------------------------------------

    @property
    def busy(self) -> bool:
        return bool(self.scheduler.queue_depth or self.pool.n_active)

    def submit(self, prompt, max_new_tokens: int, temperature: float = 0.0,
               top_k: int | None = None, top_p: float | None = None,
               eos_id: int | None = None, seed: int | None = None,
               on_token=None, arrival_time: float | None = None,
               cls: str | None = None, priority: int = 0,
               ttft_deadline_s: float | None = None,
               deadline_s: float | None = None,
               adapter: str | None = None,
               denoising_steps: int | None = None) -> Request:
        """Enqueue one request; returns its live handle immediately.

        ``arrival_time`` backdates ``submit_time`` to when the request
        actually ARRIVED (the open-loop simulator's Poisson timestamp), so
        TTFT absorbs queue wait accrued while the engine was inside a tick
        — without it, arrival-to-submit wait would silently vanish from
        the headline latency exactly in the overload regime.

        ``ttft_deadline_s``/``deadline_s`` are stored on the handle; the
        serve SUPERVISOR enforces them at tick boundaries (an unsupervised
        engine is the no-deadline baseline)."""
        with tracing.span("engine.submit") as sp:
            # fault-injection site: a crash while the request is being
            # accepted (journaled by the supervisor but never admitted — the
            # recovery corner serve/supervisor.py re-admits from the journal
            # alone)
            maybe_fire("serve.admit", step=self._next_rid)
            prompt = np.asarray(prompt, np.int32)
            validate_request(prompt, max_new_tokens, temperature, top_k,
                             top_p, self.cfg.vocab, self.max_len)
            for name, v in (("ttft_deadline_s", ttft_deadline_s),
                            ("deadline_s", deadline_s)):
                if v is not None and v <= 0:
                    raise ValueError(f"{name} must be > 0, got {v}")
            self._check_adapter(adapter)
            steps = self._check_denoising_steps(denoising_steps)
            rid = self._next_rid
            self._next_rid += 1
            sp.set(rid=rid)
            if self._block > 1:
                # blocks the request will generate, the one its prompt's
                # remainder opens included
                sp.set(blocks=-(-(len(prompt) % self._block
                                  + max_new_tokens) // self._block))
            seed = rid if seed is None else seed
            r = Request(rid=rid, prompt=prompt,
                        max_new_tokens=max_new_tokens,
                        temperature=temperature, top_k=top_k, top_p=top_p,
                        eos_id=eos_id, seed=seed, on_token=on_token,
                        cls=cls, priority=priority,
                        ttft_deadline_s=ttft_deadline_s,
                        deadline_s=deadline_s, adapter=adapter,
                        block=self._block, denoising_steps=steps)
            if self._adapters is not None:
                # the version-qualified prefix-cache namespace (refreshed at
                # the admission gate — the probe and the decode must agree on
                # the adapter VERSION or a hot-swap could reuse stale K/V)
                r._prefix_ns = self._adapters.namespace_of(adapter)
            # the request's independent key stream — the SAME key a solo
            # make_cached_decoder call would be handed, so streams align
            r.key_data = _seed_key_data(seed)
            if self.speculative:
                # the draft's own stream, derived but disjoint (fold_in),
                # so sampled proposals never consume the target's splits —
                # greedy consumes neither, which is what keeps greedy
                # speculative decode bit-exact vs solo
                r.draft_key_data = _seed_key_data(seed, fold=1)
            r.submit_time = (self._clock() if arrival_time is None
                             else arrival_time)
            self._now = max(self._now, r.submit_time)
            self.requests[rid] = r
            self.scheduler.enqueue(r)
            if self.metrics is not None:
                self.metrics.on_submit()
            if self.trace is not None:
                self.trace.on_submit(r, r.submit_time)
            return r

    def _check_denoising_steps(self, steps: int | None) -> int:
        """A request's denoising steps: the model's default, 0 for a model
        whose step is one token."""
        if self._block == 1:
            if steps is not None:
                raise ValueError(
                    "denoising_steps is for a model that generates by "
                    "diffusion over blocks; this one emits a token a step")
            return 0
        steps = self.cfg.denoising_steps if steps is None else int(steps)
        if not 1 <= steps <= self._block:
            raise ValueError(
                f"denoising_steps must be in [1, {self._block}] (the "
                f"block's length), got {steps}")
        return steps

    # -- adapter plumbing --------------------------------------------------

    def register_adapter(self, name: str, weights: dict) -> None:
        """Add or hot-swap a named LoRA adapter (host-side only; the
        device row uploads at the next admission). Same call shape as
        :meth:`ServeSupervisor.register_adapter` /
        :meth:`ServeFleet.register_adapter`, so callers can target any
        serving tier uniformly."""
        if self._adapters is None:
            raise ValueError("this engine was built without an "
                             "AdapterStore — pass adapters= at "
                             "construction")
        self._adapters.register(name, weights)

    def _check_adapter(self, adapter: str | None) -> None:
        if adapter is None:
            return
        if self._adapters is None:
            raise ValueError(
                f"request names adapter {adapter!r} but this engine was "
                f"built without an AdapterStore — pass adapters= at "
                f"construction")
        if not self._adapters.is_registered(adapter):
            raise KeyError(
                f"adapter {adapter!r} is not registered "
                f"(known: {list(self._adapters.names())})")

    def _adapter_board(self, r: Request) -> bool:
        """The scheduler's admission gate: pin the request's adapter row
        (uploading at this tick boundary if needed) and take its ref.
        Structurally never refuses — the bank has one more row than the
        pool has slots, and admission already holds a free slot."""
        if getattr(r, "adapter", None) is None or self._adapters is None:
            r._adapter_row = 0
            return True
        # a hot-swap between submit and boarding changes the version this
        # admission will pin: refresh the prefix namespace (and drop the
        # stale probe memo) BEFORE bind_seq probes the registry, so the
        # K/V the request reuses was computed under the version it decodes
        ns = self._adapters.namespace_of(r.adapter)
        if getattr(r, "_prefix_ns", None) != ns:
            r._prefix_ns = ns
            r._prefix_probe = None
        r._adapter_row = self._adapters.retain(r.adapter)
        return True

    def _adapter_release(self, r: Request) -> None:
        row = getattr(r, "_adapter_row", 0)
        if row and self._adapters is not None:
            self._adapters.release(row)
        r._adapter_row = 0

    def _adapter_inputs(self, active: list[int]) -> np.ndarray:
        """Per-slot adapter row indices for a batched tick — the same
        discipline as :meth:`_sampling_inputs` (inactive slots gather the
        zero base row, whose delta is exactly 0)."""
        aids = np.zeros(self.pool.n_slots, np.int32)
        for s in active:
            r = self.requests[self.pool.occupant(s)]
            aids[s] = getattr(r, "_adapter_row", 0)
        return aids

    def _bank_args(self, aids) -> tuple:
        """The trailing ``(bank, aids)`` program args — empty without a
        store, so every call site stays a one-splat edit."""
        if self._adapters is None:
            return ()
        return (self._adapters.bank, aids)

    def step(self) -> int:
        """One tick; returns the number of tokens emitted. A true no-op
        returning 0 when idle — idle ticks touch no metrics, so a polling
        loop cannot drag the occupancy histogram toward zero.

        A tick: admit (board slots, match prefixes, reserve blocks) -> ONE
        prefill chunk of the oldest prefilling request -> batched
        block-gather decode over the DECODING slots -> retire; the decode
        comes first where the model's programs keep the newest tokens on
        the device (:meth:`_tick_ahead`).
        """
        if not self.busy:
            return 0
        # the tick by phase (telemetry/tracing.py): its work lies in child
        # spans, so that host time has a cause. Left in none, each a walk
        # over the slots at most: the fault-injection probe, who decodes
        # now and next (``_decoding_slots``, ``_seats_ahead``) and the
        # attributes set at the end (``_state_slots``). A stall there is
        # the tick's own (its ``cpu_ns`` / ``runq_ns`` less its waits'),
        # between the two children around it
        with tracing.span("engine.tick", sched=True,
                          tick=self._tick_count + 1) as sp:
            return self._tick(sp)

    def _tick(self, sp) -> int:
        """The body of a busy :meth:`step` under its ``engine.tick`` span
        ``sp``, which is told at the end what the tick did."""
        # fault-injection site (resilience/faults.py): slow-tick stalls the
        # tick (a degraded device), wedged-device raises DeviceWedged —
        # no-op without an installed plan
        maybe_fire("serve.tick", step=self._tick_count)
        self._tick_count += 1
        runs = self._runs
        with tracing.span("engine.admit") as admit:
            # host-tier upload progress FIRST: blocks completing this
            # tick register before admission probes the prefix registry,
            # so a request blocked on its own prefetch boards this very
            # tick
            self.pool.advance_transfers()
            if self.trace is not None and self.pool._inflight:
                # trace the upload gate: a queued request held back by
                # its own in-flight prefetch gets ONE ``gate`` row per
                # episode (attribution's queue-vs-prefetch split).
                # Stamped with the most recent clock read, like
                # admission — and only probed while uploads are actually
                # in flight, so the common path pays one attribute test
                for r in self.scheduler.queue:
                    if (r.rid not in self._gated
                            and self.pool.prefetch_blocked(r)):
                        self._gated.add(r.rid)
                        self.trace.on_gate(r, self._now)
            declined = self.pool.prefix_declined_total
            admit.set(boarded=self._admit(),
                      prefix_declined=(self.pool.prefix_declined_total
                                       - declined))
            if self._window_group is not None:
                # blocks handed back behind a window since the last tick's
                # admission (the hand-backs happen where a program's rows
                # are allocated: counted here, a tick late)
                released = self.pool.window_released_total
                admit.set(window_released=released - self._window_released)
                self._window_released = released
        chunk = int(bool(self._prefilling))
        ahead = 0
        if self._dispatch_ahead:
            emitted, decode_active, ahead = self._tick_ahead()
        else:
            emitted = self._prefill_tick()
            # occupancy the batched decode actually RUNS at — sampled
            # before same-tick retirement so short requests cannot bias it
            # low
            decoding = self._decoding_slots()
            decode_active = len(decoding)
            emitted += (self._spec_tick(decoding) if self.speculative
                        else self._decode_tick(decoding))
        if self.metrics is not None or self.flight is not None:
            with tracing.span("engine.bookkeeping"):
                if self.metrics is not None:
                    live, predicted = self.kv_drift()
                    self.metrics.on_tick(
                        self.scheduler.queue_depth, self.pool.n_active,
                        self.pool.n_slots, decode_active=decode_active,
                        block_stats=self.pool.stats(),
                        tp=self.tp, spec_k=self.spec_k,
                        kv_predicted=predicted, kv_drift=live - predicted,
                        attn_kernel=self.attn_kernel,
                        adapter_stats=(self._adapters.stats()
                                       if self._adapters is not None
                                       else None))
                if self.flight is not None:
                    self.flight.snap(self, self._tick_count, emitted)
        sp.set(chunk=chunk, decoding=decode_active, emitted=emitted,
               ahead=ahead, queue=self.scheduler.queue_depth,
               runs=self._runs - runs, state_slots=self._state_slots(),
               kv_blocks=self.pool.blocks_in_use,
               sampling=self._sampling if decode_active else 0,
               kv_positions=self._kv_positions if decode_active else 0)
        if self._window_group is not None:
            # what ONE window layer and ONE full layer hold at the tick's
            # end, and what the window layer's decode read
            sp.set(kv_window_positions=(self._kv_window_positions
                                        if decode_active else 0),
                   kv_window_blocks=self._window_group.blocks_in_use,
                   kv_full_blocks=self.pool.blocks_in_use)
        if self._counter_names:
            # what the tick's decode run counted (0 where it ran none)
            sp.set(**(self._counted if decode_active
                      else dict.fromkeys(self._counter_names, 0)))
        return emitted

    def _state_slots(self) -> int:
        """Slots whose recurrent state is live: decoding, or past their
        first prefill chunk (a slot bound and still waiting for its chunk
        turn holds nothing of its own yet). 0 for a model without."""
        if not self.pool.recurrent:
            return 0
        return sum(1 for s in self.pool.active_slots()
                   if self.requests[self.pool.occupant(s)].prefill_pos != 0)

    def kv_drift(self) -> tuple[int, int]:
        """``(live, predicted)`` resident K/V bytes: the pool's
        ``serve_kv_bytes_resident`` gauge next to the PR-8 analyzer's
        ``predict_kv_bytes_resident`` over the live sequences' written-row
        counts — the static model checked as a RUNTIME invariant every
        tick. ``live - predicted`` is the drift gauge: exactly 0 without
        prefix sharing, ≤ 0 with it (sharing only shrinks the truth), and
        > 0 only if the pool leaks blocks the model says no live sequence
        can be pinning. A slot of the decode already dispatched for the
        next tick (:meth:`_tick_ahead`) has that position's row allocated:
        it counts one more than the host has emitted."""
        in_flight = self._ahead[0] if self._ahead else ()
        rows = []
        for s in self.pool.active_slots():
            r = self.requests[self.pool.occupant(s)]
            if r.prefill_pos is not None:
                n = r.prefill_pos
            elif self._block > 1:
                # a block's rows are reserved from its first forward on
                n = int(self.pool.positions[s]) + self._block * (
                    r.rid in in_flight or self.pool.block_fwd[s] > 0)
            else:
                n = int(self.pool.positions[s]) + (r.rid in in_flight)
            if n > 0:
                rows.append(n)
        if self.pool.recurrent or self._block > 1 or self.pool.windowed:
            # the analyzer's model is GPT's (one head count, every layer
            # attends); here the pool's own block bytes make the prediction
            # (a window group's: the most its ring holds, so the drift is
            # at most 0 there)
            return (self.pool.bytes_resident(),
                    sum(self.pool.bytes_for_rows(n) for n in rows))
        if self._predict is None:
            from simple_distributed_machine_learning_tpu.analysis.programs import (  # noqa: E501
                engine_spec,
                predict_kv_bytes_resident,
            )
            # the SAME engine->spec mapping the lint preflight uses, so
            # the drift check can never describe a different deployment
            self._predict = (engine_spec(self), predict_kv_bytes_resident)
        sspec, predict = self._predict
        return (self.pool.bytes_resident(),
                predict(sspec, rows, n_layers=self._n_layers))

    def preempt(self, rid: int) -> None:
        """Evict an ACTIVE request from its slot (priority scheduling's
        room-making — ``PriorityScheduler._make_room``): the slot and its
        K/V blocks free NOW, the request returns to the queue front with
        its emitted tokens intact. Re-admission recomputes K/V for
        ``resume_seq`` (registered prefix blocks usually make that cheap)
        and reseats on the stored last token with the key stream untouched,
        so the continued decode is bit-exact vs an unpreempted run.

        Compile-cost note: an engine with ``prefill_chunk=None`` prefills
        whole sequences, retracing per distinct length — every distinct
        preemption point is a fresh XLA compile. Preemption-heavy serving
        should set a ``prefill_chunk``, which bounds prefill shapes to
        chunk sizes the engine has already compiled."""
        r = self.requests[rid]
        if r.state != ACTIVE or r.slot is None:
            raise ValueError(
                f"request {rid} is not active (state {r.state!r}, slot "
                f"{r.slot!r}) — only active requests preempt")
        try:
            self._prefilling.remove(rid)   # may be mid-prefill
        except ValueError:
            pass
        self.pool.unbind_seq(r.slot)
        self.pool.release(r.slot)
        self._adapter_release(r)   # re-acquired (maybe a new row) on re-admit
        r.slot = None
        r.prefill_pos = None
        r.state = QUEUED
        r.n_preempted += 1
        # front of the queue: the victim arrived before anything still
        # waiting in its own class (pick() is priority-then-FCFS, so this
        # only orders it within its class)
        self.scheduler.queue.appendleft(r)
        if self.metrics is not None:
            self.metrics.on_preempt(r.cls)
        if self.trace is not None:
            self.trace.on_preempt(r, self._now)

    def cancel(self, rid: int, reason: str = "cancelled") -> Request:
        """Remove a live request NOW with a structured rejection: a queued
        request leaves the queue, an active one frees its slot, decrefs
        its table blocks and returns its unused reservation — the
        full budget refund, same release path as retirement — and the
        handle lands in ``SHED`` with ``finish_reason = reason``. The
        supervisor's deadline/overload shedding calls this; metrics
        accounting is the CALLER's job (it knows the set of reasons)."""
        r = self.requests[rid]
        if r.state not in (QUEUED, ACTIVE):
            raise ValueError(
                f"request {rid} is {r.state!r} — only queued/active "
                f"requests cancel")
        if r.state == ACTIVE:
            try:
                self._prefilling.remove(rid)    # may be mid-prefill
            except ValueError:
                pass
            self.pool.unbind_seq(r.slot)
            self.pool.release(r.slot)
            self._adapter_release(r)
            r.slot = None
            r.prefill_pos = None
        else:
            # identity scan, not deque.remove: Request's dataclass __eq__
            # would compare prompt arrays between same-rid duplicates
            for i, q in enumerate(self.scheduler.queue):
                if q is r:
                    del self.scheduler.queue[i]
                    break
            else:               # pragma: no cover - state-machine guard
                raise RuntimeError(
                    f"queued request {rid} missing from the scheduler "
                    f"queue — lifecycle bookkeeping corrupted")
        r.state = SHED
        r.finish_reason = reason
        r.done_time = self._now = self._clock()
        self._last_emit.pop(rid, None)
        if self.trace is not None:
            self.trace.on_shed(r, r.done_time, reason)
        return r

    def restore(self, request: Request) -> Request:
        """Re-admit a journal-recovered request into THIS engine (the serve
        supervisor's rebuild path): the handle keeps its rid, emitted
        tokens and live key stream, re-enters the queue and — exactly like
        a PR-7 preemption victim — re-prefills ``resume_seq`` on boarding
        with the sample and key advance discarded, reseating on its stored
        newest token, so the continued decode is bit-exact vs the
        uninterrupted run. Callers re-admit in rid order to preserve FCFS
        arrival order across the restart."""
        if request.rid in self.requests:
            raise ValueError(f"request {request.rid} already lives in this "
                             f"engine — restore() is for rebuilt engines")
        validate_request(request.prompt, request.max_new_tokens,
                         request.temperature, request.top_k, request.top_p,
                         self.cfg.vocab, self.max_len)
        self._check_adapter(getattr(request, "adapter", None))
        request.block = self._block
        request.denoising_steps = self._check_denoising_steps(
            request.denoising_steps or None)
        request.state = QUEUED
        request.slot = None
        request.prefill_pos = None
        request._adapter_row = 0   # re-acquired at boarding on THIS engine
        request._prefix_ns = (
            None if self._adapters is None
            else self._adapters.namespace_of(
                getattr(request, "adapter", None)))
        request._prefix_probe = None   # probed against THIS pool's registry
        if request.key_data is None:
            # never emitted a token: the stream starts where submit's would
            request.key_data = _seed_key_data(request.seed)
        if self.speculative and request.draft_key_data is None:
            request.draft_key_data = _seed_key_data(request.seed, fold=1)
        self.requests[request.rid] = request
        self._next_rid = max(self._next_rid, request.rid + 1)
        self.scheduler.enqueue(request)
        if self.trace is not None:
            self.trace.on_readmit(request, self._now)
        return request

    def drain(self, max_ticks: int | None = None) -> list[Request]:
        """Tick until idle (or ``max_ticks``); returns finished requests in
        completion order is not guaranteed — use ``handle.tokens``.

        Hitting the cap with work still in flight raises
        :class:`DrainTimeout` carrying the unfinished request handles —
        abandoned requests are a loud, structured signal, never a
        silently shorter return value (tests/test_serve.py pins it)."""
        ticks = 0
        while self.busy:
            if max_ticks is not None and ticks >= max_ticks:
                raise DrainTimeout(max_ticks, [
                    r for r in self.requests.values()
                    if r.state in (QUEUED, ACTIVE)])
            self.step()
            ticks += 1
        return [r for r in self.requests.values() if r.state == DONE]

    # -- tick internals ---------------------------------------------------

    def _admit(self) -> int:
        """Board waiting requests. The scheduler's admit loop already bound
        each sequence to its slot (prefix matched, shared blocks
        referenced, worst-case budget reserved — ``PagedKVPool.bind_seq``)
        and parked the first position to compute in ``r.prefill_pos``. No
        model FLOPs here — prefill happens chunk by chunk in
        :meth:`_prefill_tick`. Returns how many boarded."""
        boarded = 0
        for r in self.scheduler.admit():
            boarded += 1
            self._prefilling.append(r.rid)
            self._gated.discard(r.rid)
            if self.trace is not None:
                # boarding performs no clock read; stamped with the most
                # recent one (at most a tick stale, see serve/tracing.py)
                self.trace.on_admit(r, self._now, r.slot)
        return boarded

    def _prefill_tick(self) -> int:
        """At most ONE prefill chunk per tick — the scheduler's budget that
        keeps a long prompt from stalling every decode tick. Processes the
        oldest still-prefilling request (FCFS, matching admission order);
        the final chunk samples the request's first token (TTFT endpoint)
        and registers its prompt blocks for future prefix sharing."""
        chunk = self._prefill_dispatch()
        return 0 if chunk is None else self._prefill_finish(chunk)

    def _prefill_dispatch(self):
        """Launch the tick's prefill chunk, if a request is prefilling:
        what :meth:`_prefill_finish` needs to read it back and account
        it, else ``None``."""
        if not self._prefilling:
            return None
        r = self.requests[self._prefilling[0]]
        seq = r.resume_seq           # == r.prompt unless resuming preempted
        if self._block > 1:
            seq, opening = self._block_prefill_seq(seq)
        plen = int(seq.shape[0])
        p0 = r.prefill_pos
        c = (plen - p0 if self.prefill_chunk is None
             else min(self.prefill_chunk, plen - p0))
        with tracing.span("engine.prefill.prepare", rid=r.rid, p0=p0, n=c):
            t_start = self._now = self._clock()
            self._ensure_writable_range(r.slot, p0, c)
            if self._block > 1:
                # the last chunk seats the slot's first block: the
                # sequence's remainder fixed, the rest masked
                seat = np.concatenate([
                    [self._seat_none if p0 + c < plen else len(opening)],
                    opening, np.zeros(self._block - len(opening), np.int32)
                ]).astype(np.int32)
            else:
                # what the chunk leaves as the slot's newest token on the
                # device: nothing mid-prompt, its own sample, or a resumed
                # request's stored one (as _prefill_emit seats the host's)
                seat = np.int32(
                    self._seat_none if p0 + c < plen else
                    r.tokens[-1] if r.tokens else self._seat_sample)
            args = (
                seq[None, p0:p0 + c], np.int32(p0),
                self.pool.device_table(r.slot),
                # the chunk is told whose rows of the state these are
                np.int32(r.slot), seat,
                r.key_data, np.float32(r.temperature),
                np.int32(r.top_k if r.top_k is not None else _NO_TOP_K),
                np.float32(r.top_p if r.top_p is not None else _NO_TOP_P),
                *self._bank_args(np.int32(getattr(r, "_adapter_row", 0))))
        run = self._next_run()
        with tracing.span("engine.prefill.dispatch", rid=r.rid, run=run,
                          program="chunk"):
            tok, kd = self._run_paged(self._chunk_prefill,
                                      self._pack_chunk, *args)
        return r, seq, p0, c, t_start, tok, kd, run

    def _prefill_finish(self, chunk) -> int:
        r, seq, p0, c, t_start, tok, kd, run = chunk
        with tracing.span("engine.prefill.wait", sched=True, rid=r.rid,
                          run=run) as wait:
            _ready(wait, tok)
            tok = int(np.asarray(tok))     # host sync: honest chunk timing
        with tracing.span("engine.prefill.emit", rid=r.rid):
            return self._prefill_emit(r, seq, p0, c, t_start, tok, kd)

    def _prefill_emit(self, r: Request, seq, p0: int, c: int,
                      t_start: float, tok: int, kd) -> int:
        """Host-side tail of a prefill chunk: account it and, after the
        final chunk, publish the prefix, emit the first token and seat the
        request for decode (or finish it)."""
        plen = int(seq.shape[0])
        now = self._now = self._clock()
        if self.metrics is not None:
            self.metrics.on_prefill_chunk((now - t_start) * 1e3)
        if self.trace is not None:
            self.trace.on_prefill_chunk(r, t_start, now, p0, c)
        if p0 + c < plen:
            # mid-prompt chunk: the sampled token AND returned key are
            # discarded — the request's key stream advances exactly once,
            # at the final chunk, where its solo decode would split too
            r.prefill_pos = p0 + c
            return 0
        self._prefilling.popleft()
        r.prefill_pos = None
        # publish the sequence's blocks BEFORE any same-tick retirement so
        # even a 1-token request leaves its prefix reusable (cached blocks
        # survive end_seq as reclaimable)
        if self._block > 1:
            # no token yet: the slot's first block is seated (on the
            # device by the chunk), masked but for the sequence's remainder
            full = r.resume_seq
            n_open = len(full) % self._block
            self.pool.register_prefix(r.slot, full[:len(full) - n_open])
            self._seat_block(r, len(full) - n_open, n_open)
            if r.tokens and self.trace is not None:
                self.trace.on_resume(r, now)
            return 0
        self.pool.register_prefix(r.slot, seq)
        if self.speculative:
            # the draft prefills the WHOLE sequence in one shot at the
            # final target chunk: its cache must cover every prompt
            # position before the first propose scan, and the draft is
            # cheap by design (no chunking needed)
            self._draft_prefill_slot(r, seq)
        if r.tokens:
            # resuming after preemption: the final chunk only rebuilt K/V;
            # its sample and advanced key are discarded like a mid-prompt
            # chunk's (the stream already consumed this split before the
            # preemption) and decode restarts from the stored newest token.
            # TPOT base resets to NOW deliberately: the stall is preemption
            # wait, tracked by the preemption counters (and the
            # request-level tpot_s mean), not decode cadence — one giant
            # sample would distort the per-class cadence histogram the SLO
            # gate reads
            self.pool.seat(r.slot, plen, r.tokens[-1])
            self._last_emit[r.rid] = now
            if self.trace is not None:
                self.trace.on_resume(r, now)
            return 0
        r.key_data = np.asarray(kd)
        r.first_token_time = now
        self._last_emit[r.rid] = now
        r.emit(tok)
        if self.metrics is not None:
            self.metrics.on_first_token(r.ttft_s, cls=r.cls)
        if self.trace is not None:
            self.trace.on_first_token(r, now)
        reason = r.finished_by(tok)
        if reason is not None:
            self._finish(r, reason, now)
        else:
            self.pool.seat(r.slot, plen, tok)
        return 1

    def _block_prefill_seq(self, seq):
        """What a block-step model's chunks run over, and the tokens that
        open its first block: ``seq``'s whole blocks and its remainder. A
        sequence shorter than one block runs its chunk over that block
        itself, masks and all (rows the first forward overwrites): a slot
        is seated by a chunk."""
        B = self._block
        whole = len(seq) - len(seq) % B
        opening = seq[whole:]
        if whole:
            return seq[:whole], opening
        return np.concatenate([opening, np.full(
            B - len(opening), self.cfg.mask_id, np.int32)]), opening

    def _seat_block(self, r: Request, position: int, n_open: int) -> None:
        """``r``'s slot starts a block at ``position`` with ``n_open``
        tokens already fixed: the forwards it takes follow from the
        request's schedule."""
        self.pool.seat_block(r.slot, position, 1 + self._block_forwards(
            self._block, r.denoising_steps, self._block - n_open))

    def _next_run(self) -> int:
        self._runs += 1
        return self._runs

    def _run_paged(self, program, pack, *args):
        """Call one of the model's two paged programs (its host-side
        arguments through the model's ``pack``, where it has one) and take
        the donated pool buffers back, and the per-slot state buffers
        that ride beside them."""
        pool = self.pool
        if pack is not None:
            args = pack(*args)      # the model takes them as one transfer
        pool.kc, pool.vc, pool.state, tok, kd = program(
            self.params, pool.kc, pool.vc, pool.state, *args)
        # start the read-back now: the copies are queued behind the program,
        # and the ``*.wait`` that follows finds the bytes on the host
        tok.copy_to_host_async()
        kd.copy_to_host_async()
        return tok, kd

    def _decoding_slots(self) -> list[int]:
        """Occupied slots whose request finished prefilling — the batched
        decode's participants this tick (still-prefilling slots sit out)."""
        return [s for s in self.pool.active_slots()
                if self.requests[self.pool.occupant(s)].prefill_pos is None]

    def _decode_tick(self, active: list[int]) -> int:
        if not active:
            return 0
        return self._emit_tick(*self._decode_dispatch(
            [(s, int(self.pool.positions[s])) for s in active]))

    def _emit_tick(self, active: list[int], out, kd2, run: int,
                   sampling: int, kv_positions: int,
                   kv_window_positions: int) -> int:
        """Read one decode back (run ``run``, ``sampling`` of whose slots
        sample, over ``kv_positions`` cached positions,
        ``kv_window_positions`` of them inside a window layer's window)
        and account it: a token a slot, or (block steps) a forward a
        slot."""
        self._sampling = sampling
        self._kv_positions = kv_positions
        self._kv_window_positions = kv_window_positions
        emit = self._emit_block if self._block > 1 else self._emit_decoded
        return emit(active, out, kd2, run)

    def _decode_dispatch(self, seats: list[tuple[int, int]]):
        """Launch one decode over ``seats``, ``(slot, position)`` of every
        slot that takes part: ``(slots, tokens, key_data, run, sampling,
        kv_positions, kv_window_positions)`` as :meth:`_emit_tick` takes
        them, tokens and keys still on the device, ``sampling`` the slots
        among them whose temperature is above 0, ``kv_positions`` their
        lengths summed, the rows this step writes included, and
        ``kv_window_positions`` the same sum with each length cut to the
        pool's window (0 without a window group)."""
        S = self.pool.n_slots
        active = [s for s, _ in seats]
        with tracing.span("engine.decode.prepare"):
            kd, temps, top_ks, top_ps = self._sampling_inputs(active)
            # non-decoding slots: position 0 + all-trash table, so their
            # garbage write lands in the trash block no table references
            pos = np.zeros(S, np.int32)
            toks = np.zeros(S, np.int32)
            tables = np.full((S, self.pool.table_width),
                             PagedKVPool.TRASH, np.int32)
            for s, p in seats:
                # on-demand block allocation as this position advances (and
                # copy-on-write if the write block is still shared); a
                # block's rows are reserved before its first forward
                if (self._block == 1 or len(self.pool.tables[s])
                        * self.pool.block_size < p + self._block):
                    self._ensure_writable_range(s, p, self._block)
                tables[s] = self.pool.device_table(s)
                pos[s] = p
                toks[s] = self.pool.last_token[s]
            bank_args = self._bank_args(self._adapter_inputs(active))
            # the slots whose state this tick advances; the others'
            # (mid-prefill, seated and not yet decoding, free) must come
            # back unchanged
            live = (np.zeros(S, bool),)
            live[0][active] = True
            if self._block > 1:
                steps = np.ones(S, np.int32)
                for s in active:
                    steps[s] = self.requests[
                        self.pool.occupant(s)].denoising_steps
                live += (steps,)
        run = self._next_run()
        with tracing.span("engine.decode.dispatch", run=run,
                          program="decode"):
            toks2, kd2 = self._run_paged(
                self._decode, self._pack_decode, toks, pos, tables, *live,
                kd, temps, top_ks, top_ps, *bank_args)
        window = (self._window_group.window if self._window_group is not None
                  else 0)
        return (active, toks2, kd2, run, int(np.count_nonzero(temps > 0)),
                sum(p + self._block for _, p in seats),
                sum(min(p + self._block, window) for _, p in seats))

    def _tick_ahead(self) -> tuple[int, int]:
        """The paged tick over programs that keep the newest tokens on the
        device (``models/serving.py::PagedServing``): the decode FIRST, then
        the prefill chunk, whose slot decodes from the next tick on. In that
        order the next tick's decode needs nothing this tick has yet to
        read (its tokens are on the device, and who takes part follows
        from lengths the host knows), so it is dispatched before this
        tick's tokens are waited for and the device runs it while the host
        emits, admits and prepares. A request that can end on a token
        (``eos_id``) makes the next tick's slots unknowable: that tick
        dispatches its own decode, in the same order. Returns the tokens
        emitted, the slots that decoded, and 1 where their decode had been
        dispatched by the tick before (else 0: the ``engine.tick`` span's
        ``ahead``)."""
        ahead, self._ahead = self._ahead, None
        if ahead is None:
            seats = [(s, int(self.pool.positions[s]))
                     for s in self._decoding_slots()]
            dec = self._decode_dispatch(seats) if seats else None
        else:
            # a request preempted or cancelled since the dispatch has left
            # its slot: its token is dropped (a resumed one is sampled
            # again from the key the host kept)
            rids, (slots, *flight) = ahead
            held = [s for s, rid in zip(slots, rids)
                    if self.pool.occupant(s) == rid
                    and self.requests[rid].prefill_pos is None]
            dec = (held, *flight) if held else None
        chunk = self._prefill_dispatch()
        seats = self._seats_ahead(dec[0] if dec else (), chunk)
        if seats:
            self._ahead = ([self.pool.occupant(s) for s, _ in seats],
                           self._decode_dispatch(seats))
        emitted = self._emit_tick(*dec) if dec else 0
        if chunk is not None:
            emitted += self._prefill_finish(chunk)
        return emitted, len(dec[0]) if dec else 0, int(ahead is not None)

    def _seats_ahead(self, decoding, chunk) -> list[tuple[int, int]] | None:
        """``(slot, position)`` of the NEXT tick's decode while this
        tick's (over ``decoding``) and its ``chunk`` are in flight, or
        ``None`` where a token not yet read could end a request."""
        if self._block > 1:
            return self._block_seats_ahead(decoding, chunk)
        seats = []
        for s in decoding:
            r = self.requests[self.pool.occupant(s)]
            if len(r.tokens) + 1 >= r.max_new_tokens:
                continue            # the token in flight is its last
            if r.eos_id is not None:
                return None
            seats.append((s, int(self.pool.positions[s]) + 1))
        if chunk is not None:
            r, seq, p0, c = chunk[:4]
            if p0 + c == len(seq) and (r.tokens or r.max_new_tokens > 1):
                if r.eos_id is not None and not r.tokens:
                    return None
                seats.append((r.slot, len(seq)))
        return sorted(seats)

    def _block_seats_ahead(self, decoding, chunk):
        """:meth:`_seats_ahead` for block steps: ``(slot, block start)``.
        Under the static schedule the host's counts say which phase every
        slot's forward in flight is in: a denoising one keeps the slot on
        its block, a committing one moves it on by a block, or ends the
        request where the block reaches its budget; only a commit of a
        request with ``eos_id`` could end one unforeseen."""
        B = self._block
        seats = []
        for s in decoding:
            r = self.requests[self.pool.occupant(s)]
            p = int(self.pool.positions[s])
            if self.pool.block_fwd[s] + 1 < self.pool.block_total[s]:
                seats.append((s, p))
                continue
            if r.eos_id is not None:
                return None
            if p + B < len(r.prompt) + r.max_new_tokens:
                seats.append((s, p + B))
        if chunk is not None:
            r, seq, p0, c = chunk[:4]
            if p0 + c == len(seq):
                seats.append((r.slot, len(r.resume_seq) // B * B))
        return sorted(seats)

    def _ensure_writable_range(self, slot: int, p0: int, n: int) -> None:
        """Allocate/copy-on-write every block covering positions
        ``[p0, p0+n)`` of ``slot``'s sequence; runs the device block copy
        the pool asks for."""
        for p in range(p0, p0 + n):
            # ``oldest``: the first of these rows is the oldest query of the
            # program that writes them (a window group hands back what lies
            # behind its window, serve/slots.py)
            cp = self.pool.ensure_writable(slot, p, oldest=p0)
            if cp is not None:
                src, dst = cp
                self.pool.kc, self.pool.vc = self._copy_block(
                    self.pool.kc, self.pool.vc, np.int32(dst), np.int32(src))

    # -- speculative tick internals ----------------------------------------

    def _draft_prefill_slot(self, r: Request, seq: np.ndarray) -> None:
        """Record the draft model's K/V for ``seq`` into the draft pool's
        slot row. Greedy sampling args + a dummy key: the prefill's sampled
        token and advanced key are discarded — only the cache write
        matters, so neither the request's target stream nor its draft
        stream moves here."""
        dkc, dvc, _tok, _kd = self._draft_prefill(
            self._draft_params, self._dkc, self._dvc, seq[None, :],
            np.int32(r.slot), np.zeros(2, np.uint32), np.float32(0.0),
            np.int32(_NO_TOP_K), np.float32(_NO_TOP_P))
        self._dkc, self._dvc = dkc, dvc

    def _spec_tick(self, active: list[int]) -> int:
        """One speculative decode tick over the decoding slots: the draft
        propose scan (``spec_k`` fused draft steps) then the batched
        target verify, emitting 1..``spec_k`` tokens per slot. On a
        single-device target both halves run as ONE fused compiled
        program (``make_paged_spec_tick``: one dispatch per tick, the draft's
        ``[S, K, V]`` log-prob rows never leave the device); a TP target
        runs them as two dispatches (the verify is a shard_map program,
        the draft stays replicated), proposals flowing between on device
        with no host sync until the verify returns."""
        if not active:
            return 0
        S, K = self.pool.n_slots, self.spec_k
        with tracing.span("engine.decode.prepare"):
            kd, temps, top_ks, top_ps = self._sampling_inputs(active)
            self._sampling = int(np.count_nonzero(temps > 0))
            self._kv_positions = int(sum(
                self.pool.positions[s] + 1 for s in active))
            toks = np.zeros(S, np.int32)
            pos = np.zeros(S, np.int32)
            valid = np.zeros(S, np.int32)
            dkd = np.zeros((S, 2), np.uint32)
            for s in active:
                r = self.requests[self.pool.occupant(s)]
                toks[s] = self.pool.last_token[s]
                pos[s] = self.pool.positions[s]
                # the per-slot clamp: never speculate past the remaining token
                # budget, so every real K/V write stays inside the slot's
                # reservation (non-decoding slots keep valid 0 -> all-trash)
                valid[s] = min(K, r.max_new_tokens - len(r.tokens))
                dkd[s] = r.draft_key_data
            tables = np.full((S, self.pool.blocks_per_seq),
                             PagedKVPool.TRASH, np.int32)
            for s in active:
                self._ensure_writable_range(s, int(pos[s]), int(valid[s]))
                tables[s] = self.pool.device_table(s)
            # adapters ride the VERIFY side only: the draft proposes as the
            # base model (a wrong proposal costs acceptance rate, never
            # correctness — the adapted verify rows decide every emission)
            bank_args = self._bank_args(self._adapter_inputs(active))
        run = self._next_run()
        with tracing.span("engine.decode.dispatch", run=run,
                          program="decode"):
            if self._spec_fused is not None:
                dkc, dvc, kc, vc, otoks, nacc, kd2, dkd2 = self._spec_fused(
                    self._draft_params, self._dkc, self._dvc, self.params,
                    self.pool.kc, self.pool.vc, toks, pos, valid, tables,
                    dkd, kd, temps, top_ks, top_ps, *bank_args)
            else:
                dkc, dvc, drafts, qrows, dkd2 = self._propose(
                    self._draft_params, self._dkc, self._dvc, toks, pos, dkd,
                    temps, top_ks, top_ps)
                # the propose outputs flow into verify VERBATIM, still on
                # device; verify itself consumes only the first K-1 proposals
                # (the K-th exists to keep the draft cache ahead; models/gpt.py
                # section comment)
                kc, vc, otoks, nacc, kd2 = self._verify(
                    self.params, self.pool.kc, self.pool.vc, toks, pos,
                    drafts, qrows, valid, tables, kd, temps, top_ks,
                    top_ps, *bank_args)
            self._dkc, self._dvc = dkc, dvc
            self.pool.kc, self.pool.vc = kc, vc
        return self._emit_spec(active, otoks, nacc, kd2, dkd2, valid, run)

    def _emit_spec(self, active: list[int], otoks, nacc, kd2, dkd2,
                   valid, run: int) -> int:
        """Host-side tail of a speculative tick: emit each slot's accepted
        tokens in order (truncating at EOS — later positions' K/V is
        already written but gets overwritten before it can be attended),
        advance positions by the count actually emitted, and feed the
        proposed/accepted counters."""
        with tracing.span("engine.decode.wait", sched=True,
                          run=run) as wait:
            _ready(wait, otoks)
            otoks = np.asarray(otoks)            # host sync: tick endpoint
            nacc = np.asarray(nacc)
            kd2 = np.asarray(kd2)
            dkd2 = np.asarray(dkd2)
        with tracing.span("engine.decode.emit"):
            now = self._now = self._clock()
            emitted = proposed = accepted = 0
            for s in active:
                r = self.requests[self.pool.occupant(s)]
                r.key_data = kd2[s]
                r.draft_key_data = dkd2[s]
                m = int(nacc[s])                     # >= 1: valid[s] >= 1
                n_emit = 0
                finish = None
                for tok in otoks[s, :m]:
                    n_emit += 1
                    r.emit(int(tok))
                    finish = r.finished_by(int(tok))
                    if finish is not None:
                        break
                dt = now - self._last_emit[r.rid]
                if self.metrics is not None:
                    # the tick emitted n_emit tokens in one dt window: spread
                    # the interval so the TPOT mean stays the true cadence
                    for _ in range(n_emit):
                        self.metrics.on_token(dt / n_emit, cls=r.cls)
                self._last_emit[r.rid] = now
                emitted += n_emit
                slot_proposed = max(int(valid[s]) - 1, 0)
                slot_accepted = max(n_emit - 1, 0)
                proposed += slot_proposed
                accepted += slot_accepted
                if self.trace is not None:
                    self.trace.on_tick_tokens(r, now, n_emit,
                                              proposed=slot_proposed,
                                              accepted=slot_accepted)
                if finish is not None:
                    self._finish(r, finish, now)
                else:
                    self.pool.positions[s] += n_emit
                    self.pool.last_token[s] = r.tokens[-1]
            if self.metrics is not None and proposed:
                self.metrics.on_spec(proposed, accepted)
        return emitted

    # -- shared tick tails -------------------------------------------------

    def _sampling_inputs(self, active: list[int]):
        S = self.pool.n_slots
        kd = np.zeros((S, 2), np.uint32)
        temps = np.zeros(S, np.float32)
        top_ks = np.zeros(S, np.int32)
        top_ps = np.full(S, _NO_TOP_P, np.float32)
        for s in active:
            r = self.requests[self.pool.occupant(s)]
            kd[s] = r.key_data
            temps[s] = r.temperature
            top_ks[s] = r.top_k if r.top_k is not None else _NO_TOP_K
            top_ps[s] = r.top_p if r.top_p is not None else _NO_TOP_P
        return kd, temps, top_ks, top_ps

    def _take_counters(self, rows: np.ndarray) -> np.ndarray:
        """A decode's tokens as read back, the program's counters
        (``PagedServing.counters``: the last columns, every row alike)
        taken off and kept for the tick's span."""
        n = len(self._counter_names)
        if not n:
            return rows
        self._counted = dict(zip(self._counter_names,
                                 rows[0, -n:].tolist()))
        return rows[:, :-n]

    def _emit_decoded(self, active: list[int], toks, kd2, run: int) -> int:
        with tracing.span("engine.decode.wait", sched=True,
                          run=run) as wait:
            _ready(wait, toks)
            toks = np.asarray(toks)              # host sync: tick endpoint
            kd2 = np.asarray(kd2)
        if self._counter_names:
            toks = self._take_counters(toks)[:, 0]
        with tracing.span("engine.decode.emit"):
            now = self._now = self._clock()
            emitted = 0
            for s in active:
                r = self.requests[self.pool.occupant(s)]
                tok = int(toks[s])
                r.key_data = kd2[s]
                r.emit(tok)
                emitted += 1
                if self.metrics is not None:
                    self.metrics.on_token(now - self._last_emit[r.rid],
                                          cls=r.cls)
                if self.trace is not None:
                    self.trace.on_tick_tokens(r, now, 1)
                self._last_emit[r.rid] = now
                reason = r.finished_by(tok)
                if reason is not None:
                    self._finish(r, reason, now)
                else:
                    self.pool.advance(s, tok)
        return emitted

    def _emit_block(self, active: list[int], rows, kd2, run: int) -> int:
        """:meth:`_emit_decoded` for block steps: a slot whose forward
        denoised counts it; one whose forward committed emits its block's
        tokens in order (past the prompt's remainder, cut at
        ``max_new_tokens``, ended by ``eos_id``), all stamped now, and
        moves on by a block."""
        B = self._block
        with tracing.span("engine.decode.wait", sched=True,
                          run=run) as wait:
            _ready(wait, rows)
            rows = np.asarray(rows)              # host sync: tick endpoint
            kd2 = np.asarray(kd2)
        with tracing.span("engine.decode.emit"):
            now = self._now = self._clock()
            toks, order, committed = self._unpack_block(
                self._take_counters(rows), B)
            emitted = 0
            for s in active:
                r = self.requests[self.pool.occupant(s)]
                r.key_data = kd2[s]
                self.pool.block_fwd[s] += 1
                if committed[s] != (self.pool.block_fwd[s]
                                    == self.pool.block_total[s]):
                    raise RuntimeError(
                        f"slot {s}: forward {self.pool.block_fwd[s]} of "
                        f"{self.pool.block_total[s]} committed="
                        f"{bool(committed[s])} — the host's schedule and "
                        f"the device's block state disagree")
                if not committed[s]:
                    continue
                p = int(self.pool.positions[s])
                r.blocks.append((p, toks[s].tolist(), order[s].tolist()))
                first = r.first_token_time is None
                if first:
                    r.first_token_time = now
                    if self.metrics is not None:
                        self.metrics.on_first_token(r.ttft_s, cls=r.cls)
                    if self.trace is not None:
                        self.trace.on_first_token(r, now)
                dt = now - self._last_emit.get(r.rid, now)
                self._last_emit[r.rid] = now
                n_emit, finish = 0, None
                for tok in toks[s, max(len(r.prompt) - p, 0):]:
                    n_emit += 1
                    r.emit(int(tok))
                    finish = r.finished_by(int(tok))
                    if finish is not None:
                        break
                if self.metrics is not None:
                    # the gap since the last block, spread over this one's
                    # tokens (as _emit_spec does); a request's first token
                    # is its TTFT's
                    for _ in range(n_emit - first):
                        self.metrics.on_token(dt / n_emit, cls=r.cls)
                if self.trace is not None:
                    self.trace.on_tick_tokens(r, now, n_emit)
                emitted += n_emit
                if finish is not None:
                    self._finish(r, finish, now)
                else:
                    self._seat_block(r, p + B, 0)
        return emitted

    def _finish(self, r: Request, reason: str, now: float) -> None:
        r.done_time = now
        self._last_emit.pop(r.rid, None)
        if self.trace is not None:
            self.trace.on_finish(r, now, reason)
        if r.state == ACTIVE:
            # scheduler.retire unbinds the sequence (decref table blocks —
            # registered ones stay reclaimable — and return the unused
            # reservation) before the slot frees
            self.scheduler.retire(r, reason)
        self._adapter_release(r)
        if self.metrics is not None:
            self.metrics.on_complete(cls=r.cls,
                                     adapter=getattr(r, "adapter", None))
