"""What a paged serving program is made of.

The one place that says it: the contract a model hands ``serve/engine.py``
(:class:`PagedServing`), the memo of built programs, the K/V cache's dtype
rule, the pool's rows (scatter, gather, the two attention paths), the ONE
sampler of the serve programs, and the host arguments packed as one array.
Every family (``models/gpt.py``, ``jamba.py``, ``sdar.py``,
``nemotron_h.py``, ``zaya.py``, ``cohere2.py``, ``kimi_linear.py``) builds its two programs from
these and imports no other family's file; ``serve/`` takes the contract and
the dtype rule from here. This module imports no family and nothing of
``serve/``.
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from simple_distributed_machine_learning_tpu.ops.layers import (
    matmul_acc32,
    rms_norm,
)


class PagedServing(NamedTuple):
    """What a model hands ``serve/engine.py`` for the paged layout
    (``cfg.paged_serving(stages, max_len, block_size, cache_dtype, mesh=,
    kernel=, adapters=)``): the cache's layout and the two programs.

    The pool holds ``kv_layers x kv_heads x head_dim`` K/V rows a position
    (the CACHE's head count: a grouped-query model's is smaller than its
    query heads'). ``state_shapes`` is a pytree of per-slot
    ``jax.ShapeDtypeStruct``: device buffers the pool keeps beside the
    blocks, one ``[n_slots, *shape]`` array per leaf. First what the
    family's layers carry from one step to the next (a state-space layer's
    recurrent pair, a block in progress; GPT has none), and LAST every
    slot's newest token and sampling key (:data:`NEWEST_PAIR`). Whether any
    of it is RECURRENT is the config's to say (``cfg.recurrent_state``: a
    summary of the whole prefix, which rules out prefix sharing and more,
    ``serve/slots.py``).

    The programs are ``chunk_prefill(params, kc, vc, state, tokens [1, c],
    p0, table, slot, seat, key_data, temperature, top_k, top_p) -> (kc, vc,
    state, token, key_data)`` and ``decode(params, kc, vc, state, toks, pos,
    tables, live [S], key_data, temps, top_ks, top_ps) -> (kc, vc, state,
    tokens, key_data)``, ``state`` donated like the pool. The newest pair
    stays on the device: the decode reads its tokens and keys there (the
    host's ``toks`` and ``key_data`` are not looked at), advances the
    ``live`` slots alone and writes their new ones back
    (:func:`feed_newest`); the chunk's ``seat`` says what it leaves there
    for ``slot`` (:func:`seat_newest`): :data:`SEAT_NONE` (a mid-prompt
    chunk: the slot's pair stays), :data:`SEAT_SAMPLE` (its own sample and
    advanced key) or a token (that token and the key it was handed: a
    resumed request's). Nothing a decode needs then waits for the host to
    read the last one, and the engine dispatches a tick's decode before it
    reads the previous tick's tokens (``serve/engine.py::_tick_ahead``).

    ``pack_chunk`` / ``pack_decode``: where given, the engine hands them a
    program's host-side arguments (everything after the buffers) and calls
    the program with what they return instead. Every numpy argument of a
    call is a transfer of its own, about 0.13 ms each on a v5e's host (my
    chip run, PR 28); a model may take them as one array, or leave behind
    what its program does not read.

    ``serve_params``: where given, the two programs read not the stages'
    parameter trees (``[s.params for s in stages]``, or the engine's
    ``params=``) but what this function makes of that list, and the engine
    calls it ONCE, where it takes its parameters: a layout of the same
    weights that only the programs need (``models/cohere2.py`` holds a
    window layer's query and key projections in the lane order their
    rotation reads). Programs and layout travel together: whoever calls
    ``chunk_prefill`` or ``decode`` by hand hands them
    ``serve_params(params)``, and a program refuses by name a tree that did
    not pass through it. ``None`` (every other family): the programs read
    the stages' trees themselves and the engine keeps the list it was given.

    ``block``: how many positions a slot's step works on. 1 (GPT, the
    hybrid): a step reads one token, writes one K/V row and emits one
    token. ``block > 1`` (``models/sdar.py``, generation by diffusion over
    blocks): a step is one FORWARD of the slot's block of ``block``
    positions, which either denoises (fixes some of its still-masked
    tokens, writes nothing that lasts, emits nothing) or commits (writes
    the block's K/V rows for good and emits its tokens). The block in
    progress rides ``state_shapes`` before the newest pair; the chunk's
    ``seat`` is ``[1 + block]`` (how many tokens of the prompt's remainder
    open the block, or :data:`SEAT_NONE`, then those tokens); the decode
    takes ``steps [S]`` (each slot's denoising steps) after ``live`` and
    returns ``[S, 2 * block + 1]`` int32 for its tokens, which
    ``unpack_rows(rows, block)`` reads: every slot's block, the forward
    that fixed each position and whether the forward committed.
    ``block_forwards(block, steps, masked)``: the denoising forwards a
    block with ``masked`` open positions takes under a request's
    ``steps``; the host foresees every slot's phase from it
    (``models/sdar.py::denoise_forwards``, ``unpack_block_rows``).

    ``counters``: names of int32 counts a decode run makes of itself (the
    experts it hit, the forwards it ran). A program that names ``n`` hands
    its tokens as ``[S, w + n]`` int32 instead of ``[S]`` (``w = 1``) or
    ``[S, w]``: the last ``n`` columns hold the counts, the same in every
    row. They ride the read-back the engine makes of the tokens anyway (a
    tick late where the decode was dispatched ahead; a second transfer
    would cost 0.13 ms) and become attributes of that tick's
    ``engine.tick`` span, 0 where a tick ran no decode.

    ``windows``: each K/V layer's KIND, one entry a layer: ``None`` a full
    layer (it attends every earlier position), else the window in positions
    (``models/cohere2.py``). Layers of one window value are a GROUP of the
    pool, with buffers sized by the window, a ring table a slot and blocks
    handed back behind the window (``serve/slots.py``, "Layer kinds"); the
    programs are then handed every group's table side by side
    (``PagedKVPool.device_table``: the full group's ``ceil(max_len /
    block)`` entries, then each ring). ``()``: every layer is full.

    ``value_lanes``: ``None``, every K/V layer has a key buffer and a value
    buffer of ``kv_heads x head_dim`` lanes a position. A number
    (``models/kimi_linear.py``, an ABSORBED latent cache): a K/V layer holds
    ONE buffer, a position's row the normed latent and then the key lanes
    all heads share, and the row's leading ``value_lanes`` lanes are its
    values. The pool then allocates no value buffer (``PagedKVPool.vc`` is
    the empty tuple, which the programs are handed, donate and hand back as
    it is), ``kv_block_bytes`` counts one stream, and the decode attends
    through ``ops/paged_attention.py``'s one-stream case (``vc=None``). No
    quantized dtype (a row's scale planes are a head's)."""
    kv_layers: int
    kv_heads: int
    head_dim: int
    state_shapes: tuple
    chunk_prefill: Callable
    decode: Callable
    pack_chunk: Callable | None = None
    pack_decode: Callable | None = None
    block: int = 1
    block_forwards: Callable | None = None
    unpack_rows: Callable | None = None
    counters: tuple = ()
    windows: tuple = ()
    serve_params: Callable | None = None
    value_lanes: int | None = None


# a chunk's ``seat`` where it is no token (PagedServing)
SEAT_NONE = -2
SEAT_SAMPLE = -1
# one slot's newest token and sampling key data (PagedServing)
NEWEST_PAIR = (jax.ShapeDtypeStruct((), jnp.int32),
               jax.ShapeDtypeStruct((2,), jnp.uint32))


def seat_newest(pair, slot, seat, tok, kd, key_data):
    """The pair ``([S] int32, [S, 2] uint32)`` after a prefill chunk of
    ``slot``: left as it was (:data:`SEAT_NONE`),
    or the slot's row set to the chunk's own sample ``tok`` and advanced
    key ``kd`` (:data:`SEAT_SAMPLE`), or to the token ``seat`` and the key
    the chunk was handed."""
    newest, keys = pair
    own = seat == SEAT_SAMPLE
    # the clamp changes no value (a negative seat is a code and takes
    # another branch): it lets the analyzer's bounds pass prove that what
    # the next decode looks up is a token
    newest = newest.at[slot].set(jnp.where(
        seat == SEAT_NONE, newest[slot],
        jnp.where(own, tok, jnp.maximum(seat, 0))))
    keys = keys.at[slot].set(jnp.where(
        seat == SEAT_NONE, keys[slot], jnp.where(own, kd, key_data)))
    return newest, keys


def feed_newest(pair, live, toks2, kd2):
    """The pair after a decode step: the ``live`` slots' rows are the
    step's samples and keys, the others' as they were (a slot between its
    chunk and its first decode must find what the chunk seated)."""
    toks, key_data = pair
    return (jnp.where(live, toks2, toks),
            jnp.where(live[:, None], kd2, key_data))


# Built decode-path programs, keyed by their STATIC config. Every function
# cached here closes over shape scalars only — params (and therefore the
# stages' weights and layer count) arrive as traced ARGUMENTS — so two
# builds with the same key return one shared jitted callable and its
# compiled executables. Build-time validation still runs per call (it
# checks the CALLER's stages); only the trace/compile work is shared.
# This is what keeps a fleet of serving engines (and a test suite full of
# them) from recompiling identical programs per instance.
_DECODE_BUILD_CACHE: dict = {}


def memo_build(key: tuple, build):
    fn = _DECODE_BUILD_CACHE.get(key)
    if fn is None:
        fn = _DECODE_BUILD_CACHE[key] = build()
    return fn


def storage_dtype(cache_dtype):
    """K/V cache storage dtype (None = f32). bf16 HALVES decode memory — the
    cache is the dominant inference allocation at L x B x H x total x dh x 2
    buffers — at ~1e-3 relative logit error (attention math still
    accumulates in f32 via einsum promotion). The one copy of the rule for
    every decoder (cached, beam, pipeline-parallel).

    QUANTIZED storage (``int8``, and the fp8 formats where the jnp build
    has them) quarters/halves-again the paged pool's block bytes: blocks
    store narrow-dtype rows plus one f32 scale per (position, head) row —
    a :class:`QuantKV` pytree instead of a bare array — with quantize
    fused into every scatter and dequantize into every gather/kernel
    (:func:`quantize_rows` / :func:`paged_gather`). Quantization is a
    PAGED-pool feature: the speculative draft's slot rows and the solo
    cached decoder (the parity anchor) carry no scale planes and reject it
    (:func:`check_cache_quantization`)."""
    return jnp.float32 if cache_dtype is None else jnp.dtype(cache_dtype)


# fp8 availability is build-dependent on the 0.4.x line; int8 always exists
_QUANT_QMAX = {"int8": 127.0}
for _fp8_name, _fp8_qmax in (("float8_e4m3fn", 448.0),
                             ("float8_e5m2", 57344.0)):
    if hasattr(jnp, _fp8_name):
        _QUANT_QMAX[_fp8_name] = _fp8_qmax


def is_quantized_dtype(cache_dtype) -> bool:
    """Whether ``cache_dtype`` selects the quantized (data + scales) K/V
    block format — the one predicate pool construction, byte accounting
    and program tracing all branch on."""
    return (cache_dtype is not None
            and jnp.dtype(cache_dtype).name in _QUANT_QMAX)


class QuantKV(NamedTuple):
    """One layer's quantized K or V pool buffer: narrow-dtype block
    ``data`` (``[n_blocks+1, bs, H*dh]``, a position's heads side by side
    like the plain buffer's) plus the per-row f32 dequant ``scale`` plane
    (``[n_blocks+1, bs, H]`` — one scale per written position per head, so
    incremental decode writes never re-quantize a block's existing rows).
    A NamedTuple so jax treats the pair as ONE pytree buffer: jit
    donation, device_put sharding and tree_map'd block copies all flow
    through unchanged engine/pool code."""
    data: jax.Array
    scale: jax.Array

    @property
    def dtype(self):
        """The storage dtype — what ``engine_spec``/``ServeSpec`` record
        as the deployment's cache_dtype."""
        return self.data.dtype

    @property
    def nbytes(self) -> int:
        return self.data.nbytes + self.scale.nbytes

    @property
    def shape(self):
        return self.data.shape


def quantize_rows(rows: jax.Array, dtype) -> tuple[jax.Array, jax.Array]:
    """Quantize K/V rows ``[..., dh]`` to ``dtype`` with one f32 scale per
    row: ``scale = amax(|row|) / qmax`` (floored so all-zero rows stay
    finite), data = ``round(row / scale)`` for int8, the plain cast for
    fp8 (whose format rounds itself). Dequantization is exactly
    ``data * scale`` — the round trip's relative error is bounded by
    ~``1/(2*qmax)`` per element (tests/test_paged_attention.py pins it)."""
    dtype = jnp.dtype(dtype)
    qmax = _QUANT_QMAX[dtype.name]
    rows = rows.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(rows), axis=-1) / qmax, 1e-8)
    q = rows / scale[..., None]
    if dtype.name == "int8":
        q = jnp.clip(jnp.round(q), -127.0, 127.0)
    return q.astype(dtype), scale.astype(jnp.float32)


def check_cache_quantization(cache_dtype, caller: str,
                             paged: bool) -> None:
    """Quantized caches are paged-pool-only (the solo cached decoder is the
    bit-exactness anchor the quantized pool's pinned tolerance is judged
    against, and the draft's slot rows have no scale planes); unknown
    narrow dtypes fail loudly here instead of as a shape error
    mid-trace."""
    if cache_dtype is None:
        return
    name = jnp.dtype(cache_dtype).name
    if name in ("float8_e4m3fn", "float8_e5m2") and name not in _QUANT_QMAX:
        raise ValueError(
            f"{caller}: cache_dtype={name} is not available in this jnp "
            f"build — use int8 (always available) or a wider dtype")
    if is_quantized_dtype(cache_dtype) and not paged:
        raise ValueError(
            f"{caller}: quantized cache_dtype={name} is a paged-pool "
            f"feature (per-block scales live beside physical blocks); "
            f"the cached decoder and the draft's slot rows take f32/bf16")


def paged_scatter(kc, li, phys, off, rows):
    """Land K/V ``rows`` (``[..., H, dh]``, aligned with the ``phys``/
    ``off`` index arrays ``[...]``) in layer ``li``'s buffer of a paged
    pool — the ONE scatter every paged program uses. ``kc`` is the pool's
    tuple of per-layer buffers ``[n_blocks+1, bs, H*dh]``: a position's
    heads lie side by side in one row, the two indexed axes lead and are
    adjacent, so the write is one contiguous row a position and XLA keeps
    it in place on the donated buffer. Plain buffers cast to the storage
    dtype; :class:`QuantKV` buffers quantize each head's row and land its
    scale in the matching plane, so a quantized pool never holds a
    half-updated (data, scale) pair."""
    buf = kc[li]
    if isinstance(buf, QuantKV):
        qd, sc = quantize_rows(rows, buf.data.dtype)
        new = QuantKV(
            buf.data.at[phys, off].set(qd.reshape(*qd.shape[:-2], -1)),
            buf.scale.at[phys, off].set(sc))
    else:
        new = buf.at[phys, off].set(
            rows.reshape(*rows.shape[:-2], -1).astype(buf.dtype))
    return kc[:li] + (new,) + kc[li + 1:]


def paged_gather(kc, li, table, n_heads):
    """Layer ``li``'s K or V rows of a sequence, assembled from the paged
    pool for the dense-math attention path. ``table``: logical->physical
    block ids, ``[NB]`` (one sequence) or ``[S, NB]`` (one per slot);
    ``n_heads``: the heads in a pool row. Returns ``[..., H, NB*bs, dh]``
    with position ``p`` of the sequence at flattened row index ``p`` —
    EXACTLY a contiguous cache row's order (the cached decoder's), so the
    attention math downstream is unchanged and the trailing garbage rows
    (trash-block entries past the allocated span) are removed by the same
    position mask that hides not-yet-written rows. :class:`QuantKV`
    buffers dequantize
    (``data * scale``, f32) so the downstream einsums see ordinary rows."""
    buf = kc[li]
    quant = isinstance(buf, QuantKV)
    rows = (buf.data if quant else buf)[table]    # [..., NB, bs, H*dh]
    lead = rows.shape[:-3]
    span = rows.shape[-3] * rows.shape[-2]
    rows = rows.reshape(*lead, span, n_heads, -1)
    if quant:
        sc = buf.scale[table].reshape(*lead, span, n_heads)
        rows = rows.astype(jnp.float32) * sc[..., None]
    return jnp.moveaxis(rows, -3, -2)             # [..., H, span, dh]


#: cached positions one step of :func:`span_attention` gathers and scores:
#: whole pool blocks, ``heads x chunk x ATTEND_ROWS`` float32 scores a step
ATTEND_ROWS = 512

_NEG = -1e30        # a masked score: finite, so an empty step changes nothing


def entry(table, block, window):
    """The physical block of logical block(s) ``block`` through a layer's
    ``table [N, NB]`` (``block [N, ...]``): entry ``block``, or in a window
    layer's ring ``block % NB``."""
    if window is not None:
        block = block % table.shape[-1]
    flat = jnp.take_along_axis(table, block.reshape(block.shape[0], -1),
                               axis=1)
    return flat.reshape(block.shape)


def span_attention(q, kbuf, vbuf, table, qpos, window, kv: int, bs: int):
    """Softmax attention of ``q [N, L, H, dh]`` at positions ``qpos [N, L]``
    (non-decreasing along ``L``) over ONE layer's pool buffers ``kbuf`` /
    ``vbuf [n_blocks + 1, bs, KV dh]`` (``kv`` K/V heads a row, each read by
    its ``H / kv`` query heads) through that layer's ``table [N, NB]``,
    over the live positions alone: steps of :data:`ATTEND_ROWS`
    positions from the one that holds the oldest query's first visible key
    to the one that holds the newest query, a running maximum and sum
    between them (``ops/paged_attention.py``'s walk in ``jax.numpy``, for a
    chunk's many query rows). A step's blocks before the first live one or
    past the newest fetch that one instead, and the position mask removes
    them: no block wholly behind a window, and none past the newest query's,
    is gathered, whatever the table holds there. Returns ``[N, L, H dh]``
    float32. The prefill chunks of ``models/cohere2.py`` (a window or a full
    layer) and of ``models/zaya.py`` (``window=None``) attend through it."""
    f32 = jnp.float32
    n, lq, heads, dh = q.shape
    g = heads // kv
    # operands in the POOL's dtype, sums in float32, as every matmul here
    # reads its weights: a bfloat16 pool's rows go to the matrix unit as
    # they lie (what the chip's one-pass float32 product makes of them
    # anyway, ops/paged_attention.py), a float32 pool keeps float32. A K/V
    # head's group of query heads are ROWS of one product, [N, KV, g L, dh]
    # against [N, KV, R, dh]: the scores' lanes are the step's positions
    q = jnp.moveaxis(q.reshape(n, lq, kv, g, dh) / math.sqrt(dh), 1, 3)
    q = q.reshape(n, kv, g * lq, dh).astype(kbuf.dtype)
    rowpos = jnp.tile(qpos, (1, g))[:, None, :, None]        # [N, 1, g L, 1]
    blocks = max(1, min(table.shape[-1], ATTEND_ROWS // bs))
    rows = blocks * bs
    oldest = qpos[:, 0] if window is None else jnp.maximum(
        qpos[:, 0] - (window - 1), 0)
    first_blk = (0 * oldest if window is None else oldest // bs)[:, None]
    last_blk = (qpos[:, -1] // bs)[:, None]
    batched = ((0, 1), (0, 1))

    def step(i, carry):
        m_prev, l_prev, acc = carry
        want = i * blocks + jnp.arange(blocks)[None, :]          # [1, G]
        phys = entry(table, jnp.clip(want, first_blk, last_blk), window)
        k = jnp.swapaxes(kbuf[phys].reshape(n, rows, kv, dh), 1, 2)
        v = jnp.swapaxes(vbuf[phys].reshape(n, rows, kv, dh), 1, 2)
        back = rowpos - (i * rows + jnp.arange(rows))    # [N, 1, g L, R]
        mask = back >= 0
        if window is not None:
            mask &= back < window
        scores = lambda q: jax.lax.dot_general(  # noqa: E731
            q, k, (((3,), (3,)), batched), preferred_element_type=f32)
        m_new = jnp.maximum(m_prev, jnp.where(mask, scores(q), _NEG).max(
            axis=-1, keepdims=True))
        # the scores a second time, behind a barrier that keeps the compiler
        # from sharing the first product: each product then keeps its
        # epilogue (the row maximum; exp and the cast) in its own fusion
        # and the float32 scores of a step, heads x chunk x step x 4 bytes,
        # are never written out (they were 800 MB of a step's traffic and
        # two thirds of its time on the chip: PERF.md section 6, PR 44)
        p = jnp.where(mask, jnp.exp(
            scores(jax.lax.optimization_barrier(q)) - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        return (m_new, l_prev * corr + p.sum(axis=-1, keepdims=True),
                acc * corr + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((3,), (2,)), batched),
                    preferred_element_type=f32))

    lo = (jnp.min(oldest) // rows if window is not None else 0)
    hi = jnp.max(qpos[:, -1]) // rows + 1
    _, l, acc = jax.lax.fori_loop(lo, hi, step, (
        jnp.full((n, kv, g * lq, 1), _NEG, f32),
        jnp.zeros((n, kv, g * lq, 1), f32),
        jnp.zeros((n, kv, g * lq, dh), f32)))
    out = (acc / jnp.maximum(l, 1e-30)).reshape(n, kv, g, lq, dh)
    return jnp.moveaxis(out, 3, 1).reshape(n, lq, heads * dh)


def paged_attend(kc, vc, li, q, tables, qpos, bs):
    """The FUSED attention path: one Pallas pass over layer ``li``'s
    physical blocks (gather + mask + online-softmax attention, dequant
    fused for :class:`QuantKV` pools) — see ``ops/paged_attention.py``.
    The layer's buffer goes over whole, as the pool holds it. ``q``:
    [S, H, K, dh]; ``qpos``: [S, K]. Returns f32 [S, H, K, dh], exactly
    the dense-math path's masked attention output."""
    from simple_distributed_machine_learning_tpu.ops.paged_attention import (
        paged_attention,
    )
    k, v = kc[li], vc[li]
    if isinstance(k, QuantKV):
        return paged_attention(q, k.data, v.data, tables, qpos,
                               block_size=bs, kscale=k.scale,
                               vscale=v.scale)
    return paged_attention(q, k, v, tables, qpos, block_size=bs)


def check_attn_kernel(kernel: str, caller: str) -> str:
    if kernel not in ("dense", "fused"):
        raise ValueError(
            f"{caller}: kernel must be 'dense' (gather-then-dense "
            f"attention, the parity anchor) or 'fused' (the Pallas "
            f"paged-attention kernel), got {kernel!r}")
    return kernel


def merged_stage_trees(params_list):
    """Re-join per-stage param trees into ``(embed, blocks, head)`` — the
    one copy shared by every single-device decoder (cached, beam)."""
    embed = head = None
    blocks = []
    for p in params_list:
        blocks.extend(p["blocks"])
        embed = p.get("embed", embed)
        head = p.get("head", head)
    return embed, blocks, head


def qkv(ap: dict, u, cfg):
    """``q [N, L, H, dh]``, ``k`` / ``v [N, L, KV, dh]``, float32."""
    n, n_tok, _ = u.shape
    dh = cfg.head_dim
    return (matmul_acc32(u, ap["wq"]).reshape(n, n_tok, cfg.n_heads, dh),
            matmul_acc32(u, ap["wk"]).reshape(n, n_tok, cfg.n_kv_heads, dh),
            matmul_acc32(u, ap["wv"]).reshape(n, n_tok, cfg.n_kv_heads, dh))


def grouped_attention(q, k, v, mask, cfg):
    """Softmax attention of ``q [N, Lq, H, dh]`` over ``k`` / ``v [N, Lk,
    KV, dh]`` where ``mask [N or 1, Lq, Lk]`` allows: every group of
    ``H / KV`` query heads reads its one K/V head, never a repeated copy.
    Returns ``[N, Lq, H * dh]``."""
    n, lq, _, dh = q.shape
    kv = cfg.n_kv_heads
    q = q.reshape(n, lq, kv, cfg.n_heads // kv, dh)
    scores = jnp.einsum("nqkgd,npkd->nkgqp", q, k.astype(jnp.float32))
    scores = jnp.where(mask[:, None, None], scores / math.sqrt(dh), -jnp.inf)
    a = jnp.einsum("nkgqp,npkd->nqkgd", jax.nn.softmax(scores, axis=-1),
                   v.astype(jnp.float32))
    return a.reshape(n, lq, cfg.n_heads * dh)


def tied_logits(params_or_trees, h, cfg):
    """Final norm, then the embedding matrix itself as the head."""
    table = params_or_trees["embed"]["tok"]
    hn = rms_norm(params_or_trees["head"]["norm_f"], h, cfg.rms_eps)
    return jax.lax.dot_general(
        hn.astype(table.dtype), table,
        (((hn.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def filter_top_dyn(scaled: jax.Array, top_k: jax.Array,
                   top_p: jax.Array) -> jax.Array:
    """Traced-argument counterpart of ``gpt._filter_top`` on ONE row [V] —
    the serving engine's decode tick samples every slot in a single compiled
    program, so each request's top-k/top-p knobs arrive as device scalars.
    ``top_k == 0`` disables top-k; ``top_p > 1`` disables top-p. When a
    filter IS enabled the math mirrors the static version step for step
    (same k-th-largest threshold, same exclusive-cumsum rule, top-k before
    top-p with the second sort on the top-k-filtered row), so a served
    request's filtered distribution matches its solo decode bit for bit."""
    V = scaled.shape[-1]
    srt = jnp.flip(jnp.sort(scaled, axis=-1), axis=-1)        # descending
    kth = jnp.take(srt, jnp.clip(top_k, 1, V) - 1, axis=-1)
    scaled = jnp.where((top_k >= 1) & (scaled < kth), -jnp.inf, scaled)
    srt = jnp.flip(jnp.sort(scaled, axis=-1), axis=-1)        # post-top-k
    p = jax.nn.softmax(srt, axis=-1)
    exclusive = jnp.cumsum(p, axis=-1) - p
    keep = exclusive < top_p                                  # top-1 always
    thresh = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1)
    return jnp.where((top_p <= 1.0) & (scaled < thresh), -jnp.inf, scaled)


def sample_dyn(row: jax.Array, key_data: jax.Array, temperature: jax.Array,
               top_k: jax.Array, top_p: jax.Array
                ) -> tuple[jax.Array, jax.Array]:
    """One decode step on ONE row [V] with TRACED sampling params ->
    ``(token, next_key_data)``. Mirrors ``gpt._sample_row``'s key-split
    discipline exactly — greedy (``temperature == 0``) consumes no
    randomness, sampling splits once per token — so a served request's key
    stream (and therefore its tokens) match its solo decode bit for bit.
    Keys travel as raw uint32 key data so per-slot selection can use
    ``jnp.where`` (typed key arrays reject it); ``vmap`` over slots is the
    loop semantics, so per-slot draws equal the unbatched calls."""
    k = jax.random.wrap_key_data(key_data)
    nk, ks = jax.random.split(k)
    safe_t = jnp.where(temperature > 0, temperature, jnp.float32(1.0))
    filtered = filter_top_dyn(row / safe_t, top_k, top_p)
    samp = jax.random.categorical(ks, filtered, axis=-1)
    tok = jnp.where(temperature > 0, samp, jnp.argmax(row, axis=-1))
    kd = jnp.where(temperature > 0, jax.random.key_data(nk), key_data)
    return tok.astype(jnp.int32), kd


def sample_slots(rows: jax.Array, key_data: jax.Array, temps: jax.Array,
                 top_ks: jax.Array, top_ps: jax.Array
                  ) -> tuple[jax.Array, jax.Array]:
    """:func:`sample_dyn` over the rows ``[S, V]`` with each row's own
    ``key_data [S, 2]`` and params ``[S]`` -> ``(tokens int32 [S],
    key_data [S, 2])`` — unless every row is greedy: ``sample_dyn`` sorts
    each row twice for its top-k / top-p filters whatever the temperature,
    and over a vocabulary those sorts cost as much as the rest of a decode
    tick. A greedy row's result is the same either way (its ``argmax``, its
    key unchanged), so one sampled row takes ``vmap`` of ``sample_dyn``
    for every row and an all-greedy batch the ``argmax`` alone. The ONE
    row-batch sampler of the serve programs: every family's but
    ``models/sdar.py::_sample_block``, which keeps a ``cond`` of its own,
    whose sampled branch folds the position into the key."""
    return jax.lax.cond(
        jnp.any(temps > 0),
        lambda: jax.vmap(sample_dyn)(rows, key_data, temps, top_ks, top_ps),
        lambda: (jnp.argmax(rows, axis=-1).astype(jnp.int32), key_data))


def sample_slot(row, key_data, temperature, top_k, top_p):
    """:func:`sample_slots` for ONE row ``[V]`` with scalar params."""
    tok, kd = sample_slots(row[None], key_data[None], temperature[None],
                           top_k[None], top_p[None])
    return tok[0], kd[0]


def validate_hybrid_build(stages, cfg, max_len: int, block_size: int,
                          cache_dtype, mesh, adapters: bool,
                          caller: str = "JambaConfig.paged_serving",
                          maker: str = "make_jamba_stages") -> None:
    """What any family with recurrent state refuses of ``paged_serving``'s
    arguments, by name, and what every one-stage family checks of its build
    (``caller`` / ``maker``: the names in the messages)."""
    for name, asked, reason in (
            ("mesh (tensor-parallel serving)", mesh is not None,
             "the scan's channels and the state buffers have no sharded "
             "placement"),
            ("adapters", adapters,
             "the LoRA bank rides GPT's wq / wv (models/lora.py)"),
            ("a quantized cache_dtype", is_quantized_dtype(cache_dtype),
             "K/V blocks would carry scale planes, the recurrent state has "
             "no such format: use float32 or bfloat16")):
        if asked:
            raise ValueError(
                f"{name} is not available with a model that has recurrent "
                f"state: {reason}")
    if len(stages) != 1 or "embed" not in stages[0].params:
        raise ValueError(
            f"{caller} needs {maker}' one stage (it has no pipeline "
            f"build), got {len(stages)} stages")
    table = stages[0].params["embed"]["tok"]
    if table.shape != (cfg.vocab, cfg.d_model) or len(
            stages[0].params["blocks"]) != cfg.n_layers:
        raise ValueError(
            f"cfg (vocab={cfg.vocab}, d_model={cfg.d_model}, "
            f"n_layers={cfg.n_layers}) does not match the stage's build "
            f"(embedding {table.shape}, "
            f"{len(stages[0].params['blocks'])} layers)")
    if not 2 <= max_len <= cfg.seq_len:
        raise ValueError(
            f"slot max_len={max_len} outside [2, seq_len={cfg.seq_len}]")
    if block_size < 1:
        raise ValueError(f"{caller} needs block_size >= 1, got {block_size}")


# -- host inputs as one array -------------------------------------------------
#
# A program's host-side arguments travel as ONE int32 array (float32 and
# uint32 values by their bits): eight small numpy arguments are eight
# transfers, a millisecond of every launch on a v5e's host.

_DECODE_COLS = 5    # a slot's columns before its block table
_CHUNK_COLS = 8     # a chunk's scalars before its block table


def bits(a, dtype=np.float32) -> np.ndarray:
    return np.asarray(a, dtype).view(np.int32)


def pack_decode_inputs(toks, pos, tables, live, key_data, temps, top_ks,
                       top_ps) -> tuple[np.ndarray]:
    """``[S, 5 + NB]`` int32: a slot's position, live flag, top-k,
    temperature and top-p bits, then its block table. ``toks`` and
    ``key_data``, the engine's host copies, stay behind: the program reads
    the newest token and key of every slot from its state."""
    del toks, key_data
    cols = [pos, live, top_ks, bits(temps), bits(top_ps)]
    return (np.concatenate([np.stack(cols, axis=1).astype(np.int32),
                            np.asarray(tables, np.int32)], axis=1),)


def unpack_decode(host):
    f32 = lambda c: jax.lax.bitcast_convert_type(host[:, c], jnp.float32)  # noqa: E731
    return (host[:, 0], host[:, _DECODE_COLS:], host[:, 1] != 0, f32(3),
            host[:, 2], f32(4))


def pack_chunk_inputs(tokens, p0, table, slot, seat, key_data, temperature,
                      top_k, top_p) -> tuple[np.ndarray, np.ndarray]:
    """``(tokens [1, c], [8 + NB] int32)``: position, slot, top-k, two key
    words, temperature and top-p bits and ``seat``, then the block table.
    The tokens stay an argument of their own: their length is the one
    shape the program is traced for."""
    head = [p0, slot, top_k, *bits(key_data, np.uint32), bits(temperature),
            bits(top_p), seat]
    return (np.asarray(tokens, np.int32),
            np.concatenate([np.asarray(head, np.int32),
                            np.asarray(table, np.int32)]))


def unpack_chunk(host):
    f32 = lambda c: jax.lax.bitcast_convert_type(host[c], jnp.float32)  # noqa: E731
    kd = jax.lax.bitcast_convert_type(host[3:5], jnp.uint32)
    return (host[0], host[_CHUNK_COLS:], host[1], host[7], kd, f32(5),
            host[2], f32(6))


def slot_pair(ssm, tail, slot, fresh):
    """``slot``'s recurrent pair ``([1, S, Di], [1, d_conv - 1, Di])`` as a
    chunk starts from it: zeros when the chunk is the sequence's first."""
    h0 = jax.lax.dynamic_slice_in_dim(ssm, slot, 1, 0)
    t0 = jax.lax.dynamic_slice_in_dim(tail, slot, 1, 0)
    return (jnp.where(fresh, jnp.zeros_like(h0), h0),
            jnp.where(fresh, jnp.zeros_like(t0), t0))
