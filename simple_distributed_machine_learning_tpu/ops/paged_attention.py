"""Pallas paged attention: fused block-table gather + flash-style decode.

The serving hot path (``models/gpt.py::_paged_decode_fwd`` and the
speculative ``_paged_verify_fwd``) historically did the standard two-pass
dance every tick: gather each slot's physical K/V blocks into a dense
``[S, H, span, dh]`` row buffer (one full HBM read of resident K/V plus a
full write of the gathered copy), then dense masked attention over that
buffer (a second full read). This module fuses the two into ONE Pallas
kernel pass (the structure of JAX's own paged-attention kernel):

- **a slot's own blocks, copied by the kernel**: the grid is one cell a
  slot. The per-slot block table and query positions ride in as
  scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``), the pool's
  buffers go in unblocked (``memory_space=pl.ANY``), and the cell loops
  over the slot's live SPANS only, ``newest position // span + 1`` trips:
  each trip's blocks are copied by ``pltpu.make_async_copy`` through
  ``tables[s, b]`` into one contiguous double-buffered VMEM span, the next
  span's copies started before the current one is waited for, and under a
  slot's last span the NEXT slot's first one. A seat that sits a tick out
  (position 0 of an all-trash table) costs one trip, a 300-position slot
  two (the grid before PR 33 stepped through 16 cells for either, at two
  thirds of a microsecond a skipped cell), and no gathered dense copy ever
  exists;
- **copies the scalar core can issue fast**: on the chip a trip is mostly
  the issuing of its block copies and the waiting for them, not the bytes
  and not the two products (``PERF.md`` section 6, PR 43: on a 256-lane
  row 0.87 of a span's 1.10 us, the products 0.19). So a stream's blocks
  of a span all signal ONE semaphore and the span is waited for ONCE a
  stream, through a descriptor over the whole buffer half (a DMA
  semaphore counts bytes); the block loop is traced once and lowered
  unrolled; and the starts sit under a branch a buffer half, so that
  every destination offset is static;
- **online softmax** (flash style): ``(m, l, acc)`` are the loop's carries,
  written to the output once, so the ``[K, max_len]`` score matrix is never
  materialized;
- **nothing past a slot's length**: a span's blocks past the newest query
  position are fetched as the newest block again and removed by the
  position mask; a table entry past it is never read;
- **a window layer's walk** (``window=``): a layer whose queries look
  back ``window`` positions and no further (query ``t`` attends keys ``j``
  with ``t - window < j <= t``) starts at the span that holds the oldest
  query's ``qpos - window + 1``, and the mask adds ``qpos - j < window``.
  Its table is a RING (``serve/slots.py``, "Layer kinds"): logical block
  ``j`` lives at entry ``j % NB``, and an entry whose block lies wholly
  behind the window has been handed back to the pool (it reads TRASH, or
  already names the block of ``j + NB``). So the first span's blocks
  before the first live one fetch THAT one again, as the blocks past the
  newest fetch the newest: no block behind the window is ever fetched,
  and the position mask, which counts logical positions, removes what the
  stand-ins hold. With ``window=None`` none of this is traced;
- **one stream** (``vc=None``, ``v_lanes=``): an ABSORBED latent cache
  (``models/kimi_linear.py``) holds ONE row a position, the normed latent
  and then the key lanes all heads share, and the row's leading
  ``v_lanes`` lanes ARE the values. The kernel then copies one stream (a
  span's blocks once, where handing the same buffer over as ``kc`` and
  ``vc`` would copy every block twice and halve what the cache was built
  to save), takes the values as that lane slice of the span it already
  holds, and writes ``v_lanes`` lanes a query row; every query head is a
  row over the one stream. With ``vc`` given none of this is traced;
- **fused dequantization**: int8/fp8 K/V blocks carry per-row (position x
  head) f32 scales, copied beside them; the kernel multiplies them back in
  VMEM right after the load, so a quantized pool pays the narrow dtype's
  HBM bytes without a separate dequantize pass (the whole point of
  quantizing: the decode tick is memory-bound on exactly this stream);
- **f32 score/accumulator math**: K/V tiles are upcast (or dequantized) to
  f32 before the dots, matching the dense path's einsum promotion — which
  is what keeps greedy decode through this kernel TOKEN-bit-exact against
  the gather-then-dense path (logits agree to accumulation-order ulps;
  tests/test_paged_attention.py pins both). What a float32 product IS
  depends on who runs it: the CPU interpreter multiplies in float32; on
  the chip Mosaic, like XLA for the dense path's einsums, multiplies
  float32 operands at default precision in ONE bfloat16 pass (measured,
  PR 43: casting ``q`` and ``p`` to bfloat16 and leaving K/V unwidened
  gives the kernel's output bit for bit, at the same speed). So there
  are no extra passes over K and V to save, and carrying ``q`` and ``p``
  as three stacked bfloat16 terms (float32 to the last bit in one
  product) RAISES the chip's precision at 6-48 % more kernel time: not
  built. Mask, scale, ``exp`` and the softmax state are float32
  everywhere.

On non-TPU backends the same kernel runs in Pallas interpret mode
(``flash_attention._interpret``), so the serving engine's ``kernel="fused"``
path is exercised hermetically on CPU. One kernel serves both tick shapes:
the single-query flash-decode tick is the ``K = 1`` case of the K-token
speculative verify.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from simple_distributed_machine_learning_tpu.ops.flash_attention import (
    _LANES,
    NEG_INF,
    _compiler_params,
    _interpret,
    _struct,
    _vma_of,
    pltpu,
)


#: blocks of one span at most, and the fast memory the two double-buffered
#: span buffers (K and V, two halves each) may take. Measured on the chip
#: (``PERF.md`` section 6, PR 33): the grid this loop replaced, ``(slots,
#: max_len / 4 blocks)``, paid 0.66-0.70 us for every cell it SKIPPED and
#: 1.17-1.84 us for a live one, so a call was mostly its grid; here a
#: further span of 16 blocks costs a slot 0.4-0.6 us, against 3-7 us a slot
#: costs before its first span, so a span is as long as the table and the
#: buffers allow (16 beat 8 and 4 at all three cells' shapes)
_SPAN_BLOCKS = 16
_KV_VMEM_BYTES = 4 << 20


def _span_blocks(bs: int, block_bytes: int, n_table: int, itemsize: int):
    """Blocks of one span, from what the call can see. A block lands at row
    ``g * bs`` of the span buffer, which Mosaic takes only where a block is
    whole sublane tiles of the POOL's dtype (8 rows of 4 bytes, 16 of 2, 32
    of 1); where it is not, a span is one block. Otherwise the largest
    power of two up to ``_SPAN_BLOCKS`` that the table holds and whose four
    buffers fit ``_KV_VMEM_BYTES``."""
    if bs % (32 // itemsize):
        return 1
    n = _SPAN_BLOCKS
    while n > 1 and (n > n_table or 4 * n * block_bytes > _KV_VMEM_BYTES):
        n //= 2
    return n


def _whole_lanes(a):
    """``a`` with its last axis zero-padded to whole lane tiles."""
    pad = -a.shape[-1] % _LANES
    return lax.pad(a, jnp.zeros((), a.dtype), [(0, 0, 0)] * (a.ndim - 1)
                   + [(0, pad, 0)]) if pad else a


def _paged_attn_kernel(tables_ref, qpos_ref, q_ref, *rest, bs: int,
                       n_q: int, scale: float, quant: bool, n_sub: int,
                       nested: bool = False, window: int | None = None,
                       ring: int = 0, v_lanes: int | None = None):
    """One slot: a loop over the slot's live spans of ``n_sub`` blocks.

    ``q_ref``: [1, H, R, dh], this slot's query rows, all heads: row ``r``
    stands at position ``qpos[s, r % n_q]``; ``k_hbm`` / ``v_hbm``: the
    pool's buffers where they lie, [n_blocks+1, H, bs, dh]; with ``quant``
    the scale planes ``ks_hbm`` / ``vs_hbm`` [n_blocks+1, H, bs in whole
    lane tiles]; ``o_ref``: [1, H, R, dh] f32. Scratch, all of it kept from
    one slot to the next: the double-buffered spans ``kbuf`` / ``vbuf`` [2,
    H, n_sub * bs, dh] in the pool's dtype (with ``quant`` ``ksbuf`` /
    ``vsbuf`` [2, n_sub, H, lanes] f32), the DMA semaphores [streams, 2]
    (one a stream and half: a span's blocks all signal it), and ``first``
    (SMEM [1]): the buffer half that holds this slot's first span. ``H``
    and ``dh`` are the CALL's: the wrapper hands a rows-in-lanes pool over
    as one stream (``H = 1``) whose ``dh`` is the whole row. ``window``: the
    layer's window in positions (module docstring), its table a ring of
    ``ring`` entries; every line it adds is under ``window is not None``.
    ``v_lanes``: ONE stream (module docstring): no ``v_hbm``, no ``vbuf``,
    the values the leading ``v_lanes`` lanes of the key rows, ``o_ref`` [1,
    H, R, v_lanes]; plain pools only."""
    n_streams = 1 if v_lanes is not None else 4 if quant else 2
    hbm, rest = rest[:n_streams], rest[n_streams:]
    o_ref, rest = rest[0], rest[1:]
    bufs, (sem, first) = rest[:n_streams], rest[n_streams:]
    s_idx = pl.program_id(0)
    span = n_sub * bs
    trips = lax.div(qpos_ref[s_idx, n_q - 1], span) + 1  # newest position

    def live_from(slot):
        """The first position ``slot``'s oldest query still sees."""
        return lax.max(qpos_ref[slot, 0] - (window - 1), 0)

    def trip0(slot):
        """The span ``slot``'s walk starts at: 0, or (a window layer) the
        one that holds the oldest query's ``qpos - window + 1``."""
        return 0 if window is None else lax.div(live_from(slot), span)

    def start(slot, it, half: int):
        """Start the DMAs of ``slot``'s span ``it`` into buffer half
        ``half``: block ``g`` of every stream, through the slot's table. A
        span's blocks past the slot's newest one fetch that one again (the
        position mask removes them; a table entry past it is never read).
        The kernel is bound by how fast the scalar core issues these (a
        descriptor of 8-40 KB each; ``PERF.md`` section 6, PR 43), so the
        half is a Python int (every destination offset static) and the
        blocks are a loop traced ONCE and lowered unrolled: ``n_sub``
        copies of its body at trace time cost 2.2 s of a program's set-up
        (PR 33), the rolled loop a quarter of a call."""
        last_blk = lax.div(qpos_ref[slot, n_q - 1], bs)

        if window is not None:
            first_blk = lax.div(live_from(slot), bs)

        def block(g, _):
            if window is None:
                blk = tables_ref[slot, lax.min(it * n_sub + g, last_blk)]
            else:
                blk = tables_ref[slot, lax.rem(lax.max(lax.min(
                    it * n_sub + g, last_blk), first_blk), ring)]
            at = pl.ds(pl.multiple_of(g * bs, bs), bs)
            for w, (ref, buf) in enumerate(zip(hbm, bufs)):
                dst = buf.at[half, :, at] if w < 2 else buf.at[half, g]
                pltpu.make_async_copy(ref.at[blk], dst,
                                      sem.at[w, half]).start()

        lax.fori_loop(0, n_sub, block, None, unroll=True)

    def wait(half):
        """Wait for the span in buffer half ``half``: a stream's ``n_sub``
        block copies signal ONE semaphore, and one wait a stream, its
        descriptor the whole half, takes the span's bytes off it."""
        for w, buf in enumerate(bufs):
            pltpu.make_async_copy(buf.at[half], buf.at[half],
                                  sem.at[w, half]).wait()

    def rows(buf, sc, half):
        """The span's K or V rows, [H, span, dh] f32 (dequantized)."""
        x = buf[half].astype(jnp.float32)
        if sc is None:
            return x
        parts = [x[:, g * bs:(g + 1) * bs] * sc[half, g, :, :bs][..., None]
                 for g in range(n_sub)]
        return parts[0] if n_sub == 1 else jnp.concatenate(parts, axis=1)

    def slot():
        @pl.when(s_idx == 0)
        def _first():           # nobody before the first slot fetched for it
            first[0] = 0
            start(0, trip0(0), 0)

        q = q_ref[0].astype(jnp.float32)                  # [H, R, dh]
        H, R, dh = q.shape
        # the rows' positions, in the sublanes as the scores' rows are
        qp = jnp.full((1, R, 1), qpos_ref[s_idx, 0], jnp.int32)
        row = lax.rem(lax.broadcasted_iota(jnp.int32, (1, R, 1), 1), n_q)
        for j in range(1, n_q):
            qp = jnp.where(row == j, qpos_ref[s_idx, j], qp)
        half0 = first[0]
        more = s_idx + 1 < pl.num_programs(0)
        it0 = trip0(s_idx)
        since = lambda it: it if window is None else it - it0  # noqa: E731

        def trip(it, carry):
            """Span ``it``: start what comes after it, wait for it, fold it
            into the online-softmax state ``(m, l, acc)``: [H, R, 1] twice
            and [H, R, dh], float32."""
            m_prev, l_prev, acc = carry
            half = lax.rem(half0 + since(it), 2)
            # in flight meanwhile: the slot's next span, and under its last
            # span the NEXT slot's first one, so that a call waits for a
            # copy it has not overlapped once, not once a slot
            mine = it + 1 < trips

            for h in (0, 1):        # a branch a half: static offsets
                @pl.when((mine | more) & (half == h))
                def _next(h=h):
                    start(jnp.where(mine, s_idx, s_idx + 1),
                          jnp.where(mine, it + 1, 0 if window is None else
                                    trip0(lax.min(s_idx + 1,
                                                  pl.num_programs(0) - 1))),
                          1 - h)

            wait(half)
            k = rows(bufs[0], bufs[2] if quant else None, half)
            v = (k[..., :v_lanes] if v_lanes is not None
                 else rows(bufs[1], bufs[3] if quant else None, half))
            # scores in f32 — the dense path's einsum promotion, so the
            # fused logits track the gather-then-dense ones to ulps
            s = lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,)))) * scale
            kpos = it * span + lax.broadcasted_iota(
                jnp.int32, (1, R, span), 2)
            mask = kpos <= qp                             # [1, R, span]
            if window is not None:
                mask &= qp - kpos < window
            s = jnp.where(mask, s, NEG_INF)               # [H, R, span]
            m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            acc = acc * corr + lax.dot_general(
                p, v, (((2,), (1,)), ((0,), (0,))))
            return (m_new, l_prev * corr + p.sum(axis=2, keepdims=True),
                    acc)

        _, l, acc = lax.fori_loop(it0, trips, trip, (
            jnp.full((H, R, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, R, 1), jnp.float32),
            jnp.zeros((H, R, dh if v_lanes is None else v_lanes),
                      jnp.float32)))
        first[0] = lax.rem(half0 + since(trips), 2)  # the next slot's start
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    # ``nested``: the interpreter under ``shard_map`` alone. It holds the
    # kernel's top-level equations to the mesh axes the pool varies over,
    # which a kernel body (traced with that typing off) cannot state; what
    # lies inside a branch it leaves alone. Every slot has a first span
    (pl.when(trips > 0)(slot) if nested else slot())


def _attend_blocks(q, kc, vc, tables, qpos, bs, scale, kscale, vscale,
                   interpret=None, window=None, v_lanes=None):
    """The Pallas call over head-major operands: ``q`` [S, H, K, dh],
    ``kc``/``vc`` [n_blocks+1, H, bs, dh], scales [n_blocks+1, H, bs] or
    None, ``qpos`` [S, n_q]: query row ``r`` of a head stands at position
    ``qpos[s, r % n_q]`` (``K`` a multiple of ``n_q``), and the last
    column is the slot's newest position. ``window``: ``tables`` is a
    window layer's ring (module docstring). ``v_lanes``: ``vc`` is
    ``None`` and the values are the leading ``v_lanes`` lanes of ``kc``'s
    rows (one stream; then f32 [S, H, K, v_lanes] comes back). Returns f32
    [S, H, K, dh]."""
    if interpret is None:
        interpret = _interpret()
    NB = tables.shape[1]
    quant = kscale is not None
    # the kernel copies a block itself, and Mosaic copies whole lane tiles
    # of the source only: a stream whose rows are not whole tiles (a toy
    # width; every scale plane, whose row is a block's positions) is
    # padded with zeros, which a score and a row's output add exactly
    n_kv = 2 if v_lanes is None else 1
    streams = [kc, vc][:n_kv] + ([kscale, vscale] if quant else [])
    held = sum(math.prod(a.shape[1:]) * a.dtype.itemsize for a in streams)
    dh_call = q.shape[-1]
    q, *streams = (_whole_lanes(a) for a in (q, *streams))
    kc = streams[0]
    S, H, K, dh = q.shape
    dv = dh if v_lanes is None else v_lanes
    # what the call moves at most: the query block in and the output block
    # back, and every slot's whole table span of every stream once, as the
    # pool holds it. The analyzer reads the K/V stream of operands it
    # cannot see blocked from this (analysis/kernels.py::kernel_hbm_costs)
    moved = q.size * (q.dtype.itemsize + 4) + S * NB * held
    block_bytes = H * bs * dh * kc.dtype.itemsize
    n_sub = _span_blocks(bs, block_bytes, NB, kc.dtype.itemsize)
    span = n_sub * bs

    def _q_idx(s, tables_ref, qpos_ref):
        return (s, 0, 0, 0)

    # the pool's buffers stay where they are: the kernel copies what a
    # slot has, block by block
    in_specs = ([pl.BlockSpec((1, H, K, dh), _q_idx)]
                + [pl.BlockSpec(memory_space=pl.ANY)] * len(streams))
    scratch = [pltpu.VMEM((2, H, span, dh), kc.dtype)] * n_kv
    if quant:
        scratch += [pltpu.VMEM((2, n_sub, *streams[2].shape[1:]),
                               kscale.dtype)] * 2
    scratch += [pltpu.SemaphoreType.DMA((len(streams), 2)),
                pltpu.SMEM((1,), jnp.int32)]

    vma = _vma_of(q, *streams[:n_kv])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, K, dv), _q_idx),
        scratch_shapes=scratch,
    )
    # what only a window layer's or a one-stream call's kernel is told
    extra = {} if window is None else {"window": window, "ring": NB}
    if v_lanes is not None:
        extra["v_lanes"] = v_lanes
    return pl.pallas_call(
        functools.partial(_paged_attn_kernel, bs=bs, n_q=qpos.shape[1],
                          scale=scale, quant=quant, n_sub=n_sub,
                          nested=bool(interpret and vma), **extra),
        grid_spec=grid_spec,
        out_shape=_struct((S, H, K, dv), jnp.float32, vma),
        # in order: a slot fetches its successor's first span
        compiler_params=_compiler_params("arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=2 * S * H * K * NB * bs * (dh + dv),
            transcendentals=S * H * K * NB * bs,
            bytes_accessed=moved),
        interpret=interpret,
        name="paged_attention",
    )(tables.astype(jnp.int32), qpos.astype(jnp.int32), q,
      *streams)[..., :dh_call if v_lanes is None else v_lanes]


def paged_attention(q: jax.Array, kc: jax.Array, vc: jax.Array | None,
                    tables: jax.Array, qpos: jax.Array, *,
                    block_size: int, kscale: jax.Array | None = None,
                    vscale: jax.Array | None = None,
                    window: int | None = None, v_lanes: int | None = None,
                    scale: float | None = None) -> jax.Array:
    """Fused paged attention over one layer's physical block pool.

    ``q``: [S, H, K, dh] queries (K = 1 for the flash-decode tick, the
    speculative width for verify); ``kc``/``vc``: [n_blocks+1, bs, KVH*dh],
    one layer's pool buffer as ``serve/slots.py::PagedKVPool`` holds it: a
    position's K/V heads side by side in the lanes, trash block 0
    included; ``tables``: [S, NB] int32 logical->physical ids; ``qpos``:
    [S, K] int32 query positions, NON-DECREASING along K (the engine's
    ``pos + j`` plan). With a quantized pool pass ``kscale``/``vscale``
    [n_blocks+1, bs, KVH] — the per-(position, head) f32 dequant scales —
    and int8/fp8 ``kc``/``vc``.

    ``window``: the layer attends ``window`` positions back and no further
    (query ``t`` sees keys ``t - window < j <= t``), and ``tables`` is its
    group's RING (``serve/slots.py::PagedKVPool.device_table(slot, g)``):
    logical block ``j`` at entry ``j % NB``. An entry whose block lies
    wholly behind the oldest query's window is a RELEASED block's: it may
    read TRASH or already name a newer block, and the kernel never looks
    it up (module docstring). ``NB * block_size`` must cover ``window`` and
    the ``K`` query positions; plain pools only (no scale planes).

    The pool is handed to the kernel as it lies: ONE K/V stream whose row
    is the whole ``KVH*dh`` lanes, the ``H`` query heads its group rows.
    Each head's query sits in its own K/V head's ``dh`` lanes of a zeroed
    row, so a score is the same sum with exact zeros added, and the head's
    output is that lane block of its row (taken here, outside the kernel).
    Nothing of pool size is sliced, padded or transposed. ``KVH`` is read
    off the shapes (``kc.shape[-1] / dh``) and must divide ``H``: query
    head ``h`` reads K/V head ``h // (H / KVH)``; multi-query (``KVH = 1``)
    is the case where the row is one head wide and no lane is zero.

    A quantized pool with SEVERAL heads in a row cannot take that path
    with the kernel body as it is (a row's heads carry different scales,
    which the body applies per K/V row): it is laid out head-major for
    the call, ``[n_blocks+1, KVH, bs, dh]``, one copy of the layer per
    tick. No benchmark cell runs one (``ROADMAP.md`` D4).

    ``vc=None``: ONE stream (module docstring). ``kc`` [n_blocks+1, bs, D]
    holds one row a position that every one of the ``H`` query heads reads,
    ``q`` is [S, H, K, D] (a query laid out in the row's lanes), the values
    are the row's leading ``v_lanes`` lanes, and ``scale`` multiplies the
    scores (the row is no head, so its width says nothing about it: both
    must be given). Returns f32 [S, H, K, v_lanes]. Plain pools, no window.

    Returns f32 [S, H, K, dh]: exactly what the dense-math path's masked
    softmax-attention einsum pair produces over the gathered span, with
    rows past each query's position masked out (trash-table entries
    included, same as the dense mask).

    The work is one jitted function: a program calls it once per layer at
    the same shapes, and traces and lowers the kernel once for all of them
    (36 lowerings of it were 6 s of ``gpt2-large.serve-closed``'s set-up).
    """
    if vc is None:
        if None in (v_lanes, scale) or not (
                kscale is None and vscale is None and window is None):
            raise ValueError(
                "one stream (vc=None) takes v_lanes= and scale=, and neither "
                "scale planes nor a window")
        return _paged_attention(q, kc, None, tables, qpos, None, None,
                                bs=int(block_size), interpret=_interpret(),
                                v_lanes=int(v_lanes), scale=float(scale))
    if v_lanes is not None or scale is not None:
        raise ValueError("v_lanes= and scale= belong to one stream "
                         "(vc=None)")
    if window is None:
        return _paged_attention(q, kc, vc, tables, qpos, kscale, vscale,
                                bs=int(block_size), interpret=_interpret())
    if kscale is not None or vscale is not None:
        raise ValueError("a window layer's pool carries no scale planes")
    if window < 1:
        raise ValueError(f"window must be >= 1 position, got {window}")
    return _paged_attention(q, kc, vc, tables, qpos, None, None,
                            bs=int(block_size), interpret=_interpret(),
                            window=int(window))


@functools.partial(jax.jit, static_argnames=("bs", "interpret", "window",
                                             "v_lanes", "scale"))
def _paged_attention(q, kc, vc, tables, qpos, kscale, vscale, *, bs,
                     interpret, window=None, v_lanes=None, scale=None):
    S, H, n_q, dh = q.shape
    if vc is None:
        # one stream: every head a row over the one row a position holds
        if kc.shape[1:] != (bs, dh) or not 0 < v_lanes <= dh:
            raise ValueError(
                f"one stream: kc must be [n_blocks+1, {bs}, {dh}] (the "
                f"queries' lanes) with 0 < v_lanes <= {dh}, got {kc.shape} "
                f"and v_lanes={v_lanes}")
        out = _attend_blocks(q.reshape(S, 1, H * n_q, dh), kc[:, None], None,
                             tables, qpos, bs, scale, None, None, interpret,
                             v_lanes=v_lanes)
        return out.reshape(S, H, n_q, v_lanes)
    if kc.ndim != 3 or kc.shape[1] != bs:
        raise ValueError(f"kc must be [n_blocks+1, {bs}, KVH*dh], got "
                         f"{kc.shape}")
    n_phys, _, width = kc.shape
    kvh = width // dh
    if width % dh or H % kvh:
        raise ValueError(f"the pool's rows of {width} lanes ({kvh} K/V "
                         f"heads of {dh}) do not divide the {H} query heads")
    quant = kscale is not None
    if quant != (vscale is not None):
        raise ValueError("pass both kscale and vscale, or neither")
    scale = 1.0 / math.sqrt(dh)
    rows = (H // kvh) * n_q
    # head h = kv * group + g: its queries are rows g * n_q + k of K/V head
    # kv, and row r of any head stands at qpos[s, r % n_q]: what the kernel
    # asks of their order
    q = q.reshape(S, kvh, rows, dh)
    if quant and kvh > 1:
        def heads_first(a):
            return jnp.swapaxes(a.reshape(n_phys, bs, kvh, -1), 1, 2)

        out = _attend_blocks(
            q, heads_first(kc), heads_first(vc), tables, qpos, bs, scale,
            jnp.swapaxes(kscale, 1, 2), jnp.swapaxes(vscale, 1, 2),
            interpret)
        return out.reshape(S, H, n_q, dh)
    if kvh > 1:
        own = jnp.eye(kvh, dtype=q.dtype)[None, :, None, :, None]
        q = q[:, :, :, None, :] * own             # [S, KVH, rows, KVH, dh]
    out = _attend_blocks(
        q.reshape(S, 1, kvh * rows, width), kc[:, None], vc[:, None],
        tables, qpos, bs, scale,
        kscale.reshape(n_phys, 1, bs) if quant else None,
        vscale.reshape(n_phys, 1, bs) if quant else None, interpret, window)
    out = out.reshape(S, kvh, rows, kvh, dh)
    if kvh > 1:
        out = jnp.moveaxis(jnp.diagonal(out, axis1=1, axis2=3), -1, 1)
    return out.reshape(S, H, n_q, dh)


def paged_flash_decode(q: jax.Array, kc: jax.Array, vc: jax.Array,
                       tables: jax.Array, pos: jax.Array, *,
                       block_size: int, kscale: jax.Array | None = None,
                       vscale: jax.Array | None = None) -> jax.Array:
    """The one-query-per-slot flash-decode tick: ``q`` [S, H, 1, dh],
    ``pos`` [S] — the ``K = 1`` specialization of :func:`paged_attention`
    (the decode tick attends every position ``<= pos[s]``)."""
    return paged_attention(q, kc, vc, tables, pos[:, None],
                           block_size=block_size, kscale=kscale,
                           vscale=vscale)
