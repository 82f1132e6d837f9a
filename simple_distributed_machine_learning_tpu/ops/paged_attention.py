"""Pallas paged attention: fused block-table gather + flash-style decode.

The serving hot path (``models/gpt.py::_paged_decode_fwd`` and the
speculative ``_paged_verify_fwd``) historically did the standard two-pass
dance every tick: gather each slot's physical K/V blocks into a dense
``[S, H, span, dh]`` row buffer (one full HBM read of resident K/V plus a
full write of the gathered copy), then dense masked attention over that
buffer (a second full read). This module fuses the two into ONE Pallas
kernel pass, following the grid/online-softmax structure of
``ops/flash_attention.py``:

- **block-table-indexed gather**: the per-slot block table and query
  positions ride in as scalar-prefetch operands
  (``pltpu.PrefetchScalarGridSpec``), so the K/V BlockSpec index maps
  dereference ``tables[s, kb]`` directly — each physical block streams from
  HBM into VMEM exactly once per tick, already in sequence order, and no
  gathered dense copy ever exists;
- **online softmax** (flash style): the k-block grid axis is innermost and
  carries ``(acc, l, m)`` scratch across iterations, so the ``[K, span]``
  score matrix is never materialized and VMEM holds O(H·K·dh + H·bs·dh);
- **past-the-end fetch elision**: k-blocks wholly past the newest query
  position are predicated off with ``pl.when``, and the index map clamps
  their block id at the last needed one — an unchanged index between
  iterations means Mosaic's pipeline issues no HBM copy (the
  ``_diag_kv_index`` trick from the causal kernel, applied to the
  position mask instead of the diagonal);
- **fused dequantization**: int8/fp8 K/V blocks carry per-row (position x
  head) f32 scales; the kernel multiplies them back in VMEM right after the
  block load, so a quantized pool pays the narrow dtype's HBM bytes without
  a separate dequantize pass (the whole point of quantizing: the decode
  tick is memory-bound on exactly this stream);
- **f32 score/accumulator math**: K/V tiles are upcast (or dequantized) to
  f32 before the dots, matching the dense path's einsum promotion — which
  is what keeps greedy decode through this kernel TOKEN-bit-exact against
  the gather-then-dense path (logits agree to accumulation-order ulps;
  tests/test_paged_attention.py pins both).

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


#: K/V blocks a grid cell reads (each through an operand of its own, so
#: their fetches are in flight together) and attends as ONE span of
#: ``n * bs`` positions. What bounds the kernel is neither bytes nor
#: fetches but the dependent chain of a cell (scores, row maximum,
#: exponential, weighted sum, the scratch's read-modify-write): about a
#: microsecond whatever the block holds (``PERF.md``, PR 29), so a cell takes
#: as many blocks as divide the table and fit the kernel's fast memory
_BLOCKS_PER_CELL = 4
_KV_VMEM_BYTES = 4 << 20


def _paged_attn_kernel(tables_ref, qpos_ref, q_ref, *rest, bs: int,
                       n_q: int, scale: float, quant: bool, n_sub: int):
    """One (slot, span of ``n_sub`` k-blocks) grid cell; the k axis is
    innermost and carries the online-softmax state.

    ``q_ref``: [1, H, K, dh] (this slot's queries, all heads); then
    ``n_sub`` K refs and ``n_sub`` V refs, each [1, H, bs, dh] — the
    PHYSICAL blocks the index maps dereferenced through the slot's table,
    consecutive logical blocks of the sequence; with ``quant``, as many
    ``ks``/``vs`` refs [1, H, bs], the per-row dequant scales of the same
    blocks; ``o_ref``: [1, H, K, dh] f32. Scratch: ``acc`` [H, K, dh] f32
    and the lane-broadcast ``l``/``m`` [H, K, _LANES] f32
    (flash_attention's scratch idiom). ``H`` and ``dh`` are the CALL's: the
    wrapper hands a rows-in-lanes pool over as one stream (``H = 1``) whose
    ``dh`` is the whole row."""
    k_refs, v_refs = rest[:n_sub], rest[n_sub:2 * n_sub]
    rest = rest[2 * n_sub:]
    ks_refs = vs_refs = (None,) * n_sub
    if quant:
        ks_refs, vs_refs = rest[:n_sub], rest[n_sub:2 * n_sub]
        rest = rest[2 * n_sub:]
    o_ref, acc_scr, l_scr, m_scr = rest
    s_idx = pl.program_id(0)
    kb = pl.program_id(1)
    n_kb = pl.num_programs(1)
    span = n_sub * bs

    @pl.when(kb == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        l_scr[...] = jnp.zeros_like(l_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)

    def rows(refs, scale_refs):
        """The cell's K or V rows, [H, span, dh] f32 (dequantized)."""
        parts = []
        for ref, sc in zip(refs, scale_refs):
            x = ref[0].astype(jnp.float32)                # [H, bs, dh]
            parts.append(x if sc is None else x * sc[0][..., None])
        return parts[0] if n_sub == 1 else jnp.concatenate(parts, axis=1)

    # spans wholly past the newest query position contribute nothing —
    # skip (their fetches are elided by the index-map clamp below, which
    # also hands a live span's own past-the-end blocks the last live one
    # again: the position mask removes them)
    @pl.when(kb * span <= qpos_ref[s_idx, n_q - 1])
    def _compute():
        # per-query positions of this slot (K is static and small)
        qp = jnp.stack([qpos_ref[s_idx, j] for j in range(n_q)])
        q = q_ref[0].astype(jnp.float32)                  # [H, K, dh]
        k = rows(k_refs, ks_refs)                         # [H, span, dh]
        v = rows(v_refs, vs_refs)
        # scores in f32 — the dense path's einsum promotion, so the fused
        # logits track the gather-then-dense ones to ulps
        s = lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,)))) * scale
        kpos = kb * span + lax.broadcasted_iota(jnp.int32, (1, n_q, span), 2)
        mask = kpos <= qp[None, :, None]                  # [1, K, span]
        s = jnp.where(mask, s, NEG_INF)                   # [H, K, span]
        m_prev = m_scr[..., 0]                            # [H, K]
        l_prev = l_scr[..., 0]
        m_new = jnp.maximum(m_prev, s.max(axis=2))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        acc_scr[...] = (acc_scr[...] * corr[..., None]
                        + lax.dot_general(p, v,
                                          (((2,), (1,)), ((0,), (0,)))))
        l_scr[...] = jnp.broadcast_to(
            (l_prev * corr + p.sum(axis=2))[..., None], l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new[..., None], m_scr.shape)

    @pl.when(kb == n_kb - 1)
    def _finalize():
        l = l_scr[..., 0]
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l, 1e-30)[..., None]).astype(o_ref.dtype)


def _attend_blocks(q, kc, vc, tables, qpos, bs, scale, kscale, vscale,
                   interpret=None):
    """The Pallas call over head-major operands: ``q`` [S, H, K, dh],
    ``kc``/``vc`` [n_blocks+1, H, bs, dh], scales [n_blocks+1, H, bs] or
    None, ``qpos`` [S, K] (non-decreasing along K). Returns f32
    [S, H, K, dh]."""
    if interpret is None:
        interpret = _interpret()
    S, H, K, dh = q.shape
    NB = tables.shape[1]
    quant = kscale is not None
    # the pool goes in once per block of a cell (the same buffer under
    # another index map): logical block kb * n_sub + g through operand g.
    # One block a cell where the blocks are not whole f32 sublane tiles
    # (their rows could not be joined in place)
    block_bytes = H * bs * dh * kc.dtype.itemsize
    n_sub = next(g for g in (_BLOCKS_PER_CELL, 2, 1) if g == 1 or (
        NB % g == 0 and bs % 8 == 0
        and 4 * g * block_bytes <= _KV_VMEM_BYTES))

    def _block(g):
        def index(s, kb, tables_ref, qpos_ref):
            # past-the-end fetch elision: clamp at the newest query's block
            # so skipped blocks revisit it (no HBM copy when unchanged)
            last = qpos_ref[s, K - 1] // bs
            return tables_ref[s, jnp.minimum(kb * n_sub + g, last)]

        return index

    def _q_idx(s, kb, tables_ref, qpos_ref):
        return (s, 0, 0, 0)

    def _specs(block, tail):
        return [pl.BlockSpec(block, lambda *a, i=_block(g): (i(*a), *tail))
                for g in range(n_sub)]

    in_specs = ([pl.BlockSpec((1, H, K, dh), _q_idx)]
                + 2 * _specs((1, H, bs, dh), (0, 0, 0)))
    operands = [q] + [kc] * n_sub + [vc] * n_sub
    if quant:
        in_specs += 2 * _specs((1, H, bs), (0, 0))
        operands += [kscale] * n_sub + [vscale] * n_sub

    vma = _vma_of(q, kc, vc)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, NB // n_sub),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, K, dh), _q_idx),
        scratch_shapes=[
            pltpu.VMEM((H, K, dh), jnp.float32),
            pltpu.VMEM((H, K, _LANES), jnp.float32),
            pltpu.VMEM((H, K, _LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_attn_kernel, bs=bs, n_q=K, scale=scale,
                          quant=quant, n_sub=n_sub),
        grid_spec=grid_spec,
        out_shape=_struct((S, H, K, dh), jnp.float32, vma),
        # slots are independent; the k axis carries scratch state
        compiler_params=_compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name="paged_attention",
    )(tables.astype(jnp.int32), qpos.astype(jnp.int32), *operands)


def paged_attention(q: jax.Array, kc: jax.Array, vc: jax.Array,
                    tables: jax.Array, qpos: jax.Array, *,
                    block_size: int, kscale: jax.Array | None = None,
                    vscale: jax.Array | None = None) -> jax.Array:
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

    Returns f32 [S, H, K, dh]: exactly what the dense-math path's masked
    softmax-attention einsum pair produces over the gathered span, with
    rows past each query's position masked out (trash-table entries
    included, same as the dense mask).

    The work is one jitted function: a program calls it once per layer at
    the same shapes, and traces and lowers the kernel once for all of them
    (36 lowerings of it were 6 s of ``gpt2-large.serve-closed``'s set-up).
    """
    return _paged_attention(q, kc, vc, tables, qpos, kscale, vscale,
                            bs=int(block_size), interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def _paged_attention(q, kc, vc, tables, qpos, kscale, vscale, *, bs,
                     interpret):
    S, H, n_q, dh = q.shape
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
    # kv; each group's last row holds the newest position, which is all the
    # kernel asks of their order
    q = q.reshape(S, kvh, rows, dh)
    if quant and kvh > 1:
        def heads_first(a):
            return jnp.swapaxes(a.reshape(n_phys, bs, kvh, -1), 1, 2)

        out = _attend_blocks(
            q, heads_first(kc), heads_first(vc), tables,
            jnp.tile(qpos, (1, H // kvh)), bs, scale,
            jnp.swapaxes(kscale, 1, 2), jnp.swapaxes(vscale, 1, 2),
            interpret)
        return out.reshape(S, H, n_q, dh)
    if kvh > 1:
        own = jnp.eye(kvh, dtype=q.dtype)[None, :, None, :, None]
        q = q[:, :, :, None, :] * own             # [S, KVH, rows, KVH, dh]
    out = _attend_blocks(
        q.reshape(S, 1, kvh * rows, width), kc[:, None], vc[:, None],
        tables, jnp.tile(qpos, (1, H)), bs, scale,
        kscale.reshape(n_phys, 1, bs) if quant else None,
        vscale.reshape(n_phys, 1, bs) if quant else None, interpret)
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
