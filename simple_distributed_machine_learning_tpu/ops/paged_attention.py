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


def _paged_attn_kernel(tables_ref, qpos_ref, q_ref, k_ref, v_ref, *rest,
                       bs: int, n_q: int, scale: float, quant: bool,
                       packed: bool):
    """One (slot, k-block) grid cell; k-block innermost carries the
    online-softmax state.

    ``q_ref``: [1, H, K, dh] (this slot's queries, all heads);
    ``k_ref``/``v_ref``: [1, H, bs, dh] — the PHYSICAL block the index map
    dereferenced through the slot's table (``packed``: [1, H, dh, bs], the
    block positions living in the 128-lane slot so a small head dim pads
    to sublanes, not lanes); with ``quant``, ``ks_ref``/``vs_ref``:
    [1, H, bs] per-row dequant scales of the same block; ``o_ref``:
    [1, H, K, dh] f32. Scratch: ``acc`` [H, K, dh] f32 and the
    lane-broadcast ``l``/``m`` [H, K, _LANES] f32 (flash_attention's
    scratch idiom)."""
    if quant:
        ks_ref, vs_ref, o_ref, acc_scr, l_scr, m_scr = rest
    else:
        o_ref, acc_scr, l_scr, m_scr = rest
    s_idx = pl.program_id(0)
    kb = pl.program_id(1)
    n_kb = pl.num_programs(1)

    @pl.when(kb == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        l_scr[...] = jnp.zeros_like(l_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)

    # k-blocks wholly past the newest query position contribute nothing —
    # skip (their fetch is elided by the index-map clamp below)
    @pl.when(kb * bs <= qpos_ref[s_idx, n_q - 1])
    def _compute():
        # per-query positions of this slot (K is static and small)
        qp = jnp.stack([qpos_ref[s_idx, j] for j in range(n_q)])
        q = q_ref[0].astype(jnp.float32)                  # [H, K, dh]
        k = k_ref[0].astype(jnp.float32)      # [H, bs, dh] / packed [H, dh, bs]
        v = v_ref[0].astype(jnp.float32)
        if quant:
            scl = (ks_ref[0][:, None, :], vs_ref[0][:, None, :]) \
                if packed else (ks_ref[0][..., None], vs_ref[0][..., None])
            k = k * scl[0]
            v = v * scl[1]
        # scores in f32 — the dense path's einsum promotion, so the fused
        # logits track the gather-then-dense ones to ulps
        kdim = 1 if packed else 2
        s = lax.dot_general(q, k, (((2,), (kdim,)), ((0,), (0,)))) * scale
        kpos = kb * bs + lax.broadcasted_iota(jnp.int32, (1, n_q, bs), 2)
        mask = kpos <= qp[None, :, None]                  # [1, K, bs]
        s = jnp.where(mask, s, NEG_INF)                   # [H, K, bs]
        m_prev = m_scr[..., 0]                            # [H, K]
        l_prev = l_scr[..., 0]
        m_new = jnp.maximum(m_prev, s.max(axis=2))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        vdim = 2 if packed else 1
        acc_scr[...] = (acc_scr[...] * corr[..., None]
                        + lax.dot_general(p, v,
                                          (((2,), (vdim,)), ((0,), (0,)))))
        l_scr[...] = jnp.broadcast_to(
            (l_prev * corr + p.sum(axis=2))[..., None], l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new[..., None], m_scr.shape)

    @pl.when(kb == n_kb - 1)
    def _finalize():
        l = l_scr[..., 0]
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l, 1e-30)[..., None]).astype(o_ref.dtype)


#: f32 sublane quantum — the ``packed`` layout pads the head dim to this
_SUBLANES = 8


def paged_attention(q: jax.Array, kc: jax.Array, vc: jax.Array,
                    tables: jax.Array, qpos: jax.Array, *,
                    block_size: int, kscale: jax.Array | None = None,
                    vscale: jax.Array | None = None,
                    _layout: str = "auto") -> jax.Array:
    """Fused paged attention over one layer's physical block pool.

    ``q``: [S, H, K, dh] queries (K = 1 for the flash-decode tick, the
    speculative width for verify); ``kc``/``vc``: [n_blocks+1, H, bs, dh]
    physical blocks (trash block 0 included); ``tables``: [S, NB] int32
    logical->physical ids; ``qpos``: [S, K] int32 query positions,
    NON-DECREASING along K (the engine's ``pos + j`` plan). With a
    quantized pool pass ``kscale``/``vscale`` [n_blocks+1, H, bs] — the
    per-row f32 dequant scales — and int8/fp8 ``kc``/``vc``.

    Grouped-query attention: the pool may hold FEWER heads than ``q``
    (``kc``/``vc``: [n_blocks+1, KVH, bs, dh] with ``KVH`` dividing ``H``;
    multi-query is ``KVH = 1``). The ``H / KVH`` query heads of a group then
    ride their one K/V block stream as more query rows of it (each with its
    own position), so a block is read once for the whole group and the pool
    never holds a repeated head.

    Returns f32 [S, H, K, dh]: exactly what the dense-math path's masked
    softmax-attention einsum pair produces over the gathered span, with
    rows past each query's position masked out (trash-table entries
    included, same as the dense mask).

    ``_layout`` picks how K/V blocks meet Mosaic's (sublane, lane) tiles:

    - ``"natural"`` — blocks stream as stored, ``[1, H, bs, dh]`` with the
      head dim in the 128-lane slot. Fine when ``dh`` is a lane multiple;
      a small head dim pads every block up to 128 lanes (the ROADMAP #2
      hazard the ``kernel-tile.pad-waste`` lint flags).
    - ``"packed"`` — K/V blocks are transposed once on the host to
      ``[1, H, dh', bs]`` (``dh'`` = ``dh`` rounded up to the f32 sublane
      quantum, 8): block positions take the lane slot, the small head dim
      pads at most 2x into sublanes instead of up to 32x into lanes. The
      zero-padded rows contribute nothing to either dot, so the math is
      identical to ``"natural"``.
    - ``"auto"`` (default) — ``natural`` when ``dh`` is a lane multiple or
      in interpret mode (no tiling there), else ``packed``.
    """
    S, n_q_heads, n_q, dh = q.shape
    NB = tables.shape[1]
    bs = int(block_size)
    if kc.shape[-2] != bs:
        raise ValueError(f"kc block axis {kc.shape[-2]} != block_size {bs}")
    H = kc.shape[1]
    if n_q_heads % H:
        raise ValueError(f"the pool's {H} K/V heads do not divide the "
                         f"{n_q_heads} query heads")
    group = n_q_heads // H
    if group > 1:
        # head h = kv * group + g: the group's queries become rows
        # g * n_q + k of K/V head kv; the last row still holds the newest
        # position, which is all the kernel asks of their order
        q = q.reshape(S, H, group * n_q, dh)
        qpos = jnp.tile(qpos, (1, group))
    K = group * n_q
    quant = kscale is not None
    if quant != (vscale is not None):
        raise ValueError("pass both kscale and vscale, or neither")
    if _layout not in ("auto", "natural", "packed"):
        raise ValueError(f"_layout must be auto/natural/packed, "
                         f"got {_layout!r}")
    scale = 1.0 / math.sqrt(dh)
    interpret = _interpret()
    layout = _layout
    if layout == "auto":
        layout = ("natural" if interpret or dh % _LANES == 0
                  else "packed")
    packed = layout == "packed"
    dp = dh
    if packed:
        dp = dh + (-dh) % _SUBLANES
        if dp != dh:
            pad = [(0, 0)] * 3 + [(0, dp - dh)]
            q = jnp.pad(q, pad)
            kc = jnp.pad(kc, pad)
            vc = jnp.pad(vc, pad)
        # one host-side transpose per tick ([..., bs, dh'] -> [..., dh', bs])
        # beats the old pad-to-128-lanes copy (<= 2x bytes vs up to 32x)
        kc = jnp.swapaxes(kc, -1, -2)
        vc = jnp.swapaxes(vc, -1, -2)

    def _kv_idx(s, kb, tables_ref, qpos_ref):
        # past-the-end fetch elision: clamp at the newest query's block so
        # skipped iterations revisit it (no HBM copy when unchanged)
        last = qpos_ref[s, K - 1] // bs
        return (tables_ref[s, jnp.minimum(kb, last)], 0, 0, 0)

    def _q_idx(s, kb, tables_ref, qpos_ref):
        return (s, 0, 0, 0)

    def _scale_idx(s, kb, tables_ref, qpos_ref):
        last = qpos_ref[s, K - 1] // bs
        return (tables_ref[s, jnp.minimum(kb, last)], 0, 0)

    kv_block = (1, H, dp, bs) if packed else (1, H, bs, dp)
    in_specs = [
        pl.BlockSpec((1, H, K, dp), _q_idx),
        pl.BlockSpec(kv_block, _kv_idx),
        pl.BlockSpec(kv_block, _kv_idx),
    ]
    operands = [q, kc, vc]
    if quant:
        in_specs += [pl.BlockSpec((1, H, bs), _scale_idx),
                     pl.BlockSpec((1, H, bs), _scale_idx)]
        operands += [kscale, vscale]

    vma = _vma_of(q, kc, vc)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, NB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, K, dp), _q_idx),
        scratch_shapes=[
            pltpu.VMEM((H, K, dp), jnp.float32),
            pltpu.VMEM((H, K, _LANES), jnp.float32),
            pltpu.VMEM((H, K, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, bs=bs, n_q=K, scale=scale,
                          quant=quant, packed=packed),
        grid_spec=grid_spec,
        out_shape=_struct((S, H, K, dp), jnp.float32, vma),
        # slots are independent; the k-block axis carries scratch state
        compiler_params=_compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name="paged_attention",
    )(tables.astype(jnp.int32), qpos.astype(jnp.int32), *operands)
    return out[..., :dh].reshape(S, n_q_heads, n_q, dh)


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
