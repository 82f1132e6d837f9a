"""Pallas flash attention: the fused causal-attention kernel for TPU.

The reference has no attention at all (conv+FC only, SURVEY §5.7) and no
custom kernels — its hot ops bottom out in ATen's C++/CUDA kernels
(``/root/reference/simple_distributed.py:42-46,:75-79``; SURVEY §2.3). The
TPU-native analogue of "a hand-tuned native kernel for the hot op" is a
Pallas kernel lowered through Mosaic to the MXU. This module provides one for
the framework's hottest op — causal multi-head attention:

- **blockwise online softmax** (flash style): the [T, T] score matrix is never
  materialized; K/V stream through VMEM one ``block_k`` tile at a time via a
  third grid axis, so VMEM holds O(block_q·d + block_k·d) regardless of T;
- **MXU-shaped tiles**: q/k/v blocks are zero-padded to a 128-lane head dim
  and (block_q, block_k) multiples of the sublane tile, so both matmuls in the
  inner loop land on the 128x128 systolic array;
- **causal block skipping**: k-blocks wholly past the diagonal are predicated
  off with ``pl.when`` (forward) / a diagonal-bounded loop (backward),
  halving FLOPs vs masking a full sweep — and their HBM fetches are elided
  too: the block index maps clamp at the diagonal, so skipped iterations
  revisit the previous block and Mosaic's pipeline issues no copy (without
  the clamp, K/V traffic is rectangular while the work is triangular, and
  the waste grows with T);
- **f32 accumulation** in VMEM scratch regardless of input dtype;
- backward via ``jax.custom_vjp`` recompute: cotangents re-derive the
  attention weights blockwise from the saved (l, m) softmax statistics —
  standard flash-attention-2 practice, no [T, T] residuals.

On non-TPU backends the same kernel runs in Pallas interpret mode, so the
test suite exercises the real kernel code path hermetically on CPU
(tests/test_flash_attention.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # finite -inf stand-in: keeps exp/max NaN-free in the kernel
_LANES = 128     # TPU lane width: head dim is padded to this; l/m scratch width

def _compiler_params(*dimension_semantics: str):
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics))


def _interpret() -> bool:
    """Compiled through Mosaic when the default backend is a TPU; Pallas
    interpret mode on every other backend (the CPU the tests run on has no
    Mosaic lowering). There is no other way to reach ``interpret=True``:
    on a chip the kernels always run compiled."""
    return jax.default_backend() != "tpu"


def _vma_of(*xs) -> frozenset:
    """Union of the operands' varying-manual-axes (vma) sets.

    Under ``shard_map(..., check_vma=True)`` — how every pipeline engine
    here runs — ``pallas_call`` out_shape structs must declare how outputs
    vary over the manual mesh axes, or tracing fails; the kernel's outputs
    vary exactly as its operands do. Outside shard_map this is the empty
    set and changes nothing."""
    from simple_distributed_machine_learning_tpu.parallel.compat import (
        vma_of,
    )
    vma = frozenset()
    for x in xs:
        vma |= vma_of(x)
    return vma


def _struct(shape, dtype, vma: frozenset = frozenset()):
    """``jax.ShapeDtypeStruct`` carrying the vma declaration."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _diag_kv_index(block_q: int, block_k: int):
    """Index map for K/V blocks on a (bh, q-block, k-block) grid, clamped at
    the causal diagonal: k-blocks wholly past the diagonal revisit the last
    needed block, so Mosaic's pipeline elides their HBM fetch (no copy when
    the block index is unchanged between iterations). One copy of the clamp
    arithmetic for the forward and dq passes."""
    def idx(i, j, kb):
        return (i, jnp.minimum(kb, ((j + 1) * block_q - 1) // block_k), 0)
    return idx


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, m_ref,
                  acc_scr, l_scr, m_scr, *,
                  block_q: int, block_k: int, t_real: int, scale: float):
    """One (batch*head, q-block, k-block) grid cell.

    The k-block axis is innermost: for a fixed (bh, q-block), scratch
    (acc, l, m) carries the online-softmax state across k iterations; the
    output block is written on the last one (standard revisiting pattern).

    q_ref: [1, block_q, d]; k_ref/v_ref: [1, block_k, d];
    o_ref: [1, block_q, d]; l_ref/m_ref: [1, 1, block_q] (saved for
    backward — the length-1 middle axis keeps the last-two block dims
    (1, block_q) legal under Mosaic's (8, 128) tiling rule: a 2-D
    [bh, tq] layout with (1, block_q) blocks fails to lower on real TPU);
    l_scr/m_scr: [block_q, 128] f32 (value broadcast across lanes).
    """
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    n_kb = pl.num_programs(2)
    q_start = qi * block_q
    k_start = kb * block_k

    @pl.when(kb == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        l_scr[...] = jnp.zeros_like(l_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)

    # causal: k-blocks wholly past the diagonal contribute nothing — skip
    @pl.when(k_start < q_start + block_q)
    def _compute():
        # dots run in the INPUT dtype (bf16 stays bf16 on the MXU — 3x the
        # f32 throughput) with f32 accumulation via preferred_element_type;
        # only the softmax statistics are f32
        q = q_ref[0]                                      # [bq, d]
        k = k_ref[0]                                      # [bk, d]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = (qpos >= kpos) & (kpos < t_real)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        acc_scr[...] = (acc_scr[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        l_new = l_prev * corr + p.sum(axis=1)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)

    @pl.when(kb == n_kb - 1)
    def _finalize():
        l = l_scr[:, 0]
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
        l_ref[0, 0] = l
        m_ref[0, 0] = m_scr[:, 0]


def _flash_fwd_call(q, k, v, block_q: int, block_k: int):
    """Run the kernel. q/k/v: [B, H, T, Dh] -> (o [B,H,T,Dh], l, m [B,H,T])."""
    b, h, t, dh = q.shape
    scale = 1.0 / math.sqrt(dh)
    # MXU tiling: lane dim -> 128, q/k blocks -> sublane multiples
    qp = _pad_to(_pad_to(q, 3, _LANES), 2, block_q)
    kp = _pad_to(_pad_to(k, 3, _LANES), 2, block_k)
    vp = _pad_to(_pad_to(v, 3, _LANES), 2, block_k)
    tq, dp = qp.shape[2], qp.shape[3]
    tk = kp.shape[2]
    bh = b * h
    qp = qp.reshape(bh, tq, dp)
    kp = kp.reshape(bh, tk, dp)
    vp = vp.reshape(bh, tk, dp)

    grid = (bh, tq // block_q, tk // block_k)
    kernel = functools.partial(_flash_kernel, block_q=block_q,
                               block_k=block_k, t_real=t, scale=scale)
    # bh and q-blocks are independent; the k axis carries scratch state
    compiler_params = _compiler_params("parallel", "parallel", "arbitrary")

    # Causal fetch elision (_diag_kv_index): the kernel predicates off
    # compute for k-blocks past the diagonal, but an unclamped index map
    # would still FETCH those blocks from HBM every iteration — rectangular
    # K/V traffic for triangular work, growing with T (the r4 "flash trails
    # dense more the longer the sequence" signature). The clamp cuts K/V
    # HBM reads ~2x for causal.
    _kv_idx = _diag_kv_index(block_q, block_k)
    vma = _vma_of(qp, kp, vp)

    o, l, m = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_k, dp), _kv_idx),
            pl.BlockSpec((1, block_k, dp), _kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dp), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j, kb: (i, 0, j)),
            pl.BlockSpec((1, 1, block_q), lambda i, j, kb: (i, 0, j)),
        ],
        out_shape=[
            _struct((bh, tq, dp), q.dtype, vma),
            _struct((bh, 1, tq), jnp.float32, vma),
            _struct((bh, 1, tq), jnp.float32, vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dp), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=_interpret(),
        name="flash_attention_fwd",
    )(qp, kp, vp)
    o = o.reshape(b, h, tq, dp)[:, :, :t, :dh]
    l = l.reshape(b, h, tq)[:, :, :t]
    m = m.reshape(b, h, tq)[:, :, :t]
    return o, l, m


def _rows_3d(x: jax.Array, bh: int, tq: int) -> jax.Array:
    """[B, H, Tpad] -> [bh, 1, tq]: the Mosaic-legal per-row layout (see
    ``_flash_kernel`` docstring on the length-1 middle axis)."""
    return x.reshape(bh, 1, tq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """Causal flash attention. q/k/v: [B, H, T, Dh] -> [B, H, T, Dh].

    Matches the dense reference :func:`~.attention.causal_attention` core to
    float tolerance while never materializing the [T, T] score matrix.
    """
    o, _, _ = _flash_fwd_call(q, k, v, block_q, block_k)
    return o


def _flash_fwd(q, k, v, block_q, block_k):
    o, l, m = _flash_fwd_call(q, k, v, block_q, block_k)
    return o, (q, k, v, o, l, m)


def _recompute_p(q_ref, k_ref, m_ref, li_ref, q_start, k_start,
                 block_q, block_k, t_real, scale):
    """Shared backward-block math: re-derive the probability block
    ``p = exp(s - m) / l`` from the saved softmax statistics (exactly the
    forward's value — no [T, T] residuals; flash-attention-2 practice).
    Returns q/k in their INPUT dtype (the callers' dots stay on the native-
    dtype MXU path) and p in f32."""
    qs = q_ref[0]                                         # [bq, d]
    kk = k_ref[0]                                         # [bk, d]
    s = jax.lax.dot_general(qs, kk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qpos = q_start + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = k_start + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = (qpos >= kpos) & (kpos < t_real) & (qpos < t_real)
    m_row = m_ref[0, 0]                                   # [bq]
    li_row = li_ref[0, 0]
    p = jnp.where(mask, jnp.exp(s - m_row[:, None]) * li_row[:, None], 0.0)
    return qs, kk, p


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, li_ref, dl_ref,
               dq_ref, dq_scr, *,
               block_q: int, block_k: int, t_real: int, scale: float):
    """dq pass: grid (bh, q-block, k-block), k innermost.

    For a fixed q block the scratch accumulates ``dq += ds·k·scale`` across
    its (diagonal-bounded) k blocks; ``ds = p*(dp - delta)`` with
    ``dp = do·vᵀ`` and ``delta = rowsum(do*o)`` precomputed outside.
    """
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    n_kb = pl.num_programs(2)
    q_start = qi * block_q
    k_start = kb * block_k

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    # causal: k-blocks wholly past the diagonal contribute nothing — skip
    @pl.when(k_start < q_start + block_q)
    def _compute():
        _, kk, p = _recompute_p(q_ref, k_ref, m_ref, li_ref, q_start,
                                k_start, block_q, block_k, t_real, scale)
        do = do_ref[0]                                    # [bq, d]
        v = v_ref[0]                                      # [bk, d]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dl_ref[0, 0][:, None])
        dq_scr[...] += jax.lax.dot_general(
            (ds * scale).astype(kk.dtype), kk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == n_kb - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, m_ref, li_ref, dl_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *,
                block_q: int, block_k: int, t_real: int, scale: float):
    """dk/dv pass: grid (bh, k-block, q-block), q innermost.

    For a fixed k block the scratch accumulates ``dv += pᵀ·do`` and
    ``dk += dsᵀ·(q·scale)`` across its q blocks, starting at the causal
    diagonal (earlier q blocks are fully masked).
    """
    kbi = pl.program_id(1)
    qb = pl.program_id(2)
    n_qb = pl.num_programs(2)
    k_start = kbi * block_k
    q_start = qb * block_q

    @pl.when(qb == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # causal: q-blocks wholly before this k block see none of it — skip
    @pl.when(q_start + block_q > k_start)
    def _compute():
        qs, _, p = _recompute_p(q_ref, k_ref, m_ref, li_ref, q_start,
                                k_start, block_q, block_k, t_real, scale)
        do = do_ref[0]                                    # [bq, d]
        v = v_ref[0]                                      # [bk, d]
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),  # pᵀ·do
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dl_ref[0, 0][:, None])
        dk_scr[...] += jax.lax.dot_general(
            (ds * scale).astype(qs.dtype), qs,
            (((0,), (0,)), ((), ())),                     # dsᵀ·qs -> [bk, d]
            preferred_element_type=jnp.float32)

    @pl.when(qb == n_qb - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd(block_q, block_k, res, do):
    """Pallas recompute-based backward (flash-attention-2 style).

    Two kernels with the forward's blocking: a dq pass (k innermost,
    diagonal-bounded like the forward) and a dk/dv pass (q innermost,
    starting at the diagonal). Both re-derive each probability block from
    the saved (l, m) — ``p = exp(s - m)/l`` — so no [T, T] matrix and no
    attention-weight residuals ever exist; VMEM stays O(block·d) per cell.
    """
    q, k, v, o, l, m = res
    b, h, t, dh = q.shape
    scale = 1.0 / math.sqrt(dh)
    # delta_i = sum_j do_ij * o_ij (rowwise), the softmax-jacobian constant
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
    # padded q rows: mask has qpos >= t_real, so their p-blocks are all-zero;
    # linv pads to 0 as belt-and-braces
    mp = _pad_to(m, 2, block_q)
    linvp = _pad_to(1.0 / jnp.maximum(l, 1e-30), 2, block_q)
    dlp = _pad_to(delta, 2, block_q)
    qp = _pad_to(_pad_to(q, 3, _LANES), 2, block_q)
    dop = _pad_to(_pad_to(do, 3, _LANES), 2, block_q)
    kp = _pad_to(_pad_to(k, 3, _LANES), 2, block_k)
    vp = _pad_to(_pad_to(v, 3, _LANES), 2, block_k)
    tq, dp_ = qp.shape[2], qp.shape[3]
    tk = kp.shape[2]
    bh = b * h
    qp = qp.reshape(bh, tq, dp_)
    dop = dop.reshape(bh, tq, dp_)
    kp = kp.reshape(bh, tk, dp_)
    vp = vp.reshape(bh, tk, dp_)
    mp = _rows_3d(mp, bh, tq)
    linvp = _rows_3d(linvp, bh, tq)
    dlp = _rows_3d(dlp, bh, tq)
    n_qb, n_kb = tq // block_q, tk // block_k
    vma = _vma_of(qp, kp, vp, dop, mp, linvp, dlp)

    q_spec = pl.BlockSpec((1, block_q, dp_), lambda i, j, kb: (i, j, 0))
    # clamp past-diagonal k fetches to the last needed block (same causal
    # fetch elision as the forward — skipped cells must not cost HBM reads)
    k_spec = pl.BlockSpec((1, block_k, dp_), _diag_kv_index(block_q, block_k))
    row_spec = pl.BlockSpec((1, 1, block_q), lambda i, j, kb: (i, 0, j))
    compiler_params = _compiler_params("parallel", "parallel", "arbitrary")

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_q=block_q, block_k=block_k,
                          t_real=t, scale=scale),
        grid=(bh, n_qb, n_kb),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec,
                  row_spec],
        out_specs=pl.BlockSpec((1, block_q, dp_), lambda i, j, kb: (i, j, 0)),
        out_shape=_struct((bh, tq, dp_), q.dtype, vma),
        scratch_shapes=[pltpu.VMEM((block_q, dp_), jnp.float32)],
        compiler_params=compiler_params,
        interpret=_interpret(),
        name="flash_attention_dq",
    )(qp, kp, vp, dop, mp, linvp, dlp)

    # dkv grid: (bh, k-block, q-block) — index maps select by the axis kind.
    # Pre-diagonal q-blocks see none of this k block: clamp their fetches up
    # to the first needed q block (fetch elision, mirror of the forward)
    def _q_idx(i, j, qb):
        return (i, jnp.maximum(qb, (j * block_k) // block_q), 0)

    def _row_idx(i, j, qb):
        return (i, 0, jnp.maximum(qb, (j * block_k) // block_q))

    kv_spec = pl.BlockSpec((1, block_k, dp_), lambda i, j, qb: (i, j, 0))
    qi_spec = pl.BlockSpec((1, block_q, dp_), _q_idx)
    rowi_spec = pl.BlockSpec((1, 1, block_q), _row_idx)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=block_q, block_k=block_k,
                          t_real=t, scale=scale),
        grid=(bh, n_kb, n_qb),
        in_specs=[kv_spec, kv_spec, qi_spec, qi_spec, rowi_spec, rowi_spec,
                  rowi_spec],
        out_specs=[
            pl.BlockSpec((1, block_k, dp_), lambda i, j, qb: (i, j, 0)),
            pl.BlockSpec((1, block_k, dp_), lambda i, j, qb: (i, j, 0)),
        ],
        out_shape=[
            _struct((bh, tk, dp_), k.dtype, vma),
            _struct((bh, tk, dp_), v.dtype, vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, dp_), jnp.float32),
            pltpu.VMEM((block_k, dp_), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=_interpret(),
        name="flash_attention_dkv",
    )(kp, vp, qp, dop, mp, linvp, dlp)

    dq = dq.reshape(b, h, tq, dp_)[:, :, :t, :dh]
    dk = dk.reshape(b, h, tk, dp_)[:, :, :t, :dh]
    dv = dv.reshape(b, h, tk, dp_)[:, :, :t, :dh]
    return dq, dk, dv


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_mha(params: dict, x: jax.Array, n_heads: int,
              block_q: int = 128, block_k: int = 128) -> jax.Array:
    """Drop-in for :func:`~.attention.causal_attention` using the Pallas core.

    x: [B, T, D] -> [B, T, D], with the same QKVO params
    (:func:`~.attention.mha_init`).
    """
    from simple_distributed_machine_learning_tpu.ops.attention import (
        _merge_heads,
        _split_heads,
    )
    q = _split_heads(x @ params["wq"], n_heads)
    k = _split_heads(x @ params["wk"], n_heads)
    v = _split_heads(x @ params["wv"], n_heads)
    o = flash_attention(q, k, v, block_q, block_k)
    return _merge_heads(o) @ params["wo"]
