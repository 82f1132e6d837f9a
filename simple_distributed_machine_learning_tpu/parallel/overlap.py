"""Latency-hiding collective matmuls: ppermute-chunked, overlap-scheduled.

The monolithic collectives on the tensor-parallel hot paths — the
``lax.psum`` closing every row-parallel matmul (``tensor.tp_pair_apply``),
the backward psum of ``tensor.grad_sync`` — serialize the widest matmuls in
the model against a full blocking all-reduce: the chip idles for the entire
ICI transfer. The framework's thesis (one fused XLA program so transfer
overlaps compute) says they should not.

This module provides the canonical TPU latency-hiding decomposition (Kumar et
al., arXiv:2011.03641; the "collective matmul" of Wang et al., ASPLOS'23):
every monolithic collective becomes a ring of ``lax.ppermute`` hops over the
mesh axis, each hop carrying ``1/mp`` of the tensor, with the matching chunk
of the matmul scheduled against it — XLA's async collective-permute then
runs chunk ``s``'s transfer under chunk ``s+1``'s compute. Primitives:

- :func:`allgather_matmul` — ``allgather(x) @ w`` for a row-sharded ``x``:
  each arriving activation chunk multiplies while the next is in flight
  (the column-parallel layer of a scattered Megatron pair, and the backward
  of :func:`matmul_reducescatter`);
- :func:`matmul_reducescatter` — ``reduce_scatter(x @ w)``: partial products
  ring-shift and accumulate instead of one blocking all-reduce (the
  row-parallel layer of a scattered pair);
- :func:`ring_psum` — chunked all-reduce with a replicated result: drop-in
  for the ``lax.psum`` closing a row-parallel matmul whose activations stay
  replicated (reduce-scatter ring + all-gather ring over column chunks);
- :func:`ring_all_gather` / :func:`ring_reduce_scatter` — the bare data
  movers the matmul forms compose with.

Each differentiable primitive carries a ``custom_vjp`` whose backward pass is
the MIRRORED overlapped schedule (the transpose of an all-gather ring is a
reduce-scatter ring and vice versa), so the backward matmuls hide their ICI
transfer exactly like the forward ones.

Every chunk's compute and hop is wrapped in
:func:`~..utils.profiler.annotate_scope`, so an XProf trace shows the
per-chunk interleave as named regions (``ring_psum/chunk0`` beside
``ring_psum/hop0`` …) instead of one opaque all-reduce bar.

Numerics: ring schedules sum partial products in ring order — a FIXED order
per chunk (device ``c+1``, ``c+2``, …, ``c`` for the chunk ending at device
``c``), so all devices hold bit-identical replicas of replicated results, but
the order differs from XLA's monolithic all-reduce: parity with the ``psum``
path is to float tolerance, not bit-exact (the same caveat as any psum
re-association; pinned by tests/test_overlap.py). With ``mp == 1`` every
primitive degenerates to the plain local matmul/identity.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from simple_distributed_machine_learning_tpu.parallel.compat import (
    ct_like as _ct_like,
    pvary_to as _pvary_to,
)
from simple_distributed_machine_learning_tpu.utils.profiler import (
    annotate_scope,
)

OVERLAP_CHOICES = ("none", "ring")


def check_overlap(overlap: str) -> str:
    if overlap not in OVERLAP_CHOICES:
        raise ValueError(
            f"overlap must be one of {OVERLAP_CHOICES}, got {overlap!r}")
    return overlap


def _fwd_perm(mp: int) -> list[tuple[int, int]]:
    """The ring: device j sends to j+1 (mod mp)."""
    return [(j, (j + 1) % mp) for j in range(mp)]


def _bwd_perm(mp: int) -> list[tuple[int, int]]:
    """The mirrored ring: device j sends to j-1 (mod mp) — the transpose of
    :func:`_fwd_perm`, used by the backward schedules."""
    return [(j, (j - 1) % mp) for j in range(mp)]


def _row_chunk(x: jax.Array, c, n: int) -> jax.Array:
    """Rows ``[c*n, (c+1)*n)`` of ``x`` (``c`` may be traced)."""
    return lax.dynamic_slice_in_dim(x, c * n, n, axis=0)


def _put_row_chunk(buf: jax.Array, chunk: jax.Array, c, n: int) -> jax.Array:
    return lax.dynamic_update_slice_in_dim(buf, chunk, c * n, axis=0)


# ---- bare ring data movers ---------------------------------------------


def _ring_all_gather_impl(x, axis, perm_fn=_fwd_perm, tag="ring_all_gather"):
    """[n, ...] shard -> [mp*n, ...] gathered along axis 0, via mp-1 hops."""
    mp = lax.axis_size(axis)
    if mp == 1:
        return x
    n = x.shape[0]
    i = lax.axis_index(axis)
    perm = perm_fn(mp)
    sign = 1 if perm_fn is _fwd_perm else -1
    out = jnp.zeros((mp * n,) + x.shape[1:], x.dtype)
    out = _put_row_chunk(out, x, i, n)
    have = x
    for s in range(1, mp):
        with annotate_scope(f"{tag}/hop{s - 1}"):
            have = lax.ppermute(have, axis, perm)
        # after s forward hops we hold the chunk that originated s devices
        # back around the ring
        with annotate_scope(f"{tag}/chunk{s}"):
            out = _put_row_chunk(out, have, (i - sign * s) % mp, n)
    return out


def _ring_reduce_scatter_impl(x, axis, perm_fn=_fwd_perm,
                              tag="ring_reduce_scatter"):
    """[mp*n, ...] per-device partials -> [n, ...] chunk ``i`` of the sum.

    The accumulator for the chunk ending at device ``c`` starts at device
    ``c+1`` and visits ``c+2, …, c`` — a fixed summation order per chunk, so
    a following all-gather yields bit-identical replicas.
    """
    mp = lax.axis_size(axis)
    if mp == 1:
        return x
    if x.shape[0] % mp:
        raise ValueError(
            f"ring_reduce_scatter: leading axis {x.shape[0]} not divisible "
            f"by axis size {mp}")
    n = x.shape[0] // mp
    i = lax.axis_index(axis)
    perm = perm_fn(mp)
    sign = 1 if perm_fn is _fwd_perm else -1
    with annotate_scope(f"{tag}/chunk0"):
        acc = _row_chunk(x, (i - sign) % mp, n)
    for s in range(1, mp):
        with annotate_scope(f"{tag}/hop{s - 1}"):
            acc = lax.ppermute(acc, axis, perm)
        with annotate_scope(f"{tag}/chunk{s}"):
            acc = acc + _row_chunk(x, (i - sign * (s + 1)) % mp, n)
    return acc


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def ring_all_gather(x: jax.Array, axis: str) -> jax.Array:
    """All-gather ``x`` along its leading axis via a ppermute ring.

    Call inside ``shard_map``: ``x [n, ...]`` is this device's row shard
    (shard ``i`` = global rows ``[i*n, (i+1)*n)``); returns the gathered
    ``[mp*n, ...]``, identical on every device. Backward is the mirrored
    reduce-scatter ring.
    """
    return _ring_all_gather_impl(x, axis)


def _ring_all_gather_fwd(x, axis):
    return _ring_all_gather_impl(x, axis), None


def _ring_all_gather_bwd(axis, _, ct):
    # y[chunk c] = x_c on EVERY device: dx = psum(ct)[chunk i], i.e. the
    # mirrored reduce-scatter ring
    return (_ring_reduce_scatter_impl(ct, axis, perm_fn=_bwd_perm,
                                      tag="ring_all_gather_bwd"),)


ring_all_gather.defvjp(_ring_all_gather_fwd, _ring_all_gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def ring_reduce_scatter(x: jax.Array, axis: str) -> jax.Array:
    """Reduce-scatter per-device partials via a ppermute ring.

    Call inside ``shard_map``: every device holds partial ``x [mp*n, ...]``;
    device ``i`` returns rows ``[i*n, (i+1)*n)`` of ``psum(x)`` (summed in
    ring order — see module docstring). Backward is the mirrored all-gather
    ring.
    """
    return _ring_reduce_scatter_impl(x, axis)


def _ring_reduce_scatter_fwd(x, axis):
    return _ring_reduce_scatter_impl(x, axis), None


def _ring_reduce_scatter_bwd(axis, _, ct):
    return (_ring_all_gather_impl(ct, axis, perm_fn=_bwd_perm,
                                  tag="ring_reduce_scatter_bwd"),)


ring_reduce_scatter.defvjp(_ring_reduce_scatter_fwd,
                           _ring_reduce_scatter_bwd)


# ---- chunked all-reduce (replicated result) ----------------------------


def _ring_psum_impl(x, axis, perm_fn=_fwd_perm, tag="ring_psum"):
    """All-reduce with a replicated, bit-identical-across-devices result,
    as a reduce-scatter ring + all-gather ring over column chunks.

    Falls back to one ``lax.psum`` when the last axis does not divide by the
    ring size (the chunks must be equal for static shapes) — cast back up to
    axis-varying, the type the ring's ppermutes give, so that the result and
    its cotangent are typed the same on both routes.
    """
    mp = lax.axis_size(axis)
    if mp == 1:
        return x
    d = x.shape[-1]
    if d % mp:
        return _pvary_to(lax.psum(x, axis), (axis,))
    # chunk the LAST axis (the matmul output features): move it leading so
    # the row-chunk ring helpers apply, then restore
    xt = jnp.moveaxis(x, -1, 0)
    acc = _ring_reduce_scatter_impl(xt, axis, perm_fn=perm_fn, tag=tag)
    full = _ring_all_gather_impl(acc, axis, perm_fn=perm_fn, tag=tag + "/ag")
    return jnp.moveaxis(full, 0, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def ring_psum(x: jax.Array, axis: str) -> jax.Array:
    """Drop-in for ``lax.psum(x, axis)`` with the transfer chunked over a
    ppermute ring so each chunk's hop hides under another chunk's add.

    Same value on every device (bit-identical across devices; equal to the
    monolithic psum to float tolerance — ring summation order). Same
    cotangent accounting as ``lax.psum`` inside ``shard_map``: the backward
    pass psums the per-device cotangents — here with the mirrored ring, so
    the gradient all-reduce overlaps too.
    """
    return _ring_psum_impl(x, axis)


def _ring_psum_fwd(x, axis):
    return _ring_psum_impl(x, axis), None


def _ring_psum_bwd(axis, _, ct):
    # transpose of psum inside shard_map is psum of the per-device
    # cotangents (how the full cotangent is reassembled from per-replica
    # splits — see tensor.grad_sync); mirrored ring direction
    return (_ring_psum_impl(ct, axis, perm_fn=_bwd_perm,
                            tag="ring_psum_bwd"),)


ring_psum.defvjp(_ring_psum_fwd, _ring_psum_bwd)


# ---- collective matmuls ------------------------------------------------


def _allgather_matmul_impl(x, w, axis, perm_fn=_fwd_perm,
                           tag="allgather_matmul"):
    """y = allgather(x) @ w, chunk-at-a-time: multiply the held activation
    chunk while the next one rides the ring."""
    mp = lax.axis_size(axis)
    if mp == 1:
        return x @ w
    n = x.shape[0]
    i = lax.axis_index(axis)
    perm = perm_fn(mp)
    sign = 1 if perm_fn is _fwd_perm else -1
    out = jnp.zeros((mp * n, w.shape[-1]), jnp.result_type(x, w))
    chunk = x
    for s in range(mp):
        if s + 1 < mp:
            # issue the NEXT chunk's hop before this chunk's matmul: XLA's
            # async collective-permute then runs under the compute
            with annotate_scope(f"{tag}/hop{s}"):
                nxt = lax.ppermute(chunk, axis, perm)
        with annotate_scope(f"{tag}/chunk{s}"):
            out = _put_row_chunk(out, chunk @ w, (i - sign * s) % mp, n)
        if s + 1 < mp:
            chunk = nxt
    return out


def _matmul_reducescatter_impl(x, w, axis, perm_fn=_fwd_perm,
                               tag="matmul_reducescatter"):
    """y = reduce_scatter(x @ w): each row-chunk's partial product computes
    while the accumulator for the previous chunk rides the ring."""
    mp = lax.axis_size(axis)
    if mp == 1:
        return x @ w
    if x.shape[0] % mp:
        raise ValueError(
            f"matmul_reducescatter: {x.shape[0]} rows not divisible by axis "
            f"size {mp}")
    n = x.shape[0] // mp
    i = lax.axis_index(axis)
    perm = perm_fn(mp)
    sign = 1 if perm_fn is _fwd_perm else -1
    with annotate_scope(f"{tag}/chunk0"):
        acc = _row_chunk(x, (i - sign) % mp, n) @ w
    for s in range(1, mp):
        with annotate_scope(f"{tag}/hop{s - 1}"):
            acc = lax.ppermute(acc, axis, perm)
        # the incoming hop and this chunk's matmul are independent: XLA
        # overlaps them, the add joins them after
        with annotate_scope(f"{tag}/chunk{s}"):
            acc = acc + _row_chunk(x, (i - sign * (s + 1)) % mp, n) @ w
    return acc


def _gatherT_matmul_impl(x, dy, axis, n_rows, perm_fn=_fwd_perm,
                         tag="gatherT_matmul"):
    """dw = allgather(x)^T @ dy without materializing the gather: circulate
    the ``x`` chunks and accumulate ``x_c^T @ dy[rows c]`` per hop. ``dy``
    is local ``[mp*n_rows, k]``; ``x`` is this device's ``[n_rows, d]``."""
    mp = lax.axis_size(axis)
    if mp == 1:
        return x.T @ dy
    i = lax.axis_index(axis)
    perm = perm_fn(mp)
    sign = 1 if perm_fn is _fwd_perm else -1
    acc = jnp.zeros((x.shape[-1], dy.shape[-1]), jnp.result_type(x, dy))
    chunk = x
    for s in range(mp):
        if s + 1 < mp:
            with annotate_scope(f"{tag}/hop{s}"):
                nxt = lax.ppermute(chunk, axis, perm)
        with annotate_scope(f"{tag}/chunk{s}"):
            c = (i - sign * s) % mp
            acc = acc + chunk.T @ _row_chunk(dy, c, n_rows)
        if s + 1 < mp:
            chunk = nxt
    return acc


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def allgather_matmul(x: jax.Array, w: jax.Array, axis: str) -> jax.Array:
    """``allgather(x, axis) @ w`` with the gather ring hidden under the
    chunk matmuls — the column-parallel collective matmul.

    Call inside ``shard_map`` over ``axis`` (size ``mp``):

    - ``x [n, d]``: this device's row shard of the global activation
      ``[mp*n, d]`` (shard ``i`` = rows ``[i*n, (i+1)*n)``);
    - ``w [d, k]``: this device's weight (typically a column shard);
    - returns ``[mp*n, k]`` — every gathered chunk multiplied against the
      local weight, chunk ``s``'s matmul overlapping chunk ``s+1``'s hop.

    Backward is the mirrored schedule: ``dx`` via
    :func:`matmul_reducescatter` of ``dy @ w^T`` (reversed ring), ``dw`` by
    circulating the saved ``x`` chunks against ``dy``.
    """
    return _allgather_matmul_impl(x, w, axis)


def _allgather_matmul_fwd(x, w, axis):
    return _allgather_matmul_impl(x, w, axis), (x, w)


def _allgather_matmul_bwd(axis, res, dy):
    x, w = res
    # dx_i = psum_j(dy_j @ w_j^T)[rows i]: the mirrored matmul+reduce-scatter
    dx = _matmul_reducescatter_impl(dy, w.T, axis, perm_fn=_bwd_perm,
                                    tag="allgather_matmul_bwd_dx")
    # dw = allgather(x)^T @ dy, re-circulating x chunk-by-chunk
    dw = _gatherT_matmul_impl(x, dy, axis, x.shape[0], perm_fn=_bwd_perm,
                              tag="allgather_matmul_bwd_dw")
    return _ct_like(dx, x), _ct_like(dw, w)


allgather_matmul.defvjp(_allgather_matmul_fwd, _allgather_matmul_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def matmul_reducescatter(x: jax.Array, w: jax.Array, axis: str) -> jax.Array:
    """``reduce_scatter(x @ w, axis)`` with the partial products ring-shifted
    and accumulated instead of one blocking all-reduce — the row-parallel
    collective matmul.

    Call inside ``shard_map`` over ``axis`` (size ``mp``):

    - ``x [N, d]``: local activations (``N`` divisible by ``mp``), typically
      against a contracting-dim weight shard ``w [d, k]``;
    - returns rows ``[i*N/mp, (i+1)*N/mp)`` of ``psum(x @ w)`` on device
      ``i`` (ring summation order — see module docstring).

    Backward is the mirrored schedule: ``dx`` via :func:`allgather_matmul`
    of ``dy`` against ``w^T`` (reversed ring), ``dw`` by circulating the
    ``dy`` chunks against the saved ``x`` rows.
    """
    return _matmul_reducescatter_impl(x, w, axis)


def _matmul_reducescatter_fwd(x, w, axis):
    return _matmul_reducescatter_impl(x, w, axis), (x, w)


def _matmul_reducescatter_bwd(axis, res, dy):
    x, w = res
    mp = lax.axis_size(axis)
    n = x.shape[0] // mp
    # d(x@w) = allgather(dy) (each device's dy is the cotangent of its row
    # chunk of the summed product): dx = allgather_matmul(dy, w^T)
    dx = _allgather_matmul_impl(dy, w.T, axis, perm_fn=_bwd_perm,
                                tag="matmul_reducescatter_bwd_dx")
    # dw = x^T @ allgather(dy): circulate the dy chunks against x's rows
    i = lax.axis_index(axis)
    perm = _bwd_perm(mp)
    acc = jnp.zeros((w.shape[0], w.shape[1]), jnp.result_type(x, dy))
    chunk = dy
    for s in range(mp):
        if s + 1 < mp:
            with annotate_scope(f"matmul_reducescatter_bwd_dw/hop{s}"):
                nxt = lax.ppermute(chunk, axis, perm)
        with annotate_scope(f"matmul_reducescatter_bwd_dw/chunk{s}"):
            c = (i + s) % mp
            acc = acc + _row_chunk(x, c, n).T @ chunk
        if s + 1 < mp:
            chunk = nxt
    return _ct_like(dx, x), _ct_like(acc, w)


matmul_reducescatter.defvjp(_matmul_reducescatter_fwd,
                            _matmul_reducescatter_bwd)
