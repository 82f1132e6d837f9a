"""The dropless expert layer of the serving path: route, sort, grouped
matrix products over the experts that got rows, weighted sum back.

``parallel/expert.py`` is the TRAINER's mixture of experts (GShard: a static
capacity per expert, tokens past it dropped, ``[T, E, C]`` one-hot
dispatch). A server cannot offer that: with a capacity a request's tokens
would depend on who else is in the batch. Here every routed token is
computed: the ``k * T`` (token, expert) pairs are sorted by expert, and one
Pallas kernel (``name="moe_experts"``, after JAX's ``megablox.gmm``) walks
the sorted rows tile by tile, multiplying each stretch of rows by its own
expert's matrix. An expert that got no row is never visited: its weights are
not read.

The kernel's grid is static (``m / tm + E - 1`` visits of a (group, row
tile) pair at most); the visits a batch needs are counted on the device
(scalar prefetch), the rest are skipped and their index maps stay on the
last block, so they fetch nothing. A visit multiplies the whole ``[tm, K]``
tile and keeps the rows that belong to its group. Each row's product is one
dot over the whole ``K``, whatever else the tile holds: a token's numbers do
not depend on its neighbours.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from simple_distributed_machine_learning_tpu.ops.flash_attention import (
    _compiler_params,
    _interpret,
    pltpu,
)
from simple_distributed_machine_learning_tpu.ops.layers import matmul_acc32

#: rows of a tile: the MXU's height; a batch with fewer rows takes them all
_TILE_M = 128
#: the most bytes of one expert's matrix a visit fetches as one block
_RHS_BLOCK_BYTES = 4 << 20


def _visits(group_sizes, m: int, tm: int):
    """The (group, row tile) pairs a grouped product has to visit, in row
    order, for groups laid end to end over ``m`` rows: ``(offsets [E + 1],
    group_ids [V], tile_ids [V], n [1])`` with ``V = m / tm + E - 1`` the
    static most. Entries past ``n`` repeat the last visit."""
    n_groups = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    first = offsets[:-1] // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    most = m // tm + n_groups - 1
    n = tiles.sum().astype(jnp.int32)
    at = jnp.minimum(jnp.arange(most, dtype=jnp.int32), jnp.maximum(n - 1, 0))
    gids = jnp.repeat(jnp.arange(n_groups, dtype=jnp.int32), tiles,
                      total_repeat_length=most)[at]
    before = jnp.cumsum(tiles) - tiles          # visits before each group
    tids = (first[gids] + at - before[gids]).astype(jnp.int32)
    return offsets, gids, tids, n[None]


def _kernel(offsets_ref, gids_ref, tids_ref, n_ref, lhs_ref, rhs_ref,
            out_ref, *, tm: int):
    v = pl.program_id(1)

    @pl.when(v < n_ref[0])
    def _visit():
        g = gids_ref[v]
        acc = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        rows = tids_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = (rows >= offsets_ref[g]) & (rows < offsets_ref[g + 1])
        # a tile that several groups share is visited once for each, one
        # after the other: the rows of the others stay as they were
        out_ref[...] = jnp.where(mine, acc, out_ref[...])


def grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """``out[r] = lhs[r] @ rhs[g]`` for every row ``r`` of group ``g``,
    float32. ``lhs [m, K]``: the groups' rows laid end to end in group
    order (``group_sizes [E]`` int32, summing to ``m``); ``rhs [E, K, N]``.
    Operands in ``rhs``'s dtype."""
    return _grouped_matmul(lhs.astype(rhs.dtype), rhs,
                           group_sizes.astype(jnp.int32),
                           interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _grouped_matmul(lhs, rhs, group_sizes, *, interpret):
    m, k = lhs.shape
    n_groups, _, n = rhs.shape
    tm = _TILE_M if m >= _TILE_M else -(-m // 8) * 8
    padded = -(-m // tm) * tm
    if padded != m:             # rows past the last group: never kept
        lhs = jnp.pad(lhs, ((0, padded - m), (0, 0)))
    tn = n
    while k * tn * rhs.dtype.itemsize > _RHS_BLOCK_BYTES and tn % 256 == 0:
        tn //= 2
    offsets, gids, tids, count = _visits(group_sizes, padded, tm)
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, gids.shape[0]),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, o, g, t, c: (t[v], 0)),
                pl.BlockSpec((None, k, tn),
                             lambda j, v, o, g, t, c: (g[v], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, o, g, t, c: (t[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((padded, n), jnp.float32),
        compiler_params=_compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name="moe_experts",
    )(offsets, gids, tids, count, lhs, rhs)
    return out[:m]


def dropless_experts(params: dict, x: jax.Array, top_k: int):
    """The sparse feed-forward part over rows ``x [T, d]`` (float32,
    already normed): ``g = softmax(x W_r)`` in float32 over the ``E``
    experts, the ``top_k`` largest renormalised to sum 1, and ``sum_e w_e *
    (silu(x Wg_e) * (x Wu_e)) Wd_e`` over them. ``params``: ``router [d,
    E]``, ``gate`` / ``up`` ``[E, d, f]``, ``down [E, f, d]``. No capacity:
    every routed pair is computed. Returns ``(y [T, d] float32, rows [E]
    int32)``, the second how many rows each expert got."""
    n_tok = x.shape[0]
    n_experts = params["router"].shape[1]
    probs = jax.nn.softmax(matmul_acc32(x, params["router"]), axis=-1)
    w, ids = jax.lax.top_k(probs, top_k)                    # [T, k]
    w = w / w.sum(-1, keepdims=True)
    flat = ids.reshape(-1)
    order = jnp.argsort(flat, stable=True)                  # pairs by expert
    sizes = jnp.bincount(flat, length=n_experts).astype(jnp.int32)
    rows = x.astype(params["gate"].dtype)[order // top_k]   # [k T, d]
    mid = jax.nn.silu(grouped_matmul(rows, params["gate"], sizes)) * (
        grouped_matmul(rows, params["up"], sizes))
    out = grouped_matmul(mid, params["down"], sizes)        # [k T, d]
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    out = out[back].reshape(n_tok, top_k, -1)
    return jnp.einsum("tk,tkd->td", w, out), sizes
