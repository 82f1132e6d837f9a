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
    order (``group_sizes [E]`` int32, summing to ``m`` or less: rows past
    the last group are multiplied by nothing and come back unwritten);
    ``rhs [E, K, N]``. Operands in ``rhs``'s dtype."""
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
    # the widest lane-multiple divisor of n whose block keeps to its budget
    tn = max((t for t in range(128, n + 1, 128)
              if n % t == 0 and k * t * rhs.dtype.itemsize
              <= _RHS_BLOCK_BYTES), default=n)
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


# -- routing rules: scores [T, E] -> the chosen experts' (weights, ids) [T, k] --


def softmax_top_k(scores: jax.Array, top_k: int):
    """``g = softmax(scores)`` over the ``E`` experts, the ``top_k`` largest
    renormalised to sum 1."""
    w, ids = jax.lax.top_k(jax.nn.softmax(scores, axis=-1), top_k)
    return w / w.sum(-1, keepdims=True), ids


def sigmoid_top_k(bias: jax.Array, scale: float):
    """The rule ``s = sigmoid(scores)``; chosen: the ``top_k`` largest of
    ``s + bias`` (``bias [E]`` steers the choice alone); ``w_e = scale * s_e
    / (sum of s over the chosen + 1e-20)``."""
    def route(scores, top_k):
        s = jax.nn.sigmoid(scores)
        _, ids = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(s, ids, axis=-1)
        return scale * w / (w.sum(-1, keepdims=True) + 1e-20), ids

    return route


def softmax_top_1(bias: jax.Array):
    """The rule ``p = softmax(scores)``; chosen: the ONE largest of ``p +
    bias`` (``bias [E]`` steers the choice alone); its weight is ``p_e``
    itself: with one expert a token there is nothing to renormalise."""
    def route(scores, top_k):
        if top_k != 1:
            raise ValueError(f"softmax_top_1 routes one expert a token, "
                             f"got top_k={top_k}")
        p = jax.nn.softmax(scores, axis=-1)
        ids = jnp.argmax(p + bias.astype(jnp.float32), axis=-1)[:, None]
        return jnp.take_along_axis(p, ids, axis=-1), ids.astype(jnp.int32)

    return route


# -- expert bodies: (params, rows [m, K] sorted by expert, sizes) -> [m, K] ----


def swiglu_experts(params: dict, rows: jax.Array, sizes: jax.Array):
    """Three matrices an expert: ``(silu(x Wg_e) * (x Wu_e)) Wd_e`` with
    ``gate`` / ``up [E, K, f]`` and ``down [E, f, K]``."""
    mid = jax.nn.silu(grouped_matmul(rows, params["gate"], sizes)) * (
        grouped_matmul(rows, params["up"], sizes))
    return grouped_matmul(mid, params["down"], sizes)


def relu2_experts(params: dict, rows: jax.Array, sizes: jax.Array):
    """Two matrices an expert: ``relu(x W1_e)^2 W2_e`` with ``w1 [E, K, f]``
    and ``w2 [E, f, K]``."""
    mid = jax.nn.relu(grouped_matmul(rows, params["w1"], sizes))
    return grouped_matmul(mid * mid, params["w2"], sizes)


def dropless_experts(params: dict, x: jax.Array, top_k: int, *,
                     route=softmax_top_k, experts=swiglu_experts,
                     held: tuple[int, int] | None = None,
                     rows: jax.Array | None = None,
                     scores: jax.Array | None = None):
    """The sparse feed-forward part over ``x [T, d]`` (float32, already
    normed): ``route(x W_r, top_k)`` in float32 over ALL the ``E`` experts
    of ``params["router"] [d, E]`` gives each token its ``top_k`` experts
    and their weights (``scores [T, E]`` float32, where given, stand in
    for ``x W_r``: a router that is more than one matrix, or that carries
    state from layer to layer, is the model's to run, and ``params`` then
    needs no ``"router"``); ``experts(params, rows, sizes)`` multiplies the
    (token, expert) pairs, sorted by expert, by their own expert's
    matrices; the result is ``sum_e w_e * E_e(row)``. The experts read
    ``rows [T, K]`` where given (a mixture in a latent space hands its
    down-projected rows), else ``x``; beside a router matrix they are cast
    to its dtype here, beside ready ``scores`` the caller hands them in the
    dtype the experts read. No capacity: every routed pair of a held expert
    is computed.

    ``held = (first, n)``: the expert matrices in ``params`` are those of
    experts ``first .. first + n - 1`` alone (``None``: all ``E``). Routing,
    the choice and the weights' normaliser are over all ``E`` as they would
    be anywhere; the sum is over the chosen experts that are held. A pair
    routed to an absent expert sorts past the held groups, so no tile of it
    is visited and no weight is read for it; what it would have added is
    left out.

    Returns ``(y [T, K] float32, sizes [n] int32)``, the second how many
    rows each HELD expert got."""
    n_tok = x.shape[0]
    rows = x if rows is None else rows
    if scores is None:
        scores = matmul_acc32(x, params["router"])
        # operands in the weights' dtype, cast before the gather copies them
        rows = rows.astype(params["router"].dtype)
    n_experts = scores.shape[1]
    first, n_held = (0, n_experts) if held is None else held
    if not 0 <= first <= first + n_held <= n_experts or n_held < 1:
        raise ValueError(
            f"dropless_experts: held experts [{first}, {first + n_held}) "
            f"outside the router's {n_experts}")
    w, ids = route(scores, top_k)                           # [T, k]
    flat = ids.reshape(-1)
    if n_held != n_experts:
        here = (flat >= first) & (flat < first + n_held)
        flat = jnp.where(here, flat - first, n_held)
    order = jnp.argsort(flat, stable=True)                  # pairs by expert
    sizes = jnp.bincount(flat, length=n_held + (n_held != n_experts))[
        :n_held].astype(jnp.int32)
    out = experts(params, rows[order // top_k], sizes)      # [k T, K]
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    out = out[back].reshape(n_tok, top_k, -1)
    if n_held != n_experts:
        # rows past the last held group were never written
        out = jnp.where(here.reshape(n_tok, top_k, 1), out, 0.0)
    return jnp.einsum("tk,tkd->td", w, out), sizes
