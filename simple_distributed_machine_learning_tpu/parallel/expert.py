"""Expert parallelism: mixture-of-experts FFN with all-to-all dispatch.

This is the TRAINER's expert layer: a static capacity per expert, tokens past
it dropped, two-matrix experts. The SERVING path has its own,
``ops/moe_experts.py`` (dropless: every routed token is computed, by a
grouped product over the experts that got rows, SwiGLU experts): with a
capacity a request's tokens would depend on who else is in the batch, which
a server cannot offer. No flag chooses between them: a model's training
stage builds this one, its serve programs call the other.

Not owed for reference parity (SURVEY §2.2: the reference has no MoE), but a
first-class parallelism strategy of this framework, alongside pipeline
(``pipeline.py``), tensor (``tensor.py``) and sequence (``sequence.py``)
parallelism.

TPU-first design (GShard/Switch recipe, not a torch translation):

- **routing** is a small matmul + top-k over experts; the dispatch and combine
  steps are expressed as one-hot einsums (``[T,E,C]`` dispatch tensor against
  ``[T,d]`` tokens), which XLA tiles onto the MXU — no gather/scatter with
  dynamic shapes, no data-dependent control flow, so the whole layer stays
  inside one compiled program;
- **capacity** is static (``capacity_factor * k * T / E`` slots per expert):
  tokens beyond an expert's capacity are dropped (their combine weight is 0 and
  the residual path carries them), which keeps every shape static for XLA;
- **expert parallelism** shards the expert axis over an ``"expert"`` mesh axis:
  each device holds ``E / D`` experts and a ``1/D`` shard of the tokens. One
  ``lax.all_to_all`` ships each expert's capacity buffer to its owner, the
  owner runs its experts' FFN on a ``[E/D, D·C, d]`` batch (one big MXU
  matmul), and a second ``all_to_all`` ships results back — the canonical
  2×all-to-all MoE schedule, riding ICI.

The dense path (:func:`moe_apply`) is the single-device ground truth; the EP
path (:func:`moe_apply_ep`, called inside ``shard_map``) computes exactly the
same function when the token shards match (parity-tested in
``tests/test_expert_parallel.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


from simple_distributed_machine_learning_tpu.ops.layers import linear_init

EXPERT_AXIS = "expert"


def moe_init(key: jax.Array, d_model: int, d_hidden: int, n_experts: int,
             dtype=jnp.float32) -> dict:
    """Params for a MoE FFN: router + ``n_experts`` two-layer MLPs.

    Expert weights are stacked on a leading ``[E, ...]`` axis so the expert
    axis can be sharded ``P('expert')`` and the per-expert matmul is a single
    batched einsum.
    """
    kr, *ke = jax.random.split(key, 1 + n_experts)
    experts = [
        {"in": linear_init(jax.random.fold_in(k, 0), d_model, d_hidden, dtype),
         "out": linear_init(jax.random.fold_in(k, 1), d_hidden, d_model, dtype)}
        for k in ke
    ]
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *experts)
    return {
        # router bias-free (Switch convention); small init keeps early routing
        # near-uniform
        "router": 0.02 * jax.random.normal(kr, (d_model, n_experts), dtype),
        "experts": stacked,
    }


def n_experts_of(params: dict) -> int:
    return params["router"].shape[-1]


def _route(params: dict, x: jax.Array, k: int, capacity: int):
    """Top-k routing → dispatch/combine tensors.

    x: [T, d] tokens. Returns ``(dispatch [T,E,C] one-hot, combine [T,E,C]
    gate-weighted, aux_loss scalar)``. Static shapes throughout; tokens past an
    expert's capacity get zero combine weight (dropped — the caller's residual
    connection carries them).
    """
    T, _ = x.shape
    E = n_experts_of(params)
    logits = x @ params["router"]                       # [T, E]
    gates = jax.nn.softmax(logits, axis=-1)

    # Switch-style load-balancing aux loss: E * sum_e f_e * p_e where f_e is
    # the fraction of tokens whose top-1 choice is e and p_e the mean gate.
    top1 = jnp.argmax(gates, axis=-1)
    f = jnp.mean(jax.nn.one_hot(top1, E), axis=0)
    p = jnp.mean(gates, axis=0)
    aux_loss = E * jnp.sum(f * p)

    _, topk_idx = lax.top_k(gates, k)                   # [T, k]
    # renormalize the selected gates so they sum to 1 per token
    topk_gate = jnp.take_along_axis(gates, topk_idx, axis=-1)
    topk_gate = topk_gate / jnp.maximum(
        jnp.sum(topk_gate, axis=-1, keepdims=True), 1e-9)

    # position of each (token, choice) in its expert's capacity buffer:
    # flatten choices in priority order (all rank-0 choices first, token order
    # within a rank) so earlier tokens win capacity slots deterministically.
    sel = jax.nn.one_hot(topk_idx, E, dtype=jnp.int32)  # [T, k, E]
    sel_flat = sel.transpose(1, 0, 2).reshape(k * T, E)  # [k*T, E] rank-major
    pos_flat = jnp.cumsum(sel_flat, axis=0) - sel_flat   # slot index per entry
    pos = pos_flat.reshape(k, T, E).transpose(1, 0, 2)   # [T, k, E]
    in_cap = (pos < capacity) & (sel > 0)

    # dispatch[t, e, c] = 1 iff token t occupies slot c of expert e.
    # Built one routing rank at a time: peak memory is one [T, E, C] tensor,
    # not [T, k, E, C] (C scales with T, so the k axis would square the cost).
    dispatch = jnp.zeros((T, E, capacity), x.dtype)
    combine = jnp.zeros((T, E, capacity), x.dtype)
    for j in range(k):
        oh = jnp.where(in_cap[:, j, :], 1.0, 0.0)[..., None] * jax.nn.one_hot(
            jnp.clip(pos[:, j, :], 0, capacity - 1), capacity)   # [T, E, C]
        dispatch = dispatch + oh
        combine = combine + oh * topk_gate[:, j, None, None]
    return dispatch, combine, aux_loss


def _expert_ffn(experts: dict, xs: jax.Array, activation=jax.nn.gelu
                ) -> jax.Array:
    """Batched per-expert MLP. xs: [E, C, d] -> [E, C, d]; one einsum per
    layer so the E·C token block hits the MXU as a single contraction."""
    h = jnp.einsum("ecd,edh->ech", xs, experts["in"]["w"])
    h = activation(h + experts["in"]["b"][:, None, :])
    y = jnp.einsum("ech,ehd->ecd", h, experts["out"]["w"])
    return y + experts["out"]["b"][:, None, :]


def default_capacity(n_tokens: int, n_experts: int, k: int,
                     capacity_factor: float = 1.25) -> int:
    return max(1, int(capacity_factor * k * n_tokens / n_experts))


def moe_apply(params: dict, x: jax.Array, k: int = 2,
              capacity: int | None = None) -> tuple[jax.Array, jax.Array]:
    """Dense (single-device) MoE FFN — the EP path's ground truth.

    x: [T, d] (flatten batch/sequence first). Returns ``(y [T, d], aux_loss)``.
    """
    T, _ = x.shape
    E = n_experts_of(params)
    capacity = default_capacity(T, E, k) if capacity is None else capacity
    dispatch, combine, aux = _route(params, x, k, capacity)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)   # [E, C, d]
    expert_out = _expert_ffn(params["experts"], expert_in)
    y = jnp.einsum("tec,ecd->td", combine, expert_out)
    return y, aux


def moe_apply_ep(params: dict, x: jax.Array, k: int = 2,
                 capacity: int | None = None, axis: str = EXPERT_AXIS,
                 overlap: str = "none") -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE FFN — call inside ``shard_map`` over ``axis``.

    ``params['experts']`` is THIS device's ``[E/D, ...]`` expert shard; the
    router is replicated. ``x``: this device's ``[T_local, d]`` token shard.
    ``capacity`` is per (expert, source device) — each expert's total buffer is
    ``D * capacity``. Returns this shard's ``(y [T_local, d], aux_loss)``
    (aux is psum-averaged over the axis so every shard sees the global value).

    ``overlap='none'``: the canonical 2x ``all_to_all`` schedule — dispatch
    everything, run one batched FFN, ship everything back; the chip blocks
    for each full exchange. ``overlap='ring'``: the dispatch/combine exchange
    decomposes into ``D-1`` ppermute offset hops (``parallel/overlap.py``
    style): each remote shard's capacity buffer FFNs as it arrives while the
    next offset's buffer is in flight, and results stream back on the
    mirrored permute — same math per capacity slot, so parity with the
    all_to_all path is to float tolerance (the FFN matmul batches differ:
    ``[E/D, C, d]`` per chunk vs ``[E/D, D*C, d]`` in one piece).
    """
    from simple_distributed_machine_learning_tpu.parallel.overlap import (
        check_overlap,
    )
    from simple_distributed_machine_learning_tpu.utils.profiler import (
        annotate_scope,
    )

    check_overlap(overlap)
    D = lax.axis_size(axis)
    T, _ = x.shape
    E = n_experts_of(params)                             # global expert count
    capacity = default_capacity(T, E, k) if capacity is None else capacity
    dispatch, combine, aux = _route(params, x, k, capacity)
    aux = lax.pmean(aux, axis)

    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)   # [E, C, d] local contrib
    if overlap == "ring" and D > 1:
        e_loc = E // D
        i = lax.axis_index(axis)
        expert_out = jnp.zeros_like(expert_in)
        # own chunk first — no hop to hide it under
        with annotate_scope("moe_ep_ring/chunk0"):
            own = lax.dynamic_slice_in_dim(expert_in, i * e_loc, e_loc, 0)
            expert_out = lax.dynamic_update_slice_in_dim(
                expert_out, _expert_ffn(params["experts"], own), i * e_loc, 0)
        for s in range(1, D):
            # offset-s exchange: send the chunk destined for owner i+s, FFN
            # the chunk arriving from source i-s, return it on the mirrored
            # permute — XLA overlaps offset s+1's hop with offset s's FFN
            fwd = [(j, (j + s) % D) for j in range(D)]
            rev = [(j, (j - s) % D) for j in range(D)]
            dst = (i + s) % D
            with annotate_scope(f"moe_ep_ring/hop{s}"):
                send = lax.dynamic_slice_in_dim(expert_in, dst * e_loc,
                                                e_loc, 0)
                recv = lax.ppermute(send, axis, fwd)
            with annotate_scope(f"moe_ep_ring/chunk{s}"):
                y_chunk = _expert_ffn(params["experts"], recv)
            with annotate_scope(f"moe_ep_ring/return{s}"):
                back = lax.ppermute(y_chunk, axis, rev)
                expert_out = lax.dynamic_update_slice_in_dim(
                    expert_out, back, dst * e_loc, 0)
    else:
        # ship each expert's buffer to its owner: split the E axis D-ways,
        # concat the shards' contributions along capacity → [E/D, D*C, d]
        expert_in = lax.all_to_all(expert_in, axis, split_axis=0,
                                   concat_axis=1, tiled=True)
        expert_out = _expert_ffn(params["experts"], expert_in)
        # inverse exchange: send each source shard its slice back → [E, C, d]
        expert_out = lax.all_to_all(expert_out, axis, split_axis=1,
                                    concat_axis=0, tiled=True)
    y = jnp.einsum("tec,ecd->td", combine, expert_out)
    return y, aux
