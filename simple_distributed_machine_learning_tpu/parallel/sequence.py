"""Sequence/context parallelism: Ulysses-style all-to-all attention.

Long-context support is first-class in this framework (the reference has no
sequence axis at all — conv+FC on 28x28 images, SURVEY §5.7). Two
complementary strategies shard the sequence over a mesh axis:

- **ring attention** (:func:`~..ops.attention.ring_attention`): K/V blocks
  rotate around the device ring via ``lax.ppermute``; memory per device is
  O(T_local), communication is S-1 neighbor hops riding ICI. Best when T is
  huge and heads are few.
- **Ulysses** (this module): two ``lax.all_to_all`` collectives re-shard
  [B, T/s, H, Dh] -> [B, T, H/s, Dh] around a *local full-sequence* attention
  over the device's head subset. One pair of all-to-alls per attention call,
  each moving the same bytes as one ring hop — fewer, larger transfers, so it
  wins when the mesh axis divides the head count and T is moderate.

Both are plain functions called inside ``shard_map`` and compose with the
pipeline's ``stage`` axis and the ``data`` axis. Output matches the dense
single-device :func:`~..ops.attention.causal_attention` to float tolerance
(tests/test_sequence_parallel.py).
"""

from __future__ import annotations

import jax
from jax import lax


from simple_distributed_machine_learning_tpu.ops.attention import (
    SEQ_AXIS,
    causal_attention_core,
)


def ulysses_attention(params: dict, x: jax.Array, n_heads: int,
                      axis: str = SEQ_AXIS) -> jax.Array:
    """Causal MHA with the sequence sharded over mesh axis ``axis``.

    Call inside ``shard_map``: ``x`` is this device's sequence chunk
    ``[B, T_local, D]`` (chunk i = global positions
    ``[i*T_local, (i+1)*T_local)``). The axis size must divide ``n_heads``
    (each device ends up owning ``n_heads / axis_size`` whole heads).

    Data movement (DeepSpeed-Ulysses recipe, re-derived for XLA collectives):
    project locally to q/k/v ``[B, T_local, H, Dh]``; ``all_to_all`` scatters
    the head axis and gathers the sequence axis, giving each device the FULL
    sequence for ``H/s`` heads; plain causal attention runs locally (no masks
    crossing devices — causality is exact); the reverse ``all_to_all``
    restores sequence sharding for the output projection.
    """
    s = lax.axis_size(axis)
    if n_heads % s:
        raise ValueError(f"{n_heads} heads not divisible by axis size {s}")
    b, t_loc, d = x.shape
    dh = d // n_heads

    def qkv(w):
        return (x @ w).reshape(b, t_loc, n_heads, dh)

    q, k, v = qkv(params["wq"]), qkv(params["wk"]), qkv(params["wv"])

    def scatter_heads(a):
        # [B, T_loc, H, Dh] -> [B, T_loc*s, H/s, Dh]: split heads across the
        # axis, concatenate the sequence chunks (tiled=True keeps them ordered)
        return lax.all_to_all(a, axis, split_axis=2, concat_axis=1, tiled=True)

    q, k, v = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    # local dense causal attention over the full sequence, head subset
    o = causal_attention_core(q.transpose(0, 2, 1, 3),   # [B, H/s, T, Dh]
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3))
    o = o.transpose(0, 2, 1, 3)      # [B, T, H/s, Dh]
    # reverse: gather heads, scatter sequence -> [B, T_loc, H, Dh]
    o = lax.all_to_all(o, axis, split_axis=1, concat_axis=2, tiled=True)
    return o.reshape(b, t_loc, d) @ params["wo"]
