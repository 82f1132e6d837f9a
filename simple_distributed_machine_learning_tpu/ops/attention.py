"""Attention: causal multi-head self-attention + ring attention over a mesh.

The reference has no attention at all (conv+FC only, SURVEY §5.7); the
tiny-GPT pipeline config (BASELINE.json config 5) introduces a sequence axis,
and long-context support is first-class in this framework: ``ring_attention``
shards the sequence over a mesh axis and rotates K/V blocks with
``lax.ppermute`` over ICI — the same collective the pipeline engine uses for
stage hops — with blockwise-stable (flash-style) softmax accumulation, so
attention over sequences far larger than one chip's HBM is a mesh-width knob,
not a rewrite.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import PartitionSpec as P

SEQ_AXIS = "seq"


def mha_init(key: jax.Array, d_model: int, n_heads: int,
             dtype=jnp.float32) -> dict:
    """QKVO projection params for multi-head attention."""
    if d_model % n_heads:
        raise ValueError(f"d_model {d_model} not divisible by {n_heads} heads")
    ks = jax.random.split(key, 4)
    bound = 1.0 / math.sqrt(d_model)

    def w(k):
        return jax.random.uniform(k, (d_model, d_model), dtype,
                                  minval=-bound, maxval=bound)

    return {"wq": w(ks[0]), "wk": w(ks[1]), "wv": w(ks[2]), "wo": w(ks[3])}


def _split_heads(x: jax.Array, n_heads: int) -> jax.Array:
    b, t, d = x.shape
    return x.reshape(b, t, n_heads, d // n_heads).transpose(0, 2, 1, 3)


def _merge_heads(x: jax.Array) -> jax.Array:
    b, h, t, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * dh)


def causal_attention_core(q: jax.Array, k: jax.Array,
                          v: jax.Array) -> jax.Array:
    """Dense causal softmax attention on split heads: [B, H, T, Dh] each.

    The single source of the masked-softmax math — reused by
    :func:`causal_attention` and the Ulysses sequence-parallel path
    (``parallel/sequence.py``); the Pallas kernel and ring attention are
    tested against it.
    """
    dh = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(dh)
    t = q.shape[2]
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def causal_attention(params: dict, x: jax.Array, n_heads: int) -> jax.Array:
    """Standard causal MHA on one device. x: [B, T, D] -> [B, T, D]."""
    h = n_heads
    q = _split_heads(x @ params["wq"], h)
    k = _split_heads(x @ params["wk"], h)
    v = _split_heads(x @ params["wv"], h)
    return _merge_heads(causal_attention_core(q, k, v)) @ params["wo"]


def _block_accumulate(q, k, v, acc, q_off, k_off, scale):
    """One flash-style block: fold (k, v) into the running (o, l, m) for q.

    q: [B,H,Tq,Dh]; k/v: [B,H,Tk,Dh]; positions are global offsets for the
    causal mask. Numerically stable: running rowmax m, normalizer l.
    """
    o, l, m = acc
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    tq, tk = q.shape[2], k.shape[2]
    qpos = q_off + jnp.arange(tq)[:, None]
    kpos = k_off + jnp.arange(tk)[None, :]
    scores = jnp.where(qpos >= kpos, scores, -jnp.inf)
    m_new = jnp.maximum(m, scores.max(-1))
    # guard: rows with everything masked so far keep m=-inf; exp(-inf+inf)=nan
    corr = jnp.where(jnp.isneginf(m_new), 0.0, jnp.exp(m - m_new))
    p = jnp.exp(scores - m_new[..., None])
    p = jnp.where(jnp.isneginf(scores), 0.0, p)
    o = o * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    l = l * corr + p.sum(-1)
    return o, l, m_new


def ring_attention(params: dict, x: jax.Array, n_heads: int,
                   axis: str = SEQ_AXIS) -> jax.Array:
    """Causal MHA with the sequence sharded over mesh axis ``axis``.

    Must be called inside ``shard_map``: ``x`` is this device's local sequence
    chunk ``[B, T_local, D]`` (chunk i = global positions
    ``[i*T_local, (i+1)*T_local)``). K/V blocks rotate around the ring via
    ``ppermute``; each hop rides ICI and XLA overlaps it with the current
    block's attention compute. Output matches :func:`causal_attention` on the
    gathered sequence to float tolerance (see tests/test_attention.py).
    """
    h = n_heads
    s = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    q = _split_heads(x @ params["wq"], h)
    k = _split_heads(x @ params["wk"], h)
    v = _split_heads(x @ params["wv"], h)
    b, _, t_loc, dh = q.shape
    scale = 1.0 / math.sqrt(dh)
    q_off = idx * t_loc

    def body(carry, r):
        k_r, v_r, acc = carry
        src = (idx - r) % s           # whose block we currently hold
        acc = _block_accumulate(q, k_r, v_r, acc, q_off, src * t_loc, scale)
        # pass K/V to the next device in the ring (device i -> i+1), so at
        # step r+1 we hold block (idx - r - 1): walking left = causal history
        perm = [(i, (i + 1) % s) for i in range(s)]
        k_r = lax.ppermute(k_r, axis, perm)
        v_r = lax.ppermute(v_r, axis, perm)
        return (k_r, v_r, acc), None

    # derive (l, m) from q so they inherit q's full varying-axes type — the
    # scan carry must type-match the loop body under check_vma no matter
    # which enclosing mesh axes (seq alone, or the pipeline's data/stage/
    # model too) the inputs vary over
    acc0 = (jnp.zeros_like(q),
            jnp.zeros_like(q[..., 0]),
            jnp.full_like(q[..., 0], -jnp.inf))
    (_, _, (o, l, _)), _ = lax.scan(body, (k, v, acc0), jnp.arange(s))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return _merge_heads(out) @ params["wo"]
