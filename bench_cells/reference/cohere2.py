"""The Cohere2 mixture decoder (CohereLabs,
https://huggingface.co/CohereLabs/command-a-plus-05-2026, ``model_type``
``cohere2_moe``) as plain ``jax.numpy``: the reference for every cell whose
configuration names ``"reference": "cohere2"``.

Hidden ``h [T, d]``. A layer, with ``H`` query heads over ``KV`` K/V heads
of ``dh`` lanes, ``E`` routed experts of which this build holds ``held``
from ``first_expert`` on, and ``S`` shared experts:

1. Norm: ``u = (h - mean(h)) / sqrt(var(h) + eps) * g``, mean and variance
   over the channels, ``g [d]``, NO bias. ONE norm a layer.
2. Parallel block: ``h <- h + Attn(u) + FFN(u)``: both read the same ``u``.
3. ``q = u W_q`` (``H dh``), ``k = u W_k``, ``v = u W_v`` (``KV dh``); query
   head ``i`` reads K/V head ``i // (H / KV)``; scores over ``sqrt(dh)``.
4. Layer ``l`` is a WINDOW layer unless ``l % full_every == full_every -
   1``: rotary at position ``t`` over the whole head, NEIGHBOURING lanes
   paired (pair ``i`` is lanes ``(2i, 2i + 1)``, angle ``t theta^(-2i /
   dh)``, ``(x_2i, x_2i+1) -> (x_2i cos - x_2i+1 sin, x_2i+1 cos + x_2i
   sin)``); query ``t`` attends keys ``j`` with ``t - window < j <= t``. A
   FULL layer: no position encoding at all, causal over every earlier key.
5. ``Attn(u) = concat_heads(softmax(q k^T / sqrt(dh)) v) W_o``.
6. Router: ``s = sigmoid(u W_r)`` over all ``E``; chosen: the ``top_k``
   largest; ``w_e = s_e / sum of s over the chosen``.
7. Experts: ``E(x) = (silu(x W_g) * (x W_u)) W_d``, routed and shared of one
   shape.
8. ``FFN(u) = sum over the chosen AND HELD of w_e E_e(u) + (1 / S) sum_i
   S_i(u)``: a pair routed to an expert that is not held adds nothing.
10. After the last layer ``logits = logit_scale * norm_f(h) Emb^T`` with the
    held rows of the embedding itself, ``norm_f`` as line 1.

The tree is the program's (``models/cohere2.py``): matrices ``[in, out]``,
the held experts' ``[held, in, out]``, the shared experts side by side
(``gate`` / ``up [d, S f]``, ``down [S f, d]``: expert ``i`` is columns, and
rows, ``[i f, (i + 1) f)``); here they are taken apart again and run as
``S`` separate experts.

Float32 throughout; every function here runs under
``jax.default_matmul_precision("highest")`` (set by the caller). No kernel,
no cache, no batching: one sequence, every token at once, the window a mask
built from positions. Two things are blocked so that 32,768 positions fit,
neither changing a number's meaning: attention runs one K/V head's group of
query heads and a block of 512 queries at a time over ALL the keys, and every
held expert runs over all the rows and keeps its own (a mask). The weights
may arrive in bfloat16 (the values the program reads); they are upcast one
layer (one expert) at a time, which is exact. ``quant="int8"`` exists only
for the control: it fake-quantises both operands of every weight matmul
symmetrically (weights per output channel, activations per row), the
experts', the router's and the head's included.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: queries of one attention block
_Q_BLOCK = 512


def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x, w, quant):
    """``x [..., in] @ w [in, out]``."""
    if quant == "int8":
        return _fake_int8(x, -1) @ _fake_int8(w, -2)
    if quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return x @ w


def _norm(g, x, eps):                                         # line 1
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g


def _rotary(x, theta):
    """``x [T, heads, dh]`` at positions ``0 .. T - 1``, neighbouring lanes
    paired."""
    t, n, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                      odd * jnp.cos(ang) + even * jnp.sin(ang)],
                     axis=-1).reshape(t, n, dh)


def _attend(q, k, v, window):
    """Softmax attention of ONE K/V head's query heads ``q [T, G, dh]`` over
    its ``k`` / ``v [T, dh]``: query ``t`` sees keys ``j <= t`` and, with a
    ``window``, ``t - j < window``. A block of queries at a time."""
    t, _, dh = q.shape
    block = min(_Q_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions are no whole blocks of {block}")

    def one(start):
        rows = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = jnp.einsum("qgd,kd->gqk", rows, k) / math.sqrt(dh)
        back = (start + jnp.arange(block)[:, None]) - jnp.arange(t)[None, :]
        seen = back >= 0
        if window is not None:
            seen &= back < window
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(one, jnp.arange(0, t, block))
    return out.reshape(t, -1)                                 # [T, G dh]


def attention_part(ap, u, n_heads, n_kv_heads, theta, window, quant):
    """``Attn(u) [T, d]``, one K/V head's group of query heads after the
    other (a scan: the group's queries, scores and outputs are the only
    ones alive): ``concat_heads(...) W_o`` is the sum over the groups of
    each group's heads times its rows of ``W_o`` (the int8 control scales a
    group's rows on their own)."""
    t, d = u.shape
    group = n_heads // n_kv_heads
    dh = ap["wq"].shape[1] // n_heads
    wq = ap["wq"].reshape(d, n_kv_heads, group * dh)
    wk = ap["wk"].reshape(d, n_kv_heads, dh)
    wv = ap["wv"].reshape(d, n_kv_heads, dh)
    wo = ap["wo"].reshape(n_kv_heads, group * dh, d)

    def one(acc, xs):
        wq_g, wk_g, wv_g, wo_g = xs
        q = _mm(u, wq_g, quant).reshape(t, group, dh)         # line 3
        k = _mm(u, wk_g, quant).reshape(t, 1, dh)
        v = _mm(u, wv_g, quant)
        if window is not None:                                # line 4
            q, k = _rotary(q, theta), _rotary(k, theta)
        o = _attend(q, k[:, 0], v, window)
        return acc + _mm(o, wo_g, quant), None                # line 5

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.moveaxis(wq, 1, 0), jnp.moveaxis(wk, 1, 0),
        jnp.moveaxis(wv, 1, 0), wo))
    return out


def _expert(x, wg, wu, wd, quant):                            # line 7
    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd, quant)


def router(w_r, u, top_k, quant):
    """``(w [T, top_k], chosen [T, top_k])`` over all the experts."""
    s = jax.nn.sigmoid(_mm(u, w_r, quant))                    # line 6
    top, chosen = jax.lax.top_k(s, top_k)
    return top / top.sum(-1, keepdims=True), chosen


def expert_part(bp, u, top_k, first_expert, n_shared, quant):
    """``FFN(u) [T, d]``. ``bp``'s expert matrices may still be in the dtype
    they are held in: one expert is upcast at a time."""
    f32 = jnp.float32
    w, chosen = router(bp["moe"]["router"].astype(f32), u, top_k, quant)

    def one(acc, xs):
        e, wg, wu, wd = xs
        y = _expert(u, wg.astype(f32), wu.astype(f32), wd.astype(f32), quant)
        mine = jnp.where(chosen == first_expert + e, w, 0.0).sum(-1)
        return acc + mine[:, None] * y, None

    held = bp["moe"]["gate"].shape[0]
    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (jnp.arange(held), bp["moe"]["gate"], bp["moe"]["up"],
         bp["moe"]["down"]))
    sp = bp["shared"]
    f = sp["gate"].shape[1] // n_shared
    shared = sum(_expert(u, sp["gate"][:, i * f:(i + 1) * f].astype(f32),
                         sp["up"][:, i * f:(i + 1) * f].astype(f32),
                         sp["down"][i * f:(i + 1) * f].astype(f32), quant)
                 for i in range(n_shared))
    return routed + shared / n_shared                         # line 8


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "theta", "window", "top_k", "first_expert",
    "n_shared", "eps", "quant"))
def layer(bp, h, *, n_heads, n_kv_heads, theta, window, top_k, first_expert,
          n_shared, eps, quant=None):
    """One layer over ``h [T, d]``: a window layer with ``window``, a full
    layer with ``None``."""
    f32 = jnp.float32
    u = _norm(bp["norm"].astype(f32), h, eps)
    ap = jax.tree.map(lambda a: a.astype(f32), bp["attn"])
    return h + attention_part(ap, u, n_heads, n_kv_heads, theta, window,
                              quant) + expert_part(
        bp, u, top_k, first_expert, n_shared, quant)          # line 2


def layer_window(l: int, window: int, full_every: int):
    return None if l % full_every == full_every - 1 else window


def hidden(params, tokens, *, window, full_every, quant=None, **kw):
    """The residual stream ``[T, d]`` after the last layer (before the final
    norm) for one sequence ``tokens [T]``."""
    h = params["embed"]["tok"][tokens].astype(jnp.float32)
    for l, bp in enumerate(params["blocks"]):
        h = layer(bp, h, window=layer_window(l, window, full_every),
                  quant=quant, **kw)
    return h


@functools.partial(jax.jit, static_argnames=("eps", "logit_scale", "quant"))
def head(norm_f, table, rows, *, eps, logit_scale, quant=None):
    """``logits [n, V]`` of the residual rows ``rows [n, d]``."""
    f32 = jnp.float32
    return logit_scale * _mm(_norm(norm_f.astype(f32), rows, eps),
                             table.astype(f32).T, quant)      # line 10


def full_logits(params, tokens, *, eps, logit_scale=1.0, quant=None, **kw):
    """Logits ``[T, V]`` of one whole sequence."""
    h = hidden(params, tokens, eps=eps, quant=quant, **kw)
    return head(params["head"]["norm_f"], params["embed"]["tok"], h, eps=eps,
                logit_scale=logit_scale, quant=quant)
