"""The SDAR sparse block-diffusion decoder (JetLM,
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat) as plain ``jax.numpy``: the
reference for every cell whose configuration names ``"reference": "sdar"``.

Per layer of hidden ``h [N, d]`` at positions ``p [N]``: ``a = rms(h;
w_in)``; ``q = a W_q`` as ``[N, H, dh]``, ``k = a W_k``, ``v = a W_v`` as
``[N, KV, dh]``, no bias; ``q = rms(q; w_qn)``, ``k = rms(k; w_kn)`` over
each head's ``dh``; rotary over the whole head in the rotate-half
convention (``x * cos + (-x2, x1) * sin``, angle ``p * theta ** (-2i /
dh)``); query head ``i`` reads K/V head ``i // (H / KV)`` (each K/V head
repeated here for its group); softmax of ``q k^T / sqrt(dh)`` where the
mask allows; ``h = h + concat(heads) W_o``. Then ``m = rms(h; w_post)``;
``g = softmax(m W_r)`` over the ``E`` experts; ``E(t)`` the ``top_k``
largest, ``w_e = g_e / sum of g over E(t)``; ``h = h + sum over e of
gate[t, e] * ((silu(m Wg_e) * (m Wu_e)) Wd_e)`` as the plain sum over ALL
``E`` experts with ``gate[t, e] = w_e`` inside ``E(t)`` and 0 outside. No
shared expert, no capacity. ``logits = rms(h; w_f) W_h`` with its own head
matrix; the logit AT a position predicts that position's token (no shift).
``rms(x; w) = w * x / sqrt(mean(x^2) + eps)``.

Generation is by diffusion over blocks of ``B`` positions, so ONE forward a
request runs two streams (:func:`hidden`):

- the CLEAN sequence (prompt and served tokens) under the block mask:
  position ``i`` sees ``j`` iff ``j // B <= i // B``;
- NOISY groups, one for every (block, denoising forward) the served path
  took: that block's ``B`` tokens as they stood at that forward (mask
  tokens where nothing was fixed yet), at the block's positions, seeing
  the clean blocks before theirs and their own ``B`` rows.

:func:`noisy_logits` returns the logits of every noisy row: what a correct
server holds when it decides a block's tokens at that forward.

Float32 throughout; every function here runs under
``jax.default_matmul_precision("highest")`` (set by the caller). No kernel,
no cache, no batching. The weights may arrive in bfloat16 (the values the
program reads); they are held so and upcast one layer at a time.
``quant="int8"`` exists only for the control: it fake-quantises both
operands of every weight matmul symmetrically (weights per output channel,
activations per row), the experts' included.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x, w, quant):
    if quant == "int8":
        return _fake_int8(x, -1) @ _fake_int8(w, 0)
    if quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return x @ w


def _rms(w, x, eps):
    return w * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rotate(x, positions, theta):
    """``x [N, H, dh]`` rotated to ``positions [N]``, rotate-half."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    return (x * jnp.concatenate([jnp.cos(ang)] * 2, -1)
            + jnp.concatenate([-x2, x1], -1)
            * jnp.concatenate([jnp.sin(ang)] * 2, -1))


def _attention(ap, u, positions, mask, n_heads, n_kv_heads, theta, eps,
               quant):
    n = u.shape[0]
    dh = ap["q_norm"].shape[0]
    q = _mm(u, ap["wq"], quant).reshape(n, n_heads, dh)
    k = _mm(u, ap["wk"], quant).reshape(n, n_kv_heads, dh)
    v = _mm(u, ap["wv"], quant).reshape(n, n_kv_heads, dh)
    q = rotate(_rms(ap["q_norm"], q, eps), positions, theta)
    k = rotate(_rms(ap["k_norm"], k, eps), positions, theta)
    k = jnp.repeat(k, n_heads // n_kv_heads, axis=1)
    v = jnp.repeat(v, n_heads // n_kv_heads, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh)
    scores = jnp.where(mask[None], scores, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return _mm(a.reshape(n, n_heads * dh), ap["wo"], quant)


def expert_gates(probs, top_k):
    """``gate [N, E]``: the ``top_k`` largest of each row renormalised to
    sum 1, zero elsewhere."""
    w, ids = jax.lax.top_k(probs, top_k)
    w = w / w.sum(-1, keepdims=True)
    return jnp.zeros_like(probs).at[
        jnp.arange(probs.shape[0])[:, None], ids].set(w)


def experts(mp, u, top_k, quant):
    """The masked sum over all the experts of ``u [N, d]``."""
    gate = expert_gates(
        jax.nn.softmax(_mm(u, mp["router"], quant), axis=-1), top_k)

    def one(acc, xs):
        wg, wu, wd, g = xs
        mid = jax.nn.silu(_mm(u, wg, quant)) * _mm(u, wu, quant)
        return acc + g[:, None] * _mm(mid, wd, quant), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (mp["gate"], mp["up"], mp["down"], gate.T))
    return out


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "top_k", "theta", "eps", "quant"))
def _layer(bp, h, positions, mask, *, n_heads, n_kv_heads, top_k, theta,
           eps, quant):
    """One layer over ``h [N, d]``; ``bp`` is upcast here, alone."""
    bp = jax.tree.map(lambda a: a.astype(jnp.float32), bp)
    h = h + _attention(bp["attn"], _rms(bp["norm_in"], h, eps), positions,
                       mask, n_heads, n_kv_heads, theta, eps, quant)
    return h + experts(bp["moe"], _rms(bp["norm_ff"], h, eps), top_k, quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(out, norm_f, rows, *, eps, quant):
    return _mm(_rms(norm_f.astype(jnp.float32), rows, eps),
               out.astype(jnp.float32), quant)


def two_stream_mask(n_clean: int, starts, block: int):
    """``[N, N]`` bool over ``n_clean`` clean rows followed by ``len(starts)
    * block`` noisy rows (group ``r`` at positions ``starts[r] ..``): who
    sees whom, and every row's position ``[N]``."""
    n_noisy = starts.shape[0] * block
    clean_pos = jnp.arange(n_clean)
    noisy_pos = (starts[:, None] + jnp.arange(block)).reshape(-1)
    group = jnp.repeat(jnp.arange(starts.shape[0]), block)
    blk_c, blk_n = clean_pos // block, noisy_pos // block
    top = jnp.concatenate([blk_c[None, :] <= blk_c[:, None],
                           jnp.zeros((n_clean, n_noisy), bool)], axis=1)
    bottom = jnp.concatenate([blk_c[None, :] < blk_n[:, None],
                              group[None, :] == group[:, None]], axis=1)
    return (jnp.concatenate([top, bottom], axis=0),
            jnp.concatenate([clean_pos, noisy_pos]))


def hidden(params, clean, noisy, starts, *, n_heads, n_kv_heads, top_k,
           block, theta, eps, quant=None):
    """The residual stream after the last layer (before the final norm)
    over both streams: ``clean [L]`` token ids, then ``noisy [R, block]``
    token ids of the groups that start at positions ``starts [R]``. ``R``
    may be 0: the clean stream alone under the block mask."""
    mask, positions = two_stream_mask(clean.shape[0], starts, block)
    tokens = jnp.concatenate([clean, noisy.reshape(-1)])
    h = params["embed"]["tok"][tokens].astype(jnp.float32)
    for bp in params["blocks"]:
        h = _layer(bp, h, positions, mask, n_heads=n_heads,
                   n_kv_heads=n_kv_heads, top_k=top_k, theta=theta, eps=eps,
                   quant=quant)
    return h


def clean_logits(params, tokens, *, eps, quant=None, **kw):
    """Logits ``[T, V]`` of one sequence ``tokens [T]`` under the block
    mask: row ``i`` predicts the token AT position ``i``."""
    h = hidden(params, tokens, jnp.zeros((0, kw["block"]), jnp.int32),
               jnp.zeros((0,), jnp.int32), eps=eps, quant=quant, **kw)
    return _head(params["head"]["out"], params["head"]["norm_f"], h,
                 eps=eps, quant=quant)


def noisy_logits(params, clean, noisy, starts, *, eps, quant=None, **kw):
    """Logits ``[R * block, V]`` of the noisy rows, group by group."""
    h = hidden(params, clean, noisy, starts, eps=eps, quant=quant, **kw)
    return _head(params["head"]["out"], params["head"]["norm_f"],
                 h[clean.shape[0]:], eps=eps, quant=quant)
