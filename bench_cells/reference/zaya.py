"""The ZAYA1 decoder (Zyphra, https://huggingface.co/Zyphra/ZAYA1-8B,
``model_type`` ``zaya``) as plain ``jax.numpy``: the reference for every
cell whose configuration names ``"reference": "zaya"``.

Hidden ``h [T, d]``; ``rms(x; w) = w * x / sqrt(mean(x^2) + eps)``. A layer
is two parts, each merged as ``h <- (s_r * h + b_r) + (s_o * y + b_o)``
(four vectors a part). With ``H`` query heads over ``KV`` K/V heads of
``dh`` lanes and ``x_{-1} = 0`` for every shifted row:

Attention part, ``u = rms(h; w_a)``:

1. ``c_t = u_t W_qk = [q~_t ; k~_t]`` (``H dh`` then ``KV dh`` channels).
2. Value shift: ``[v1 ; v2] = u W_v``; the first half of the K/V heads is
   ``v1_t`` (this token's), the second ``v2_{t-1}`` (the token before's).
3. Convolutions over the sequence, causal, zeros before position 0:
   ``a_t = sum_j w0[j] * c_{t-(k0-1)+j} + b0`` (depthwise); then per head
   (``H + KV`` groups of ``dh`` channels) ``g_t = sum_j a_{t-(k1-1)+j}
   A[j] + b1`` with ``A[j]`` a ``dh x dh`` matrix a group.
4. q-k mean: ``q = g^q + (q~ + rep(k~)) / 2``, ``k = g^k + (mean(q~) +
   k~) / 2``: ``k~`` repeated over its ``H / KV`` query heads, ``q~``
   averaged over them.
5. Per head ``q^ = sqrt(dh) q / |q|``, ``k^ = tau sqrt(dh) k / |k|``,
   ``tau`` one scalar a K/V head.
6. Rotary at position ``t`` over the first ``rotated`` lanes of each head,
   rotate-half inside them (lane ``i`` with lane ``i + rotated / 2``,
   angle ``t theta^(-2i / rotated)``), the rest passed through.
7. Causal ``softmax(q^_h . k^_g(h) / sqrt(dh)) v_g(h)``, query head ``h``
   reading K/V head ``h // (H / KV)``; ``y = o W_o``.

Expert part, ``u = rms(h; w_m)``:

8. Router state ``r_l = u W_down + b_down``, and for every layer but the
   first ``+ gamma_l * r_{l-1}`` (the same token's state of the layer
   before, after its own sum); ``s = W_3 gelu(W_2 gelu(W_1 rms(r_l; w_r) +
   b_1) + b_2)`` (exact ``erf`` GELU); ``p = softmax(s)``; ``e* =
   argmax(p + bias)``; ``y = p_{e*} E_{e*}(u)``.
9. ``E_e(x) = (silu(x Wg_e) * (x Wu_e)) Wd_e``, computed here for every
   expert over every row and kept by a boolean mask of the expert's own
   rows.

After the last layer ``logits = rms(h; w_f) Emb^T`` with the embedding
itself.

The tree is the program's (``models/zaya.py``): matrices ``[in, out]``,
the experts' ``[E, in, out]``, ``conv1_w [k1, H + KV, dh, dh]``.

Float32 throughout; every function here runs under
``jax.default_matmul_precision("highest")`` (set by the caller). No kernel,
no cache, no batching: one sequence, every token at once, the convolutions
as shifted sums over the whole sequence. Two things are blocked so that
8,192 positions fit beside the weights, neither changing a number's
meaning: attention runs a block of queries at a time over all the keys, and
the head is read a slice of the vocabulary at a time (:func:`served_gaps`).
The weights may arrive in bfloat16 (the values the program reads); they are
held so and upcast one layer (one expert, one slice of the embedding) at a
time, which is exact. ``quant="int8"`` exists only for the control: it
fake-quantises both operands of every weight matmul symmetrically (weights
per output channel, activations per row), the experts', the router's and
the per-head convolution's included.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: queries of one attention block, and the most slices the head is read in
_Q_BLOCK = 1024
_V_SLICES = 8


def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x, w, quant):
    """``x [..., in] @ w [..., in, out]``."""
    if quant == "int8":
        return _fake_int8(x, -1) @ _fake_int8(w, -2)
    if quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return x @ w


def _rms(w, x, eps):
    return w * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _back(x, k):
    """Row ``t`` of the result is row ``t - k`` of ``x [T, ...]``, zeros
    before the first."""
    return jnp.pad(x, ((k, 0),) + ((0, 0),) * (x.ndim - 1))[:x.shape[0]]


def _rotary(x, theta, rotated):
    """``x [T, heads, dh]`` at positions ``0 .. T - 1``."""
    half = rotated // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv
    x1, x2, rest = x[..., :half], x[..., half:rotated], x[..., rotated:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang), rest],
                           axis=-1)


def _attend(q, k, v):
    """Causal softmax attention of ``q [T, H, dh]`` over ``k`` / ``v [T,
    H, dh]``, a block of queries at a time."""
    t, n_heads, dh = q.shape
    block = min(_Q_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions are no whole blocks of {block}")

    def one(start):
        rows = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = jnp.einsum("qhd,khd->hqk", rows, k) / math.sqrt(dh)
        seen = (jnp.arange(t)[None, :]
                <= start + jnp.arange(block)[:, None])
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(one, jnp.arange(0, t, block))
    return out.reshape(t, n_heads * dh)


def attention_part(ap, u, n_heads, n_kv_heads, theta, rotated, quant):
    t = u.shape[0]
    k0, k1 = ap["conv0_w"].shape[0], ap["conv1_w"].shape[0]
    dh = ap["conv1_w"].shape[-1]
    group = n_heads // n_kv_heads
    c = _mm(u, ap["wqk"], quant)                              # line 1
    v1, v2 = jnp.split(_mm(u, ap["wv"], quant), 2, axis=-1)   # line 2
    v = jnp.concatenate([v1, _back(v2, 1)], axis=-1).reshape(
        t, n_kv_heads, dh)
    a = sum(_back(c, k0 - 1 - j) * ap["conv0_w"][j]           # line 3
            for j in range(k0)) + ap["conv0_b"]
    a = a.reshape(t, n_heads + n_kv_heads, dh)
    g = sum(jnp.swapaxes(_mm(jnp.swapaxes(_back(a, k1 - 1 - j), 0, 1),
                             ap["conv1_w"][j], quant), 0, 1)
            for j in range(k1)) + ap["conv1_b"].reshape(-1, dh)
    c = c.reshape(t, n_heads + n_kv_heads, dh)
    q_raw = c[:, :n_heads].reshape(t, n_kv_heads, group, dh)  # line 4
    k_raw = c[:, n_heads:]
    q = g[:, :n_heads] + (q_raw + k_raw[:, :, None]).reshape(
        t, n_heads, dh) / 2
    k = g[:, n_heads:] + (q_raw.mean(axis=2) + k_raw) / 2
    length = lambda x: jnp.sqrt((x * x).sum(-1, keepdims=True))  # noqa: E731
    q = math.sqrt(dh) * q / length(q)                         # line 5
    k = ap["tau"][:, None] * math.sqrt(dh) * k / length(k)
    q, k = _rotary(q, theta, rotated), _rotary(k, theta, rotated)  # line 6
    o = _attend(q, jnp.repeat(k, group, axis=1),              # line 7
                jnp.repeat(v, group, axis=1))
    return _mm(o, ap["wo"], quant)


def router(rp, u, carried, eps, quant):
    """``(p [T, E], the chosen expert [T], the state r [T, R])``."""
    r = _mm(u, rp["down"], quant) + rp["down_b"]
    if carried is not None:
        r = r + rp["gamma"] * carried
    x = _rms(rp["norm"], r, eps)
    x = jax.nn.gelu(_mm(x, rp["w1"], quant) + rp["b1"], approximate=False)
    x = jax.nn.gelu(_mm(x, rp["w2"], quant) + rp["b2"], approximate=False)
    p = jax.nn.softmax(_mm(x, rp["w3"], quant), axis=-1)
    return p, jnp.argmax(p + rp["bias"], axis=-1), r


def expert_part(ep, u, carried, eps, quant):
    """``(p_{e*} E_{e*}(u) [T, d], r [T, R])``. ``ep``'s expert matrices
    may still be in the dtype they are held in: one expert is upcast at a
    time."""
    f32 = jnp.float32
    p, chosen, r = router(
        jax.tree.map(lambda a: a.astype(f32), ep["router"]), u, carried,
        eps, quant)
    weight = jnp.take_along_axis(p, chosen[:, None], axis=-1)

    def one(acc, xs):
        e, wg, wu, wd = xs
        wg, wu, wd = wg.astype(f32), wu.astype(f32), wd.astype(f32)
        y = _mm(jax.nn.silu(_mm(u, wg, quant)) * _mm(u, wu, quant), wd,
                quant)
        return jnp.where((chosen == e)[:, None], weight * y, acc), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (jnp.arange(ep["gate"].shape[0]), ep["gate"], ep["up"], ep["down"]))
    return out, r


def _merge(part, h, y):
    return (part["res_scale"] * h + part["res_bias"]) + (
        part["out_scale"] * y + part["out_bias"])


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "theta", "rotated", "eps", "quant"))
def _layer(bp, h, carried, *, n_heads, n_kv_heads, theta, rotated, eps,
           quant):
    """One layer over ``h [T, d]`` with the router's state ``carried`` (or
    ``None``: the first layer); ``bp`` is upcast here, alone."""
    f32 = jnp.float32
    ap = jax.tree.map(lambda a: a.astype(f32), bp["attn"])
    h = _merge(ap, h, attention_part(
        ap, _rms(ap["norm"], h, eps), n_heads, n_kv_heads, theta, rotated,
        quant))
    experts = ("gate", "up", "down")
    ep = {k: (v if k in experts else jax.tree.map(
        lambda a: a.astype(f32), v)) for k, v in bp["moe"].items()}
    y, carried = expert_part(ep, _rms(ep["norm"], h, eps), carried, eps,
                             quant)
    return _merge(ep, h, y), carried


def hidden(params, tokens, *, quant=None, **kw):
    """The residual stream ``[T, d]`` after the last layer (before the final
    norm) for one sequence ``tokens [T]``."""
    h = params["embed"]["tok"][tokens].astype(jnp.float32)
    carried = None
    for bp in params["blocks"]:
        h, carried = _layer(bp, h, carried, quant=quant, **kw)
    return h


@functools.partial(jax.jit, static_argnames=("n_out", "eps"))
def _head_rows(norm_f, h, first, *, n_out, eps):
    return _rms(norm_f.astype(jnp.float32),
                jax.lax.dynamic_slice_in_dim(h, first, n_out, 0), eps)


def full_logits(params, tokens, *, eps, quant=None, **kw):
    """Logits ``[T, V]`` of one whole sequence."""
    h = hidden(params, tokens, eps=eps, quant=quant, **kw)
    rows = _head_rows(params["head"]["norm_f"], h, 0,
                      n_out=tokens.shape[0], eps=eps)
    return _mm(rows, params["embed"]["tok"].astype(jnp.float32).T, quant)


@functools.partial(jax.jit, static_argnames=("quant",))
def _gaps(table, rows, got_rows, served, *, quant):
    """The head, a slice of the vocabulary at a time: per row, how far the
    served token's reference logit lies below the reference's best, and the
    same for the token the ``quant`` head over ``got_rows`` puts first."""
    f32 = jnp.float32
    vocab, d = table.shape
    n_slices = math.gcd(vocab, _V_SLICES)
    per = vocab // n_slices
    low = jnp.full(rows.shape[0], -jnp.inf, f32)

    def one(carry, xs):
        best, at_served, got_best, at_got = carry
        start, part = xs
        part = part.astype(f32).T                             # [d, per]
        ref = _mm(rows, part, None)
        pick = lambda ids: jnp.take_along_axis(  # noqa: E731
            ref, jnp.clip(ids - start, 0, per - 1)[:, None], axis=-1)[:, 0]
        here = (served >= start) & (served < start + per)
        got = ref if quant is None else _mm(got_rows, part, quant)
        top = got.max(-1)
        return (jnp.maximum(best, ref.max(-1)),
                jnp.where(here, pick(served), at_served),
                jnp.maximum(got_best, top),
                jnp.where(top > got_best,
                          pick(start + jnp.argmax(got, -1)), at_got)), None

    (best, at_served, _, at_got), _ = jax.lax.scan(
        one, (low, low, low, low),
        (jnp.arange(0, vocab, per), table.reshape(n_slices, per, d)))
    return best - at_served, best - at_got


def served_gaps(params, tokens, first, served, *, eps, quant=None, **kw):
    """Over positions ``first .. first + len(served) - 1`` of one padded
    sequence ``tokens [T]`` (row ``i`` is what a correct server holds when
    it chooses output token ``i``): how far ``served[i]``'s reference logit
    lies under the reference's best there, and the same for the token the
    ``quant`` forward puts first (the control's reading; the reference's
    own best, a gap of 0, where ``quant`` is ``None``). Causal attention,
    causal convolutions and a shift that looks back make the padding behind
    the last real token irrelevant to those rows."""
    n_out = served.shape[0]
    norm_f, table = params["head"]["norm_f"], params["embed"]["tok"]
    rows = _head_rows(norm_f, hidden(params, tokens, eps=eps, **kw), first,
                      n_out=n_out, eps=eps)
    got_rows = rows if quant is None else _head_rows(
        norm_f, hidden(params, tokens, eps=eps, quant=quant, **kw), first,
        n_out=n_out, eps=eps)
    return _gaps(table, rows, got_rows, served, quant=quant)
