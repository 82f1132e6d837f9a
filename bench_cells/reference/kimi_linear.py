"""The Kimi-Linear hybrid decoder (Moonshot AI,
https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct, ``model_type``
``kimi_linear``; the Kimi Linear report, arXiv:2510.26692) as plain
``jax.numpy``: the reference for every cell whose configuration names
``"reference": "kimi_linear"``.

Hidden ``h [T, d]``. The lines are ISSUE 49's, which the configuration
file's ``assumed`` repeats with their marks:

1.  Block: ``h <- h + Mix(rms(h))``, then ``h <- h + FFN(rms(h))``; ``rms(x)
    = x / sqrt(mean(x^2) + eps) * w``, two a layer.
2.  A layer is a LATENT-attention layer or a KDA layer (the tree says which:
    ``mla`` or ``kda``); its feed-forward part dense (``mlp``) or a mixture
    (``moe`` + ``shared``).
3.  KDA projections: ``q~ = u W_q``, ``k~ = u W_k``, ``v~ = u W_v``; each
    passes a causal depthwise convolution of ``d_conv`` taps over time (no
    bias) and SiLU; per head ``q = q^ / |q^|``, ``k = k^ / |k^|`` (``|x| =
    sqrt(sum x^2 + 1e-6)``), ``v = v^``; ``q`` times ``dk^-0.5``.
4.  KDA gates: ``g = -exp(A_log_h) softplus(u W_fa W_fb + dt_bias)``,
    ``alpha = exp(g)``; ``beta = sigmoid(u W_b)``.
5.  KDA recurrence, per head, ``S [dk, dk]``, ``S_0 = 0``: ``S' =
    diag(alpha_t) S_{t-1}``; ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``;
    ``o_t = S_t^T q_t``. A ``lax.scan`` over the tokens, exactly so.
6.  KDA output: ``y_t = rms_dk(o_t) w_o_norm sigmoid(z_t)``, ``z = u W_ga
    W_gb``; ``Mix(u) = concat_heads(y) W_o``.
7.  Latent attention: ``q = u W_q`` (``H`` heads of ``d_nope + d_rope``);
    ``[c_t ; r_t] = u W_kva``; ``c^_t = rms(c_t) w_kv``; per head ``[k^n ;
    v] = c^_t W_kvb,i``; key ``[k^n ; r_t]`` (``r_t`` shared by the heads,
    NO rotation of any lane); scores over ``sqrt(d_nope + d_rope)``, causal;
    ``Mix(u) = concat_heads(softmax . v) W_o``. EXPANDED: the keys and
    values of every position are made, a block of queries at a time attends
    over all of them (the program's cache and its absorbed products are its
    own affair).
9.  Router: ``s = sigmoid(u W_r)`` over all ``E``; chosen: the ``top_k``
    largest of ``s + b``; ``w_e = scale s_e / (sum of s over the chosen +
    1e-20)``.
10. Experts: ``E(x) = (silu(x W_g) * (x W_u)) W_d``; ``FFN(u) = sum over the
    chosen AND HELD of w_e E_e(u) + S(u)``, the shared experts of the same
    shape added unscaled: a pair routed to an expert that is not held adds
    nothing. A dense layer: ``FFN(u) = E_dense(u)``.
11. ``logits = rms(h) W_head`` over the held rows, the head untied.

The tree is the program's (``models/kimi_linear.py``): matrices ``[in,
out]``, a convolution's taps ``[d_conv, channels]`` (the last tap meets the
newest input), the held experts' ``[held, in, out]``, the shared experts
side by side.

Float32 throughout; every function here runs under
``jax.default_matmul_precision("highest")`` (set by the caller). No kernel,
no cache, no batching: one sequence, every token at once. The weights may
arrive in bfloat16 (the values the program reads); they are upcast a layer
(an expert) at a time, which is exact. ``quant="int8"`` exists only for the
control: it fake-quantises both operands of every weight matmul
symmetrically (weights per output channel, activations per row), the
experts', the router's and the head's included; the recurrence stays as it
is. ``state_dtype`` (tests only): the recurrence's state rounded to that
dtype after every token, the nearest thing to a served state held in less
than float32.
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


def _rms(w, x, eps):                                          # line 1
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _conv_silu(w, x):
    """Line 3's convolution over the whole sequence ``x [T, C]`` from an
    empty past: ``y_t = sum_j w[j] x_{t - (K - 1) + j}``, then SiLU."""
    k = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return jax.nn.silu(sum(padded[j:j + x.shape[0]] * w[j]
                           for j in range(k)))


def delta_rule(q, k, v, alpha, beta, state_dtype=None):
    """Line 5 for every head: ``q``, ``k``, ``alpha [T, H, dk]``, ``v [T, H,
    dv]``, ``beta [T, H]`` -> ``o [T, H, dv]``."""
    def token(s, x):
        q_t, k_t, v_t, a_t, b_t = x
        s = a_t[:, :, None] * s
        err = v_t - jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + b_t[:, None, None] * k_t[:, :, None] * err[:, None, :]
        if state_dtype is not None:
            s = s.astype(state_dtype).astype(jnp.float32)
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(token, s0, (q, k, v, alpha, beta))[1]


def kda_part(kp, u, eps, quant, state_dtype=None):
    """``Mix(u) [T, d]`` of a KDA layer."""
    t = u.shape[0]
    nh, dk = kp["A_log"].shape[0], kp["o_norm"].shape[0]
    heads = lambda x: x.reshape(t, nh, dk)  # noqa: E731
    unit = lambda x: x / jnp.sqrt((x * x).sum(-1, keepdims=True)  # noqa: E731
                                  + 1e-6)
    q, k, v = (heads(_conv_silu(kp["conv_" + n], _mm(u, kp["w" + n], quant)))
               for n in "qkv")                                # line 3
    q, k = unit(q) * dk ** -0.5, unit(k)
    g = -jnp.exp(kp["A_log"])[:, None] * heads(jax.nn.softplus(
        _mm(_mm(u, kp["f_a"], quant), kp["f_b"], quant) + kp["dt_bias"]))
    beta = jax.nn.sigmoid(_mm(u, kp["w_beta"], quant))        # line 4
    o = delta_rule(q, k, v, jnp.exp(g), beta, state_dtype)    # line 5
    z = heads(_mm(_mm(u, kp["g_a"], quant), kp["g_b"], quant))
    y = _rms(kp["o_norm"], o, eps) * jax.nn.sigmoid(z)        # line 6
    return _mm(y.reshape(t, nh * dk), kp["wo"], quant)


def latent_part(mp, u, n_heads, eps, quant):
    """``Mix(u) [T, d]`` of a latent-attention layer (line 7), a block of
    queries at a time over all the expanded keys and values."""
    t = u.shape[0]
    d_latent = mp["kv_norm"].shape[0]
    d_rope = mp["wkv_a"].shape[1] - d_latent
    d_qk = mp["wq"].shape[1] // n_heads
    d_nope = d_qk - d_rope
    q = _mm(u, mp["wq"], quant).reshape(t, n_heads, d_qk)
    ckv = _mm(u, mp["wkv_a"], quant)
    c = _rms(mp["kv_norm"], ckv[:, :d_latent], eps)
    kv = _mm(c, mp["wkv_b"], quant).reshape(t, n_heads, -1)
    k = jnp.concatenate([kv[..., :d_nope], jnp.broadcast_to(
        ckv[:, None, d_latent:], (t, n_heads, d_rope))], axis=-1)
    v = kv[..., d_nope:]
    block = min(_Q_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions are no whole blocks of {block}")

    def one(start):
        rows = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = jnp.einsum("qhd,khd->hqk", rows, k) / math.sqrt(d_qk)
        seen = (start + jnp.arange(block)[:, None]) >= jnp.arange(t)[None, :]
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(
            jnp.where(seen, scores, -jnp.inf), axis=-1), v)

    a = jax.lax.map(one, jnp.arange(0, t, block)).reshape(t, -1)
    return _mm(a, mp["wo"], quant)


def _expert(x, wg, wu, wd, quant):                            # line 10
    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd, quant)


def router(w_r, bias, u, top_k, scale, quant):
    """``(w [T, top_k], chosen [T, top_k])`` over all the experts."""
    s = jax.nn.sigmoid(_mm(u, w_r, quant))                    # line 9
    _, chosen = jax.lax.top_k(s + bias, top_k)
    top = jnp.take_along_axis(s, chosen, axis=-1)
    return scale * top / (top.sum(-1, keepdims=True) + 1e-20), chosen


def ffn_part(bp, u, top_k, scale, first_expert, quant):
    """``FFN(u) [T, d]``. ``bp``'s expert matrices may still be in the dtype
    they are held in: one expert is upcast at a time."""
    f32 = jnp.float32
    up = lambda tree: jax.tree.map(lambda a: a.astype(f32), tree)  # noqa: E731
    if "mlp" in bp:
        m = up(bp["mlp"])
        return _expert(u, m["gate"], m["up"], m["down"], quant)
    w, chosen = router(bp["moe"]["router"].astype(f32),
                       bp["moe"]["bias"].astype(f32), u, top_k, scale, quant)

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
    sp = up(bp["shared"])
    return routed + _expert(u, sp["gate"], sp["up"], sp["down"], quant)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "top_k", "scale", "first_expert", "eps", "quant",
    "state_dtype"))
def layer(bp, h, *, n_heads, top_k, scale, first_expert, eps, quant=None,
          state_dtype=None):
    """One layer over ``h [T, d]``, of the kinds its tree names."""
    f32 = jnp.float32
    u = _rms(bp["norm1"].astype(f32), h, eps)
    if "mla" in bp:
        mix = latent_part(jax.tree.map(lambda a: a.astype(f32), bp["mla"]),
                          u, n_heads, eps, quant)
    else:
        mix = kda_part(jax.tree.map(lambda a: a.astype(f32), bp["kda"]), u,
                       eps, quant, state_dtype)
    h = h + mix                                               # line 1
    return h + ffn_part(bp, _rms(bp["norm2"].astype(f32), h, eps), top_k,
                        scale, first_expert, quant)


def hidden(params, tokens, *, quant=None, **kw):
    """The residual stream ``[T, d]`` after the last layer (before the final
    norm) for one sequence ``tokens [T]``."""
    h = params["embed"]["tok"][tokens].astype(jnp.float32)
    for bp in params["blocks"]:
        h = layer(bp, h, quant=quant, **kw)
    return h


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(hp, rows, *, eps, quant=None):
    """``logits [n, V]`` of the residual rows ``rows [n, d]``."""
    f32 = jnp.float32
    return _mm(_rms(hp["norm_f"].astype(f32), rows, eps),
               hp["out"].astype(f32), quant)                  # line 11


def full_logits(params, tokens, *, eps, quant=None, **kw):
    """Logits ``[T, V]`` of one whole sequence."""
    h = hidden(params, tokens, eps=eps, quant=quant, **kw)
    return head(params["head"], h, eps=eps, quant=quant)
