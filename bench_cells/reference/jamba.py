"""The Jamba hybrid decoder (AI21, https://huggingface.co/ai21labs/AI21-Jamba2-3B)
as plain ``jax.numpy``: the reference for every cell whose configuration
names ``"reference": "jamba"``.

Per layer ``i`` of hidden ``h [T, d]``: ``h = h + mixer_i(rms(h; w_in))``,
then ``h = h + mlp(rms(h; w_ff))``, ``rms(x; w) = w * x / sqrt(mean(x^2) +
eps)``; ``mlp(x) = W_down (silu(W_gate x) * (W_up x))``. A layer that holds
``attn`` weights is attention, the others are Mamba (the order is the
configuration's: the program's builder decides it from ``attn_layer_period``
/ ``attn_layer_offset``, and this file follows the tree it is given).

- Attention: ``q = W_q x`` (``n_heads`` heads), ``k = W_k x``, ``v = W_v
  x`` (``n_kv_heads`` heads, each repeated here for the query heads of its
  group), no bias, NO positional encoding of any kind, causal softmax of
  ``q k^T / sqrt(head)``, ``W_o``.
- Mamba-1 with Jamba's inner norms: ``[x, z] = W_in u``; ``x_t =
  silu(conv(x)_t + b_conv)``, ``conv(x)_t = sum_k w[k] * x_{t - (K - 1) +
  k}`` (causal, depthwise, zeros before the first token); ``[dt, B, C] =
  W_x x_t``, each through its own ``rms``; ``delta = softplus(W_dt dt +
  b_dt)``; ``A = -exp(A_log)``; ``H_t = exp(delta * A) * H_{t-1} + (delta *
  x_t) B_t``, ``H_0 = 0``; ``y_t = H_t C_t + D * x_t``; ``W_out (y_t *
  silu(z_t))``. The recurrence is a ``lax.scan`` over the tokens.
- Model: token embedding, the layers, final ``rms``, ``logits = E h`` with
  the embedding matrix itself.

Departures from the published model, shared with the program under test and
stated in the configuration file: weights random from a seed. The tree is
the program's (``models/jamba.py``): ``A_log`` is stored ``[d_state,
d_inner]``, matrices ``[in, out]``.

Float32 throughout; every function here runs under
``jax.default_matmul_precision("highest")`` (set by the caller). No kernel,
no cache, no batching: one sequence, every token at once. The weights may
arrive in bfloat16 (the values the program reads); they are held so and
upcast one layer at a time, which is exact and keeps 3 B parameters at 6 GB
beside the activations. ``quant="int8"`` exists only for the control: it
fake-quantises both operands of every weight matmul symmetrically (weights
per output channel, activations per row).
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


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _attention(ap, u, n_heads, n_kv_heads, quant):
    t, d = u.shape
    dh = d // n_heads
    q = _mm(u, ap["wq"], quant).reshape(t, n_heads, dh)
    k = _mm(u, ap["wk"], quant).reshape(t, n_kv_heads, dh)
    v = _mm(u, ap["wv"], quant).reshape(t, n_kv_heads, dh)
    k = jnp.repeat(k, n_heads // n_kv_heads, axis=1)
    v = jnp.repeat(v, n_heads // n_kv_heads, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return _mm(a.reshape(t, d), ap["wo"], quant)


def _mamba(mp, u, dt_rank, eps, quant):
    t = u.shape[0]
    n_state = mp["A_log"].shape[0]
    x, z = jnp.split(_mm(u, mp["in_proj"], quant), 2, axis=-1)
    k = mp["conv_w"].shape[0]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(padded[j:j + t] * mp["conv_w"][j] for j in range(k))
                    + mp["conv_b"])
    dbc = _mm(x, mp["x_proj"], quant)
    dt = _rms(mp["dt_norm"], dbc[:, :dt_rank], eps)
    b = _rms(mp["b_norm"], dbc[:, dt_rank:dt_rank + n_state], eps)
    c = _rms(mp["c_norm"], dbc[:, dt_rank + n_state:], eps)
    delta = jax.nn.softplus(_mm(dt, mp["dt_proj"], quant) + mp["dt_bias"])
    a = -jnp.exp(mp["A_log"])                        # [d_state, d_inner]

    def step(h, inputs):
        x_t, delta_t, b_t, c_t = inputs
        h = jnp.exp(delta_t[None, :] * a) * h + (
            delta_t * x_t)[None, :] * b_t[:, None]
        return h, (h * c_t[:, None]).sum(0)

    _, y = jax.lax.scan(step, jnp.zeros_like(a), (x, delta, b, c))
    return _mm((y + mp["D"] * x) * jax.nn.silu(z), mp["out_proj"], quant)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv_heads",
                                             "dt_rank", "eps", "quant"))
def _layer(bp, h, *, n_heads, n_kv_heads, dt_rank, eps, quant):
    """One layer over ``h [T, d]``; ``bp`` is upcast here, alone."""
    bp = _f32(bp)
    u = _rms(bp["norm_in"], h, eps)
    if "attn" in bp:
        h = h + _attention(bp["attn"], u, n_heads, n_kv_heads, quant)
    else:
        h = h + _mamba(bp["mamba"], u, dt_rank, eps, quant)
    u = _rms(bp["norm_ff"], h, eps)
    mid = jax.nn.silu(_mm(u, bp["mlp"]["gate"], quant)) * _mm(
        u, bp["mlp"]["up"], quant)
    return h + _mm(mid, bp["mlp"]["down"], quant)


@functools.partial(jax.jit, static_argnames=("n_out", "eps", "quant"))
def _head(table, norm_f, h, first, *, n_out, eps, quant):
    rows = jax.lax.dynamic_slice_in_dim(h, first, n_out, 0)
    hn = _rms(norm_f.astype(jnp.float32), rows, eps)
    return _mm(hn, table.astype(jnp.float32).T, quant)


def hidden(params, tokens, *, n_heads, n_kv_heads, dt_rank, eps, quant=None):
    """The residual stream ``[T, d]`` after the last layer (before the final
    norm) for one sequence ``tokens [T]``."""
    h = params["embed"]["tok"][tokens].astype(jnp.float32)
    for bp in params["blocks"]:
        h = _layer(bp, h, n_heads=n_heads, n_kv_heads=n_kv_heads,
                   dt_rank=dt_rank, eps=eps, quant=quant)
    return h


def served_logits(params, tokens, first, *, n_heads, n_kv_heads, dt_rank,
                  eps, n_out, quant=None):
    """Logits ``[n_out, V]`` at positions ``first .. first + n_out - 1`` of
    one padded sequence ``tokens [T]``: row ``i`` is what a correct server
    holds when it chooses output token ``i``. Causal attention, a causal
    convolution and a forward recurrence make the padding behind the last
    real token irrelevant to those rows."""
    h = hidden(params, tokens, n_heads=n_heads, n_kv_heads=n_kv_heads,
               dt_rank=dt_rank, eps=eps, quant=quant)
    return _head(params["embed"]["tok"], params["head"]["norm_f"], h, first,
                 n_out=n_out, eps=eps, quant=quant)
