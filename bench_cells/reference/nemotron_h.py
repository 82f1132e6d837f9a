"""The Nemotron-H hybrid decoder (NVIDIA,
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16,
``model_type`` ``nemotron_h``) as plain ``jax.numpy``: the reference for
every cell whose configuration names ``"reference": "nemotron_h"``.

Layer ``l`` of hidden ``h [T, d]`` is ONE part, of the kind its weights say
(the order is the configuration's ``hybrid_override_pattern``; this file
follows the tree it is given): ``h = h + part(rms(h; w))``, ``rms(x; w) = w
* x / sqrt(mean(x^2) + eps)``. Then a final ``rms`` and ``logits = h W_h``
with the model's own head matrix.

- ``mamba`` (Mamba-2; ``nh`` heads of ``hd`` channels, ``G`` groups of
  ``nh / G`` heads, ``S`` states): ``[z | xBC | dt] = W_in u`` (``nh hd | nh
  hd + 2 G S | nh``); ``xBC_t = silu(conv(xBC)_t + b_conv)``, ``conv(v)_t =
  sum_k w[k] * v_{t - (K - 1) + k}`` (causal, depthwise, zeros before the
  first token); ``x [nh, hd]``, ``B [G, S]``, ``C [G, S]``; ``dt =
  softplus(dt + dt_bias)`` and ``a = -exp(A_log)``, one a head; for head
  ``i`` in group ``g = i // (nh / G)``: ``H_t[i] = exp(dt_t[i] a_i)
  H_{t-1}[i] + dt_t[i] x_t[i] (outer) B_t[g]`` (``[hd, S]``, ``H_0 = 0``),
  ``y_t[i] = H_t[i] C_t[g] + D_i x_t[i]``; ``y = w_norm * y' / sqrt(mean
  over each GROUP's channels of y'^2 + eps)`` with ``y' = y * silu(z)``
  (the gate first, then the norm); ``W_out y``. The recurrence is a
  ``lax.scan`` over the tokens.
- ``attn``: ``q = W_q u`` (``n_heads`` heads of ``head_dim``), ``k``, ``v``
  (``n_kv_heads`` heads, each repeated here for the query heads of its
  group), no bias, NO positional encoding of any kind, causal softmax of ``q
  k^T / sqrt(head_dim)``, ``W_o``.
- ``moe`` (a latent mixture of which ``held`` experts from ``first`` on are
  here): ``s = sigmoid(W_r u)`` over ALL ``E``; chosen: the ``top_k``
  largest of ``s + b_sel``; ``w_e = scale * s_e / (sum of s over the chosen
  + 1e-20)``; ``v = W_dn u``; ``E_e(v) = W2_e relu(W1_e v)^2``; ``W_up (sum
  over the chosen e that are HELD of w_e E_e(v)) + W_s2 relu(W_s1 u)^2``,
  as the plain sum over the held experts with ``gate[t, e] = w_e`` where
  chosen and 0 elsewhere. What the absent experts would add is left out;
  the normaliser is over all the chosen, held or not. No capacity.

Departures from the published model, shared with the program under test and
stated in the configuration file: weights random from a seed; the
multi-token-prediction layer is not built. The tree is the program's
(``models/nemotron_h.py``): matrices ``[in, out]``, the experts' ``[held,
in, out]``.

Float32 throughout; every function here runs under
``jax.default_matmul_precision("highest")`` (set by the caller). No kernel,
no cache, no batching: one sequence, every token at once. The weights may
arrive in bfloat16 (the values the program reads); they are held so and
upcast one layer at a time, which is exact and keeps an expert layer's 0.76
B parameters at 3 GB beside nothing. ``quant="int8"`` exists only for the
control: it fake-quantises both operands of every weight matmul
symmetrically (weights per output channel, activations per row), the
experts' included.
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


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def attention(ap, u, n_heads, n_kv_heads, quant):
    t = u.shape[0]
    dh = ap["wq"].shape[1] // n_heads
    q = _mm(u, ap["wq"], quant).reshape(t, n_heads, dh)
    k = _mm(u, ap["wk"], quant).reshape(t, n_kv_heads, dh)
    v = _mm(u, ap["wv"], quant).reshape(t, n_kv_heads, dh)
    k = jnp.repeat(k, n_heads // n_kv_heads, axis=1)
    v = jnp.repeat(v, n_heads // n_kv_heads, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return _mm(a.reshape(t, n_heads * dh), ap["wo"], quant)


def mamba2(mp, u, n_groups, eps, quant):
    t = u.shape[0]
    nh = mp["A_log"].shape[0]
    di = mp["out_proj"].shape[0]
    hd = di // nh
    ch = mp["conv_w"].shape[1]
    gs = (ch - di) // 2
    n_state = gs // n_groups
    proj = _mm(u, mp["in_proj"], quant)
    z, xbc, dt = proj[:, :di], proj[:, di:di + ch], proj[:, di + ch:]
    k = mp["conv_w"].shape[0]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[j:j + t] * mp["conv_w"][j]
                          for j in range(k)) + mp["conv_b"])
    x = xbc[:, :di].reshape(t, nh, hd)
    b = xbc[:, di:di + gs].reshape(t, n_groups, n_state)
    c = xbc[:, di + gs:].reshape(t, n_groups, n_state)
    dt = jax.nn.softplus(dt + mp["dt_bias"])             # [T, nh]
    a = -jnp.exp(mp["A_log"])                            # [nh]
    per = nh // n_groups

    def step(h, inputs):                                 # h [nh, hd, S]
        x_t, dt_t, b_t, c_t = inputs
        b_h, c_h = jnp.repeat(b_t, per, axis=0), jnp.repeat(c_t, per, axis=0)
        h = jnp.exp(dt_t * a)[:, None, None] * h + (
            dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        return h, (h * c_h[:, None, :]).sum(-1) + mp["D"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((nh, hd, n_state), jnp.float32),
                        (x, dt, b, c))
    y = y.reshape(t, di) * jax.nn.silu(z)
    y = _rms(mp["norm"].reshape(n_groups, di // n_groups),
             y.reshape(t, n_groups, di // n_groups), eps).reshape(t, di)
    return _mm(y, mp["out_proj"], quant)


def expert_gates(scores, bias, top_k, scale):
    """``gate [N, E]`` from the router's ``scores``: ``scale * s / (sum of
    s over the chosen + 1e-20)`` at the ``top_k`` largest of ``s + bias``,
    zero elsewhere."""
    s = jax.nn.sigmoid(scores)
    _, ids = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = scale * w / (w.sum(-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], ids].set(w)


def routed(ep, u, top_k, scale, first, quant):
    """The held experts' part of the routed sum, in the latent ``[N,
    latent]``: the masked sum over the experts held."""
    held = ep["w1"].shape[0]
    gate = expert_gates(_mm(u, ep["router"], quant), ep["bias"], top_k,
                        scale)[:, first:first + held]
    v = _mm(u, ep["down"], quant)

    def one(acc, xs):
        w1, w2, g = xs
        return acc + g[:, None] * _mm(_relu2(_mm(v, w1, quant)), w2,
                                      quant), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(v),
                          (ep["w1"], ep["w2"], gate.T))
    return out


def shared_expert(ep, u, quant):
    return _mm(_relu2(_mm(u, ep["shared_in"], quant)), ep["shared_out"],
               quant)


def experts(ep, u, top_k, scale, first, quant):
    return _mm(routed(ep, u, top_k, scale, first, quant), ep["up"],
               quant) + shared_expert(ep, u, quant)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "n_groups", "top_k", "scale", "first_expert",
    "eps", "quant"))
def _layer(bp, h, *, n_heads, n_kv_heads, n_groups, top_k, scale,
           first_expert, eps, quant):
    """One layer over ``h [T, d]``; ``bp`` is upcast here, alone."""
    bp = jax.tree.map(lambda a: a.astype(jnp.float32), bp)
    u = _rms(bp["norm"], h, eps)
    if "attn" in bp:
        return h + attention(bp["attn"], u, n_heads, n_kv_heads, quant)
    if "mamba" in bp:
        return h + mamba2(bp["mamba"], u, n_groups, eps, quant)
    return h + experts(bp["moe"], u, top_k, scale, first_expert, quant)


@functools.partial(jax.jit, static_argnames=("n_out", "eps", "quant"))
def _head(out, norm_f, h, first, *, n_out, eps, quant):
    rows = jax.lax.dynamic_slice_in_dim(h, first, n_out, 0)
    return _mm(_rms(norm_f.astype(jnp.float32), rows, eps),
               out.astype(jnp.float32), quant)


def hidden(params, tokens, *, quant=None, **kw):
    """The residual stream ``[T, d]`` after the last layer (before the final
    norm) for one sequence ``tokens [T]``."""
    h = params["embed"]["tok"][tokens].astype(jnp.float32)
    for bp in params["blocks"]:
        h = _layer(bp, h, quant=quant, **kw)
    return h


def served_logits(params, tokens, first, *, n_out, eps, quant=None, **kw):
    """Logits ``[n_out, V]`` at positions ``first .. first + n_out - 1`` of
    one padded sequence ``tokens [T]``: row ``i`` is what a correct server
    holds when it chooses output token ``i``. Causal attention, a causal
    convolution and a forward recurrence make the padding behind the last
    real token irrelevant to those rows."""
    h = hidden(params, tokens, eps=eps, quant=quant, **kw)
    return _head(params["head"]["out"], params["head"]["norm_f"], h, first,
                 n_out=n_out, eps=eps, quant=quant)


def full_logits(params, tokens, *, eps, quant=None, **kw):
    """Logits ``[T, V]`` of one whole sequence."""
    return served_logits(params, tokens, 0, n_out=tokens.shape[0], eps=eps,
                         quant=quant, **kw)
