"""GPT-2 (Radford et al. 2019) as plain ``jax.numpy``: the reference for
every cell whose configuration names ``"reference": "gpt2"``.

Pre-LayerNorm decoder blocks, learned positions, causal softmax attention,
tanh-GELU MLP (``gelu_new``), final LayerNorm and output head. Departures
from the published model, shared with the program under test and stated in
the configuration files: no bias on the attention projections, an untied
output head, dropout 0.

Float32 throughout; every function here runs under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs as one bfloat16 pass). ``quant`` and ``compute`` exist only
for the controls: ``quant="int8"`` fake-quantises both operands of every
weight matmul symmetrically (weights per output channel, activations per
row), and ``compute`` runs the whole forward in that type, as the program's
mixed-precision step does in bfloat16.

The layers are scanned over stacked weights and rematerialised, and a batch
is processed in blocks of rows, so that a step at the timed batch fits
beside nothing else on one chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

LN_EPS = 1e-5


def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    # straight-through: the backward pass sees the identity
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, quant):
    if quant == "int8":
        return _fake_int8(x, -1) @ _fake_int8(w, 0)
    if quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return x @ w


def _layer_norm(p, x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(bp, h, n_heads, quant):
    b, t, d = h.shape
    dh = d // n_heads
    hn = _layer_norm(bp["ln1"], h)

    def heads(w):
        return _mm(hn, w, quant).reshape(b, t, n_heads, dh).transpose(
            0, 2, 1, 3)

    q, k, v = heads(bp["attn"]["wq"]), heads(bp["attn"]["wk"]), heads(
        bp["attn"]["wv"])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
    a = a.transpose(0, 2, 1, 3).reshape(b, t, d)
    h = h + _mm(a, bp["attn"]["wo"], quant)
    hn2 = _layer_norm(bp["ln2"], h)
    mid = _gelu_new(_mm(hn2, bp["mlp_in"]["w"], quant) + bp["mlp_in"]["b"])
    return h + _mm(mid, bp["mlp_out"]["w"], quant) + bp["mlp_out"]["b"]


@jax.jit
def stack_blocks(params):
    """The reference's own layout: the list of per-block trees becomes one
    tree of ``[n_layers, ...]`` arrays, which the layers are scanned over."""
    return {"embed": params["embed"], "head": params["head"],
            "blocks": jax.tree.map(lambda *xs: jnp.stack(xs),
                                   *params["blocks"])}


def unstack_blocks(tree):
    """Back to the list-of-blocks layout (for small trees of numbers)."""
    n = jax.tree.leaves(tree["blocks"])[0].shape[0]
    return {"embed": tree["embed"], "head": tree["head"],
            "blocks": [jax.tree.map(lambda a: a[i], tree["blocks"])
                       for i in range(n)]}


def hidden(params, tokens, n_heads, quant=None):
    """Final-LayerNorm hidden states ``[b, t, d]`` for ``tokens [b, t]``;
    ``params`` in the stacked layout."""
    t = tokens.shape[1]
    h = params["embed"]["tok"][tokens] + params["embed"]["pos"][:t]

    @jax.checkpoint
    def body(h, bp):
        return _block(bp, h, n_heads, quant), None

    h, _ = jax.lax.scan(body, h, params["blocks"])
    return _layer_norm(params["head"]["ln_f"], h)


def logits_of(params, hn, quant=None):
    return _mm(hn, params["head"]["out"]["w"], quant) + params["head"][
        "out"]["b"]


# -- serving: logits at the served positions ------------------------------


@functools.partial(jax.jit, static_argnames=("n_heads", "n_out", "quant"))
def served_logits(params, tokens, first, *, n_heads, n_out, quant=None):
    """Logits ``[n_out, V]`` at positions ``first .. first + n_out - 1`` of
    one padded sequence ``tokens [T]``: row ``i`` is what a correct server
    holds when it chooses output token ``i``. Causal attention makes the
    padding behind the last real token irrelevant to those rows."""
    hn = hidden(params, tokens[None], n_heads, quant)[0]
    rows = jax.lax.dynamic_slice_in_dim(hn, first, n_out, 0)
    return logits_of(params, rows, quant)


# -- training: loss, gradients and AdamW over a batch in row blocks --------


def _nll_sum(params, x, targets, n_heads, quant, compute):
    if compute is not None:
        # the controls only: the whole forward in a lower precision, the
        # loss taken in float32 from its log-probabilities
        params = jax.tree.map(lambda a: a.astype(compute), params)
    logits = logits_of(params, hidden(params, x, n_heads, quant), quant)
    logp = jax.nn.log_softmax(logits, axis=-1).astype(jnp.float32)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).sum()


def _spread(tree, mesh, replicate: bool = False):
    """On several chips (``mesh``, one axis ``rows``) the reference's
    gradients and AdamW moments are cut over the chips along the first axis
    that divides evenly, and its parameters are held whole on each, so that
    the rows of a block can be taken a chip each. No-op without a mesh."""
    if mesh is None:
        return tree

    def place(a):
        spec = P()
        if not replicate:
            for i, n in enumerate(a.shape):
                if n >= mesh.size and n % mesh.size == 0:
                    spec = P(*([None] * i), "rows")
                    break
        return jax.lax.with_sharding_constraint(a, NamedSharding(mesh, spec))

    return jax.tree.map(place, tree)


@functools.partial(jax.jit, static_argnames=("mesh",))
def _zeros_like(tree, mesh):
    return _spread(jax.tree.map(jnp.zeros_like, tree), mesh)


@functools.partial(jax.jit,
                   static_argnames=("n_heads", "quant", "compute", "mesh"),
                   donate_argnums=(0,))
def _accumulate(acc, params, x, targets, *, n_heads, quant, compute, mesh):
    nll, g = jax.value_and_grad(_nll_sum)(params, x, targets, n_heads, quant,
                                          compute)
    acc_nll, acc_g = acc
    return acc_nll + nll, _spread(jax.tree.map(jnp.add, acc_g, g), mesh)


@functools.partial(jax.jit, static_argnames=("mesh",),
                   donate_argnums=(0, 1, 2))
def _adamw(params, m, v, gsum, n_tok, step, lr, b1, b2, eps, weight_decay,
           mesh):
    """torch.optim.AdamW: decoupled decay, bias-corrected moments. The
    gradient is ``gsum / n_tok`` (the mean over the batch's tokens)."""
    t = step.astype(jnp.float32)
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * (g_ / n_tok),
                     m, gsum)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * (g_ / n_tok) ** 2,
                     v, gsum)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, m_, v_):
        p = p * (1 - lr * weight_decay)
        return p - lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)

    return (_spread(jax.tree.map(upd, params, m, v), mesh, replicate=True),
            _spread(m, mesh), _spread(v, mesh))


def _norm(a, stacked):
    axes = tuple(range(1, a.ndim)) if stacked else None
    return jnp.sqrt(jnp.sum(jnp.square(a), axis=axes))


@jax.jit
def leaf_norms(tree, scale=1.0):
    """L2 norm of every leaf of a stacked-layout tree times ``scale``; a
    block leaf gives one norm per layer."""
    return {k: jax.tree.map(lambda a: scale * _norm(a, k == "blocks"), sub)
            for k, sub in tree.items()}


@jax.jit
def _diff_norms(a, b):
    return {k: jax.tree.map(lambda x, y: _norm(x - y, k == "blocks"),
                            a[k], b[k]) for k in a}


def train_steps(make_params, batches, *, n_heads, optimizer, rows,
                quant=None, compute=None, mesh=None):
    """Follow ``len(batches)`` AdamW steps from ``make_params()`` (the
    list-of-blocks tree of ``weights.init_gpt``; called again at the end
    for the starting point, so that no copy is held meanwhile).

    ``batches``: ``[(x [B, T] int32, targets [B, T] int32), ...]``; the loss
    is the mean token NLL over the whole batch, taken ``rows`` rows at a
    time. Returns ``(losses, first-gradient leaf norms, leaf norms of the
    parameters' change over all the steps)``, the last two as
    list-of-blocks trees of numbers. With a ``mesh`` of several chips (one
    axis, ``rows``) a block's rows are taken a chip each; see
    :func:`_spread`.
    """
    put = lambda a, spec: a if mesh is None else jax.device_put(  # noqa: E731
        a, NamedSharding(mesh, spec))
    with jax.default_matmul_precision("highest"):
        params = put(stack_blocks(make_params()), P())
        zeros = lambda: _zeros_like(params, mesh)  # noqa: E731
        m, v = zeros(), zeros()
        losses, first_grad = [], None
        for i, (x, t) in enumerate(batches):
            acc = (jnp.float32(0.0), zeros())
            for r in range(0, x.shape[0], rows):
                acc = _accumulate(acc, params, put(x[r:r + rows], P("rows")),
                                  put(t[r:r + rows], P("rows")),
                                  n_heads=n_heads, quant=quant,
                                  compute=compute, mesh=mesh)
            n_tok = float(x.shape[0] * x.shape[1])
            losses.append(float(acc[0]) / n_tok)
            if first_grad is None:
                first_grad = jax.device_get(leaf_norms(acc[1], 1.0 / n_tok))
            params, m, v = _adamw(
                params, m, v, acc[1], n_tok, jnp.int32(i + 1),
                optimizer["lr"], optimizer["b1"], optimizer["b2"],
                optimizer["eps"], optimizer["weight_decay"], mesh)
            del acc
        del m, v
        change = jax.device_get(
            _diff_norms(params, put(stack_blocks(make_params()), P())))
    return losses, unstack_blocks(first_grad), unstack_blocks(change)
