"""Seeded Nemotron-H weights, made on the device and rounded to bfloat16
once.

The benchmark makes the weights itself and hands them to the program (as its
one stage's parameters) and to the plain reference alike, so the reference
takes nothing that the program has made; both read the same rounded values.
The tree has the layout ``models/nemotron_h.py::make_nemotron_h_stages``
builds (``embed`` / ``blocks`` / ``head``; a block holds its ``norm`` and
ONE of ``mamba``, ``attn``, ``moe``; the experts' matrices are those of the
experts HELD, ``[experts_held, in, out]``).

Matrices, the embedding and the head normal(0, 0.02); the scan's start as
the Mamba-2 code has it (``A`` uniform in 1..16 a head, the ``dt`` bias the
inverse softplus of values log-uniform in ``time_step_min`` 1e-3 ..
``time_step_max`` 1e-1, floored at ``time_step_floor`` 1e-4, ``D`` 1); the
depthwise convolution uniform within ``1/sqrt(d_conv)`` (torch's ``Conv1d``
default, which the family's code leaves in place); the selection bias 0
(float32); norm weights 1. One departure from "normal 0.02 everywhere": the
three matrices that map a layer's inner activations back to the model's
width (``out_proj``, the experts' ``w2``, ``shared_out``) are centred
(``_centred``). Left as drawn, the shared expert's ``relu^2`` mean alone
gives every token's residual stream a common direction (cosine 0.05 between
two tokens' router inputs at the first expert layer, 0.20 at the fifth),
the rows of a tick route alike, and a decode run reads 78-81 % of the held
experts, by the seed, where independent rows would read 98.5 %; centred the
cosine stays under 0.04. One jitted draw per KIND of layer, called once
per layer: eleven layers in one program would hold their float32 draws side
by side.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

STD = 0.02


def _mat(key, shape, dt):
    return (STD * jax.random.normal(key, shape, jnp.float32)).astype(dt)


def _centred(key, shape, dt):
    """``_mat`` with each output column's mean over its inputs removed: for
    the matrices that map a layer's inner activations back to the model's
    width. Those activations have a positive mean (``relu^2``; a ``silu``
    times a ``silu``), which a zero-mean matrix of finite height turns into
    ONE direction that every token's residual stream then shares."""
    w = STD * jax.random.normal(key, shape, jnp.float32)
    return (w - w.mean(axis=-2, keepdims=True)).astype(dt)


@functools.partial(jax.jit, static_argnames=("d", "qd", "kvd", "dtype"))
def _attention_layer(key, *, d, qd, kvd, dtype):
    dt = jnp.dtype(dtype)
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {"norm": jnp.ones((d,), dt), "attn": {
        "wq": _mat(kq, (d, qd), dt), "wk": _mat(kk, (d, kvd), dt),
        "wv": _mat(kv, (d, kvd), dt), "wo": _mat(ko, (qd, d), dt)}}


@functools.partial(jax.jit, static_argnames=("d", "di", "ch", "nh", "d_conv",
                                             "dtype"))
def _mamba_layer(key, *, d, di, ch, nh, d_conv, dtype):
    dt = jnp.dtype(dtype)
    ki, kw, kb, kt, ka, ko = jax.random.split(key, 6)
    bound = 1.0 / math.sqrt(d_conv)
    step = jnp.maximum(jnp.exp(jax.random.uniform(
        kt, (nh,), minval=math.log(1e-3), maxval=math.log(1e-1))), 1e-4)
    return {"norm": jnp.ones((d,), dt), "mamba": {
        "in_proj": _mat(ki, (d, di + ch + nh), dt),
        "conv_w": jax.random.uniform(kw, (d_conv, ch), minval=-bound,
                                     maxval=bound).astype(dt),
        "conv_b": jax.random.uniform(kb, (ch,), minval=-bound,
                                     maxval=bound).astype(dt),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "A_log": jnp.log(jax.random.uniform(ka, (nh,), minval=1.0,
                                            maxval=16.0)).astype(dt),
        "D": jnp.ones((nh,), dt),
        "norm": jnp.ones((di,), dt),
        "out_proj": _centred(ko, (di, d), dt)}}


@functools.partial(jax.jit, static_argnames=("d", "n_experts", "held", "lat",
                                             "f", "fs", "dtype"))
def _expert_layer(key, *, d, n_experts, held, lat, f, fs, dtype):
    dt = jnp.dtype(dtype)
    kr, kd, ku, k1, k2, ks, kt = jax.random.split(key, 7)
    return {"norm": jnp.ones((d,), dt), "moe": {
        "router": _mat(kr, (d, n_experts), dt),
        "bias": jnp.zeros((n_experts,), jnp.float32),
        "down": _mat(kd, (d, lat), dt), "up": _mat(ku, (lat, d), dt),
        "w1": _mat(k1, (held, lat, f), dt),
        "w2": _centred(k2, (held, f, lat), dt),
        "shared_in": _mat(ks, (d, fs), dt),
        "shared_out": _centred(kt, (fs, d), dt)}}


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _matrix(key, *, shape, dtype):
    return _mat(key, shape, jnp.dtype(dtype))


def init_nemotron_h(seed: int, cfg: dict) -> dict:
    """The whole model's parameter tree from ``seed``; ``cfg`` is the
    configuration file's ``nemotron_h_config``."""
    d, dtype = cfg["d_model"], cfg["param_dtype"]
    di = cfg["mamba_heads"] * cfg["mamba_head_dim"]
    ke, kh, *kb = jax.random.split(jax.random.key(seed),
                                   2 + len(cfg["pattern"]))
    blocks = []
    for k, kind in zip(kb, cfg["pattern"]):
        if kind == "*":
            blocks.append(_attention_layer(
                k, d=d, qd=cfg["n_heads"] * cfg["head_dim"],
                kvd=cfg["n_kv_heads"] * cfg["head_dim"], dtype=dtype))
        elif kind == "M":
            blocks.append(_mamba_layer(
                k, d=d, di=di, nh=cfg["mamba_heads"], d_conv=cfg["d_conv"],
                ch=di + 2 * cfg["n_groups"] * cfg["d_state"], dtype=dtype))
        else:
            blocks.append(_expert_layer(
                k, d=d, n_experts=cfg["n_experts"],
                held=cfg["experts_held"], lat=cfg["d_latent"],
                f=cfg["d_expert"], fs=cfg["d_shared"], dtype=dtype))
    return {"embed": {"tok": _matrix(ke, shape=(cfg["vocab"], d),
                                     dtype=dtype)},
            "blocks": blocks,
            "head": {"norm_f": jnp.ones((d,), jnp.dtype(dtype)),
                     "out": _matrix(kh, shape=(d, cfg["vocab"]),
                                    dtype=dtype)}}
