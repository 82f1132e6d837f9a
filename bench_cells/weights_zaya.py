"""Seeded ZAYA1 weights, made on the device and rounded to bfloat16 once.

The benchmark makes the weights itself and hands them to the program (as its
one stage's parameters) and to the plain reference alike, so the reference
takes nothing that the program has made; both read the same rounded values.
The tree has the layout ``models/zaya.py::make_zaya_stages`` builds
(``embed`` / ``blocks`` / ``head``; a block holds ``attn`` and ``moe``, each
with its norm and its four residual-scaling vectors; ``moe`` holds
``router`` and the experts' ``gate`` / ``up [E, d, f]``, ``down [E, f,
d]``).

Matrices and the embedding normal(0, 0.02); the two convolutions uniform
within torch's default bound (``1 / sqrt(fan in)``: the taps for the
depthwise one, taps times ``head_dim`` for the per-head one); norm weights,
``gamma`` and the residual scalings' factors 1, their biases 0. Three
departures from "normal 0.02 everywhere", each of the WEIGHTS and none of
an equation (``configs/zaya1-8b.json`` says them under ``departures``):

- the router's three MLP matrices are drawn at ``ROUTER_GAIN / sqrt(fan
  in)`` and CENTRED (each output column's mean over its inputs removed). At
  0.02 the MLP's output is a hundredth of a unit, ``softmax`` gives every
  expert a sixteenth to three digits and the choice follows the one
  direction that GELU's positive mean gives every token alike: a tick's
  rows land on one or two experts of sixteen. At unit scale and centred the
  scores differ by token and by expert;
- the selection ``bias`` is drawn by the same rule as a trained model's is
  learned, from the router itself: over ``BALANCE_ROWS`` seeded unit-norm
  router states it is moved against each expert's load (``BALANCE_STEPS``
  steps of ``BALANCE_RATE`` times the sign of the load's excess: what
  loss-free balancing does in training) so that the sixteen get equal
  shares of random rows. It steers the choice alone, as published;
- ``tau`` is ``TAU``, not 1: the keys and queries are unit vectors times
  ``sqrt(head_dim)``, so at 1 a random model's scores have standard
  deviation 1 and its attention is a broad average over the context (what
  ``weights_sdar.py`` says of ``HEAD_NORM``).

One jitted draw a layer: twenty layers in one program would hold their
float32 draws side by side.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

STD = 0.02
TAU = 4.0
ROUTER_GAIN = 2.0
BALANCE_ROWS = 4096
BALANCE_STEPS = 200
BALANCE_RATE = 0.002


def _mat(key, shape, dt, std):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dt)


def _uniform(key, shape, dt, bound):
    return jax.random.uniform(key, shape, jnp.float32, minval=-bound,
                              maxval=bound).astype(dt)


def _centred(key, shape, dt, std):
    """``_mat`` with each output column's mean over its inputs removed."""
    w = std * jax.random.normal(key, shape, jnp.float32)
    return (w - w.mean(axis=-2, keepdims=True)).astype(dt)


def _balanced_bias(key, w1, w2, w3, n_experts):
    """The selection bias that gives the experts equal shares of
    ``BALANCE_ROWS`` random router states (unit RMS, as the router's own
    norm leaves them), through the MLP as the program reads it (the
    matrices rounded to their dtype)."""
    f32 = jnp.float32
    x = jax.random.normal(key, (BALANCE_ROWS, w1.shape[0]), f32)
    x = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True))
    for w in (w1, w2):
        x = jax.nn.gelu(x @ w.astype(f32), approximate=False)
    p = jax.nn.softmax(x @ w3.astype(f32), axis=-1)

    def step(bias, _):
        load = jnp.bincount(jnp.argmax(p + bias, axis=-1),
                            length=n_experts) / BALANCE_ROWS
        return bias - BALANCE_RATE * jnp.sign(load - 1 / n_experts), None

    bias, _ = jax.lax.scan(step, jnp.zeros((n_experts,), f32), None,
                           length=BALANCE_STEPS)
    return bias


@functools.partial(jax.jit, static_argnames=(
    "d", "n_heads", "n_kv_heads", "dh", "conv0", "conv1", "n_experts", "f",
    "r", "dtype", "std", "tau", "router_gain"))
def _layer(key, *, d, n_heads, n_kv_heads, dh, conv0, conv1, n_experts, f, r,
           dtype, std, tau, router_gain):
    dt = jnp.dtype(dtype)
    mat = functools.partial(_mat, dt=dt, std=std)
    (kq, kv, ko, k0, kb0, k1, kb1, kd, kr1, kr2, kr3, kg, ku, kw,
     kbal) = jax.random.split(key, 15)
    groups = n_heads + n_kv_heads
    c = groups * dh
    ones = lambda m: jnp.ones((m,), dt)  # noqa: E731
    zeros = lambda m: jnp.zeros((m,), dt)  # noqa: E731
    scaling = lambda: {  # noqa: E731
        "res_scale": ones(d), "res_bias": zeros(d),
        "out_scale": ones(d), "out_bias": zeros(d)}
    b0, b1 = 1 / math.sqrt(conv0), 1 / math.sqrt(conv1 * dh)
    gain = router_gain / math.sqrt(r)
    w1, w2, w3 = (_centred(kr1, (r, r), dt, gain),
                  _centred(kr2, (r, r), dt, gain),
                  _centred(kr3, (r, n_experts), dt, gain))
    return {
        "attn": {
            "norm": ones(d),
            "wqk": mat(kq, (d, c)),
            "wv": mat(kv, (d, n_kv_heads * dh)),
            "conv0_w": _uniform(k0, (conv0, c), dt, b0),
            "conv0_b": _uniform(kb0, (c,), dt, b0),
            "conv1_w": _uniform(k1, (conv1, groups, dh, dh), dt, b1),
            "conv1_b": _uniform(kb1, (c,), dt, b1),
            "tau": tau * ones(n_kv_heads),
            "wo": mat(ko, (n_heads * dh, d)), **scaling()},
        "moe": {
            "norm": ones(d),
            "router": {
                "down": mat(kd, (d, r)), "down_b": zeros(r),
                "gamma": ones(r), "norm": ones(r),
                "w1": w1, "b1": zeros(r), "w2": w2, "b2": zeros(r),
                "w3": w3,
                "bias": _balanced_bias(kbal, w1, w2, w3, n_experts)},
            "gate": mat(kg, (n_experts, d, f)),
            "up": mat(ku, (n_experts, d, f)),
            "down": mat(kw, (n_experts, f, d)), **scaling()},
    }


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std"))
def _matrix(key, *, shape, dtype, std):
    return _mat(key, shape, jnp.dtype(dtype), std)


def init_zaya(seed: int, cfg: dict) -> dict:
    """The whole model's parameter tree from ``seed``; ``cfg`` is the
    configuration file's ``zaya_config``."""
    d, dtype = cfg["d_model"], cfg["param_dtype"]
    ke, *kb = jax.random.split(jax.random.key(seed), 1 + cfg["n_layers"])
    blocks = [_layer(k, d=d, n_heads=cfg["n_heads"],
                     n_kv_heads=cfg["n_kv_heads"], dh=cfg["head_dim"],
                     conv0=cfg["conv0"], conv1=cfg["conv1"],
                     n_experts=cfg["n_experts"], f=cfg["d_expert"],
                     r=cfg["d_router"], dtype=dtype, std=STD, tau=TAU,
                     router_gain=ROUTER_GAIN) for k in kb]
    return {"embed": {"tok": _matrix(ke, shape=(cfg["vocab"], d),
                                     dtype=dtype, std=STD)},
            "blocks": blocks,
            "head": {"norm_f": jnp.ones((d,), jnp.dtype(dtype))}}
