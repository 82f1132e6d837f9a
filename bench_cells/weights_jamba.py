"""Seeded Jamba weights, made on the device and rounded to bfloat16 once.

The benchmark makes the weights itself and hands them to the program (as its
one stage's parameters) and to the plain reference alike, so the reference
takes nothing that the program has made; both read the same rounded values.
The tree has the layout ``models/jamba.py::make_jamba_stages`` builds
(``embed`` / ``blocks`` / ``head``; a block holds ``attn`` or ``mamba``;
``A_log`` is ``[d_state, d_inner]``).

Matrices and the embedding normal(0, 0.02); the scan's start as the Mamba
paper has it (``A = -(1..d_state)`` per channel, the ``dt`` bias the inverse
softplus of values log-uniform in 1e-3..1e-1, ``D`` 1); the depthwise
convolution uniform within ``1/sqrt(d_conv)`` (torch's ``Conv1d`` default);
norm weights 1. One jitted draw per KIND of layer, called once per layer:
28 layers in one program would hold their float32 draws side by side.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

STD = 0.02


def _mat(key, shape, dt):
    return (STD * jax.random.normal(key, shape, jnp.float32)).astype(dt)


def _common(key, d, ff, dt) -> dict:
    kg, ku, kd = jax.random.split(key, 3)
    return {"norm_in": jnp.ones((d,), dt), "norm_ff": jnp.ones((d,), dt),
            "mlp": {"gate": _mat(kg, (d, ff), dt), "up": _mat(ku, (d, ff), dt),
                    "down": _mat(kd, (ff, d), dt)}}


@functools.partial(jax.jit, static_argnames=("d", "ff", "kv_dim", "dtype"))
def _attention_layer(key, *, d, ff, kv_dim, dtype):
    dt = jnp.dtype(dtype)
    kc, kq, kk, kv, ko = jax.random.split(key, 5)
    return dict(_common(kc, d, ff, dt), attn={
        "wq": _mat(kq, (d, d), dt), "wk": _mat(kk, (d, kv_dim), dt),
        "wv": _mat(kv, (d, kv_dim), dt), "wo": _mat(ko, (d, d), dt)})


@functools.partial(jax.jit, static_argnames=("d", "ff", "di", "n_state",
                                             "dt_rank", "d_conv", "dtype"))
def _mamba_layer(key, *, d, ff, di, n_state, dt_rank, d_conv, dtype):
    dt = jnp.dtype(dtype)
    kc, ki, kw, kb, kx, kp, kt, ko = jax.random.split(key, 8)
    bound = 1.0 / math.sqrt(d_conv)
    step = jnp.exp(jax.random.uniform(kt, (di,), minval=math.log(1e-3),
                                      maxval=math.log(1e-1)))
    return dict(_common(kc, d, ff, dt), mamba={
        "in_proj": _mat(ki, (d, 2 * di), dt),
        "conv_w": jax.random.uniform(kw, (d_conv, di), minval=-bound,
                                     maxval=bound).astype(dt),
        "conv_b": jax.random.uniform(kb, (di,), minval=-bound,
                                     maxval=bound).astype(dt),
        "x_proj": _mat(kx, (di, dt_rank + 2 * n_state), dt),
        "dt_norm": jnp.ones((dt_rank,), dt),
        "b_norm": jnp.ones((n_state,), dt),
        "c_norm": jnp.ones((n_state,), dt),
        "dt_proj": _mat(kp, (dt_rank, di), dt),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n_state + 1, dtype=jnp.float32))[:, None],
            (n_state, di)).astype(dt),
        "D": jnp.ones((di,), dt),
        "out_proj": _mat(ko, (di, d), dt)})


@functools.partial(jax.jit, static_argnames=("vocab", "d", "dtype"))
def _embedding(key, *, vocab, d, dtype):
    return _mat(key, (vocab, d), jnp.dtype(dtype))


def init_jamba(seed: int, cfg: dict) -> dict:
    """The whole model's parameter tree from ``seed``; ``cfg`` is the
    configuration file's ``jamba_config``."""
    d, ff, dtype = cfg["d_model"], cfg["d_ff"], cfg["param_dtype"]
    ke, *kb = jax.random.split(jax.random.key(seed), 1 + cfg["n_layers"])
    blocks = []
    for i, k in enumerate(kb):
        if i % cfg["attn_period"] == cfg["attn_offset"]:
            blocks.append(_attention_layer(
                k, d=d, ff=ff, dtype=dtype,
                kv_dim=cfg["n_kv_heads"] * (d // cfg["n_heads"])))
        else:
            blocks.append(_mamba_layer(
                k, d=d, ff=ff, di=cfg["expand"] * d, dtype=dtype,
                n_state=cfg["d_state"], dt_rank=cfg["dt_rank"],
                d_conv=cfg["d_conv"]))
    return {"embed": {"tok": _embedding(ke, vocab=cfg["vocab"], d=d,
                                        dtype=dtype)},
            "blocks": blocks,
            "head": {"norm_f": jnp.ones((d,), jnp.dtype(dtype))}}
