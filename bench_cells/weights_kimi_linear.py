"""Seeded Kimi-Linear weights, made on the device and rounded to bfloat16
once.

The benchmark makes the weights itself and hands them to the program (as its
one stage's parameters) and to the plain reference alike, so the reference
takes nothing that the program has made; both read the same rounded values.
The tree has the layout ``models/kimi_linear.py::make_kimi_linear_stages``
builds (``embed`` / ``blocks`` / ``head``; a block holds ``norm1``,
``norm2``, ONE of ``kda`` / ``mla`` and ONE of ``mlp`` / (``moe`` +
``shared``); the routed experts' matrices are those of the experts HELD,
``[experts_held, in, out]``).

Matrices, the embedding and the head normal(0, 0.02), norm weights 1; the
three depthwise convolutions uniform within ``1 / sqrt(d_conv)`` (torch's
``Conv1d`` default, no bias); ``A_log = log(uniform(1, 16))`` a head and
``dt_bias`` the inverse softplus of values log-uniform in 1e-3 .. 1e-1 (how
the two state-space families of this benchmark start theirs); the selection
bias 0 (float32). ONE departure from "normal 0.02 everywhere", of the
WEIGHTS and of no equation (``configs/kimi-linear-48b-a3b.json`` says it
under ``departures``): the matrices that map a layer's inner activations
back to the model's width (a KDA and a latent mixer's ``wo``, the dense
part's, the routed and the shared experts' ``down``) are CENTRED, each
output column's mean over its inputs removed (:func:`_centred`, as
``weights_nemotron_h.py`` does and for its reason). Left as drawn, the 64
rows of a decode tick route alike though 99.2 % of a tick's tokens differ: a
run reads 68.0-70.6 % of the 26 x 16 held experts, by the seed, with 12-16
rows on the fullest, where independent rows over the same window would read
82 % and 8-9; centred 77.4-78.1 % on both seeds tried and 8.4-8.6 rows (my
chip runs, PR 49). The held experts are experts ``expert_offset ..`` of the
layer and the held rows rows ``0 ..`` of the embedding and head of ONE seeded
model: which chip a matrix lies on changes no number of it, so each held
matrix is drawn from a key of its own and no absent expert is drawn at all.

One jitted draw a MATRIX: a layer's sixteen experts alone are 113 M float32
draws. :func:`init_layer` makes one layer (the reference walks the model a
layer at a time and never holds two).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

STD = 0.02


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std"))
def _matrix(key, *, shape, dtype, std):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(
        jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std"))
def _centred(key, *, shape, dtype, std):
    """:func:`_matrix` with each output column's mean over its inputs
    removed: for the matrices that lead back to the model's width. What they
    read (a gated head norm, a ``silu`` times a product, an average of
    values) has a mean over its channels that one token shares with the
    next, which a zero-mean matrix of finite height turns into ONE direction
    that every token's residual stream then shares."""
    w = std * jax.random.normal(key, shape, jnp.float32)
    return (w - w.mean(axis=-2, keepdims=True)).astype(jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "lo", "hi"))
def _uniform(key, *, shape, dtype, lo, hi):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi).astype(
        jnp.dtype(dtype))


def _keys(seed: int, cfg: dict):
    """The embedding's key, the head's, and one a layer."""
    ke, kh, *kb = jax.random.split(jax.random.key(seed),
                                   2 + cfg["n_layers"])
    return ke, kh, kb


def init_layer(seed: int, cfg: dict, layer: int) -> dict:
    """Layer ``layer``'s tree from ``seed``; ``cfg`` is the configuration
    file's ``kimi_linear_config``."""
    dt = cfg["param_dtype"]
    d, f, held = cfg["d_model"], cfg["d_expert"], cfg["experts_held"]
    mat = lambda k, *shape: _matrix(  # noqa: E731
        k, shape=shape, dtype=dt, std=STD)
    back = lambda k, *shape: _centred(  # noqa: E731
        k, shape=shape, dtype=dt, std=STD)
    ones = lambda n: jnp.ones((n,), jnp.dtype(dt))  # noqa: E731
    km, kf = jax.random.split(_keys(seed, cfg)[2][layer])
    out = {"norm1": ones(d), "norm2": ones(d)}
    if layer in cfg["attn_layers"]:
        kq, ka, kb, ko = jax.random.split(km, 4)
        h, dn, dr = cfg["n_heads"], cfg["d_nope"], cfg["d_rope"]
        out["mla"] = {
            "wq": mat(kq, d, h * (dn + dr)),
            "wkv_a": mat(ka, d, cfg["d_latent"] + dr),
            "kv_norm": ones(cfg["d_latent"]),
            "wkv_b": mat(kb, cfg["d_latent"], h * (dn + cfg["d_v"])),
            "wo": back(ko, h * cfg["d_v"], d)}
    else:
        ks = jax.random.split(km, 14)
        nh, r = cfg["kda_heads"], cfg["d_gate"]
        c = nh * cfg["kda_head_dim"]
        bound = 1.0 / math.sqrt(cfg["d_conv"])
        conv = lambda k: _uniform(  # noqa: E731
            k, shape=(cfg["d_conv"], c), dtype=dt, lo=-bound, hi=bound)
        step = jnp.exp(_uniform(ks[12], shape=(c,), dtype="float32",
                                lo=math.log(1e-3), hi=math.log(1e-1)))
        out["kda"] = {
            "wq": mat(ks[0], d, c), "wk": mat(ks[1], d, c),
            "wv": mat(ks[2], d, c),
            "conv_q": conv(ks[3]), "conv_k": conv(ks[4]),
            "conv_v": conv(ks[5]),
            "f_a": mat(ks[6], d, r), "f_b": mat(ks[7], r, c),
            "A_log": jnp.log(_uniform(ks[13], shape=(nh,), dtype="float32",
                                      lo=1.0, hi=16.0)).astype(dt),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
            "w_beta": mat(ks[8], d, nh),
            "g_a": mat(ks[9], d, r), "g_b": mat(ks[10], r, c),
            "o_norm": ones(cfg["kda_head_dim"]),
            "wo": back(ks[11], c, d)}
    if layer < cfg["n_dense"]:
        kg, ku, kd = jax.random.split(kf, 3)
        ff = cfg["d_ff"]
        out["mlp"] = {"gate": mat(kg, d, ff), "up": mat(ku, d, ff),
                      "down": back(kd, ff, d)}
    else:
        kr, kg, ku, kd, sg, su, sd = jax.random.split(kf, 7)
        sf = cfg["n_shared"] * f
        out["moe"] = {"router": mat(kr, d, cfg["n_experts"]),
                      "bias": jnp.zeros((cfg["n_experts"],), jnp.float32),
                      "gate": mat(kg, held, d, f), "up": mat(ku, held, d, f),
                      "down": back(kd, held, f, d)}
        out["shared"] = {"gate": mat(sg, d, sf), "up": mat(su, d, sf),
                         "down": back(sd, sf, d)}
    return out


def init_ends(seed: int, cfg: dict) -> dict:
    """The held rows of the embedding, the final norm and the held columns
    of the untied head."""
    dt = cfg["param_dtype"]
    ke, kh, _ = _keys(seed, cfg)
    shape = (cfg["vocab"], cfg["d_model"])
    return {"embed": {"tok": _matrix(ke, shape=shape, dtype=dt, std=STD)},
            "head": {"norm_f": jnp.ones((cfg["d_model"],), jnp.dtype(dt)),
                     "out": _matrix(kh, shape=shape[::-1], dtype=dt,
                                    std=STD)}}


def init_kimi_linear(seed: int, cfg: dict) -> dict:
    """The whole held model's parameter tree from ``seed``."""
    ends = init_ends(seed, cfg)
    return {"embed": ends["embed"],
            "blocks": [init_layer(seed, cfg, l)
                       for l in range(cfg["n_layers"])],
            "head": ends["head"]}
