"""Seeded Cohere2 weights, made on the device and rounded to bfloat16 once.

The benchmark makes the weights itself and hands them to the program (as its
one stage's parameters) and to the plain reference alike, so the reference
takes nothing that the program has made; both read the same rounded values.
The tree has the layout ``models/cohere2.py::make_cohere2_stages`` builds
(``embed`` / ``blocks`` / ``head``; a block holds its one ``norm``, ``attn``
(``wq`` / ``wk`` / ``wv`` / ``wo``), ``moe`` (the ``router`` over ALL the
experts and the HELD experts' ``gate`` / ``up [held, d, f]``, ``down [held,
f, d]``) and ``shared`` (the shared experts side by side)).

Matrices and the embedding normal(0, 0.02), norm weights 1. ONE departure
from "normal 0.02 everywhere", of the WEIGHTS and of no equation
(``configs/command-a-plus-05-2026.json`` says it under ``departures``):
``W_q`` and ``W_k`` are drawn at ``ATTN_GAIN`` times 0.02. At 0.02 a random
model's scores have standard deviation 1.6, its attention is a broad average
over the context, every slot's residual stream is its newest token's expert
output plus one direction all contexts share, and greedy decoding under the
tied head falls onto ONE token within a few steps (1 to 26 distinct tokens
in 400; my chip run, PR 44): the sixteen rows of a tick route alike and a
decode run reads 50 % of the held experts, by the seed, where independent
rows read 64 %. At twice the scale (scores four times as sharp, what
``weights_zaya.py`` says of ``TAU`` and ``weights_sdar.py`` of
``HEAD_NORM``) a token's stream depends on its context, 394 of 400 tokens
are distinct and the run reads 64.5 %; three times reads the same. The
held experts are experts ``expert_offset ..`` of the layer and the held rows
rows ``0 ..`` of the embedding of ONE seeded model: which chip a matrix
lies on changes no number of it, so each held matrix is drawn from a key of
its own and no absent expert is drawn at all.

One jitted draw a MATRIX: a layer's sixteen experts alone are 805 M float32
draws, 3.2 GB before they are rounded. :func:`init_layer` makes one layer
(the reference walks the model a layer at a time and never holds two).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STD = 0.02
ATTN_GAIN = 2.0


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std"))
def _matrix(key, *, shape, dtype, std):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(
        jnp.dtype(dtype))


def _keys(seed: int, cfg: dict):
    """The embedding's key and one a layer."""
    ke, *kb = jax.random.split(jax.random.key(seed), 1 + cfg["n_layers"])
    return ke, kb


def init_layer(seed: int, cfg: dict, layer: int) -> dict:
    """Layer ``layer``'s tree from ``seed``; ``cfg`` is the configuration
    file's ``cohere2_config``."""
    dt = cfg["param_dtype"]
    d, f, held = cfg["d_model"], cfg["d_expert"], cfg["experts_held"]
    qd = cfg["n_heads"] * cfg["head_dim"]
    kvd = cfg["n_kv_heads"] * cfg["head_dim"]
    sf = cfg["n_shared"] * f
    mat = lambda k, *shape, gain=1.0: _matrix(  # noqa: E731
        k, shape=shape, dtype=dt, std=gain * STD)
    kq, kk, kv, ko, kr, kg, ku, kd, sg, su, sd = jax.random.split(
        _keys(seed, cfg)[1][layer], 11)
    return {
        "norm": jnp.ones((d,), jnp.dtype(dt)),
        "attn": {"wq": mat(kq, d, qd, gain=ATTN_GAIN),
                 "wk": mat(kk, d, kvd, gain=ATTN_GAIN),
                 "wv": mat(kv, d, kvd), "wo": mat(ko, qd, d)},
        "moe": {"router": mat(kr, d, cfg["n_experts"]),
                "gate": mat(kg, held, d, f), "up": mat(ku, held, d, f),
                "down": mat(kd, held, f, d)},
        "shared": {"gate": mat(sg, d, sf), "up": mat(su, d, sf),
                   "down": mat(sd, sf, d)},
    }


def init_ends(seed: int, cfg: dict) -> dict:
    """The held rows of the tied embedding and the final norm."""
    dt = cfg["param_dtype"]
    return {"embed": {"tok": _matrix(
        _keys(seed, cfg)[0], shape=(cfg["vocab"], cfg["d_model"]),
        dtype=dt, std=STD)},
        "head": {"norm_f": jnp.ones((cfg["d_model"],), jnp.dtype(dt))}}


def init_cohere2(seed: int, cfg: dict) -> dict:
    """The whole held model's parameter tree from ``seed``."""
    ends = init_ends(seed, cfg)
    return {"embed": ends["embed"],
            "blocks": [init_layer(seed, cfg, l)
                       for l in range(cfg["n_layers"])],
            "head": ends["head"]}
