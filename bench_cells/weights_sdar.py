"""Seeded SDAR weights, made on the device and rounded to bfloat16 once.

The benchmark makes the weights itself and hands them to the program (as its
one stage's parameters) and to the plain reference alike, so the reference
takes nothing that the program has made; both read the same rounded values.
The tree has the layout ``models/sdar.py::make_sdar_stages`` builds (``embed``
/ ``blocks`` / ``head``; a block holds ``attn`` with its two head norms and
``moe`` with ``router [d, E]``, ``gate`` / ``up [E, d, f]``, ``down [E, f,
d]``). Matrices normal(0, 0.02); layer and final norm weights 1; the two head
norms (``q_norm``, ``k_norm``) ``HEAD_NORM`` = 2, so that a score has
standard deviation 4 and not 1. At 1 a random model's attention is a broad
average over its context: every row's residual is its context's mean, which
the Zipf prompts and the model's own repeated tokens make nearly the same for
all rows, the router sends them to the same few experts, and how many experts
a tick reads (82 to 86 % of them, by the seed) decides its time: the cell's
rate then spread 0.9 % over seeds on one machine (my chip runs, PR 32;
``PERF.md`` section 6). A trained router is balanced by its loss; at 2 the
attention is peaked as a trained QK-norm model's, rows differ, and a tick
hits every expert whatever the seed. One jitted draw a layer: seven layers
in one program would hold their float32 draws side by side.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STD = 0.02
HEAD_NORM = 2.0


def _mat(key, shape, dt, std):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dt)


@functools.partial(jax.jit, static_argnames=(
    "d", "n_heads", "n_kv_heads", "dh", "n_experts", "f", "dtype", "std",
    "head_norm"))
def _layer(key, *, d, n_heads, n_kv_heads, dh, n_experts, f, dtype, std,
           head_norm):
    dt = jnp.dtype(dtype)
    kq, kk, kv, ko, kr, kg, ku, kd = jax.random.split(key, 8)
    ones = lambda m: jnp.ones((m,), dt)  # noqa: E731
    return {
        "norm_in": ones(d), "norm_ff": ones(d),
        "attn": {"wq": _mat(kq, (d, n_heads * dh), dt, std),
                 "wk": _mat(kk, (d, n_kv_heads * dh), dt, std),
                 "wv": _mat(kv, (d, n_kv_heads * dh), dt, std),
                 "wo": _mat(ko, (n_heads * dh, d), dt, std),
                 "q_norm": head_norm * ones(dh),
                 "k_norm": head_norm * ones(dh)},
        "moe": {"router": _mat(kr, (d, n_experts), dt, std),
                "gate": _mat(kg, (n_experts, d, f), dt, std),
                "up": _mat(ku, (n_experts, d, f), dt, std),
                "down": _mat(kd, (n_experts, f, d), dt, std)},
    }


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std"))
def _matrix(key, *, shape, dtype, std):
    return _mat(key, shape, jnp.dtype(dtype), std)


def init_sdar(seed: int, cfg: dict) -> dict:
    """The whole model's parameter tree from ``seed``; ``cfg`` is the
    configuration file's ``sdar_config``."""
    std = STD
    d, dtype = cfg["d_model"], cfg["param_dtype"]
    ke, kh, *kb = jax.random.split(jax.random.key(seed), 2 + cfg["n_layers"])
    blocks = [_layer(k, d=d, n_heads=cfg["n_heads"],
                     n_kv_heads=cfg["n_kv_heads"], dh=cfg["head_dim"],
                     n_experts=cfg["n_experts"], f=cfg["d_expert"],
                     dtype=dtype, std=std, head_norm=HEAD_NORM) for k in kb]
    return {"embed": {"tok": _matrix(ke, shape=(cfg["vocab"], d),
                                     dtype=dtype, std=std)},
            "blocks": blocks,
            "head": {"norm_f": jnp.ones((d,), jnp.dtype(dtype)),
                     "out": _matrix(kh, shape=(d, cfg["vocab"]),
                                    dtype=dtype, std=std)}}
