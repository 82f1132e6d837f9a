"""Seeded GPT-2 weights, made on the device in one jitted call.

The benchmark makes the weights itself and hands them to the program (as
stage parameters) and to the plain reference alike, so the reference takes
nothing that the program has made. The tree has the layout the program's
GPT builders consume (``embed``/``blocks``/``head``); attention projections
carry no bias and the output head is untied, as in ``models/gpt.py``.

GPT-2's published initialisation: matrices and embeddings normal(0, 0.02),
biases zero, LayerNorm scale one.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

STD = 0.02


def _block(key, d: int, hidden: int) -> dict:
    kq, kk, kv, ko, k1, k2 = jax.random.split(key, 6)

    def mat(k, shape):
        return STD * jax.random.normal(k, shape, jnp.float32)

    def norm():
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}

    return {
        "ln1": norm(),
        "attn": {"wq": mat(kq, (d, d)), "wk": mat(kk, (d, d)),
                 "wv": mat(kv, (d, d)), "wo": mat(ko, (d, d))},
        "ln2": norm(),
        "mlp_in": {"w": mat(k1, (d, hidden)),
                   "b": jnp.zeros((hidden,), jnp.float32)},
        "mlp_out": {"w": mat(k2, (hidden, d)),
                    "b": jnp.zeros((d,), jnp.float32)},
    }


def _blocks(key, n_layers: int, d: int, hidden: int) -> list[dict]:
    """All the blocks from one draw per kind of leaf (a random draw per
    leaf of every layer takes the TPU compiler a minute), then cut into the
    list of per-block trees the program's builders consume."""
    stacked = jax.vmap(lambda k: _block(k, d, hidden))(
        jax.random.split(key, n_layers))
    return [jax.tree.map(lambda a: a[i], stacked) for i in range(n_layers)]


@functools.partial(jax.jit, static_argnames=("vocab", "seq_len", "d_model",
                                             "n_layers", "mlp_ratio"))
def _init(key, *, vocab, seq_len, d_model, n_layers, mlp_ratio):
    ke, kp, kh, kb = jax.random.split(key, 4)
    d = d_model
    return {
        "embed": {"tok": STD * jax.random.normal(ke, (vocab, d), jnp.float32),
                  "pos": STD * jax.random.normal(kp, (seq_len, d),
                                                 jnp.float32)},
        "blocks": _blocks(kb, n_layers, d, mlp_ratio * d),
        "head": {"ln_f": {"scale": jnp.ones((d,), jnp.float32),
                          "bias": jnp.zeros((d,), jnp.float32)},
                 "out": {"w": STD * jax.random.normal(kh, (d, vocab),
                                                      jnp.float32),
                         "b": jnp.zeros((vocab,), jnp.float32)}},
    }


def init_gpt(seed: int, gpt_config: dict) -> dict:
    """The whole model's float32 parameter tree from ``seed``."""
    return _init(jax.random.key(seed), vocab=gpt_config["vocab"],
                 seq_len=gpt_config["seq_len"],
                 d_model=gpt_config["d_model"],
                 n_layers=gpt_config["n_layers"],
                 mlp_ratio=gpt_config.get("mlp_ratio", 4))


def split_stages(params: dict, n_stages: int) -> list[dict]:
    """Per-stage trees: blocks contiguous, earlier stages take the
    remainder, the first stage owns the embeddings and the last the head
    (the layout ``make_gpt_stages`` documents)."""
    blocks = params["blocks"]
    n = len(blocks)
    per = [n // n_stages + (1 if i < n % n_stages else 0)
           for i in range(n_stages)]
    out, start = [], 0
    for s, p in enumerate(per):
        tree: dict = {"blocks": blocks[start:start + p]}
        if s == 0:
            tree["embed"] = params["embed"]
        if s == n_stages - 1:
            tree["head"] = params["head"]
        out.append(tree)
        start += p
    return out


def leaf_layout(stage_tree: dict) -> list[tuple[str, int, int]]:
    """``(path, offset, size)`` of every leaf in a stage's packed float32
    row: leaves in ``jax.tree`` flatten order, laid end to end (how
    ``parallel/staging.py`` documents the packed row)."""
    leaves = jax.tree_util.tree_flatten_with_path(stage_tree)[0]
    out, off = [], 0
    for path, leaf in leaves:
        size = math.prod(leaf.shape)
        out.append((jax.tree_util.keystr(path), off, size))
        off += size
    return out
