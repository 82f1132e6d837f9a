"""The program's GPT stages over the benchmark's own weights."""

from __future__ import annotations

import dataclasses

import jax

from simple_distributed_machine_learning_tpu.models.gpt import (
    GPTConfig,
    make_gpt_stages,
)


def gpt_stages(cfg: GPTConfig, n_stages: int, stage_trees: list[dict]):
    """``make_gpt_stages``'s stage list with ``stage_trees`` (the
    benchmark's seeded weights, ``weights.split_stages``) as parameters.

    The program's builder runs abstractly (``jax.eval_shape``), which gives
    its stage functions and the shapes it expects without making its own
    weights; a tree that does not match those shapes is an error, not a
    silent reshape."""
    held = {}

    def build(key):
        stages, wire_dim, out_shape = make_gpt_stages(key, cfg, n_stages)
        held.update(stages=stages, wire_dim=wire_dim, out_shape=out_shape)
        return [s.params for s in stages]

    want = jax.eval_shape(build, jax.random.key(0))
    have = jax.eval_shape(lambda t: t, stage_trees)
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have))):
        raise SystemExit(
            "bench_cells: the program's GPT parameter layout is not the "
            "one bench_cells/weights.py makes; the benchmark needs a new "
            "weights file for it")
    stages = [dataclasses.replace(s, params=t)
              for s, t in zip(held["stages"], stage_trees)]
    return stages, held["wire_dim"], held["out_shape"]
