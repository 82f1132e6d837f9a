"""Stage packing: heterogeneous per-stage params → one stage-sharded buffer.

The reference places each pipeline stage's parameters on their owning process
as ordinary module attributes, and stitches them together with RRefs
(``/root/reference/simple_distributed.py:52-58,:82-83``). SPMD has no remote
references; instead, ownership is expressed with sharding: all stages' params
are packed into a single ``[n_stages, max_size]`` float buffer sharded
``P('stage')``, so each device physically holds exactly its own stage's
parameters (owner-local, like the reference) while the whole training step
remains one compiled program.

Because stages are heterogeneous (LeNet's conv front vs fc back), each stage's
param pytree is flattened and zero-padded to the size of the largest stage.
``StageMeta`` records the static structure needed to unflatten the local row
back into the stage's pytree inside a ``lax.switch`` branch.

Inter-stage activations use the same trick ("wire format"): every hop carries a
``[microbatch, wire_dim]`` array, with ``wire_encode``/``wire_decode`` padding /
unpadding each stage's real boundary shape. For homogeneous-width models the
pad is zero-cost; for ragged boundaries it costs a copy of the difference —
bandwidth that in exchange lets XLA compile ONE ppermute for the whole
pipeline (the reference instead pays a blocking RPC round-trip per hop,
``simple_distributed.py:49``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def contiguous_split(units: Sequence[Any], n_stages: int) -> list[list]:
    """Assign ``units`` (layers/blocks) contiguously to ``n_stages`` stages,
    earlier stages taking the remainder — THE stage-distribution rule, shared
    by every splittable model builder (models/mlp.py, models/gpt.py) and the
    checkpoint repacker (train/checkpoint.py), so they can never drift."""
    n = len(units)
    if n < n_stages:
        raise ValueError(f"{n} layers cannot fill {n_stages} stages")
    per = [n // n_stages + (1 if i < n % n_stages else 0)
           for i in range(n_stages)]
    out, start = [], 0
    for p in per:
        out.append(list(units[start:start + p]))
        start += p
    return out


@dataclasses.dataclass(frozen=True)
class StageMeta:
    """Static description of one stage's packed parameter layout."""
    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    sizes: tuple[int, ...]
    total: int


def _flatten_one(params: Any) -> tuple[jax.Array, StageMeta]:
    leaves, treedef = jax.tree.flatten(params)
    shapes = tuple(tuple(l.shape) for l in leaves)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    flat = (jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in leaves])
            if leaves else jnp.zeros((0,), jnp.float32))
    return flat, StageMeta(treedef, shapes, sizes, int(flat.shape[0]))


def pack_stage_params(stage_params: Sequence[Any]) -> tuple[jax.Array, list[StageMeta]]:
    """Pack per-stage pytrees into a ``[n_stages, max_size]`` f32 buffer.

    Returns the buffer (row s = stage s's flattened params, zero-padded) and
    the per-stage metadata needed by :func:`unpack_stage_params`.
    """
    flats, metas = [], []
    for p in stage_params:
        f, m = _flatten_one(p)
        flats.append(f)
        metas.append(m)
    max_size = max((m.total for m in metas), default=0)
    rows = [jnp.pad(f, (0, max_size - f.shape[0])) for f in flats]
    return jnp.stack(rows), metas


def unpack_stage_params(row: jax.Array, meta: StageMeta) -> Any:
    """Rebuild one stage's param pytree from its packed row.

    ONE ``lax.split`` cuts the row into the leaves (and the zero padding,
    dropped), so that its transpose is ONE ``concatenate`` of the leaf
    cotangents: the gradient with respect to the row writes every element
    once. Do not cut it with a slice per leaf: each slice transposes to a
    ``pad`` to the row's width, added to the others, O(leaves x row) — on
    gpt2-medium's 294 leaves that was more than half the train step
    (PERF.md, Findings, PR 27)."""
    pad = row.shape[0] - meta.total
    pieces = jax.lax.split(row, meta.sizes + ((pad,) if pad else ()))
    leaves = [jnp.reshape(p, shape) for p, shape in zip(pieces, meta.shapes)]
    return jax.tree.unflatten(meta.treedef, leaves)


def pack_stage_grads(tree: Any, meta: StageMeta, width: int) -> jax.Array:
    """In-graph inverse of :func:`unpack_stage_params`: flatten a pytree with
    ``meta``'s leaf order into a zero-padded ``[width]`` f32 row. Used by the
    1F1B engine, whose hand-scheduled backward produces per-stage grad
    pytrees that must ride the same packed layout as the param buffer."""
    leaves = jax.tree.flatten(tree)[0]
    flat = (jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in leaves])
            if leaves else jnp.zeros((0,), jnp.float32))
    return jnp.pad(flat, (0, width - flat.shape[0]))


def wire_encode(x: jax.Array, wire_dim: int) -> jax.Array:
    """Flatten per-sample features and zero-pad to the pipeline wire width."""
    flat = jnp.reshape(x, (x.shape[0], -1))
    pad = wire_dim - flat.shape[1]
    if pad < 0:
        raise ValueError(
            f"activation width {flat.shape[1]} exceeds wire_dim {wire_dim}")
    return jnp.pad(flat, ((0, 0), (0, pad))) if pad else flat


def wire_decode(wire: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    """Slice the leading features off the wire and reshape to ``shape``
    (per-sample shape, excluding the batch dim)."""
    size = int(np.prod(shape))
    return jnp.reshape(wire[:, :size], (wire.shape[0],) + tuple(shape))
