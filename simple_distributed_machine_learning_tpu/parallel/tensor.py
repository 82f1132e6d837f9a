"""Tensor (model) parallelism: Megatron-style sharded linear pairs.

Not owed for reference parity (SURVEY §2.2: the reference has no TP), but a
first-class capability of this framework: a ``model`` mesh axis shards the
hidden dimension of a linear pair —

- **column-parallel** first layer: weight ``[d_in, d_hidden/mp]`` per device,
  output stays sharded, the nonlinearity applies elementwise locally;
- **row-parallel** second layer: weight ``[d_hidden/mp, d_out]`` per device,
  partial products are summed with one ``lax.psum`` over ICI.

One all-reduce per pair, exactly the Megatron recipe, expressed as plain
functions to be called inside ``shard_map`` (composable with the pipeline's
``stage`` axis and the ``data`` axis).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from simple_distributed_machine_learning_tpu.ops.layers import linear_init
from simple_distributed_machine_learning_tpu.parallel.compat import (
    ct_like,
    pvary_to,
)
from simple_distributed_machine_learning_tpu.parallel.mesh import MODEL_AXIS


def tp_pair_init(key: jax.Array, d_in: int, d_hidden: int, d_out: int,
                 n_shards: int) -> list[dict]:
    """Per-shard params for a column→row parallel linear pair.

    Returns a list of ``n_shards`` pytrees; shard i holds columns
    ``[i*h, (i+1)*h)`` of W1 (h = d_hidden/n_shards) and the matching rows of
    W2. Initialization matches the unsharded :func:`linear_init` layers, so a
    TP run is numerically identical to the dense run (see tests).
    """
    if d_hidden % n_shards:
        raise ValueError(f"d_hidden {d_hidden} not divisible by {n_shards}")
    k1, k2 = jax.random.split(key)
    w1 = linear_init(k1, d_in, d_hidden)
    w2 = linear_init(k2, d_hidden, d_out)
    h = d_hidden // n_shards
    shards = []
    for i in range(n_shards):
        shards.append({
            "w1": {"w": w1["w"][:, i * h:(i + 1) * h],
                   "b": w1["b"][i * h:(i + 1) * h]},
            # w2's bias is REPLICATED on every shard and added after the
            # psum: each replica then receives the identical cotangent, so
            # SPMD updates keep the copies in sync and the effective bias
            # trains at exactly the dense rate (a shard-0-only bias added
            # pre-psum would train n_shards times too fast)
            "w2": {"w": w2["w"][i * h:(i + 1) * h, :], "b": w2["b"]},
        })
    return shards


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def grad_sync(x: jax.Array, axis: str, overlap: str = "none") -> jax.Array:
    """Identity forward; psum over ``axis`` backward.

    For params that are REPLICATED over a mesh axis inside ``shard_map`` but
    carried in per-device (axis-sharded) storage: when the loss is built from
    axis-replicated values, the transpose machinery splits the loss cotangent
    evenly across the axis (each replica sees 1/axis_size of it). Leaves whose
    forward path crosses a psum recover the full cotangent through the psum's
    transpose; leaves that stay replicated (e.g. a row-parallel pair's output
    bias, or a whole non-tensor-parallel stage on a model>1 mesh) do not —
    their grads come out 1/axis_size of the true value, and replicas would
    train too slowly. Wrapping such params in ``grad_sync`` restores the full
    gradient on every replica (and keeps replicas bit-identical, since each
    gets the same psum).

    ``overlap='ring'`` runs the backward all-reduce as the chunked ppermute
    ring of :func:`~.overlap.ring_psum` instead of one blocking ``lax.psum``,
    so the gradient sync of wide replicated leaves hides its ICI transfer
    under neighbouring backward compute (ring summation order: replicas stay
    bit-identical to each other, tolerance-equal to the monolithic psum).
    """
    return x


def _grad_sync_fwd(x, axis, overlap):
    return x, None


def _grad_sync_bwd(axis, overlap, _, ct):
    # the primal is the per-device (axis-varying) storage leaf and the
    # output IS the primal, so ct carries the primal's vma; psum types its
    # result axis-invariant, so cast it back up to the primal's type
    if overlap == "ring":
        from simple_distributed_machine_learning_tpu.parallel.overlap import (
            _bwd_perm,
            _ring_psum_impl,
        )
        red = _ring_psum_impl(ct, axis, perm_fn=_bwd_perm,
                              tag="grad_sync_ring")
    else:
        red = lax.psum(ct, axis)
    return (ct_like(red, ct),)


grad_sync.defvjp(_grad_sync_fwd, _grad_sync_bwd)


def tp_pair_apply(params: dict, x: jax.Array, activation=jax.nn.relu,
                  axis: str = MODEL_AXIS, overlap: str = "none") -> jax.Array:
    """Column→activation→row parallel pair. Call inside shard_map; ``params``
    is THIS device's shard. One all-reduce over ``axis`` per call; the output
    bias is replicated and added after the reduce (see :func:`tp_pair_init`),
    with :func:`grad_sync` restoring its full (unsplit) gradient.

    ``overlap='none'``: the Megatron monolithic ``lax.psum`` — the chip
    blocks for the full collective after the row matmul. ``overlap='ring'``:
    the chunked-psum collective matmul of :func:`~.overlap.ring_psum` — the
    partial products ring-shift chunk by chunk so each hop hides under
    another chunk's accumulate (forward AND backward; tolerance-equal, see
    overlap.py's numerics note).

    The ``pmean`` around the bias is the vma-checker's replication proof:
    the replicas are bit-identical (grad_sync keeps them in sync), so it is
    the identity value-wise, and its transpose (ct/n per replica) composes
    with grad_sync's psum to hand every replica the full cotangent — the
    same accounting the implicit replicated out_spec used to do. On the ring
    path the reduced value stays varying-typed (ppermutes carry no
    replication proof), so the bias term is pcast up to match."""
    h = activation(x @ params["w1"]["w"] + params["w1"]["b"])
    z = h @ params["w2"]["w"]
    bias = lax.pmean(grad_sync(params["w2"]["b"], axis, overlap), axis)
    if overlap == "ring":
        from simple_distributed_machine_learning_tpu.parallel.overlap import (
            ring_psum,
        )
        return ring_psum(z, axis) + pvary_to(bias, (axis,))
    return lax.psum(z, axis) + bias


def stack_tp_shards(shards: list[dict]):
    """Stack per-shard pytrees along a leading axis for ``P('model')``
    placement: leaf i of the result has shape ``[n_shards, ...]``."""
    return jax.tree.map(lambda *ls: jnp.stack(ls), *shards)


def make_mlp_tp_stages(key: jax.Array, dims, n_stages: int, n_model: int,
                       overlap: str = "none"):
    """Tensor-parallel MLP pipeline stages: dp x pp x tp in one step.

    Like :func:`~..models.mlp.make_mlp_stages` but each stage is a
    column→row parallel linear *pair* sharded ``n_model`` ways over the
    ``model`` mesh axis, so ``dims`` must have ``2 * n_stages`` layers
    (length ``2 * n_stages + 1``) and every hidden width must divide by
    ``n_model``. Initialization splits the same dense init as the unsharded
    layers, so the TP pipeline matches a dense single-device run to float
    tolerance (tests/test_tp_pipeline.py).

    ``overlap``: the collective schedule of every pair's all-reduce —
    ``'none'`` (monolithic psum) or ``'ring'`` (latency-hiding chunked ring,
    ``overlap.ring_psum``; same losses to float tolerance).

    Returns ``(stages, wire_dim, out_dim)`` for :class:`~.pipeline.Pipeline`
    on a ``make_mesh(n_stages=..., n_model=...)`` mesh.
    """
    from simple_distributed_machine_learning_tpu.ops.losses import log_softmax
    from simple_distributed_machine_learning_tpu.parallel.overlap import (
        check_overlap,
    )
    from simple_distributed_machine_learning_tpu.parallel.pipeline import Stage

    check_overlap(overlap)
    dims = [int(d) for d in dims]
    if len(dims) != 2 * n_stages + 1:
        raise ValueError(
            f"TP stages hold one column->row pair each: need exactly "
            f"{2 * n_stages} layers for {n_stages} stages, got {len(dims) - 1}")
    keys = jax.random.split(key, n_stages)

    stages = []
    for s in range(n_stages):
        d_in, d_h, d_out = dims[2 * s], dims[2 * s + 1], dims[2 * s + 2]
        shards = tuple(tp_pair_init(keys[s], d_in, d_h, d_out, n_model))
        is_last = s == n_stages - 1

        def apply(params, x, key, deterministic, _last=is_last):
            y = tp_pair_apply(params, x, activation=jax.nn.relu,
                              overlap=overlap)
            return log_softmax(y) if _last else jax.nn.relu(y)

        stages.append(Stage(apply=apply, params=shards[0],
                            in_shape=(d_in,), shards=shards))
    # only stage inputs/outputs (even-index dims) cross the wire; hidden
    # widths live inside a stage and must not inflate the ppermute buffers
    return stages, max(dims[::2]), dims[-1]
