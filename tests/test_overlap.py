"""Latency-hiding collective matmuls (parallel/overlap.py).

Parity of the ppermute-chunked ring schedules against the dense math and the
monolithic collectives they replace — forward AND backward (the custom_vjps
mirror the schedules) — on 2- and 4-shard meshes, plus the tensor-parallel
pair, the EP dispatch ring, and a GPT TP training-trajectory parity run with
``overlap='ring'``.

Everything is jitted: the ring schedules are built for one fused XLA program
(eager per-primitive dispatch of collective-permutes is not a supported
execution mode). Ring summation order differs from the monolithic all-reduce,
so comparisons are to float tolerance, not bit-exact (overlap.py's numerics
note).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from simple_distributed_machine_learning_tpu.parallel.compat import (
    pvary_to,
    shard_map,
)
from simple_distributed_machine_learning_tpu.parallel.overlap import (
    allgather_matmul,
    check_overlap,
    matmul_reducescatter,
    ring_all_gather,
    ring_psum,
    ring_reduce_scatter,
)

TOL = dict(rtol=2e-5, atol=2e-5)


def _mesh(mp):
    return Mesh(np.array(jax.devices()[:mp]), ("model",))


@pytest.mark.parametrize("mp", [2, 4])
def test_ring_all_gather_and_reduce_scatter(mp):
    mesh = _mesh(mp)
    x = jax.random.normal(jax.random.key(0), (8, 6))

    ag = jax.jit(shard_map(lambda s: ring_all_gather(s, "model"),
                           mesh=mesh, in_specs=P("model"), out_specs=P(None),
                           check_vma=False))
    np.testing.assert_allclose(np.asarray(ag(x)), np.asarray(x), **TOL)

    # per-device partials x * (i+1): the scattered sum is x * sum(1..mp)
    def rs(xf):
        i = lax.axis_index("model")
        return ring_reduce_scatter(xf * (i + 1.0), "model")

    f = jax.jit(shard_map(rs, mesh=mesh, in_specs=P(None),
                          out_specs=P("model"), check_vma=False))
    np.testing.assert_allclose(np.asarray(f(x)),
                               np.asarray(x) * sum(range(1, mp + 1)), **TOL)


@pytest.mark.parametrize("mp", [2, 4])
def test_ring_psum_matches_psum_fwd_and_grad(mp):
    mesh = _mesh(mp)
    x = jax.random.normal(jax.random.key(1), (6, 8))

    def loss(xf, use_ring):
        def body(v):
            part = v * (lax.axis_index("model") + 1.0)
            tot = (ring_psum(part, "model") if use_ring
                   else lax.psum(part, "model"))
            return jnp.sum(tot ** 2)
        return shard_map(body, mesh=mesh, in_specs=P(None), out_specs=P(),
                         check_vma=False)(xf)

    l_ring = jax.jit(lambda v: loss(v, True))(x)
    l_psum = jax.jit(lambda v: loss(v, False))(x)
    np.testing.assert_allclose(float(l_ring), float(l_psum), rtol=1e-6)
    g_ring = jax.jit(jax.grad(lambda v: loss(v, True)))(x)
    g_psum = jax.jit(jax.grad(lambda v: loss(v, False)))(x)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_psum), **TOL)


@pytest.mark.parametrize("mp", [2, 4])
def test_ring_psum_indivisible_last_axis_falls_back(mp):
    """A last axis that does not divide by the ring size silently takes the
    monolithic psum path — same value, no shape error."""
    mesh = _mesh(mp)
    x = jax.random.normal(jax.random.key(2), (4, 5))  # 5 % mp != 0
    f = jax.jit(shard_map(lambda v: ring_psum(v, "model"), mesh=mesh,
                          in_specs=P(None), out_specs=P(None, None),
                          check_vma=False))
    np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x) * mp, **TOL)


@pytest.mark.parametrize("mp", [2, 4])
def test_allgather_matmul_matches_dense(mp):
    """Column-parallel collective matmul: sharded rows x column-sharded
    weight == the dense product, values and both grads."""
    mesh = _mesh(mp)
    N, d, k = 8, 12, 8
    X = jax.random.normal(jax.random.key(0), (N, d))
    W = jax.random.normal(jax.random.key(1), (d, k))

    fwd = jax.jit(shard_map(
        lambda xs, ws: allgather_matmul(xs, ws, "model"),
        mesh=mesh, in_specs=(P("model"), P(None, "model")),
        out_specs=P(None, "model"), check_vma=False))
    np.testing.assert_allclose(np.asarray(fwd(X, W)), np.asarray(X @ W),
                               **TOL)

    def loss(Xf, Wf, use_ring):
        def body(xs, ws):
            y = (allgather_matmul(xs, ws, "model") if use_ring
                 else lax.all_gather(xs, "model", axis=0, tiled=True) @ ws)
            return lax.psum(jnp.sum(y ** 2), "model")
        return shard_map(body, mesh=mesh,
                         in_specs=(P("model"), P(None, "model")),
                         out_specs=P(), check_vma=False)(Xf, Wf)

    gx_r, gw_r = jax.jit(jax.grad(lambda a, b: loss(a, b, True),
                                  argnums=(0, 1)))(X, W)
    gx_m, gw_m = jax.jit(jax.grad(lambda a, b: loss(a, b, False),
                                  argnums=(0, 1)))(X, W)
    np.testing.assert_allclose(np.asarray(gx_r), np.asarray(gx_m), **TOL)
    np.testing.assert_allclose(np.asarray(gw_r), np.asarray(gw_m), **TOL)


@pytest.mark.parametrize("mp", [2, 4])
def test_matmul_reducescatter_matches_monolithic_psum(mp):
    """Row-parallel collective matmul: ring-accumulated partial products ==
    one blocking psum then slice, values and both grads."""
    mesh = _mesh(mp)
    N, k = 8, 8
    X = jax.random.normal(jax.random.key(3), (N, mp * 4))
    W = jax.random.normal(jax.random.key(4), (mp * 4, k))

    def y_of(xs, ws, use_ring):
        if use_ring:
            return matmul_reducescatter(xs, ws, "model")
        full = lax.psum(xs @ ws, "model")
        return lax.dynamic_slice_in_dim(
            full, lax.axis_index("model") * (N // mp), N // mp, 0)

    fwd = jax.jit(shard_map(
        lambda xs, ws: y_of(xs, ws, True), mesh=mesh,
        in_specs=(P(None, "model"), P("model")), out_specs=P("model"),
        check_vma=False))
    np.testing.assert_allclose(np.asarray(fwd(X, W)), np.asarray(X @ W),
                               **TOL)

    def loss(Xf, Wf, use_ring):
        def body(xs, ws):
            return lax.psum(jnp.sum(y_of(xs, ws, use_ring) ** 2), "model")
        return shard_map(body, mesh=mesh,
                         in_specs=(P(None, "model"), P("model")),
                         out_specs=P(), check_vma=False)(Xf, Wf)

    gx_r, gw_r = jax.jit(jax.grad(lambda a, b: loss(a, b, True),
                                  argnums=(0, 1)))(X, W)
    gx_m, gw_m = jax.jit(jax.grad(lambda a, b: loss(a, b, False),
                                  argnums=(0, 1)))(X, W)
    np.testing.assert_allclose(np.asarray(gx_r), np.asarray(gx_m), **TOL)
    np.testing.assert_allclose(np.asarray(gw_r), np.asarray(gw_m), **TOL)


@pytest.mark.parametrize("mp", [2, 4])
def test_tp_pair_ring_matches_none_and_dense(mp):
    """tp_pair_apply with overlap='ring' == overlap='none' == the dense
    pair, values and grads."""
    from simple_distributed_machine_learning_tpu.ops.layers import (
        linear,
        linear_init,
    )
    from simple_distributed_machine_learning_tpu.parallel.tensor import (
        stack_tp_shards,
        tp_pair_apply,
        tp_pair_init,
    )

    key = jax.random.key(0)
    d_in, d_h, d_out = 8, 16, 6
    x = jax.random.normal(jax.random.key(1), (4, d_in))
    mesh = _mesh(mp)
    stacked = stack_tp_shards(tp_pair_init(key, d_in, d_h, d_out, mp))

    def loss(p, xx, overlap):
        def body(pp, v):
            local = jax.tree.map(lambda l: l[0], pp)
            y = tp_pair_apply(local, v, axis="model", overlap=overlap)
            # the monolithic psum types y model-invariant, the ring leaves
            # it varying: cast up so one psum/mp serves both under the vma
            # checker (pcast's transpose needs typed cotangents, so this
            # gradient cannot be taken with check_vma=False)
            y = pvary_to(y, ("model",))
            return lax.psum(jnp.sum(y ** 2), "model") / mp
        return shard_map(body, mesh=mesh, in_specs=(P("model"), P()),
                         out_specs=P())(p, xx)

    l_ring, g_ring = jax.jit(jax.value_and_grad(
        lambda p: loss(p, x, "ring")))(stacked)
    l_none, g_none = jax.jit(jax.value_and_grad(
        lambda p: loss(p, x, "none")))(stacked)
    np.testing.assert_allclose(float(l_ring), float(l_none), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g_ring), jax.tree.leaves(g_none)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)

    # dense ground truth for the forward value
    k1, k2 = jax.random.split(key)
    w1, w2 = linear_init(k1, d_in, d_h), linear_init(k2, d_h, d_out)
    want = linear(w2, jax.nn.relu(linear(w1, x)))
    got = jax.jit(shard_map(
        lambda pp, v: tp_pair_apply(jax.tree.map(lambda l: l[0], pp), v,
                                    axis="model", overlap="ring"),
        mesh=mesh, in_specs=(P("model"), P()), out_specs=P(None, None),
        check_vma=False))(stacked, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("ep", [2, 4])
def test_moe_ep_ring_matches_all_to_all(ep):
    """moe_apply_ep overlap='ring' (offset-ppermute dispatch, per-chunk FFN)
    == the 2x all_to_all schedule, loss and grads."""
    from simple_distributed_machine_learning_tpu.parallel.expert import (
        moe_apply_ep,
        moe_init,
    )

    mesh = Mesh(np.array(jax.devices()[:ep]), ("expert",))
    E, d, dh, T = 4, 8, 16, 12
    params = moe_init(jax.random.key(0), d, dh, E)
    x = jax.random.normal(jax.random.key(1), (ep * T, d))
    per = E // ep
    shards = [
        {"router": params["router"],
         "experts": jax.tree.map(lambda l, m=m: l[m * per:(m + 1) * per],
                                 params["experts"])}
        for m in range(ep)
    ]
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *shards)

    def loss(stk, xs, overlap):
        def body(p, xv):
            p = jax.tree.map(lambda l: l[0], p)
            y, aux = moe_apply_ep(p, xv, k=2, capacity=6, overlap=overlap)
            return lax.psum(jnp.sum(y ** 2), "expert") + aux
        return shard_map(body, mesh=mesh,
                         in_specs=(P("expert"), P("expert")), out_specs=P(),
                         check_vma=False)(stk, xs)

    l_ring, g_ring = jax.jit(jax.value_and_grad(
        lambda p: loss(p, x, "ring")))(stacked)
    l_none, g_none = jax.jit(jax.value_and_grad(
        lambda p: loss(p, x, "none")))(stacked)
    np.testing.assert_allclose(float(l_ring), float(l_none), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g_ring), jax.tree.leaves(g_none)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)


# ---- GPT tensor parallelism end to end ---------------------------------


def _gpt_losses(ntp, overlap, n_steps, n_stages=1):
    """Train the tiny TP GPT through the real engine; return the losses.

    Ring runs use a 1-stage mesh: the whole point of the GPipe switch is
    that different stage devices execute different branches, and XLA:CPU's
    collective-permute rendezvous is global — branch-divergent ppermute
    rings deadlock there (on TPU the permutes are independent ICI DMAs).
    One stage keeps the switch single-branch while still driving the full
    shard_map engine.
    """
    from simple_distributed_machine_learning_tpu.models.gpt import (
        GPTConfig,
        make_gpt_stages,
    )
    from simple_distributed_machine_learning_tpu.parallel.mesh import (
        make_mesh,
    )
    from simple_distributed_machine_learning_tpu.parallel.pipeline import (
        Pipeline,
    )
    from simple_distributed_machine_learning_tpu.train.optimizer import sgd
    from simple_distributed_machine_learning_tpu.train.step import (
        make_train_step,
    )

    cfg = GPTConfig(vocab=16, seq_len=8, d_model=16, n_heads=4, n_layers=2,
                    n_tensor_parallel=ntp, overlap=overlap)
    stages, wd, od = make_gpt_stages(jax.random.key(0), cfg, n_stages)
    mesh = make_mesh(n_stages=n_stages, n_data=1, n_model=ntp)
    pipe = Pipeline(stages, mesh, wd, od, n_microbatches=2, overlap=overlap)
    buf = pipe.init_params()
    opt = sgd(0.1, momentum=0.5)
    state = opt.init(buf)
    step = make_train_step(pipe, opt)
    x = jax.random.randint(jax.random.key(1), (4, 8), 0, 16).astype(
        jnp.float32)
    y = jax.random.randint(jax.random.key(2), (4, 8), 0, 16)
    losses = []
    for i in range(n_steps):
        buf, state, l = step(buf, state, x, y, jax.random.key(i))
        losses.append(float(l))
    return np.array(losses)


def test_gpt_tp_matches_dense_pipeline():
    """TP sharding alone (overlap='none') is loss-exact against the dense
    build through the 2-stage engine — the slices recompose the same math."""
    dense = _gpt_losses(1, "none", n_steps=5, n_stages=2)
    tp = _gpt_losses(2, "none", n_steps=5, n_stages=2)
    np.testing.assert_allclose(tp, dense, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("ntp", [2, 4])
def test_gpt_tp_ring_trajectory_matches_none(ntp):
    """The acceptance gate: overlap='ring' tracks overlap='none' within
    1e-5 over a 20-step GPT TP training run (4-device CPU mesh at ntp=4)."""
    l_none = _gpt_losses(ntp, "none", n_steps=20)
    l_ring = _gpt_losses(ntp, "ring", n_steps=20)
    np.testing.assert_allclose(l_ring, l_none, rtol=0, atol=1e-5)
    assert l_ring[-1] < l_ring[0]       # it actually trains


def test_overlap_validation():
    from simple_distributed_machine_learning_tpu.models.gpt import GPTConfig
    from simple_distributed_machine_learning_tpu.parallel.pipeline import (
        Pipeline,  # noqa: F401 - imported for the knob's home
    )

    with pytest.raises(ValueError, match="overlap"):
        check_overlap("diagonal")
    with pytest.raises(ValueError, match="overlap"):
        GPTConfig(overlap="diagonal")
    with pytest.raises(ValueError, match="n_heads"):
        GPTConfig(n_heads=4, n_tensor_parallel=3)
    with pytest.raises(ValueError, match="expert"):
        GPTConfig(n_experts=4, n_tensor_parallel=2)
    with pytest.raises(ValueError, match="attn_impl"):
        GPTConfig(attn_impl="flash", n_tensor_parallel=2)
