"""The compiled train/eval steps.

One ``jit`` covers what the reference spreads over four distributed subsystems
per batch — forward RPC, loss, distributed-autograd backward, remote optimizer
step (``/root/reference/simple_distributed.py:109-113``). Buffers are donated,
so params and optimizer state update in place on-device.
"""

from __future__ import annotations

import functools
from typing import Any

import jax

from simple_distributed_machine_learning_tpu.parallel.pipeline import Pipeline
from simple_distributed_machine_learning_tpu.train.optimizer import Optimizer


def make_train_step(pipe: Pipeline, opt: Optimizer,
                    with_grad_norm: bool = False):
    """Returns ``step(buf, opt_state, x, targets, key) -> (buf, opt_state, loss)``.

    The whole pipeline fwd + bwd + update is one XLA program: the forward
    ppermute hops, their autodiff transposes (the backward hops), and each
    stage's owner-local optimizer update all schedule together, letting XLA
    overlap ICI transfer with compute — the overlap the reference's blocking
    RPC design structurally cannot have (SURVEY §3.3).

    ``with_grad_norm``: the step additionally returns the global L2 norm of
    the packed gradient buffer as a fourth output — the one extra scalar the
    numeric-anomaly sentinel (``resilience/sentinel.py``) watches for
    NaN/Inf alongside the loss. Computed from the gradients the update
    consumes anyway; the loss math is unchanged.
    """
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step_train(buf, opt_state, x, targets, key, weights=None):
        # Pipeline.loss_and_grads: GPipe via value_and_grad of the loss-only
        # engine (no [batch, *out_shape] accumulator rides the scan), or the
        # hand-scheduled 1F1B interleave when the pipeline was built with
        # schedule='1f1b'
        loss, grads = pipe.loss_and_grads(buf, x, targets, key,
                                          deterministic=False,
                                          weights=weights)
        buf2, opt_state2 = opt.update(grads, opt_state, buf)
        if with_grad_norm:
            gnorm = jnp.sqrt(jnp.sum(jnp.square(
                grads.astype(jnp.float32))))
            return buf2, opt_state2, loss, gnorm
        return buf2, opt_state2, loss

    return step_train


def make_scanned_train_step(pipe: Pipeline, opt: Optimizer, unroll: int = 1,
                            pool_steps: int | None = None):
    """Returns ``step(buf, opt_state, xs, targets, key) -> (buf, opt_state, losses)``
    where ``xs``/``targets`` carry a leading ``n_steps`` axis: one compiled
    program runs ``n_steps`` optimizer steps via ``lax.scan``.

    Why this exists: the reference dispatches every batch from Python through
    a blocking RPC (``simple_distributed.py:108-113``), so host overhead is
    paid per batch. On TPU the same Python-side loop would pay ~ms-scale
    dispatch per step, dwarfing the sub-ms compute of reference-scale models.
    Scanning the whole window keeps the chip busy back-to-back — this is the
    TPU-idiomatic shape of a training loop, and what ``bench.py`` measures.

    ``pool_steps``: when set, ``xs``/``targets`` are a POOL of ``P`` batches
    rather than one per step; the scan runs ``pool_steps`` optimizer steps,
    reading batch ``t % P`` at step ``t``. This keeps the resident input
    footprint at ``P`` batches however long the window is (a 5000-step f32
    MNIST window would otherwise pin ~1 GB of HBM for inputs alone).
    """

    from simple_distributed_machine_learning_tpu.parallel.staging import (
        pack_stage_params,
        unpack_stage_params,
    )

    # shards-is-None matters: a tensor-/expert-parallel stage's apply uses
    # mesh collectives, which cannot be traced outside shard_map
    trivial_mesh = (pipe.n_stages == 1 and pipe.n_data == 1
                    and pipe.n_model == 1 and pipe.n_seq == 1
                    and pipe.n_expert == 1
                    and pipe.stages[0].shards is None
                    and pipe.stages[0].expert_shards is None)

    from simple_distributed_machine_learning_tpu.ops.losses import nll_loss

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step_train_scanned(buf, opt_state, xs, targets, key):
        import jax.numpy as jnp

        def scan_batches(body, init):
            if pool_steps is None:
                return jax.lax.scan(body, init, (xs, targets), unroll=unroll)
            n_pool = xs.shape[0]

            def body_pool(carry, t):
                x = jax.lax.dynamic_index_in_dim(xs, t % n_pool, 0,
                                                 keepdims=False)
                tt = jax.lax.dynamic_index_in_dim(targets, t % n_pool, 0,
                                                  keepdims=False)
                return body(carry, (x, tt))

            return jax.lax.scan(body_pool, init, jnp.arange(pool_steps),
                                unroll=unroll)

        # On the degenerate single-device mesh, unpack params and any
        # buffer-shaped optimizer state to pytrees ONCE per window, scan on
        # pytrees, repack at the end: no iteration then cuts the packed
        # [1, 1, P] row or forms its gradient (per step that is one split
        # forward and one concatenate of the leaf cotangents backward, a
        # pass over the row each). Buffer-shaped state leaves (SGD
        # momentum, AdamW m/v) are unpacked alongside the params; scalar
        # leaves (step counters, carried bias-correction powers) pass through
        # unchanged — excluding them from this path sent every
        # counter-carrying optimizer down the packed-buffer engine, which
        # XLA:CPU compiles to ~1.4x the bytes and ~7x the live temp of the
        # pytree path for AdamW (benchmarks/opt_cost_analysis.py, the
        # round-5 "AdamW halves gpt_bf16" regression).
        os_leaves, os_def = jax.tree.flatten(opt_state)

        def _buf_shaped(l):
            return getattr(l, "shape", None) == buf.shape

        unpackable = trivial_mesh and all(
            _buf_shaped(l) or getattr(l, "ndim", None) == 0
            for l in os_leaves)

        if unpackable:
            meta = pipe.metas[0]
            stage = pipe.stages[0]
            buf_slot = [_buf_shaped(l) for l in os_leaves]

            def repack(tree):
                return pack_stage_params([tree])[0].reshape(buf.shape)

            params0 = unpack_stage_params(buf[0, 0, 0], meta)
            state0 = jax.tree.unflatten(os_def, [
                unpack_stage_params(l[0, 0, 0], meta) if is_buf else l
                for l, is_buf in zip(os_leaves, buf_slot)])

            def loss_tree(pp, x, t, k):
                # same math and RNG stream as Pipeline._fused_loss
                kk = jax.random.fold_in(
                    jax.random.fold_in(jax.random.fold_in(k, 0), 0), 0)
                xs = x.reshape((x.shape[0],) + tuple(stage.in_shape))
                if pipe.compute_dtype is not None:
                    pp = jax.tree.map(
                        lambda a: a.astype(pipe.compute_dtype), pp)
                    xs = stage.cast_input(xs, pipe.compute_dtype)
                out = stage.apply(pp, xs, kk, False)
                import jax.numpy as jnp
                aux = jnp.float32(0.0)
                if isinstance(out, tuple):
                    out, aux = out
                    aux = aux.astype(jnp.float32)
                return nll_loss(out.astype(jnp.float32), t, "mean") + aux

            def body(carry, batch):
                p, s, i = carry
                x, t = batch
                k = jax.random.fold_in(key, i)
                loss, grads = jax.value_and_grad(loss_tree)(p, x, t, k)
                p2, s2 = opt.update(grads, s, p)
                return (p2, s2, i + 1), loss

            (p2, s2, _), losses = scan_batches(body, (params0, state0, 0))
            # s2's buffer-slot "leaves" are params-shaped trees
            # (flatten_up_to recovers them for repacking); scalar slots come
            # back as the scalars they are
            opt2 = jax.tree.unflatten(
                os_def, [repack(t_) if is_buf else t_
                         for t_, is_buf in zip(os_def.flatten_up_to(s2),
                                               buf_slot)])
            return repack(p2), opt2, losses

        def body(carry, batch):
            b, s, i = carry
            x, t = batch
            k = jax.random.fold_in(key, i)
            loss, grads = pipe.loss_and_grads(b, x, t, k,
                                              deterministic=False)
            b2, s2 = opt.update(grads, s, b)
            return (b2, s2, i + 1), loss

        (buf2, opt2, _), losses = scan_batches(body, (buf, opt_state, 0))
        return buf2, opt2, losses

    return step_train_scanned


def make_eval_step(pipe: Pipeline):
    """Returns ``eval_step(buf, x, targets, key, n_valid) -> (sum_nll, n_correct)``.

    Deterministic: dropout is OFF — deliberately diverging from the
    reference's quirk of leaving worker-side dropout active during eval
    (``simple_distributed.py:75`` with ``model.eval()`` not crossing RPC at
    ``:120``; SURVEY §3.5 flags this as a bug not to carry over).

    ``n_valid`` masks zero-padded trailing rows of a ragged final batch (the
    compiled pipeline needs static shapes; the reference's DataLoader just
    emits a short batch, ``simple_distributed.py:95``).

    Memory: built on ``Pipeline.eval_metrics`` — the sums are computed
    inside the shard_map scan, so no ``[batch, *out_shape]`` logits tensor
    is ever materialized or replicated across stages (eval fits wherever
    training fits, even for vocab-wide LM outputs).
    """
    import jax.numpy as jnp

    @jax.jit
    def step_eval(buf, x, targets, key, n_valid):
        # per-sample 0/1 validity mask; eval_metrics broadcasts it over any
        # token axes (LM targets [B, T])
        mask = (jnp.arange(x.shape[0]) < n_valid).astype(jnp.float32)
        sum_loss, _, correct = pipe.eval_metrics(buf, x, targets, key,
                                                 weights=mask)
        return sum_loss, correct          # correct is exact int32

    return step_eval
