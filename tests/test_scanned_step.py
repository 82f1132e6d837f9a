"""The epoch-compiled (lax.scan) train step must match the per-step loop."""

import jax
import jax.numpy as jnp
import numpy as np

from simple_distributed_machine_learning_tpu.models.mlp import make_mlp_stages
from simple_distributed_machine_learning_tpu.parallel.mesh import make_mesh
from simple_distributed_machine_learning_tpu.parallel.pipeline import Pipeline
from simple_distributed_machine_learning_tpu.train.optimizer import sgd
from simple_distributed_machine_learning_tpu.train.step import (
    make_scanned_train_step,
    make_train_step,
)


def test_scanned_matches_per_step_loop():
    key = jax.random.key(0)
    stages, wd, od = make_mlp_stages(key, [12, 16, 10], 2)
    mesh = make_mesh(n_stages=2, n_data=1)
    pipe = Pipeline(stages, mesh, wd, od, n_microbatches=2)
    opt = sgd(0.1, 0.5)

    n_steps, batch = 4, 8
    xs = jax.random.normal(key, (n_steps, batch, 12))
    ts = jax.random.randint(key, (n_steps, batch), 0, 10)

    # scanned: one compiled program for all steps
    buf_a = pipe.init_params()
    st_a = opt.init(buf_a)
    scanned = make_scanned_train_step(pipe, opt)
    buf_a, st_a, losses = scanned(buf_a, st_a, xs, ts, key)

    # loop: same RNG schedule (fold_in(key, i))
    buf_b = pipe.init_params()
    st_b = opt.init(buf_b)
    step = make_train_step(pipe, opt)
    loop_losses = []
    for i in range(n_steps):
        buf_b, st_b, l = step(buf_b, st_b, xs[i], ts[i],
                              jax.random.fold_in(key, i))
        loop_losses.append(float(l))

    np.testing.assert_allclose(np.asarray(losses), loop_losses,
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(buf_a), np.asarray(buf_b),
                               rtol=2e-5, atol=2e-5)


def test_scanned_adamw_single_device_matches_loop():
    """Scalar-state optimizers (AdamW's step counter) must ride the
    single-device UNPACKED fast path and still match the per-step loop.

    Regression for the round-5 finding: the fast-path gate required every
    optimizer-state leaf to be buffer-shaped, so AdamW fell onto the packed
    engine (~1.9x bytes, ~7x live temp; benchmarks/opt_cost_analysis.py).
    """
    from simple_distributed_machine_learning_tpu.train.optimizer import adamw

    key = jax.random.key(3)
    stages, wd, od = make_mlp_stages(key, [12, 16, 10], 1)
    mesh = make_mesh(n_stages=1, n_data=1)
    pipe = Pipeline(stages, mesh, wd, od, n_microbatches=1)
    opt = adamw(5e-3)

    n_steps, batch = 4, 8
    xs = jax.random.normal(key, (n_steps, batch, 12))
    ts = jax.random.randint(key, (n_steps, batch), 0, 10)

    buf_a = pipe.init_params()
    st_a = opt.init(buf_a)
    scanned = make_scanned_train_step(pipe, opt)
    buf_a, st_a, losses = scanned(buf_a, st_a, xs, ts, key)

    buf_b = pipe.init_params()
    st_b = opt.init(buf_b)
    step = make_train_step(pipe, opt)
    loop_losses = []
    for i in range(n_steps):
        buf_b, st_b, l = step(buf_b, st_b, xs[i], ts[i],
                              jax.random.fold_in(key, i))
        loop_losses.append(float(l))

    np.testing.assert_allclose(np.asarray(losses), loop_losses,
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(buf_a), np.asarray(buf_b),
                               rtol=2e-5, atol=2e-5)
    # the step counter must come back as the scalar it went in as
    assert st_a[0].shape == ()
    assert int(st_a[0]) == n_steps


def test_adamw_rides_unpacked_fast_path():
    """Compiled-cost regression: on the trivial mesh, AdamW's scanned window
    must stay within ~1.6x of SGD's bytes accessed. The packed-engine
    fallback measured 1.9-2.0x (and 7x live temp) - if this ratio regresses,
    the fast-path gate broke again."""
    from simple_distributed_machine_learning_tpu.train.optimizer import adamw

    key = jax.random.key(4)
    stages, wd, od = make_mlp_stages(key, [12, 16, 10], 1)
    mesh = make_mesh(n_stages=1, n_data=1)
    pipe = Pipeline(stages, mesh, wd, od, n_microbatches=1)
    xs = jax.random.normal(key, (4, 8, 12))
    ts = jax.random.randint(key, (4, 8), 0, 10)

    def window_bytes(opt):
        buf = pipe.init_params()
        st = opt.init(buf)
        step = make_scanned_train_step(pipe, opt)
        compiled = step.lower(buf, st, xs, ts, key).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        return cost["bytes accessed"]

    ratio = window_bytes(adamw(1e-3)) / window_bytes(sgd(0.1, 0.5))
    assert ratio < 1.6, f"AdamW window bytes {ratio:.2f}x SGD - packed-path?"

    # absolute anchor: a state shape the gate CANNOT unpack (a (2,)-vector
    # counter) forces the packed engine, whose scan carries the packed
    # [1, 1, 1, P] buffer; the real AdamW's scan must carry leaves only. If
    # a regression knocked every optimizer off the fast path, the adamw/sgd
    # ratio above would still pass (packed-vs-packed) but this anchor
    # catches it. Structure, not cost: with the unpack one lax.split the
    # two paths no longer separate by bytes accessed or live temp.
    from simple_distributed_machine_learning_tpu.analysis.trace import (
        subjaxprs,
    )
    from simple_distributed_machine_learning_tpu.train.optimizer import (
        Optimizer,
        adamw as _adamw,
    )

    def packed_adamw(lr) -> Optimizer:
        inner = _adamw(lr)

        def init(params):
            step, m, v = inner.init(params)
            return (jnp.zeros((2,), jnp.int32), m, v)

        def update(grads, state, params):
            vec, m, v = state
            new_params, (step, m, v) = inner.update(
                grads, (vec[0], m, v), params)
            return new_params, (jnp.stack([step, step]), m, v)

        return Optimizer(init, update)

    def scans(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                yield eqn
            for _key, _i, sub in subjaxprs(eqn):
                yield from scans(sub)

    def window_scan_carries_buffer(opt):
        buf = pipe.init_params()
        st = opt.init(buf)
        step = make_scanned_train_step(pipe, opt)
        window = max(scans(jax.make_jaxpr(step)(buf, st, xs, ts, key).jaxpr),
                     key=lambda e: len(e.invars))
        return any(v.aval.shape == buf.shape for v in window.invars)

    assert window_scan_carries_buffer(packed_adamw(1e-3))
    assert not window_scan_carries_buffer(adamw(1e-3)), (
        "AdamW's window scans over the packed buffer - did the fast-path "
        "gate regress for every optimizer?")


def test_scanned_clip_single_device_matches_loop():
    """clip_by_global_norm(adamw, ..., replication_weights()) on the trivial
    mesh: the scanned fast path unpacks grads to per-param pytrees, so the
    packed-buffer norm_weights no longer align leaf-for-leaf. Regression for
    the silent zip-truncation that computed the global norm from the FIRST
    gradient leaf only (under-clipping); the wrapper must detect the
    identity-weight case, drop the weights, and match the per-step packed
    loop exactly — with max_norm small enough that clipping is ACTIVE."""
    from simple_distributed_machine_learning_tpu.train.optimizer import (
        adamw,
        clip_by_global_norm,
    )

    key = jax.random.key(7)
    stages, wd, od = make_mlp_stages(key, [12, 16, 10], 1)
    mesh = make_mesh(n_stages=1, n_data=1)
    pipe = Pipeline(stages, mesh, wd, od, n_microbatches=1)
    # max_norm far below a fresh-init nll gradient's global norm: every step
    # clips, so a wrong norm changes the trajectory
    opt = clip_by_global_norm(adamw(5e-3), 1e-3, pipe.replication_weights())

    n_steps, batch = 4, 8
    xs = jax.random.normal(key, (n_steps, batch, 12))
    ts = jax.random.randint(key, (n_steps, batch), 0, 10)

    buf_a = pipe.init_params()
    st_a = opt.init(buf_a)
    scanned = make_scanned_train_step(pipe, opt)
    buf_a, st_a, losses = scanned(buf_a, st_a, xs, ts, key)

    buf_b = pipe.init_params()
    st_b = opt.init(buf_b)
    step = make_train_step(pipe, opt)     # packed path: weights align
    loop_losses = []
    for i in range(n_steps):
        buf_b, st_b, l = step(buf_b, st_b, xs[i], ts[i],
                              jax.random.fold_in(key, i))
        loop_losses.append(float(l))

    np.testing.assert_allclose(np.asarray(losses), loop_losses,
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(buf_a), np.asarray(buf_b),
                               rtol=2e-5, atol=2e-5)

    # non-identity weights CANNOT be mapped onto unpacked grads — loud error,
    # not a silently wrong norm
    import pytest

    bad = clip_by_global_norm(adamw(5e-3), 1e-3,
                              0.5 * pipe.replication_weights())
    buf_c = pipe.init_params()
    st_c = bad.init(buf_c)
    with pytest.raises(ValueError, match="non-identity"):
        make_scanned_train_step(pipe, bad)(buf_c, st_c, xs, ts, key)
