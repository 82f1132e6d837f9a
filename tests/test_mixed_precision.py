"""Mixed precision (bfloat16 compute, f32 master) and rematerialization."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simple_distributed_machine_learning_tpu.models.mlp import make_mlp_stages
from simple_distributed_machine_learning_tpu.parallel.mesh import make_mesh
from simple_distributed_machine_learning_tpu.parallel.pipeline import Pipeline
from simple_distributed_machine_learning_tpu.train.optimizer import sgd
from simple_distributed_machine_learning_tpu.train.step import (
    make_scanned_train_step,
    make_train_step,
)


def _problem(batch=8):
    key = jax.random.key(0)
    stages, wd, od = make_mlp_stages(key, [16, 32, 10], 2)
    x = jax.random.normal(jax.random.key(1), (batch, 16))
    y = jax.random.randint(jax.random.key(2), (batch,), 0, 10)
    return stages, wd, od, x, y


def _pipe(stages, wd, od, **kw):
    return Pipeline(stages, make_mesh(n_stages=2, n_data=1), wd, od,
                    n_microbatches=2, **kw)


def test_bf16_close_to_f32_and_master_stays_f32():
    stages, wd, od, x, y = _problem()
    p32 = _pipe(stages, wd, od)
    p16 = _pipe(stages, wd, od, compute_dtype=jnp.bfloat16)
    l32, lp32 = p32.loss_and_logits(p32.init_params(), x, y, jax.random.key(0),
                                    deterministic=True)
    l16, lp16 = p16.loss_and_logits(p16.init_params(), x, y, jax.random.key(0),
                                    deterministic=True)
    assert lp16.dtype == jnp.float32          # loss path re-enters f32
    np.testing.assert_allclose(float(l16), float(l32), rtol=3e-2)
    np.testing.assert_allclose(np.asarray(lp16), np.asarray(lp32), atol=0.15)


def test_bf16_trains():
    stages, wd, od, x, y = _problem(batch=16)
    pipe = _pipe(stages, wd, od, compute_dtype=jnp.bfloat16)
    buf = pipe.init_params()
    assert buf.dtype == jnp.float32           # master params stay f32
    opt = sgd(0.3, momentum=0.5)
    state = opt.init(buf)
    step = make_train_step(pipe, opt)
    l0 = None
    for i in range(20):
        buf, state, l = step(buf, state, x, y, jax.random.key(i))
        l0 = float(l) if l0 is None else l0
    assert float(l) < 0.7 * l0
    assert buf.dtype == jnp.float32


def test_remat_is_numerically_identical():
    stages, wd, od, x, y = _problem()
    base = _pipe(stages, wd, od)
    rem = _pipe(stages, wd, od, remat=True)

    def grad_of(pipe):
        buf = pipe.init_params()
        return jax.grad(lambda b: pipe.loss_and_logits(
            b, x, y, jax.random.key(0), deterministic=True)[0])(buf)

    g1, g2 = grad_of(base), grad_of(rem)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-6, atol=1e-7)


def test_bf16_scanned_fast_path():
    """Single-device scanned window honors compute_dtype (the bench path)."""
    key = jax.random.key(0)
    stages, wd, od = make_mlp_stages(key, [16, 32, 10], 1)
    pipe = Pipeline(stages, make_mesh(1, 1), wd, od,
                    compute_dtype=jnp.bfloat16)
    opt = sgd(0.1, momentum=0.5)
    buf = pipe.init_params()
    state = opt.init(buf)
    step = make_scanned_train_step(pipe, opt)
    xs = jax.random.normal(key, (5, 8, 16))
    ts = jax.random.randint(key, (5, 8), 0, 10)
    buf, state, losses = step(buf, state, xs, ts, key)
    assert buf.dtype == jnp.float32
    assert np.isfinite(np.asarray(losses)).all()
    assert float(losses[-1]) < float(losses[0]) + 0.5


@pytest.mark.parametrize("path", ["fused", "gpipe", "1f1b", "scanned"])
def test_bf16_gpt_keeps_token_ids_exact(path):
    """Token ids ride into the first GPT stage as float32 and must reach its
    embedding lookup EXACT under bf16 compute, on every engine path. A bf16
    cast keeps 8 significant bits: it rounds every id above 256 and sends
    8191 to 8192 — out of the vocabulary, which ``jnp.take`` fills with NaN
    (the ``final_loss: NaN`` of the bf16 GPT bench rows). So: the top ids of
    a real-sized vocabulary give a finite loss that tracks f32 compute."""
    from simple_distributed_machine_learning_tpu.models.gpt import (
        GPTConfig,
        make_gpt_stages,
    )
    from simple_distributed_machine_learning_tpu.train.optimizer import sgd

    cfg = GPTConfig(vocab=8192, seq_len=8, d_model=16, n_heads=2, n_layers=2)
    n_stages = 1 if path in ("fused", "scanned") else 2
    stages, wd, od = make_gpt_stages(jax.random.key(0), cfg, n_stages)
    assert [s.token_input for s in stages] == [True] + [False] * (n_stages - 1)
    ids = jnp.array([[8191, 8190, 8177, 4097, 1025, 513, 257, 3]] * 4)
    x, y = ids.astype(jnp.float32), jnp.roll(ids, -1, axis=1)
    mesh = make_mesh(n_stages=n_stages, n_data=1)

    def loss(dtype):
        pipe = Pipeline(stages, mesh, wd, od, n_microbatches=2,
                        compute_dtype=dtype,
                        schedule="1f1b" if path == "1f1b" else "gpipe")
        buf = pipe.init_params()
        if path == "scanned":
            opt = sgd(0.1)
            step = make_scanned_train_step(pipe, opt)
            _, _, losses = step(buf, opt.init(buf), x[None], y[None],
                                jax.random.key(1))
            return float(losses[0])
        l, g = jax.jit(lambda b: pipe.loss_and_grads(
            b, x, y, jax.random.key(1), deterministic=True))(buf)
        assert bool(jnp.isfinite(g).all())
        return float(l)

    l32, l16 = loss(None), loss(jnp.bfloat16)
    assert np.isfinite(l16)
    np.testing.assert_allclose(l16, l32, rtol=3e-2)
