"""Memory-flat eval engine (Pipeline.eval_metrics): parity + memory.

The eval path used to go through ``loss_and_logits``, whose scan carries the
full ``[M, mb, *out_shape]`` log-probs accumulator replicated across stages —
for a vocab-wide LM, eval would OOM long before training. ``eval_metrics``
folds each microbatch's log-probs into three scalars inside the scan; these
tests pin (a) exact agreement with metrics computed from the materialized
logits across pp/dp/sp/ep topologies and ragged masks, and (b) that the
compiled program's temp memory actually shrinks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simple_distributed_machine_learning_tpu.data.text import synthetic_tokens
from simple_distributed_machine_learning_tpu.models.gpt import (
    GPTConfig,
    make_gpt_stages,
)
from simple_distributed_machine_learning_tpu.models.mlp import make_mlp_stages
from simple_distributed_machine_learning_tpu.ops.losses import nll_loss
from simple_distributed_machine_learning_tpu.parallel.mesh import make_mesh
from simple_distributed_machine_learning_tpu.parallel.pipeline import Pipeline


def _reference_metrics(pipe, buf, x, y, key, weights):
    """The old eval computation: materialize logits, reduce on the host."""
    _, logp = pipe.loss_and_logits(buf, x, y, key, deterministic=True)
    nll = nll_loss(logp, y, "none")
    w = (jnp.ones((x.shape[0],), jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    wb = jnp.broadcast_to(w.reshape(w.shape + (1,) * (nll.ndim - 1)),
                          nll.shape)
    hit = (logp.argmax(-1) == y) & (wb > 0)
    return (float(jnp.sum(nll * wb)), float(jnp.sum(wb)),
            int(jnp.sum(hit.astype(jnp.int32))))


def _check(pipe, buf, x, y, key, weights, rtol=2e-5):
    want = _reference_metrics(pipe, buf, x, y, key, weights)
    got = pipe.eval_metrics(buf, x, y, key, weights=weights)
    np.testing.assert_allclose(float(got[0]), want[0], rtol=rtol, atol=1e-4)
    np.testing.assert_allclose(float(got[1]), want[1], rtol=0, atol=1e-6)
    # correct-counts are exact int32: require exact agreement
    assert int(got[2]) == want[2], (got, want)


def test_eval_metrics_gpt_pp_dp_weighted():
    cfg = GPTConfig(vocab=32, seq_len=16, d_model=32, n_heads=2, n_layers=2)
    stages, wire_dim, out_shape = make_gpt_stages(jax.random.key(0), cfg, 2)
    pipe = Pipeline(stages, make_mesh(n_stages=2, n_data=2), wire_dim,
                    out_shape, n_microbatches=2)
    buf = pipe.init_params()
    data = synthetic_tokens(8, cfg.seq_len, cfg.vocab, seed=2)
    x = jnp.asarray(data.x, jnp.float32)
    y = jnp.asarray(data.y)
    _check(pipe, buf, x, y, jax.random.key(3), None)
    # ragged mask: last 3 rows are padding
    mask = (jnp.arange(8) < 5).astype(jnp.float32)
    _check(pipe, buf, x, y, jax.random.key(3), mask)


def test_eval_metrics_gpt_seq_parallel():
    cfg = GPTConfig(vocab=32, seq_len=16, d_model=32, n_heads=2, n_layers=2,
                    attn_impl="ring", n_seq=2)
    stages, wire_dim, out_shape = make_gpt_stages(jax.random.key(0), cfg, 2)
    pipe = Pipeline(stages, make_mesh(n_stages=2, n_data=1, n_seq=2),
                    wire_dim, out_shape, n_microbatches=2)
    buf = pipe.init_params()
    data = synthetic_tokens(4, cfg.seq_len, cfg.vocab, seed=4)
    _check(pipe, buf, jnp.asarray(data.x, jnp.float32),
           jnp.asarray(data.y), jax.random.key(5), None)


def test_eval_metrics_moe_expert_parallel():
    cfg = GPTConfig(vocab=32, seq_len=16, d_model=32, n_heads=2, n_layers=2,
                    n_experts=4, n_expert_parallel=2)
    stages, wire_dim, out_shape = make_gpt_stages(jax.random.key(0), cfg, 2)
    pipe = Pipeline(stages, make_mesh(n_stages=2, n_data=1, n_expert=2),
                    wire_dim, out_shape, n_microbatches=1)
    buf = pipe.init_params()
    data = synthetic_tokens(4, cfg.seq_len, cfg.vocab, seed=6)
    _check(pipe, buf, jnp.asarray(data.x, jnp.float32),
           jnp.asarray(data.y), jax.random.key(7), None)


def test_eval_metrics_tensor_parallel():
    """n_model > 1: exercises the metrics path's model-axis replication
    proof (pmean for the float sums, integer psum // n_model for the
    count) on real column->row TP shards."""
    from simple_distributed_machine_learning_tpu.parallel.tensor import (
        make_mlp_tp_stages,
    )

    stages, wire_dim, out_dim = make_mlp_tp_stages(
        jax.random.key(0), [8, 16, 12, 16, 10], 2, 2)
    pipe = Pipeline(stages, make_mesh(n_stages=2, n_data=2, n_model=2),
                    wire_dim, out_dim, n_microbatches=2)
    buf = pipe.init_params()
    x = jax.random.normal(jax.random.key(1), (8, 8))
    y = jax.random.randint(jax.random.key(2), (8,), 0, 10)
    _check(pipe, buf, x, y, jax.random.key(3), None)
    mask = (jnp.arange(8) < 7).astype(jnp.float32)
    _check(pipe, buf, x, y, jax.random.key(3), mask)


def test_eval_metrics_classifier_ragged():
    stages, wire_dim, out_dim = make_mlp_stages(
        jax.random.key(0), [12, 16, 10], 2)
    pipe = Pipeline(stages, make_mesh(n_stages=2, n_data=2), wire_dim,
                    out_dim, n_microbatches=2)
    buf = pipe.init_params()
    x = jax.random.normal(jax.random.key(1), (8, 12))
    y = jax.random.randint(jax.random.key(2), (8,), 0, 10)
    mask = (jnp.arange(8) < 6).astype(jnp.float32)
    _check(pipe, buf, x, y, jax.random.key(3), mask)


def test_eval_metrics_trivial_mesh_fused():
    """Single-device fast path agrees with the engine semantics."""
    cfg = GPTConfig(vocab=32, seq_len=16, d_model=32, n_heads=2, n_layers=2)
    stages, wire_dim, out_shape = make_gpt_stages(jax.random.key(0), cfg, 1)
    mesh = make_mesh(n_stages=1, n_data=1, devices=jax.devices()[:1])
    pipe = Pipeline(stages, mesh, wire_dim, out_shape, n_microbatches=1)
    buf = pipe.init_params()
    data = synthetic_tokens(4, cfg.seq_len, cfg.vocab, seed=8)
    _check(pipe, buf, jnp.asarray(data.x, jnp.float32),
           jnp.asarray(data.y), jax.random.key(9), None)


def test_eval_metrics_memory_smaller_than_logits_path():
    """The compiled metrics program must not carry the [M, mb, T, V] logits
    accumulator: its temp allocation stays well under the logits path's on a
    config where that accumulator dominates (V=512, M=4)."""
    cfg = GPTConfig(vocab=512, seq_len=32, d_model=32, n_heads=2, n_layers=2)
    stages, wire_dim, out_shape = make_gpt_stages(jax.random.key(0), cfg, 2)
    pipe = Pipeline(stages, make_mesh(n_stages=2, n_data=1), wire_dim,
                    out_shape, n_microbatches=4)
    buf = pipe.init_params()
    data = synthetic_tokens(16, cfg.seq_len, cfg.vocab, seed=10)
    x = jnp.asarray(data.x, jnp.float32)
    y = jnp.asarray(data.y)
    key = jax.random.key(11)

    def temp_bytes(fn):
        lowered = jax.jit(fn).lower(buf, x, y, key)
        mem = lowered.compile().memory_analysis()
        if mem is None or not hasattr(mem, "temp_size_in_bytes"):
            pytest.skip("backend exposes no memory analysis")
        return mem.temp_size_in_bytes

    t_metrics = temp_bytes(
        lambda b, xx, yy, k: pipe.eval_metrics(b, xx, yy, k))
    t_logits = temp_bytes(
        lambda b, xx, yy, k: pipe.loss_and_logits(b, xx, yy, k,
                                                  deterministic=True))
    # the logits path carries [M=4, mb=4, T=32, V=512] f32 (~1 MB) in the
    # carry plus its stage-axis psum; the metrics path carries scalars
    assert t_metrics < t_logits, (t_metrics, t_logits)
    acc_bytes = 4 * 4 * 32 * 512 * 4
    assert t_logits - t_metrics > acc_bytes // 2, (t_metrics, t_logits)
