"""Pipeline correctness: the #1 test battery (SURVEY §7 "hard parts" (a)).

Every test compares the N-device pipeline (shard_map + ppermute + lax.switch
+ GPipe scan) against the single-device fused composition of the same stages
— forward values, gradients, and whole SGD training trajectories must match
to float tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simple_distributed_machine_learning_tpu.models.mlp import make_mlp_stages
from simple_distributed_machine_learning_tpu.ops.losses import nll_loss
from simple_distributed_machine_learning_tpu.parallel.mesh import make_mesh
from simple_distributed_machine_learning_tpu.parallel.pipeline import (
    Pipeline,
    fused_reference,
)
from simple_distributed_machine_learning_tpu.parallel.staging import (
    pack_stage_params,
)
from simple_distributed_machine_learning_tpu.train.optimizer import sgd
from simple_distributed_machine_learning_tpu.train.step import make_train_step

RTOL = 2e-5
ATOL = 2e-5


def _fused_loss(stages, stage_params, x, targets):
    fused = fused_reference(stages)
    logp = fused(stage_params, x, jax.random.key(0), deterministic=True)
    return nll_loss(logp, targets, "mean")


def _make_problem(key, dims, n_stages, batch):
    km, kx, kt = jax.random.split(key, 3)
    stages, wire_dim, out_dim = make_mlp_stages(km, dims, n_stages)
    x = jax.random.normal(kx, (batch, dims[0]))
    targets = jax.random.randint(kt, (batch,), 0, dims[-1])
    return stages, wire_dim, out_dim, x, targets


@pytest.mark.parametrize("n_stages,n_data,n_micro", [
    (2, 1, 1),   # the reference's own topology: 2 stages, sequential schedule
    (2, 1, 4),   # 2-stage GPipe
    (4, 1, 1),   # BASELINE config 3: 4-stage, microbatch=1
    (4, 2, 4),   # pipeline + data parallel + GPipe combined
    (1, 1, 2),   # degenerate single-stage (fused) pipeline
])
@pytest.mark.slow            # heavy parity sweep: per-round gate
def test_pipeline_matches_fused_loss_and_grad(n_stages, n_data, n_micro):
    key = jax.random.key(42)
    dims = [12, 16, 16, 16, 10] if n_stages == 4 else [12, 16, 10]
    batch = 8 * n_micro
    stages, wire_dim, out_dim, x, targets = _make_problem(
        key, dims, max(n_stages, 1), batch)

    mesh = make_mesh(n_stages=n_stages, n_data=n_data)
    pipe = Pipeline(stages, mesh, wire_dim, out_dim, n_microbatches=n_micro)
    buf = pipe.init_params()

    loss, logp = pipe.loss_and_logits(buf, x, targets, jax.random.key(0),
                                      deterministic=True)
    want_loss = _fused_loss(stages, [s.params for s in stages], x, targets)
    np.testing.assert_allclose(float(loss), float(want_loss),
                               rtol=RTOL, atol=ATOL)

    # log-probs on the wire match the fused forward
    fused = fused_reference(stages)
    want_logp = fused([s.params for s in stages], x, jax.random.key(0), True)
    np.testing.assert_allclose(np.asarray(logp), np.asarray(want_logp),
                               rtol=RTOL, atol=ATOL)

    # gradients through ppermute/scan/switch match fused autodiff
    grads = jax.grad(lambda b: pipe.loss_and_logits(
        b, x, targets, jax.random.key(0), deterministic=True)[0])(buf)
    fused_grads = jax.grad(
        lambda ps: _fused_loss(stages, ps, x, targets)
    )([s.params for s in stages])
    want_buf, _ = pack_stage_params(fused_grads)
    # grads buffer is [n_stages, n_model=1, n_expert=1, P]; fused pack is
    # [n_stages, P]
    np.testing.assert_allclose(np.asarray(grads)[:, 0, 0],
                               np.asarray(want_buf), rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("n_micro", [1, 4])
@pytest.mark.slow
def test_loss_only_engine_matches_full(n_micro):
    """Pipeline.loss (the training path: no logits accumulator in the scan
    carry) must produce the identical value AND gradient as
    loss_and_logits()[0] — same RNG stream, same reductions."""
    key = jax.random.key(7)
    stages, wire_dim, out_dim, x, targets = _make_problem(
        key, [12, 16, 10], 2, 8 * n_micro)
    mesh = make_mesh(n_stages=2, n_data=1)
    pipe = Pipeline(stages, mesh, wire_dim, out_dim, n_microbatches=n_micro)
    buf = pipe.init_params()
    k = jax.random.key(1)

    l_full, g_full = jax.value_and_grad(
        lambda b: pipe.loss_and_logits(b, x, targets, k, False)[0])(buf)
    l_only, g_only = jax.value_and_grad(
        lambda b: pipe.loss(b, x, targets, k, False))(buf)
    np.testing.assert_allclose(float(l_only), float(l_full),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g_only), np.asarray(g_full),
                               rtol=1e-6, atol=1e-6)


def test_training_trajectory_matches_fused():
    """5 SGD(momentum) steps on the 2-stage pipeline == fused single-device."""
    key = jax.random.key(7)
    stages, wire_dim, out_dim, x, targets = _make_problem(key, [12, 16, 10], 2, 8)
    mesh = make_mesh(n_stages=2, n_data=1)
    pipe = Pipeline(stages, mesh, wire_dim, out_dim, n_microbatches=1)
    buf = pipe.init_params()
    opt = sgd(0.1, momentum=0.5)

    # pipeline side (deterministic: rebuild train step without dropout noise)
    import functools

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def pipe_step(b, m, x, t):
        loss, grads = jax.value_and_grad(lambda bb: pipe.loss_and_logits(
            bb, x, t, jax.random.key(0), deterministic=True)[0])(b)
        b2, m2 = opt.update(grads, m, b)
        return b2, m2, loss

    # fused side
    fused_params = [s.params for s in stages]
    fused_state = opt.init(fused_params)
    mom = opt.init(buf)
    pipe_losses, fused_losses = [], []
    for _ in range(5):
        buf, mom, loss = pipe_step(buf, mom, x, targets)
        pipe_losses.append(float(loss))
        fl, fg = jax.value_and_grad(
            lambda ps: _fused_loss(stages, ps, x, targets))(fused_params)
        fused_params, fused_state = opt.update(fg, fused_state, fused_params)
        fused_losses.append(float(fl))
    np.testing.assert_allclose(pipe_losses, fused_losses, rtol=1e-4, atol=1e-4)
    # losses should be strictly decreasing on this toy problem
    assert pipe_losses[-1] < pipe_losses[0]


@pytest.mark.slow
def test_data_parallel_matches_single_data_rank():
    """Same global batch, dp=4 vs dp=1: identical loss and grads."""
    key = jax.random.key(9)
    stages, wire_dim, out_dim, x, targets = _make_problem(key, [12, 16, 10], 2, 16)

    results = []
    for n_data in (1, 4):
        mesh = make_mesh(n_stages=2, n_data=n_data)
        pipe = Pipeline(stages, mesh, wire_dim, out_dim, n_microbatches=2)
        buf = pipe.init_params()
        loss = pipe.loss_and_logits(buf, x, targets, jax.random.key(0),
                                    deterministic=True)[0]
        grads = jax.grad(lambda b: pipe.loss_and_logits(
            b, x, targets, jax.random.key(0), deterministic=True)[0])(buf)
        results.append((float(loss), np.asarray(grads)))
    np.testing.assert_allclose(results[0][0], results[1][0], rtol=RTOL)
    np.testing.assert_allclose(results[0][1], results[1][1],
                               rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("seed,parent_gap", [(0, 3.278836e-02),
                                             (1, 5.882350e-02)])
def test_data_axis_adds_the_row_gradient_in_f32(seed, parent_gap):
    """bf16 compute, 2 stages: 2 data shards x 4 microbatches are the same
    eight 4-sample groups as 1 shard x 8 microbatches, and every group's
    bf16 leaf cotangents are the same numbers. The row is data-varying
    before the scan, so each shard adds its four in f32 and the two shards'
    rows are added in f32: the gradient is the unsharded engine's to f32
    summation order. While the row was data-invariant each leaf cotangent
    was added over the shards and ROUNDED to bf16 at every scan step: that
    engine was 1.5e-3 to 1.9e-3 of the gradient's norm away from the
    unsharded one, and ``parent_gap`` (commit 3c4690d, this CPU backend)
    from the fused one-device f32 gradient of the same batch."""
    dims, batch = [32, 128, 128, 64, 10], 32
    stages, wire_dim, out_dim, x, targets = _make_problem(
        jax.random.key(seed), dims, 2, batch)
    want = jax.grad(lambda ps: _fused_loss(stages, ps, x, targets))(
        [s.params for s in stages])
    want = np.asarray(pack_stage_params(want)[0])

    got = {}
    for n_data, n_micro in ((1, 8), (2, 4)):
        pipe = Pipeline(stages, make_mesh(n_stages=2, n_data=n_data),
                        wire_dim, out_dim, n_microbatches=n_micro,
                        remat=True, compute_dtype=jnp.bfloat16)
        _, grads = jax.jit(lambda b, p=pipe: p.loss_and_grads(
            b, x, targets, jax.random.key(3), deterministic=True))(
                pipe.init_params())
        got[n_data] = np.asarray(grads)[:, 0, 0]
    norm = np.linalg.norm(want)
    assert np.linalg.norm(got[2] - got[1]) / norm < 1e-6
    gap = {n: np.linalg.norm(g - want) / norm for n, g in got.items()}
    assert gap[2] <= gap[1] * (1 + 1e-5)
    assert gap[2] < parent_gap


def test_weighted_loss_masks_padding():
    """Zero-weighted padded rows must not dilute the loss: weighted loss over
    a padded batch == unweighted loss over just the valid prefix."""
    key = jax.random.key(13)
    stages, wire_dim, out_dim, x, targets = _make_problem(key, [12, 16, 10], 2, 16)
    mesh = make_mesh(n_stages=2, n_data=1)
    pipe = Pipeline(stages, mesh, wire_dim, out_dim, n_microbatches=2)
    buf = pipe.init_params()

    n_valid = 10
    x_pad = x.at[n_valid:].set(0.0)
    w = (jnp.arange(16) < n_valid).astype(jnp.float32)
    loss_w = pipe.loss_and_logits(buf, x_pad, targets, key, True, weights=w)[0]

    # unweighted over the valid prefix (use a divisible sub-batch)
    pipe1 = Pipeline(stages, mesh, wire_dim, out_dim, n_microbatches=1)
    loss_ref = pipe1.loss_and_logits(buf, x[:n_valid], targets[:n_valid],
                                     key, True)[0]
    np.testing.assert_allclose(float(loss_w), float(loss_ref),
                               rtol=RTOL, atol=RTOL)


def test_dropout_trains_and_eval_is_deterministic():
    key = jax.random.key(11)
    stages, wire_dim, out_dim, x, targets = _make_problem(key, [12, 16, 10], 2, 8)
    mesh = make_mesh(n_stages=2, n_data=1)
    pipe = Pipeline(stages, mesh, wire_dim, out_dim, n_microbatches=2)
    buf = pipe.init_params()
    l1 = pipe.loss_and_logits(buf, x, targets, jax.random.key(1), True)[0]
    l2 = pipe.loss_and_logits(buf, x, targets, jax.random.key(2), True)[0]
    np.testing.assert_allclose(float(l1), float(l2))  # eval ignores the key


@pytest.mark.slow
def test_gpipe_replicated_plain_stages_on_sharded_mesh():
    """Plain (unsharded) stages on a model=2 mesh: the switch transpose
    used to reject this with 'mismatched varying manual axes' — the
    zero-valued full-vma anchor in each branch pins every branch's input
    cotangent type. Gradients must match the fused model on every slot."""
    from simple_distributed_machine_learning_tpu.ops.losses import nll_loss
    from simple_distributed_machine_learning_tpu.parallel.staging import (
        unpack_stage_params,
    )

    stages, wd, od = make_mlp_stages(jax.random.key(0), [8, 16, 4], 2)
    mesh = make_mesh(n_stages=2, n_model=2, n_data=1)
    pipe = Pipeline(stages, mesh, wd, od, n_microbatches=2)
    x = jax.random.normal(jax.random.key(1), (8, 8))
    y = jax.random.randint(jax.random.key(2), (8,), 0, 4)
    buf = pipe.init_params()
    k = jax.random.key(7)
    fused = fused_reference(stages)

    def floss(b):
        ps = [unpack_stage_params(b[s, 0, 0], pipe.metas[s])
              for s in range(2)]
        return nll_loss(fused(ps, x, k, True), y, "mean")

    lF, gF = jax.value_and_grad(floss)(buf)
    lg, gg = pipe.loss_and_grads(buf, x, y, k, deterministic=True)
    np.testing.assert_allclose(float(lg), float(lF), rtol=1e-6)
    gF, gg = np.asarray(gF), np.asarray(gg)
    for s in range(2):
        for m in range(2):
            np.testing.assert_allclose(gg[s, m, 0], gF[s, 0, 0],
                                       rtol=1e-5, atol=1e-7)


@pytest.mark.slow
def test_gpipe_mixed_dense_and_moe_stages_on_expert_mesh():
    """A dense GPT stage and an EP-MoE GPT stage in ONE pipeline on an
    expert=2 mesh — another switch-transpose vma mismatch fixed by the
    branch anchor (the closed-over param row is a cond operand too).
    Smoke: loss/grads compute and are finite."""
    from simple_distributed_machine_learning_tpu.models.gpt import (
        GPTConfig,
        make_gpt_stages,
    )

    cfg_d = GPTConfig(vocab=32, seq_len=16, d_model=32, n_heads=2,
                      n_layers=2, n_experts=0)
    cfg_m = GPTConfig(vocab=32, seq_len=16, d_model=32, n_heads=2,
                      n_layers=2, n_experts=2, moe_top_k=2,
                      n_expert_parallel=2)
    sd, wdd, _ = make_gpt_stages(jax.random.key(0), cfg_d, 2)
    sm, wdm, od = make_gpt_stages(jax.random.key(0), cfg_m, 2)
    mesh = make_mesh(n_stages=2, n_data=1, n_expert=2)
    pipe = Pipeline([sd[0], sm[1]], mesh, max(wdd, wdm), od,
                    n_microbatches=2)
    x = jax.random.randint(jax.random.key(1), (8, 16), 0,
                           32).astype(jax.numpy.float32)
    y = jax.random.randint(jax.random.key(2), (8, 16), 0, 32)
    buf = pipe.init_params()
    loss, grads = pipe.loss_and_grads(buf, x, y, jax.random.key(7),
                                      deterministic=True)
    assert np.isfinite(float(loss))
    assert np.isfinite(np.asarray(grads)).all()
