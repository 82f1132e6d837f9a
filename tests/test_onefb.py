"""1F1B schedule: loss/grad parity with the GPipe engine and the fused model.

The two engines compute the SAME objective by construction; these tests pin
it numerically across topologies, microbatch counts, weighted batches and
aux-loss (dense-MoE) stages — the same bar the GPipe engine met
(tests/test_pipeline.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simple_distributed_machine_learning_tpu.models.mlp import make_mlp_stages
from simple_distributed_machine_learning_tpu.parallel.mesh import make_mesh
from simple_distributed_machine_learning_tpu.parallel.pipeline import Pipeline



# sweep-heavy module: slow-tier (per-round gate). The quick per-commit gate
# still exercises the 1F1B engine via the parity smoke in
# tests/test_schedules.py::test_1f1b_quick_parity_smoke.
pytestmark = pytest.mark.slow


def _pipes(dims, n_stages, n_data=1, n_micro=1):
    key = jax.random.key(0)
    stages, wire, out = make_mlp_stages(key, dims, n_stages)
    mesh = make_mesh(n_stages=n_stages, n_data=n_data)
    gp = Pipeline(stages, mesh, wire, out, n_microbatches=n_micro)
    fb = Pipeline(stages, mesh, wire, out, n_microbatches=n_micro,
                  schedule="1f1b")
    return gp, fb


def _data(dims, batch, seed=1):
    x = jax.random.normal(jax.random.key(seed), (batch, dims[0]))
    y = jax.random.randint(jax.random.key(seed + 1), (batch,), 0, dims[-1])
    return x, y


@pytest.mark.parametrize("n_stages,n_data,n_micro,batch", [
    (2, 1, 1, 8),     # the reference's sequential schedule
    (2, 1, 4, 8),     # GPipe microbatching
    (4, 1, 4, 8),     # deeper pipeline
    (2, 2, 2, 8),     # dp x pp
    (4, 2, 4, 16),    # dp x deep pp
])
def test_1f1b_matches_gpipe_loss_and_grads(n_stages, n_data, n_micro, batch):
    dims = [12, 16, 16, 16, 10][: n_stages + 1] if n_stages > 2 else [12, 16, 10]
    gp, fb = _pipes(dims, n_stages, n_data, n_micro)
    x, y = _data(dims, batch)
    buf = gp.init_params()
    key = jax.random.key(7)
    lg, gg = gp.loss_and_grads(buf, x, y, key, deterministic=True)
    lf, gf = fb.loss_and_grads(buf, x, y, key, deterministic=True)
    np.testing.assert_allclose(float(lg), float(lf), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gg), np.asarray(gf),
                               rtol=2e-4, atol=1e-6)


def test_1f1b_weighted_batch_matches():
    """Ragged-batch 0/1 weights flow through the manual backward seeds."""
    gp, fb = _pipes([12, 16, 10], 2, n_micro=2)
    x, y = _data([12, 16, 10], 8)
    w = jnp.asarray([1, 1, 1, 1, 1, 0, 0, 0], jnp.float32)
    buf = gp.init_params()
    key = jax.random.key(3)
    lg, gg = gp.loss_and_grads(buf, x, y, key, deterministic=True, weights=w)
    lf, gf = fb.loss_and_grads(buf, x, y, key, deterministic=True, weights=w)
    np.testing.assert_allclose(float(lg), float(lf), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gg), np.asarray(gf),
                               rtol=2e-4, atol=1e-6)


def test_1f1b_sgd_trajectory_matches_gpipe():
    from simple_distributed_machine_learning_tpu.train.optimizer import sgd
    from simple_distributed_machine_learning_tpu.train.step import (
        make_train_step,
    )

    gp, fb = _pipes([12, 16, 10], 2, n_micro=2)
    x, y = _data([12, 16, 10], 8)
    opt = sgd(0.1, 0.5)
    res = {}
    for name, pipe in (("gpipe", gp), ("1f1b", fb)):
        buf = pipe.init_params()
        state = opt.init(buf)
        step = make_train_step(pipe, opt)
        for i in range(4):
            # deterministic parity needs dropout-free stages; the MLP has
            # none, so the differing RNG streams do not matter
            buf, state, loss = step(buf, state, x, y,
                                    jax.random.fold_in(jax.random.key(0), i))
        res[name] = (np.asarray(buf), float(loss))
    np.testing.assert_allclose(res["gpipe"][0], res["1f1b"][0],
                               rtol=3e-4, atol=1e-6)
    np.testing.assert_allclose(res["gpipe"][1], res["1f1b"][1], rtol=1e-4)


def test_1f1b_moe_aux_stage_matches():
    """Dense-MoE stages return (y, aux): the aux seed (1/(M*n_data)) must
    reproduce the GPipe engine's unweighted aux mean exactly."""
    from simple_distributed_machine_learning_tpu.models.gpt import (
        GPTConfig,
        make_gpt_stages,
    )

    cfg = GPTConfig(vocab=32, seq_len=8, d_model=16, n_heads=2, n_layers=2,
                    n_experts=2, moe_top_k=1)
    key = jax.random.key(0)
    stages, wire, out = make_gpt_stages(key, cfg, 2)
    mesh = make_mesh(n_stages=2, n_data=1)
    gp = Pipeline(stages, mesh, wire, out, n_microbatches=2)
    fb = Pipeline(stages, mesh, wire, out, n_microbatches=2, schedule="1f1b")
    x = jax.random.randint(jax.random.key(1), (4, cfg.seq_len), 0,
                           cfg.vocab).astype(jnp.float32)
    y = jax.random.randint(jax.random.key(2), (4, cfg.seq_len), 0, cfg.vocab)
    buf = gp.init_params()
    lg, gg = gp.loss_and_grads(buf, x, y, key, deterministic=True)
    lf, gf = fb.loss_and_grads(buf, x, y, key, deterministic=True)
    np.testing.assert_allclose(float(lg), float(lf), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gg), np.asarray(gf),
                               rtol=5e-4, atol=2e-6)


@pytest.mark.parametrize("n_experts,top_k,n_data", [(2, 2, 1), (4, 2, 2)])
def test_1f1b_expert_parallel_matches_gpipe(n_experts, top_k, n_data):
    """1F1B x expert parallelism: EP-sharded MoE stages (2x all-to-all
    dispatch, grad-synced replicated leaves, nonzero aux weight) on an
    expert=2 mesh match the GPipe engine. The aux path is the crux: each
    stage's expert-invariant aux is pcast to varying inside the
    differentiated function so its transpose reassembles the full aux
    cotangent from the per-slot 1/n seeds (see onefb.py docstring)."""
    from simple_distributed_machine_learning_tpu.models.gpt import (
        GPTConfig,
        make_gpt_stages,
    )

    cfg = GPTConfig(vocab=32, seq_len=16, d_model=32, n_heads=2, n_layers=2,
                    n_experts=n_experts, moe_top_k=top_k,
                    n_expert_parallel=2, moe_aux_weight=0.01)
    stages, wd, od = make_gpt_stages(jax.random.key(0), cfg, 2)
    mesh = make_mesh(n_stages=2, n_data=n_data, n_expert=2)
    gp = Pipeline(stages, mesh, wd, od, n_microbatches=2)
    fb = Pipeline(stages, mesh, wd, od, n_microbatches=2, schedule="1f1b")
    x = jax.random.randint(jax.random.key(1), (8, cfg.seq_len), 0,
                           cfg.vocab).astype(jnp.float32)
    y = jax.random.randint(jax.random.key(2), (8, cfg.seq_len), 0, cfg.vocab)
    buf = gp.init_params()
    k = jax.random.key(7)
    lg, gg = gp.loss_and_grads(buf, x, y, k, deterministic=True)
    lf, gf = fb.loss_and_grads(buf, x, y, k, deterministic=True)
    np.testing.assert_allclose(float(lg), float(lf), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gg), np.asarray(gf),
                               rtol=5e-4, atol=2e-6)


def test_1f1b_memory_flat_in_microbatches():
    """The schedule's reason to exist: compiled temp memory is bounded by
    the topology S, not the microbatch count M (GPipe's grows with M
    because autodiff keeps every microbatch's residuals alive between the
    sweeps). Measured from XLA's own memory analysis, via the SAME helper
    benchmarks/onefb_memory.py records its artifact with."""
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "onefb_memory", os.path.join(repo, "benchmarks", "onefb_memory.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    temp_bytes = mod.temp_bytes

    g4, g32 = temp_bytes("gpipe", 4), temp_bytes("gpipe", 32)
    f4, f32 = temp_bytes("1f1b", 4), temp_bytes("1f1b", 32)
    assert g32 / g4 > 2.0, (g4, g32)       # GPipe residuals scale with M
    assert f32 / f4 < 1.3, (f4, f32)       # 1F1B stays topology-bounded


def test_cli_1f1b_end_to_end(capsys):
    from simple_distributed_machine_learning_tpu.cli import main

    main(["--rank", "0", "--world_size", "1", "--model", "mlp",
          "--mlp-dims", "784,32,10", "--stages", "2", "--epochs", "1",
          "--data-root", "/nonexistent", "--microbatches", "4",
          "--schedule", "1f1b"])
    out = capsys.readouterr().out
    assert "Test set: Average loss:" in out


def test_cli_1f1b_ep_end_to_end(capsys):
    from simple_distributed_machine_learning_tpu.cli import main

    main(["--rank", "0", "--world_size", "1", "--model", "gpt",
          "--stages", "2", "--epochs", "1", "--microbatches", "2",
          "--batch-size", "32", "--lr", "0.01", "--experts", "2",
          "--ep", "2", "--schedule", "1f1b"])
    out = capsys.readouterr().out
    assert "Test set: Average loss:" in out


def test_cli_1f1b_gpt(capsys):
    """GPT family under the 1F1B schedule through the CLI (per-token LM
    loss, dropout active, embedding/head stages vjp-recomputed)."""
    from simple_distributed_machine_learning_tpu.cli import main

    main(["--rank", "0", "--world_size", "1", "--model", "gpt",
          "--stages", "2", "--epochs", "1", "--microbatches", "2",
          "--batch-size", "32", "--lr", "0.01",
          "--schedule", "1f1b"])
    out = capsys.readouterr().out
    assert "Test set: Average loss:" in out


@pytest.mark.parametrize("attn", ["ring", "ulysses"])
def test_1f1b_seq_parallel_matches_gpipe(attn):
    """1F1B x sequence parallelism: token axis sharded over the seq axis,
    ring/Ulysses collectives inside the vjp-recomputed stages. Loss and
    packed-buffer grads must match the GPipe engine on the same sp mesh.

    Runs in a SUBPROCESS: stacking several 4-device seq-collective programs
    in one process can trip XLA:CPU's InProcessCommunicator rendezvous
    timeout on a loaded single-core machine (observed 'only 2 of 4 arrived'
    aborts); each config is timing-clean in a fresh interpreter."""
    import os
    import subprocess
    import sys

    code = f"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from simple_distributed_machine_learning_tpu.models.gpt import GPTConfig, make_gpt_stages
from simple_distributed_machine_learning_tpu.parallel.mesh import make_mesh
from simple_distributed_machine_learning_tpu.parallel.pipeline import Pipeline
from simple_distributed_machine_learning_tpu.train.optimizer import sgd
from simple_distributed_machine_learning_tpu.train.step import make_train_step

cfg = GPTConfig(vocab=32, seq_len=16, d_model=32, n_heads=4, n_layers=2,
                attn_impl={attn!r}, n_seq=2)
stages, wd, od = make_gpt_stages(jax.random.key(0), cfg, 2)
mesh = make_mesh(n_stages=2, n_data=1, n_seq=2)
gp = Pipeline(stages, mesh, wd, od, n_microbatches=2)
fb = Pipeline(stages, mesh, wd, od, n_microbatches=2, schedule="1f1b")
x = jax.random.randint(jax.random.key(1), (4, cfg.seq_len), 0,
                       cfg.vocab).astype(jnp.float32)
y = jax.random.randint(jax.random.key(2), (4, cfg.seq_len), 0, cfg.vocab)
buf = gp.init_params()
key = jax.random.key(7)
lg, gg = gp.loss_and_grads(buf, x, y, key, deterministic=True)
lf, gf = fb.loss_and_grads(buf, x, y, key, deterministic=True)
np.testing.assert_allclose(float(lg), float(lf), rtol=1e-5)
np.testing.assert_allclose(np.asarray(gg), np.asarray(gf),
                           rtol=5e-4, atol=2e-6)
# and a pp x dp x sp train step: loss falls
mesh2 = make_mesh(n_stages=2, n_data=2, n_seq=2)
pipe = Pipeline(stages, mesh2, wd, od, n_microbatches=2, schedule="1f1b")
buf2 = pipe.init_params()
opt = sgd(0.1, 0.5)
state = opt.init(buf2)
step = make_train_step(pipe, opt)
x8 = jax.random.randint(jax.random.key(1), (8, cfg.seq_len), 0,
                        cfg.vocab).astype(jnp.float32)
y8 = jax.random.randint(jax.random.key(2), (8, cfg.seq_len), 0, cfg.vocab)
losses = []
for i in range(4):
    buf2, state, loss = step(buf2, state, x8, y8,
                             jax.random.fold_in(jax.random.key(3), i))
    losses.append(float(loss))
assert losses[-1] < losses[0], losses
print("SEQ_1F1B_OK", losses[-1])
"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # retry on XLA:CPU's InProcessCommunicator rendezvous-timeout abort: on a
    # single-core machine the 4 device threads can starve each other past
    # the hard 40 s rendezvous deadline (thread-scheduling luck, not a
    # program-order divergence — see module docstring); the parity asserts
    # inside the script are what this test is for
    last = None
    for _ in range(3):
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=560, cwd=repo, env=env)
        last = r
        if r.returncode == 0 or "Termination timeout" not in r.stderr:
            break
    if last.returncode != 0 and "Termination timeout" in last.stderr:
        # every attempt died in the rendezvous, not in a numeric assert:
        # record the runtime artifact without failing CI (ulysses — whose
        # collective mix does not trip it — remains the hard gate)
        pytest.skip(f"XLA:CPU in-process rendezvous starvation ({attn})")
    assert last.returncode == 0, f"seq-1f1b {attn} failed:\n{last.stderr[-3000:]}"
    assert "SEQ_1F1B_OK" in last.stdout


def test_1f1b_tensor_parallel_matches_gpipe():
    """1F1B x tensor parallelism: Megatron column->row stages on a
    dp x pp x tp mesh. The wire is typed model-invariant so the pullback's
    implicit psum assembles per-shard partial cotangents; grads must be
    BIT-EXACT vs the GPipe engine (same collectives, same order)."""
    from simple_distributed_machine_learning_tpu.parallel.tensor import (
        make_mlp_tp_stages,
    )

    stages, wd, od = make_mlp_tp_stages(jax.random.key(0),
                                        [8, 16, 16, 16, 4], 2, 2)
    x = jax.random.normal(jax.random.key(1), (8, 8))
    y = jax.random.randint(jax.random.key(2), (8,), 0, 4)
    for nd in (1, 2):
        mesh = make_mesh(n_stages=2, n_model=2, n_data=nd)
        gp = Pipeline(stages, mesh, wd, od, n_microbatches=2)
        fb = Pipeline(stages, mesh, wd, od, n_microbatches=2,
                      schedule="1f1b")
        buf = gp.init_params()
        k = jax.random.key(7)
        lg, gg = gp.loss_and_grads(buf, x, y, k, deterministic=True)
        lf, gf = fb.loss_and_grads(buf, x, y, k, deterministic=True)
        np.testing.assert_allclose(float(lg), float(lf), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(gg), np.asarray(gf))


def test_1f1b_replicated_stages_on_tp_mesh_match_fused():
    """Plain (unsharded) stages on a model=2 mesh compute redundantly per
    slot; the rescaled pullback must give every slot the FULL gradient
    (slot grads identical and equal to the fused single-device grads).
    (Historically the GPipe engine's switch transpose rejected this case;
    its branch anchor now covers it too — tests/test_pipeline.py.)"""
    from simple_distributed_machine_learning_tpu.ops.losses import nll_loss
    from simple_distributed_machine_learning_tpu.parallel.pipeline import (
        fused_reference,
    )
    from simple_distributed_machine_learning_tpu.parallel.staging import (
        unpack_stage_params,
    )

    stages, wd, od = make_mlp_stages(jax.random.key(0), [8, 16, 4], 2)
    mesh = make_mesh(n_stages=2, n_model=2, n_data=1)
    fb = Pipeline(stages, mesh, wd, od, n_microbatches=2, schedule="1f1b")
    x = jax.random.normal(jax.random.key(1), (8, 8))
    y = jax.random.randint(jax.random.key(2), (8,), 0, 4)
    buf = fb.init_params()
    k = jax.random.key(7)
    fused = fused_reference(stages)

    def floss(b):
        ps = [unpack_stage_params(b[s, 0, 0], fb.metas[s]) for s in range(2)]
        return nll_loss(fused(ps, x, k, True), y, "mean")

    lF, gF = jax.value_and_grad(floss)(buf)
    lf, gf = fb.loss_and_grads(buf, x, y, k, deterministic=True)
    np.testing.assert_allclose(float(lF), float(lf), rtol=1e-6)
    gF, gf = np.asarray(gF), np.asarray(gf)
    for s in range(2):
        # every model slot holds the full gradient (the fused reference
        # only populated slot 0)
        for m in range(2):
            np.testing.assert_allclose(gf[s, m, 0], gF[s, 0, 0],
                                       rtol=1e-5, atol=1e-7)


def test_1f1b_mixed_tp_and_plain_stages_grad_check():
    """A TP pair stage feeding plain stages on one model=2 mesh: loss and
    every gradient leaf match a hand-fused single-device reference
    (GPipe's backward cannot run this stage mix — its switch transpose
    trips a vma mismatch — so the fused model is the ground truth).

    Replicated leaves INSIDE the sharded stage (the row bias, kept in sync
    by grad_sync) get the FULL cotangent on every slot, so they are
    compared against a reference that differentiates ONE shared copy."""
    from simple_distributed_machine_learning_tpu.ops.losses import nll_loss
    from simple_distributed_machine_learning_tpu.parallel.staging import (
        unpack_stage_params,
    )
    from simple_distributed_machine_learning_tpu.parallel.tensor import (
        make_mlp_tp_stages,
    )

    tps, twd, _ = make_mlp_tp_stages(jax.random.key(0),
                                     [8, 16, 16, 16, 4], 2, 2)
    ps, pwd, pod = make_mlp_stages(jax.random.key(3), [16, 12, 4], 2)
    mixed = [tps[0], ps[0], ps[1]]
    mesh = make_mesh(n_stages=3, n_model=2, n_data=1)
    gp = Pipeline(mixed, mesh, max(twd, pwd), pod, n_microbatches=2)
    fb = Pipeline(mixed, mesh, max(twd, pwd), pod, n_microbatches=2,
                  schedule="1f1b")
    x = jax.random.normal(jax.random.key(1), (8, 8))
    y = jax.random.randint(jax.random.key(2), (8,), 0, 4)
    buf = fb.init_params()
    k = jax.random.key(7)
    lg = gp.loss(buf, x, y, k, deterministic=True)   # fwd engines agree
    lf, gf = fb.loss_and_grads(buf, x, y, k, deterministic=True)
    np.testing.assert_allclose(float(lg), float(lf), rtol=1e-6)

    def floss(b):
        sh = [unpack_stage_params(b[0, m, 0], fb.metas[0]) for m in range(2)]
        acc = 0
        for m in range(2):
            p = sh[m]
            hm = jnp.maximum(x @ p["w1"]["w"] + p["w1"]["b"], 0)
            acc = acc + hm @ p["w2"]["w"]
        # ONE shared bias copy (slot 0): its gradient is the full cotangent
        h = jnp.maximum(acc + sh[0]["w2"]["b"], 0)
        for s in (1, 2):
            p = unpack_stage_params(b[s, 0, 0], fb.metas[s])
            h = fb.stages[s].apply(p, h.reshape(h.shape[0], -1), k, True)
        return nll_loss(h, y, "mean")

    lF, gF = jax.value_and_grad(floss)(buf)
    np.testing.assert_allclose(float(lF), float(lf), rtol=1e-6)
    gF, gfn = np.asarray(gF), np.asarray(gf)
    meta0 = fb.metas[0]
    ref0 = unpack_stage_params(jnp.asarray(gF[0, 0, 0]), meta0)
    for m in range(2):
        got = unpack_stage_params(jnp.asarray(gfn[0, m, 0]), meta0)
        ref_m = unpack_stage_params(jnp.asarray(gF[0, m, 0]), meta0)
        # sharded leaves: per-slot reference; the replicated bias: the
        # shared-copy (slot 0) reference on every slot
        np.testing.assert_allclose(got["w1"]["w"], ref_m["w1"]["w"],
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(got["w1"]["b"], ref_m["w1"]["b"],
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(got["w2"]["w"], ref_m["w2"]["w"],
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(got["w2"]["b"], ref0["w2"]["b"],
                                   rtol=1e-5, atol=1e-7)
    for s in (1, 2):
        for m in range(2):
            np.testing.assert_allclose(gfn[s, m, 0], gF[s, 0, 0],
                                       rtol=1e-5, atol=1e-7)
