"""Static analyzer (analysis/): seeded defects flag, shipping steps pass.

Three contracts pin the preflight gate:

1. every seeded-defect fixture (one per rule family) produces a finding of
   its family — the analyzer can actually see the defect classes it claims;
2. the EXACT train/eval steps of every shipping model/schedule combination
   analyze clean — the gate never cries wolf on a good launch;
3. the PR-2 caveat is machine-checked: the branch-divergent ring shape that
   deadlocks old XLA:CPU (ring attention inside a >= 2-stage pipeline's
   stage switch) is flagged, and the 1-stage CPU fallback analyzes clean.

Everything here is trace-only (ShapeDtypeStructs): no collective ever runs,
which is the point — the deadlock shape is ANALYZED on the same CPU backend
it would hang.
"""

import jax
import pytest

from simple_distributed_machine_learning_tpu.analysis import (
    Severity,
    abstractify,
    analyze,
)
from simple_distributed_machine_learning_tpu.analysis.fixtures import FIXTURES
from simple_distributed_machine_learning_tpu.analysis.preflight import (
    validate_tp_overlap,
)
from simple_distributed_machine_learning_tpu.parallel.mesh import make_mesh
from simple_distributed_machine_learning_tpu.parallel.pipeline import Pipeline
from simple_distributed_machine_learning_tpu.train.optimizer import sgd
from simple_distributed_machine_learning_tpu.train.step import (
    make_eval_step,
    make_train_step,
)


def _abstract(pipe, batch, in_dim):
    import numpy as np
    x = jax.ShapeDtypeStruct((batch, in_dim), np.float32)
    t = jax.ShapeDtypeStruct((batch,) + pipe.out_shape[:-1], np.int32)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
    return x, t, key


def _train_report(pipe, batch, in_dim, opt=None):
    opt = opt or sgd(0.1, momentum=0.5)
    buf = abstractify(pipe.init_params())
    state = jax.eval_shape(opt.init, buf)
    x, t, key = _abstract(pipe, batch, in_dim)
    return analyze(make_train_step(pipe, opt), buf, state, x, t, key,
                   mesh=pipe.mesh)


# ---- 1. seeded defects MUST flag ----------------------------------------

@pytest.mark.parametrize("name", [
    "partial_ppermute", "dropped_grad_sync", "wrong_axis_name",
    "bf16_psum_accumulator", "read_after_donate",
    "oob_block_table", "cow_read_after_donate", "unmemoized_retrace",
    "dropped_gather_before_use",
    "kernel_oob_index_map", "kernel_grid_race", "kernel_bad_tile",
    "kernel_f16_accumulator",
])
def test_seeded_defect_is_flagged(name):
    fx = FIXTURES[name]
    assert fx.defect
    report = fx.build()
    fams = {f.family for f in report.findings}
    assert fx.family in fams, (
        f"{name}: expected a {fx.family} finding, got {report.format()}")
    # the CLI's fixture mode exits non-zero on these (fail_on=warning)
    assert not report.ok(fail_on="warning")


def test_seeded_defect_severities():
    # the four hard defects are ERRORs (they gate --lint preflights);
    # dtype drift is a WARNING (a deliberate bf16 run must still launch)
    assert FIXTURES["partial_ppermute"].build().errors
    assert FIXTURES["dropped_grad_sync"].build().errors
    assert FIXTURES["wrong_axis_name"].build().errors
    assert FIXTURES["read_after_donate"].build().errors
    drift = FIXTURES["bf16_psum_accumulator"].build()
    assert not drift.errors and drift.warnings
    rules = {f.rule for f in drift.findings}
    assert "dtype-drift.low-precision-reduction" in rules
    assert "dtype-drift.low-precision-carry" in rules


def test_new_family_defect_severities():
    # the serve-path defect classes are all ERRORs: silent K/V corruption,
    # device use-after-free and unmemoized recompiles must gate --lint
    for name in ("oob_block_table", "cow_read_after_donate",
                 "unmemoized_retrace", "dropped_gather_before_use"):
        assert FIXTURES[name].build().errors, name
    # inside the kernel box: a provably-escaping index map and a parallel
    # write race are ERRORs; tiling waste and a sub-f32 scratch accumulator
    # are WARNINGs (real, but an autotuner candidate may accept them)
    for name in ("kernel_oob_index_map", "kernel_grid_race"):
        assert FIXTURES[name].build().errors, name
    for name in ("kernel_bad_tile", "kernel_f16_accumulator"):
        report = FIXTURES[name].build()
        assert not report.errors and report.warnings, report.format()


def test_clean_fixtures_pass():
    for name in ("clean_grad_sync", "clean_pipeline_step",
                 "clean_cow_tick", "clean_gather_before_use",
                 "kernel_clean_paged", "kernel_clean_grid",
                 "kernel_rows_in_lanes_tile", "kernel_f32_accumulator"):
        report = FIXTURES[name].build()
        assert report.ok(fail_on="warning"), report.format()


def test_sharded_state_vary_threads_through_cond_and_while():
    """Declared ``vary=`` contracts must survive cond/switch and while
    sub-jaxpr boundaries, whose invars are NOT arity-identical to the
    eqn's (branches drop the predicate; while's two jaxprs each see their
    own consts + the carry). The dropped-gather defect wrapped in a
    lax.cond used to analyze vacuously clean — a certified-clean report
    for a silently-diverging-params program."""
    import numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from simple_distributed_machine_learning_tpu.analysis import spec
    from simple_distributed_machine_learning_tpu.analysis.fixtures import (
        _mesh,
    )
    from simple_distributed_machine_learning_tpu.parallel.compat import (
        shard_map,
    )

    mesh = _mesh(4)

    def _inner(reduced):
        def step(w, m, g):
            m2 = 0.9 * m + g
            if reduced:
                m2 = lax.pmean(m2, "data")
            return w - 0.1 * m2, m2
        return shard_map(step, mesh=mesh, in_specs=(P(), P(), P()),
                         out_specs=(P(), P()), check_vma=False)

    w = jax.ShapeDtypeStruct((16, 4), jax.numpy.float32)
    g = jax.ShapeDtypeStruct((16, 4), jax.numpy.float32)
    m = spec((16, 4), np.float32, vary=("data",))
    pred = jax.ShapeDtypeStruct((), jax.numpy.bool_)

    def behind_cond(reduced):
        inner = _inner(reduced)
        return lambda p, w, m, g: lax.cond(
            p, inner, lambda w, m, g: (w, m), w, m, g)

    def behind_while(w_, m_, g_):
        inner = _inner(False)
        def body(c):
            i, cw, cm = c
            nw, nm = inner(cw, cm, g_)
            return i + 1, nw, nm
        return lax.while_loop(lambda c: c[0] < 3, body, (0, w_, m_))

    def rules(report):
        return {f.rule for f in report.findings}

    assert "sharded-state.missing-gather" in rules(
        analyze(behind_cond(False), pred, w, m, g, mesh=mesh))
    assert "sharded-state.missing-gather" in rules(
        analyze(behind_while, w, m, g, mesh=mesh))
    # the reduced twin stays clean through the same boundary — threading
    # must not invent variance the pmean already retired
    assert not any("sharded-state" in r for r in rules(
        analyze(behind_cond(True), pred, w, m, g, mesh=mesh)))


# ---- 2. shipping model/schedule combos analyze clean --------------------

def _mlp_pipe(schedule, n_stages=2, n_data=2, n_model=1):
    if n_model > 1:
        from simple_distributed_machine_learning_tpu.parallel.tensor import (
            make_mlp_tp_stages,
        )
        dims = [16] * (2 * n_stages) + [10]
        stages, wire, out = make_mlp_tp_stages(jax.random.key(0), dims,
                                               n_stages, n_model)
    else:
        from simple_distributed_machine_learning_tpu.models.mlp import (
            make_mlp_stages,
        )
        stages, wire, out = make_mlp_stages(jax.random.key(0),
                                            [16] * n_stages + [10], n_stages)
    mesh = make_mesh(n_stages=n_stages, n_data=n_data, n_model=n_model,
                     devices=jax.devices()[:n_stages * n_data * n_model])
    return Pipeline(stages, mesh, wire, out, n_microbatches=2,
                    schedule=schedule)


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
@pytest.mark.parametrize("n_data", [1, 2])
def test_mlp_pipeline_step_clean(schedule, n_data):
    pipe = _mlp_pipe(schedule, n_data=n_data)
    report = _train_report(pipe, batch=4 * n_data, in_dim=16)
    assert report.ok(fail_on="warning"), report.format()


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_tp_pipeline_step_clean(schedule):
    # dp x pp x tp: the full 3D mesh of the 8-device dryrun
    pipe = _mlp_pipe(schedule, n_stages=2, n_data=2, n_model=2)
    report = _train_report(pipe, batch=8, in_dim=16)
    assert report.ok(fail_on="warning"), report.format()


def test_lenet_pipeline_step_clean():
    from simple_distributed_machine_learning_tpu.models.lenet import (
        make_lenet_stages,
    )
    stages, wire, out = make_lenet_stages(jax.random.key(0), 2)
    mesh = make_mesh(n_stages=2, n_data=2, devices=jax.devices()[:4])
    pipe = Pipeline(stages, mesh, wire, out, n_microbatches=2)
    opt = sgd(0.1, momentum=0.5)
    import numpy as np
    buf = abstractify(pipe.init_params())
    state = jax.eval_shape(opt.init, buf)
    x = jax.ShapeDtypeStruct((8, 28, 28, 1), np.float32)
    t = jax.ShapeDtypeStruct((8,), np.int32)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
    report = analyze(make_train_step(pipe, opt), buf, state, x, t, key,
                     mesh=mesh)
    assert report.ok(fail_on="warning"), report.format()


def _gpt_pipe(schedule="gpipe", n_stages=2, n_seq=1, attn="dense"):
    from simple_distributed_machine_learning_tpu.models.gpt import (
        GPTConfig,
        make_gpt_stages,
    )
    cfg = GPTConfig(vocab=16, seq_len=8, d_model=16, n_heads=2, n_layers=2,
                    attn_impl=attn, n_seq=n_seq)
    stages, wire, out = make_gpt_stages(jax.random.key(0), cfg, n_stages)
    mesh = make_mesh(n_stages=n_stages, n_data=1, n_seq=n_seq,
                     devices=jax.devices()[:n_stages * n_seq])
    return Pipeline(stages, mesh, wire, out, n_microbatches=2,
                    schedule=schedule), cfg


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_gpt_pipeline_step_clean(schedule):
    pipe, cfg = _gpt_pipe(schedule)
    report = _train_report(pipe, batch=4, in_dim=cfg.seq_len)
    assert report.ok(fail_on="warning"), report.format()


def test_eval_step_clean():
    import numpy as np
    pipe = _mlp_pipe("gpipe", n_data=2)
    buf = abstractify(pipe.init_params())
    x, t, key = _abstract(pipe, 8, 16)
    n_valid = jax.ShapeDtypeStruct((), np.int32)
    report = analyze(make_eval_step(pipe), buf, x, t, key, n_valid,
                     mesh=pipe.mesh)
    assert report.ok(fail_on="warning"), report.format()


def test_cost_report_ranks_dp_grad_allreduce():
    # the dominant collective of a dp=2 train step is the gradient psum the
    # shard_map transpose inserts — the cost table must surface it
    pipe = _mlp_pipe("gpipe", n_data=2)
    report = _train_report(pipe, batch=8, in_dim=16)
    assert report.costs, "cost table empty"
    top = max(report.costs, key=lambda c: c.total_bytes)
    assert top.prim == "psum" and "data" in top.axes


# ---- 3. the PR-2 caveat, machine-checked --------------------------------

def test_ring_in_divergent_branches_flagged():
    """Ring attention inside a >= 2-stage pipeline's stage switch is the
    exact shape that deadlocks old XLA:CPU's global collective-permute
    rendezvous (PR-2 caveat): the analyzer must flag it — as a WARNING
    (portability hazard), not an ERROR (it is correct on TPU ICI)."""
    pipe, cfg = _gpt_pipe(n_stages=2, n_seq=2, attn="ring")
    report = _train_report(pipe, batch=4, in_dim=cfg.seq_len // 2)
    rules = {f.rule for f in report.findings}
    assert "ppermute-deadlock.ring-in-branch" in rules, report.format()
    assert report.ok(fail_on="error"), report.format()


def test_ring_one_stage_fallback_clean():
    """The 1-stage CPU fallback (what cli/tests run on old jax) keeps the
    ring out of any stage switch: must analyze clean."""
    pipe, cfg = _gpt_pipe(n_stages=1, n_seq=2, attn="ring")
    report = _train_report(pipe, batch=4, in_dim=cfg.seq_len // 2)
    deadlock = [f for f in report.findings
                if f.family == "ppermute-deadlock"]
    assert not deadlock, report.format()
    assert report.ok(), report.format()


# ---- preflight spec validation (bench --tp/--overlap routing) -----------

def test_validate_tp_overlap_divisibility():
    from simple_distributed_machine_learning_tpu.models.gpt import GPTConfig
    cfg = GPTConfig(vocab=16, seq_len=8, d_model=16, n_heads=4, n_layers=1)
    errors, _ = validate_tp_overlap(3, "none", 8, cfg)
    assert any("n_heads" in e for e in errors)
    assert any("hidden width" in e for e in errors)
    errors, _ = validate_tp_overlap(16, "none", 8, cfg)
    assert any("devices" in e for e in errors)
    errors, _ = validate_tp_overlap(1, "ring", 8, cfg)
    assert any("ring" in e for e in errors)
    errors, warns = validate_tp_overlap(2, "ring", 8, cfg,
                                        batch=4, n_micro=1)
    assert not errors and not warns
    # d_model=16 splits over tp=2; a tp that does not divide it only
    # degrades the ring to the monolithic psum: warning, not error
    cfg2 = GPTConfig(vocab=16, seq_len=10, d_model=20, n_heads=4,
                     n_layers=1, mlp_ratio=2)
    errors, warns = validate_tp_overlap(4, "ring", 8, cfg2,
                                        batch=6, n_micro=2)
    assert not errors
    assert any("falls back" in w for w in warns)


def test_validate_clean_spec_passes():
    from simple_distributed_machine_learning_tpu.models.gpt import GPTConfig
    cfg = GPTConfig(vocab=16, seq_len=8, d_model=16, n_heads=4, n_layers=1)
    errors, warns = validate_tp_overlap(2, "none", 8, cfg)
    assert not errors and not warns


# ---- CLI exit codes (in-process) ----------------------------------------

def test_cli_fixture_exit_codes():
    from simple_distributed_machine_learning_tpu.analysis.__main__ import main
    assert main(["--fixture", "dropped_grad_sync"]) == 1
    assert main(["--fixture", "clean_grad_sync"]) == 0
    assert main(["--list"]) == 0


def test_cli_dryrun_clean():
    # the CI lint gate's per-config invocation, in-process on the 8 virtual
    # devices the suite already runs under
    from simple_distributed_machine_learning_tpu.analysis.__main__ import main
    assert main(["--dryrun", "2"]) == 0


def test_severity_ordering_and_families():
    assert Severity.ERROR > Severity.WARNING > Severity.INFO
    fams = {fx.family for fx in FIXTURES.values() if fx.defect}
    assert fams == {"ppermute-deadlock", "unreduced-gradient", "mesh-axis",
                    "dtype-drift", "donation", "scatter-bounds",
                    "retrace-explosion", "sharded-state",
                    "kernel-oob", "kernel-race", "kernel-tile",
                    "kernel-dtype-drift", "protocol"}
