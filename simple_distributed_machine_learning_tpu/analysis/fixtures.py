"""Seeded-defect fixtures: programs the analyzer MUST flag (and clean twins
it must not).

Each fixture is a tiny, deliberately broken distributed step built the same
way the engine builds real ones (``compat.shard_map`` over a named mesh) —
one per rule family, mirroring the ways a hand-written stage fn actually
goes wrong: a ring permutation that skips the wraparound hop, a
data-parallel update that forgets the gradient all-reduce, a collective
over a misspelled axis, a bf16 running sum, a buffer read after donation.

``tests/test_analysis.py`` asserts every defect fixture produces a finding
of its family and every ``defect=False`` twin analyzes clean; the CLI's
``--fixtures`` self-test mode re-runs the same contract from the command
line (non-zero exit when any fixture misbehaves), which is what the CI lint
job invokes.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

from simple_distributed_machine_learning_tpu.analysis import Report, analyze


@dataclasses.dataclass(frozen=True)
class Fixture:
    name: str
    family: str              # rule family expected in the findings
    defect: bool             # True: must flag; False: must be clean
    description: str
    build: Callable[[], Report]


def _devs(n: int):
    import jax
    devices = jax.devices()
    if len(devices) < n:
        raise SystemExit(
            f"fixture needs {n} devices, have {len(devices)} (run under "
            f"xla_force_host_platform_device_count)")
    import numpy as np
    return np.array(devices[:n])


def _mesh(n: int, axis: str = "data"):
    from jax.sharding import Mesh
    return Mesh(_devs(n), (axis,))


# -- ppermute-deadlock: a ring missing its wraparound hop ------------------

def partial_ppermute() -> Report:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from simple_distributed_machine_learning_tpu.parallel.compat import (
        shard_map,
    )

    mesh = _mesh(4)

    def shift(x):
        # BUG: [(j, j+1)] without the (3, 0) wraparound — not a bijection;
        # device 0 receives from nobody, device 3's send has no pair
        return lax.ppermute(x, "data", [(0, 1), (1, 2), (2, 3)])

    fn = jax.jit(shard_map(shift, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"), check_vma=False))
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    return analyze(fn, x, mesh=mesh, name="fixture:partial_ppermute")


# -- unreduced-gradient: data-parallel SGD missing the grad psum -----------

def _dp_sgd_report(sync: bool, name: str) -> Report:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from simple_distributed_machine_learning_tpu.parallel.compat import (
        shard_map,
    )

    mesh = _mesh(4)

    def step(w, x):
        def loss(w):
            return jnp.mean((x @ w) ** 2)
        g = jax.grad(loss)(w)
        if sync:
            g = lax.pmean(g, "data")
        # else BUG: each data shard applies only ITS batch shard's gradient
        # while the out_spec claims the replicas stay identical
        return w - 0.1 * g

    # check_vma=False: the engines this analyzer preflights run check-free
    # (old-jax compat), so the missing reduction must be caught HERE, not by
    # modern jax's own trace-time checker
    fn = jax.jit(shard_map(step, mesh=mesh, in_specs=(P(), P("data")),
                           out_specs=P(), check_vma=False))
    w = jax.ShapeDtypeStruct((16, 4), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    return analyze(fn, w, x, mesh=mesh, name=name)


def dropped_grad_sync() -> Report:
    return _dp_sgd_report(False, "fixture:dropped_grad_sync")


def clean_grad_sync() -> Report:
    return _dp_sgd_report(True, "fixture:clean_grad_sync")


# -- mesh-axis: collective over an axis the mesh does not bind -------------

def wrong_axis_name() -> Report:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from simple_distributed_machine_learning_tpu.parallel.compat import (
        shard_map,
    )

    mesh = _mesh(4)          # axes: ('data',)

    def reduce(x):
        # BUG: the mesh has no 'model' axis — a TP stage fn pasted into a
        # data-parallel launch
        return lax.psum(x, "model")

    fn = jax.jit(shard_map(reduce, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"), check_vma=False))
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    return analyze(fn, x, mesh=mesh, name="fixture:wrong_axis_name")


# -- dtype-drift: bf16 psum into a bf16 scan accumulator -------------------

def bf16_psum_accumulator() -> Report:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from simple_distributed_machine_learning_tpu.parallel.compat import (
        shard_map,
    )

    mesh = _mesh(4)

    def accumulate(xs):
        def body(acc, x_t):
            # BUG x2: the cross-device reduction runs in bf16, and the
            # running sum is carried in bf16 — increments vanish once the
            # sum outgrows 256x the step size
            return acc + jnp.sum(lax.psum(x_t, "data"), axis=0), ()

        acc0 = jnp.zeros((16,), jnp.bfloat16)
        acc, _ = lax.scan(body, acc0, xs)
        return acc

    fn = jax.jit(shard_map(accumulate, mesh=mesh, in_specs=P(None, "data"),
                           out_specs=P(), check_vma=False))
    xs = jax.ShapeDtypeStruct((32, 8, 16), jnp.bfloat16)
    return analyze(fn, xs, mesh=mesh, name="fixture:bf16_psum_accumulator")


# -- donation: buffer read after being donated -----------------------------

def read_after_donate() -> Report:
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0,))
    def update(buf, grads):
        return buf - 0.1 * grads

    def two_phase(buf, grads):
        new_buf = update(buf, grads)
        # BUG: the old buffer was donated to update() — its pages may
        # already back new_buf; this read is use-after-free on device
        drift = jnp.sum(new_buf - buf)
        return new_buf, drift

    b = jax.ShapeDtypeStruct((1024,), jnp.float32)
    g = jax.ShapeDtypeStruct((1024,), jnp.float32)
    return analyze(two_phase, b, g, name="fixture:read_after_donate")


# -- scatter-bounds: a block-table index past the pool ---------------------

def _tiny_serve():
    """One tiny GPT build + paged geometry shared by the serve fixtures."""
    import jax

    from simple_distributed_machine_learning_tpu.models.gpt import (
        GPTConfig,
        make_gpt_stages,
    )
    cfg = GPTConfig(vocab=16, seq_len=16, d_model=8, n_heads=2, n_layers=1)
    stages, _, _ = make_gpt_stages(jax.random.key(0), cfg, 1)
    return cfg, stages


def oob_block_table() -> Report:
    """The paged decode step handed a block-table contract that can reach
    one past the pool (what an engine WITHOUT slots.py's invariant-guarded
    tables could feed it): the K/V scatter provably lands outside
    ``n_blocks + 1`` — another request's blocks, silently."""
    import jax
    import numpy as np

    from simple_distributed_machine_learning_tpu.analysis import spec
    from simple_distributed_machine_learning_tpu.models.gpt import (
        make_paged_decode_step,
    )
    cfg, stages = _tiny_serve()
    S, ml, bs = 2, 12, 4
    NB, n_blocks = 3, 6
    step = make_paged_decode_step(stages, cfg, ml, bs)
    params = [jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), s.params)
        for s in stages]
    kc = (jax.ShapeDtypeStruct((n_blocks + 1, bs, 2 * 4), np.float32),)
    state = ((spec((S,), np.int32, 0, cfg.vocab - 1),
              jax.ShapeDtypeStruct((S, 2), np.uint32)),)
    return analyze(
        step, params, kc, kc, state,
        spec((S,), np.int32, 0, ml - 1),
        # BUG: entries may reach n_blocks + 1 — one past the last block
        spec((S, NB), np.int32, 0, n_blocks + 1),
        jax.ShapeDtypeStruct((S,), np.bool_),
        jax.ShapeDtypeStruct((S,), np.float32),
        spec((S,), np.int32, 0, cfg.vocab),
        jax.ShapeDtypeStruct((S,), np.float32),
        name="fixture:oob_block_table")


# -- donation v2: a CoW copy reading buffers the prefill donated -----------

def _cow_tick_report(threaded: bool, name: str) -> Report:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from simple_distributed_machine_learning_tpu.analysis import spec
    from simple_distributed_machine_learning_tpu.models.gpt import (
        make_paged_block_copy,
        make_paged_prefill_chunk,
    )
    from simple_distributed_machine_learning_tpu.models.serving import (
        SEAT_NONE,
    )
    cfg, stages = _tiny_serve()
    ml, bs, n_blocks = 12, 4, 6
    chunk = make_paged_prefill_chunk(stages, cfg, ml, bs)
    copy = make_paged_block_copy()

    def tick(params, kc, vc, state, tokens, p0, table, slot, seat, kd, t,
             k_, p_):
        kc2, vc2, _state2, tok, _kd2 = chunk(
            params, kc, vc, state, tokens, p0, table, slot, seat, kd, t,
            k_, p_)
        if threaded:
            kc3, vc3 = copy(kc2, vc2, jnp.int32(2), jnp.int32(1))
        else:
            # BUG: the copy reads the PRE-PREFILL pool buffers — the chunk
            # call already donated them, so their pages may back kc2/vc2
            # by now; this is the cross-program read-after-donate
            kc3, vc3 = copy(kc, vc, jnp.int32(2), jnp.int32(1))
        return kc3, vc3, tok

    params = [jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), s.params)
        for s in stages]
    kc = (jax.ShapeDtypeStruct((n_blocks + 1, bs, 2 * 4), np.float32),)
    S = 2
    state = ((spec((S,), np.int32, 0, cfg.vocab - 1),
              jax.ShapeDtypeStruct((S, 2), np.uint32)),)
    return analyze(
        tick, params, kc, kc, state,
        spec((1, 3), np.int32, 0, cfg.vocab - 1),
        spec((), np.int32, 0, ml - 4),
        spec((3,), np.int32, 0, n_blocks),
        spec((), np.int32, 0, S - 1),
        spec((), np.int32, SEAT_NONE, cfg.vocab - 1),
        jax.ShapeDtypeStruct((2,), np.uint32),
        jax.ShapeDtypeStruct((), np.float32),
        spec((), np.int32, 0, cfg.vocab),
        jax.ShapeDtypeStruct((), np.float32),
        name=name)


def cow_read_after_donate() -> Report:
    return _cow_tick_report(False, "fixture:cow_read_after_donate")


def clean_cow_tick() -> Report:
    return _cow_tick_report(True, "fixture:clean_cow_tick")


# -- retrace-explosion: a builder that forgets the build cache -------------

def unmemoized_retrace() -> Report:
    """A decode builder that reconstructs its jitted program on every call
    instead of routing through ``_DECODE_BUILD_CACHE`` — each engine/test
    would re-trace and re-compile an identical program."""
    import jax

    from simple_distributed_machine_learning_tpu.analysis.programs import (
        check_builder_memo,
    )

    def bad_make_decode():
        @jax.jit
        def decode(tok):
            return tok + 1
        return decode

    return Report(name="fixture:unmemoized_retrace",
                  findings=check_builder_memo("bad_make_decode",
                                              bad_make_decode))


# -- sharded-state: a ZeRO shard consumed without its gather ---------------

def _zero1_report(reduced: bool, name: str) -> Report:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from simple_distributed_machine_learning_tpu.analysis import spec
    from simple_distributed_machine_learning_tpu.parallel.compat import (
        shard_map,
    )

    mesh = _mesh(4)

    def step(w, m, g):
        # ZeRO-style: m is each device's OWN opt-state shard carried in a
        # replicated-shape buffer (the check_rep=False idiom — no in_spec
        # can express it, which is what analysis.spec(vary=...) declares)
        m2 = 0.9 * m + g
        if reduced:
            m2 = lax.pmean(m2, "data")   # gather/reduce before the update
        # else BUG: each device updates the replicated params with ITS
        # shard's momentum — params silently diverge across the axis
        return w - 0.1 * m2, m2

    fn = jax.jit(shard_map(step, mesh=mesh, in_specs=(P(), P(), P()),
                           out_specs=(P(), P()), check_vma=False))
    w = jax.ShapeDtypeStruct((16, 4), jnp.float32)
    g = jax.ShapeDtypeStruct((16, 4), jnp.float32)
    m = spec((16, 4), np.float32, vary=("data",))
    return analyze(fn, w, m, g, mesh=mesh, name=name)


def dropped_gather_before_use() -> Report:
    return _zero1_report(False, "fixture:dropped_gather_before_use")


def clean_gather_before_use() -> Report:
    return _zero1_report(True, "fixture:clean_gather_before_use")


# -- kernel-*: seeded Pallas kernel defects (analysis/kernels.py) ----------

def _paged_kernel_report(table_hi_slack: int, H: int,
                         dh: int, bs: int, name: str) -> Report:
    """Trace the REAL fused paged-attention kernel on synthetic shapes
    (``H`` heads of ``dh`` in a pool row) with a block-table contract
    reaching ``n_blocks + table_hi_slack`` — slack 0 is the slots.py
    invariant (clean), slack 1 is a table that can point one block past
    the pool (kernel-oob)."""
    import jax
    import numpy as np

    from simple_distributed_machine_learning_tpu.analysis import (
        analyze,
        spec,
    )
    from simple_distributed_machine_learning_tpu.ops.paged_attention import (
        paged_attention,
    )
    S, K, NB, n_blocks = 2, 1, 3, 5

    def attend(q, kc, vc, tables, qpos):
        return paged_attention(q, kc, vc, tables, qpos, block_size=bs)

    q = jax.ShapeDtypeStruct((S, H, K, dh), np.float32)
    kv = jax.ShapeDtypeStruct((n_blocks + 1, bs, H * dh), np.float32)
    return analyze(
        attend, q, kv, kv,
        spec((S, NB), np.int32, 0, n_blocks + table_hi_slack),
        spec((S, K), np.int32, 0, NB * bs - 1),
        name=name)


def kernel_oob_index_map() -> Report:
    """The fused kernel's own block copies fed a block-table contract that
    can reach one past the pool: a ``dma_start`` would read a window
    outside the backing buffer (``kernel-oob.dma-source``)."""
    return _paged_kernel_report(1, H=2, dh=8, bs=4,
                                name="fixture:kernel_oob_index_map")


def kernel_clean_paged() -> Report:
    """The same kernel under the slots.py table invariant — every copy
    and index map proves in bounds (must be fully clean)."""
    return _paged_kernel_report(0, H=2, dh=8, bs=4,
                                name="fixture:kernel_clean_paged")


def kernel_bad_tile() -> Report:
    """A pool row too narrow for the lanes at a TPU-realistic block size:
    ONE head of dh=4 in the 128-lane slot pads every K/V block 32x (the
    ROADMAP #2 small-head-dim hazard)."""
    return _paged_kernel_report(0, H=1, dh=4, bs=128,
                                name="fixture:kernel_bad_tile")


def kernel_rows_in_lanes_tile() -> Report:
    """The pool's layout at the same head dim and block size: all 32 heads
    of a position side by side fill the 128 lanes, nothing padded (must be
    clean)."""
    return _paged_kernel_report(0, H=32, dh=4, bs=128,
                                name="fixture:kernel_rows_in_lanes_tile")


def _grid_kernel_report(racing: bool, scratch_dtype, name: str) -> Report:
    """A hand-built pallas_call over a parallel grid axis — ``racing``
    collapses every cell's output window onto block 0 (what an autotuner
    mutation that drops the output index silently does)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from simple_distributed_machine_learning_tpu.analysis import analyze
    from simple_distributed_machine_learning_tpu.ops.flash_attention import (
        _compiler_params,
        pltpu,
    )

    def kern(x_ref, o_ref, acc_ref):
        acc_ref[...] = x_ref[...].astype(acc_ref.dtype) * 2
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    out_idx = (lambda i: (0, 0)) if racing else (lambda i: (i, 0))

    def fn(x):
        return pl.pallas_call(
            kern,
            grid=(4,),
            in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((1, 128), out_idx),
            out_shape=jax.ShapeDtypeStruct((4, 128), jnp.float32),
            scratch_shapes=[pltpu.VMEM((1, 128), scratch_dtype)],
            compiler_params=_compiler_params("parallel"),
            interpret=True,
        )(x)

    x = jax.ShapeDtypeStruct((4, 128), jnp.float32)
    return analyze(fn, x, name=name)


def kernel_grid_race() -> Report:
    import jax.numpy as jnp
    return _grid_kernel_report(True, jnp.float32,
                               "fixture:kernel_grid_race")


def kernel_clean_grid() -> Report:
    import jax.numpy as jnp
    return _grid_kernel_report(False, jnp.float32,
                               "fixture:kernel_clean_grid")


def kernel_f16_accumulator() -> Report:
    """An online-softmax-style scratch accumulator allocated in f16: state
    carried across grid iterations below f32 drifts from the dense path's
    einsum promotion (the bit-exactness contract)."""
    import jax.numpy as jnp
    return _grid_kernel_report(False, jnp.float16,
                               "fixture:kernel_f16_accumulator")


def kernel_f32_accumulator() -> Report:
    import jax.numpy as jnp
    return _grid_kernel_report(False, jnp.float32,
                               "fixture:kernel_f32_accumulator")


# -- clean twin: a full pipeline train step must produce zero findings -----

def clean_pipeline_step() -> Report:
    import jax

    from simple_distributed_machine_learning_tpu.analysis.preflight import (
        _abstract_batch,
        abstractify,
    )
    from simple_distributed_machine_learning_tpu.models.mlp import (
        make_mlp_stages,
    )
    from simple_distributed_machine_learning_tpu.parallel.mesh import make_mesh
    from simple_distributed_machine_learning_tpu.parallel.pipeline import (
        Pipeline,
    )
    from simple_distributed_machine_learning_tpu.train.optimizer import sgd
    from simple_distributed_machine_learning_tpu.train.step import (
        make_train_step,
    )

    stages, wire, out = make_mlp_stages(jax.random.key(0), [16, 16, 10], 2)
    mesh = make_mesh(n_stages=2, n_data=2, devices=jax.devices()[:4])
    pipe = Pipeline(stages, mesh, wire, out, n_microbatches=2)
    opt = sgd(0.1, momentum=0.5)
    buf = abstractify(pipe.init_params())
    state = jax.eval_shape(opt.init, buf)
    x, t, k = _abstract_batch(pipe, 8, 16)
    return analyze(make_train_step(pipe, opt), buf, state, x, t, k,
                   mesh=mesh, name="fixture:clean_pipeline_step")


# -- protocol: seeded defects in the abstract fleet model ------------------
#
# Each builder runs the bounded model checker over a fleet model carrying
# ONE protocol defect (a knob on ProtocolConfig that mirrors a real bug
# class in serve/fleet.py + serve/supervisor.py).  The defect fixtures must
# produce a `protocol.*` ERROR with a concrete counterexample trace; the
# clean twin explores the same transition system with the defect knobs off
# and must prove every invariant to its depth.  Pure stdlib — no jax.

def protocol_dropped_handoff() -> Report:
    from simple_distributed_machine_learning_tpu.analysis.protocol import (
        DROPPED_TOMBSTONE,
        check_protocol,
    )
    return check_protocol(DROPPED_TOMBSTONE)


def protocol_legacy_handoff_order() -> Report:
    from simple_distributed_machine_learning_tpu.analysis.protocol import (
        LEGACY_ORDER,
        check_protocol,
    )
    return check_protocol(LEGACY_ORDER)


def protocol_skipped_refund() -> Report:
    from simple_distributed_machine_learning_tpu.analysis.protocol import (
        SKIPPED_REFUND,
        check_protocol,
    )
    return check_protocol(SKIPPED_REFUND)


def protocol_ungated_boarding() -> Report:
    from simple_distributed_machine_learning_tpu.analysis.protocol import (
        UNGATED_BOARDING,
        check_protocol,
    )
    return check_protocol(UNGATED_BOARDING)


def protocol_clean_fleet() -> Report:
    from simple_distributed_machine_learning_tpu.analysis.protocol import (
        CLEAN,
        check_protocol,
    )
    return check_protocol(CLEAN)


FIXTURES: dict[str, Fixture] = {f.name: f for f in [
    Fixture("partial_ppermute", "ppermute-deadlock", True,
            "ring permutation missing its wraparound hop", partial_ppermute),
    Fixture("dropped_grad_sync", "unreduced-gradient", True,
            "data-parallel update without the gradient all-reduce",
            dropped_grad_sync),
    Fixture("wrong_axis_name", "mesh-axis", True,
            "psum over an axis the mesh does not bind", wrong_axis_name),
    Fixture("bf16_psum_accumulator", "dtype-drift", True,
            "bf16 cross-device reduction into a bf16 scan carry",
            bf16_psum_accumulator),
    Fixture("read_after_donate", "donation", True,
            "buffer read after being donated to a jitted update",
            read_after_donate),
    Fixture("oob_block_table", "scatter-bounds", True,
            "paged decode with a block-table contract one past the pool",
            oob_block_table),
    Fixture("cow_read_after_donate", "donation", True,
            "CoW block copy reading buffers the prefill chunk donated",
            cow_read_after_donate),
    Fixture("unmemoized_retrace", "retrace-explosion", True,
            "decode builder rebuilding its program outside the memo",
            unmemoized_retrace),
    Fixture("dropped_gather_before_use", "sharded-state", True,
            "ZeRO opt-state shard consumed without gather/reduce",
            dropped_gather_before_use),
    Fixture("kernel_oob_index_map", "kernel-oob", True,
            "fused paged kernel with a block-table contract past the pool",
            kernel_oob_index_map),
    Fixture("kernel_grid_race", "kernel-race", True,
            "pallas output index map collapsing a parallel grid axis",
            kernel_grid_race),
    Fixture("kernel_bad_tile", "kernel-tile", True,
            "small head dim in the 128-lane slot (32x Mosaic tile padding)",
            kernel_bad_tile),
    Fixture("kernel_f16_accumulator", "kernel-dtype-drift", True,
            "f16 scratch accumulator carried across grid iterations",
            kernel_f16_accumulator),
    Fixture("protocol_dropped_handoff", "protocol", True,
            "handoff sealed without journaling the source tombstone",
            protocol_dropped_handoff),
    Fixture("protocol_legacy_handoff_order", "protocol", True,
            "tombstone-then-copy handoff (pre-fix ordering, loses the rid)",
            protocol_legacy_handoff_order),
    Fixture("protocol_skipped_refund", "protocol", True,
            "shed/preempt path that never refunds the KV block refcounts",
            protocol_skipped_refund),
    Fixture("protocol_ungated_boarding", "protocol", True,
            "decode boarding not gated on the prefetch upload landing",
            protocol_ungated_boarding),
    Fixture("clean_grad_sync", "", False,
            "the dropped_grad_sync fixture with the pmean restored",
            clean_grad_sync),
    Fixture("clean_cow_tick", "", False,
            "the CoW tick with donated buffers threaded correctly",
            clean_cow_tick),
    Fixture("clean_gather_before_use", "", False,
            "the ZeRO update with the reduce restored (must be clean)",
            clean_gather_before_use),
    Fixture("clean_pipeline_step", "", False,
            "a 2-stage dp=2 GPipe train step (must be clean)",
            clean_pipeline_step),
    Fixture("kernel_clean_paged", "", False,
            "the fused paged kernel under the slots.py table invariant",
            kernel_clean_paged),
    Fixture("kernel_clean_grid", "", False,
            "the grid kernel with its output indexed by the parallel axis",
            kernel_clean_grid),
    Fixture("kernel_rows_in_lanes_tile", "", False,
            "the small-head-dim kernel over rows that fill the lanes",
            kernel_rows_in_lanes_tile),
    Fixture("kernel_f32_accumulator", "", False,
            "the grid kernel with its scratch accumulator in f32",
            kernel_f32_accumulator),
    Fixture("protocol_clean_fleet", "", False,
            "the 2-pool fleet model with every defect knob off (proves)",
            protocol_clean_fleet),
]}


def _replay_exported_drill() -> tuple[bool, list[str]]:
    """Anti-vacuous gate for the model checker's counterexample export: the
    FaultPlan exported from the dropped-tombstone model's double-serve
    counterexample must replay as a REAL failure (more tokens streamed than
    the request asked for) on a live 3-replica disaggregated fleet carrying
    the same seeded defect (``log_handoff`` suppressed), and the intact
    twin must stay exactly-once under the identical kill schedule.  Without
    this, a model bug that exports unparseable or toothless schedules would
    pass every purely-abstract check."""
    import os
    import tempfile

    import jax
    import numpy as np

    from simple_distributed_machine_learning_tpu.analysis.protocol import (
        DROPPED_TOMBSTONE,
        check_protocol,
        export_fault_plan,
    )
    from simple_distributed_machine_learning_tpu.models.gpt import (
        GPTConfig,
        make_gpt_stages,
    )
    from simple_distributed_machine_learning_tpu.resilience import faults
    from simple_distributed_machine_learning_tpu.serve import (
        RequestJournal,
        ServeFleet,
        engine_factory,
    )
    from simple_distributed_machine_learning_tpu.serve.request import DONE

    lines = []
    report = check_protocol(DROPPED_TOMBSTONE)
    viol = next((v for v in report.exploration.violations
                 if v.invariant == "double-serve"), None)
    if viol is None:
        return False, ["== exported-drill replay: model found no "
                       "double-serve counterexample -> FAILED"]
    plan_text, note = export_fault_plan(viol)
    if plan_text is None:
        return False, [f"== exported-drill replay: counterexample not "
                       f"expressible as a FaultPlan ({note}) -> FAILED"]
    lines.append(f"  exported plan: {plan_text}")

    cfg = GPTConfig(vocab=32, seq_len=48, d_model=32, n_heads=2, n_layers=2)
    stages = make_gpt_stages(jax.random.key(0), cfg, 2)[0]
    prompt = np.asarray(
        jax.random.randint(jax.random.key(7), (4,), 0, cfg.vocab), np.int32)
    max_new = 3

    def run(drop_tombstone: bool) -> int:
        """Drive the model's scenario (submit -> prefill -> handoff ->
        DONE), then install the exported plan and keep ticking; returns
        total tokens streamed to the caller over the whole run."""
        faults.uninstall()
        orig = RequestJournal.log_handoff
        if drop_tombstone:
            RequestJournal.log_handoff = lambda self, **kw: None
        try:
            with tempfile.TemporaryDirectory() as td:
                fleet = ServeFleet(
                    engine_factory(stages, cfg, n_slots=2, block_size=4,
                                   prefill_chunk=3),
                    os.path.join(td, "j"), n_replicas=3,
                    prefill_replicas=1, journal_sync=False)
                got = []
                h = fleet.submit(prompt, max_new_tokens=max_new, seed=11,
                                 on_token=lambda req, tok: got.append(tok))
                for _ in range(60):
                    fleet.step()
                    if h.state == DONE and fleet.handoffs >= 1:
                        break
                faults.install(faults.FaultPlan.parse(plan_text))
                for _ in range(len(plan_text.split(";")) + 1):
                    fleet.step()
                faults.uninstall()
                for _ in range(60):
                    if h.state == DONE:
                        break
                    fleet.step()
                fleet.close()
                return len(got)
        finally:
            RequestJournal.log_handoff = orig
            faults.uninstall()

    defect_tokens = run(drop_tombstone=True)
    clean_tokens = run(drop_tombstone=False)
    defect_good = defect_tokens > max_new
    clean_good = clean_tokens == max_new
    lines.append(f"  defect twin (log_handoff dropped): streamed "
                 f"{defect_tokens}/{max_new} tokens -> "
                 f"{'double-served as predicted' if defect_good else 'NO REAL FAILURE (vacuous export)'}")  # noqa: E501
    lines.append(f"  clean twin (tombstone intact):     streamed "
                 f"{clean_tokens}/{max_new} tokens -> "
                 f"{'exactly-once' if clean_good else 'UNEXPECTED FAILURE'}")
    ok = defect_good and clean_good
    lines.insert(0, f"== exported-drill replay: counterexample must fail a "
                    f"real fleet -> {'OK' if ok else 'FAILED'}")
    return ok, lines


def self_test() -> tuple[bool, str]:
    """Run every fixture against its contract, plus the chaos drill
    coverage lint (``resilience.faults.drill_coverage``: every registered
    fault kind x site fired by at least one test/CI drill). Returns
    (ok, report_text) — the CLI ``--fixtures`` mode prints the text and
    exits 0 iff ok."""
    lines = []
    ok = True
    for fx in FIXTURES.values():
        report = fx.build()
        flagged = not report.ok(fail_on="warning")
        family_hit = (not fx.defect or
                      any(f.family == fx.family for f in report.findings))
        good = (flagged and family_hit) if fx.defect else not flagged
        ok = ok and good
        verdict = "OK" if good else "FIXTURE CONTRACT VIOLATED"
        want = (f"must flag [{fx.family}]" if fx.defect else "must be clean")
        lines.append(f"== {fx.name}: {want} -> {verdict}")
        lines.append(report.format(costs=False))
    from simple_distributed_machine_learning_tpu.resilience.faults import (
        drill_coverage,
    )
    gaps = drill_coverage()
    verdict = "OK" if not gaps else "COVERAGE GAPS"
    lines.append(f"== fault drill coverage: every kind x site fired "
                 f"-> {verdict}")
    for g in gaps:
        lines.append(f"  MISSING: {g}")
        ok = False
    replay_ok, replay_lines = _replay_exported_drill()
    ok = ok and replay_ok
    lines.extend(replay_lines)
    return ok, "\n".join(lines)
