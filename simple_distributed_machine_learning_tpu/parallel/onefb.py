"""1F1B pipeline schedule: alternating forward/backward with O(S) memory.

The GPipe engine (``pipeline.py``) differentiates its scanned forward with
``jax.grad``: XLA runs the whole forward sweep first, so every one of the
``M`` microbatches' residuals is alive when the backward sweep starts —
activation memory grows with the BATCH. This module hand-schedules the
classic one-forward-one-backward interleave instead (PipeDream-flush /
Megatron's non-interleaved 1F1B): at tick ``t`` the device holding stage
``s``

- runs the FORWARD of microbatch ``m_f = t - s`` (GPipe fill order), and
- runs the BACKWARD of microbatch ``m_b = t - 2(S-1) + s - 1`` — the
  microbatch whose output-cotangent just arrived on the reverse ring,

so forwards and backwards overlap in steady state and a stage keeps at most
``2(S - s) - 1 <= 2S - 1`` microbatch INPUTS in flight — bounded by the
topology ``S``, independent of ``M``. Activations themselves are never
stored: the backward tick recomputes the stage forward from its saved input
under ``jax.vjp`` (deterministic RNG replay keyed by microbatch), exactly
the activation-recompute trade the deepest pipelines run.

Both hops ride ``lax.ppermute`` rings in opposite directions inside one
``lax.scan`` — one compiled SPMD program, like the GPipe engine; gradients
come out packed in the param buffer's ``[S, 1, 1, P]`` layout, ready for
the owner-local optimizer update (no autodiff through the scan at all).

Worked timeline, S=2 stages, M=3 microbatches (T = M + 2S - 1 = 6 ticks;
``Fm`` = forward of microbatch m, ``Bm`` = backward; stage0: m_f = t,
m_b = t - 3; stage1: m_f = t - 1, m_b = t - 2):

    tick     0     1     2        3        4     5
    stage0   F0    F1    F2       B0       B1    B2
    stage1   .     F0    F1+B0    F2+B1    B2    .

stage1 runs a forward and a backward in the same tick (the steady-state
interleave; middle stages of deeper pipelines do the same); stage0's
backward lags one extra tick because the cotangent crosses the reverse
ring. Each saved input lives at most 2S-1 ticks.

Scope: ALL five mesh axes compose — stage x data x seq x model x expert.
Sequence parallelism: ring / Ulysses collectives inside stage applies
transpose under the vjp; the pullback's implicit psum extends to the seq
axis since params are seq-invariant. Tensor parallelism: wires are typed
model-INVARIANT, so a TP stage's pullback assembles its per-shard partial
input cotangents via the same implicit psum, while replicated stages'
pullbacks are rescaled by 1/n_model (they would otherwise sum n identical
full cotangents) — bit-exact vs the GPipe engine on full-TP pipelines.
Expert parallelism uses the opposite, GPipe-native discipline: wires stay
expert-VARYING (each slot carries its own chain's cotangent), objective
seeds divide by n_expert, expert-replicated stages' params get grad_sync
wraps, and — crucially — each stage's aux loss is pcast to expert-varying
INSIDE the differentiated function before entering the objective, so the
pcast transpose reassembles the full aux cotangent from the n 1/n seeds
(without it, a non-last MoE stage's expert-invariant aux node starves by
1/n_expert; the last stage was saved only by its varying num term forcing
the same pcast). The reference has no analogue of any of this — its
two-stage "schedule" is one blocking RPC per batch with zero overlap
(``simple_distributed.py:49``, SURVEY §3.3).

CPU-backend caveat (virtual-device testing only): with seq parallelism the
per-tick collective density is high enough that XLA:CPU's in-process
rendezvous (hard 40 s deadline per collective) can abort under thread
starvation on few-core machines — a runtime artifact, not a collective-
order divergence (each device's collective sequence is identical to the
GPipe engine's, which runs the same ring/Ulysses ops in the same
stage-dispatched branches). TPU lowers these to ICI collective-permutes
with no thread rendezvous. tests/test_onefb.py isolates and retries
accordingly.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from simple_distributed_machine_learning_tpu.parallel.mesh import (
    DATA_AXIS,
    EXPERT_AXIS,
    MODEL_AXIS,
    SEQ_AXIS,
    STAGE_AXIS,
)
from simple_distributed_machine_learning_tpu.parallel.staging import (
    pack_stage_grads,
    unpack_stage_params,
    wire_decode,
    wire_encode,
)


def build_1f1b_fn(pipe, deterministic: bool) -> Callable:
    """Build the shard_mapped 1F1B loss-and-grads function for ``pipe``.

    Returns ``fn(buf, x_mb, tgt_mb, w_mb, key) -> (loss, grads)`` with
    ``grads`` shaped/sharded like the packed param buffer. Inputs are the
    ``Pipeline._prep_inputs`` layout.
    """
    # (seq-parallel + classifier out_shape is rejected by Pipeline.__init__
    # before any schedule is built — no separate guard here)
    if pipe.n_stages < 2:
        raise ValueError("1F1B needs >= 2 pipeline stages")

    S = pipe.n_stages
    M = pipe.n_microbatches
    # stage s has m_f - m_b = 2(S-1) - 2s + 1 <= 2S-1 microbatches in flight
    # INCLUSIVE of the one written and the one read this tick — depth 2S
    # keeps the slots distinct (2S-1 would alias stage 0's write and read)
    D = 2 * S
    T = M + 2 * S - 1              # ticks: last bwd is stage 0's m=M-1
    wire_dim = pipe.wire_dim
    out_shape = pipe.out_shape
    metas = list(pipe.metas)
    applies = [s.apply for s in pipe.stages]
    in_shapes = [s.in_shape for s in pipe.stages]
    compute_dtype = pipe.compute_dtype
    n_data = pipe.n_data
    from simple_distributed_machine_learning_tpu.ops.losses import nll_loss
    from simple_distributed_machine_learning_tpu.parallel.compat import (
        pvary_to as _pvary_to,
        shard_map as _shard_map,
        vma_of as _vma_of,
    )

    # sequence parallelism: the token axis of the wire, targets and logits
    # is sharded over the seq axis (stage in_shapes/wire_dim are per-shard,
    # the Pipeline convention); stage applies do their own cross-token
    # mixing via ring/Ulysses collectives, which jax.vjp transposes
    seq_on = pipe.n_seq > 1
    tp_on = pipe.n_model > 1
    ep_on = pipe.n_expert > 1
    n_model = pipe.n_model
    n_expert = pipe.n_expert
    # which stages carry REAL tensor / expert shards (vs redundant replicas)
    model_sharded = [s.shards is not None for s in pipe.stages]
    expert_sharded = [s.expert_shards is not None for s in pipe.stages]
    # the mesh always carries all five named axes (size 1 when unused); the
    # param row varies over stage/model/expert via its sharding, inputs over
    # data (and seq when the token axis is sharded) — match the GPipe
    # engine's vma discipline exactly
    vary_axes = (DATA_AXIS, STAGE_AXIS, MODEL_AXIS) + (
        (SEQ_AXIS,) if seq_on else ()) + (
        (EXPERT_AXIS,) if pipe._has_expert else ())
    # grad rows come out of the pullback invariant over data AND seq (the
    # implicit psums — params are invariant over both)
    vary_axes_nodata = tuple(a for a in vary_axes
                             if a not in (DATA_AXIS, SEQ_AXIS))
    # tensor parallelism: activations on the wire are logically REPLICATED
    # over the model axis (a TP stage ends each column->row pair in its own
    # psum; a replicated stage computes redundantly). Typing the wires
    # model-INVARIANT makes the vjp pullback's implicit psum over 'model'
    # assemble the true input cotangent for TP stages (sum of per-shard
    # partials); replicated stages' pullbacks then overcount by n_model
    # (n identical full cotangents summed) and are rescaled below.
    #
    # expert parallelism uses the OPPOSITE discipline — GPipe's: wires stay
    # expert-VARYING (each slot carries its own chain's cotangent), every
    # objective seed is divided by n_expert, and the expert-axis psums
    # living inside the applies' custom vjps (all-to-all transposes,
    # expert.py's grad_sync of replicated leaves) reassemble full
    # gradients from the n 1/n-weighted chains. Expert-replicated stages'
    # params get the same grad_sync wrap the GPipe branches give them.
    shard_axes = (MODEL_AXIS,) if tp_on else ()
    wire_axes = tuple(a for a in vary_axes if a not in shard_axes)
    ep_div = n_expert if ep_on else 1
    cpu_backend = jax.default_backend() == "cpu"

    def per_device(row4d, x_mb, tgt_mb, w_mb, key):
        row = row4d[0, 0, 0]
        stage = lax.axis_index(STAGE_AXIS)
        mb = x_mb.shape[1]
        width = row.shape[0]
        # the weighted-mean denominator is global and param-independent:
        # every backward seed carries w/den_g directly
        tok_per_sample = 1
        for d in out_shape[:-1]:
            tok_per_sample *= d
        den_g = lax.psum(jnp.sum(w_mb), DATA_AXIS) * tok_per_sample

        def stage_key(m):
            k = jax.random.fold_in(
                jax.random.fold_in(key, m), stage)
            k = jax.random.fold_in(k, lax.axis_index(DATA_AXIS))
            if seq_on:
                # distinct dropout noise per seq shard (GPipe does the same)
                k = jax.random.fold_in(k, lax.axis_index(SEQ_AXIS))
            return k

        def stage_fn(s):
            """The pure per-microbatch stage function the backward vjp's:
            params, x -> (wire_out, objective_contribution, num_raw, aux).

            Last stage: objective = sum(w*nll)/(den_g*ep_div) +
            aux/(M*n_data*n_seq*ep_div) (its wire_out is zeros). Inner
            stage: the aux term only (NLL reaches it through the wire
            cotangent). Every divisor mirrors the GPipe engine's psum/pmean
            reduction of the same term.
            """
            is_last = s == S - 1

            def fn(params, x_wire, k, tgt, w):
                x = wire_decode(x_wire, in_shapes[s])
                p = params
                if ep_on and not expert_sharded[s]:
                    # GPipe's replicated-params treatment on the expert
                    # axis: grad_sync's backward psums the n per-slot
                    # (1/n-seeded) cotangents into the full gradient on
                    # every slot, keeping the replicas in sync
                    from simple_distributed_machine_learning_tpu.parallel.tensor import (
                        grad_sync,
                    )
                    p = jax.tree.map(
                        lambda a: grad_sync(a, EXPERT_AXIS), p)
                if compute_dtype is not None:
                    p = jax.tree.map(lambda a: a.astype(compute_dtype), p)
                    x = pipe.stages[s].cast_input(x, compute_dtype)
                y = applies[s](p, x, k, deterministic)
                aux = jnp.float32(0.0)
                if isinstance(y, tuple):
                    y, aux = y
                    aux = aux.astype(jnp.float32)
                # pvary aux over the EXPERT axis before it enters the
                # objective (GPipe's branch-exit pcast, done inside the
                # differentiated function): an EP-MoE stage's aux is
                # expert-INVARIANT (expert.py pmeans it), and without this
                # the aux node of a NON-last stage received a
                # 1/n_expert-starved cotangent — the last stage was saved
                # only by its varying num term forcing the same implicit
                # pcast. The pcast's transpose psums the n per-slot 1/n
                # seeds into the full cotangent. EXPERT ONLY: the model
                # axis runs the invariant-wire discipline, where an extra
                # pcast would double-count through its psum transpose.
                if ep_on:
                    aux = _pvary_to(aux, (EXPERT_AXIS,))
                obj = aux / (
                    M * n_data * (pipe.n_seq if seq_on else 1) * ep_div)
                num_raw = jnp.float32(0.0)
                if is_last:
                    nll = nll_loss(y.astype(jnp.float32), tgt, "none")
                    wb = jnp.broadcast_to(
                        w.reshape(w.shape + (1,) * (nll.ndim - 1)), nll.shape)
                    num_raw = jnp.sum(nll * wb)
                    obj = obj + num_raw / (den_g * ep_div)
                    out = jnp.zeros((x_wire.shape[0], wire_dim), jnp.float32)
                else:
                    out = wire_encode(y.astype(jnp.float32), wire_dim)
                return out, obj, num_raw, aux
            return fn

        def _to_wire_type(v):
            """Normalize an activation to the wire's vma: a replicated
            stage's output is typed model/expert-varying (its param row is)
            with REPLICATED values — pmean over the axis is the identity-
            valued replication proof that drops it (the GPipe engine's
            logits/num trick); then pvary any missing axes."""
            for ax in shard_axes:
                if ax in _vma_of(v):
                    v = lax.pmean(v, ax)
            return _pvary_to(v, wire_axes)

        def make_fwd_branch(s):
            def branch(x_wire, k, tgt, w):
                params = unpack_stage_params(row, metas[s])
                out, _, _, aux = stage_fn(s)(params, x_wire, k, tgt, w)
                return (_to_wire_type(out), _pvary_to(aux, vary_axes))
            return branch

        def make_bwd_branch(s):
            is_last = s == S - 1

            def branch(x_wire, cot_wire, k, tgt, w):
                params = unpack_stage_params(row, metas[s])

                def f(p, xw):
                    out, obj, num_raw, _ = stage_fn(s)(p, xw, k, tgt, w)
                    return (out, obj), num_raw

                primals, pull, num_raw = jax.vjp(f, params, x_wire,
                                                 has_aux=True)
                # cotangents must match each primal's vma exactly (zeros for
                # the last stage's never-on-the-wire output; 1 for the
                # scalar objective contribution)
                def like(ct, primal):
                    vma = tuple(_vma_of(primal))
                    return _pvary_to(ct, vma)
                cot_out = (like(jnp.zeros(cot_wire.shape, cot_wire.dtype),
                                primals[0]) if is_last
                           else like(cot_wire, primals[0]))
                d_params, d_x = pull((cot_out,
                                      like(jnp.float32(1.0), primals[1])))
                # x_wire is typed invariant over each sharded axis, so
                # the pullback psum'd the per-slot input-cotangents over
                # it: for sharded stages that assembles the PARTIALS (the
                # real cotangent, no correction); for replicated stages it
                # summed n IDENTICAL full cotangents — rescale per axis.
                if tp_on and not model_sharded[s]:
                    d_x = d_x / n_model
                # vma-aware autodiff semantics: ``params`` is data-INVARIANT
                # (the buffer is replicated over the data axis), so the
                # pullback's d_params must be too — jax inserts the implicit
                # psum over 'data' itself, exactly the DP gradient
                # all-reduce (the same rule tensor.grad_sync compensates for
                # in the GPipe engine). d_params arrives ALREADY summed
                # across data shards; any further data reduction would
                # double-count.
                grad_row = pack_stage_grads(d_params, metas[s], width)
                return (_pvary_to(grad_row, vary_axes_nodata),
                        _pvary_to(d_x, wire_axes),
                        _pvary_to(num_raw, vary_axes))
            return branch

        fwd_branches = [make_fwd_branch(s) for s in range(S)]
        bwd_branches = [make_bwd_branch(s) for s in range(S)]
        fwd_ring = [(i, (i + 1) % S) for i in range(S)]
        bwd_ring = [(i, (i - 1) % S) for i in range(S)]

        def step(carry, t):
            wire_f, wire_b, inbuf, grad_acc, num_acc, aux_acc = carry

            # ---- forward half-tick -------------------------------------
            m_f = t - stage
            valid_f = (m_f >= 0) & (m_f < M)
            mf_safe = jnp.clip(m_f, 0, M - 1)
            inj = lax.dynamic_index_in_dim(x_mb, jnp.clip(t, 0, M - 1), 0,
                                           keepdims=False)
            x_in = jnp.where(stage == 0, inj, wire_f)
            tgt_f = lax.dynamic_index_in_dim(tgt_mb, mf_safe, 0,
                                             keepdims=False)
            w_f = lax.dynamic_index_in_dim(w_mb, mf_safe, 0, keepdims=False)
            out_f, aux = lax.switch(stage, fwd_branches, x_in,
                                    stage_key(mf_safe), tgt_f, w_f)
            out_f = jnp.where(valid_f, out_f, jnp.zeros_like(out_f))
            aux_acc = aux_acc + jnp.where(valid_f, aux, 0.0)
            # the backward's input read happens BEFORE this tick's save (the
            # slots are distinct with D=2S, but keep the order load-bearing)
            m_b = t - 2 * (S - 1) + stage - 1
            valid_b = (m_b >= 0) & (m_b < M)
            mb_safe = jnp.clip(m_b, 0, M - 1)
            x_saved = lax.dynamic_index_in_dim(inbuf, mb_safe % D, 0,
                                               keepdims=False)
            # save this microbatch's input for the backward recompute
            slot_f = mf_safe % D
            prev = lax.dynamic_index_in_dim(inbuf, slot_f, 0, keepdims=False)
            inbuf = lax.dynamic_update_index_in_dim(
                inbuf, jnp.where(valid_f, x_in, prev), slot_f, 0)

            # ---- backward half-tick ------------------------------------
            tgt_b = lax.dynamic_index_in_dim(tgt_mb, mb_safe, 0,
                                             keepdims=False)
            w_b = lax.dynamic_index_in_dim(w_mb, mb_safe, 0, keepdims=False)
            grad_row, d_x, num_raw = lax.switch(
                stage, bwd_branches, x_saved, wire_b, stage_key(mb_safe),
                tgt_b, w_b)
            grad_acc = grad_acc + jnp.where(valid_b, grad_row,
                                            jnp.zeros_like(grad_row))
            num_acc = num_acc + jnp.where(valid_b, num_raw, 0.0)
            d_x = jnp.where(valid_b, d_x, jnp.zeros_like(d_x))

            # ---- the two rings -----------------------------------------
            wire_f = lax.ppermute(out_f, STAGE_AXIS, fwd_ring)
            if cpu_backend:
                # serialize the reverse hop behind the forward one ON THE
                # CPU BACKEND ONLY: the hops are data-independent, and
                # letting the runtime float both (plus branch collectives)
                # concurrently starves XLA:CPU's in-process rendezvous on
                # few-core machines. On TPU the barrier would cost one ICI
                # hop of comm-comm overlap per tick, so it is omitted.
                wire_f, d_x = lax.optimization_barrier((wire_f, d_x))
            wire_b = lax.ppermute(d_x, STAGE_AXIS, bwd_ring)
            return (wire_f, wire_b, inbuf, grad_acc, num_acc, aux_acc), None

        init0 = (jnp.zeros((mb, wire_dim), jnp.float32),
                 jnp.zeros((mb, wire_dim), jnp.float32),
                 jnp.zeros((D, mb, wire_dim), jnp.float32),
                 None,                              # grad_acc: data-invariant
                 jnp.float32(0.0), jnp.float32(0.0))
        init = tuple(
            _pvary_to(jnp.zeros((width,), jnp.float32), vary_axes_nodata)
            if a is None else _pvary_to(a, wire_axes if i < 3 else vary_axes)
            for i, a in enumerate(init0))
        carry, _ = lax.scan(step, init, jnp.arange(T))
        _, _, _, grad_acc, num_acc, aux_acc = carry

        # loss value (reporting): identical reduction to the GPipe engine
        num = lax.psum(lax.psum(num_acc, STAGE_AXIS), DATA_AXIS)
        aux = lax.pmean(lax.psum(aux_acc, STAGE_AXIS) / M, DATA_AXIS)
        if seq_on:
            num = lax.psum(num, SEQ_AXIS)
            aux = lax.pmean(aux, SEQ_AXIS)
        loss = num / jnp.maximum(den_g, 1e-12) + aux
        loss = lax.pmean(loss, MODEL_AXIS)
        if pipe._has_expert:
            loss = lax.pmean(loss, EXPERT_AXIS)
        # grad_acc is already the data-summed gradient (the pullback's
        # implicit psum, see make_bwd_branch) and data-invariant, so the
        # data-unmentioned param-spec output takes one copy per stage row
        return loss, grad_acc.reshape(1, 1, 1, width)

    from jax.sharding import PartitionSpec as P

    # LM targets carry token axes ([M, mb, T]); on a seq mesh the wire's
    # feature axis and the targets' token axis are sharded over it (the
    # host packs one contiguous wire chunk per seq shard, _prep_inputs)
    seq_or_none = SEQ_AXIS if seq_on else None
    tok_axes = len(out_shape) - 1
    tgt_tok = ((seq_or_none,) + (None,) * (tok_axes - 1)) if tok_axes else ()
    return _shard_map(
        per_device,
        mesh=pipe.mesh,
        in_specs=(pipe.param_spec(), P(None, DATA_AXIS, seq_or_none),
                  P(None, DATA_AXIS, *tgt_tok), P(None, DATA_AXIS), P()),
        out_specs=(P(), pipe.param_spec()),
    )
