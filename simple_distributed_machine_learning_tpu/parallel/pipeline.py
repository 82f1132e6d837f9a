"""The pipeline engine: GPipe-scheduled SPMD pipeline parallelism.

This is the TPU-native replacement for the reference's entire hot path — the
blocking master→worker activation RPC (``/root/reference/simple_distributed.py:49``),
the worker→master reply (``:80``), the distributed-autograd backward hop
(``:109-112``), and the remote optimizer step (``:113``). All of it compiles
into ONE ``jit``-ed SPMD program:

- every device runs the same scanned loop; at step ``t`` the device holding
  stage ``s`` computes microbatch ``t - s`` (GPipe schedule);
- the inter-stage hop is a single ``lax.ppermute`` over the ``stage`` mesh
  axis — on TPU this is a compiled collective-permute over ICI, overlapped by
  XLA with the next step's compute (the reference's RPC hop is fully blocking:
  per-step time = t(stage0) + 2·t(transfer) + t(stage1), SURVEY §3.3);
- backward needs no distributed-autograd engine: ``jax.grad`` through
  ``ppermute`` emits the transposed permute, so activation cotangents hop
  stage ``s+1`` → ``s`` inside the same compiled program; each data shard
  accumulates its own float32 parameter-row cotangent through the reversed
  scan, and the row crosses the ``data`` axis once a step, after it;
- heterogeneous stages (conv front / fc back, as in the reference's
  Network1/Network2 split ``:26-83``) are dispatched with ``lax.switch`` on
  the device's stage index, over the packed stage-sharded parameter buffer
  (see ``staging.py``).

The sequential reference schedule is the ``n_microbatches=1`` special case;
a fused single-device model is the ``n_stages=1`` special case — which is what
makes loss-parity tests against a single-device run exact (SURVEY §7, test #1).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from simple_distributed_machine_learning_tpu.ops.losses import nll_loss
from simple_distributed_machine_learning_tpu.parallel.mesh import (
    DATA_AXIS,
    EXPERT_AXIS,
    MODEL_AXIS,
    SEQ_AXIS,
    STAGE_AXIS,
)


from simple_distributed_machine_learning_tpu.parallel.compat import (
    pvary_to as _pvary_to,
    shard_map as _shard_map,
)
from simple_distributed_machine_learning_tpu.parallel.staging import (
    StageMeta,
    pack_stage_params,
    unpack_stage_params,
    wire_decode,
    wire_encode,
)
from simple_distributed_machine_learning_tpu.telemetry import tracing


@dataclasses.dataclass(frozen=True)
class Stage:
    """One pipeline stage.

    ``apply(params, x, key, deterministic) -> y`` operates on real (unpadded)
    activations: ``x`` has per-sample shape ``in_shape``; ``y``'s trailing
    features are re-encoded onto the wire by the engine. The last stage must
    return log-probabilities ``[batch, out_dim]`` (the reference's stage 1
    ends in ``log_softmax``, ``simple_distributed.py:79``).

    ``shards``: optional per-model-shard params for tensor parallelism — a
    tuple of ``n_model`` pytrees (identical tree structure and leaf shapes).
    When set, ``apply`` receives THIS device's shard and may use collectives
    over the ``model`` mesh axis (e.g. ``tensor.tp_pair_apply``); every model
    shard must return the same (replicated) activation, i.e. finish each
    sharded group with its psum. When ``shards`` is None on a mesh with
    ``n_model > 1``, ``params`` is replicated to every model slot and the
    stage computes redundantly (correct, just not sharded).

    ``expert_shards``: optional per-expert-device params for expert (MoE)
    parallelism — a tuple of ``n_expert`` pytrees (identical structure and
    leaf shapes; typically the stage's expert weights split ``E/n_expert``
    per device with everything else replicated). ``apply`` receives THIS
    device's shard and may use collectives over the ``expert`` mesh axis
    (e.g. ``expert.moe_apply_ep``); the apply is responsible for grad-syncing
    its replicated (non-expert) leaves over the axis and must return the
    same activation on every expert device (e.g. via ``all_gather``).
    Mutually exclusive with ``shards``.

    ``apply`` may return either ``y`` or ``(y, aux)`` — ``aux`` is a scalar
    auxiliary loss (e.g. the MoE load-balancing term, already scaled by its
    weight) that the engine adds to the objective (summed over stages,
    averaged over microbatches/data shards).

    ``token_input``: ``x`` carries integer token ids as float32 (exact up to
    2**24). Under mixed precision the engine hands them to ``apply`` UNCAST:
    bfloat16 keeps 8 significant bits, so a cast rounds every id above 256
    (8191 becomes 8192 — out of the vocabulary, which ``jnp.take`` fills
    with NaN: the ``final_loss: NaN`` of every bf16 GPT row with a real
    vocabulary).
    """
    apply: Callable[[Any, jax.Array, jax.Array, bool], jax.Array]
    params: Any
    in_shape: tuple[int, ...]
    shards: tuple | None = None
    expert_shards: tuple | None = None
    token_input: bool = False

    def cast_input(self, x: jax.Array, compute_dtype) -> jax.Array:
        """``x`` in the mixed-precision compute dtype, unless it is ids."""
        return x if self.token_input else x.astype(compute_dtype)


class Pipeline:
    """Compiled GPipe pipeline over a ``(data, stage)`` mesh.

    Parameters live in a ``[n_stages, max_param_size]`` buffer sharded
    ``P('stage')`` — each device holds only its own stage's params
    (owner-local, like the reference's per-process modules) and updates them
    locally inside the compiled step (replacing DistributedOptimizer,
    ``simple_distributed.py:100-104``).
    """

    def __init__(self, stages: Sequence[Stage], mesh: jax.sharding.Mesh,
                 wire_dim: int, out_dim: int | tuple[int, ...],
                 n_microbatches: int = 1, compute_dtype=None,
                 remat: bool = False, schedule: str = "gpipe",
                 overlap: str = "none"):
        if schedule not in ("gpipe", "1f1b"):
            raise ValueError(f"unknown schedule {schedule!r}")
        from simple_distributed_machine_learning_tpu.parallel.overlap import (
            check_overlap,
        )
        # the engine-level knob covers the engine's OWN collectives — the
        # backward grad_sync all-reduce of stages stored replicated over the
        # model/expert axes becomes the chunked ppermute ring of
        # overlap.ring_psum. Stage-internal collectives (TP pairs, TP GPT
        # blocks, EP dispatch) carry their own overlap choice from the model
        # build. The 1F1B engine (onefb.py) does its own replication
        # accounting without grad_sync and ignores this knob.
        self.overlap = check_overlap(overlap)
        self.schedule = schedule
        self.stages = list(stages)
        self.mesh = mesh
        self.n_stages = mesh.shape[STAGE_AXIS]
        self.n_data = mesh.shape[DATA_AXIS]
        self.n_model = mesh.shape.get(MODEL_AXIS, 1)
        # sequence/context parallelism: when the mesh has a seq axis, the
        # token axis (axis 0 of every stage's in_shape and of out_shape) is
        # sharded over it. Stage in_shapes and wire_dim are then LOCAL
        # (per-seq-shard) sizes; out_dim stays GLOBAL (the host-facing logits
        # shape). Stage applies use seq collectives (ring attention / Ulysses
        # all-to-all) for any cross-token mixing.
        self._has_seq = SEQ_AXIS in mesh.shape
        self.n_seq = mesh.shape.get(SEQ_AXIS, 1)
        # expert (MoE) parallelism: expert-sharded stages hold 1/n_expert of
        # their expert weights per expert-axis device (see Stage.expert_shards)
        self._has_expert = EXPERT_AXIS in mesh.shape
        self.n_expert = mesh.shape.get(EXPERT_AXIS, 1)
        if len(self.stages) != self.n_stages:
            raise ValueError(
                f"{len(self.stages)} stages but mesh stage axis is {self.n_stages}")
        self.wire_dim = int(wire_dim)
        # per-sample output shape; last axis = classes. (C,) for classifiers,
        # (T, V) for per-token language-model log-probs
        self.out_shape = ((int(out_dim),) if isinstance(out_dim, int)
                          else tuple(int(d) for d in out_dim))
        self.out_dim = self.out_shape[-1]
        if self.n_seq > 1:
            if len(self.out_shape) < 2:
                raise ValueError(
                    "sequence parallelism (mesh seq axis > 1) requires a "
                    "per-token output shape like (T, V); got "
                    f"out_dim={out_dim!r}")
            if self.out_shape[0] % self.n_seq:
                raise ValueError(
                    f"token axis {self.out_shape[0]} not divisible by "
                    f"seq axis size {self.n_seq}")
        # per-device output shape: token axis divided over the seq shards
        self.out_local = ((self.out_shape[0] // self.n_seq,)
                          + self.out_shape[1:])
        self.n_microbatches = int(n_microbatches)
        # mixed precision: params and activations are cast to compute_dtype
        # around each stage apply (bfloat16 doubles MXU throughput and halves
        # HBM traffic); master params, the wire, and the loss stay float32.
        # remat: stage applies recompute in backward (jax.checkpoint), trading
        # FLOPs for activation memory — the standard deep-pipeline trade.
        self.compute_dtype = compute_dtype
        self.remat = bool(remat)
        self._sm_cache: dict[bool, Callable] = {}
        # param buffer rows: one per (stage, model-shard, expert-shard).
        # Stages without shards are replicated across the model/expert axes
        # (redundant compute, identical grads — the data-axis story, one
        # level down); expert-sharded stages genuinely split their expert
        # weights' STORAGE across the expert axis.
        per_shard: list[Any] = []
        for s in self.stages:
            if s.shards is not None and s.expert_shards is not None:
                raise ValueError(
                    "a stage cannot be both tensor- (shards) and expert- "
                    "(expert_shards) sharded")
            if s.shards is not None and len(s.shards) != self.n_model:
                raise ValueError(
                    f"stage has {len(s.shards)} model shards, mesh model "
                    f"axis is {self.n_model}")
            if (s.expert_shards is not None
                    and len(s.expert_shards) != self.n_expert):
                raise ValueError(
                    f"stage has {len(s.expert_shards)} expert shards, mesh "
                    f"expert axis is {self.n_expert}")
            model_trees = (list(s.shards) if s.shards is not None
                           else [s.params] * self.n_model)
            for mt in model_trees:
                if s.expert_shards is not None:
                    per_shard.extend(s.expert_shards)
                else:
                    per_shard.extend([mt] * self.n_expert)
        # set-up by phase (telemetry/tracing.py): the eager pack on the
        # first device, the row's trip to the host, the rest of the build
        with tracing.span("pipeline.init") as init:
            with tracing.span("pipeline.pack"):
                flat, metas_all = pack_stage_params(per_shard)
                # the pack is dispatched eagerly: wait here, or its time
                # reads as the transfer's
                jax.block_until_ready(flat)
            import numpy as np
            # keep the master copy on the HOST: device_put of an on-device
            # array with a matching sharding ALIASES it, and a later donated
            # train step would delete the alias — init_params() must survive
            # any number of donating steps
            nbytes = int(flat.nbytes)
            init.set(bytes=nbytes)
            with tracing.span("pipeline.to_host", bytes=nbytes):
                self._buf0 = np.asarray(jax.device_get(flat.reshape(
                    self.n_stages, self.n_model, self.n_expert, -1)))
            # shard 0's layout stands for the stage (shards are
            # shape-identical)
            stride = self.n_model * self.n_expert
            self.metas = metas_all[::stride]
            for s, stage in enumerate(self.stages):
                if (stage.shards is not None
                        or stage.expert_shards is not None):
                    m0 = metas_all[s * stride]
                    for m in metas_all[s * stride:(s + 1) * stride]:
                        if m.shapes != m0.shapes:
                            raise ValueError(
                                f"stage {s}: model/expert shards have "
                                f"differing leaf shapes — sharded params "
                                f"must split evenly")
            self._validate_boundaries()

    def _validate_boundaries(self) -> None:
        """Shape-check every stage hop at build time (via eval_shape — no FLOPs).

        The wire codec zero-pads/truncates, so a stage whose output width does
        not match the next stage's ``in_shape`` would otherwise train silently
        on fabricated zeros. Plain stages are eval_shape'd directly; TP-, EP-
        and seq-parallel stage applies use mesh collectives (psum /
        all-to-all / ring ppermute), so they are traced under a ``shard_map``
        over the real mesh (``check_vma=False`` — only shape semantics are
        wanted here) and validated on per-shard feature widths.
        """
        import numpy as np
        batch = 2
        for s, stage in enumerate(self.stages):
            on_mesh = (self.n_seq > 1 or stage.shards is not None
                       or stage.expert_shards is not None)
            exact_shape = None
            if on_mesh:
                shard_shape = self._sharded_out_shape(stage, batch)
                out_size = int(np.prod(shard_shape))
            else:
                x = jax.ShapeDtypeStruct((batch,) + tuple(stage.in_shape),
                                         jnp.float32)
                key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
                out = jax.eval_shape(
                    lambda p, xx, kk, _a=stage.apply: _a(p, xx, kk, True),
                    stage.params, x, key)
                if isinstance(out, tuple):
                    # MoE stages return (y, aux_loss); only y rides the wire
                    out = out[0]
                exact_shape = out.shape
                out_size = int(np.prod(out.shape[1:]))
            # the last stage's output never rides the wire (its log-probs
            # are consumed locally by the loss), so only inter-stage hops
            # must fit wire_dim
            if s + 1 < len(self.stages) and out_size > self.wire_dim:
                raise ValueError(
                    f"stage {s} output width {out_size} exceeds wire_dim "
                    f"{self.wire_dim}")
            if s + 1 < len(self.stages):
                nxt = int(np.prod(self.stages[s + 1].in_shape))
                if out_size != nxt:
                    raise ValueError(
                        f"stage {s} outputs {out_size} features but stage "
                        f"{s + 1} declares in_shape={self.stages[s + 1].in_shape} "
                        f"({nxt} features)")
            elif exact_shape is not None:
                if exact_shape[1:] != self.out_shape:
                    raise ValueError(
                        f"last stage must output [batch, *{self.out_shape}], "
                        f"got {exact_shape}")
            elif shard_shape != tuple(self.out_local):
                per = ("per seq shard " if self.n_seq > 1 else "")
                raise ValueError(
                    f"last stage outputs {shard_shape} {per}but the pipeline "
                    f"declares out_shape={self.out_shape} "
                    f"({tuple(self.out_local)} {per.strip() or 'per device'})")
            if int(np.prod(stage.in_shape)) > self.wire_dim:
                raise ValueError(
                    f"stage {s} in_shape {stage.in_shape} exceeds wire_dim "
                    f"{self.wire_dim}")

    def _sharded_out_shape(self, stage: Stage, batch: int) -> tuple[int, ...]:
        """Per-shard output feature shape of a TP/EP/seq stage, traced under
        ``shard_map`` on the real mesh with zero FLOPs (``jax.eval_shape``).

        Params ride in stacked over their shard axis (model or expert) so
        each device sees its own shard; in a seq mesh the activation's token
        axis (axis 0 of ``in_shape``) is sharded over the seq axis. The
        per-shard shape is captured at trace time (shapes are static), since
        the shard_map out_spec only reassembles a flattened width.
        """
        if stage.expert_shards is not None:
            trees, p_axis = stage.expert_shards, EXPERT_AXIS
        elif stage.shards is not None:
            trees, p_axis = stage.shards, MODEL_AXIS
        else:
            trees, p_axis = None, None
        if trees is not None:
            p_sds = jax.tree.map(
                lambda *ls: jax.ShapeDtypeStruct((len(ls),) + ls[0].shape,
                                                 ls[0].dtype), *trees)
            p_spec, unstack = P(p_axis), True
        else:
            p_sds = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), stage.params)
            p_spec, unstack = P(), False

        in_local = tuple(stage.in_shape)
        if self.n_seq > 1:
            x_glob = (batch, in_local[0] * self.n_seq) + in_local[1:]
            x_spec = P(None, SEQ_AXIS, *(None,) * (len(in_local) - 1))
        else:
            x_glob = (batch,) + in_local
            x_spec = P(*(None,) * (len(in_local) + 1))
        x = jax.ShapeDtypeStruct(x_glob, jnp.float32)
        key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)

        shard_shape: list[tuple[int, ...]] = []

        def run(p, xx, kk):
            if unstack:
                p = jax.tree.map(lambda a: a[0], p)   # this device's shard
            y = stage.apply(p, xx, kk, True)
            if isinstance(y, tuple):
                y = y[0]
            shard_shape.append(tuple(y.shape[1:]))
            return y.reshape(xx.shape[0], -1)

        fn = _shard_map(
            run, mesh=self.mesh,
            in_specs=(p_spec, x_spec, P()),
            out_specs=P(None, SEQ_AXIS if self.n_seq > 1 else None),
            check_vma=False)
        jax.eval_shape(fn, p_sds, x, key)
        return shard_shape[0]

    # ---- parameters -----------------------------------------------------

    def replication_weights(self):
        """``[S, n_model, n_expert, 1]`` float32 multipliers for squared-
        gradient-norm sums over the packed buffer: stages stored redundantly
        across the model/expert axes (``Stage.shards``/``expert_shards`` is
        None) get ``1/replication`` so each parameter counts once in a global
        norm (``train.optimizer.clip_by_global_norm``); genuinely sharded
        rows count fully. Padding tail bytes are zero-gradient anyway."""
        import numpy as np
        w = np.ones((self.n_stages, self.n_model, self.n_expert, 1),
                    np.float32)
        for s, stage in enumerate(self.stages):
            rep = 1
            if stage.shards is None:
                rep *= self.n_model
            if stage.expert_shards is None:
                rep *= self.n_expert
            w[s] = 1.0 / rep
        return w

    def param_spec(self) -> P:
        """PartitionSpec of the packed ``[n_stages, n_model, n_expert, P]``
        buffer."""
        return P(STAGE_AXIS, MODEL_AXIS,
                 EXPERT_AXIS if self._has_expert else None, None)

    def init_params(self) -> jax.Array:
        """Place the packed stage-param buffer on the mesh (stage- and
        model-shard-sharded; replicated over the data axis)."""
        sharding = NamedSharding(self.mesh, self.param_spec())
        return jax.device_put(self._buf0, sharding)

    def unpack(self, buf: jax.Array) -> list[Any]:
        """Host-side: recover the per-stage param pytrees (for tests/ckpt).
        For model-/expert-sharded stages the entry is the list of per-shard
        trees."""
        rows = jax.device_get(buf)
        out = []
        for s in range(self.n_stages):
            if self.stages[s].shards is not None:
                out.append([unpack_stage_params(
                    jnp.asarray(rows[s, m, 0]), self.metas[s])
                    for m in range(self.n_model)])
            elif self.stages[s].expert_shards is not None:
                out.append([unpack_stage_params(
                    jnp.asarray(rows[s, 0, e]), self.metas[s])
                    for e in range(self.n_expert)])
            else:
                out.append(unpack_stage_params(
                    jnp.asarray(rows[s, 0, 0]), self.metas[s]))
        return out

    # ---- forward/loss ---------------------------------------------------

    def _shard_fn(self, deterministic: bool, loss_only: bool = False,
                  metrics: bool = False) -> Callable:
        """Build (once per mode) the shard_mapped pipeline loss function.

        ``loss_only``: the training mode. The scan carry drops the
        ``[M, mb, *out_shape]`` log-probs accumulator (for a language model
        that is the full [B, T, V] replicated over every stage — the
        dominant activation at scale) and the function returns just the
        scalar loss; gradients are identical because the accumulator never
        feeds the loss.

        ``metrics``: the eval mode. Like ``loss_only`` the carry never holds
        the log-probs accumulator; instead the loop folds each last-stage
        microbatch's log-probs straight into three scalars — weighted NLL
        sum, weight sum, weighted argmax-correct count — and returns them
        un-divided (the caller decides mean vs sum). Eval of a model whose
        ``[B, T, V]`` logits would not fit replicated across stages costs no
        more memory than training.
        """
        cache_key = (deterministic, loss_only, metrics)
        if cache_key in self._sm_cache:
            return self._sm_cache[cache_key]
        if loss_only and metrics:
            raise ValueError("loss_only and metrics are distinct modes")

        S = self.n_stages
        M = self.n_microbatches
        T = M + S - 1
        wire_dim = self.wire_dim
        out_shape = self.out_local          # per-device (seq-local) shape
        # the seq axis engages only for per-token outputs: a classifier has
        # no token axis to shard, so its wire/targets/logits stay seq-
        # replicated even on a mesh that has a seq axis
        seq_on = self._has_seq and len(self.out_shape) > 1
        n_seq = self.n_seq
        metas = list(self.metas)
        applies = [s.apply for s in self.stages]
        in_shapes = [s.in_shape for s in self.stages]
        n_model = self.n_model
        n_expert = self.n_expert
        # stages without model/expert shards compute redundantly on every
        # slot of those axes; their params need the grad_sync treatment (see
        # tensor.grad_sync) so each replica receives the full, not
        # 1/axis_size, gradient
        replicated_over_model = [s.shards is None for s in self.stages]
        replicated_over_expert = [s.expert_shards is None for s in self.stages]
        overlap = self.overlap
        compute_dtype = self.compute_dtype
        remat = self.remat
        # every mesh axis the loop's values can vary over (data via inputs,
        # stage/model/expert via the param row, seq via the sharded wire)
        vary_axes = (DATA_AXIS, STAGE_AXIS, MODEL_AXIS) + (
            (SEQ_AXIS,) if seq_on else ()) + (
            (EXPERT_AXIS,) if self._has_expert else ())

        def per_device(row4d, x_mb, tgt_mb, w_mb, key):
            # row4d: [1, 1, 1, P] this device's (stage, model-shard,
            # expert-shard) param row; x_mb: [M, mb, wire]; tgt_mb/w_mb:
            # [M, mb(...)] targets and weights. The row, replicated over
            # data, is typed data-varying HERE, once: the transpose of this
            # cast is the ONE psum over data its gradient needs, on the f32
            # row cotangent each shard accumulates through the reversed
            # scan. Left invariant it is cast where a leaf meets an
            # activation, inside switch and scan, and the transpose
            # all-reduces every leaf's cotangent at every scan step.
            row = _pvary_to(row4d[0, 0, 0], (DATA_AXIS,))
            stage = lax.axis_index(STAGE_AXIS)
            mb = x_mb.shape[1]

            def make_branch(s):
                is_last = (s == S - 1)

                def branch(wire, k):
                    from simple_distributed_machine_learning_tpu.parallel.tensor import (
                        grad_sync,
                    )
                    params = unpack_stage_params(row, metas[s])
                    if n_model > 1 and replicated_over_model[s]:
                        params = jax.tree.map(
                            lambda a: grad_sync(a, MODEL_AXIS, overlap),
                            params)
                    if n_expert > 1 and replicated_over_expert[s]:
                        params = jax.tree.map(
                            lambda a: grad_sync(a, EXPERT_AXIS, overlap),
                            params)
                    x = wire_decode(wire, in_shapes[s])
                    if compute_dtype is not None:
                        params = jax.tree.map(
                            lambda a: a.astype(compute_dtype), params)
                        x = self.stages[s].cast_input(x, compute_dtype)
                    y = applies[s](params, x, k, deterministic)
                    aux = jnp.float32(0.0)
                    if isinstance(y, tuple):
                        y, aux = y
                        aux = aux.astype(jnp.float32)
                    # the last stage's output (the log-probs) never rides the
                    # ppermute ring: it is consumed locally by the loss, so
                    # the wire stays inter-stage-activation wide (for a GPT
                    # that keeps vocab-width [T, V] log-probs off the hop and
                    # off the wire padding) and the last stage sends zeros
                    # (stage 0 overwrites its inbox with the next injected
                    # microbatch anyway)
                    if is_last:
                        out = jnp.zeros((y.shape[0], wire_dim), jnp.float32)
                        y_out = y.astype(jnp.float32)
                    else:
                        out = wire_encode(y.astype(jnp.float32), wire_dim)
                        y_out = jnp.zeros((y.shape[0],) + out_shape,
                                          jnp.float32)
                    # uniformize branch output vma for lax.switch and the
                    # scan carry: a TP stage's psum (or an EP stage's
                    # all_gather) leaves its output less-varying than a
                    # replicated stage's. Value-identity; the transpose
                    # (psum of per-replica cotangents, each ct/n after the
                    # loss pmean) reassembles the full cotangent.
                    #
                    # the zero-valued full-vma anchor additionally pins each
                    # branch's INPUT-cotangent type: without it, branches
                    # whose wire feeds a narrower-vma path (e.g. a plain
                    # stage beside sharded ones, or the last stage's
                    # loss-only use) transpose to mismatched cotangent vmas
                    # and jax's cond transpose rejects the switch
                    # ("mismatched varying manual axes"). Adding 0*sum(wire)
                    # is value-free but makes every branch's wire cotangent
                    # at least vary_axes-typed. The closed-over param row
                    # needs no such pin (its type is fixed outside the scan),
                    # and 0*sum(row) would be a pass over the row forward and
                    # a row-wide add of zeros backward, at every scan step.
                    anchor = _pvary_to(
                        jnp.float32(0.0) * jnp.sum(wire),
                        vary_axes)
                    return (_pvary_to(out, vary_axes) + anchor,
                            _pvary_to(aux, vary_axes) + anchor,
                            _pvary_to(y_out, vary_axes) + anchor)
                if remat:
                    return jax.checkpoint(branch)
                return branch

            branches = [make_branch(s) for s in range(S)]
            fwd = [(i, (i + 1) % S) for i in range(S)]

            def step(carry, t):
                if loss_only:
                    wire, num_acc, den_acc, aux_acc = carry
                elif metrics:
                    wire, num_acc, den_acc, aux_acc, correct_acc = carry
                else:
                    wire, num_acc, den_acc, aux_acc, logits_acc = carry
                # stage 0 injects a fresh microbatch every step (clipped so the
                # drain steps recompute-and-discard the last one — finite math,
                # zeroed below by the validity mask).
                inj = lax.dynamic_index_in_dim(
                    x_mb, jnp.clip(t, 0, M - 1), 0, keepdims=False)
                wire = jnp.where(stage == 0, inj, wire)
                # distinct dropout noise per (step, stage, data-shard) — and
                # per seq-shard when the token axis is sharded, so dropout
                # patterns do not repeat chunk-to-chunk (left out of the fold
                # at n_seq=1 to keep the fused path's RNG stream identical)
                k_t = jax.random.fold_in(
                    jax.random.fold_in(jax.random.fold_in(key, t), stage),
                    lax.axis_index(DATA_AXIS))
                if n_seq > 1:
                    k_t = jax.random.fold_in(k_t, lax.axis_index(SEQ_AXIS))
                out, aux, logits = lax.switch(stage, branches, wire, k_t)
                m = t - stage           # microbatch index this stage is working on
                valid = (m >= 0) & (m < M)
                out = jnp.where(valid, out, jnp.zeros_like(out))
                # auxiliary losses (e.g. MoE load balancing) accumulate once
                # per (stage, valid microbatch)
                aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
                # the last stage's branch just produced log-probs for
                # microbatch m (zeros on every other stage)
                is_out = valid & (stage == S - 1)
                m_safe = jnp.clip(m, 0, M - 1)
                tgt = lax.dynamic_index_in_dim(tgt_mb, m_safe, 0, keepdims=False)
                w = lax.dynamic_index_in_dim(w_mb, m_safe, 0, keepdims=False)
                # per-sample weights broadcast over any token axes (e.g. the
                # sequence axis of a per-token LM loss)
                nll = nll_loss(logits, tgt, "none")
                wb = w.reshape(w.shape + (1,) * (nll.ndim - 1))
                per_tok = jnp.broadcast_to(wb, nll.shape)
                num_acc = num_acc + jnp.where(is_out, jnp.sum(nll * per_tok), 0.0)
                den_acc = den_acc + jnp.where(is_out, jnp.sum(per_tok), 0.0)
                # the hop: stage s -> s+1 over ICI; autodiff transposes this
                # into the backward s+1 -> s hop.
                wire = lax.ppermute(out, STAGE_AXIS, fwd)
                if loss_only:
                    return (wire, num_acc, den_acc, aux_acc), None
                if metrics:
                    # fold the microbatch's log-probs into the correct count
                    # right here — they never outlive this scan step. The
                    # count is int32 (exact to 2^31; a float32 running sum
                    # silently drops increments past 2^24 ≈ 16.7M tokens) and
                    # counts predictions whose weight is NONZERO — identical
                    # to the weighted sum for 0/1 validity masks, which is
                    # what a count of "correct predictions" means
                    hit = (logits.argmax(-1) == tgt) & (per_tok > 0)
                    correct_acc = correct_acc + jnp.where(
                        is_out, jnp.sum(hit.astype(jnp.int32)), 0)
                    return (wire, num_acc, den_acc, aux_acc, correct_acc), None
                prev = lax.dynamic_index_in_dim(logits_acc, m_safe, 0, keepdims=False)
                logits_acc = lax.dynamic_update_index_in_dim(
                    logits_acc, jnp.where(is_out, logits, prev), m_safe, 0)
                return (wire, num_acc, den_acc, aux_acc, logits_acc), None

            # the init carry is device-uniform but the loop body makes it
            # vary over every mesh axis (the row over data/stage/model/
            # expert, inputs over data, seq-sharded tokens over seq), the
            # row's cotangent included: it is reduced over data after the
            # reversed scan, not in it. pcast aligns the
            # carry types for check_vma. The scalar accumulators ride as
            # shape-(1,) arrays: scan-resident rank-0 carries trip the
            # scalar-residual promotion of older jax's shard_map partial
            # eval, and the singleton axis is free either way
            init0 = (jnp.zeros((mb, wire_dim), x_mb.dtype),
                     jnp.zeros((1,), jnp.float32),
                     jnp.zeros((1,), jnp.float32),
                     jnp.zeros((1,), jnp.float32))
            if metrics:
                init0 += (jnp.zeros((1,), jnp.int32),)
            elif not loss_only:
                init0 += (jnp.zeros((M, mb) + out_shape, jnp.float32),)
            init = jax.tree.map(lambda a: _pvary_to(a, vary_axes), init0)
            carry_out, _ = lax.scan(step, init, jnp.arange(T))
            if loss_only:
                _, num, den, aux = carry_out
            elif metrics:
                _, num, den, aux, correct = carry_out
                correct = correct[0]
            else:
                _, num, den, aux, logits_acc = carry_out
            num, den, aux = num[0], den[0], aux[0]

            # weighted global mean: sum(w * nll) / sum(w), reduced over the
            # stage axis (only the last stage contributed), the data axis,
            # and — for a seq-sharded token axis — the seq axis.
            num = lax.psum(lax.psum(num, STAGE_AXIS), DATA_AXIS)
            den = lax.psum(lax.psum(den, STAGE_AXIS), DATA_AXIS)
            if seq_on:
                num = lax.psum(num, SEQ_AXIS)
                den = lax.psum(den, SEQ_AXIS)
            if metrics:
                # correct reduces exactly like num: only the last stage
                # contributed, data (and seq) shards partition the samples
                # (tokens), model/expert slots replicate. The replication
                # proof over model/expert stays integer-exact as psum//size
                # (identical replicas sum to size*v) instead of a float pmean
                correct = lax.psum(lax.psum(correct, STAGE_AXIS), DATA_AXIS)
                if seq_on:
                    correct = lax.psum(correct, SEQ_AXIS)
                num = lax.pmean(num, MODEL_AXIS)
                den = lax.pmean(den, MODEL_AXIS)
                correct = lax.psum(correct, MODEL_AXIS) // n_model
                if self._has_expert:
                    num = lax.pmean(num, EXPERT_AXIS)
                    den = lax.pmean(den, EXPERT_AXIS)
                    correct = lax.psum(correct, EXPERT_AXIS) // n_expert
                return num, den, correct
            # model-axis replication proof for check_vma: every model slot
            # computed the same value (replicated stages run redundantly; TP
            # stages end each pair in their own psum), so pmean is the
            # identity value-wise — and gradient-wise: its transpose hands
            # each replica ct/n_model, exactly what the implicit replicated
            # out_spec did, which grad_sync already compensates for.
            num = lax.pmean(num, MODEL_AXIS)
            den = lax.pmean(den, MODEL_AXIS)
            # auxiliary losses: summed over stages (each MoE stage adds its
            # layers' terms), averaged UNWEIGHTED over microbatches — sample
            # weights scale the NLL term only (see loss_and_logits docstring);
            # data/seq/expert shards each routed a different token subset, so
            # averaging over them matches the dense "mean over all routing
            # groups"; model replicas are identical (pmean = replication
            # proof).
            aux = lax.psum(aux, STAGE_AXIS) / M
            aux = lax.pmean(lax.pmean(aux, DATA_AXIS), MODEL_AXIS)
            if seq_on:
                aux = lax.pmean(aux, SEQ_AXIS)
            if self._has_expert:
                aux = lax.pmean(aux, EXPERT_AXIS)
                num = lax.pmean(num, EXPERT_AXIS)
                den = lax.pmean(den, EXPERT_AXIS)
            loss = num / jnp.maximum(den, 1e-12) + aux
            if loss_only:
                return loss
            # logits stay seq-sharded (the out_spec reassembles the token
            # axis); only the stage/model/expert axes are reduced away
            logits = lax.pmean(                            # replicate last stage's
                lax.psum(logits_acc, STAGE_AXIS), MODEL_AXIS)
            if self._has_expert:
                logits = lax.pmean(logits, EXPERT_AXIS)
            return loss, logits

        # activations/targets are replicated over the model axis (left
        # unmentioned); TP stages shard their compute internally and restore
        # replication with their own psums. On a seq mesh, the wire's feature
        # axis is sharded over seq (the host packs one contiguous
        # wire_dim-wide chunk per seq shard), and the targets'/logits' token
        # axis (axis 0 of out_shape) is sharded over seq directly.
        tok_axes = len(self.out_shape) - 1
        seq_or_none = SEQ_AXIS if seq_on else None
        tgt_tok = ((seq_or_none,) + (None,) * (tok_axes - 1)
                   if tok_axes else ())
        fn = _shard_map(
            per_device,
            mesh=self.mesh,
            in_specs=(self.param_spec(),
                      P(None, DATA_AXIS, seq_or_none),
                      P(None, DATA_AXIS, *tgt_tok),
                      P(None, DATA_AXIS), P()),
            out_specs=(P() if loss_only
                       else (P(), P(), P()) if metrics
                       else (P(), P(None, DATA_AXIS, *tgt_tok, None))),
        )
        self._sm_cache[cache_key] = fn
        return fn

    def loss_and_logits(self, buf: jax.Array, x: jax.Array, targets: jax.Array,
                        key: jax.Array, deterministic: bool = False,
                        weights: jax.Array | None = None
                        ) -> tuple[jax.Array, jax.Array]:
        """Weighted-mean NLL loss + per-example log-probs for a global batch.

        ``x``: [B, ...] model input (stage 0's real input shape);
        ``targets``: [B] int labels; ``weights``: optional [B] per-sample loss
        weights (e.g. a 0/1 validity mask for a zero-padded ragged batch —
        loss = sum(w·nll)/sum(w), so padding does not dilute the mean). B must
        divide by ``n_microbatches * n_data``.

        ``weights`` applies to the NLL term ONLY. MoE auxiliary
        (load-balancing) losses are accumulated unweighted — a uniform mean
        over microbatches — exactly as the dense path computes aux over the
        full batch including zero-weight rows: router balance is a property
        of every token that was dispatched, padding included, so weighting it
        would let padded batches skew expert utilisation pressure
        (pinned by tests/test_expert_pipeline.py::
        test_weighted_loss_applies_to_nll_only).
        """
        if self._trivial_mesh():
            return self._fused_loss(buf, x, targets, key, deterministic,
                                    weights)
        xw, tgt, w = self._prep_inputs(x, targets, weights)
        loss, logits = self._shard_fn(deterministic)(buf, xw, tgt, w, key)
        return loss, logits.reshape((x.shape[0],) + self.out_shape)

    def eval_metrics(self, buf: jax.Array, x: jax.Array, targets: jax.Array,
                     key: jax.Array, weights: jax.Array | None = None
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """``(sum_nll, sum_weight, correct)`` — the memory-flat eval path.

        ``sum(w·nll)`` and ``sum(w)`` are weighted sums over the global
        batch with ``w`` broadcast over any token axes (so a per-sample 0/1
        validity mask zeroes padded rows of a ragged batch); ``correct`` is
        the int32 COUNT of predictions with ``argmax == target`` among
        nonzero-weight entries — an integer accumulation exact to 2^31
        (a float32 weighted sum would silently stop counting past ~16.7M).
        Always deterministic (dropout off — deliberately NOT the reference's
        eval-dropout quirk, SURVEY §3.5).

        Unlike ``loss_and_logits``, nothing ``[batch, *out_shape]``-sized is
        materialized, carried, or psum'd: each last-stage microbatch's
        log-probs fold into the three scalars inside the scan step. For a
        vocab-wide LM the logits accumulator is the dominant eval
        activation — this path removes it, so eval fits wherever training
        fits (``make_eval_step`` builds on this).
        """
        if self._trivial_mesh():
            logp, _ = self._fused_logits(buf, x, key, True)
            num, den, wb = _weighted_nll_sums(logp, targets, weights)
            hit = (logp.argmax(-1) == targets) & (wb > 0)
            return num, den, jnp.sum(hit.astype(jnp.int32))
        xw, tgt, w = self._prep_inputs(x, targets, weights)
        return self._shard_fn(deterministic=True, metrics=True)(
            buf, xw, tgt, w, key)

    def loss(self, buf: jax.Array, x: jax.Array, targets: jax.Array,
             key: jax.Array, deterministic: bool = False,
             weights: jax.Array | None = None) -> jax.Array:
        """Scalar loss only — the training path.

        Same math as ``loss_and_logits(...)[0]`` (same RNG stream, same
        gradients) but the engine skips the per-microbatch log-probs
        accumulator entirely: nothing [batch, *out_shape]-sized rides the
        scan carry or is psum'd across stages. For a language model that is
        the difference between carrying [B, T, vocab] on every device and
        carrying two scalars.
        """
        if self._trivial_mesh():
            return self._fused_loss(buf, x, targets, key, deterministic,
                                    weights)[0]
        xw, tgt, w = self._prep_inputs(x, targets, weights)
        return self._shard_fn(deterministic, loss_only=True)(
            buf, xw, tgt, w, key)

    def loss_and_grads(self, buf: jax.Array, x: jax.Array,
                       targets: jax.Array, key: jax.Array,
                       deterministic: bool = False,
                       weights: jax.Array | None = None
                       ) -> tuple[jax.Array, jax.Array]:
        """Scalar loss + packed-buffer gradients — the training contract.

        ``schedule='gpipe'`` (default): ``jax.value_and_grad`` over the
        scanned loss-only engine (XLA reverses the scan; all ``M``
        microbatch residuals are alive between the sweeps).
        ``schedule='1f1b'``: the hand-scheduled interleave in ``onefb.py``
        — same loss/gradients (parity-tested), activation memory bounded by
        the topology ``S`` instead of ``M``.
        """
        if self.schedule == "1f1b" and not self._trivial_mesh():
            from simple_distributed_machine_learning_tpu.parallel.onefb import (
                build_1f1b_fn,
            )
            cache_key = ("1f1b", deterministic)
            if cache_key not in self._sm_cache:
                self._sm_cache[cache_key] = build_1f1b_fn(self, deterministic)
            xw, tgt, w = self._prep_inputs(x, targets, weights)
            return self._sm_cache[cache_key](buf, xw, tgt, w, key)

        def loss_fn(b):
            return self.loss(b, x, targets, key, deterministic=deterministic,
                             weights=weights)
        return jax.value_and_grad(loss_fn)(buf)

    def _trivial_mesh(self) -> bool:
        """Degenerate single-device mesh: the pipeline IS the fused model.
        Skip the shard_map engine: its scan, switch and wire codec have
        nothing to schedule or overlap on one device. Both paths
        differentiate through the same ``unpack_stage_params`` (one split,
        whose transpose is one concatenate of the leaf cotangents)."""
        return (self.n_stages == 1 and self.n_data == 1 and self.n_model == 1
                and self.n_seq == 1 and self.n_expert == 1
                and self.stages[0].shards is None
                and self.stages[0].expert_shards is None)

    def _prep_inputs(self, x, targets, weights):
        """Host-side packing: microbatch split + wire encoding of the global
        batch (seq-sharded wires are chunked token-major per shard)."""
        import jax.numpy as jnp

        M = self.n_microbatches
        B = x.shape[0]
        if B % (M * self.n_data) != 0:
            raise ValueError(
                f"batch {B} not divisible by microbatches*data = {M * self.n_data}")
        # the wire is always float32 (stages decode/cast as needed — e.g. the
        # GPT embedding stage reads token ids back out of the float wire)
        if self.n_seq > 1:
            # seq-sharded wire: chunk the token axis (axis 0 of the
            # per-sample shape, so the flatten is token-major and each chunk
            # is contiguous), pad each chunk to the LOCAL wire width, and lay
            # the chunks side by side — the shard_map in_spec then hands each
            # seq shard exactly its own wire_dim-wide chunk.
            chunks = jnp.reshape(x, (B, self.n_seq, -1))
            pad = self.wire_dim - chunks.shape[-1]
            if pad < 0:
                raise ValueError(
                    f"per-shard activation width {chunks.shape[-1]} exceeds "
                    f"wire_dim {self.wire_dim}")
            xw = jnp.pad(chunks, ((0, 0), (0, 0), (0, pad)))
        else:
            xw = wire_encode(x, self.wire_dim)
        xw = xw.astype(jnp.float32).reshape(
            M, B // M, self.n_seq * self.wire_dim)
        tgt = targets.reshape((M, B // M) + self.out_shape[:-1])
        w = (jnp.ones((B,), jnp.float32) if weights is None
             else weights.astype(jnp.float32)).reshape(M, B // M)
        return xw, tgt, w

    def _fused_logits(self, buf, x, key, deterministic):
        """Single-device forward: ``(log_probs, aux)`` from the fused stage.
        Same RNG stream as the engine's stage-0 key at step 0, data shard 0."""
        B = x.shape[0]
        stage = self.stages[0]
        params = unpack_stage_params(buf[0, 0, 0], self.metas[0])
        xs = x.reshape((B,) + tuple(stage.in_shape))
        if self.compute_dtype is not None:
            params = jax.tree.map(
                lambda a: a.astype(self.compute_dtype), params)
            xs = stage.cast_input(xs, self.compute_dtype)
        k = jax.random.fold_in(
            jax.random.fold_in(jax.random.fold_in(key, 0), 0), 0)
        out = stage.apply(params, xs, k, deterministic)
        aux = jnp.float32(0.0)
        if isinstance(out, tuple):
            out, aux = out
            aux = aux.astype(jnp.float32)
        return out.astype(jnp.float32), aux

    def _fused_loss(self, buf, x, targets, key, deterministic, weights):
        """Single-device fast path. Identical to the engine for
        ``n_microbatches == 1`` or deterministic mode; with several
        microbatches AND dropout the engine draws per-microbatch noise while
        this path draws one batch-wide key — same distribution, different
        stream."""
        logp, aux = self._fused_logits(buf, x, key, deterministic)
        num, den, _ = _weighted_nll_sums(logp, targets, weights)
        return num / jnp.maximum(den, 1e-12) + aux, logp


def _weighted_nll_sums(logp, targets, weights):
    """``(sum(w·nll), sum(w), wb)`` with per-sample ``weights`` (or ones)
    broadcast over token axes — the one copy of the weighted-metrics
    arithmetic shared by the fused loss and eval paths."""
    nll = nll_loss(logp, targets, "none")
    w = (jnp.ones((logp.shape[0],), jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    wb = jnp.broadcast_to(
        w.reshape(w.shape + (1,) * (nll.ndim - 1)), nll.shape)
    return jnp.sum(nll * wb), jnp.sum(wb), wb


def fused_reference(stages: Sequence[Stage]) -> Callable:
    """Single-device composition of the stages (ground truth for parity tests:
    the pipeline on N devices must match this to float tolerance, SURVEY §7)."""
    def apply(stage_params: Sequence[Any], x: jax.Array, key: jax.Array,
              deterministic: bool = False) -> jax.Array:
        h = x
        for s, (stage, params) in enumerate(zip(stages, stage_params)):
            k = jax.random.fold_in(key, s)
            h = h.reshape((h.shape[0],) + stage.in_shape)
            h = stage.apply(params, h, k, deterministic)
            if isinstance(h, tuple):    # (y, aux): ground truth drops aux
                h = h[0]
        return h
    return apply
