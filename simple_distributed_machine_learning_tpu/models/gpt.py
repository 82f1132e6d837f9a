"""Tiny GPT as pipeline stages (BASELINE.json config 5).

A decoder-only transformer LM — token+position embeddings, pre-LN blocks
(causal MHA + GELU MLP), final LN + untied head + log_softmax — expressed in
the same :class:`~..parallel.pipeline.Stage` form as MLP/LeNet, so the exact
GPipe/ppermute machinery that runs the reference's conv↔fc split also runs a
transformer with per-token next-token loss.

The reference has no attention or sequence models at all (SURVEY §5.7); this
is pure capability extension mandated by the driver's config 5 ("2-layer
tiny-GPT d=128, 2-stage pipeline with GPipe microbatching").

Wire notes: stage 0 consumes tokens (cast to float on the wire, exact for any
realistic vocab), emits the [T, d] hidden state; the last stage emits [T, V]
log-probs. The engine's per-token loss path (``Pipeline(out_dim=(T, V))``)
averages NLL over batch and sequence.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from simple_distributed_machine_learning_tpu.ops.attention import (
    _merge_heads,
    _split_heads,
    causal_attention,
    causal_attention_core,
    mha_init,
)
from simple_distributed_machine_learning_tpu.ops.layers import (
    dropout,
    embedding_init,
    embedding_lookup,
    layer_norm,
    layer_norm_init,
    linear,
    linear_init,
)
from simple_distributed_machine_learning_tpu.models.lora import lora_delta
from simple_distributed_machine_learning_tpu.models.serving import (
    NEWEST_PAIR,
    # tests/bench_cells/test_bench_cells_ahead.py and
    # test_bench_cells_program_spans.py import this name from here
    SEAT_SAMPLE,
    PagedServing,
    check_attn_kernel,
    check_cache_quantization,
    feed_newest,
    filter_top_dyn,
    is_quantized_dtype,
    memo_build,
    merged_stage_trees,
    paged_attend,
    paged_gather,
    paged_scatter,
    sample_slot,
    sample_slots,
    seat_newest,
    storage_dtype,
)
from simple_distributed_machine_learning_tpu.ops.losses import log_softmax
from simple_distributed_machine_learning_tpu.parallel.pipeline import Stage


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab: int = 128
    seq_len: int = 64
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    mlp_ratio: int = 4
    dropout_rate: float = 0.0   # tiny-GPT default: no dropout
    # attention implementation:
    #   "dense"   — plain causal MHA (single-device math)
    #   "flash"   — Pallas fused kernel (ops/flash_attention.py)
    #   "ring"    — ring attention over the mesh's seq axis: K/V blocks
    #               rotate via ppermute (ops/attention.py); requires n_seq > 1
    #               to actually shard (falls back to dense math at n_seq=1)
    #   "ulysses" — DeepSpeed-Ulysses all-to-all head/sequence re-sharding
    #               (parallel/sequence.py); n_heads must divide by n_seq
    attn_impl: str = "dense"
    # Pallas flash kernel block sizes (attn_impl="flash" only): the tuned
    # values from benchmarks/flash_tune.py go here — bigger block_q cuts K/V
    # HBM passes, bigger block_k cuts grid steps (VMEM bounds both)
    flash_block_q: int = 128
    flash_block_k: int = 128
    # sequence parallelism: n_seq > 1 shards the token axis over the mesh's
    # "seq" axis — stage in_shapes, the wire, and all block compute are then
    # per-shard (seq_len / n_seq tokens); cross-token mixing happens only in
    # the attention collective chosen above.
    n_seq: int = 1
    # MoE: n_experts > 0 replaces each block's MLP with a mixture-of-experts
    # FFN (top-k routed, see parallel/expert.py). The Switch load-balancing
    # aux loss (scaled by moe_aux_weight) is returned alongside the stage
    # output and threaded into the pipeline objective by the engine.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    moe_aux_weight: float = 0.01
    # expert parallelism: n_expert_parallel > 1 shards each block's expert
    # weights over the mesh's "expert" axis (E / n_ep experts per device) and
    # splits the microbatch's sequences across it — each device routes its
    # own sequences, the 2x all-to-all inside moe_apply_ep ships capacity
    # buffers to the expert owners, and an all_gather reassembles the batch.
    # Routing groups (one sequence each) are identical to the dense path, so
    # EP is numerically exact vs n_expert_parallel=1.
    n_expert_parallel: int = 1
    # tensor (Megatron) parallelism: n_tensor_parallel > 1 shards every
    # block's QKV/O projections (by head) and MLP hidden width over the
    # mesh's "model" axis. Init slices the same dense init, so a TP run
    # matches the dense run to float tolerance. Dense attention + dense MLP
    # blocks only (no MoE/seq-parallel/flash composition).
    n_tensor_parallel: int = 1
    # collective schedule for the TP all-reduces (and the EP dispatch):
    #   "none" — monolithic lax.psum / all_to_all: the chip blocks for the
    #            whole collective after the widest matmuls
    #   "ring" — ppermute-chunked latency-hiding collective matmuls
    #            (parallel/overlap.py): allgather_matmul + reduce-scatter
    #            ring through each block's MLP, chunked-psum ring on the
    #            attention output projection; same losses to float tolerance
    overlap: str = "none"

    # the serving engine's question of any model config: per-slot state
    # beside the K/V pool (models/jamba.py has it)
    recurrent_state = False

    def __post_init__(self):
        if self.attn_impl not in ("dense", "flash", "ring", "ulysses"):
            raise ValueError(
                f"attn_impl must be one of dense/flash/ring/ulysses, got "
                f"{self.attn_impl!r}")
        if self.flash_block_q < 1 or self.flash_block_k < 1:
            raise ValueError(
                f"flash blocks must be positive, got "
                f"{self.flash_block_q}/{self.flash_block_k}")
        if self.n_seq < 1 or self.seq_len % self.n_seq:
            raise ValueError(
                f"seq_len {self.seq_len} not divisible by n_seq {self.n_seq}")
        if self.n_seq > 1 and self.attn_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"n_seq={self.n_seq} needs a sequence-parallel attention "
                f"(ring or ulysses), got {self.attn_impl!r}")
        if (self.attn_impl == "ulysses" and self.n_seq > 1
                and self.n_heads % self.n_seq):
            raise ValueError(
                f"ulysses needs n_heads ({self.n_heads}) divisible by "
                f"n_seq ({self.n_seq})")
        if self.n_experts < 0 or (self.n_experts > 0 and not
                                  1 <= self.moe_top_k <= self.n_experts):
            raise ValueError(
                f"invalid MoE config: n_experts={self.n_experts}, "
                f"top_k={self.moe_top_k}")
        if self.n_expert_parallel < 1 or (
                self.n_expert_parallel > 1
                and (self.n_experts == 0
                     or self.n_experts % self.n_expert_parallel)):
            raise ValueError(
                f"n_expert_parallel={self.n_expert_parallel} needs "
                f"n_experts ({self.n_experts}) > 0 and divisible by it")
        if self.overlap not in ("none", "ring"):
            raise ValueError(
                f"overlap must be 'none' or 'ring', got {self.overlap!r}")
        ntp = self.n_tensor_parallel
        if ntp < 1:
            raise ValueError(f"n_tensor_parallel must be >= 1, got {ntp}")
        if ntp > 1:
            if self.n_heads % ntp:
                raise ValueError(
                    f"n_tensor_parallel={ntp} needs n_heads "
                    f"({self.n_heads}) divisible by it")
            if (self.mlp_ratio * self.d_model) % ntp:
                raise ValueError(
                    f"n_tensor_parallel={ntp} needs the MLP hidden width "
                    f"({self.mlp_ratio * self.d_model}) divisible by it")
            if self.attn_impl != "dense":
                raise ValueError(
                    f"tensor parallelism shards attention by head and "
                    f"computes dense math on the local heads; "
                    f"attn_impl={self.attn_impl!r} is not composable with it")
            if self.n_experts > 0 or self.n_expert_parallel > 1:
                raise ValueError(
                    "a stage cannot be both tensor- and expert-sharded "
                    "(Stage.shards vs expert_shards): use n_tensor_parallel "
                    "with dense-MLP blocks only")
            if self.n_seq > 1:
                raise ValueError(
                    "n_tensor_parallel > 1 with n_seq > 1 is not supported "
                    "(the wire's token sharding and the TP row scatter "
                    "would both claim the token axis)")

    def paged_serving(self, stages, max_len: int, block_size: int,
                      cache_dtype=None, mesh=None, kernel: str = "dense",
                      adapters: bool = False) -> PagedServing:
        """The engine's model interface (:class:`PagedServing`): every
        block is an attention layer with ``n_heads`` K/V heads, and beside
        its blocks a slot has its newest token and sampling key and nothing
        else (the programs feed them back on the device)."""
        return PagedServing(
            kv_layers=sum(len(s.params["blocks"]) for s in stages),
            kv_heads=self.n_heads, head_dim=self.d_model // self.n_heads,
            state_shapes=(NEWEST_PAIR,),
            chunk_prefill=make_paged_prefill_chunk(
                stages, self, max_len, block_size, cache_dtype, mesh=mesh,
                adapters=adapters),
            decode=make_paged_decode_step(
                stages, self, max_len, block_size, cache_dtype, mesh=mesh,
                kernel=kernel, adapters=adapters),
            pack_decode=_leave_host_pair_behind)


def _block_init(key: jax.Array, cfg: GPTConfig) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    d, dh = cfg.d_model, cfg.mlp_ratio * cfg.d_model
    p = {
        "ln1": layer_norm_init(d),
        "attn": mha_init(k1, d, cfg.n_heads),
        "ln2": layer_norm_init(d),
    }
    if cfg.n_experts > 0:
        from simple_distributed_machine_learning_tpu.parallel.expert import (
            moe_init,
        )
        p["moe"] = moe_init(k2, d, dh, cfg.n_experts)
    else:
        p["mlp_in"] = linear_init(k2, d, dh)
        p["mlp_out"] = linear_init(k3, dh, d)
    return p


def _block_apply(params: dict, h: jax.Array, cfg: GPTConfig, key: jax.Array,
                 deterministic: bool) -> tuple[jax.Array, jax.Array]:
    """One transformer block. Returns ``(h, aux)`` — aux is the block's MoE
    load-balancing loss (0 for a dense MLP block)."""
    k1, k2 = jax.random.split(key)
    hn1 = layer_norm(params["ln1"], h)
    if cfg.attn_impl == "flash":
        from simple_distributed_machine_learning_tpu.ops.flash_attention import (
            flash_mha,
        )
        a = flash_mha(params["attn"], hn1, cfg.n_heads,
                      block_q=cfg.flash_block_q, block_k=cfg.flash_block_k)
    elif cfg.attn_impl == "ring" and cfg.n_seq > 1:
        from simple_distributed_machine_learning_tpu.ops.attention import (
            SEQ_AXIS,
            ring_attention,
        )
        a = ring_attention(params["attn"], hn1, cfg.n_heads, axis=SEQ_AXIS)
    elif cfg.attn_impl == "ulysses" and cfg.n_seq > 1:
        from simple_distributed_machine_learning_tpu.parallel.sequence import (
            ulysses_attention,
        )
        a = ulysses_attention(params["attn"], hn1, cfg.n_heads)
    else:
        # dense — also the n_seq == 1 degenerate case of ring/ulysses
        # (identical math on the whole sequence)
        a = causal_attention(params["attn"], hn1, cfg.n_heads)
    a = dropout(k1, a, cfg.dropout_rate, deterministic)
    h = h + a
    hn = layer_norm(params["ln2"], h)
    aux = jnp.float32(0.0)
    if cfg.n_experts > 0:
        from simple_distributed_machine_learning_tpu.parallel.expert import (
            EXPERT_AXIS,
            default_capacity,
            moe_apply,
            moe_apply_ep,
        )
        # route per sequence (vmap over batch): keeps the [T, E, C] dispatch
        # tensors at seq_len scale instead of batch*seq_len (C grows with the
        # routed group size, so global routing would cost O((B*T)^2/E))
        cap = default_capacity(hn.shape[1], cfg.n_experts, cfg.moe_top_k,
                               cfg.moe_capacity_factor)
        if cfg.n_expert_parallel > 1:
            # expert-parallel: each expert-axis device takes its slice of the
            # microbatch's SEQUENCES (routing groups identical to dense),
            # runs the 2x-all-to-all EP FFN on its E/D expert shard, and the
            # all_gather reassembles the batch (replicated again)
            D = cfg.n_expert_parallel
            b = hn.shape[0]
            if b % D:
                raise ValueError(
                    f"microbatch of {b} sequences not divisible by "
                    f"n_expert_parallel={D}")
            nb = b // D
            i = jax.lax.axis_index(EXPERT_AXIS)
            hn_loc = jax.lax.dynamic_slice_in_dim(hn, i * nb, nb, 0)
            m_loc, aux_v = jax.vmap(
                lambda t: moe_apply_ep(params["moe"], t, k=cfg.moe_top_k,
                                       capacity=cap,
                                       overlap=cfg.overlap))(hn_loc)
            aux = jnp.mean(aux_v)   # already pmean'd over the expert axis
            m = jax.lax.all_gather(m_loc, EXPERT_AXIS, axis=0, tiled=True)
        else:
            m, aux_v = jax.vmap(
                lambda t: moe_apply(params["moe"], t, k=cfg.moe_top_k,
                                    capacity=cap))(hn)
            aux = jnp.mean(aux_v)
    else:
        m = linear(params["mlp_out"], jax.nn.gelu(linear(params["mlp_in"], hn)))
    m = dropout(k2, m, cfg.dropout_rate, deterministic)
    return h + m, aux


def _slice_tp_block(bp: dict, m: int, mp: int) -> dict:
    """Model-shard ``m``'s slice of one dense block's params (Megatron):
    QKV columns / O rows by head, MLP hidden width column→row; norms and the
    MLP output bias replicated. Slicing the SAME dense init keeps a TP run
    numerically identical to the dense run (tests/test_overlap.py)."""
    d = bp["attn"]["wq"].shape[0]
    dc = d // mp                      # head-aligned qkv column chunk
    hc = bp["mlp_in"]["w"].shape[1] // mp
    return {
        "ln1": bp["ln1"],
        "attn": {"wq": bp["attn"]["wq"][:, m * dc:(m + 1) * dc],
                 "wk": bp["attn"]["wk"][:, m * dc:(m + 1) * dc],
                 "wv": bp["attn"]["wv"][:, m * dc:(m + 1) * dc],
                 "wo": bp["attn"]["wo"][m * dc:(m + 1) * dc, :]},
        "ln2": bp["ln2"],
        "mlp_in": {"w": bp["mlp_in"]["w"][:, m * hc:(m + 1) * hc],
                   "b": bp["mlp_in"]["b"][m * hc:(m + 1) * hc]},
        "mlp_out": {"w": bp["mlp_out"]["w"][m * hc:(m + 1) * hc, :],
                    "b": bp["mlp_out"]["b"]},
    }


def _slice_tp_stage(params: dict, m: int, mp: int) -> dict:
    """Model-shard ``m``'s stage tree: blocks sliced, embed/head replicated
    (stored per-shard like the MLP TP pair's output bias — grad_sync'd)."""
    out = {"blocks": [_slice_tp_block(bp, m, mp) for bp in params["blocks"]]}
    for k in ("embed", "head"):
        if k in params:
            out[k] = params[k]
    return out


def _is_tp_sharded_leaf(path) -> bool:
    """True for leaves genuinely split across the model axis — their grads
    arrive through the TP collectives' transposes; everything else (norms,
    the MLP output bias, embed, head) is replicated-in-sharded-storage and
    needs grad_sync over the model axis."""
    keys = [getattr(p, "key", None) for p in path]
    if "attn" in keys or "mlp_in" in keys:
        return True
    return "mlp_out" in keys and keys[-1] == "w"


def _grad_sync_non_tp(params: dict, overlap: str) -> dict:
    import jax.tree_util as jtu

    from simple_distributed_machine_learning_tpu.parallel.mesh import (
        MODEL_AXIS,
    )
    from simple_distributed_machine_learning_tpu.parallel.tensor import (
        grad_sync,
    )
    return jtu.tree_map_with_path(
        lambda path, leaf: (leaf if _is_tp_sharded_leaf(path)
                            else grad_sync(leaf, MODEL_AXIS, overlap)),
        params)


def _block_apply_tp(params: dict, h: jax.Array, cfg: GPTConfig,
                    key: jax.Array, deterministic: bool) -> jax.Array:
    """One transformer block, tensor-parallel over the model axis — call
    inside ``shard_map``. ``params`` is THIS shard's slice
    (:func:`_slice_tp_block`); ``h`` is replicated and the return is too.

    Attention: QKV project onto the local ``H/mp`` heads (column shards are
    head-aligned), dense causal math runs on them, and the output projection
    is row-parallel — closed by ``lax.psum`` (``overlap='none'``) or the
    chunked-psum ring of :func:`~..parallel.overlap.ring_psum`.

    MLP with ``overlap='ring'`` runs the full scattered collective-matmul
    pair: each device takes its ``1/mp`` row slice of the (replicated)
    tokens, :func:`~..parallel.overlap.allgather_matmul` re-gathers them
    chunk-by-chunk under the column matmul,
    :func:`~..parallel.overlap.matmul_reducescatter` ring-accumulates the
    row matmul's partial products, and a ring all-gather restores
    replication — every hop hidden under a chunk's compute, forward and
    backward (the custom_vjp mirrors). Falls back to the chunked-psum form
    when the token count does not divide by ``mp``. ``overlap='none'`` is
    the monolithic Megatron schedule (one blocking psum).
    """
    from jax import lax

    from simple_distributed_machine_learning_tpu.parallel.compat import (
        pvary_to,
        vma_of,
    )
    from simple_distributed_machine_learning_tpu.parallel.mesh import (
        MODEL_AXIS,
    )
    from simple_distributed_machine_learning_tpu.parallel.overlap import (
        allgather_matmul,
        matmul_reducescatter,
        ring_all_gather,
        ring_psum,
    )

    mp = cfg.n_tensor_parallel
    ring = cfg.overlap == "ring"
    axis = MODEL_AXIS

    def reduce_full(z):
        # replicated all-reduce of a row-parallel product, typed to match
        # the (varying) residual stream for the vma checker
        red = ring_psum(z, axis) if ring else lax.psum(z, axis)
        return pvary_to(red, tuple(vma_of(h)))

    k1, k2 = jax.random.split(key)
    hn = layer_norm(params["ln1"], h)
    h_loc = cfg.n_heads // mp
    q = _split_heads(hn @ params["attn"]["wq"], h_loc)
    k_ = _split_heads(hn @ params["attn"]["wk"], h_loc)
    v = _split_heads(hn @ params["attn"]["wv"], h_loc)
    a = _merge_heads(causal_attention_core(q, k_, v))      # [B, T, d/mp]
    a = reduce_full(a @ params["attn"]["wo"])
    h = h + dropout(k1, a, cfg.dropout_rate, deterministic)

    hn2 = layer_norm(params["ln2"], h)
    b, t, d = hn2.shape
    rows = hn2.reshape(b * t, d)
    if ring and (b * t) % mp == 0:
        n_loc = (b * t) // mp
        i = lax.axis_index(axis)
        x_shard = lax.dynamic_slice_in_dim(rows, i * n_loc, n_loc, 0)
        mid = jax.nn.gelu(
            allgather_matmul(x_shard, params["mlp_in"]["w"], axis)
            + params["mlp_in"]["b"])
        y_shard = matmul_reducescatter(mid, params["mlp_out"]["w"], axis)
        m = (ring_all_gather(y_shard, axis).reshape(b, t, d)
             + params["mlp_out"]["b"])
        m = pvary_to(m, tuple(vma_of(h)))
    else:
        mid = jax.nn.gelu(rows @ params["mlp_in"]["w"]
                          + params["mlp_in"]["b"])
        m = reduce_full((mid @ params["mlp_out"]["w"]).reshape(b, t, d))
        m = m + params["mlp_out"]["b"]
    return h + dropout(k2, m, cfg.dropout_rate, deterministic)


def make_gpt_stages(key: jax.Array, cfg: GPTConfig = GPTConfig(),
                    n_stages: int = 2) -> tuple[list[Stage], int, tuple[int, int]]:
    """Build the GPT as ``n_stages`` pipeline stages.

    Blocks are split contiguously; stage 0 additionally owns the embeddings,
    the last stage owns the final LN + head. Returns
    ``(stages, wire_dim, (seq_len, vocab))`` — pass the tuple as the
    Pipeline's ``out_dim`` for the per-token loss.

    With ``cfg.n_seq > 1`` the stages are sequence-parallel: in_shapes and
    ``wire_dim`` are per-seq-shard sizes (``seq_len / n_seq`` tokens), the
    embedding stage offsets its positional slice by the shard's global
    position, and attention runs as the configured seq collective. Build the
    Pipeline on a ``make_mesh(..., n_seq=cfg.n_seq)`` mesh; the returned
    out_dim stays GLOBAL — the engine reassembles the token axis.

    With ``cfg.n_tensor_parallel > 1`` the stages are tensor-parallel
    (Megatron): every block's QKV/O projections shard by head and the MLP
    hidden width column→row over the mesh's ``model`` axis
    (``Stage.shards``), with ``cfg.overlap`` choosing the collective
    schedule (monolithic psum vs the latency-hiding ppermute rings of
    ``parallel/overlap.py``). Build on a ``make_mesh(...,
    n_model=cfg.n_tensor_parallel)`` mesh. Single-device decode helpers
    (``generate``/``make_decoder``/``fused_reference``) need an unsharded
    build of the same weights — the same restriction as ``n_seq > 1``.
    """
    if cfg.n_layers < n_stages and not (n_stages == 1 and cfg.n_layers == 0):
        raise ValueError(
            f"{cfg.n_layers} layers cannot fill {n_stages} stages")
    ke, kp, kh, *kb = jax.random.split(key, 3 + cfg.n_layers)
    embed = {"tok": embedding_init(ke, cfg.vocab, cfg.d_model),
             "pos": 0.02 * jax.random.normal(kp, (cfg.seq_len, cfg.d_model))}
    blocks = [_block_init(kb[i], cfg) for i in range(cfg.n_layers)]
    head = {"ln_f": layer_norm_init(cfg.d_model),
            "out": linear_init(kh, cfg.d_model, cfg.vocab)}

    from simple_distributed_machine_learning_tpu.parallel.staging import (
        contiguous_split,
    )
    block_split = (contiguous_split(blocks, n_stages) if blocks
                   else [[] for _ in range(n_stages)])
    t_loc = cfg.seq_len // cfg.n_seq        # tokens per seq shard

    stages: list[Stage] = []
    for s in range(n_stages):
        stage_blocks = block_split[s]
        first, last = s == 0, s == n_stages - 1
        params: dict = {"blocks": stage_blocks}
        if first:
            params["embed"] = embed
        if last:
            params["head"] = head

        def apply(params, x, key, deterministic,
                  _first=first, _last=last, _n=len(stage_blocks)):
            if cfg.n_expert_parallel > 1:
                # this stage's storage row is expert-sharded: expert weights
                # are genuinely per-device, everything else (router, attn,
                # norms, embed/head) is replicated-in-sharded-storage and
                # needs grad_sync over the expert axis to receive its full
                # gradient on every replica
                params = _grad_sync_non_expert(params)
            if cfg.n_tensor_parallel > 1:
                # likewise for a tensor-sharded row: QKV/O and MLP weights
                # are genuinely per-device (their grads arrive through the
                # TP collectives' transposes); norms, the MLP output bias,
                # embed and head are replicated-in-sharded-storage
                params = _grad_sync_non_tp(params, cfg.overlap)
            if _first:
                ids = x.astype(jnp.int32)                     # tokens on the wire
                pos = params["embed"]["pos"]
                if cfg.n_seq > 1:
                    # this shard holds global positions [i*t_loc, (i+1)*t_loc)
                    from simple_distributed_machine_learning_tpu.ops.attention import (
                        SEQ_AXIS,
                    )
                    off = jax.lax.axis_index(SEQ_AXIS) * t_loc
                    pos = jax.lax.dynamic_slice_in_dim(pos, off, t_loc, 0)
                h = embedding_lookup(params["embed"]["tok"], ids) + pos
            else:
                h = x                                         # [B, T_loc, d]
            aux = jnp.float32(0.0)
            for i in range(_n):
                if cfg.n_tensor_parallel > 1:
                    h = _block_apply_tp(params["blocks"][i], h, cfg,
                                        jax.random.fold_in(key, i),
                                        deterministic)
                else:
                    h, a = _block_apply(params["blocks"][i], h, cfg,
                                        jax.random.fold_in(key, i),
                                        deterministic)
                    aux = aux + a
            if _last:
                h = layer_norm(params["head"]["ln_f"], h)
                h = log_softmax(linear(params["head"]["out"], h))
            if cfg.n_experts > 0:
                return h, cfg.moe_aux_weight * aux
            return h

        in_shape = (t_loc,) if first else (t_loc, cfg.d_model)
        if cfg.n_expert_parallel > 1:
            shards = tuple(_slice_expert_shard(params, e, cfg)
                           for e in range(cfg.n_expert_parallel))
            stages.append(Stage(apply=apply, params=shards[0],
                                in_shape=in_shape, expert_shards=shards,
                                token_input=first))
        elif cfg.n_tensor_parallel > 1:
            # slice the SAME dense init per model shard (Megatron layout):
            # the TP pipeline matches the dense build to float tolerance
            shards = tuple(_slice_tp_stage(params, m, cfg.n_tensor_parallel)
                           for m in range(cfg.n_tensor_parallel))
            stages.append(Stage(apply=apply, params=shards[0],
                                in_shape=in_shape, shards=shards,
                                token_input=first))
        else:
            stages.append(Stage(apply=apply, params=params, in_shape=in_shape,
                                token_input=first))

    # the wire carries only INTER-stage activations ([t_loc, d_model] blocks
    # and the stage-0 token ids); the last stage's [t_loc, vocab] log-probs
    # are consumed locally by the engine's loss and never ride the ppermute
    # ring, so vocab never widens the wire
    wire_dim = t_loc * cfg.d_model
    return stages, wire_dim, (cfg.seq_len, cfg.vocab)


def _is_expert_leaf(path) -> bool:
    return any(getattr(p, "key", None) == "experts" for p in path)


def _slice_expert_shard(params: dict, e: int, cfg: GPTConfig) -> dict:
    """Expert-device ``e``'s param tree: blocks' ``experts`` leaves sliced
    ``[e*E/D, (e+1)*E/D)`` on their leading expert axis, all else shared."""
    import jax.tree_util as jtu

    per = cfg.n_experts // cfg.n_expert_parallel
    return jtu.tree_map_with_path(
        lambda path, leaf: (leaf[e * per:(e + 1) * per]
                            if _is_expert_leaf(path) else leaf),
        params)


def _grad_sync_non_expert(params: dict) -> dict:
    """grad_sync every leaf EXCEPT the expert weights over the expert axis
    (expert weights are genuinely sharded; their grads arrive through the
    all-to-all transposes)."""
    import jax.tree_util as jtu

    from simple_distributed_machine_learning_tpu.parallel.expert import (
        EXPERT_AXIS,
    )
    from simple_distributed_machine_learning_tpu.parallel.tensor import (
        grad_sync,
    )
    return jtu.tree_map_with_path(
        lambda path, leaf: (leaf if _is_expert_leaf(path)
                            else grad_sync(leaf, EXPERT_AXIS)),
        params)


def _filter_top(scaled: jax.Array, top_k: int | None,
                top_p: float | None) -> jax.Array:
    """Top-k / nucleus filtering on temperature-scaled log-probs [B, V].

    Masked tokens get -inf (zero probability under categorical). Applied
    after temperature scaling, top-k before top-p — the standard sampling
    pipeline. The top-1 token is always kept (top_p exclusive-cumsum rule),
    so the distribution can never become empty.
    """
    if top_k is not None and top_k > scaled.shape[-1]:
        raise ValueError(
            f"top_k={top_k} exceeds the row width {scaled.shape[-1]} "
            f"(the model's vocab)")
    if top_k is not None:
        kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]       # [B, 1]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    if top_p is not None:
        srt = jnp.flip(jnp.sort(scaled, axis=-1), axis=-1)    # descending
        p = jax.nn.softmax(srt, axis=-1)
        exclusive = jnp.cumsum(p, axis=-1) - p
        keep = exclusive < top_p                               # top-1 always
        thresh = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                         keepdims=True)
        scaled = jnp.where(scaled < thresh, -jnp.inf, scaled)
    return scaled


def _dense_qkv(bp, h, n_heads, ab=None):
    """ln1 + QKV projections of one dense block — the ONE copy shared by the
    cached and pipeline-parallel decoders (prefill and step), so their math
    can never drift apart.

    ``ab`` (optional): this layer's LoRA factors ``(aq, bq, av, bv)`` — the
    multi-tenant serving path's merge-free per-request delta,
    ``q += (hn @ aq) @ bq`` (same for v, k unadapted; classic LoRA
    targets). Factors are unbatched ``[d, r]`` in prefill (one request) or
    leading-``[S]``-batched in the ticks (each slot's own gathered
    adapter) — :func:`~.lora.lora_delta`'s matmul broadcasting covers
    both. The all-zero base row contributes an exact 0 delta, so base
    requests keep the adapter-free token stream."""
    hn = layer_norm(bp["ln1"], h)
    q = hn @ bp["attn"]["wq"]
    v = hn @ bp["attn"]["wv"]
    if ab is not None:
        aq, bq, av, bv = ab
        q = q + lora_delta(hn, aq, bq)
        v = v + lora_delta(hn, av, bv)
    return (_split_heads(q, n_heads),
            _split_heads(hn @ bp["attn"]["wk"], n_heads),
            _split_heads(v, n_heads))


def _adapter_layers(bank, aid):
    """Per-request adapter slices for the decode-path programs: gather
    row(s) ``aid`` (a traced scalar for one-request prefill, ``[S]`` for
    the batched ticks) from the stacked bank
    (``{"aq": [N, L, d, r], "bq": [N, L, r, d], "av": ..., "bv": ...}``)
    and return a per-layer lookup ``at(li) -> (aq, bq, av, bv)`` feeding
    :func:`_dense_qkv`. The gather is data — one compiled program serves
    any adapter mix per tick, and a bank-row hot-swap never retraces."""
    sel = {k: bank[k][aid] for k in ("aq", "bq", "av", "bv")}

    def at(li):
        return tuple(sel[k][..., li, :, :]
                     for k in ("aq", "bq", "av", "bv"))

    return at


def _dense_attn_tail(bp, h, a):
    """wo merge + residual + ln2 + MLP + residual (the dense block tail)."""
    h = h + _merge_heads(a) @ bp["attn"]["wo"]
    hn2 = layer_norm(bp["ln2"], h)
    return h + linear(bp["mlp_out"], jax.nn.gelu(linear(bp["mlp_in"], hn2)))


# -- tensor-parallel serving ------------------------------------------------
#
# The serving builders below accept a GPTConfig with n_tensor_parallel > 1:
# the same program math then runs inside shard_map over the mesh's "model"
# axis with the training path's Megatron layout — QKV/O head-sharded
# (_slice_tp_block slices the SAME dense weights, so a TP engine serves the
# identical model the dense build trains and solo-decodes), the MLP as the
# column→row collective pair of tensor.tp_pair_apply (overlap='ring'|'none'
# knob included), and the K/V pool sharded over its HEAD axis so per-chip
# cache bytes drop by tp. Stages stay the UNSHARDED dense build — the
# serving layer slices per shard itself (pack_tp_serve_params), which keeps
# checkpoint restore and the solo-decode parity anchor on one weight set.


def pack_tp_serve_params(params_list, tp: int):
    """Slice dense per-stage trees into the TP serving layout:
    ``([stacked per-layer block trees], {"embed": ..., "head": ...})`` —
    leaf i of a stacked block tree is shard i's Megatron slice (leading
    axis ``tp``, placed ``P('model')`` by the engine); embed and head are
    replicated. The slices are exactly :func:`_slice_tp_block`'s, so a TP
    engine serves the identical model."""
    embed, blocks, head = merged_stage_trees(params_list)
    stacked = [jax.tree.map(lambda *ls: jnp.stack(ls),
                            *[_slice_tp_block(bp, m, tp) for m in range(tp)])
               for bp in blocks]
    return stacked, {"embed": embed, "head": head}


def _tp_local_trees(params):
    """Inside the serving shard_map: this shard's block slices (the stacked
    leading axis arrives split to size 1 by the ``P('model')`` in_spec) and
    the replicated embed/head."""
    stacked, rep = params
    blocks = [jax.tree.map(lambda leaf: leaf[0], bp) for bp in stacked]
    return blocks, rep["embed"], rep["head"]


def _tp_attn_tail(bp, h, a, overlap="none"):
    """TP twin of :func:`_dense_attn_tail` — call inside ``shard_map`` with
    shard-sliced block params (``a`` holds the local ``H/tp`` heads). The
    attention output projection is row-parallel (``wo`` rows are
    head-aligned), closed by one ``lax.psum`` (``overlap='none'``) or the
    chunked-psum ring of ``overlap.ring_psum``; the MLP is the training
    path's column→row collective pair (``tensor.tp_pair_apply``, gelu).
    Same numbers as the dense tail up to the all-reduce's summation split
    (token-level parity is pinned in tests/test_serve.py)."""
    from jax import lax

    from simple_distributed_machine_learning_tpu.parallel.compat import (
        pvary_to,
        vma_of,
    )
    from simple_distributed_machine_learning_tpu.parallel.mesh import (
        MODEL_AXIS,
    )
    from simple_distributed_machine_learning_tpu.parallel.tensor import (
        tp_pair_apply,
    )

    z = _merge_heads(a) @ bp["attn"]["wo"]
    if overlap == "ring":
        from simple_distributed_machine_learning_tpu.parallel.overlap import (
            ring_psum,
        )
        red = ring_psum(z, MODEL_AXIS)
    else:
        red = lax.psum(z, MODEL_AXIS)
    h = pvary_to(h, tuple(vma_of(red))) + red
    hn2 = layer_norm(bp["ln2"], h)
    return h + tp_pair_apply({"w1": bp["mlp_in"], "w2": bp["mlp_out"]}, hn2,
                             activation=jax.nn.gelu, overlap=overlap)


def _close_rows(rows):
    """Re-replicate the sampling rows across the model axis before any
    token is drawn. With ``overlap='none'`` the replicas are already
    bit-identical (psum is symmetric) and the pmean is the exact identity
    for power-of-two tp (``(x * tp) / tp`` is exact in binary floating
    point); with the ring schedule each shard's accumulation ORDER differs
    by a ulp, and sampling on per-shard rows could argmax-diverge — the
    pmean makes every shard sample the same row bits."""
    from jax import lax

    from simple_distributed_machine_learning_tpu.parallel.mesh import (
        MODEL_AXIS,
    )
    return lax.pmean(rows, MODEL_AXIS)


def _tp_adapter_layers(bank, aid, tp):
    """TP twin of :func:`_adapter_layers` — call inside ``shard_map``. The
    bank arrives replicated (it is tiny next to the weights); each shard
    slices its LOCAL output columns of the B factors — ``bq``/``bv``
    columns are head-aligned exactly like ``wq``/``wv``'s Megatron column
    shards, and column slicing commutes with the matmul — so the local
    delta lands on the same columns the local base projection produces,
    bit-identically to the dense build's slice."""
    from jax import lax

    from simple_distributed_machine_learning_tpu.parallel.mesh import (
        MODEL_AXIS,
    )
    at_full = _adapter_layers(bank, aid)
    m = lax.axis_index(MODEL_AXIS)

    def at(li):
        aq, bq, av, bv = at_full(li)
        dc = bq.shape[-1] // tp
        bq = lax.dynamic_slice_in_dim(bq, m * dc, dc, bq.ndim - 1)
        bv = lax.dynamic_slice_in_dim(bv, m * dc, dc, bv.ndim - 1)
        return aq, bq, av, bv

    return at


def _tp_jit(name, body, mesh, n_buf_in, n_rest_in, n_buf_out, n_rest_out,
            donate=(1, 2)):
    """``jit(shard_map(body))`` with the serving specs, the program called
    ``name`` (what a device trace shows it as): params as the
    ``(stacked blocks, replicated embed/head)`` pair, ``n_buf_in`` K/V pool
    buffers sharded on their HEAD axis (dim 2 of every leaf of the paged
    pool's per-layer ``[n_blocks+1, bs, H*dh]``, whose lanes hold a shard's
    heads contiguously, and of a quantized pool's scale plane; one spec is
    the prefix of the pytree), everything else replicated (the per-slot
    state pair of the chunk and decode programs too: it leads the rest on
    both sides). ``donate`` as in the single-device builders."""
    from jax.sharding import PartitionSpec as P

    from simple_distributed_machine_learning_tpu.parallel.compat import (
        shard_map as _shard_map,
    )
    from simple_distributed_machine_learning_tpu.parallel.mesh import (
        MODEL_AXIS,
    )
    cache = P(None, None, MODEL_AXIS)
    in_specs = (((P(MODEL_AXIS), P()),) + (cache,) * n_buf_in
                + (P(),) * n_rest_in)
    out_specs = (cache,) * n_buf_out + (P(),) * n_rest_out
    fn = _shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    fn.__name__ = name
    return functools.partial(jax.jit, donate_argnums=donate)(fn)


def _validate_tp_serve(cfg: GPTConfig, mesh, caller: str):
    """Serving-op TP validation: ``n_tensor_parallel > 1`` needs a mesh
    whose ``model`` axis is exactly that size (the shard_map programs bind
    it); tp == 1 normalizes mesh to None so memo keys stay shared."""
    tp = cfg.n_tensor_parallel
    if tp == 1:
        return None
    from simple_distributed_machine_learning_tpu.parallel.mesh import (
        MODEL_AXIS,
    )
    if mesh is None or dict(mesh.shape).get(MODEL_AXIS, 1) != tp:
        got = None if mesh is None else dict(mesh.shape)
        raise ValueError(
            f"{caller}: cfg.n_tensor_parallel={tp} needs a mesh with a "
            f"'{MODEL_AXIS}' axis of that size, got {got}")
    return mesh


def _leave_host_pair_behind(toks, pos, tables, live, key_data, *rest):
    """GPT's ``PagedServing.pack_decode``: the engine's host copies of the
    tokens and keys stay behind, the decode reads the device's."""
    del toks, key_data
    return (pos, tables, live, *rest)


def dense_block_prefill(bp, h, li, kc, vc, prompt_len, n_heads):
    """One block over the whole prompt [b, T0, d], recording cache row
    ``li`` for positions [0, prompt_len). K/V are cast to the cache's dtype
    (a bf16 cache halves decode memory; reads promote back in the einsum)."""
    q, k, v = _dense_qkv(bp, h, n_heads)
    kc = kc.at[li, :, :, :prompt_len].set(k.astype(kc.dtype))
    vc = vc.at[li, :, :, :prompt_len].set(v.astype(vc.dtype))
    return _dense_attn_tail(bp, h, causal_attention_core(q, k, v)), kc, vc


def dense_block_step(bp, h, li, kc, vc, i, total, n_heads):
    """One block on ONE token [b, 1, d] against cache row ``li``; writes K/V
    at position ``i`` (cast to the cache's dtype). Same scale expression as
    causal_attention_core (divide by sqrt(dh)) so prefill and step compile
    to identical math."""
    dh = h.shape[-1] // n_heads
    q, knew, vnew = _dense_qkv(bp, h, n_heads)          # [B,H,1,dh] each
    kc = jax.lax.dynamic_update_slice(kc, knew[None].astype(kc.dtype),
                                      (li, 0, 0, i, 0))
    vc = jax.lax.dynamic_update_slice(vc, vnew[None].astype(vc.dtype),
                                      (li, 0, 0, i, 0))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, kc[li]) / math.sqrt(dh)
    live = (jnp.arange(total) <= i)[None, None, None, :]
    scores = jnp.where(live, scores, -jnp.inf)
    a = jnp.einsum("bhqk,bhkd->bhqd",
                   jax.nn.softmax(scores, axis=-1), vc[li])
    return _dense_attn_tail(bp, h, a), kc, vc


def validate_decode_build(stages, cfg, prompt_len, n_new, caller):
    """Shared decoder-build validation (cached + pipeline-parallel): dense
    blocks only, sane lengths, and cfg matching the stages' ACTUAL build
    shapes (a mismatched cfg would otherwise silently clamp pos-table
    slices past the real seq_len instead of raising)."""
    if cfg.n_experts > 0:
        raise ValueError(
            f"{caller} supports dense-MLP blocks only — MoE capacity is a "
            f"full-sequence quantity, so per-token cached routing would "
            f"change overflow behavior; use make_decoder")
    if prompt_len < 1:
        raise ValueError(
            f"{caller} needs a non-empty prompt (t0 >= 1): the first "
            f"decoded token is conditioned on the prompt's last position")
    if n_new < 1:
        raise ValueError(f"{caller} needs n_new >= 1 (there is nothing to "
                         f"cache for a pure-prefill call)")
    total = prompt_len + n_new
    if total > cfg.seq_len:
        raise ValueError(
            f"prompt {prompt_len} + n_new {n_new} exceeds the model's "
            f"sequence length {cfg.seq_len}")
    _check_embed_matches(stages, cfg)
    return total


def _check_embed_matches(stages, cfg: GPTConfig) -> None:
    """The one copy of the cfg-vs-build shape check every decoder-style
    builder runs (cached/beam via :func:`validate_decode_build`, the
    serving slot ops via :func:`_validate_slot_build`): a mismatched cfg
    would otherwise silently clamp pos-table slices past the real seq_len
    instead of raising."""
    embed = next((s.params.get("embed") for s in stages
                  if isinstance(s.params, dict) and "embed" in s.params),
                 None)
    if embed is None or embed["pos"].shape != (cfg.seq_len, cfg.d_model):
        got = None if embed is None else embed["pos"].shape
        raise ValueError(
            f"cfg (seq_len={cfg.seq_len}, d_model={cfg.d_model}) does not "
            f"match the stages' embedding table {got} — pass the GPTConfig "
            f"the stages were built with")


def head_logprobs(head, h_last):
    """[B, d] final hidden -> [B, V] log-probs (ln_f + untied head)."""
    return log_softmax(linear(head["out"], layer_norm(head["ln_f"], h_last)))


def sample_from(row, ks, temperature, top_k, top_p):
    """Scale/filter/categorical core on a PRE-SPLIT subkey ``ks`` (argmax
    when temperature == 0) — the ONE copy of the sampling math, shared by
    every decoder (cached, recompute, pipeline-parallel)."""
    if temperature > 0.0:
        return jax.random.categorical(
            ks, _filter_top(row / temperature, top_k, top_p), axis=-1)
    return jnp.argmax(row, axis=-1)


def _sample_row(row, k, temperature, top_k, top_p):
    """One decode step on [B, V] log-probs -> ``(tokens, next_key)``.

    The ONE copy of the split discipline (exactly one split per sampled
    token) over :func:`sample_from` — the single-device decoders call it,
    which is what keeps their key streams (and therefore their sampled
    tokens) exactly identical; the pipeline decoder performs the same split
    itself (uniformly on every device) and calls :func:`sample_from`."""
    if temperature > 0.0:
        k, ks = jax.random.split(k)
        return sample_from(row, ks, temperature, top_k, top_p), k
    return jnp.argmax(row, axis=-1), k


def check_sampling_args(temperature, top_k, top_p, vocab=None):
    if (top_k is not None or top_p is not None) and temperature <= 0.0:
        raise ValueError("top_k/top_p filtering needs temperature > 0 "
                         "(greedy decoding ignores the filtered tail)")
    if top_k is not None and (top_k < 1 or
                              (vocab is not None and top_k > vocab)):
        raise ValueError(f"top_k={top_k} out of range [1, vocab={vocab}]")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p={top_p} out of range (0, 1]")


def generate(stages, prompt: jax.Array, n_new: int,
             key: jax.Array | None = None,
             temperature: float = 0.0,
             cfg: GPTConfig | None = None,
             top_k: int | None = None,
             top_p: float | None = None) -> jax.Array:
    """Autoregressive decoding from the (single-device) stage composition.

    ``prompt``: [B, T0] int tokens; returns [B, T0 + n_new]. The whole decode
    is ONE ``lax.scan`` over a fixed-length token buffer — static shapes, no
    per-step Python dispatch (the TPU-idiomatic decode shape). Each step
    recomputes the full prefix forward; causal masking makes the
    not-yet-written zero padding at positions > current length invisible to
    the prediction read at the current position. Full-prefix recompute is
    O(T²) per sequence — right for reference-scale models; a KV-cache decode
    path is the standard next optimization.

    ``temperature=0`` → greedy argmax; ``> 0`` → softmax sampling with
    ``key`` (required); ``top_k``/``top_p`` filter the sampling
    distribution. One-shot convenience: retraces per call — build the
    decoder once with :func:`make_decoder` / :func:`make_cached_decoder`
    for repeated generation.

    ``cfg``: pass the stages' build config to decode through the O(T)
    KV-cache path (:func:`make_cached_decoder`) instead of the O(T²)
    full-prefix recompute — same tokens, faster; dense-MLP single-device
    builds only (the cached path's restrictions apply).

    The reference has no inference path at all (eval only,
    ``/root/reference/simple_distributed.py:119-132``); this is a capability
    extension.
    """
    if temperature > 0.0 and key is None:
        raise ValueError("sampling (temperature > 0) needs a PRNG key")
    key = key if key is not None else jax.random.key(0)
    if cfg is not None:
        dec = make_cached_decoder(stages, cfg, int(prompt.shape[1]), n_new,
                                  temperature=temperature, top_k=top_k,
                                  top_p=top_p)
    else:
        dec = make_decoder(stages, int(prompt.shape[1]), n_new,
                           temperature=temperature, top_k=top_k, top_p=top_p)
    return dec([s.params for s in stages], prompt, key)


def make_cached_decoder(stages, cfg: GPTConfig, prompt_len: int, n_new: int,
                        temperature: float = 0.0, top_k: int | None = None,
                        top_p: float | None = None, cache_dtype=None):
    """KV-cache decode: ``decode(params, prompt, key) -> [B, prompt_len+n_new]``.

    Same contract as :func:`make_decoder` but O(T) per generated token instead
    of O(T²): a one-shot prefill runs the prompt through every block once,
    recording each layer's K/V projections into static ``[L, B, H, total, dh]``
    cache buffers, and the decode ``lax.scan`` then pushes ONE token per step —
    the new K/V row lands in the cache via ``lax.dynamic_update_slice`` and
    attention is a single [1, total] masked row against the cache. Static
    shapes throughout (the TPU decode idiom: no growing buffers, no retraces).

    For ``attn_impl="dense"`` builds greedy tokens match :func:`make_decoder`
    exactly (same math, different association; see
    tests/test_gpt.py::test_cached_decoder_matches_recompute). The cached path
    always computes DENSE attention math on the weights — an
    ``attn_impl="flash"`` build decodes fine here (flash is the same math),
    but ``make_decoder`` would run the Pallas kernel, whose different
    accumulation order can flip a near-tie argmax; cross-decoder token
    equality is only to float tolerance in that case.

    Single-device dense-MLP composition only: MoE routing capacity is defined
    per full sequence (``default_capacity(T, ...)``), so per-token routing
    would silently change which tokens overflow — decode MoE models with
    :func:`make_decoder`. Sequence-parallel builds (``cfg.n_seq > 1``) use mesh
    collectives in their applies and cannot run here either (same restriction
    as :func:`make_decoder`).

    The reference has no inference path at all (eval only,
    ``/root/reference/simple_distributed.py:119-132``).

    Builds are memoized on their static config (``_DECODE_BUILD_CACHE``):
    the program traces everything model-shaped from ``params``, so two
    calls with the same (cfg, lengths, sampling, cache dtype) share one
    jitted callable — and its compiled executables — even across stages
    builds.
    """
    if cfg.n_seq > 1:
        raise ValueError(
            "cached decode is single-device; rebuild the stages with n_seq=1 "
            "(same weights) as make_decoder requires too")
    check_sampling_args(temperature, top_k, top_p, cfg.vocab)
    check_cache_quantization(cache_dtype, "make_cached_decoder",
                             paged=False)
    total = validate_decode_build(stages, cfg, prompt_len, n_new,
                                  "make_cached_decoder")
    H, d = cfg.n_heads, cfg.d_model
    dh = d // H
    cd = storage_dtype(cache_dtype)
    key_ = ("cached_decoder", cfg, prompt_len, n_new, temperature, top_k,
            top_p, jnp.dtype(cd).name)
    return memo_build(key_, lambda: _build_cached_decoder(
        total, prompt_len, n_new, H, dh, cd, temperature, top_k, top_p))


def _build_cached_decoder(total, prompt_len, n_new, H, dh, cd,
                          temperature, top_k, top_p):
    from jax import lax

    _merged = merged_stage_trees
    _head_row = head_logprobs

    def _pick(row, k):
        return _sample_row(row, k, temperature, top_k, top_p)

    @jax.jit
    def decode(params, prompt, key):
        embed, blocks, head = _merged(params)
        b = prompt.shape[0]
        L = len(blocks)
        kc = jnp.zeros((L, b, H, total, dh), cd)
        vc = jnp.zeros((L, b, H, total, dh), cd)

        # --- prefill: one dense causal pass over the whole prompt, recording
        # every layer's K/V rows for positions [0, prompt_len)
        ids = prompt.astype(jnp.int32)
        h = embedding_lookup(embed["tok"], ids) + embed["pos"][:prompt_len]
        for li, bp in enumerate(blocks):
            h, kc, vc = dense_block_prefill(bp, h, li, kc, vc, prompt_len, H)
        row = _head_row(head, h[:, -1])
        tok, key = _pick(row, key)          # token for position prompt_len

        # --- decode: one token per step; the input token sits at position i,
        # its K/V row lands at cache index i, and the masked attention row
        # covers positions [0, i]
        def step(carry, i):
            kc, vc, tok, k = carry
            pos = lax.dynamic_slice_in_dim(embed["pos"], i, 1, 0)
            h = embedding_lookup(embed["tok"], tok[:, None]) + pos   # [B,1,d]
            for li, bp in enumerate(blocks):
                h, kc, vc = dense_block_step(bp, h, li, kc, vc, i, total, H)
            row = _head_row(head, h[:, 0])
            nxt, k = _pick(row, k)
            return (kc, vc, nxt, k), tok

        # steps i = prompt_len .. total-2 each CONSUME the carried token at
        # position i and emit it, producing the next; the final carried token
        # (position total-1) is appended after the scan
        (_, _, last, _), toks = lax.scan(
            step, (kc, vc, tok, key), prompt_len + jnp.arange(n_new - 1))
        out = jnp.concatenate(
            [prompt.astype(jnp.int32),
             jnp.moveaxis(toks, 0, 1),
             last[:, None]], axis=1)
        return out

    return decode


def _validate_slot_build(stages, cfg: GPTConfig, max_len: int,
                         caller: str, cache_dtype=None) -> None:
    """Shared validation for the serving ops: single-device dense-MLP
    builds only (the :func:`make_cached_decoder` restrictions — MoE routing
    capacity is a full-sequence quantity; sharded stage trees are per-shard
    slices, not the whole model), ``max_len`` within the position
    table, and no quantized cache dtype (the draft's slot rows carry no
    scale planes; the paged validator re-allows quantization)."""
    check_cache_quantization(cache_dtype, caller, paged=False)
    if cfg.n_experts > 0:
        raise ValueError(
            f"{caller} supports dense-MLP blocks only — MoE capacity is a "
            f"full-sequence quantity (make_cached_decoder's restriction)")
    if cfg.n_seq > 1:
        raise ValueError(
            f"{caller} is single-device; rebuild the stages with n_seq=1")
    if any(getattr(s, "shards", None) is not None
           or getattr(s, "expert_shards", None) is not None for s in stages):
        raise ValueError(
            f"{caller} needs unsharded stage params — gather tensor/expert "
            f"shards into a dense build first")
    if not 2 <= max_len <= cfg.seq_len:
        raise ValueError(
            f"slot max_len={max_len} outside [2, seq_len={cfg.seq_len}] "
            f"(the position table bounds every slot's sequence budget)")
    _check_embed_matches(stages, cfg)


# -- the speculative draft's programs ----------------------------------------
#
# The served TARGET model's K/V lives in the paged pool and nowhere else. The
# speculative DRAFT model keeps one ``max_len`` row per slot, ``[L, n_slots,
# H, max_len, dh]`` (``InferenceEngine._init_draft_pool``): it is small by
# design, so paging it buys nothing, and a row past a slot's newest position
# is overwritten before it can be attended. ``make_slot_prefill`` fills a
# slot's row from a prompt and ``make_slot_propose`` (speculative section
# below) decodes ``spec_k`` tokens over every row; both are single-device and
# run the base model.


def _refuse_tp_draft(cfg: GPTConfig, caller: str) -> None:
    if cfg.n_tensor_parallel > 1:
        raise ValueError(
            f"{caller} runs the draft model single-device "
            f"(replicated under a TP target): build the draft with "
            f"n_tensor_parallel=1")


def make_slot_prefill(stages, cfg: GPTConfig, max_len: int,
                      cache_dtype=None):
    """The draft's prefill-into-slot: ``prefill(params, kc, vc, prompt
    [1, T0], slot, key_data, temperature, top_k, top_p) -> (kc, vc, token,
    key_data)``.

    Runs ONE request's prompt through every block (batch 1, exactly the
    solo decoder's prefill shapes and math — shared :func:`_dense_qkv` /
    ``causal_attention_core`` / :func:`_dense_attn_tail`), writes each
    layer's K/V rows into row ``slot`` at positions ``[0, T0)``, and
    samples a token with the given params and key (:func:`sample_dyn`'s
    sentinels: ``top_k=0`` / ``top_p=2.0`` disable; the engine discards
    both, only the cache write matters to a draft). Retraces per distinct
    prompt length (the prompt shape is static).

    ``kc``/``vc``: the draft's buffers, ``[L, n_slots, H, max_len, dh]`` in
    the :func:`storage_dtype` storage dtype. They are DONATED — the engine
    threads the returned buffers back, and donation lets XLA update the
    slot row in place instead of copying the buffer per call. Single-device
    like :func:`make_slot_propose`: a tensor-parallel ``cfg`` is refused.
    """
    _validate_slot_build(stages, cfg, max_len, "make_slot_prefill",
                         cache_dtype)
    _refuse_tp_draft(cfg, "make_slot_prefill")
    H = cfg.n_heads
    return memo_build(("slot_prefill", cfg, max_len),
                      lambda: _build_slot_prefill(H))


def _build_slot_prefill(H):
    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def prefill(params, kc, vc, prompt, slot, key_data, temperature,
                top_k, top_p):
        embed, blocks, head = merged_stage_trees(params)
        t0 = prompt.shape[1]
        ids = prompt.astype(jnp.int32)
        h = embedding_lookup(embed["tok"], ids) + embed["pos"][:t0]
        for li, bp in enumerate(blocks):
            q, k_, v = _dense_qkv(bp, h, H)               # [1, H, T0, dh]
            kc = jax.lax.dynamic_update_slice(
                kc, k_.astype(kc.dtype)[None], (li, slot, 0, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                vc, v.astype(vc.dtype)[None], (li, slot, 0, 0, 0))
            h = _dense_attn_tail(bp, h, causal_attention_core(q, k_, v))
        row = head_logprobs(head, h[:, -1])[0]           # [V]
        tok, kd = sample_slot(row, key_data, temperature, top_k, top_p)
        return kc, vc, tok, kd

    return prefill


def _dense_block_step_slots(bp, h, li, kc, vc, pos, n_heads):
    """One block of the DRAFT on one token per SLOT (``h``: [S, 1, d])
    against its cache row ``li``; each slot writes its new K/V at its OWN
    position (``pos``: [S]) and attends ``[0, pos]``. Per-slot math is
    exactly :func:`dense_block_step`'s (same scale expression, same
    einsums, same masked-row softmax), and every slot's output depends only
    on its own cache row."""
    q, knew, vnew = _dense_qkv(bp, h, n_heads)            # [S, H, 1, dh]
    # scale from the PROJECTED head dim (q's trailing axis): the
    # causal_attention_core convention
    dh = q.shape[-1]

    def upd(cache, new, p):
        return jax.lax.dynamic_update_slice(cache, new, (0, p, 0))

    kci = jax.vmap(upd)(kc[li], knew.astype(kc.dtype), pos)
    vci = jax.vmap(upd)(vc[li], vnew.astype(vc.dtype), pos)
    kc = kc.at[li].set(kci)
    vc = vc.at[li].set(vci)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, kci) / math.sqrt(dh)
    live = (jnp.arange(kci.shape[-2])[None, None, None, :]
            <= pos[:, None, None, None])
    scores = jnp.where(live, scores, -jnp.inf)
    a = jnp.einsum("bhqk,bhkd->bhqd",
                   jax.nn.softmax(scores, axis=-1), vci)
    return _dense_attn_tail(bp, h, a), kc, vc


def _slot_decode_fwd(blocks, embed, head, kc, vc, toks, pos, H):
    """One step of the draft proposer's scan (:func:`make_slot_propose`):
    one token per slot through every block over the draft's slot rows."""
    pe = jnp.take(embed["pos"], pos, axis=0)[:, None]      # [S, 1, d]
    h = embedding_lookup(embed["tok"], toks[:, None]) + pe
    for li, bp in enumerate(blocks):
        h, kc, vc = _dense_block_step_slots(bp, h, li, kc, vc, pos, H)
    return kc, vc, head_logprobs(head, h[:, 0])           # rows: [S, V]


def _validate_paged_build(stages, cfg: GPTConfig, max_len: int,
                          block_size: int, caller: str,
                          cache_dtype=None) -> None:
    """Paged-op validation: the slot-op restrictions plus a sane block.
    Quantized cache dtypes are allowed HERE (the paged pool carries the
    per-block scale planes) — only their availability is checked."""
    _validate_slot_build(stages, cfg, max_len, caller)
    check_cache_quantization(cache_dtype, caller, paged=True)
    if block_size < 1:
        raise ValueError(f"{caller} needs block_size >= 1, got {block_size}")


def make_paged_prefill_chunk(stages, cfg: GPTConfig, max_len: int,
                             block_size: int, cache_dtype=None, mesh=None,
                             adapters: bool = False):
    """Chunked serving prefill into paged blocks: ``chunk(params, kc, vc,
    state, tokens [1, c], p0, table [NB], slot, seat, key_data, temperature,
    top_k, top_p) -> (kc, vc, state, token, key_data)``.

    Runs ONE request's prompt positions ``[p0, p0+c)`` through every block
    (batch 1, the solo decoder's math via the shared :func:`_dense_qkv` /
    :func:`_dense_attn_tail`), scattering each position's K/V into its
    physical block (``table[p // bs]``, offset ``p % bs``) and attending
    the gathered block row masked to ``<= position`` — which covers both
    earlier chunks (already in the cache, including SHARED prefix blocks
    another request prefilled) and the chunk's own freshly written rows.
    The engine interleaves these chunks with decode ticks so a long prompt
    never stalls in-flight requests; the last chunk's final position feeds
    the head and samples the request's first token (:func:`sample_slot` —
    the engine discards the sampled token and key for non-final chunks, so
    the request's key stream advances exactly once, at the same point as
    its solo decode).

    ``state`` is ``((newest [S] int32, keys [S, 2] uint32),)``, every
    slot's newest token and sampling key (:data:`NEWEST_PAIR`,
    ``PagedServing``), and ``seat`` says what this chunk leaves there
    for ``slot``: nothing (:data:`SEAT_NONE`, a mid-prompt chunk), its own
    sample and advanced key (:data:`SEAT_SAMPLE`), or a resumed request's
    stored token with the key handed in (:func:`seat_newest`). The decode
    step reads its inputs there, so the slot's first decode waits for no
    host.

    Retraces per distinct chunk length. Bit-exactness vs the solo
    ``make_cached_decoder`` holds for f32 caches: the chunk reads earlier
    K/V back out of the cache, so a bf16 cache rounds where the solo
    monolithic prefill attends fresh f32 K/V — the one place the paged
    path's parity is dtype-conditional (the decode tick round-trips the
    cache in BOTH paths, so it is exempt).

    ``kc``/``vc`` (one ``[n_blocks+1, block_size, H*dh]`` buffer a layer,
    ``serve/slots.py::PagedKVPool``) and ``state`` are donated, every
    leaf — the engine always threads the returned buffers back into the
    pool, and donation lets XLA write the rows in place.

    ``adapters=True`` builds the multi-tenant variant: two TRACED args
    append to the signature — the stacked adapter ``bank`` pytree and the
    request's bank-row index ``aid`` — and every block's q/v projection
    adds the gathered low-rank delta (:func:`_dense_qkv`). One static
    BOOL in the memo key: bank contents, row count and rank are all data,
    so adapter registration/hot-swap never retraces and any adapter mix
    shares this one program.

    With ``cfg.n_tensor_parallel > 1`` (pass the ``mesh``): the same math
    inside ``shard_map`` — QKV on the local ``H/tp`` heads, K/V landing in
    this shard's lanes of the head-sharded pool, the attention/MLP reduces
    of :func:`_tp_attn_tail` — with ``params`` in the
    :func:`pack_tp_serve_params` layout and ``state`` replicated.
    """
    _validate_paged_build(stages, cfg, max_len, block_size,
                          "make_paged_prefill_chunk", cache_dtype)
    mesh = _validate_tp_serve(cfg, mesh, "make_paged_prefill_chunk")
    H, bs = cfg.n_heads, block_size
    dh = cfg.d_model // H
    key_ = ("paged_chunk", cfg, max_len, block_size, mesh, adapters)
    if cfg.n_tensor_parallel > 1:
        return memo_build(key_, lambda: _build_paged_prefill_chunk_tp(
            cfg, bs, dh, mesh, adapters))
    return memo_build(key_, lambda: _build_paged_prefill_chunk(
        H, bs, dh, adapters))


def _paged_chunk_fwd(blocks, embed, head, kc, vc, tokens, p0, table, H, bs,
                     dh, tail, ab_at=None):
    """One prompt chunk's scatter + block-gather attention — the shared
    forward of the single-device and TP paged prefill builds."""
    c = tokens.shape[1]
    ids = tokens.astype(jnp.int32)
    pos_emb = jax.lax.dynamic_slice_in_dim(embed["pos"], p0, c, 0)
    h = embedding_lookup(embed["tok"], ids) + pos_emb
    idx = p0 + jnp.arange(c)
    phys = table[idx // bs]                       # [c]
    off = idx % bs
    span = table.shape[0] * bs
    live = (jnp.arange(span)[None, :] <= idx[:, None])[None, None]
    for li, bp in enumerate(blocks):
        q, k_, v = _dense_qkv(bp, h, H,           # [1, H, c, dh]
                              None if ab_at is None else ab_at(li))
        kc = paged_scatter(kc, li, phys, off, k_[0].swapaxes(0, 1))
        vc = paged_scatter(vc, li, phys, off, v[0].swapaxes(0, 1))
        krow = paged_gather(kc, li, table, H)    # [H, span, dh]
        vrow = paged_gather(vc, li, table, H)
        scores = jnp.einsum("bhqd,hkd->bhqk", q, krow) / math.sqrt(dh)
        scores = jnp.where(live, scores, -jnp.inf)
        a = jnp.einsum("bhqk,hkd->bhqd",
                       jax.nn.softmax(scores, axis=-1), vrow)
        h = tail(bp, h, a)
    return kc, vc, head_logprobs(head, h[:, -1])[0]    # row: [V]


def _build_paged_prefill_chunk(H, bs, dh, adapters=False):
    def run(params, kc, vc, state, tokens, p0, table, slot, seat, key_data,
            temperature, top_k, top_p, ab_at=None):
        embed, blocks, head = merged_stage_trees(params)
        kc, vc, row = _paged_chunk_fwd(blocks, embed, head, kc, vc,
                                       tokens, p0, table, H, bs, dh,
                                       _dense_attn_tail, ab_at)
        tok, kd = sample_slot(row, key_data, temperature, top_k, top_p)
        pair, = state
        return (kc, vc, (seat_newest(pair, slot, seat, tok, kd, key_data),),
                tok, kd)

    if adapters:
        @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
        def chunk_paged_prefill(params, kc, vc, state, tokens, p0, table,
                                slot, seat, key_data, temperature, top_k,
                                top_p, bank, aid):
            return run(params, kc, vc, state, tokens, p0, table, slot, seat,
                       key_data, temperature, top_k, top_p,
                       _adapter_layers(bank, aid))

        return chunk_paged_prefill

    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def chunk_paged_prefill(params, kc, vc, state, tokens, p0, table, slot,
                            seat, key_data, temperature, top_k, top_p):
        return run(params, kc, vc, state, tokens, p0, table, slot, seat,
                   key_data, temperature, top_k, top_p)

    return chunk_paged_prefill


def _build_paged_prefill_chunk_tp(cfg, bs, dh, mesh, adapters=False):
    tp = cfg.n_tensor_parallel
    tail = functools.partial(_tp_attn_tail, overlap=cfg.overlap)
    H_loc = cfg.n_heads // tp

    def run(params, kc, vc, state, tokens, p0, table, slot, seat, key_data,
            temperature, top_k, top_p, ab_at=None):
        blocks, embed, head = _tp_local_trees(params)
        kc, vc, row = _paged_chunk_fwd(blocks, embed, head, kc, vc,
                                       tokens, p0, table, H_loc, bs, dh,
                                       tail, ab_at)
        row = _close_rows(row)
        tok, kd = sample_slot(row, key_data, temperature, top_k, top_p)
        pair, = state
        return (kc, vc, (seat_newest(pair, slot, seat, tok, kd, key_data),),
                tok, kd)

    if adapters:
        def body(params, kc, vc, state, tokens, p0, table, slot, seat,
                 key_data, temperature, top_k, top_p, bank, aid):
            return run(params, kc, vc, state, tokens, p0, table, slot, seat,
                       key_data, temperature, top_k, top_p,
                       _tp_adapter_layers(bank, aid, tp))

        return _tp_jit("chunk_paged_prefill_tp", body, mesh, n_buf_in=2,
                       n_rest_in=12, n_buf_out=2, n_rest_out=3,
                       donate=(1, 2, 3))

    def body(params, kc, vc, state, tokens, p0, table, slot, seat, key_data,
             temperature, top_k, top_p):
        return run(params, kc, vc, state, tokens, p0, table, slot, seat,
                   key_data, temperature, top_k, top_p)

    return _tp_jit("chunk_paged_prefill_tp", body, mesh, n_buf_in=2,
                   n_rest_in=10, n_buf_out=2, n_rest_out=3,
                   donate=(1, 2, 3))


def make_paged_decode_step(stages, cfg: GPTConfig, max_len: int,
                           block_size: int, cache_dtype=None, mesh=None,
                           kernel: str = "dense",
                           adapters: bool = False):
    """Paged serving decode tick: ``step(params, kc, vc, state, pos [S],
    tables [S, NB], live [S], temps [S], top_ks [S], top_ps [S]) -> (kc,
    vc, state, next_toks [S], next_key_data [S, 2])``.

    ONE batched token step over ALL ``n_slots`` slots — static shapes, so
    a single compiled program serves every tick regardless of occupancy.
    Every slot's input token and sampling key are ``state``'s pair
    ``((newest [S] int32, keys [S, 2] uint32),)``, where the chunk that
    finished the slot's prompt seated them and where the ``live`` slots'
    new ones go back (:func:`feed_newest`, ``PagedServing``): the
    next step needs nothing from the host that this one computes, and the
    engine launches it before it has read this one's tokens.
    Each slot consumes its carried token at its own position, lands its new
    K/V via a per-slot scatter into physical block ``tables[s, pos // bs]``
    at offset ``pos % bs``, attends the row assembled from its block table
    (:func:`paged_gather`) masked to ``<= pos``, and samples with its own
    params and key stream (:func:`sample_slots`: ``vmap`` of
    :func:`sample_dyn` — loop semantics, per-slot draws equal the
    unbatched calls — or, every slot greedy, the ``argmax``). Values for live
    positions are the cached decoder's (same numbers, different storage)
    and the mask removes everything else: the bit-exactness anchor
    continuous batching rests on.

    A non-decoding slot's table entries may alias blocks reused by a live
    request, so the ENGINE routes those slots' tick inputs to the trash
    block (``pos = 0``, all-trash table) — their garbage K/V lands where
    no real table points, the engine discards their tokens host-side and
    their pair stays as it was (``live`` is false for them).
    ``kc``/``vc``/``state`` are donated (one in-place update per tick).

    With ``cfg.n_tensor_parallel > 1`` (pass the ``mesh``): the shard_map
    twin over the head-sharded block pool
    (:func:`make_paged_prefill_chunk`'s TP and adapter notes apply — block
    tables and positions stay replicated host inputs; ``adapters=True``
    appends ``(bank, aids [S])``, each slot gathering its OWN adapter's
    factors by index).

    ``kernel="fused"`` swaps the gather-then-dense attention for the
    single-pass Pallas paged-attention kernel (flash-decode layout,
    ``ops/paged_attention.py``): one HBM read of resident K/V per tick
    instead of read-materialize-reread. Greedy token streams are
    bit-exact vs ``kernel="dense"`` (logits to accumulation-order ulps);
    quantized pools dequantize inside the kernel.
    """
    _validate_paged_build(stages, cfg, max_len, block_size,
                          "make_paged_decode_step", cache_dtype)
    mesh = _validate_tp_serve(cfg, mesh, "make_paged_decode_step")
    check_attn_kernel(kernel, "make_paged_decode_step")
    H, bs = cfg.n_heads, block_size
    dh = cfg.d_model // H
    key_ = ("paged_decode", cfg, max_len, block_size, mesh, kernel,
            adapters)
    if cfg.n_tensor_parallel > 1:
        return memo_build(key_, lambda: _build_paged_decode_step_tp(
            cfg, bs, dh, mesh, kernel, adapters))
    return memo_build(key_, lambda: _build_paged_decode_step(
        H, bs, dh, kernel, adapters))


def _paged_decode_fwd(blocks, embed, head, kc, vc, toks, pos, tables, H, bs,
                      dh, tail, kernel="dense", ab_at=None):
    """The batched one-token-per-slot block-gather step's forward — shared
    by the single-device and TP paged decode builds. ``kernel`` selects the
    attention path: ``"dense"`` gathers each slot's table span into a
    dense row buffer and runs masked softmax-attention einsums over it
    (two passes over resident K/V); ``"fused"`` runs the one-pass Pallas
    flash-decode kernel (:func:`paged_attend`). Scatter (and quantize,
    for :class:`QuantKV` pools) happens before either path attends, so
    the new token's row is visible at its own position in both."""
    pe = jnp.take(embed["pos"], pos, axis=0)[:, None]     # [S, 1, d]
    h = embedding_lookup(embed["tok"], toks[:, None]) + pe
    phys = jnp.take_along_axis(tables, (pos // bs)[:, None],
                               axis=1)[:, 0]              # [S]
    off = pos % bs
    span = tables.shape[1] * bs
    live = (jnp.arange(span)[None, None, None, :]
            <= pos[:, None, None, None])
    for li, bp in enumerate(blocks):
        q, knew, vnew = _dense_qkv(bp, h, H,              # [S, H, 1, dh]
                                   None if ab_at is None else ab_at(li))
        kc = paged_scatter(kc, li, phys, off, knew[:, :, 0, :])
        vc = paged_scatter(vc, li, phys, off, vnew[:, :, 0, :])
        if kernel == "fused":
            a = paged_attend(kc, vc, li, q, tables, pos[:, None], bs)
        else:
            krow = paged_gather(kc, li, tables, H)       # [S,H,span,dh]
            vrow = paged_gather(vc, li, tables, H)
            scores = (jnp.einsum("bhqd,bhkd->bhqk", q, krow)
                      / math.sqrt(dh))
            scores = jnp.where(live, scores, -jnp.inf)
            a = jnp.einsum("bhqk,bhkd->bhqd",
                           jax.nn.softmax(scores, axis=-1), vrow)
        h = tail(bp, h, a)
    return kc, vc, head_logprobs(head, h[:, 0])          # rows: [S, V]


def _build_paged_decode_step(H, bs, dh, kernel="dense", adapters=False):
    def run(params, kc, vc, state, pos, tables, live, temps, top_ks, top_ps,
            ab_at=None):
        pair, = state
        toks, key_data = pair
        embed, blocks, head = merged_stage_trees(params)
        kc, vc, rows = _paged_decode_fwd(blocks, embed, head, kc, vc, toks,
                                         pos, tables, H, bs, dh,
                                         _dense_attn_tail, kernel, ab_at)
        toks2, kd2 = sample_slots(rows, key_data, temps, top_ks, top_ps)
        return kc, vc, (feed_newest(pair, live, toks2, kd2),), toks2, kd2

    if adapters:
        @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
        def step_paged_decode(params, kc, vc, state, pos, tables, live,
                              temps, top_ks, top_ps, bank, aids):
            return run(params, kc, vc, state, pos, tables, live, temps,
                       top_ks, top_ps, _adapter_layers(bank, aids))

        return step_paged_decode

    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def step_paged_decode(params, kc, vc, state, pos, tables, live, temps,
                          top_ks, top_ps):
        return run(params, kc, vc, state, pos, tables, live, temps, top_ks,
                   top_ps)

    return step_paged_decode


def _build_paged_decode_step_tp(cfg, bs, dh, mesh, kernel="dense",
                                adapters=False):
    tp = cfg.n_tensor_parallel
    tail = functools.partial(_tp_attn_tail, overlap=cfg.overlap)
    H_loc = cfg.n_heads // tp

    def run(params, kc, vc, state, pos, tables, live, temps, top_ks, top_ps,
            ab_at=None):
        pair, = state
        toks, key_data = pair
        blocks, embed, head = _tp_local_trees(params)
        kc, vc, rows = _paged_decode_fwd(blocks, embed, head, kc, vc, toks,
                                         pos, tables, H_loc, bs, dh, tail,
                                         kernel, ab_at)
        rows = _close_rows(rows)
        toks2, kd2 = sample_slots(rows, key_data, temps, top_ks, top_ps)
        return kc, vc, (feed_newest(pair, live, toks2, kd2),), toks2, kd2

    if adapters:
        def body(params, kc, vc, state, pos, tables, live, temps, top_ks,
                 top_ps, bank, aids):
            return run(params, kc, vc, state, pos, tables, live, temps,
                       top_ks, top_ps, _tp_adapter_layers(bank, aids, tp))

        return _tp_jit("step_paged_decode_tp", body, mesh, n_buf_in=2,
                       n_rest_in=9, n_buf_out=2, n_rest_out=3,
                       donate=(1, 2, 3))

    def body(params, kc, vc, state, pos, tables, live, temps, top_ks,
             top_ps):
        return run(params, kc, vc, state, pos, tables, live, temps, top_ks,
                   top_ps)

    return _tp_jit("step_paged_decode_tp", body, mesh, n_buf_in=2,
                   n_rest_in=7, n_buf_out=2, n_rest_out=3,
                   donate=(1, 2, 3))


def make_paged_block_copy():
    """The copy-on-write device op: ``copy(kc, vc, dst, src) -> (kc, vc)``
    duplicates one physical block's rows across every layer before a
    divergent write. Buffers are donated so XLA updates the pool in place
    instead of materializing a second pool; ``dst``/``src`` are traced
    scalars so one compiled program serves every copy. Tree-mapped over
    the buffer leaves (every layer's, the physical-block axis leading), so
    a quantized pool's :class:`QuantKV` pair (block data AND its scale
    plane) copies atomically — a CoW that moved rows without their scales
    would silently rescale the destination block."""
    def build():
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def copy(kc, vc, dst, src):
            def one(buf):
                blk = jax.lax.dynamic_slice_in_dim(buf, src, 1, 0)
                return jax.lax.dynamic_update_slice_in_dim(buf, blk, dst, 0)

            return jax.tree.map(one, kc), jax.tree.map(one, vc)

        return copy

    return memo_build(("paged_block_copy",), build)


def make_paged_block_write():
    """The host tier's upload: ``write(kc, vc, dst, hk, hv) -> (kc, vc)``
    lands one block's host rows (``hk``/``hv``: the pool's pytrees less
    the block axis, what ``PagedKVPool._block_to_host`` took) at physical
    block ``dst`` of every layer. Donated and jitted like the copy above:
    an eager ``.at[].set`` copies a whole buffer for every promoted block."""
    def build():
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def write(kc, vc, dst, hk, hv):
            def one(buf, rows):
                return jax.lax.dynamic_update_slice_in_dim(
                    buf, rows[None], dst, 0)

            return jax.tree.map(one, kc, hk), jax.tree.map(one, vc, hv)

        return write

    return memo_build(("paged_block_write",), build)


def make_adapter_bank_update():
    """The tick-boundary adapter upload: ``update(bank, idx, adapter) ->
    bank`` rewrites ONE row of the stacked adapter bank in place (the
    bank is donated; ``idx`` is a traced scalar so one compiled program
    serves every upload/evict). This is how the AdapterStore hot-swaps a
    tenant's weights between ticks without retracing any decode program:
    the decode builders close over bank SHAPES only — bank contents are
    traced data, so a row rewrite is invisible to the trace cache."""
    def build():
        @functools.partial(jax.jit, donate_argnums=(0,))
        def update(bank, idx, adapter):
            return jax.tree.map(lambda b, a: b.at[idx].set(a), bank,
                                adapter)

        return update

    return memo_build(("adapter_bank_update",), build)


# -- speculative decoding ---------------------------------------------------
#
# Draft/verify serving (ISSUE 9): a small draft model proposes tokens with
# cheap sequential steps, and the target model scores ALL of them in one
# batched K-token step, emitting the longest prefix it agrees with (plus
# its own correction at the first disagreement). With spec_k = K, a tick
# emits 1..K tokens per slot from TWO program dispatches (one propose scan,
# one verify) instead of one dispatch per token.
#
# Index discipline (the engine's contract): a slot at position p with
# pending input t0 would solo-decode by consuming t0@p -> g0, g0@p+1 -> g1,
# ... The draft's propose scan runs K steps (consuming t0, d0, .., d_{K-2}
# at p..p+K-1) producing proposals d0..d_{K-1}; verify consumes the K
# inputs [t0, d0, .., d_{K-2}] at positions p..p+K-1 in one forward,
# yielding rows r0..r_{K-1} where r_j is EXACTLY the row solo decode would
# sample token j+1 from — provided d0..d_{j-1} matched. Greedy acceptance
# therefore emits g_j = argmax(r_j) for j up to (and including) the first
# draft mismatch, which keeps greedy speculative decode bit-exact vs the
# solo make_cached_decoder stream (tests/test_serve.py). The last proposal
# d_{K-1} is never consumed by verify: the extra draft step exists so the
# draft cache already covers position p+K-1 when a tick accepts everything
# (static shapes; no conditional catch-up step next tick).
#
# Rejected-tail K/V: verify writes all K positions before it knows how
# many survive. In-budget positions land in the slot's own rows/blocks and
# are overwritten by the next tick before they can be attended (the same
# trailing-write argument the draft's slot rows rest on); positions beyond
# the slot's remaining token budget (j >= valid_n) are routed to a trash
# sink — the paged pool's trash block 0 for the target, the never-live row
# max_len-1 for the draft — so they cannot land past the reservation or in
# a neighbour.
#
# Sampled modes (temperature > 0) use standard residual-rejection
# sampling: accept draft token d with probability min(1, p(d)/q(d)) on the
# FILTERED target/draft distributions, else emit a sample from the
# normalized positive part of (p - q); the first rejection ends the tick's
# emission for that slot. Marginally each emitted token is distributed
# exactly as a solo sample, but the key stream spends TWO splits on a
# rejected position (accept draw + residual draw), so sampled speculative
# streams are deterministic-per-seed yet not token-identical to solo —
# only greedy carries the bit-exactness anchor.


def _check_spec_k(spec_k: int, caller: str) -> None:
    if spec_k < 2:
        raise ValueError(
            f"{caller}: spec_k must be >= 2 (spec_k=1 is plain one-token "
            f"decode — use the decode step), got {spec_k}")


def _spec_accept_sampled(rows, drafts, draft_rows, valid_n, key_data,
                         temperature, top_k, top_p):
    """Per-slot residual-rejection acceptance on the verify rows:
    ``(rows [K, V], drafts [K-1], draft_rows [K-1, V], valid_n, key_data,
    temperature, top_k, top_p) -> (toks [K], n_acc, key_data)`` —
    ``toks[:n_acc]`` are the emitted tokens. ``vmap`` over slots inside
    the SAMPLED branch of :func:`_spec_accept_rows` (the scheme is
    documented in the module-section comment); greedy slots' results are
    discarded by the caller's per-slot select, so the guard temperature
    below only keeps the math finite."""
    K = rows.shape[0]
    safe_t = jnp.where(temperature > 0, temperature, jnp.float32(1.0))

    def samp_step(carry, j):
        kd, alive = carry
        k = jax.random.wrap_key_data(kd)
        nk, ks = jax.random.split(k)               # sample_dyn's split
        pt_log = filter_top_dyn(rows[j] / safe_t, top_k, top_p)
        pt = jax.nn.softmax(pt_log)
        jj = jnp.minimum(j, K - 2)
        d = drafts[jj]
        qt = jax.nn.softmax(filter_top_dyn(draft_rows[jj] / safe_t,
                                           top_k, top_p))
        accept = (jax.random.uniform(ks)
                  < jnp.minimum(pt[d] / jnp.maximum(qt[d], 1e-30), 1.0))
        # rejection: one more split funds the residual draw; an empty
        # residual (q >= p everywhere it matters, a numerical corner)
        # falls back to the plain filtered target distribution
        nk2, kr = jax.random.split(nk)
        resid = jnp.maximum(pt - qt, 0.0)
        resid_log = jnp.where(jnp.sum(resid) > 0,
                              jnp.log(jnp.maximum(resid, 1e-38)), pt_log)
        r_tok = jax.random.categorical(kr, resid_log).astype(jnp.int32)
        # the bonus row (j == K-1, no draft): a plain solo-style sample
        bonus = jax.random.categorical(ks, pt_log).astype(jnp.int32)
        has_draft = j < K - 1
        tok = jnp.where(has_draft, jnp.where(accept, d, r_tok), bonus)
        kd_next = jnp.where(has_draft & ~accept,
                            jax.random.key_data(nk2),
                            jax.random.key_data(nk))
        emit = alive & (j < valid_n)
        kd = jnp.where(emit, kd_next, kd)
        return (kd, emit & accept & has_draft), (tok, emit)

    (kd_s, _), (toks_s, emits) = jax.lax.scan(
        samp_step, (key_data, jnp.bool_(True)), jnp.arange(K))
    return (toks_s.astype(jnp.int32),
            jnp.sum(emits.astype(jnp.int32)).astype(jnp.int32), kd_s)


def _spec_accept_rows(rows, drafts, draft_rows, valid_n, key_data, temps,
                      top_ks, top_ps):
    """Batched speculative acceptance over every slot: ``(rows [S, K, V],
    drafts [S, K], draft_rows [S, K, V] — the propose outputs VERBATIM,
    only the first K-1 proposals are consumed — valid_n [S],
    key_data [S, 2], temps/top_ks/top_ps [S]) -> (toks [S, K],
    n_acc [S], key_data [S, 2])``.

    Greedy (``temps[s] == 0``): the slot's tokens are the target's own
    argmaxes; the emitted count is one more than the leading run of
    draft==argmax matches (the first mismatch position still emits the
    target's correction), capped at ``valid_n``; no randomness is
    consumed, so the key stream stays bit-aligned with solo decode.
    Sampled: the residual-rejection scheme of
    :func:`_spec_accept_sampled`. The sampled scan sits behind ONE
    batch-level ``lax.cond`` — an all-greedy tick (every greedy
    deployment, and the accept-all bench case the >= 2x throughput gate
    measures) never executes the K-step rejection scan at all, which is
    what keeps the verify program's marginal per-token cost near the
    attention math."""
    g = jnp.argmax(rows, axis=-1).astype(jnp.int32)          # [S, K]
    lead = jnp.cumprod((drafts[:, :-1] == g[:, :-1]).astype(jnp.int32),
                       axis=1)
    m_greedy = jnp.minimum(1 + jnp.sum(lead, axis=1),
                           valid_n).astype(jnp.int32)

    def sampled(_):
        return jax.vmap(_spec_accept_sampled)(
            rows, drafts[:, :-1], draft_rows[:, :-1], valid_n, key_data,
            temps, top_ks, top_ps)

    def greedy(_):
        return g, m_greedy, key_data

    toks_s, n_s, kd_s = jax.lax.cond(jnp.any(temps > 0), sampled, greedy,
                                     None)
    sm = temps > 0
    toks = jnp.where(sm[:, None], toks_s, g).astype(jnp.int32)
    n_acc = jnp.where(sm, n_s, m_greedy).astype(jnp.int32)
    kd = jnp.where(sm[:, None], kd_s, key_data)
    return toks, n_acc, kd


def make_slot_propose(stages, cfg: GPTConfig, max_len: int, spec_k: int,
                      cache_dtype=None):
    """Draft proposer: ``propose(params, kc, vc, toks [S], pos [S],
    key_data [S, 2], temps [S], top_ks [S], top_ps [S]) -> (kc, vc,
    drafts [S, K], draft_rows [S, K, V], key_data [S, 2])``.

    ``spec_k`` sequential draft decode steps over the draft's slot-row
    pool, fused into ONE compiled ``lax.scan`` — one dispatch proposes the
    whole tick's draft tokens (plus their raw log-prob rows, which the
    sampled verify's rejection test needs). Step j consumes the carried
    token at position ``pos + j`` (clamped to the never-live trash row
    ``max_len - 1`` past the budget; see the section comment) and per-slot
    math is exactly the decode tick's, so draft K/V rows stay valid for
    every accepted continuation. ``key_data`` is the request's SEPARATE
    draft key stream (greedy proposals consume none of it). The draft runs
    single-device/replicated even under a TP target — it is small by
    design; ``kc``/``vc`` are donated."""
    _validate_slot_build(stages, cfg, max_len, "make_slot_propose",
                         cache_dtype)
    _check_spec_k(spec_k, "make_slot_propose")
    _refuse_tp_draft(cfg, "make_slot_propose")
    H = cfg.n_heads
    key_ = ("slot_propose", cfg, max_len, spec_k)
    return memo_build(key_, lambda: _build_slot_propose(H, spec_k,
                                                        max_len))


def _build_slot_propose(H, K, ml):
    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def propose(params, kc, vc, toks, pos, key_data, temps, top_ks,
                top_ps):
        embed, blocks, head = merged_stage_trees(params)

        def step(carry, j):
            kc, vc, tok, kd = carry
            p = jnp.minimum(pos + j, ml - 1)
            kc, vc, rows = _slot_decode_fwd(blocks, embed, head, kc, vc,
                                            tok, p, H)
            nxt, kd = sample_slots(rows, kd, temps, top_ks, top_ps)
            return (kc, vc, nxt, kd), (nxt, rows)

        (kc, vc, _, kd2), (drafts, rows) = jax.lax.scan(
            step, (kc, vc, toks, key_data), jnp.arange(K))
        return (kc, vc, jnp.moveaxis(drafts, 0, 1),
                jnp.moveaxis(rows, 0, 1), kd2)

    return propose


def _paged_verify_fwd(blocks, embed, head, kc, vc, xs, qpos, wphys, woff,
                      tables, H, bs, dh, tail, kernel="dense", ab_at=None):
    """K-tokens-per-slot verify forward over the paged block pool: scatter
    each position's K/V into ``(wphys, woff)`` (the trash block past the
    budget) and attend the table span, masked per query — via the
    gather-then-dense einsums (``kernel="dense"``) or the one-pass Pallas
    paged-attention kernel's K-token variant (``kernel="fused"``; the
    per-query mask is the kernel's own ``qpos`` plan)."""
    S, K = xs.shape
    pe = jnp.take(embed["pos"], qpos.reshape(-1),
                  axis=0).reshape(S, K, -1)
    h = embedding_lookup(embed["tok"], xs) + pe              # [S, K, d]
    span = tables.shape[1] * bs
    live = (jnp.arange(span)[None, None, None, :]
            <= qpos[:, None, :, None])                       # [S,1,K,span]
    for li, bp in enumerate(blocks):
        q, knew, vnew = _dense_qkv(                          # [S, H, K, dh]
            bp, h, H, None if ab_at is None else ab_at(li))
        kc = paged_scatter(kc, li, wphys, woff, knew.swapaxes(1, 2))
        vc = paged_scatter(vc, li, wphys, woff, vnew.swapaxes(1, 2))
        if kernel == "fused":
            a = paged_attend(kc, vc, li, q, tables, qpos, bs)
        else:
            krow = paged_gather(kc, li, tables, H)          # [S,H,span,dh]
            vrow = paged_gather(vc, li, tables, H)
            scores = (jnp.einsum("bhqd,bhkd->bhqk", q, krow)
                      / math.sqrt(dh))
            scores = jnp.where(live, scores, -jnp.inf)
            a = jnp.einsum("bhqk,bhkd->bhqd",
                           jax.nn.softmax(scores, axis=-1), vrow)
        h = tail(bp, h, a)
    return kc, vc, head_logprobs(head, h)                   # [S, K, V]


def make_paged_verify_step(stages, cfg: GPTConfig, max_len: int,
                           block_size: int, spec_k: int, cache_dtype=None,
                           mesh=None, kernel: str = "dense",
                           adapters: bool = False):
    """Target verify tick: ``verify(params, kc, vc,
    toks [S], pos [S], drafts [S, K], draft_rows [S, K, V],
    valid_n [S], tables [S, NB], key_data [S, 2], temps [S], top_ks [S],
    top_ps [S]) -> (kc, vc, toks [S, K], n_acc [S], key_data [S, 2])``.

    ONE batched forward scores all ``spec_k`` positions of every slot
    (inputs ``[t0, d0, .., d_{K-2}]`` at positions ``pos .. pos+K-1``) and
    runs :func:`_spec_accept_rows`; ``valid_n`` is the slot's clamp
    ``min(spec_k, remaining token budget)`` (0 for non-decoding slots),
    bounding both emission and which positions write real K/V. Per-position
    physical blocks come from the slot's table (``tables[s, (pos+j)//bs]``
    at offset ``(pos+j) % bs``), with positions past ``valid_n`` routed to
    the pool's trash block 0 — a rejected tail (or a non-decoding slot)
    can neither overrun the slot's reservation nor touch a neighbour's
    blocks. The engine must have ``ensure_writable``'d positions
    ``pos .. pos+valid_n-1`` first (same contract as the decode tick).
    ``kc``/``vc`` are donated. With ``cfg.n_tensor_parallel > 1`` (pass the
    ``mesh``): the shard_map twin — head-sharded QKV/O over the
    head-sharded pool, rows re-closed across the model axis before
    acceptance, so every shard accepts the same prefix.
    ``kernel="fused"`` runs the K-token variant of the Pallas
    paged-attention kernel instead of gather-then-dense (same greedy
    bit-exactness contract as :func:`make_paged_decode_step`)."""
    _validate_paged_build(stages, cfg, max_len, block_size,
                          "make_paged_verify_step", cache_dtype)
    _check_spec_k(spec_k, "make_paged_verify_step")
    mesh = _validate_tp_serve(cfg, mesh, "make_paged_verify_step")
    check_attn_kernel(kernel, "make_paged_verify_step")
    H, bs = cfg.n_heads, block_size
    dh = cfg.d_model // H
    key_ = ("paged_verify", cfg, max_len, block_size, spec_k, mesh, kernel,
            adapters)
    if cfg.n_tensor_parallel > 1:
        return memo_build(key_, lambda: _build_paged_verify_step_tp(
            cfg, spec_k, max_len, bs, dh, mesh, kernel, adapters))
    return memo_build(key_, lambda: _build_paged_verify_step(
        H, spec_k, max_len, bs, dh, kernel, adapters))


def _paged_verify_routing(pos, valid_n, tables, K, bs, ml):
    """Per-position write routing for the paged verify: physical block and
    offset for ``pos + j``, the trash block (0) once past the budget."""
    j = jnp.arange(K)[None, :]
    qpos = jnp.minimum(pos[:, None] + j, ml - 1)
    NB = tables.shape[1]
    phys = jnp.take_along_axis(tables, jnp.clip(qpos // bs, 0, NB - 1),
                               axis=1)                       # [S, K]
    wphys = jnp.where(j < valid_n[:, None], phys, 0)         # 0 == TRASH
    woff = qpos % bs
    return qpos, wphys, woff


def _build_paged_verify_step(H, K, ml, bs, dh, kernel="dense",
                             adapters=False):
    def run(params, kc, vc, toks, pos, drafts, draft_rows, valid_n,
            tables, key_data, temps, top_ks, top_ps, ab_at=None):
        embed, blocks, head = merged_stage_trees(params)
        xs = jnp.concatenate([toks[:, None], drafts[:, :-1]], axis=1)
        qpos, wphys, woff = _paged_verify_routing(pos, valid_n, tables, K,
                                                  bs, ml)
        kc, vc, rows = _paged_verify_fwd(blocks, embed, head, kc, vc, xs,
                                         qpos, wphys, woff, tables, H, bs,
                                         dh, _dense_attn_tail, kernel,
                                         ab_at)
        toks2, n_acc, kd2 = _spec_accept_rows(
            rows, drafts, draft_rows, valid_n, key_data, temps, top_ks,
            top_ps)
        return kc, vc, toks2, n_acc, kd2

    if adapters:
        @functools.partial(jax.jit, donate_argnums=(1, 2))
        def verify(params, kc, vc, toks, pos, drafts, draft_rows, valid_n,
                   tables, key_data, temps, top_ks, top_ps, bank, aids):
            return run(params, kc, vc, toks, pos, drafts, draft_rows,
                       valid_n, tables, key_data, temps, top_ks, top_ps,
                       _adapter_layers(bank, aids))

        return verify

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def verify(params, kc, vc, toks, pos, drafts, draft_rows, valid_n,
               tables, key_data, temps, top_ks, top_ps):
        return run(params, kc, vc, toks, pos, drafts, draft_rows, valid_n,
                   tables, key_data, temps, top_ks, top_ps)

    return verify


def _build_paged_verify_step_tp(cfg, K, ml, bs, dh, mesh, kernel="dense",
                                adapters=False):
    tp = cfg.n_tensor_parallel
    tail = functools.partial(_tp_attn_tail, overlap=cfg.overlap)
    H_loc = cfg.n_heads // tp

    def run(params, kc, vc, toks, pos, drafts, draft_rows, valid_n,
            tables, key_data, temps, top_ks, top_ps, ab_at=None):
        blocks, embed, head = _tp_local_trees(params)
        xs = jnp.concatenate([toks[:, None], drafts[:, :-1]], axis=1)
        qpos, wphys, woff = _paged_verify_routing(pos, valid_n, tables, K,
                                                  bs, ml)
        kc, vc, rows = _paged_verify_fwd(blocks, embed, head, kc, vc, xs,
                                         qpos, wphys, woff, tables, H_loc,
                                         bs, dh, tail, kernel, ab_at)
        rows = _close_rows(rows)
        toks2, n_acc, kd2 = _spec_accept_rows(
            rows, drafts, draft_rows, valid_n, key_data, temps, top_ks,
            top_ps)
        return kc, vc, toks2, n_acc, kd2

    if adapters:
        def body(params, kc, vc, toks, pos, drafts, draft_rows, valid_n,
                 tables, key_data, temps, top_ks, top_ps, bank, aids):
            return run(params, kc, vc, toks, pos, drafts, draft_rows,
                       valid_n, tables, key_data, temps, top_ks, top_ps,
                       _tp_adapter_layers(bank, aids, tp))

        return _tp_jit("verify_paged_tp", body, mesh,
                       n_buf_in=2, n_rest_in=12, n_buf_out=2, n_rest_out=3)

    def body(params, kc, vc, toks, pos, drafts, draft_rows, valid_n,
             tables, key_data, temps, top_ks, top_ps):
        return run(params, kc, vc, toks, pos, drafts, draft_rows, valid_n,
                   tables, key_data, temps, top_ks, top_ps)

    return _tp_jit("verify_paged_tp", body, mesh,
                   n_buf_in=2, n_rest_in=10, n_buf_out=2, n_rest_out=3)


def make_paged_spec_tick(stages, cfg: GPTConfig, draft_stages,
                         draft_cfg: GPTConfig, max_len: int,
                         block_size: int, spec_k: int, cache_dtype=None,
                         kernel: str = "dense", adapters: bool = False):
    """The FUSED speculative tick (single-device targets): ``tick(dparams,
    dkc, dvc, params, kc, vc, toks [S], pos [S], valid_n [S],
    tables [S, NB], draft_key_data [S, 2], key_data [S, 2], temps [S],
    top_ks [S], top_ps [S]) -> (dkc, dvc, kc, vc, toks [S, K], n_acc [S],
    key_data, draft_key_data)``.

    One compiled program runs the draft propose scan AND the batched
    target verify — ONE dispatch per speculative tick instead of two, and
    the ``[S, K, V]`` draft log-prob rows never materialize as a program
    output (they flow straight into the acceptance test). Exactly
    :func:`make_slot_propose` over the draft's slot rows composed with
    :func:`make_paged_verify_step` over the target's paged pool
    (``kernel="fused"`` routes it through the Pallas paged-attention
    kernel), so the greedy bit-exactness contract carries over unchanged.
    All four buffers are donated.

    With ``adapters=True`` the tick takes trailing ``(bank, aids)`` and
    forwards them to the VERIFY side only: the draft proposer stays the
    base model (a wrong proposal only costs acceptance rate, never
    correctness — verify's adapted rows decide every emitted token)."""
    if cfg.n_tensor_parallel > 1:
        raise ValueError(
            "make_paged_spec_tick fuses the single-device tick only — a TP "
            "target runs propose and verify as separate dispatches (the "
            "verify is a shard_map program; see InferenceEngine)")
    if draft_cfg.vocab != cfg.vocab:
        raise ValueError(
            f"make_paged_spec_tick: draft vocab {draft_cfg.vocab} != target "
            f"vocab {cfg.vocab}")
    # the draft's slot rows carry no scales: a quantized TARGET dtype falls
    # back to f32 for the draft (the engine builds its draft buffers with
    # the same rule)
    draft_cd = None if is_quantized_dtype(cache_dtype) else cache_dtype
    propose = make_slot_propose(draft_stages, draft_cfg, max_len, spec_k,
                                draft_cd)
    verify = make_paged_verify_step(stages, cfg, max_len, block_size,
                                    spec_k, cache_dtype, kernel=kernel,
                                    adapters=adapters)

    def build():
        def run(dparams, dkc, dvc, params, kc, vc, toks, pos, valid_n,
                tables, dkd, kd, temps, top_ks, top_ps, extra=()):
            dkc, dvc, drafts, qrows, dkd2 = propose(
                dparams, dkc, dvc, toks, pos, dkd, temps, top_ks, top_ps)
            kc, vc, otoks, nacc, kd2 = verify(
                params, kc, vc, toks, pos, drafts, qrows, valid_n,
                tables, kd, temps, top_ks, top_ps, *extra)
            return dkc, dvc, kc, vc, otoks, nacc, kd2, dkd2

        if adapters:
            @functools.partial(jax.jit, donate_argnums=(1, 2, 4, 5))
            def tick(dparams, dkc, dvc, params, kc, vc, toks, pos,
                     valid_n, tables, dkd, kd, temps, top_ks, top_ps,
                     bank, aids):
                return run(dparams, dkc, dvc, params, kc, vc, toks, pos,
                           valid_n, tables, dkd, kd, temps, top_ks,
                           top_ps, (bank, aids))

            return tick

        @functools.partial(jax.jit, donate_argnums=(1, 2, 4, 5))
        def tick(dparams, dkc, dvc, params, kc, vc, toks, pos, valid_n,
                 tables, dkd, kd, temps, top_ks, top_ps):
            return run(dparams, dkc, dvc, params, kc, vc, toks, pos,
                       valid_n, tables, dkd, kd, temps, top_ks, top_ps)

        return tick

    return memo_build(("paged_spec_tick", cfg, draft_cfg, max_len,
                       block_size, spec_k, kernel, adapters), build)


# The memoized decode-path builders, by name — the single list the
# analyzer's program registry and host-side AST lint key off
# (analysis/programs.py enumerates these as compiled entry points;
# analysis/hostlint.py checks each definition routes through memo_build
# and that no call site bypasses it).
DECODE_BUILDERS = {
    "make_cached_decoder": make_cached_decoder,
    "make_slot_prefill": make_slot_prefill,
    "make_paged_prefill_chunk": make_paged_prefill_chunk,
    "make_paged_decode_step": make_paged_decode_step,
    "make_paged_block_copy": make_paged_block_copy,
    "make_paged_block_write": make_paged_block_write,
    "make_adapter_bank_update": make_adapter_bank_update,
    "make_slot_propose": make_slot_propose,
    "make_paged_verify_step": make_paged_verify_step,
    "make_paged_spec_tick": make_paged_spec_tick,
}


def decoder_from_pipeline(pipe, cfg: GPTConfig, prompt_len: int, n_new: int,
                          temperature: float = 0.0, top_k: int | None = None,
                          top_p: float | None = None, cache_dtype=None):
    """Cached decode bound to a training :class:`~..parallel.pipeline.Pipeline`:
    returns ``decode(buf, prompt, key)`` taking the LIVE packed param buffer.

    The bridge from training to inference: no manual unpacking, no separate
    weight copy — checkpoint-restore or train, then decode from the same
    buffer. The buffer is gathered to host and re-split into stage trees per
    call (``Pipeline.unpack``), then the single-device KV-cache decoder runs
    on them; for a training run that decodes once per eval epoch this
    host-side gather is noise. Tensor-/expert-sharded stages are rejected
    (their trees are per-shard slices, not the whole model).
    """
    if any(s.shards is not None or s.expert_shards is not None
           for s in pipe.stages):
        raise ValueError(
            "decoder_from_pipeline needs unsharded stage params — gather "
            "tensor/expert shards into a dense build first")
    dec = make_cached_decoder(pipe.stages, cfg, prompt_len, n_new,
                              temperature=temperature, top_k=top_k,
                              top_p=top_p, cache_dtype=cache_dtype)

    def decode(buf, prompt, key):
        return dec(pipe.unpack(buf), prompt, key)

    return decode


def make_decoder(stages, prompt_len: int, n_new: int,
                 temperature: float = 0.0, top_k: int | None = None,
                 top_p: float | None = None):
    """Build the jitted decode fn: ``decode(params, prompt, key) ->
    [B, prompt_len + n_new]`` tokens.

    Like the ``make_train_step`` pattern: build ONCE and reuse across calls
    to amortize the trace/compile (``generate`` is the one-shot convenience
    wrapper and rebuilds per call). Single-device composition only: stages
    from a ``cfg.n_seq > 1`` build use mesh collectives in their applies and
    cannot run here — decode with an ``n_seq=1`` build of the same weights.
    """
    from jax import lax

    from simple_distributed_machine_learning_tpu.parallel.pipeline import (
        fused_reference,
    )

    if prompt_len < 1:
        raise ValueError(
            "generate needs a non-empty prompt (t0 >= 1): the first decoded "
            "token is conditioned on the prompt's last position")
    # vocab-bound validation of top_k happens at trace time in _filter_top
    # against the actual row width — no reach into the param layout here
    check_sampling_args(temperature, top_k, top_p)
    # the stages are traced at a fixed sequence length (stage 0's in_shape);
    # decode inside that static buffer
    seq_len = int(stages[0].in_shape[0])
    if prompt_len + n_new > seq_len:
        raise ValueError(
            f"prompt {prompt_len} + n_new {n_new} exceeds the model's "
            f"sequence length {seq_len}")
    fused = fused_reference(stages)

    @jax.jit
    def decode(params, prompt, key):
        b = prompt.shape[0]
        buf = jnp.zeros((b, seq_len), jnp.int32)
        buf = lax.dynamic_update_slice_in_dim(
            buf, prompt.astype(jnp.int32), 0, 1)

        def step(carry, i):
            buf, k = carry
            logp = fused(params, buf.astype(jnp.float32), k, True)
            # prediction for position i comes from the read at i-1
            row = lax.dynamic_index_in_dim(logp, i - 1, 1, keepdims=False)
            tok, k = _sample_row(row, k, temperature, top_k, top_p)
            buf = lax.dynamic_update_slice_in_dim(
                buf, tok[:, None].astype(jnp.int32), i, 1)
            return (buf, k), None

        (buf, _), _ = lax.scan(step, (buf, key),
                               prompt_len + jnp.arange(n_new))
        return buf[:, :prompt_len + n_new]

    return decode
