"""Jamba-style hybrid decoder: Mamba-1 layers beside a few attention layers.

The second block family of the model zoo (``models/gpt.py`` is the first),
written from the published ``config.json`` of AI21's Jamba family
(https://huggingface.co/ai21labs/AI21-Jamba2-3B):

- layer ``i``: ``h = h + mixer_i(rms(h))``, then ``h = h + mlp(rms(h))``;
  ``mixer_i`` is attention when ``i % attn_period == attn_offset``, else
  Mamba; the feed-forward part is the dense SwiGLU of ``ops/layers.py``
  (``num_experts`` 1: no routing);
- attention: grouped-query (``n_kv_heads`` dividing ``n_heads``; the
  published model has ONE K/V head under 20 query heads), no bias and **no
  positional encoding of any kind** (the Mamba layers carry order);
- Mamba (Gu & Dao 2023) with Jamba's inner RMS norms on ``dt``, ``B`` and
  ``C``: ``[x, z] = W_in u``; ``x = silu(conv(x) + b)`` (causal depthwise,
  width ``d_conv``); ``[dt, B, C] = W_x x``, each normed; ``delta =
  softplus(W_dt dt + b_dt)``; the selective scan of
  ``ops/selective_scan.py``; ``W_out (y * silu(z))``;
- no position table, a final RMS norm, and the head TIED to the token
  embedding (``logits = E h``).

Precision: matmul operands in the weights' dtype (bfloat16 as published)
with float32 accumulation; the residual stream, the norms, ``softplus``,
the scan and its state in float32.

Two kinds of per-sequence state, so serving threads two kinds of buffer:
K/V blocks of the attention layers in the paged pool, and per slot and Mamba
layer a recurrent pair ``(H [d_state, d_inner] float32, the last d_conv - 1
pre-convolution inputs)``, laid out with ``d_inner`` in the lane dimension.
:meth:`JambaConfig.paged_serving` hands ``serve/engine.py`` that layout and
the two compiled programs (``jit_chunk_hybrid_prefill``,
``jit_step_hybrid_decode``). Training this family is not built: the stage
runs forward (:func:`make_jamba_stages`), nothing here has a backward rule
through the scan kernel.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from simple_distributed_machine_learning_tpu.models.serving import (
    NEWEST_PAIR,
    SEAT_NONE,
    SEAT_SAMPLE,
    PagedServing,
    check_attn_kernel,
    grouped_attention,
    memo_build,
    merged_stage_trees,
    pack_chunk_inputs,
    pack_decode_inputs,
    paged_attend,
    paged_gather,
    paged_scatter,
    qkv,
    sample_slot,
    sample_slots,
    # tests/bench_cells/test_bench_cells_jamba.py patches this name here
    slot_pair as _slot_pair,
    storage_dtype,
    tied_logits,
    unpack_chunk,
    unpack_decode,
    validate_hybrid_build,
)
from simple_distributed_machine_learning_tpu.ops.layers import (
    embedding_lookup,
    gated_mlp,
    matmul_acc32,
    rms_norm,
)
from simple_distributed_machine_learning_tpu.ops.losses import log_softmax
from simple_distributed_machine_learning_tpu.ops.selective_scan import (
    selective_scan,
)
from simple_distributed_machine_learning_tpu.parallel.pipeline import Stage


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab: int = 256
    # the longest sequence a serving slot may hold: a budget, not a shape
    # (the family has no position table)
    seq_len: int = 64
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 1
    d_ff: int = 128
    n_layers: int = 4
    attn_period: int = 2
    attn_offset: int = 1
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 8
    rms_eps: float = 1e-6
    # what the weights are held and read in; bfloat16 as published
    param_dtype: str = "float32"

    # the serving engine's questions of any model config
    recurrent_state = True      # per-slot state beside the K/V pool
    n_tensor_parallel = 1       # no tensor-parallel build of this family

    def __post_init__(self):
        if self.d_model % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"n_heads ({self.n_heads}) must divide d_model "
                f"({self.d_model}) and n_kv_heads ({self.n_kv_heads}) must "
                f"divide n_heads")
        if not 0 <= self.attn_offset < self.attn_period:
            raise ValueError(
                f"attn_offset {self.attn_offset} outside [0, attn_period "
                f"{self.attn_period})")
        if self.d_conv < 2:
            raise ValueError(f"d_conv must be >= 2, got {self.d_conv}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    def is_attention(self, layer: int) -> bool:
        """The family's own rule for the order of the layer types."""
        return layer % self.attn_period == self.attn_offset

    @property
    def n_attn_layers(self) -> int:
        return sum(self.is_attention(i) for i in range(self.n_layers))

    def paged_serving(self, stages, max_len: int, block_size: int,
                      cache_dtype=None, mesh=None, kernel: str = "dense",
                      adapters: bool = False) -> PagedServing:
        """The engine's model interface (``models/serving.py::PagedServing``):
        the paged pool holds the attention layers' K/V heads only, and every
        slot has one recurrent pair per Mamba layer and, last, its newest
        token and sampling key (the programs feed them back on the
        device)."""
        validate_hybrid_build(stages, self, max_len, block_size,
                              cache_dtype, mesh, adapters)
        check_attn_kernel(kernel, "JambaConfig.paged_serving")
        cd = storage_dtype(cache_dtype)
        pair = (jax.ShapeDtypeStruct((self.d_state, self.d_inner),
                                     jnp.float32),
                jax.ShapeDtypeStruct((self.d_conv - 1, self.d_inner), cd))
        return PagedServing(
            kv_layers=self.n_attn_layers, kv_heads=self.n_kv_heads,
            head_dim=self.head_dim,
            state_shapes=(pair,) * (self.n_layers - self.n_attn_layers) + (
                NEWEST_PAIR,),
            chunk_prefill=memo_build(
                ("hybrid_chunk", self, block_size),
                lambda: _build_hybrid_prefill_chunk(self, block_size)),
            decode=memo_build(
                ("hybrid_decode", self, block_size, kernel),
                lambda: _build_hybrid_decode_step(self, block_size, kernel)),
            pack_chunk=pack_chunk_inputs, pack_decode=pack_decode_inputs)


# -- parameters ---------------------------------------------------------------


def _mlp_init(key, cfg: JambaConfig, dt) -> dict:
    kg, ku, kd = jax.random.split(key, 3)
    mat = lambda k, s: (0.02 * jax.random.normal(k, s)).astype(dt)  # noqa: E731
    return {"gate": mat(kg, (cfg.d_model, cfg.d_ff)),
            "up": mat(ku, (cfg.d_model, cfg.d_ff)),
            "down": mat(kd, (cfg.d_ff, cfg.d_model))}


def _block_init(key, cfg: JambaConfig, layer: int) -> dict:
    """One layer's tree. Matrices normal(0, 0.02); the Mamba paper's own
    start for the scan (``A = -(1..d_state)`` per channel, ``delta``'s bias
    the inverse softplus of log-uniform 1e-3..1e-1, ``D`` 1); the depthwise
    convolution at torch's ``Conv1d`` default (uniform within
    ``1/sqrt(d_conv)``); norm weights 1."""
    dt = jnp.dtype(cfg.param_dtype)
    d, di, n, r = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.dt_rank
    km, kf = jax.random.split(key)
    mat = lambda k, s: (0.02 * jax.random.normal(k, s)).astype(dt)  # noqa: E731
    ones = lambda m: jnp.ones((m,), dt)  # noqa: E731
    block = {"norm_in": ones(d), "norm_ff": ones(d),
             "mlp": _mlp_init(kf, cfg, dt)}
    if cfg.is_attention(layer):
        kq, kk, kv, ko = jax.random.split(km, 4)
        kvd = cfg.n_kv_heads * cfg.head_dim
        block["attn"] = {"wq": mat(kq, (d, d)), "wk": mat(kk, (d, kvd)),
                         "wv": mat(kv, (d, kvd)), "wo": mat(ko, (d, d))}
        return block
    ki, kc, kb, kx, kp, kt, ko = jax.random.split(km, 7)
    bound = 1.0 / math.sqrt(cfg.d_conv)
    step = jnp.exp(jax.random.uniform(kt, (di,), minval=math.log(1e-3),
                                      maxval=math.log(1e-1)))
    block["mamba"] = {
        "in_proj": mat(ki, (d, 2 * di)),
        "conv_w": jax.random.uniform(kc, (cfg.d_conv, di), minval=-bound,
                                     maxval=bound).astype(dt),
        "conv_b": jax.random.uniform(kb, (di,), minval=-bound,
                                     maxval=bound).astype(dt),
        "x_proj": mat(kx, (di, r + 2 * n)),
        "dt_norm": ones(r), "b_norm": ones(n), "c_norm": ones(n),
        "dt_proj": mat(kp, (r, di)),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        # [d_state, d_inner]: the channels in the lane dimension
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None],
            (n, di)).astype(dt),
        "D": ones(di),
        "out_proj": mat(ko, (di, d)),
    }
    return block


def make_jamba_stages(key: jax.Array, cfg: JambaConfig = JambaConfig(),
                      n_stages: int = 1):
    """The model as the repo's ``Stage`` list, as ``make_gpt_stages`` gives
    it: ``(stages, wire_dim, (seq_len, vocab))``. One stage only: the head
    is the token embedding itself, which a first and a last pipeline stage
    would both have to hold (and train) as one tensor."""
    if n_stages != 1:
        raise ValueError(
            f"make_jamba_stages builds one stage, got n_stages={n_stages}: "
            f"the tied head (logits = E h with the embedding matrix "
            f"itself) is not split across pipeline stages")
    ke, *kb = jax.random.split(key, 1 + cfg.n_layers)
    dt = jnp.dtype(cfg.param_dtype)
    params = {
        "embed": {"tok": (0.02 * jax.random.normal(
            ke, (cfg.vocab, cfg.d_model))).astype(dt)},
        "blocks": [_block_init(kb[i], cfg, i) for i in range(cfg.n_layers)],
        "head": {"norm_f": jnp.ones((cfg.d_model,), dt)},
    }

    def apply(params, x, key, deterministic):
        del key, deterministic          # no dropout in this family
        return log_softmax(full_logits(params, x.astype(jnp.int32), cfg))

    stage = Stage(apply=apply, params=params, in_shape=(cfg.seq_len,),
                  token_input=True)
    return [stage], cfg.seq_len * cfg.d_model, (cfg.seq_len, cfg.vocab)


# -- the layers ---------------------------------------------------------------


def _mamba_mixer(mp: dict, u, tail, h0, cfg: JambaConfig, live=None):
    """The Mamba mixer over ``u [N, L, d]`` (already normed) from the
    recurrent pair ``(h0 [N, S, Di] f32, tail [N, d_conv - 1, Di])``.
    Returns ``(out [N, L, d], h, tail)``. ``live [N]`` (decode ticks): the
    sequences that advance; the others' pair comes back unchanged.

    The pre-convolution input is rounded to the tail's dtype BEFORE the
    convolution, current tokens and remembered ones alike, so a sequence's
    numbers do not depend on where its prompt was cut into chunks."""
    f32 = jnp.float32
    n_tok, k = u.shape[1], cfg.d_conv
    x, z = jnp.split(matmul_acc32(u, mp["in_proj"]), 2, axis=-1)
    window = jnp.concatenate([tail, x.astype(tail.dtype)], axis=1)
    new_tail = window[:, -(k - 1):]
    w = mp["conv_w"].astype(f32)
    conv = sum(window[:, j:j + n_tok].astype(f32) * w[j] for j in range(k))
    x = jax.nn.silu(conv + mp["conv_b"].astype(f32))
    r, s = cfg.dt_rank, cfg.d_state
    dbc = matmul_acc32(x, mp["x_proj"])
    dt = rms_norm(mp["dt_norm"], dbc[..., :r], cfg.rms_eps)
    b = rms_norm(mp["b_norm"], dbc[..., r:r + s], cfg.rms_eps)
    c = rms_norm(mp["c_norm"], dbc[..., r + s:], cfg.rms_eps)
    delta = jax.nn.softplus(matmul_acc32(dt, mp["dt_proj"])
                            + mp["dt_bias"].astype(f32))
    if live is not None:
        # delta 0 is the recurrence's identity (exp(0) * H + 0): the
        # sequences that sit this tick out keep their state bit for bit
        delta = jnp.where(live[:, None, None], delta, 0.0)
        new_tail = jnp.where(live[:, None, None], new_tail, tail)
    y, h = selective_scan(x, delta, z, b, c, -jnp.exp(mp["A_log"].astype(f32)),
                          mp["D"], h0)
    return matmul_acc32(y, mp["out_proj"]), h, new_tail


def full_logits(params: dict, tokens, cfg: JambaConfig):
    """Logits ``[B, T, V]`` of whole sequences ``tokens [B, T]`` from empty
    state: the stage's forward (no cache, every token at once)."""
    f32 = jnp.float32
    bsz, n_tok = tokens.shape
    h = embedding_lookup(params["embed"]["tok"], tokens).astype(f32)
    causal = jnp.tril(jnp.ones((n_tok, n_tok), bool))[None]
    for bp in params["blocks"]:
        u = rms_norm(bp["norm_in"], h, cfg.rms_eps)
        if "attn" in bp:
            q, k, v = qkv(bp["attn"], u, cfg)
            h = h + matmul_acc32(grouped_attention(q, k, v, causal, cfg),
                                 bp["attn"]["wo"])
        else:
            out, _, _ = _mamba_mixer(
                bp["mamba"], u,
                jnp.zeros((bsz, cfg.d_conv - 1, cfg.d_inner), f32),
                jnp.zeros((bsz, cfg.d_state, cfg.d_inner), f32), cfg)
            h = h + out
        h = h + gated_mlp(bp["mlp"], rms_norm(bp["norm_ff"], h, cfg.rms_eps))
    return tied_logits(params, h, cfg)


# -- serving: the two paged programs ------------------------------------------


def _hybrid_chunk_fwd(params, kc, vc, state, tokens, p0, table, slot,
                      cfg: JambaConfig, bs: int):
    """One request's prompt positions ``[p0, p0 + c)`` through every layer:
    the attention layers scatter into and attend over the slot's blocks as
    GPT's chunk does, the Mamba layers carry the slot's recurrent pair from
    the previous chunk — or start from zeros when ``p0 == 0``, so a slot
    never sees its last occupant's state. Returns the last position's
    logits ``[V]``."""
    f32 = jnp.float32
    embed, blocks, head = merged_stage_trees(params)
    c = tokens.shape[1]
    h = embedding_lookup(embed["tok"], tokens.astype(jnp.int32)).astype(f32)
    idx = p0 + jnp.arange(c)
    phys, off = table[idx // bs], idx % bs
    span = table.shape[0] * bs
    seen = (jnp.arange(span)[None, :] <= idx[:, None])[None]   # [1, c, span]
    fresh = p0 == 0
    state = list(state)
    ai = mi = 0
    for bp in blocks:
        u = rms_norm(bp["norm_in"], h, cfg.rms_eps)
        if "attn" in bp:
            q, k, v = qkv(bp["attn"], u, cfg)
            kc = paged_scatter(kc, ai, phys, off, k[0])
            vc = paged_scatter(vc, ai, phys, off, v[0])
            # [KV, span, dh] -> [1, span, KV, dh]
            krow = jnp.swapaxes(
                paged_gather(kc, ai, table, cfg.n_kv_heads), 0, 1)[None]
            vrow = jnp.swapaxes(
                paged_gather(vc, ai, table, cfg.n_kv_heads), 0, 1)[None]
            h = h + matmul_acc32(
                grouped_attention(q, krow, vrow, seen, cfg),
                bp["attn"]["wo"])
            ai += 1
        else:
            ssm, tail = state[mi]
            h0, t0 = _slot_pair(ssm, tail, slot, fresh)
            out, h1, t1 = _mamba_mixer(bp["mamba"], u, t0, h0, cfg)
            state[mi] = (
                jax.lax.dynamic_update_slice_in_dim(ssm, h1, slot, 0),
                jax.lax.dynamic_update_slice_in_dim(tail, t1, slot, 0))
            h = h + out
            mi += 1
        h = h + gated_mlp(bp["mlp"], rms_norm(bp["norm_ff"], h, cfg.rms_eps))
    logits = tied_logits({"embed": embed, "head": head}, h[:, -1], cfg)
    return kc, vc, tuple(state), logits[0]


def _build_hybrid_prefill_chunk(cfg: JambaConfig, bs: int):
    """``chunk(params, kc, vc, state, tokens [1, c], host) -> (kc, vc,
    state, token, key_data)`` with ``host = pack_chunk_inputs(tokens, p0,
    table [NB], slot, seat, key_data, temperature, top_k, top_p)[1]``; pool
    and state buffers are donated. ``seat`` says what becomes the slot's
    newest token and key in the state's last pair (``PagedServing``).
    Retraces per chunk length, like GPT's."""
    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def chunk_hybrid_prefill(params, kc, vc, state, tokens, host):
        *layers, (newest, keys) = state
        (p0, table, slot, seat, key_data, temperature, top_k,
         top_p) = unpack_chunk(host)
        kc, vc, layers, row = _hybrid_chunk_fwd(
            params, kc, vc, tuple(layers), tokens, p0, table, slot, cfg, bs)
        tok, kd = sample_slot(row, key_data, temperature, top_k, top_p)
        own = seat == SEAT_SAMPLE
        newest = newest.at[slot].set(jnp.where(
            seat == SEAT_NONE, newest[slot], jnp.where(own, tok, seat)))
        keys = keys.at[slot].set(jnp.where(
            seat == SEAT_NONE, keys[slot], jnp.where(own, kd, key_data)))
        return kc, vc, (*layers, (newest, keys)), tok, kd

    return chunk_hybrid_prefill


def _hybrid_decode_fwd(params, kc, vc, state, toks, pos, tables, live,
                       cfg: JambaConfig, bs: int, kernel: str):
    """One token for every slot. ``live [S]``: the decoding slots; the
    others ride along at position 0 of an all-trash table (their K/V write
    lands in the trash block, as in GPT's tick) and their recurrent pair
    is returned unchanged — a slot in the middle of its prefill must find
    its state as its last chunk left it. Returns logits ``[S, V]``."""
    f32 = jnp.float32
    embed, blocks, head = merged_stage_trees(params)
    h = embedding_lookup(embed["tok"], toks[:, None]).astype(f32)  # [S, 1, d]
    phys = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
    off = pos % bs
    span = tables.shape[1] * bs
    seen = (jnp.arange(span)[None, None, :] <= pos[:, None, None])
    state = list(state)
    ai = mi = 0
    for bp in blocks:
        u = rms_norm(bp["norm_in"], h, cfg.rms_eps)
        if "attn" in bp:
            q, k, v = qkv(bp["attn"], u, cfg)
            kc = paged_scatter(kc, ai, phys, off, k[:, 0])
            vc = paged_scatter(vc, ai, phys, off, v[:, 0])
            if kernel == "fused":
                a = paged_attend(kc, vc, ai, jnp.swapaxes(q, 1, 2), tables,
                                 pos[:, None], bs)           # [S, H, 1, dh]
                a = jnp.swapaxes(a, 1, 2).reshape(a.shape[0], 1, -1)
            else:
                # [S, KV, span, dh] -> [S, span, KV, dh]
                krow = jnp.swapaxes(
                    paged_gather(kc, ai, tables, cfg.n_kv_heads), 1, 2)
                vrow = jnp.swapaxes(
                    paged_gather(vc, ai, tables, cfg.n_kv_heads), 1, 2)
                a = grouped_attention(q, krow, vrow, seen, cfg)
            h = h + matmul_acc32(a, bp["attn"]["wo"])
            ai += 1
        else:
            ssm, tail = state[mi]
            out, ssm, tail = _mamba_mixer(bp["mamba"], u, tail, ssm, cfg,
                                          live)
            state[mi] = (ssm, tail)
            h = h + out
            mi += 1
        h = h + gated_mlp(bp["mlp"], rms_norm(bp["norm_ff"], h, cfg.rms_eps))
    logits = tied_logits({"embed": embed, "head": head}, h[:, 0], cfg)
    return kc, vc, tuple(state), logits


def _build_hybrid_decode_step(cfg: JambaConfig, bs: int, kernel: str):
    """``step(params, kc, vc, state, host) -> (kc, vc, state, next_toks,
    next_key_data)`` with ``host, = pack_decode_inputs(toks [S], pos [S],
    tables [S, NB], live [S], key_data [S, 2], temps, top_ks, top_ps)``;
    pool and state buffers are donated. Every slot's input token and key
    are the state's last pair, where the live slots' new ones go back
    (``PagedServing``): the next step needs nothing from the host
    that this one computes."""
    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def step_hybrid_decode(params, kc, vc, state, host):
        *layers, (toks, key_data) = state
        pos, tables, live, temps, top_ks, top_ps = unpack_decode(host)
        kc, vc, layers, rows = _hybrid_decode_fwd(
            params, kc, vc, tuple(layers), toks, pos, tables, live, cfg, bs,
            kernel)
        toks2, kd2 = sample_slots(rows, key_data, temps, top_ks, top_ps)
        newest = (jnp.where(live, toks2, toks),
                  jnp.where(live[:, None], kd2, key_data))
        return kc, vc, (*layers, newest), toks2, kd2

    return step_hybrid_decode
