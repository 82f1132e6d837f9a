"""Nemotron-H-style hybrid decoder: a layer is ONE part (a Mamba-2 mixer,
an attention mixer or a latent mixture of experts), in a published order.

The fourth block family of the model zoo (``models/gpt.py``,
``models/jamba.py`` and ``models/sdar.py`` are the others), written from the
published ``config.json`` of NVIDIA's Nemotron-3 hybrids
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16,
``model_type`` ``nemotron_h``):

- layer ``l`` of kind ``pattern[l]``: ``h = h + part(rms(h))``, nothing
  else (no feed-forward part after a mixer: the pattern's ``E`` layers ARE
  the feed-forward parts); then a final RMS norm and an UNTIED head;
- ``M``, Mamba-2 (Dao & Gu 2024): ``[z | xBC | dt] = W_in u``; ``xBC =
  silu(conv(xBC) + b)`` (causal depthwise, width ``d_conv``, over ``x``,
  ``B`` and ``C`` alike); ``delta = softplus(dt + dt_bias)``, ONE a head;
  the recurrence of ``ops/selective_scan.py`` by group (``mamba_heads``
  heads of ``mamba_head_dim`` channels, one decay a head, ``B`` / ``C`` a
  group of heads); ``y = w * GroupRMSNorm(y * silu(z))``, the norm over
  each group's channels AFTER the gate; ``W_out y``;
- ``*``, attention: grouped-query, the head size its own number, no bias
  and **no positional encoding** (the Mamba layers carry order);
- ``E``, a latent mixture: ``s = sigmoid(W_r u)`` in float32 over all
  ``n_experts``, the ``top_k`` largest of ``s + b_sel`` chosen, ``w_e =
  route_scale * s_e / sum of s over the chosen``; ``v = W_dn u`` into the
  latent; experts ``W2_e relu(W1_e v)^2`` there; ``W_up (sum_e w_e E_e(v))
  + W_s2 relu(W_s1 u)^2``, the last a shared expert at full width that
  every token takes. The dropless expert layer of ``ops/moe_experts.py``
  with its sigmoid rule, its two-matrix body and the range of experts HELD:
  ``experts_held`` of the ``n_experts``, from ``expert_offset`` on, are this
  build's (one chip's share where several chips divide each layer); the
  router, the choice and the normaliser are over all of them, the sum over
  the chosen ones that are held, and what the absent ones would add is left
  out. Nothing here stands in for the other chips or their exchange.

Precision as the other hybrids': matmul operands in the weights' dtype with
float32 accumulation; the residual stream, the norms, ``softplus``, the
router's scores, the scan and its state in float32.

Serving threads two kinds of per-sequence state, as ``models/jamba.py``
does: K/V blocks of the attention layers in the paged pool, and per slot and
Mamba layer a recurrent pair ``(H [d_state, d_inner] float32, the last
d_conv - 1 pre-convolution inputs [d_conv - 1, d_inner + 2 G S])``.
:meth:`NemotronHConfig.paged_serving` hands ``serve/engine.py`` that layout
and the two programs (``jit_chunk_pattern_prefill``,
``jit_step_pattern_decode``); host inputs, sampling and seats are
``models/serving.py``'s. The decode program also counts what its expert
layers did (``PagedServing.counters``). Training this family is not built
(the scan kernels have no backward rule), nor is the published
multi-token-prediction layer (a draft head: the model's logits do not
depend on it).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from simple_distributed_machine_learning_tpu.models.serving import (
    NEWEST_PAIR,
    PagedServing,
    check_attn_kernel,
    feed_newest,
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
    seat_newest,
    # tests/bench_cells/test_bench_cells_nemotron_h.py patches this name here
    slot_pair as _slot_pair,
    storage_dtype,
    unpack_chunk,
    unpack_decode,
    validate_hybrid_build,
)
from simple_distributed_machine_learning_tpu.ops.layers import (
    embedding_lookup,
    matmul_acc32,
    rms_norm,
)
from simple_distributed_machine_learning_tpu.ops.losses import log_softmax
from simple_distributed_machine_learning_tpu.ops.moe_experts import (
    dropless_experts,
    relu2_experts,
    sigmoid_top_k,
)
from simple_distributed_machine_learning_tpu.ops.selective_scan import (
    selective_scan,
)
from simple_distributed_machine_learning_tpu.parallel.pipeline import Stage

#: what a decode run counts over its expert layers (``PagedServing.counters``)
EXPERT_COUNTERS = ("experts_hit", "expert_rows", "expert_rows_max")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab: int = 256
    # the longest sequence a serving slot may hold: a budget, not a shape
    # (the family has no position table)
    seq_len: int = 64
    d_model: int = 64
    # one letter a layer: M Mamba-2, * attention, E latent experts
    pattern: str = "ME*E"
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    mamba_heads: int = 8
    mamba_head_dim: int = 32
    n_groups: int = 2
    d_state: int = 16
    d_conv: int = 4
    n_experts: int = 8
    top_k: int = 2
    # the experts this build holds: experts_held of them from expert_offset
    experts_held: int = 8
    expert_offset: int = 0
    d_latent: int = 32
    d_expert: int = 48
    d_shared: int = 96
    route_scale: float = 2.5
    rms_eps: float = 1e-5
    # what the weights are held and read in; bfloat16 as published
    param_dtype: str = "float32"

    # the serving engine's questions of any model config
    recurrent_state = True      # per-slot state beside the K/V pool
    n_tensor_parallel = 1       # no tensor-parallel build of this family

    def __post_init__(self):
        if not self.pattern or set(self.pattern) - set("ME*"):
            raise ValueError(
                f"pattern {self.pattern!r} must be letters of 'M' (Mamba-2), "
                f"'*' (attention) and 'E' (latent experts), one a layer")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"n_kv_heads ({self.n_kv_heads}) must divide n_heads "
                f"({self.n_heads})")
        if self.mamba_heads % self.n_groups or (
                self.d_inner // self.n_groups) % 128:
            raise ValueError(
                f"n_groups ({self.n_groups}) must divide mamba_heads "
                f"({self.mamba_heads}) into runs of a multiple of 128 "
                f"channels (ops/selective_scan.py), got runs of "
                f"{self.d_inner / self.n_groups:g}")
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(
                f"top_k {self.top_k} outside [1, n_experts {self.n_experts}]")
        if not (0 <= self.expert_offset and 1 <= self.experts_held
                and self.expert_offset + self.experts_held <= self.n_experts):
            raise ValueError(
                f"held experts [{self.expert_offset}, "
                f"{self.expert_offset + self.experts_held}) outside the "
                f"router's {self.n_experts}")
        if self.d_conv < 2:
            raise ValueError(f"d_conv must be >= 2, got {self.d_conv}")

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def d_conv_channels(self) -> int:
        """What the convolution runs over: ``x`` and every group's ``B``
        and ``C``."""
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def held(self) -> tuple[int, int]:
        return self.expert_offset, self.experts_held

    def paged_serving(self, stages, max_len: int, block_size: int,
                      cache_dtype=None, mesh=None, kernel: str = "dense",
                      adapters: bool = False) -> PagedServing:
        """The engine's model interface (``models/serving.py::PagedServing``):
        the paged pool holds the attention layers' K/V heads only, and every
        slot has one recurrent pair per Mamba layer and, last, its newest
        token and sampling key."""
        validate_hybrid_build(stages, self, max_len, block_size,
                              cache_dtype, mesh, adapters,
                              caller="NemotronHConfig.paged_serving",
                              maker="make_nemotron_h_stages")
        if "*" not in self.pattern:
            raise ValueError(
                f"NemotronHConfig.paged_serving: pattern {self.pattern!r} "
                f"has no attention layer, and a paged pool without a K/V "
                f"layer is not built")
        check_attn_kernel(kernel, "NemotronHConfig.paged_serving")
        pair = (jax.ShapeDtypeStruct((self.d_state, self.d_inner),
                                     jnp.float32),
                jax.ShapeDtypeStruct((self.d_conv - 1, self.d_conv_channels),
                                     storage_dtype(cache_dtype)))
        return PagedServing(
            kv_layers=self.pattern.count("*"), kv_heads=self.n_kv_heads,
            head_dim=self.head_dim,
            state_shapes=(pair,) * self.pattern.count("M") + (NEWEST_PAIR,),
            chunk_prefill=memo_build(
                ("pattern_chunk", self, block_size),
                lambda: _build_pattern_prefill_chunk(self, block_size)),
            decode=memo_build(
                ("pattern_decode", self, block_size, kernel),
                lambda: _build_pattern_decode_step(self, block_size, kernel)),
            pack_chunk=pack_chunk_inputs, pack_decode=pack_decode_inputs,
            counters=EXPERT_COUNTERS)


# -- parameters ---------------------------------------------------------------


def _normal(key, shape, dt):
    return (0.02 * jax.random.normal(key, shape)).astype(dt)


def _layer_init(key, cfg: NemotronHConfig, kind: str) -> dict:
    """One layer's tree. Matrices normal(0, 0.02); the Mamba-2 code's own
    start for the scan (``A`` uniform in 1..16 a head, ``delta``'s bias the
    inverse softplus of log-uniform 1e-3..1e-1, ``D`` 1); the depthwise
    convolution at torch's ``Conv1d`` default (uniform within
    ``1/sqrt(d_conv)``); the selection bias 0 (float32: it is added to
    float32 scores); norm weights 1."""
    dt = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    mat = functools.partial(_normal, dt=dt)
    ones = lambda m: jnp.ones((m,), dt)  # noqa: E731
    layer = {"norm": ones(d)}
    if kind == "*":
        kq, kk, kv, ko = jax.random.split(key, 4)
        qd, kvd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        layer["attn"] = {"wq": mat(kq, (d, qd)), "wk": mat(kk, (d, kvd)),
                         "wv": mat(kv, (d, kvd)), "wo": mat(ko, (qd, d))}
    elif kind == "M":
        ki, kc, kb, kt, ka, ko = jax.random.split(key, 6)
        di, ch, nh = cfg.d_inner, cfg.d_conv_channels, cfg.mamba_heads
        bound = 1.0 / math.sqrt(cfg.d_conv)
        step = jnp.exp(jax.random.uniform(kt, (nh,), minval=math.log(1e-3),
                                          maxval=math.log(1e-1)))
        layer["mamba"] = {
            "in_proj": mat(ki, (d, di + ch + nh)),
            "conv_w": jax.random.uniform(kc, (cfg.d_conv, ch), minval=-bound,
                                         maxval=bound).astype(dt),
            "conv_b": jax.random.uniform(kb, (ch,), minval=-bound,
                                         maxval=bound).astype(dt),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
            "A_log": jnp.log(jax.random.uniform(
                ka, (nh,), minval=1.0, maxval=16.0)).astype(dt),
            "D": ones(nh),
            "norm": ones(di),
            "out_proj": mat(ko, (di, d)),
        }
    else:
        kr, kd, ku, k1, k2, ks, kt = jax.random.split(key, 7)
        lat, f, held = cfg.d_latent, cfg.d_expert, cfg.experts_held
        layer["moe"] = {
            "router": mat(kr, (d, cfg.n_experts)),
            "bias": jnp.zeros((cfg.n_experts,), jnp.float32),
            "down": mat(kd, (d, lat)), "up": mat(ku, (lat, d)),
            "w1": mat(k1, (held, lat, f)), "w2": mat(k2, (held, f, lat)),
            "shared_in": mat(ks, (d, cfg.d_shared)),
            "shared_out": mat(kt, (cfg.d_shared, d)),
        }
    return layer


def make_nemotron_h_stages(key: jax.Array,
                           cfg: NemotronHConfig = NemotronHConfig(),
                           n_stages: int = 1):
    """The model as the repo's ``Stage`` list, as ``make_gpt_stages`` gives
    it: ``(stages, wire_dim, (seq_len, vocab))``. One stage: the family is
    served, not trained, and the serving programs run on one device."""
    if n_stages != 1:
        raise ValueError(
            f"make_nemotron_h_stages builds one stage, got n_stages="
            f"{n_stages}: this family has no pipeline build (it is served "
            f"from one device and not trained)")
    ke, kh, *kb = jax.random.split(key, 2 + cfg.n_layers)
    dt = jnp.dtype(cfg.param_dtype)
    mat = functools.partial(_normal, dt=dt)
    params = {
        "embed": {"tok": mat(ke, (cfg.vocab, cfg.d_model))},
        "blocks": [_layer_init(k, cfg, kind)
                   for k, kind in zip(kb, cfg.pattern)],
        "head": {"norm_f": jnp.ones((cfg.d_model,), dt),
                 "out": mat(kh, (cfg.d_model, cfg.vocab))},
    }

    def apply(params, x, key, deterministic):
        del key, deterministic          # no dropout in this family
        return log_softmax(full_logits(params, x.astype(jnp.int32), cfg))

    stage = Stage(apply=apply, params=params, in_shape=(cfg.seq_len,),
                  token_input=True)
    return [stage], cfg.seq_len * cfg.d_model, (cfg.seq_len, cfg.vocab)


# -- the layers ---------------------------------------------------------------


def _mamba2_mixer(mp: dict, u, tail, h0, cfg: NemotronHConfig, live=None):
    """The Mamba-2 mixer over ``u [N, L, d]`` (already normed) from the
    recurrent pair ``(h0 [N, S, Di] f32, tail [N, d_conv - 1, Di + 2 G
    S])``. Returns ``(out [N, L, d], h, tail)``. ``live [N]`` (decode
    ticks): the sequences that advance; the others' pair comes back
    unchanged. The pre-convolution input is rounded to the tail's dtype
    BEFORE the convolution, as ``models/jamba.py::_mamba_mixer`` does."""
    f32 = jnp.float32
    n, n_tok, _ = u.shape
    di, ch, k = cfg.d_inner, cfg.d_conv_channels, cfg.d_conv
    g, s, per = cfg.n_groups, cfg.d_state, cfg.mamba_head_dim
    proj = matmul_acc32(u, mp["in_proj"])
    z, xbc, dt = proj[..., :di], proj[..., di:di + ch], proj[..., di + ch:]
    window = jnp.concatenate([tail, xbc.astype(tail.dtype)], axis=1)
    new_tail = window[:, -(k - 1):]
    w = mp["conv_w"].astype(f32)
    conv = sum(window[:, j:j + n_tok].astype(f32) * w[j] for j in range(k))
    xbc = jax.nn.silu(conv + mp["conv_b"].astype(f32))
    x = xbc[..., :di]
    b = xbc[..., di:di + g * s].reshape(n, n_tok, g, s)
    c = xbc[..., di + g * s:].reshape(n, n_tok, g, s)
    delta = jax.nn.softplus(dt + mp["dt_bias"].astype(f32))   # [N, L, heads]
    if live is not None:
        # delta 0 is the recurrence's identity: the sequences that sit this
        # tick out keep their state bit for bit
        delta = jnp.where(live[:, None, None], delta, 0.0)
        new_tail = jnp.where(live[:, None, None], new_tail, tail)
    each = lambda v: jnp.repeat(v.astype(f32), per, axis=-1)  # noqa: E731
    y, h = selective_scan(x, each(delta), None, b, c,
                          each(-jnp.exp(mp["A_log"].astype(f32))),
                          each(mp["D"]), h0)
    y = y * jax.nn.silu(z)
    y = rms_norm(mp["norm"].reshape(g, di // g),
                 y.reshape(n, n_tok, g, di // g), cfg.rms_eps)
    return matmul_acc32(y.reshape(n, n_tok, di), mp["out_proj"]), h, new_tail


def _latent_experts(ep: dict, u, cfg: NemotronHConfig):
    """The ``E`` layer's part over normed ``u [N, L, d]``: the held
    experts' share of the routed sum, up-projected, plus the shared
    expert; and the rows each held expert got ``[experts_held]``."""
    n, n_tok, d = u.shape
    u = u.reshape(n * n_tok, d)
    routed, rows = dropless_experts(
        ep, u, cfg.top_k, route=sigmoid_top_k(ep["bias"], cfg.route_scale),
        experts=relu2_experts, held=cfg.held,
        rows=matmul_acc32(u, ep["down"]))
    mid = jax.nn.relu(matmul_acc32(u, ep["shared_in"]))
    out = matmul_acc32(routed, ep["up"]) + matmul_acc32(mid * mid,
                                                        ep["shared_out"])
    return out.reshape(n, n_tok, d), rows


def _head_logits(head: dict, h, cfg: NemotronHConfig):
    return matmul_acc32(rms_norm(head["norm_f"], h, cfg.rms_eps),
                        head["out"])


def full_logits(params: dict, tokens, cfg: NemotronHConfig):
    """Logits ``[B, T, V]`` of whole sequences ``tokens [B, T]`` from empty
    state: the stage's forward (no cache, every token at once)."""
    f32 = jnp.float32
    bsz, n_tok = tokens.shape
    h = embedding_lookup(params["embed"]["tok"], tokens).astype(f32)
    causal = jnp.tril(jnp.ones((n_tok, n_tok), bool))[None]
    for bp in params["blocks"]:
        u = rms_norm(bp["norm"], h, cfg.rms_eps)
        if "attn" in bp:
            q, k, v = qkv(bp["attn"], u, cfg)
            out = matmul_acc32(grouped_attention(q, k, v, causal, cfg),
                               bp["attn"]["wo"])
        elif "mamba" in bp:
            out, _, _ = _mamba2_mixer(
                bp["mamba"], u,
                jnp.zeros((bsz, cfg.d_conv - 1, cfg.d_conv_channels), f32),
                jnp.zeros((bsz, cfg.d_state, cfg.d_inner), f32), cfg)
        else:
            out, _ = _latent_experts(bp["moe"], u, cfg)
        h = h + out
    return _head_logits(params["head"], h, cfg)


# -- serving: the two paged programs ------------------------------------------


def _pattern_chunk_fwd(params, kc, vc, state, tokens, p0, table, slot,
                       cfg: NemotronHConfig, bs: int):
    """One request's prompt positions ``[p0, p0 + c)`` through every layer,
    as ``models/jamba.py::_hybrid_chunk_fwd`` runs them: the attention
    layers scatter into and attend over the slot's blocks, the Mamba layers
    carry the slot's recurrent pair from the previous chunk (zeros when
    ``p0 == 0``). Returns the last position's logits ``[V]``."""
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
        u = rms_norm(bp["norm"], h, cfg.rms_eps)
        if "attn" in bp:
            q, k, v = qkv(bp["attn"], u, cfg)
            kc = paged_scatter(kc, ai, phys, off, k[0])
            vc = paged_scatter(vc, ai, phys, off, v[0])
            # [KV, span, dh] -> [1, span, KV, dh]
            krow = jnp.swapaxes(
                paged_gather(kc, ai, table, cfg.n_kv_heads), 0, 1)[None]
            vrow = jnp.swapaxes(
                paged_gather(vc, ai, table, cfg.n_kv_heads), 0, 1)[None]
            out = matmul_acc32(grouped_attention(q, krow, vrow, seen, cfg),
                               bp["attn"]["wo"])
            ai += 1
        elif "mamba" in bp:
            ssm, tail = state[mi]
            h0, t0 = _slot_pair(ssm, tail, slot, fresh)
            out, h1, t1 = _mamba2_mixer(bp["mamba"], u, t0, h0, cfg)
            state[mi] = (
                jax.lax.dynamic_update_slice_in_dim(ssm, h1, slot, 0),
                jax.lax.dynamic_update_slice_in_dim(tail, t1, slot, 0))
            mi += 1
        else:
            out, _ = _latent_experts(bp["moe"], u, cfg)
        h = h + out
    return kc, vc, tuple(state), _head_logits(head, h[0, -1], cfg)


def _build_pattern_prefill_chunk(cfg: NemotronHConfig, bs: int):
    """``chunk(params, kc, vc, state, tokens [1, c], host) -> (kc, vc,
    state, token, key_data)``: the contract of
    ``models/jamba.py::_build_hybrid_prefill_chunk`` (same host array, same
    seats), over this family's layers."""
    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def chunk_pattern_prefill(params, kc, vc, state, tokens, host):
        *layers, newest = state
        (p0, table, slot, seat, key_data, temperature, top_k,
         top_p) = unpack_chunk(host)
        kc, vc, layers, row = _pattern_chunk_fwd(
            params, kc, vc, tuple(layers), tokens, p0, table, slot, cfg, bs)
        tok, kd = sample_slot(row, key_data, temperature, top_k, top_p)
        newest = seat_newest(newest, slot, seat, tok, kd, key_data)
        return kc, vc, (*layers, newest), tok, kd

    return chunk_pattern_prefill


def _pattern_decode_fwd(params, kc, vc, state, toks, pos, tables, live,
                        cfg: NemotronHConfig, bs: int, kernel: str):
    """One token for every slot, as ``models/jamba.py::_hybrid_decode_fwd``
    runs it (the slots that sit out ride along at position 0 of an
    all-trash table, their recurrent pair comes back unchanged). Returns
    logits ``[S, V]`` and, per expert layer, the rows each held expert got
    ``[n_E, experts_held]`` (every slot's row counts: the run computes
    them all)."""
    f32 = jnp.float32
    embed, blocks, head = merged_stage_trees(params)
    h = embedding_lookup(embed["tok"], toks[:, None]).astype(f32)  # [S, 1, d]
    phys = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
    off = pos % bs
    span = tables.shape[1] * bs
    seen = (jnp.arange(span)[None, None, :] <= pos[:, None, None])
    state = list(state)
    rows = []
    ai = mi = 0
    for bp in blocks:
        u = rms_norm(bp["norm"], h, cfg.rms_eps)
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
            out = matmul_acc32(a, bp["attn"]["wo"])
            ai += 1
        elif "mamba" in bp:
            ssm, tail = state[mi]
            out, ssm, tail = _mamba2_mixer(bp["mamba"], u, tail, ssm, cfg,
                                           live)
            state[mi] = (ssm, tail)
            mi += 1
        else:
            out, r = _latent_experts(bp["moe"], u, cfg)
            rows.append(r)
        h = h + out
    rows = (jnp.stack(rows) if rows
            else jnp.zeros((1, cfg.experts_held), jnp.int32))
    return kc, vc, tuple(state), _head_logits(head, h[:, 0], cfg), rows


def _build_pattern_decode_step(cfg: NemotronHConfig, bs: int, kernel: str):
    """``step(params, kc, vc, state, host) -> (kc, vc, state, rows [S, 1 +
    3], next_key_data)``: the contract of
    ``models/jamba.py::_build_hybrid_decode_step``, and behind every slot's
    next token the run's :data:`EXPERT_COUNTERS` (``PagedServing.
    counters``): (layer, held expert) pairs that got a row, (token, expert)
    pairs that landed on a held expert, the most rows one expert got."""
    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def step_pattern_decode(params, kc, vc, state, host):
        *layers, newest = state
        toks, key_data = newest
        pos, tables, live, temps, top_ks, top_ps = unpack_decode(host)
        kc, vc, layers, logits, expert_rows = _pattern_decode_fwd(
            params, kc, vc, tuple(layers), toks, pos, tables, live, cfg, bs,
            kernel)
        toks2, kd2 = sample_slots(logits, key_data, temps, top_ks, top_ps)
        counters = jnp.stack([(expert_rows > 0).sum(), expert_rows.sum(),
                              expert_rows.max()]).astype(jnp.int32)
        rows = jnp.concatenate([
            toks2[:, None],
            jnp.broadcast_to(counters, (toks2.shape[0], 3))], axis=1)
        return (kc, vc, (*layers, feed_newest(newest, live, toks2, kd2)),
                rows, kd2)

    return step_pattern_decode
