"""Kimi-Linear-style hybrid decoder: gated delta-rule linear attention (KDA)
layers with a matrix state a head beside latent-attention layers whose cache
is ONE absorbed row a position, sigmoid top-k routed SwiGLU experts beside a
shared one behind a leading dense layer.

The seventh block family of the model zoo (``models/gpt.py``, ``jamba.py``,
``sdar.py``, ``nemotron_h.py``, ``zaya.py`` and ``cohere2.py`` are the
others), written from the published ``config.json`` of Moonshot AI's
Kimi-Linear-48B-A3B-Instruct
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct, ``model_type``
``kimi_linear``) and the family's published description (the Kimi Linear
report, arXiv:2510.26692). A layer over the residual stream ``h [T, d]``:
``h <- h + Mix(rms(h))``, then ``h <- h + FFN(rms(h))``, two RMS norms.

- ``Mix`` of a KDA layer (every layer not in ``attn_layers``): ``q~ = u
  W_q``, ``k~ = u W_k``, ``v~ = u W_v`` (``kda_heads`` heads of
  ``kda_head_dim``); each passes a causal depthwise convolution of ``d_conv``
  taps over time and SiLU; per head ``q`` and ``k`` are brought to unit
  length and ``q`` is scaled by ``dk^-0.5``. The decay, a vector a head and
  token: ``g = -exp(A_log_h) softplus(u W_fa W_fb + dt_bias)``, ``alpha =
  exp(g)``; the step ``beta = sigmoid(u W_b)``, a number a head and token.
  The recurrence of ``ops/kda.py``, ``S [dk, dk]`` float32 a head: ``S' =
  diag(alpha_t) S``; ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``; ``o_t =
  S_t^T q_t``. Then ``y = rms_dk(o) w_o_norm sigmoid(u W_ga W_gb)`` per head
  and ``concat_heads(y) W_o``.
- ``Mix`` of a latent-attention layer (``attn_layers``, counted from 0):
  ``q = u W_q`` (``n_heads`` heads of ``d_nope + d_rope``, no low-rank
  query); ``[c ; r] = u W_kva`` (``d_latent + d_rope``); ``c^ = rms(c)
  w_kv``; per head ``[k^n_i ; v_i] = c^ W_kvb,i``; the key is ``[k^n_i ;
  r]``, the ``d_rope`` lanes ``r`` shared by all heads, and **no rotation is
  applied to any lane** (the published ``mla_use_nope``: the lanes keep
  their name and are plain key lanes; the KDA layers carry order); scores
  over ``sqrt(d_nope + d_rope)``, causal.
- ``FFN``: layers below ``n_dense`` a SwiGLU of width ``d_ff``; the others
  ``s = sigmoid(u W_r)`` in float32 over all ``n_experts``, the ``top_k``
  largest of ``s + b`` chosen, ``w_e = route_scale s_e / sum of s over the
  chosen`` (``ops/moe_experts.py::sigmoid_top_k``), SwiGLU experts of
  ``d_expert`` through the dropless layer, plus ``n_shared`` shared experts
  (held as one ``d -> n_shared d_expert -> d`` gated product) added
  unscaled. The build HOLDS ``experts_held`` of the routed experts from
  ``expert_offset`` on (a chip's share of a layer that several chips split
  by experts): routing, the choice and the normaliser are over all of them,
  the sum over the chosen ones that are held.
- after the last layer a final RMS norm and an UNTIED head over the rows of
  the vocabulary this build holds.

Precision as the other served families': matmul operands in the weights'
dtype with float32 accumulation; the residual stream, the norms, ``softplus``,
the gates, the router's scores, the recurrence and its state, the
convolutions and their tails in float32.

Serving threads two kinds of per-sequence state. Per slot and KDA layer:
``S [kda_heads, dk, dk]`` float32 and the last ``d_conv - 1`` inputs of each
of the three convolutions. Per position and latent layer, in the paged pool:
ONE row ``[c^ ; r]`` (``d_latent + d_rope`` lanes, zero-padded to whole
lane tiles: :attr:`KimiLinearConfig.d_cache`), 1/18 of the expanded keys and
values at the published sizes, and NO value buffer
(``PagedServing.value_lanes``): attention over the cache is ABSORBED. With
``W_kvb,i = [W^K_i ; W^V_i]``: ``q'_i = [q^n_i W^K_i^T ; q^r_i]``, scores
``q'_i . [c^_j ; r_j]``, ``o'_i = softmax . c^_j``, ``o_i = o'_i W^V_i``: the
same sums in another order, every query head a row over one cached row
whose leading ``d_latent`` lanes are its own values. The decode
(``jit_step_kda_decode``) takes the recurrence's STEP (one token of every
live slot, the state in and out once) and attends through
``ops/paged_attention.py``'s one-stream case; the chunk
(``jit_chunk_kda_prefill``) takes its WALK from the slot's state (64 tokens
at a time as float32 matrix products, the same recurrence rearranged:
``ops/kda.py``), writes its rows, then
attends absorbed too, over the slot's live positions
(``models/serving.py::span_attention`` with the one buffer as keys and
values); :func:`full_logits` (no cache) expands keys and values as the
equations above write them. Host inputs, sampling and seats are
``models/serving.py``'s. The decode program also counts what its expert
layers did (``PagedServing.counters``), over the LIVE slots' rows: a slot
that sits a tick out is routed to no held expert and its state does not
move. Training this family is not built (``ops/kda.py`` has no backward
rule).
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
    memo_build,
    merged_stage_trees,
    pack_chunk_inputs,
    pack_decode_inputs,
    paged_scatter,
    sample_slot,
    sample_slots,
    seat_newest,
    span_attention,
    unpack_chunk,
    unpack_decode,
    validate_hybrid_build,
)
from simple_distributed_machine_learning_tpu.ops.kda import kda_recurrence
from simple_distributed_machine_learning_tpu.ops.layers import (
    embedding_lookup,
    gated_mlp,
    matmul_acc32,
    rms_norm,
)
from simple_distributed_machine_learning_tpu.ops.losses import log_softmax
from simple_distributed_machine_learning_tpu.ops.moe_experts import (
    dropless_experts,
    sigmoid_top_k,
    swiglu_experts,
)
from simple_distributed_machine_learning_tpu.ops.paged_attention import (
    paged_attention,
)
from simple_distributed_machine_learning_tpu.parallel.pipeline import Stage

#: what a decode run counts over its expert layers (``PagedServing.counters``)
EXPERT_COUNTERS = ("experts_hit", "expert_rows", "expert_rows_max")
#: unit length's floor under the square root (``q`` and ``k`` of a KDA head)
_UNIT_EPS = 1e-6
_LANES = 128


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    # the rows of the embedding and of the untied head this build holds
    vocab: int = 96
    # the longest sequence a serving slot may hold: a budget, not a shape
    seq_len: int = 64
    d_model: int = 64
    n_layers: int = 4
    # the latent-attention layers, counted from 0; every other is a KDA layer
    attn_layers: tuple = (3,)
    n_heads: int = 4
    d_nope: int = 16
    d_rope: int = 8
    d_v: int = 16
    d_latent: int = 32
    kda_heads: int = 4
    kda_head_dim: int = 16
    d_conv: int = 4
    # the rank of the decay's and of the output gate's two-matrix projections
    d_gate: int = 16
    # layers below n_dense have a dense feed-forward part of width d_ff
    n_dense: int = 1
    d_ff: int = 128
    n_experts: int = 8
    top_k: int = 2
    # the routed experts this build holds: experts_held from expert_offset
    experts_held: int = 8
    expert_offset: int = 0
    n_shared: int = 1
    d_expert: int = 32
    route_scale: float = 2.446
    rms_eps: float = 1e-5
    # what the weights are held and read in; bfloat16 as published
    param_dtype: str = "float32"

    # the serving engine's questions of any model config
    recurrent_state = True      # per-slot state beside the K/V pool
    n_tensor_parallel = 1       # no tensor-parallel build of this family

    def __post_init__(self):
        object.__setattr__(self, "attn_layers", tuple(self.attn_layers))
        if any(not 0 <= l < self.n_layers for l in self.attn_layers) or len(
                set(self.attn_layers)) != len(self.attn_layers):
            raise ValueError(
                f"attn_layers {self.attn_layers} must be distinct layers of "
                f"the {self.n_layers}, counted from 0")
        if not (0 <= self.expert_offset and 1 <= self.experts_held
                and self.expert_offset + self.experts_held <= self.n_experts
                and 1 <= self.top_k <= self.n_experts):
            raise ValueError(
                f"held experts [{self.expert_offset}, "
                f"{self.expert_offset + self.experts_held}) outside the "
                f"{self.n_experts} routed, or top_k {self.top_k} of them")
        if self.d_conv < 2 or self.n_shared < 1 or not (
                0 <= self.n_dense <= self.n_layers):
            raise ValueError(
                f"d_conv ({self.d_conv}) must be >= 2, n_shared "
                f"({self.n_shared}) >= 1 and n_dense ({self.n_dense}) within "
                f"the {self.n_layers} layers")

    @property
    def held(self) -> tuple[int, int]:
        return self.expert_offset, self.experts_held

    @property
    def d_kda(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def d_cache(self) -> int:
        """The pool's row: the normed latent, the shared key lanes, and
        zeros up to whole lane tiles (the chip holds a row in whole tiles
        whatever it is told, and the kernel copies whole tiles)."""
        return -(-(self.d_latent + self.d_rope) // _LANES) * _LANES

    def is_attn(self, layer: int) -> bool:
        return layer in self.attn_layers

    def state_shapes(self) -> tuple:
        """Per KDA layer the matrix state and the three convolutions' tails
        (``q``, ``k``, ``v``), then the newest pair."""
        sd = jax.ShapeDtypeStruct
        tail = sd((self.d_conv - 1, self.d_kda), jnp.float32)
        layer = (sd((self.kda_heads, self.kda_head_dim, self.kda_head_dim),
                    jnp.float32), tail, tail, tail)
        return (layer,) * (self.n_layers - len(self.attn_layers)) + (
            NEWEST_PAIR,)

    def paged_serving(self, stages, max_len: int, block_size: int,
                      cache_dtype=None, mesh=None, kernel: str = "dense",
                      adapters: bool = False) -> PagedServing:
        """The engine's model interface (``models/serving.py::PagedServing``):
        the paged pool holds the latent layers' ONE row a position and no
        value buffer, and every slot has a KDA layer's state four times
        over and, last, its newest token and sampling key."""
        validate_hybrid_build(stages, self, max_len, block_size,
                              cache_dtype, mesh, adapters,
                              caller="KimiLinearConfig.paged_serving",
                              maker="make_kimi_linear_stages")
        if not self.attn_layers:
            raise ValueError(
                "KimiLinearConfig.paged_serving: no latent-attention layer "
                "(attn_layers is empty), and a paged pool without a K/V "
                "layer is not built")
        check_attn_kernel(kernel, "KimiLinearConfig.paged_serving")
        return PagedServing(
            kv_layers=len(self.attn_layers), kv_heads=1,
            head_dim=self.d_cache, state_shapes=self.state_shapes(),
            chunk_prefill=memo_build(
                ("kda_chunk", self, block_size),
                lambda: _build_kda_prefill_chunk(self, block_size)),
            decode=memo_build(
                ("kda_decode", self, block_size, kernel),
                lambda: _build_kda_decode_step(self, block_size, kernel)),
            pack_chunk=pack_chunk_inputs, pack_decode=pack_decode_inputs,
            counters=EXPERT_COUNTERS, value_lanes=self.d_latent)


# -- parameters ---------------------------------------------------------------


def _layer_init(key, cfg: KimiLinearConfig, layer: int) -> dict:
    """One layer's tree. Matrices normal(0, 0.02), norm weights 1; the
    depthwise convolutions at torch's ``Conv1d`` default (uniform within
    ``1 / sqrt(d_conv)``, no bias); ``A_log = log(uniform(1, 16))`` a head
    and ``dt_bias`` the inverse softplus of a log-uniform 1e-3 to 1e-1, as
    the two state-space families start theirs; the selection bias 0
    (float32: it is added to float32 scores). The routed experts are the
    HELD ones alone."""
    dt = jnp.dtype(cfg.param_dtype)
    d, f, held = cfg.d_model, cfg.d_expert, cfg.experts_held
    mat = lambda k, *s: (0.02 * jax.random.normal(k, s)).astype(dt)  # noqa: E731
    ones = lambda n: jnp.ones((n,), dt)  # noqa: E731
    km, kf = jax.random.split(key)
    out = {"norm1": ones(d), "norm2": ones(d)}
    if cfg.is_attn(layer):
        kq, ka, kb, ko = jax.random.split(km, 4)
        h = cfg.n_heads
        out["mla"] = {
            "wq": mat(kq, d, h * (cfg.d_nope + cfg.d_rope)),
            "wkv_a": mat(ka, d, cfg.d_latent + cfg.d_rope),
            "kv_norm": ones(cfg.d_latent),
            "wkv_b": mat(kb, cfg.d_latent, h * (cfg.d_nope + cfg.d_v)),
            "wo": mat(ko, h * cfg.d_v, d)}
    else:
        ks = jax.random.split(km, 14)
        c, r, nh = cfg.d_kda, cfg.d_gate, cfg.kda_heads
        bound = 1.0 / math.sqrt(cfg.d_conv)
        conv = lambda k: jax.random.uniform(  # noqa: E731
            k, (cfg.d_conv, c), minval=-bound, maxval=bound).astype(dt)
        step = jnp.exp(jax.random.uniform(
            ks[12], (c,), minval=math.log(1e-3), maxval=math.log(1e-1)))
        out["kda"] = {
            "wq": mat(ks[0], d, c), "wk": mat(ks[1], d, c),
            "wv": mat(ks[2], d, c),
            "conv_q": conv(ks[3]), "conv_k": conv(ks[4]),
            "conv_v": conv(ks[5]),
            "f_a": mat(ks[6], d, r), "f_b": mat(ks[7], r, c),
            "A_log": jnp.log(jax.random.uniform(
                ks[13], (nh,), minval=1.0, maxval=16.0)).astype(dt),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
            "w_beta": mat(ks[8], d, nh),
            "g_a": mat(ks[9], d, r), "g_b": mat(ks[10], r, c),
            "o_norm": ones(cfg.kda_head_dim),
            "wo": mat(ks[11], c, d)}
    if layer < cfg.n_dense:
        kg, ku, kd = jax.random.split(kf, 3)
        out["mlp"] = {"gate": mat(kg, d, cfg.d_ff), "up": mat(ku, d, cfg.d_ff),
                      "down": mat(kd, cfg.d_ff, d)}
    else:
        kr, kg, ku, kd, sg, su, sd = jax.random.split(kf, 7)
        sf = cfg.n_shared * f
        out["moe"] = {"router": mat(kr, d, cfg.n_experts),
                      "bias": jnp.zeros((cfg.n_experts,), jnp.float32),
                      "gate": mat(kg, held, d, f), "up": mat(ku, held, d, f),
                      "down": mat(kd, held, f, d)}
        out["shared"] = {"gate": mat(sg, d, sf), "up": mat(su, d, sf),
                         "down": mat(sd, sf, d)}
    return out


def make_kimi_linear_stages(key: jax.Array,
                            cfg: KimiLinearConfig = KimiLinearConfig(),
                            n_stages: int = 1):
    """The model as the repo's ``Stage`` list, as ``make_gpt_stages`` gives
    it: ``(stages, wire_dim, (seq_len, vocab))``. One stage: the family is
    served, not trained, and the serving programs run on one device."""
    if n_stages != 1:
        raise ValueError(
            f"make_kimi_linear_stages builds one stage, got n_stages="
            f"{n_stages}: this family has no pipeline build (it is served "
            f"from one device and not trained)")
    ke, kh, *kb = jax.random.split(key, 2 + cfg.n_layers)
    dt = jnp.dtype(cfg.param_dtype)
    mat = lambda k, *s: (0.02 * jax.random.normal(k, s)).astype(dt)  # noqa: E731
    params = {
        "embed": {"tok": mat(ke, cfg.vocab, cfg.d_model)},
        "blocks": [_layer_init(k, cfg, l) for l, k in enumerate(kb)],
        "head": {"norm_f": jnp.ones((cfg.d_model,), dt),
                 "out": mat(kh, cfg.d_model, cfg.vocab)},
    }

    def apply(params, x, key, deterministic):
        del key, deterministic          # no dropout in this family
        return log_softmax(full_logits(params, x.astype(jnp.int32), cfg))

    stage = Stage(apply=apply, params=params, in_shape=(cfg.seq_len,),
                  token_input=True)
    return [stage], cfg.seq_len * cfg.d_model, (cfg.seq_len, cfg.vocab)


# -- the layers ---------------------------------------------------------------


def _short_conv(w, x, tail):
    """``silu`` of the causal depthwise convolution of ``x [N, L, C]`` with
    taps ``w [d_conv, C]`` (the last tap meets the newest input), the
    ``d_conv - 1`` inputs before ``x`` being ``tail [N, d_conv - 1, C]``;
    and the new tail."""
    n_tok, k = x.shape[1], w.shape[0]
    window = jnp.concatenate([tail, x], axis=1)
    w = w.astype(jnp.float32)
    conv = sum(window[:, j:j + n_tok] * w[j] for j in range(k))
    return jax.nn.silu(conv), window[:, -(k - 1):]


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + _UNIT_EPS)


def _kda_mixer(kp: dict, u, state, cfg: KimiLinearConfig, live=None):
    """The KDA mixer over normed ``u [N, L, d]`` from a layer's state ``(S
    [N, H, dk, dk], tail_q, tail_k, tail_v [N, d_conv - 1, H dk])``.
    Returns ``(out [N, L, d], state)``. ``live [N]`` (decode ticks): the
    sequences that advance; the others' state comes back bit for bit."""
    f32 = jnp.float32
    n, n_tok, _ = u.shape
    nh, dk = cfg.kda_heads, cfg.kda_head_dim
    s0, *tails = state
    heads = lambda x: x.reshape(n, n_tok, nh, dk)  # noqa: E731
    new_tails, qkv = [], []
    for name, tail in zip("qkv", tails):
        x, t = _short_conv(kp["conv_" + name],
                           matmul_acc32(u, kp["w" + name]), tail)
        if live is not None:
            t = jnp.where(live[:, None, None], t, tail)
        qkv.append(heads(x))
        new_tails.append(t)
    q, k, v = qkv
    q, k = _unit(q) * dk ** -0.5, _unit(k)
    g = -jnp.exp(kp["A_log"].astype(f32))[:, None] * heads(jax.nn.softplus(
        matmul_acc32(matmul_acc32(u, kp["f_a"]), kp["f_b"])
        + kp["dt_bias"].astype(f32)))
    beta = jax.nn.sigmoid(matmul_acc32(u, kp["w_beta"]))
    o, s = kda_recurrence(q, k, v, g, beta, s0, live=live)
    gate = jax.nn.sigmoid(heads(
        matmul_acc32(matmul_acc32(u, kp["g_a"]), kp["g_b"])))
    y = rms_norm(kp["o_norm"], o, cfg.rms_eps) * gate
    return (matmul_acc32(y.reshape(n, n_tok, nh * dk), kp["wo"]),
            (s, *new_tails))


def _latent_qr(mp: dict, u, cfg: KimiLinearConfig):
    """``q [N, L, H, d_nope + d_rope]`` and the row the cache holds of each
    position, ``[c^ ; r] [N, L, d_latent + d_rope]``, float32."""
    n, n_tok, _ = u.shape
    q = matmul_acc32(u, mp["wq"]).reshape(n, n_tok, cfg.n_heads, -1)
    ckv = matmul_acc32(u, mp["wkv_a"])
    c = rms_norm(mp["kv_norm"], ckv[..., :cfg.d_latent], cfg.rms_eps)
    return q, jnp.concatenate([c, ckv[..., cfg.d_latent:]], axis=-1)


def _score_scale(cfg: KimiLinearConfig) -> float:
    return 1.0 / math.sqrt(cfg.d_nope + cfg.d_rope)


def _latent_attention(mp: dict, u, cfg: KimiLinearConfig):
    """Latent attention over whole sequences ``u [N, L, d]``, EXPANDED (the
    keys and values of every position made from its row), causal."""
    n, n_tok, _ = u.shape
    nh, dn = cfg.n_heads, cfg.d_nope
    q, row = _latent_qr(mp, u, cfg)
    kv = matmul_acc32(row[..., :cfg.d_latent], mp["wkv_b"]).reshape(
        n, n_tok, nh, dn + cfg.d_v)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        row[:, :, None, cfg.d_latent:], (n, n_tok, nh, cfg.d_rope))], -1)
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k) * _score_scale(cfg)
    causal = jnp.tril(jnp.ones((n_tok, n_tok), bool))
    a = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(
        jnp.where(causal, scores, -jnp.inf), axis=-1), kv[..., dn:])
    return matmul_acc32(a.reshape(n, n_tok, nh * cfg.d_v), mp["wo"])


def _absorbed_query(mp: dict, q, cfg: KimiLinearConfig):
    """``q'_i = [q^n_i W^K_i^T ; q^r_i ; 0]`` in the cache row's lanes:
    ``q [N, L, H, d_nope + d_rope]`` -> ``[N, L, H, d_cache]``."""
    wk = mp["wkv_b"].reshape(cfg.d_latent, cfg.n_heads, -1)[..., :cfg.d_nope]
    lat = jnp.einsum("nqhd,lhd->nqhl", q[..., :cfg.d_nope].astype(wk.dtype),
                     wk, preferred_element_type=jnp.float32)
    pad = cfg.d_cache - cfg.d_latent - cfg.d_rope
    return jnp.concatenate(
        [lat, q[..., cfg.d_nope:], jnp.zeros((*q.shape[:-1], pad), q.dtype)],
        axis=-1)


def _absorbed_out(mp: dict, o, cfg: KimiLinearConfig):
    """``concat_heads(o'_i W^V_i) W_o`` of ``o' [N, L, H, d_latent]``."""
    wv = mp["wkv_b"].reshape(cfg.d_latent, cfg.n_heads, -1)[..., cfg.d_nope:]
    a = jnp.einsum("nqhl,lhd->nqhd", o.astype(wv.dtype), wv,
                   preferred_element_type=jnp.float32)
    return matmul_acc32(a.reshape(*a.shape[:2], -1), mp["wo"])


def _span_attend(q, buf, tables, qpos, cfg: KimiLinearConfig, bs: int):
    """Absorbed attention of ``q [N, L, H, d_cache]`` (:func:`_absorbed_query`)
    over one latent layer's pool buffer in ``jax.numpy``
    (``models/serving.py::span_attention``, the one buffer as keys and
    values; it divides by the square root of the row's lanes, which the
    query makes good): ``o' [N, L, H, d_latent]``."""
    o = span_attention(q * (_score_scale(cfg) * math.sqrt(cfg.d_cache)), buf,
                       buf, tables, qpos, None, 1, bs)
    return o.reshape(*q.shape)[..., :cfg.d_latent]


def _cache_row(row, cfg: KimiLinearConfig):
    """``[c^ ; r]`` as the pool holds it: ``[..., 1, d_cache]``."""
    pad = cfg.d_cache - row.shape[-1]
    return jnp.pad(row, [(0, 0)] * (row.ndim - 1) + [(0, pad)])[..., None, :]


def _ffn(bp: dict, u, cfg: KimiLinearConfig, live=None):
    """The feed-forward part over normed ``u [N, L, d]``: the dense one, or
    the held routed experts' weighted sum plus the shared experts; and the
    rows each held expert got ``[experts_held]`` (zeros from a dense layer).
    ``live [N]`` (a decode step's slots that take part): the rows of the
    others are sent to an expert that is not held, so that a slot that sits
    the tick out hits no expert and reads no weight; without an absent
    expert to send them to (a build that holds them all) they are computed
    like any."""
    if "mlp" in bp:
        return gated_mlp(bp["mlp"], u), jnp.zeros((cfg.experts_held,),
                                                  jnp.int32)
    n, n_tok, d = u.shape
    u = u.reshape(n * n_tok, d)
    route = sigmoid_top_k(bp["moe"]["bias"], cfg.route_scale)
    if live is not None and cfg.experts_held < cfg.n_experts:
        absent = (cfg.expert_offset + cfg.experts_held) % cfg.n_experts
        chosen = route

        def route(scores, top_k):
            w, ids = chosen(scores, top_k)
            return w, jnp.where(jnp.repeat(live, n_tok)[:, None], ids, absent)

    routed, rows = dropless_experts(
        bp["moe"], u, cfg.top_k, route=route, experts=swiglu_experts,
        held=cfg.held)
    return (routed + gated_mlp(bp["shared"], u)).reshape(n, n_tok, d), rows


def _head_logits(head: dict, h, cfg: KimiLinearConfig):
    return matmul_acc32(rms_norm(head["norm_f"], h, cfg.rms_eps),
                        head["out"])


def _empty_state(cfg: KimiLinearConfig, n: int) -> tuple:
    f32 = jnp.float32
    tail = jnp.zeros((n, cfg.d_conv - 1, cfg.d_kda), f32)
    return (jnp.zeros((n, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim),
                      f32), tail, tail, tail)


def full_logits(params: dict, tokens, cfg: KimiLinearConfig):
    """Logits ``[B, T, V]`` of whole sequences ``tokens [B, T]`` from empty
    state: the stage's forward (no cache, every token at once)."""
    h = embedding_lookup(params["embed"]["tok"], tokens).astype(jnp.float32)
    for bp in params["blocks"]:
        u = rms_norm(bp["norm1"], h, cfg.rms_eps)
        if "mla" in bp:
            h = h + _latent_attention(bp["mla"], u, cfg)
        else:
            h = h + _kda_mixer(bp["kda"], u,
                               _empty_state(cfg, tokens.shape[0]), cfg)[0]
        h = h + _ffn(bp, rms_norm(bp["norm2"], h, cfg.rms_eps), cfg)[0]
    return _head_logits(params["head"], h, cfg)


# -- serving: the two paged programs ------------------------------------------


def _kda_chunk_fwd(params, kc, state, tokens, p0, table, slot,
                   cfg: KimiLinearConfig, bs: int):
    """One request's prompt positions ``[p0, p0 + c)`` through every layer:
    a latent layer scatters its rows into the slot's blocks and attends
    absorbed over the slot's live positions, a KDA layer carries the slot's
    state from the previous chunk (zeros when ``p0 == 0``). Returns the
    last position's logits ``[V]``."""
    embed, blocks, head = merged_stage_trees(params)
    c = tokens.shape[1]
    h = embedding_lookup(embed["tok"], tokens.astype(jnp.int32)).astype(
        jnp.float32)
    idx = (p0 + jnp.arange(c))[None]                             # [1, c]
    phys, off = table[idx[0] // bs], idx[0] % bs
    state = list(state)
    ai = ki = 0
    for bp in blocks:
        u = rms_norm(bp["norm1"], h, cfg.rms_eps)
        if "mla" in bp:
            q, row = _latent_qr(bp["mla"], u, cfg)
            kc = paged_scatter(kc, ai, phys, off, _cache_row(row[0], cfg))
            o = _span_attend(_absorbed_query(bp["mla"], q, cfg), kc[ai],
                             table[None], idx, cfg, bs)
            h = h + _absorbed_out(bp["mla"], o, cfg)
            ai += 1
        else:
            mine = jax.tree.map(
                lambda a: jnp.where(p0 == 0, 0.0, jax.lax.dynamic_slice_in_dim(
                    a, slot, 1, 0)), state[ki])
            out, mine = _kda_mixer(bp["kda"], u, mine, cfg)
            state[ki] = jax.tree.map(
                lambda a, new: jax.lax.dynamic_update_slice_in_dim(
                    a, new, slot, 0), state[ki], mine)
            h = h + out
            ki += 1
        h = h + _ffn(bp, rms_norm(bp["norm2"], h, cfg.rms_eps), cfg)[0]
    return kc, tuple(state), _head_logits(head, h[0, -1], cfg)


def _build_kda_prefill_chunk(cfg: KimiLinearConfig, bs: int):
    """``chunk(params, kc, vc, state, tokens [1, c], host) -> (kc, vc,
    state, token, key_data)``: the contract of
    ``models/jamba.py::_build_hybrid_prefill_chunk`` (same host array, same
    seats); ``vc`` is the pool's empty tuple and goes back as it came."""
    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def chunk_kda_prefill(params, kc, vc, state, tokens, host):
        *layers, newest = state
        (p0, table, slot, seat, key_data, temperature, top_k,
         top_p) = unpack_chunk(host)
        kc, layers, row = _kda_chunk_fwd(
            params, kc, tuple(layers), tokens, p0, table, slot, cfg, bs)
        tok, kd = sample_slot(row, key_data, temperature, top_k, top_p)
        newest = seat_newest(newest, slot, seat, tok, kd, key_data)
        return kc, vc, (*layers, newest), tok, kd

    return chunk_kda_prefill


def _kda_decode_fwd(params, kc, state, toks, pos, tables, live,
                    cfg: KimiLinearConfig, bs: int, kernel: str):
    """One token for every slot (the slots that sit out, ``live`` false,
    ride along at position 0 of an all-trash table, write into the trash
    block, are routed to no held expert, and their state comes back bit for
    bit). Returns logits ``[S, V]`` and, per layer, the rows each held
    expert got ``[n_layers, experts_held]``."""
    embed, blocks, head = merged_stage_trees(params)
    h = embedding_lookup(embed["tok"], toks[:, None]).astype(jnp.float32)
    qpos = pos[:, None]
    phys = jnp.take_along_axis(tables, qpos // bs, axis=1)[:, 0]
    off = pos % bs
    state = list(state)
    rows = []
    ai = ki = 0
    for bp in blocks:
        u = rms_norm(bp["norm1"], h, cfg.rms_eps)
        if "mla" in bp:
            q, row = _latent_qr(bp["mla"], u, cfg)
            kc = paged_scatter(kc, ai, phys, off, _cache_row(row[:, 0], cfg))
            q = _absorbed_query(bp["mla"], q, cfg)           # [S, 1, H, D]
            if kernel == "fused":
                o = paged_attention(
                    jnp.swapaxes(q, 1, 2), kc[ai], None, tables, qpos,
                    block_size=bs, v_lanes=cfg.d_latent,
                    scale=_score_scale(cfg))              # [S, H, 1, latent]
                o = jnp.swapaxes(o, 1, 2)
            else:
                o = _span_attend(q, kc[ai], tables, qpos, cfg, bs)
            h = h + _absorbed_out(bp["mla"], o, cfg)
            ai += 1
        else:
            out, state[ki] = _kda_mixer(bp["kda"], u, state[ki], cfg, live)
            h = h + out
            ki += 1
        y, r = _ffn(bp, rms_norm(bp["norm2"], h, cfg.rms_eps), cfg, live)
        rows.append(r)
        h = h + y
    return (kc, tuple(state), _head_logits(head, h[:, 0], cfg),
            jnp.stack(rows))


def _build_kda_decode_step(cfg: KimiLinearConfig, bs: int, kernel: str):
    """``step(params, kc, vc, state, host) -> (kc, vc, state, rows [S, 1 +
    3], next_key_data)``: the contract of
    ``models/jamba.py::_build_hybrid_decode_step``, and behind every slot's
    next token the run's :data:`EXPERT_COUNTERS` (``PagedServing.
    counters``): (layer, held expert) pairs that got a row, (token, expert)
    pairs that landed on a held expert, the most rows one expert got, over
    the LIVE slots' rows. ``vc`` is the pool's empty tuple."""
    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def step_kda_decode(params, kc, vc, state, host):
        *layers, newest = state
        toks, key_data = newest
        pos, tables, live, temps, top_ks, top_ps = unpack_decode(host)
        kc, layers, logits, expert_rows = _kda_decode_fwd(
            params, kc, tuple(layers), toks, pos, tables, live, cfg, bs,
            kernel)
        toks2, kd2 = sample_slots(logits, key_data, temps, top_ks, top_ps)
        counters = jnp.stack([(expert_rows > 0).sum(), expert_rows.sum(),
                              expert_rows.max()]).astype(jnp.int32)
        rows = jnp.concatenate([
            toks2[:, None],
            jnp.broadcast_to(counters, (toks2.shape[0], 3))], axis=1)
        return (kc, vc, (*layers, feed_newest(newest, live, toks2, kd2)),
                rows, kd2)

    return step_kda_decode
