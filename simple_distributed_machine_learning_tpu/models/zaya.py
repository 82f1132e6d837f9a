"""ZAYA1-style decoder: compressed convolutional attention in a latent an
eighth of the model's width, and a top-1 mixture whose router is an MLP
that carries its state from layer to layer.

The fifth block family of the model zoo (``models/gpt.py``,
``models/jamba.py``, ``models/sdar.py`` and ``models/nemotron_h.py`` are the
others), written from the published ``config.json`` of Zyphra's ZAYA1-8B
(https://huggingface.co/Zyphra/ZAYA1-8B, ``model_type`` ``zaya``) and the
family's published description (CCA: attention in a compressed latent with
convolutional mixing; a router MLP of hidden 256; residual scaling). A
layer is an attention part and an expert part, each ``h <- (s_r * h + b_r)
+ (s_o * part(rms(h)) + b_o)`` with four learned vectors a part. With ``H``
query heads over ``KV`` K/V heads of ``dh`` lanes (``H dh`` is half the
model's width as published, ``KV dh`` an eighth):

- attention part over ``u = rms(h)``: ``q~ = u W_q``, ``k~ = u W_k``;
  VALUE SHIFT: the first half of the K/V heads read the current token,
  ``u_t W_v1``, the second half the token before, ``u_{t-1} W_v2`` (zeros
  before position 0); two causal CONVOLUTIONS over the sequence of ``c =
  [q~ ; k~]``: depthwise, ``a_t = sum_j w0[j] c_{t-(k0-1)+j} + b0``, then
  per head (``H + KV`` groups of ``dh`` channels) ``g_t = sum_j
  a_{t-(k1-1)+j} A[j] + b1`` with ``A[j]`` a ``dh x dh`` matrix a group,
  zeros before position 0 for each; the q-k MEAN: ``q = g^q + (q~ +
  rep(k~)) / 2``, ``k = g^k + (mean(q~) + k~) / 2`` (``k~`` repeated over
  its ``H / KV`` query heads, ``q~`` averaged over them); each head to
  length ``sqrt(dh)``, ``k`` times its K/V head's learned temperature
  ``tau``; rotary positions over the FIRST ``rotary_fraction`` of each
  head's lanes (``ops/layers.py::rotary``); causal grouped-query softmax
  attention, scores over ``sqrt(dh)``, entirely in the latent; ``W_o``
  back to the model's width;
- expert part over ``u = rms(h)``: the router's state ``r_l = u W_down +
  b_down (+ gamma_l * r_{l-1}`` for ``l > 0``: the same token's state of
  the layer before, after its own sum); scores ``W_3 gelu(W_2 gelu(W_1
  rms(r_l) + b_1) + b_2)``; ``p = softmax`` in float32; the ONE expert
  ``argmax(p + bias)``, weight ``p`` itself (``ops/moe_experts.py::
  softmax_top_1``); SwiGLU experts through the dropless layer
  (``dropless_experts(scores=)``), no shared expert;
- a final RMS norm and the token embedding itself as the head.

Not built: the family's descriptions name a skip choice of the router
(mixture of depths); the config read here has no key for it. Window
layers (the 74B sibling's) are not in this model's ``layer_types``.

Precision as the other served families': matmul operands in the weights'
dtype with float32 accumulation; the residual stream, the norms, the
convolutions' sums, the router's softmax and the attention in float32.

Serving threads TWO kinds of per-sequence state for ONE attention layer:
the paged pool holds ``k^`` (after convolution, mean, norm and rotary) and
the shifted ``v``, ``KV dh`` lanes each a position and layer; and every
layer keeps per slot what the next token's convolutions and value shift
reach back to: the last ``k0 - 1`` rows of ``c``, the last ``k1 - 1`` rows
of ``a`` and ``u W_v2`` of the newest token, float32. A prefill chunk
takes the slot's state as the rows before its first (zeros for a fresh
slot), so the keys it writes to the pool do not depend on where the prompt
was cut. :meth:`ZayaConfig.paged_serving` hands ``serve/engine.py`` that
layout and the two programs (``jit_chunk_cca_prefill``,
``jit_step_cca_decode``); host inputs, sampling and seats are
``models/serving.py``'s. The chunk attends through ``models/serving.py::
span_attention`` (the walk ``models/cohere2.py``'s chunk makes, with no
window): over the slot's LIVE positions, a step of pool blocks at a time
with a running maximum and sum, operands in the pool's dtype. It holds no
table-wide score array (until PR 46 every layer gathered the whole table
and wrote ``heads x chunk x max_len`` float32 scores whatever the slot
held: a quarter of the decode-and-chunk tick at ``max_len`` 8,192,
``PERF.md`` section 6) and fetches no block past the chunk's last position.
The whole-sequence path (:func:`full_logits`) and the decode program's
``kernel="dense"`` keep ``grouped_attention``. The decode program also
counts what its expert layers did (``PagedServing.counters``). Training this
family is not built.
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
    sample_slot,
    sample_slots,
    seat_newest,
    span_attention,
    tied_logits,
    unpack_chunk,
    unpack_decode,
    validate_hybrid_build,
)
from simple_distributed_machine_learning_tpu.ops.layers import (
    embedding_lookup,
    matmul_acc32,
    rms_norm,
    rotary,
)
from simple_distributed_machine_learning_tpu.ops.losses import log_softmax
from simple_distributed_machine_learning_tpu.ops.moe_experts import (
    dropless_experts,
    softmax_top_1,
)
from simple_distributed_machine_learning_tpu.parallel.pipeline import Stage

#: what a decode run counts over its expert layers (``PagedServing.counters``)
EXPERT_COUNTERS = ("experts_hit", "expert_rows_max")


@dataclasses.dataclass(frozen=True)
class ZayaConfig:
    vocab: int = 256
    # the longest sequence a serving slot may hold: a budget, not a shape
    # (positions are rotary)
    seq_len: int = 64
    d_model: int = 64
    n_layers: int = 3
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    # taps of the depthwise and of the per-head convolution (cca_time0 / 1)
    conv0: int = 2
    conv1: int = 2
    # the share of a head's lanes that rotary positions turn
    rotary_fraction: float = 0.5
    rope_theta: float = 5e6
    n_experts: int = 4
    d_expert: int = 64
    d_router: int = 8
    rms_eps: float = 1e-5
    # what the weights are held and read in; bfloat16 as published
    param_dtype: str = "float32"

    # the serving engine's questions of any model config
    recurrent_state = True      # per-slot state beside the K/V pool
    n_tensor_parallel = 1       # no tensor-parallel build of this family

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads or self.n_kv_heads % 2:
            raise ValueError(
                f"n_kv_heads ({self.n_kv_heads}) must divide n_heads "
                f"({self.n_heads}) and be even: half the K/V heads read the "
                f"current token's value, half the previous token's")
        if self.conv0 < 2 or self.conv1 < 2:
            raise ValueError(
                f"conv0 ({self.conv0}) and conv1 ({self.conv1}) must be >= "
                f"2 taps: a slot's state is the rows they reach back to")
        lanes = self.rotary_fraction * self.head_dim
        if (lanes != self.rotated or lanes % 2
                or not 2 <= lanes <= self.head_dim):
            raise ValueError(
                f"rotary_fraction {self.rotary_fraction} of head_dim "
                f"{self.head_dim} must be an even number of lanes, got "
                f"{lanes:g}")

    @property
    def rotated(self) -> int:
        """The lanes of a head that rotary positions turn."""
        return int(self.rotary_fraction * self.head_dim)

    @property
    def d_query(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def d_kv(self) -> int:
        """The pool's row: every K/V head of one position, side by side."""
        return self.n_kv_heads * self.head_dim

    @property
    def d_conv(self) -> int:
        """What the convolutions run over: ``[q~ ; k~]``."""
        return self.d_query + self.d_kv

    def paged_serving(self, stages, max_len: int, block_size: int,
                      cache_dtype=None, mesh=None, kernel: str = "dense",
                      adapters: bool = False) -> PagedServing:
        """The engine's model interface (``models/serving.py::PagedServing``):
        the paged pool holds every layer's latent K/V rows, and every slot
        has per layer the convolutions' two tails and the shifted value's
        half, float32, and last its newest token and sampling key."""
        validate_hybrid_build(stages, self, max_len, block_size,
                              cache_dtype, mesh, adapters,
                              caller="ZayaConfig.paged_serving",
                              maker="make_zaya_stages")
        check_attn_kernel(kernel, "ZayaConfig.paged_serving")
        f32 = jnp.float32
        layer = (jax.ShapeDtypeStruct((self.conv0 - 1, self.d_conv), f32),
                 jax.ShapeDtypeStruct((self.conv1 - 1, self.d_conv), f32),
                 jax.ShapeDtypeStruct((self.d_kv // 2,), f32))
        return PagedServing(
            kv_layers=self.n_layers, kv_heads=self.n_kv_heads,
            head_dim=self.head_dim,
            state_shapes=(layer,) * self.n_layers + (NEWEST_PAIR,),
            chunk_prefill=memo_build(
                ("cca_chunk", self, block_size),
                lambda: _build_cca_prefill_chunk(self, block_size)),
            decode=memo_build(
                ("cca_decode", self, block_size, kernel),
                lambda: _build_cca_decode_step(self, block_size, kernel)),
            pack_chunk=pack_chunk_inputs, pack_decode=pack_decode_inputs,
            counters=EXPERT_COUNTERS)


# -- parameters ---------------------------------------------------------------


def _scaling(d: int, dt) -> dict:
    """A part's residual scaling at its identity: ``(1 * h + 0) + (1 * y +
    0)``."""
    return {"res_scale": jnp.ones((d,), dt), "res_bias": jnp.zeros((d,), dt),
            "out_scale": jnp.ones((d,), dt), "out_bias": jnp.zeros((d,), dt)}


def _layer_init(key, cfg: ZayaConfig) -> dict:
    """One layer's tree. Matrices normal(0, 0.02); the two convolutions at
    torch's default (uniform within ``1 / sqrt(fan in)``: the taps for the
    depthwise one, taps times ``head_dim`` for the per-head one); norm
    weights, ``tau``, ``gamma`` and the residual scalings' factors 1,
    biases 0 (the selection bias float32: it is added to float32
    probabilities)."""
    dt = jnp.dtype(cfg.param_dtype)
    d, dh, c = cfg.d_model, cfg.head_dim, cfg.d_conv
    groups = cfg.n_heads + cfg.n_kv_heads
    r, e, f = cfg.d_router, cfg.n_experts, cfg.d_expert
    mat = lambda k, s: (  # noqa: E731
        0.02 * jax.random.normal(k, s)).astype(dt)
    uni = lambda k, s, b: jax.random.uniform(  # noqa: E731
        k, s, minval=-b, maxval=b).astype(dt)
    ones = lambda m: jnp.ones((m,), dt)  # noqa: E731
    zeros = lambda m: jnp.zeros((m,), dt)  # noqa: E731
    (kq, kv, ko, k0, kb0, k1, kb1, kd, kr1, kr2, kr3, kg, ku,
     kw) = jax.random.split(key, 14)
    b0, b1 = 1 / math.sqrt(cfg.conv0), 1 / math.sqrt(cfg.conv1 * dh)
    return {
        "attn": {
            "norm": ones(d),
            "wqk": mat(kq, (d, c)), "wv": mat(kv, (d, cfg.d_kv)),
            "conv0_w": uni(k0, (cfg.conv0, c), b0),
            "conv0_b": uni(kb0, (c,), b0),
            "conv1_w": uni(k1, (cfg.conv1, groups, dh, dh), b1),
            "conv1_b": uni(kb1, (c,), b1),
            "tau": ones(cfg.n_kv_heads),
            "wo": mat(ko, (cfg.d_query, d)), **_scaling(d, dt)},
        "moe": {
            "norm": ones(d),
            "router": {
                "down": mat(kd, (d, r)), "down_b": zeros(r),
                "gamma": ones(r), "norm": ones(r),
                "w1": mat(kr1, (r, r)), "b1": zeros(r),
                "w2": mat(kr2, (r, r)), "b2": zeros(r),
                "w3": mat(kr3, (r, e)),
                "bias": jnp.zeros((e,), jnp.float32)},
            "gate": mat(kg, (e, d, f)), "up": mat(ku, (e, d, f)),
            "down": mat(kw, (e, f, d)), **_scaling(d, dt)},
    }


def make_zaya_stages(key: jax.Array, cfg: ZayaConfig = ZayaConfig(),
                     n_stages: int = 1):
    """The model as the repo's ``Stage`` list, as ``make_gpt_stages`` gives
    it: ``(stages, wire_dim, (seq_len, vocab))``. One stage: the head is
    the token embedding itself (``make_jamba_stages`` says why that is not
    split), and the family is served, not trained."""
    if n_stages != 1:
        raise ValueError(
            f"make_zaya_stages builds one stage, got n_stages={n_stages}: "
            f"the tied head (logits = E h with the embedding matrix itself) "
            f"is not split across pipeline stages")
    ke, *kb = jax.random.split(key, 1 + cfg.n_layers)
    dt = jnp.dtype(cfg.param_dtype)
    params = {
        "embed": {"tok": (0.02 * jax.random.normal(
            ke, (cfg.vocab, cfg.d_model))).astype(dt)},
        "blocks": [_layer_init(k, cfg) for k in kb],
        "head": {"norm_f": jnp.ones((cfg.d_model,), dt)},
    }

    def apply(params, x, key, deterministic):
        del key, deterministic          # no dropout in this family
        return log_softmax(full_logits(params, x.astype(jnp.int32), cfg))

    stage = Stage(apply=apply, params=params, in_shape=(cfg.seq_len,),
                  token_input=True)
    return [stage], cfg.seq_len * cfg.d_model, (cfg.seq_len, cfg.vocab)


# -- the layers ---------------------------------------------------------------


def _causal_taps(x, tail):
    """``x [N, L, C]`` behind the rows it reaches back to (``tail [N, k -
    1, C]``, a sequence's start: zeros): the window ``[N, k - 1 + L, C]``
    and the tail the next rows will need."""
    window = jnp.concatenate([tail, x], axis=1)
    return window, window[:, -tail.shape[1]:]


def _cca_mix(ap: dict, u, tails, positions, cfg: ZayaConfig):
    """The attention part's lines before the attention itself, over normed
    ``u [N, L, d]`` at ``positions [N, L]`` from the layer's state ``tails
    = (c [N, k0 - 1, C], a [N, k1 - 1, C], v2 [N, KV dh / 2])``: what a
    sequence's last rows left (zeros at its start). Returns ``(q^ [N, L,
    H, dh], k^ [N, L, KV, dh], v [N, L, KV, dh], tails)``, float32."""
    f32 = jnp.float32
    n, n_tok, _ = u.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    tail_c, tail_a, tail_v = tails
    c = matmul_acc32(u, ap["wqk"])                            # [N, L, C]
    v12 = matmul_acc32(u, ap["wv"])
    now, late = v12[..., :cfg.d_kv // 2], v12[..., cfg.d_kv // 2:]
    shifted = jnp.concatenate([tail_v[:, None], late[:, :-1]], axis=1)
    v = jnp.concatenate([now, shifted], axis=-1).reshape(n, n_tok, kv, dh)

    window, tail_c = _causal_taps(c, tail_c)
    w0 = ap["conv0_w"].astype(f32)
    a = sum(window[:, j:j + n_tok] * w0[j]
            for j in range(cfg.conv0)) + ap["conv0_b"].astype(f32)
    window, tail_a = _causal_taps(a, tail_a)
    w1 = ap["conv1_w"]                               # [k1, H + KV, dh, dh]
    # operands in the weights' dtype like every matmul here, then widened
    # again: the CPU backend has no bfloat16 dot with a batch axis, and the
    # products of rounded operands are exact in float32 on any backend
    window = window.astype(w1.dtype).astype(f32).reshape(n, -1, h + kv, dh)
    g = sum(jnp.einsum("nlgi,gio->nlgo", window[:, j:j + n_tok],
                       w1[j].astype(f32))
            for j in range(cfg.conv1)) + ap["conv1_b"].astype(f32).reshape(
                h + kv, dh)

    q_raw = c[..., :cfg.d_query].reshape(n, n_tok, kv, h // kv, dh)
    k_raw = c[..., cfg.d_query:].reshape(n, n_tok, kv, dh)
    q = g[:, :, :h].reshape(q_raw.shape) + (q_raw + k_raw[:, :, :, None]) / 2
    k = g[:, :, h:] + (q_raw.mean(axis=3) + k_raw) / 2
    # each head to length sqrt(dh): x / sqrt(mean(x^2))
    unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.mean(x * x, axis=-1, keepdims=True))
    q = unit(q).reshape(n, n_tok, h, dh)
    k = unit(k) * ap["tau"].astype(f32)[:, None]
    return (rotary(q, positions, cfg.rope_theta, cfg.rotated),
            rotary(k, positions, cfg.rope_theta, cfg.rotated), v,
            (tail_c, tail_a, late[:, -1]))


def _router_scores(rp: dict, u, carried, cfg: ZayaConfig):
    """The router over normed ``u [T, d]``: its state ``r [T, R]`` (with
    ``carried``, the same tokens' state of the layer before, where there is
    one) and the scores ``[T, E]`` its MLP makes of it, float32."""
    f32 = jnp.float32
    r = matmul_acc32(u, rp["down"]) + rp["down_b"].astype(f32)
    if carried is not None:
        r = r + rp["gamma"].astype(f32) * carried
    x = rms_norm(rp["norm"], r, cfg.rms_eps)
    for w, b in (("w1", "b1"), ("w2", "b2")):
        x = jax.nn.gelu(matmul_acc32(x, rp[w]) + rp[b].astype(f32),
                        approximate=False)
    return matmul_acc32(x, rp["w3"]), r


def _top1_experts(ep: dict, u, carried, cfg: ZayaConfig):
    """The expert part over normed ``u [N, L, d]``: ``(p_e E_e(u)`` of each
    token's one expert, the router's state to carry on ``[N L, R]``, the
    rows each expert got ``[E]``)."""
    n, n_tok, d = u.shape
    u = u.reshape(n * n_tok, d)
    scores, carried = _router_scores(ep["router"], u, carried, cfg)
    out, rows = dropless_experts(
        ep, u.astype(ep["gate"].dtype), 1,
        route=softmax_top_1(ep["router"]["bias"]), scores=scores)
    return out.reshape(n, n_tok, d), carried, rows


def _merge(part: dict, h, y):
    """A part's residual scaling: ``(s_r * h + b_r) + (s_o * y + b_o)``."""
    f32 = jnp.float32
    return (part["res_scale"].astype(f32) * h + part["res_bias"].astype(f32)
            ) + (part["out_scale"].astype(f32) * y
                 + part["out_bias"].astype(f32))


def _zero_tails(cfg: ZayaConfig, n: int) -> tuple:
    f32 = jnp.float32
    return (jnp.zeros((n, cfg.conv0 - 1, cfg.d_conv), f32),
            jnp.zeros((n, cfg.conv1 - 1, cfg.d_conv), f32),
            jnp.zeros((n, cfg.d_kv // 2), f32))


def full_logits(params: dict, tokens, cfg: ZayaConfig):
    """Logits ``[B, T, V]`` of whole sequences ``tokens [B, T]`` from empty
    state: the stage's forward (no cache, every token at once)."""
    bsz, n_tok = tokens.shape
    h = embedding_lookup(params["embed"]["tok"], tokens).astype(jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(n_tok), (bsz, n_tok))
    causal = jnp.tril(jnp.ones((n_tok, n_tok), bool))[None]
    carried = None
    for bp in params["blocks"]:
        ap, ep = bp["attn"], bp["moe"]
        q, k, v, _ = _cca_mix(ap, rms_norm(ap["norm"], h, cfg.rms_eps),
                              _zero_tails(cfg, bsz), positions, cfg)
        h = _merge(ap, h, matmul_acc32(
            grouped_attention(q, k, v, causal, cfg), ap["wo"]))
        y, carried, _ = _top1_experts(
            ep, rms_norm(ep["norm"], h, cfg.rms_eps), carried, cfg)
        h = _merge(ep, h, y)
    return tied_logits(params, h, cfg)


# -- serving: the two paged programs ------------------------------------------


def _cca_chunk_fwd(params, kc, vc, state, tokens, p0, table, slot,
                   cfg: ZayaConfig, bs: int):
    """One request's prompt positions ``[p0, p0 + c)`` through every layer,
    as ``models/jamba.py::_hybrid_chunk_fwd`` runs them, except that every
    layer has BOTH kinds of state: the convolutions and the value shift
    start from the slot's tails (zeros when ``p0 == 0``, so a slot never
    sees its last occupant's), the keys they give are scattered into the
    slot's blocks, and the chunk attends over the slot's LIVE positions, a
    step of blocks at a time (``models/serving.py::span_attention``): what
    the table holds past ``p0 + c`` is never fetched. Returns the last
    position's logits ``[V]``."""
    embed, blocks, head = merged_stage_trees(params)
    c = tokens.shape[1]
    h = embedding_lookup(embed["tok"], tokens.astype(jnp.int32)).astype(
        jnp.float32)
    idx = p0 + jnp.arange(c)
    phys, off = table[idx // bs], idx % bs
    state = list(state)
    carried = None
    for li, bp in enumerate(blocks):
        ap, ep = bp["attn"], bp["moe"]
        tails = tuple(jnp.where(p0 == 0, 0.0,
                                jax.lax.dynamic_slice_in_dim(t, slot, 1, 0))
                      for t in state[li])
        q, k, v, tails = _cca_mix(ap, rms_norm(ap["norm"], h, cfg.rms_eps),
                                  tails, idx[None], cfg)
        state[li] = tuple(jax.lax.dynamic_update_slice_in_dim(t, new, slot, 0)
                          for t, new in zip(state[li], tails))
        kc = paged_scatter(kc, li, phys, off, k[0])
        vc = paged_scatter(vc, li, phys, off, v[0])
        a = span_attention(q, kc[li], vc[li], table[None], idx[None], None,
                           cfg.n_kv_heads, bs)
        h = _merge(ap, h, matmul_acc32(a, ap["wo"]))
        y, carried, _ = _top1_experts(
            ep, rms_norm(ep["norm"], h, cfg.rms_eps), carried, cfg)
        h = _merge(ep, h, y)
    logits = tied_logits({"embed": embed, "head": head}, h[:, -1], cfg)
    return kc, vc, tuple(state), logits[0]


def _build_cca_prefill_chunk(cfg: ZayaConfig, bs: int):
    """``chunk(params, kc, vc, state, tokens [1, c], host) -> (kc, vc,
    state, token, key_data)``: the contract of
    ``models/jamba.py::_build_hybrid_prefill_chunk`` (same host array, same
    seats), over this family's layers."""
    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def chunk_cca_prefill(params, kc, vc, state, tokens, host):
        *layers, newest = state
        (p0, table, slot, seat, key_data, temperature, top_k,
         top_p) = unpack_chunk(host)
        kc, vc, layers, row = _cca_chunk_fwd(
            params, kc, vc, tuple(layers), tokens, p0, table, slot, cfg, bs)
        tok, kd = sample_slot(row, key_data, temperature, top_k, top_p)
        newest = seat_newest(newest, slot, seat, tok, kd, key_data)
        return kc, vc, (*layers, newest), tok, kd

    return chunk_cca_prefill


def _cca_decode_fwd(params, kc, vc, state, toks, pos, tables, live,
                    cfg: ZayaConfig, bs: int, kernel: str):
    """One token for every slot, as ``models/jamba.py::_hybrid_decode_fwd``
    runs it (the slots that sit out ride along at position 0 of an
    all-trash table; their tails come back unchanged). Returns logits ``[S,
    V]`` and, per layer, the rows each expert got ``[n_layers, E]`` (every
    slot's row counts: the run computes them all)."""
    embed, blocks, head = merged_stage_trees(params)
    h = embedding_lookup(embed["tok"], toks[:, None]).astype(jnp.float32)
    phys = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
    off = pos % bs
    span = tables.shape[1] * bs
    seen = (jnp.arange(span)[None, None, :] <= pos[:, None, None])
    state = list(state)
    rows = []
    carried = None
    for li, bp in enumerate(blocks):
        ap, ep = bp["attn"], bp["moe"]
        q, k, v, tails = _cca_mix(ap, rms_norm(ap["norm"], h, cfg.rms_eps),
                                  state[li], pos[:, None], cfg)
        state[li] = tuple(
            jnp.where(live.reshape(-1, *[1] * (t.ndim - 1)), new, t)
            for t, new in zip(state[li], tails))
        kc = paged_scatter(kc, li, phys, off, k[:, 0])
        vc = paged_scatter(vc, li, phys, off, v[:, 0])
        if kernel == "fused":
            a = paged_attend(kc, vc, li, jnp.swapaxes(q, 1, 2), tables,
                             pos[:, None], bs)               # [S, H, 1, dh]
            a = jnp.swapaxes(a, 1, 2).reshape(a.shape[0], 1, -1)
        else:
            # [S, KV, span, dh] -> [S, span, KV, dh]
            krow = jnp.swapaxes(
                paged_gather(kc, li, tables, cfg.n_kv_heads), 1, 2)
            vrow = jnp.swapaxes(
                paged_gather(vc, li, tables, cfg.n_kv_heads), 1, 2)
            a = grouped_attention(q, krow, vrow, seen, cfg)
        h = _merge(ap, h, matmul_acc32(a, ap["wo"]))
        y, carried, r = _top1_experts(
            ep, rms_norm(ep["norm"], h, cfg.rms_eps), carried, cfg)
        rows.append(r)
        h = _merge(ep, h, y)
    logits = tied_logits({"embed": embed, "head": head}, h[:, 0], cfg)
    return kc, vc, tuple(state), logits, jnp.stack(rows)


def _build_cca_decode_step(cfg: ZayaConfig, bs: int, kernel: str):
    """``step(params, kc, vc, state, host) -> (kc, vc, state, rows [S, 1 +
    2], next_key_data)``: the contract of
    ``models/jamba.py::_build_hybrid_decode_step``, and behind every slot's
    next token the run's :data:`EXPERT_COUNTERS` (``PagedServing.
    counters``): (layer, expert) pairs that got a row, and the most rows
    one expert got in any layer."""
    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def step_cca_decode(params, kc, vc, state, host):
        *layers, newest = state
        toks, key_data = newest
        pos, tables, live, temps, top_ks, top_ps = unpack_decode(host)
        kc, vc, layers, logits, expert_rows = _cca_decode_fwd(
            params, kc, vc, tuple(layers), toks, pos, tables, live, cfg, bs,
            kernel)
        toks2, kd2 = sample_slots(logits, key_data, temps, top_ks, top_ps)
        counters = jnp.stack([(expert_rows > 0).sum(),
                              expert_rows.max()]).astype(jnp.int32)
        rows = jnp.concatenate([
            toks2[:, None],
            jnp.broadcast_to(counters, (toks2.shape[0], 2))], axis=1)
        return (kc, vc, (*layers, feed_newest(newest, live, toks2, kd2)),
                rows, kd2)

    return step_cca_decode
