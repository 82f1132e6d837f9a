"""Cohere2-style mixture decoder: window and full attention layers in one
period, a PARALLEL attention + expert block under one bias-free LayerNorm,
sigmoid top-k routed SwiGLU experts beside averaged shared ones.

The sixth block family of the model zoo (``models/gpt.py``,
``models/jamba.py``, ``models/sdar.py``, ``models/nemotron_h.py`` and
``models/zaya.py`` are the others), written from the published
``config.json`` of CohereLabs' command-a-plus-05-2026
(https://huggingface.co/CohereLabs/command-a-plus-05-2026, ``model_type``
``cohere2_moe``) and the family's published block (Command A / Cohere2). A
layer over the residual stream ``h [T, d]``:

- ONE norm: ``u = (h - mean(h)) / sqrt(var(h) + eps) * g``, no bias
  (``ops/layers.py::layer_norm`` without one);
- the parallel block: ``h <- h + Attn(u) + FFN(u)``: both parts read the
  same ``u``, neither sees the other's output;
- attention: ``q = u W_q`` (``H`` heads of ``dh``), ``k = u W_k``, ``v = u
  W_v`` (``KV`` heads), no bias, no q-k norm; query head ``i`` reads K/V
  head ``i // (H / KV)``; scores over ``sqrt(dh)``. Layer ``l`` is a WINDOW
  layer unless ``l % full_every == full_every - 1``: rotary positions over
  the whole head with NEIGHBOURING lanes paired (``rotary(...,
  interleaved=True)``), and query ``t`` attends keys ``t - window < j <=
  t``. The others are FULL layers: no position encoding at all, causal over
  every earlier key;
- routed experts: ``s = sigmoid(u W_r)`` in float32 over all ``n_experts``,
  the ``top_k`` largest chosen, ``w_e = s_e / sum of s over the chosen``
  (``ops/moe_experts.py::sigmoid_top_k`` with no selection bias and scale
  1), SwiGLU bodies through the dropless layer. The build HOLDS
  ``experts_held`` of them from ``expert_offset`` on (a chip's share of a
  layer that several chips split by experts): routing, the choice and the
  normaliser are over all of them, the sum over the chosen ones that are
  held;
- shared experts: ``n_shared`` SwiGLU experts of the same shape, their
  outputs AVERAGED and added to the routed sum unscaled. They are held as
  one ``d -> n_shared f -> d`` gated product times ``1 / n_shared`` (the
  same sum);
- after the last layer the same norm, then the token embedding itself as
  the head, times ``logit_scale``. ``vocab`` is the rows of the embedding
  this build holds (a chip's slice of the vocabulary): token ids, logits
  and sampling are over those rows.

Not built: the vision tower the family is described with
(``serve/engine.py`` takes token ids), scaled rotary frequencies (the
config has none), a dense leading layer (``first_k_dense_replace`` is 0).

Precision as the other served families': matmul operands in the weights'
dtype with float32 accumulation; the residual stream, the norm, the
router's sigmoid and the attention in float32.

Serving: the two programs read the tree :func:`serve_params` makes of the
stage's (``PagedServing.serve_params``; the engine applies it once, where it
takes its parameters). It differs in a WINDOW layer's ``attn`` alone: in
place of ``wq`` / ``wk`` it holds ``wq_halves`` / ``wk_halves``, the same
columns with every head's lanes in the order even lanes first, then odd
(``[0, 2, .., dh - 2, 1, 3, .., dh - 1]``; heads stay where they are). On
that order the neighbouring pair ``(2i, 2i + 1)`` is the pair ``(i, i + dh /
2)``, which ``rotary``'s default (rotate-half) form rotates by the same
angle: ``q`` and ``k`` leave their products in the lane order their rotation
reads. Why: ``rotary(..., interleaved=True)`` meets the pairs through a
``[.., dh / 2, 2]`` view of a head, and the chip's compiler answered that
view on the WEIGHT's side, reshaping the whole query matrix to ``[heads, dh
/ 2, 2, d]`` in every window layer of every program run. Scores do not move
(one permutation of both operands of a head's dot product); a window layer's
K rows in the pool are the published rows in the HELD lane order
(``serve/slots.py``, "Layer kinds"); V rows, ``wv``, ``wo``, the full layers
and everything after attention are the stage's own. Either path refuses the
other's tree by leaf name (:func:`_qkv`). In EVERY layer the programs also
keep the two products whole behind an ``optimization_barrier`` until they
are cut into heads: fused with that cut, a product came out heads-major over
a transposed copy of its matrix, the full layer's too. Together that was 3.3
ms of a 14.9 ms decode run and 3.65 of a 33.6 ms chunk run at hidden 4096
and 128 heads of 128 (``PERF.md`` section 6, PR 45);
``tests/test_chip_compile.py`` holds the compiled programs to "nothing but a
product reads either matrix". The whole-sequence path (:func:`full_logits`,
``Stage.apply``) reads the stage's tree and keeps the neighbouring-lane form.

The paged pool keeps the two layer kinds apart
(``PagedServing.windows``; ``serve/slots.py``, "Layer kinds"). A window
layer's buffer holds ``window + chunk`` positions a slot, its table is a
ring, and what lies behind a slot's window is handed back. The decode
(``jit_step_window_decode``) calls ``ops/paged_attention.py`` once a layer
with that layer's buffer, table and window. The chunk
(``jit_chunk_window_prefill``) writes its rows, then attends over the
slot's LIVE positions alone, a step of pool blocks at a time with a running
maximum and sum: no array of ``heads x chunk x max_len`` exists, and a block
that lies wholly behind a window is never gathered. That walk lives beside
the pool's other helpers, ``models/serving.py::span_attention`` (with
``entry``, a ring's ``block % NB``): the long-context family's chunk
(``models/zaya.py``) attends through it too, so it takes the K/V head count
and not this family's config, and both programs here trace what they traced
when it lay in this module (``tests/test_cohere2.py``). Host inputs,
sampling and seats are ``models/serving.py``'s too. The decode program also counts what its expert layers did
(``PagedServing.counters``), over the LIVE slots' rows: a slot that sits a
tick out is routed to no held expert (:func:`_ffn`), so it reads no
expert's weights and its stale token's routing is nobody's count. Training
this family is not built.
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
    entry,
    feed_newest,
    grouped_attention,
    is_quantized_dtype,
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
from simple_distributed_machine_learning_tpu.ops.layers import (
    embedding_lookup,
    gated_mlp,
    layer_norm,
    matmul_acc32,
    rotary,
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

@dataclasses.dataclass(frozen=True)
class Cohere2Config:
    # the rows of the tied embedding this build holds
    vocab: int = 97
    # the longest sequence a serving slot may hold: a budget, not a shape
    seq_len: int = 64
    d_model: int = 64
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 16
    # a window layer's query t attends keys t - window < j <= t
    window: int = 8
    # layer l is a full layer where l % full_every == full_every - 1
    full_every: int = 4
    rope_theta: float = 50000.0
    n_experts: int = 8
    top_k: int = 2
    # the routed experts this build holds: experts_held from expert_offset
    experts_held: int = 4
    expert_offset: int = 0
    n_shared: int = 2
    d_expert: int = 64
    ln_eps: float = 1e-5
    logit_scale: float = 1.0
    # what the weights are held and read in; bfloat16 as published
    param_dtype: str = "float32"

    # the serving engine's questions of any model config
    recurrent_state = False     # nothing beside the K/V pool but the newest
    n_tensor_parallel = 1       # no tensor-parallel build of this family

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError(
                f"n_kv_heads ({self.n_kv_heads}) must divide n_heads "
                f"({self.n_heads}) and head_dim ({self.head_dim}) be even")
        if not (0 <= self.expert_offset and 1 <= self.experts_held
                and self.expert_offset + self.experts_held <= self.n_experts
                and 1 <= self.top_k <= self.n_experts):
            raise ValueError(
                f"held experts [{self.expert_offset}, "
                f"{self.expert_offset + self.experts_held}) outside the "
                f"{self.n_experts} routed, or top_k {self.top_k} of them")
        if self.window < 1 or self.full_every < 1 or self.n_shared < 1:
            raise ValueError(
                f"window ({self.window}), full_every ({self.full_every}) and "
                f"n_shared ({self.n_shared}) must be >= 1")

    @property
    def held(self) -> tuple[int, int]:
        return self.expert_offset, self.experts_held

    @property
    def windows(self) -> tuple:
        """Each layer's kind (``PagedServing.windows``): its window, or
        ``None`` for a full layer."""
        return tuple(None if l % self.full_every == self.full_every - 1
                     else self.window for l in range(self.n_layers))

    @property
    def d_query(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def d_kv(self) -> int:
        """The pool's row: every K/V head of one position, side by side."""
        return self.n_kv_heads * self.head_dim

    def paged_serving(self, stages, max_len: int, block_size: int,
                      cache_dtype=None, mesh=None, kernel: str = "dense",
                      adapters: bool = False) -> PagedServing:
        """The engine's model interface (``models/serving.py::PagedServing``):
        the paged pool holds every layer's K/V rows, each layer of its kind
        (``windows``), and every slot its newest token and sampling key:
        no recurrent state."""
        _validate_build(stages, self, max_len, block_size, cache_dtype, mesh,
                        adapters)
        check_attn_kernel(kernel, "Cohere2Config.paged_serving")
        nb_full = math.ceil(max_len / block_size)
        return PagedServing(
            kv_layers=self.n_layers, kv_heads=self.n_kv_heads,
            head_dim=self.head_dim, state_shapes=(NEWEST_PAIR,),
            chunk_prefill=memo_build(
                ("window_chunk", self, block_size, nb_full),
                lambda: _build_window_prefill_chunk(self, block_size,
                                                    nb_full)),
            decode=memo_build(
                ("window_decode", self, block_size, nb_full, kernel),
                lambda: _build_window_decode_step(self, block_size, nb_full,
                                                  kernel)),
            pack_chunk=pack_chunk_inputs, pack_decode=pack_decode_inputs,
            counters=EXPERT_COUNTERS, windows=self.windows,
            serve_params=functools.partial(serve_params, cfg=self))


def _validate_build(stages, cfg: Cohere2Config, max_len: int,
                    block_size: int, cache_dtype, mesh, adapters) -> None:
    """What this family refuses of ``paged_serving``'s arguments, by name
    (the pool and the engine refuse the rest in the same words)."""
    for name, asked, reason in (
            ("mesh (tensor-parallel serving)", mesh is not None,
             "the groups' buffers and the experts have no sharded "
             "placement"),
            ("adapters", adapters,
             "the LoRA bank rides GPT's wq / wv (models/lora.py)"),
            ("a quantized cache_dtype", is_quantized_dtype(cache_dtype),
             "the window walk of ops/paged_attention.py has no scale "
             "planes: use float32 or bfloat16")):
        if asked:
            raise ValueError(
                f"{name} is not available with a model that has window "
                f"layers: {reason}")
    # the stage, its shapes and the slot's length: the checks every family
    # with one tied stage shares (none of its refusals can fire here)
    validate_hybrid_build(stages, cfg, max_len, block_size, None, None,
                          False, caller="Cohere2Config.paged_serving",
                          maker="make_cohere2_stages")


# -- parameters ---------------------------------------------------------------


def _layer_init(key, cfg: Cohere2Config) -> dict:
    """One layer's tree: matrices normal(0, 0.02), the norm 1. The routed
    experts are the HELD ones alone; the shared experts lie side by side
    (``gate`` / ``up [d, n_shared f]``, ``down [n_shared f, d]``: expert
    ``i`` is columns, and rows, ``[i f, (i + 1) f)``)."""
    dt = jnp.dtype(cfg.param_dtype)
    d, f, held = cfg.d_model, cfg.d_expert, cfg.experts_held
    mat = lambda k, s: (  # noqa: E731
        0.02 * jax.random.normal(k, s)).astype(dt)
    kq, kk, kv, ko, kr, kg, ku, kd, sg, su, sd = jax.random.split(key, 11)
    return {
        "norm": jnp.ones((d,), dt),
        "attn": {"wq": mat(kq, (d, cfg.d_query)),
                 "wk": mat(kk, (d, cfg.d_kv)), "wv": mat(kv, (d, cfg.d_kv)),
                 "wo": mat(ko, (cfg.d_query, d))},
        "moe": {"router": mat(kr, (d, cfg.n_experts)),
                "gate": mat(kg, (held, d, f)), "up": mat(ku, (held, d, f)),
                "down": mat(kd, (held, f, d))},
        "shared": {"gate": mat(sg, (d, cfg.n_shared * f)),
                   "up": mat(su, (d, cfg.n_shared * f)),
                   "down": mat(sd, (cfg.n_shared * f, d))},
    }


def make_cohere2_stages(key: jax.Array, cfg: Cohere2Config = Cohere2Config(),
                        n_stages: int = 1):
    """The model as the repo's ``Stage`` list, as ``make_gpt_stages`` gives
    it: ``(stages, wire_dim, (seq_len, vocab))``. One stage: the head is
    the token embedding itself (``make_jamba_stages`` says why that is not
    split), and the family is served, not trained."""
    if n_stages != 1:
        raise ValueError(
            f"make_cohere2_stages builds one stage, got n_stages="
            f"{n_stages}: the tied head (logits = E h with the embedding "
            f"matrix itself) is not split across pipeline stages")
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


def _norm(weight, h, cfg: Cohere2Config):
    return layer_norm({"scale": weight.astype(jnp.float32)}, h, cfg.ln_eps)


def _qkv(ap: dict, u, positions, window, cfg: Cohere2Config,
         held: bool = False):
    """``q [N, L, H, dh]``, ``k`` / ``v [N, L, KV, dh]``, float32, of
    normed ``u [N, L, d]`` at ``positions [N, L]``: rotary on ``q`` and
    ``k`` in a window layer, no position in them at all in a full one.
    ``held``: ``ap`` is a layer of the serving programs' tree
    (:func:`serve_params`): a window layer's ``q`` and ``k`` come out of
    ``wq_halves`` / ``wk_halves`` with every head's even lanes first, and
    the rotation's pairs are the head's two halves."""
    n, n_tok, _ = u.shape
    dh = cfg.head_dim
    halves = held and window is not None
    wq, wk = ("wq_halves", "wk_halves") if halves else ("wq", "wk")
    if wq not in ap or wk not in ap:
        raise ValueError(
            f"a {'window' if window is not None else 'full'} layer's attn "
            f"holds {sorted(ap)} where {wq!r} and {wk!r} are read: the "
            f"serving programs take the tree of cohere2.serve_params "
            f"(PagedServing.serve_params), full_logits the stage's own")
    q, k = matmul_acc32(u, ap[wq]), matmul_acc32(u, ap[wk])
    if held:
        # the two products WHOLE, before they are cut into heads: fused with
        # that cut, the chip's compiler lays a product's output out heads-
        # major and transposes the whole matrix to feed it, in every layer
        # of every run (0.41 ms for W_q, the full layer's too), then copies
        # the few rows back two operations later (PERF.md section 6, PR 45)
        q, k = jax.lax.optimization_barrier((q, k))
    q = q.reshape(n, n_tok, cfg.n_heads, dh)
    k = k.reshape(n, n_tok, cfg.n_kv_heads, dh)
    v = matmul_acc32(u, ap["wv"]).reshape(n, n_tok, cfg.n_kv_heads, dh)
    if window is not None:
        q = rotary(q, positions, cfg.rope_theta, interleaved=not held)
        k = rotary(k, positions, cfg.rope_theta, interleaved=not held)
    return q, k, v


def _ffn(bp: dict, u, cfg: Cohere2Config, live=None):
    """The expert part over normed ``u [N, L, d]``: the held routed experts'
    weighted sum plus the shared experts' average, and the rows each held
    expert got ``[experts_held]``. ``live [N]`` (a decode step's slots that
    take part): the rows of the others are sent to an expert that is not
    held, so that a slot that sits the tick out hits no expert and reads no
    weight (its output is thrown away); without an absent expert to send
    them to (a build that holds them all) they are computed like any."""
    n, n_tok, d = u.shape
    u = u.reshape(n * n_tok, d)
    route = sigmoid_top_k(jnp.zeros((cfg.n_experts,), jnp.float32), 1.0)
    if live is not None and cfg.experts_held < cfg.n_experts:
        absent = (cfg.expert_offset + cfg.experts_held) % cfg.n_experts
        chosen = route

        def route(scores, top_k):
            w, ids = chosen(scores, top_k)
            return w, jnp.where(jnp.repeat(live, n_tok)[:, None], ids, absent)

    routed, rows = dropless_experts(
        bp["moe"], u, cfg.top_k, route=route, experts=swiglu_experts,
        held=cfg.held)
    y = routed + gated_mlp(bp["shared"], u) / cfg.n_shared
    return y.reshape(n, n_tok, d), rows


def _head_logits(embed, head, h, cfg: Cohere2Config):
    """Final norm, then the held rows of the embedding as the head, times
    ``logit_scale``."""
    table = embed["tok"]
    hn = _norm(head["norm_f"], h, cfg)
    return cfg.logit_scale * jax.lax.dot_general(
        hn.astype(table.dtype), table, (((hn.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def full_logits(params: dict, tokens, cfg: Cohere2Config):
    """Logits ``[B, T, V]`` of whole sequences ``tokens [B, T]``: the
    stage's forward (no cache, every token at once, each layer's mask built
    from positions)."""
    bsz, n_tok = tokens.shape
    h = embedding_lookup(params["embed"]["tok"], tokens).astype(jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(n_tok), (bsz, n_tok))
    back = jnp.arange(n_tok)[:, None] - jnp.arange(n_tok)[None, :]
    for bp, window in zip(params["blocks"], cfg.windows):
        mask = (back >= 0) if window is None else (back >= 0) & (back < window)
        u = _norm(bp["norm"], h, cfg)
        q, k, v = _qkv(bp["attn"], u, positions, window, cfg)
        y, _ = _ffn(bp, u, cfg)
        h = h + matmul_acc32(grouped_attention(q, k, v, mask[None], cfg),
                             bp["attn"]["wo"]) + y
    return _head_logits(params["embed"], params["head"], h, cfg)


# -- serving: the two paged programs ------------------------------------------


@functools.partial(jax.jit, static_argnames="dh")
def _even_lanes_first(w, dh: int):
    """``w [d, heads * dh]`` with every head's columns in the order ``[0, 2,
    .., dh - 2, 1, 3, .., dh - 1]``: one transpose, no gather."""
    d = w.shape[0]
    return jnp.swapaxes(w.reshape(d, -1, dh // 2, 2), 2, 3).reshape(d, -1)


def serve_params(params: list, cfg: Cohere2Config) -> list:
    """The tree the two serving programs read (``PagedServing.
    serve_params``), of the stages' ``params``: the same leaves, the very
    arrays, but in a window layer's ``attn`` ``wq`` / ``wk`` give way to
    ``wq_halves`` / ``wk_halves`` (the module's docstring, "Serving")."""
    def layer(bp, window):
        if window is None:
            return bp
        ap = dict(bp["attn"])
        for name in ("wq", "wk"):
            ap[name + "_halves"] = _even_lanes_first(ap.pop(name),
                                                     dh=cfg.head_dim)
        return {**bp, "attn": ap}

    stage, = params                 # one stage: make_cohere2_stages
    return [{**stage, "blocks": [layer(bp, w) for bp, w in zip(
        stage["blocks"], cfg.windows)]}]


def _group_tables(tables, windows, nb_full: int):
    """The programs' side-by-side tables ``[..., nb_full + rings]`` as one
    a layer: the full group's, or the layer's window group's ring (the
    groups in ascending window order, as ``serve/slots.py`` lays them)."""
    rings = sorted({w for w in windows if w is not None})
    widths = tables.shape[-1] - nb_full
    if rings and widths % len(rings):
        raise ValueError(
            f"tables of {tables.shape[-1]} entries are not the full group's "
            f"{nb_full} and {len(rings)} rings")
    ring = widths // len(rings) if rings else 0
    out = []
    for w in windows:
        at = nb_full + rings.index(w) * ring if w is not None else 0
        out.append(tables[..., at:at + (nb_full if w is None else ring)])
    return out


def _window_chunk_fwd(params, kc, vc, tokens, p0, table,
                      cfg: Cohere2Config, bs: int, nb_full: int):
    """One request's prompt positions ``[p0, p0 + c)`` through every layer:
    each layer's K/V rows are scattered into the slot's blocks of its KIND
    (a window layer's through its ring), then the chunk attends over the
    slot's live positions in that layer (``models/serving.py::
    span_attention``). Returns the last position's logits ``[V]``."""
    embed, blocks, head = merged_stage_trees(params)
    c = tokens.shape[1]
    h = embedding_lookup(embed["tok"], tokens.astype(jnp.int32)).astype(
        jnp.float32)
    idx = (p0 + jnp.arange(c))[None]                             # [1, c]
    tables = _group_tables(table[None], cfg.windows, nb_full)
    for li, (bp, window) in enumerate(zip(blocks, cfg.windows)):
        u = _norm(bp["norm"], h, cfg)
        q, k, v = _qkv(bp["attn"], u, idx, window, cfg, held=True)
        phys, off = entry(tables[li], idx // bs, window)[0], idx[0] % bs
        kc = paged_scatter(kc, li, phys, off, k[0])
        vc = paged_scatter(vc, li, phys, off, v[0])
        a = span_attention(q, kc[li], vc[li], tables[li], idx, window,
                           cfg.n_kv_heads, bs)
        y, _ = _ffn(bp, u, cfg)
        h = h + matmul_acc32(a, bp["attn"]["wo"]) + y
    return kc, vc, _head_logits(embed, head, h[:, -1], cfg)[0]


def _build_window_prefill_chunk(cfg: Cohere2Config, bs: int, nb_full: int):
    """``chunk(params, kc, vc, state, tokens [1, c], host) -> (kc, vc,
    state, token, key_data)``: the contract of
    ``models/jamba.py::_build_hybrid_prefill_chunk`` (same host array, same
    seats), its table every group's side by side."""
    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def chunk_window_prefill(params, kc, vc, state, tokens, host):
        newest, = state
        (p0, table, slot, seat, key_data, temperature, top_k,
         top_p) = unpack_chunk(host)
        kc, vc, row = _window_chunk_fwd(params, kc, vc, tokens, p0, table,
                                        cfg, bs, nb_full)
        tok, kd = sample_slot(row, key_data, temperature, top_k, top_p)
        newest = seat_newest(newest, slot, seat, tok, kd, key_data)
        return kc, vc, (newest,), tok, kd

    return chunk_window_prefill


def _window_decode_fwd(params, kc, vc, toks, pos, tables, live,
                       cfg: Cohere2Config, bs: int, nb_full: int,
                       kernel: str):
    """One token for every slot (the slots that sit out, ``live`` false,
    ride along at position 0 of all-trash tables, write into every buffer's
    trash block and are routed to no held expert). Returns logits ``[S,
    V]`` and, per layer, the rows each held expert got ``[n_layers,
    experts_held]``."""
    embed, blocks, head = merged_stage_trees(params)
    h = embedding_lookup(embed["tok"], toks[:, None]).astype(jnp.float32)
    qpos = pos[:, None]
    off = pos % bs
    tables = _group_tables(tables, cfg.windows, nb_full)
    rows = []
    for li, (bp, window) in enumerate(zip(blocks, cfg.windows)):
        u = _norm(bp["norm"], h, cfg)
        q, k, v = _qkv(bp["attn"], u, qpos, window, cfg, held=True)
        phys = entry(tables[li], qpos // bs, window)[:, 0]
        kc = paged_scatter(kc, li, phys, off, k[:, 0])
        vc = paged_scatter(vc, li, phys, off, v[:, 0])
        if kernel == "fused":
            a = paged_attention(jnp.swapaxes(q, 1, 2), kc[li], vc[li],
                                tables[li], qpos, block_size=bs,
                                window=window)                # [S, H, 1, dh]
            a = jnp.swapaxes(a, 1, 2).reshape(a.shape[0], 1, -1)
        else:
            a = span_attention(q, kc[li], vc[li], tables[li], qpos, window,
                               cfg.n_kv_heads, bs)
        y, r = _ffn(bp, u, cfg, live)
        rows.append(r)
        h = h + matmul_acc32(a, bp["attn"]["wo"]) + y
    return kc, vc, _head_logits(embed, head, h[:, 0], cfg), jnp.stack(rows)


def _build_window_decode_step(cfg: Cohere2Config, bs: int, nb_full: int,
                              kernel: str):
    """``step(params, kc, vc, state, host) -> (kc, vc, state, rows [S, 1 +
    3], next_key_data)``: the contract of
    ``models/jamba.py::_build_hybrid_decode_step``, and behind every slot's
    next token the run's :data:`EXPERT_COUNTERS` (``PagedServing.
    counters``): (layer, held expert) pairs that got a row, (token, expert)
    pairs that landed on a held expert, the most rows one expert got, over
    the LIVE slots' rows."""
    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def step_window_decode(params, kc, vc, state, host):
        newest, = state
        toks, key_data = newest
        pos, tables, live, temps, top_ks, top_ps = unpack_decode(host)
        kc, vc, logits, expert_rows = _window_decode_fwd(
            params, kc, vc, toks, pos, tables, live, cfg, bs, nb_full, kernel)
        toks2, kd2 = sample_slots(logits, key_data, temps, top_ks, top_ps)
        counters = jnp.stack([(expert_rows > 0).sum(), expert_rows.sum(),
                              expert_rows.max()]).astype(jnp.int32)
        rows = jnp.concatenate([
            toks2[:, None],
            jnp.broadcast_to(counters, (toks2.shape[0], 3))], axis=1)
        return (kc, vc, (feed_newest(newest, live, toks2, kd2),), rows, kd2)

    return step_window_decode
