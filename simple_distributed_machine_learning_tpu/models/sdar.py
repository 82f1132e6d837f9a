"""SDAR-style sparse decoder that generates by diffusion over blocks.

The third block family of the model zoo (``models/gpt.py`` and
``models/jamba.py`` are the others), written from the published
``config.json`` of JetLM's SDAR mixture-of-experts models
(https://huggingface.co/JetLM/SDAR-30B-A3B-Chat, ``model_type`` ``sdar_moe``):

- layer: ``h = h + attn(rms(h))``, then ``h = h + experts(rms(h))``;
- attention: grouped-query (``n_kv_heads`` dividing ``n_heads``, the head
  size its own number and not ``d_model / n_heads``), no bias, an RMS norm
  over each query and each key head, then rotary positions over the whole
  head (``ops/layers.py::rotary``, rotate-half, ``rope_theta``), scores
  over ``sqrt(head_dim)``;
- the mask, with ``B = block_length``: position ``i`` sees ``j`` iff
  ``j // B <= i // B`` (causal between blocks, full inside one; ``B = 1``
  is the causal mask). It holds in prefill too;
- every layer's feed-forward part is the dropless expert layer of
  ``ops/moe_experts.py``: softmax over the experts in float32, the
  ``top_k`` largest renormalised, SwiGLU experts, no shared expert;
- a final RMS norm and an UNTIED head; the logit AT a position predicts
  that position's token (no shift).

Precision as the hybrid's: matmul operands in the weights' dtype with
float32 accumulation; the residual stream, the norms, the rotation, the
softmaxes and the router's probabilities in float32.

Generation (the family's ``low_confidence_static`` schedule): the prompt's
whole blocks are prefilled and yield no token; a remainder of ``len(prompt)
% B`` tokens opens the first generated block already fixed. A block starts
as ``B`` mask tokens. A DENOISING forward runs the block's current tokens
against the cache and its own ``B`` keys, takes at each still-masked
position the sampled token and its probability, and fixes the ``n`` masked
positions of highest probability (``n`` from the static schedule: ``B //
steps``, the remainder to the first steps). A fixed token never changes.
When none is masked a COMMITTING forward over the final tokens writes the
block's K/V rows for good and the tokens are emitted: a block costs ``steps
+ 1`` forwards. :meth:`SdarConfig.paged_serving` hands ``serve/engine.py``
the two programs (``jit_chunk_block_prefill``, ``jit_step_block_denoise``)
with ``PagedServing.block = B``: in the decode program every live slot is
in the phase its own state says. Training this family (the block-diffusion
loss) is not built.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from simple_distributed_machine_learning_tpu.models.serving import (
    NEWEST_PAIR,
    SEAT_NONE,
    PagedServing,
    bits,
    check_attn_kernel,
    grouped_attention,
    is_quantized_dtype,
    memo_build,
    merged_stage_trees,
    # tests/bench_cells/test_bench_cells_sdar.py patches this name here
    paged_attend as _paged_attend,
    paged_gather,
    paged_scatter,
    sample_dyn,
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
)
from simple_distributed_machine_learning_tpu.parallel.pipeline import Stage


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    vocab: int = 256
    # the longest sequence a serving slot may hold: a budget, not a shape
    # (positions are rotary)
    seq_len: int = 64
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    n_layers: int = 2
    n_experts: int = 8
    top_k: int = 2
    d_expert: int = 32
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    # generation by diffusion over blocks: the block, a request's default
    # number of denoising steps (1..block_length), the mask token
    block_length: int = 4
    denoising_steps: int = 4
    mask_id: int = 255
    # what the weights are held and read in; bfloat16 as published
    param_dtype: str = "float32"

    # the serving engine's questions of any model config
    recurrent_state = False     # the cache is K/V blocks alone
    n_tensor_parallel = 1       # no tensor-parallel build of this family

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError(
                f"n_kv_heads ({self.n_kv_heads}) must divide n_heads "
                f"({self.n_heads}) and head_dim ({self.head_dim}) be even")
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(
                f"top_k {self.top_k} outside [1, n_experts {self.n_experts}]")
        if not 1 <= self.denoising_steps <= self.block_length:
            raise ValueError(
                f"denoising_steps {self.denoising_steps} outside [1, "
                f"block_length {self.block_length}]")
        if not 0 <= self.mask_id < self.vocab:
            raise ValueError(
                f"mask_id {self.mask_id} outside [0, vocab {self.vocab})")

    def paged_serving(self, stages, max_len: int, block_size: int,
                      cache_dtype=None, mesh=None, kernel: str = "dense",
                      adapters: bool = False) -> PagedServing:
        """The engine's model interface (``models/serving.py::PagedServing``)
        with ``block = block_length``: per slot the block in progress (its
        tokens, the forward that fixed each, the forwards it has had) and,
        last, the newest pair (the last committed token and the sampling
        key)."""
        _validate_block_build(stages, self, max_len, block_size, cache_dtype,
                              mesh, adapters)
        check_attn_kernel(kernel, "SdarConfig.paged_serving")
        blk = self.block_length
        row = jax.ShapeDtypeStruct((blk,), jnp.int32)
        return PagedServing(
            kv_layers=self.n_layers, kv_heads=self.n_kv_heads,
            head_dim=self.head_dim,
            state_shapes=((row, row, jax.ShapeDtypeStruct((), jnp.int32)),
                          NEWEST_PAIR),
            chunk_prefill=memo_build(
                ("block_chunk", self, block_size),
                lambda: _build_block_prefill_chunk(self, block_size)),
            decode=memo_build(
                ("block_denoise", self, block_size, kernel),
                lambda: _build_block_denoise_step(self, block_size, kernel)),
            pack_chunk=pack_chunk_inputs, pack_decode=pack_decode_inputs,
            block=blk, block_forwards=denoise_forwards,
            unpack_rows=unpack_block_rows, counters=BLOCK_COUNTERS)


def denoise_schedule(block: int, steps: int) -> list[int]:
    """Positions the static schedule fixes at each denoising forward:
    ``block // steps``, the remainder to the first steps."""
    return [block // steps + (k < block % steps) for k in range(steps)]


def denoise_forwards(block: int, steps: int, masked: int) -> int:
    """Denoising forwards until ``masked`` positions are all fixed (a block
    that the prompt's remainder opens has fewer than ``block``)."""
    fixed = 0
    for k, n in enumerate(denoise_schedule(block, steps)):
        fixed += n
        if fixed >= masked:
            return k + 1
    raise ValueError(f"{masked} masked positions in a block of {block}")


# -- parameters ---------------------------------------------------------------


def _block_init(key, cfg: SdarConfig) -> dict:
    """One layer's tree: matrices normal(0, 0.02), norm weights 1."""
    dt = jnp.dtype(cfg.param_dtype)
    d, dh, f, e = cfg.d_model, cfg.head_dim, cfg.d_expert, cfg.n_experts
    kq, kk, kv, ko, kr, kg, ku, kd = jax.random.split(key, 8)
    mat = lambda k, s: (0.02 * jax.random.normal(k, s)).astype(dt)  # noqa: E731
    ones = lambda m: jnp.ones((m,), dt)  # noqa: E731
    return {
        "norm_in": ones(d), "norm_ff": ones(d),
        "attn": {"wq": mat(kq, (d, cfg.n_heads * dh)),
                 "wk": mat(kk, (d, cfg.n_kv_heads * dh)),
                 "wv": mat(kv, (d, cfg.n_kv_heads * dh)),
                 "wo": mat(ko, (cfg.n_heads * dh, d)),
                 "q_norm": ones(dh), "k_norm": ones(dh)},
        "moe": {"router": mat(kr, (d, e)), "gate": mat(kg, (e, d, f)),
                "up": mat(ku, (e, d, f)), "down": mat(kd, (e, f, d))},
    }


def make_sdar_stages(key: jax.Array, cfg: SdarConfig = SdarConfig(),
                     n_stages: int = 1):
    """The model as the repo's ``Stage`` list, as ``make_gpt_stages`` gives
    it: ``(stages, wire_dim, (seq_len, vocab))``. One stage: the family is
    served, not trained, and the serving programs run on one device."""
    if n_stages != 1:
        raise ValueError(
            f"make_sdar_stages builds one stage, got n_stages={n_stages}: "
            f"this family has no pipeline build (it is served from one "
            f"device and not trained)")
    ke, kh, *kb = jax.random.split(key, 2 + cfg.n_layers)
    dt = jnp.dtype(cfg.param_dtype)
    mat = lambda k, s: (0.02 * jax.random.normal(k, s)).astype(dt)  # noqa: E731
    params = {
        "embed": {"tok": mat(ke, (cfg.vocab, cfg.d_model))},
        "blocks": [_block_init(k, cfg) for k in kb],
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


def _qkv(ap: dict, u, positions, cfg: SdarConfig):
    """``q [N, L, H, dh]``, ``k`` / ``v [N, L, KV, dh]``, float32: each
    query and key head normed, then rotated to ``positions [N, L]``."""
    n, n_tok, _ = u.shape
    dh = cfg.head_dim
    q = matmul_acc32(u, ap["wq"]).reshape(n, n_tok, cfg.n_heads, dh)
    k = matmul_acc32(u, ap["wk"]).reshape(n, n_tok, cfg.n_kv_heads, dh)
    v = matmul_acc32(u, ap["wv"]).reshape(n, n_tok, cfg.n_kv_heads, dh)
    q = rotary(rms_norm(ap["q_norm"], q, cfg.rms_eps), positions,
               cfg.rope_theta)
    k = rotary(rms_norm(ap["k_norm"], k, cfg.rms_eps), positions,
               cfg.rope_theta)
    return q, k, v


def _experts(bp: dict, h, cfg: SdarConfig):
    """``h + experts(rms(h))`` over ``h [N, L, d]`` and the rows each
    expert got."""
    n, n_tok, d = h.shape
    u = rms_norm(bp["norm_ff"], h, cfg.rms_eps).reshape(n * n_tok, d)
    y, rows = dropless_experts(bp["moe"], u, cfg.top_k)
    return h + y.reshape(n, n_tok, d), rows


def _head_logits(head: dict, h, cfg: SdarConfig):
    return matmul_acc32(rms_norm(head["norm_f"], h, cfg.rms_eps),
                        head["out"])


def block_mask(q_pos, k_pos, block: int):
    """Where a query at ``q_pos [..., Lq]`` sees a key at ``k_pos [...,
    Lk]``: ``[..., Lq, Lk]`` bool."""
    return k_pos[..., None, :] // block <= q_pos[..., :, None] // block


def full_logits(params: dict, tokens, cfg: SdarConfig,
                block: int | None = None):
    """Logits ``[N, T, V]`` of whole sequences ``tokens [N, T]`` under the
    block mask (``block`` defaults to the configuration's): the stage's
    forward, no cache, every token at once."""
    f32 = jnp.float32
    blk = cfg.block_length if block is None else block
    n, n_tok = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(n_tok), (n, n_tok))
    seen = block_mask(pos[:1], pos[:1], blk)
    h = embedding_lookup(params["embed"]["tok"], tokens).astype(f32)
    for bp in params["blocks"]:
        q, k, v = _qkv(bp["attn"], rms_norm(bp["norm_in"], h, cfg.rms_eps),
                       pos, cfg)
        h = h + matmul_acc32(grouped_attention(q, k, v, seen, cfg),
                             bp["attn"]["wo"])
        h, _ = _experts(bp, h, cfg)
    return _head_logits(params["head"], h, cfg)


# -- serving: the two paged programs ------------------------------------------


def _validate_block_build(stages, cfg: SdarConfig, max_len: int,
                          block_size: int, cache_dtype, mesh,
                          adapters: bool) -> None:
    caller = "SdarConfig.paged_serving"
    for name, asked, reason in (
            ("mesh (tensor-parallel serving)", mesh is not None,
             "the experts and the block's state have no sharded placement"),
            ("adapters", adapters,
             "the LoRA bank rides GPT's wq / wv (models/lora.py)"),
            ("a quantized cache_dtype", is_quantized_dtype(cache_dtype),
             "a denoising forward rewrites its block's rows every step, "
             "and the rows-in-lanes kernel path takes no per-head scales: "
             "use float32 or bfloat16")):
        if asked:
            raise ValueError(
                f"{name} is not available with a model that generates by "
                f"diffusion over blocks: {reason}")
    if len(stages) != 1 or "embed" not in stages[0].params:
        raise ValueError(
            f"{caller} needs make_sdar_stages' one stage, got "
            f"{len(stages)} stages")
    p = stages[0].params
    if (p["embed"]["tok"].shape != (cfg.vocab, cfg.d_model)
            or len(p["blocks"]) != cfg.n_layers
            or p["blocks"][0]["moe"]["gate"].shape
            != (cfg.n_experts, cfg.d_model, cfg.d_expert)):
        raise ValueError(
            f"cfg (vocab={cfg.vocab}, d_model={cfg.d_model}, "
            f"n_layers={cfg.n_layers}, n_experts={cfg.n_experts}) does not "
            f"match the stage's build (embedding "
            f"{p['embed']['tok'].shape}, {len(p['blocks'])} layers, experts "
            f"{p['blocks'][0]['moe']['gate'].shape})")
    if not 2 <= max_len <= cfg.seq_len:
        raise ValueError(
            f"slot max_len={max_len} outside [2, seq_len={cfg.seq_len}]")
    blk = cfg.block_length
    if block_size < 1 or block_size % blk or max_len % blk:
        raise ValueError(
            f"{caller} needs block_size ({block_size}) and max_len "
            f"({max_len}) to be multiples of block_length {blk}: a block's "
            f"rows lie in one pool block")


# -- host inputs as one array (models/serving.py) ------------------------------

_DECODE_COLS = 6    # a slot's columns before its block table


def pack_decode_inputs(toks, pos, tables, live, steps, key_data, temps,
                       top_ks, top_ps) -> tuple[np.ndarray]:
    """``[S, 6 + NB]`` int32: a slot's block start, live flag, denoising
    steps, top-k, temperature and top-p bits, then its block table. The
    host's ``toks`` and ``key_data`` stay behind: the block and the key are
    the program's state."""
    del toks, key_data
    cols = [pos, live, steps, top_ks, bits(temps), bits(top_ps)]
    return (np.concatenate([np.stack(cols, axis=1).astype(np.int32),
                            np.asarray(tables, np.int32)], axis=1),)


def _unpack_decode(host):
    f32 = lambda c: jax.lax.bitcast_convert_type(host[:, c], jnp.float32)  # noqa: E731
    return (host[:, 0], host[:, _DECODE_COLS:], host[:, 1] != 0, host[:, 2],
            f32(4), host[:, 3], f32(5))


def pack_chunk_inputs(tokens, p0, table, slot, seat, key_data, temperature,
                      top_k, top_p) -> tuple[np.ndarray, np.ndarray]:
    """``(tokens [1, c], [4 + 1 + B + NB] int32)``: position, slot, two key
    words, then ``seat`` (how many tokens open the block, or ``SEAT_NONE``,
    and those tokens), then the block table. A chunk samples nothing: the
    sampling parameters stay behind."""
    del temperature, top_k, top_p
    head = [p0, slot, *bits(key_data, np.uint32)]
    return (np.asarray(tokens, np.int32),
            np.concatenate([np.asarray(head, np.int32),
                            np.asarray(seat, np.int32),
                            np.asarray(table, np.int32)]))


def _block_chunk_fwd(params, kc, vc, tokens, p0, table, cfg: SdarConfig,
                     bs: int):
    """One request's prompt positions ``[p0, p0 + c)`` (whole blocks)
    through every layer under the block mask: each layer scatters the
    chunk's K/V into the slot's blocks and attends over them as GPT's chunk
    does. No logits: a prefill yields no token."""
    f32 = jnp.float32
    embed, blocks, _ = merged_stage_trees(params)
    c = tokens.shape[1]
    h = embedding_lookup(embed["tok"], tokens.astype(jnp.int32)).astype(f32)
    idx = p0 + jnp.arange(c)
    phys, off = table[idx // bs], idx % bs
    span = table.shape[0] * bs
    seen = block_mask(idx, jnp.arange(span), cfg.block_length)[None]
    for li, bp in enumerate(blocks):
        q, k, v = _qkv(bp["attn"], rms_norm(bp["norm_in"], h, cfg.rms_eps),
                       idx[None], cfg)
        kc = paged_scatter(kc, li, phys, off, k[0])
        vc = paged_scatter(vc, li, phys, off, v[0])
        # [KV, span, dh] -> [1, span, KV, dh]
        krow = jnp.swapaxes(
            paged_gather(kc, li, table, cfg.n_kv_heads), 0, 1)[None]
        vrow = jnp.swapaxes(
            paged_gather(vc, li, table, cfg.n_kv_heads), 0, 1)[None]
        h = h + matmul_acc32(grouped_attention(q, krow, vrow, seen, cfg),
                             bp["attn"]["wo"])
        h, _ = _experts(bp, h, cfg)
    return kc, vc


def _build_block_prefill_chunk(cfg: SdarConfig, bs: int):
    """``chunk(params, kc, vc, state, tokens [1, c], host) -> (kc, vc,
    state, 0, key_data)`` with ``host = pack_chunk_inputs(...)[1]``; pool
    and state buffers are donated. ``seat`` (``PagedServing.block``): the
    prompt's last chunk seats the slot's first block, ``seat[0]`` tokens of
    ``seat[1:]`` fixed (forward 0) and the rest masked, and the request's
    key; a mid-prompt chunk (``SEAT_NONE``) leaves the slot's state alone.
    Retraces per chunk length, like GPT's."""
    blk = cfg.block_length

    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def chunk_block_prefill(params, kc, vc, state, tokens, host):
        (btok, border, bstep), (newest, keys) = state
        p0, slot = host[0], host[1]
        key_data = jax.lax.bitcast_convert_type(host[2:4], jnp.uint32)
        n_fixed, opening = host[4], host[5:5 + blk]
        kc, vc = _block_chunk_fwd(params, kc, vc, tokens, p0,
                                  host[5 + blk:], cfg, bs)
        seats = n_fixed != SEAT_NONE
        fixed = jnp.arange(blk) < n_fixed
        put = lambda buf, new: buf.at[slot].set(  # noqa: E731
            jnp.where(seats, new, buf[slot]))
        state = ((put(btok, jnp.where(fixed, opening, cfg.mask_id)),
                  put(border, jnp.where(fixed, 0, -1)),
                  put(bstep, 0)),
                 (newest, put(keys, key_data)))
        return kc, vc, state, jnp.int32(0), key_data

    return chunk_block_prefill


def _block_fwd(params, kc, vc, btok, pos, tables, cfg: SdarConfig, bs: int,
               kernel: str):
    """One forward of every slot's block: tokens ``btok [S, B]`` at
    positions ``pos + (0..B-1)``. Each layer writes the block's K/V rows to
    the slot's own pool blocks before it attends (a denoising forward's are
    overwritten by the next, a commit's stay), so the cache and the block
    are one stream that every query reads up to the block's last position.
    A slot that sits the tick out rides along at position 0 of an
    all-trash table. Returns logits ``[S, B, V]`` and, per layer, the rows
    each expert got ``[L, E]``."""
    f32 = jnp.float32
    embed, blocks, head = merged_stage_trees(params)
    blk = cfg.block_length
    h = embedding_lookup(embed["tok"], btok).astype(f32)       # [S, B, d]
    idx = pos[:, None] + jnp.arange(blk)                       # [S, B]
    phys = jnp.take_along_axis(tables, idx // bs, axis=1)
    off = idx % bs
    qpos = jnp.broadcast_to(pos[:, None] + blk - 1, idx.shape)
    span = tables.shape[1] * bs
    seen = jnp.arange(span)[None, None, :] <= qpos[:, :, None]
    rows = []
    for li, bp in enumerate(blocks):
        q, k, v = _qkv(bp["attn"], rms_norm(bp["norm_in"], h, cfg.rms_eps),
                       idx, cfg)
        kc = paged_scatter(kc, li, phys, off, k)
        vc = paged_scatter(vc, li, phys, off, v)
        if kernel == "fused":
            a = _paged_attend(kc, vc, li, jnp.swapaxes(q, 1, 2), tables,
                              qpos, bs)                    # [S, H, B, dh]
            a = jnp.swapaxes(a, 1, 2).reshape(a.shape[0], blk, -1)
        else:
            # [S, KV, span, dh] -> [S, span, KV, dh]
            krow = jnp.swapaxes(
                paged_gather(kc, li, tables, cfg.n_kv_heads), 1, 2)
            vrow = jnp.swapaxes(
                paged_gather(vc, li, tables, cfg.n_kv_heads), 1, 2)
            a = grouped_attention(q, krow, vrow, seen, cfg)
        h = h + matmul_acc32(a, bp["attn"]["wo"])
        h, r = _experts(bp, h, cfg)
        rows.append(r)
    return kc, vc, _head_logits(head, h, cfg), jnp.stack(rows)


def _sample_block(logits, key_data, temps, top_ks, top_ps):
    """At every position of every block the sampled token and the
    log-probability the model gives it: ``(tokens [S, B], logp [S, B],
    next keys [S, 2])``. Greedy rows take the largest and consume no
    randomness; when every slot is greedy the vocabulary-wide sorts of
    ``sample_dyn`` are skipped (as ``models/serving.py::sample_slots``
    does for the three other families; the sampled branch here folds each
    position into the key, which that one does not)."""
    blk = logits.shape[1]
    lse = jax.nn.logsumexp(logits, axis=-1)

    def greedy():
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), key_data

    def sampled():
        def slot(rows, kd, t, k, p):
            key = jax.random.wrap_key_data(kd)
            each = jax.vmap(lambda b: jax.random.key_data(
                jax.random.fold_in(key, b)))(jnp.arange(blk))
            toks, _ = jax.vmap(sample_dyn, (0, 0, None, None, None))(
                rows, each, t, k, p)
            nxt = jax.random.key_data(jax.random.split(key)[0])
            return toks, jnp.where(t > 0, nxt, kd)

        return jax.vmap(slot)(logits, key_data, temps, top_ks, top_ps)

    toks, keys = jax.lax.cond(jnp.any(temps > 0), sampled, greedy)
    picked = jnp.take_along_axis(logits, toks[..., None], axis=-1)[..., 0]
    return toks, picked - lse, keys


#: what a block tick counts of itself (``PagedServing.counters``): slots
#: that ran a block forward, slots whose block was committed, (layer,
#: expert) pairs that got a row, the most rows one expert got
BLOCK_COUNTERS = ("forwards", "commits", "experts_hit", "expert_rows_max")


def unpack_block_rows(rows: np.ndarray, block: int):
    """What the engine reads back of a block tick (``PagedServing.block``),
    its counters taken off: ``(tokens [S, B], order [S, B], committed
    [S])``. ``order``: the forward (1-based) that fixed each position, 0
    for the prompt's remainder."""
    return (rows[:, :block], rows[:, block:2 * block],
            rows[:, 2 * block] != 0)


def _build_block_denoise_step(cfg: SdarConfig, bs: int, kernel: str):
    """``step(params, kc, vc, state, host) -> (kc, vc, state, rows [S, 2 B +
    5], key_data)`` with ``host, = pack_decode_inputs(...)``; pool and state
    buffers are donated. One forward of every live slot's block, each in
    the phase its state says: with a position still masked it DENOISES
    (fixes the schedule's ``n`` masked positions of highest probability;
    its K/V rows are overwritten by the next forward); with none it
    COMMITS (its rows stay, ``rows`` carries the tokens and the flag, and
    the slot's state is a fresh block of masks for the position the host
    hands it next)."""
    blk = cfg.block_length

    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def step_block_denoise(params, kc, vc, state, host):
        (btok, border, bstep), (newest, keys) = state
        pos, tables, live, steps, temps, top_ks, top_ps = _unpack_decode(
            host)
        kc, vc, logits, expert_rows = _block_fwd(
            params, kc, vc, btok, pos, tables, cfg, bs, kernel)
        toks, logp, keys2 = _sample_block(logits, keys, temps, top_ks,
                                          top_ps)
        masked = border < 0
        commit = live & ~masked.any(-1)
        # the static schedule: block // steps a forward, the remainder to
        # the first forwards; never more than are masked
        steps = jnp.clip(steps, 1, blk)
        n = jnp.minimum(blk // steps + (bstep < blk % steps),
                        masked.sum(-1))
        conf = jnp.where(masked, logp, -jnp.inf)
        at = jnp.arange(blk)
        ahead = (conf[:, None, :] > conf[:, :, None]) | (
            (conf[:, None, :] == conf[:, :, None])
            & (at[None, None, :] < at[None, :, None]))
        fix = masked & (ahead.sum(-1) < n[:, None]) & live[:, None]
        btok = jnp.where(fix, toks, btok)
        border = jnp.where(fix, bstep[:, None] + 1, border)
        moved = live & ~commit
        counters = jnp.stack([
            live.sum(), commit.sum(), (expert_rows > 0).sum(),
            expert_rows.max()]).astype(jnp.int32)
        rows = jnp.concatenate([
            btok, border, commit[:, None].astype(jnp.int32),
            jnp.broadcast_to(counters, (btok.shape[0], 4))], axis=1)
        state = ((jnp.where(commit[:, None], cfg.mask_id, btok),
                  jnp.where(commit[:, None], -1, border),
                  jnp.where(commit, 0, bstep + moved)),
                 (jnp.where(commit, btok[:, -1], newest),
                  jnp.where(moved[:, None], keys2, keys)))
        return kc, vc, state, rows, state[1][1]

    return step_block_denoise
