"""Pipeline-parallel KV-cache decoding: inference that scales like training.

``make_pp_decoder(pipe, cfg, ...)`` returns ``decode(buf, prompt, key)``
running UNDER ``shard_map`` on the training mesh: each stage device keeps its
packed param row and a KV cache for ITS OWN blocks only (inference memory
shards with the model, like training), and the single-token hidden state
relays across stages over the same ``lax.ppermute`` stage ring the trainer
uses. One compiled program decodes ``n_new`` tokens; the data axis shards the
batch exactly as in training.

Why this exists: the single-device decoders (``make_cached_decoder``,
``decoder_from_pipeline``) gather the whole model onto one chip — fine until
the model only exists stage-sharded. This decoder never gathers: a model
that trains at S stages decodes at S stages, straight from the live packed
buffer. Parity with the single-device cached decoder is exact (same math,
same key stream; tests/test_pp_decode.py).

Schedule note: single-sequence-batch decoding through a pipeline has an
inherent S-tick latency per token (the hidden state must cross every stage);
each tick moves one [B, d] vector over ICI. Inactive stages' per-tick
compute is predicated out value-wise (``jnp.where``) — at one token per
tick the redundant FLOPs are negligible next to the HBM-resident weights.

Scope: dense blocks (no MoE), n_seq == n_model == n_expert == 1; the data
axis may be > 1 (prompt/batch shard over it). The reference has no inference
path at all (``/root/reference/simple_distributed.py:119-132`` is eval-only).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from simple_distributed_machine_learning_tpu.models.gpt import (
    GPTConfig,
    check_sampling_args,
    dense_block_prefill,
    dense_block_step,
    sample_from,
    validate_decode_build,
)
from simple_distributed_machine_learning_tpu.models.serving import (
    storage_dtype,
)
from simple_distributed_machine_learning_tpu.ops.layers import (
    embedding_lookup,
    layer_norm,
    linear,
)
from simple_distributed_machine_learning_tpu.ops.losses import log_softmax
from simple_distributed_machine_learning_tpu.parallel.mesh import (
    DATA_AXIS,
    EXPERT_AXIS,
    MODEL_AXIS,
    STAGE_AXIS,
)
from simple_distributed_machine_learning_tpu.parallel.compat import (
    pvary_to as _pvary_to,
    shard_map as _shard_map,
)
from simple_distributed_machine_learning_tpu.parallel.staging import (
    unpack_stage_params,
)


def make_pp_decoder(pipe, cfg: GPTConfig, prompt_len: int, n_new: int,
                    temperature: float = 0.0, top_k: int | None = None,
                    top_p: float | None = None, cache_dtype=None):
    """Build ``decode(buf, prompt, key) -> [B, prompt_len + n_new]`` tokens,
    stage-sharded end to end. ``buf`` is the pipeline's packed param buffer
    (the live training state); ``prompt``: [B, prompt_len] int tokens with
    ``B`` divisible by the mesh's data axis."""
    if pipe.n_seq != 1 or pipe.n_model != 1 or pipe.n_expert != 1:
        raise ValueError(
            "make_pp_decoder shards over stage (x data) only — rebuild "
            "without seq/model/expert axes for decoding")
    check_sampling_args(temperature, top_k, top_p, cfg.vocab)
    total = validate_decode_build(pipe.stages, cfg, prompt_len, n_new,
                                  "make_pp_decoder")

    S = pipe.n_stages
    metas = list(pipe.metas)
    H, d = cfg.n_heads, cfg.d_model
    dh = d // H
    # per-stage block counts come from the stage param trees ("blocks" key);
    # caches are padded to the deepest stage so every device runs one program
    n_blocks = [len(pipe.stages[s].params["blocks"]) for s in range(S)]
    L_max = max(n_blocks)
    has_embed = [("embed" in pipe.stages[s].params) for s in range(S)]
    has_head = [("head" in pipe.stages[s].params) for s in range(S)]
    if not (has_embed[0] and has_head[-1]):
        raise ValueError("stage 0 must own 'embed' and the last stage "
                         "'head' (the make_gpt_stages layout)")
    # the packed row is typed varying over stage AND the (size-1) model/
    # expert axes its sharding names — the anchors must match that type
    vary = (DATA_AXIS, STAGE_AXIS, MODEL_AXIS, EXPERT_AXIS)

    def _head_row(params, h_last):
        return log_softmax(linear(params["head"]["out"],
                                  layer_norm(params["head"]["ln_f"], h_last)))

    def _pick(row, ks):
        """ks: the per-token subkey (split uniformly on every device, so
        the stream matches make_cached_decoder's exactly); the sampling
        math itself is gpt.py's shared sample_from."""
        return sample_from(row, ks, temperature, top_k, top_p)

    fwd = [(i, (i + 1) % S) for i in range(S)]

    # cache_dtype: as make_cached_decoder (bf16 halves each stage's cache)
    cd = storage_dtype(cache_dtype)

    def per_device(row4d, prompt, key):
        row = row4d[0, 0, 0]
        stage = lax.axis_index(STAGE_AXIS)
        b = prompt.shape[0]
        kc = jnp.zeros((L_max, b, H, total, dh), cd)
        vc = jnp.zeros((L_max, b, H, total, dh), cd)
        kc = _pvary_to(kc, vary)
        vc = _pvary_to(vc, vary)

        # ---- prefill relay: S ticks; the wire carries the [b, T0, d]
        # hidden state plus one token slot (the last stage writes the first
        # sampled token there; the final ring hop lands it on stage 0)
        def prefill_branch(s):
            def br(wire, kc, vc, ks):
                params = unpack_stage_params(row, metas[s])
                if s == 0:
                    ids = prompt.astype(jnp.int32)
                    h = (embedding_lookup(params["embed"]["tok"], ids)
                         + params["embed"]["pos"][:prompt_len])
                else:
                    h = wire[:, :-1].reshape(b, prompt_len, d)
                for li in range(n_blocks[s]):
                    h, kc, vc = dense_block_prefill(params["blocks"][li],
                                                    h, li, kc, vc,
                                                    prompt_len, H)
                tok = jnp.zeros((b,), jnp.float32)
                if s == S - 1:
                    tok = _pick(_head_row(params, h[:, -1]), ks).astype(
                        jnp.float32)
                out = jnp.concatenate([h.reshape(b, prompt_len * d),
                                       tok[:, None]], axis=1)
                anchor = _pvary_to(jnp.float32(0.0) * (jnp.sum(wire)
                                                       + jnp.sum(row)), vary)
                return (_pvary_to(out, vary) + anchor,
                        jax.tree.map(lambda a: (_pvary_to(a, vary)
                                                + anchor.astype(a.dtype)),
                                     (kc, vc)))
            return br

        pre_branches = [prefill_branch(s) for s in range(S)]

        # key discipline = make_cached_decoder's: exactly ONE split per
        # sampled token, performed identically on every device (replicated
        # key stream). The prefill consumes one (the first token).
        key0 = _pvary_to(key, vary)
        if temperature > 0.0:
            key1, ks0 = jax.random.split(key0)
        else:
            key1, ks0 = key0, key0

        def pre_tick(carry, t):
            wire, kc, vc = carry
            out, (kc2, vc2) = lax.switch(stage, pre_branches, wire, kc, vc,
                                         ks0)
            active = stage == t
            wire = jnp.where(active, out, wire)
            kc = jnp.where(active, kc2, kc)
            vc = jnp.where(active, vc2, vc)
            wire = lax.ppermute(wire, STAGE_AXIS, fwd)
            return (wire, kc, vc), None

        wire0 = _pvary_to(jnp.zeros((b, prompt_len * d + 1), jnp.float32),
                          vary)
        (wire, kc, vc), _ = lax.scan(
            pre_tick, (wire0, kc, vc), jnp.arange(S))

        # ---- decode relay: for each position i the [b, d+1] wire makes S
        # ticks; stage 0 reads the token slot, the last stage writes the
        # next sampled token into it, and the wrap-around hop returns it
        def decode_branch(s):
            def br(wire, kc, vc, i, ks):
                params = unpack_stage_params(row, metas[s])
                if s == 0:
                    tok = wire[:, -1].astype(jnp.int32)
                    pos = lax.dynamic_slice_in_dim(params["embed"]["pos"],
                                                   i, 1, 0)
                    h = embedding_lookup(params["embed"]["tok"],
                                         tok[:, None]) + pos
                else:
                    h = wire[:, :-1].reshape(b, 1, d)
                for li in range(n_blocks[s]):
                    h, kc, vc = dense_block_step(params["blocks"][li], h,
                                                 li, kc, vc, i, total, H)
                tok_out = jnp.zeros((b,), jnp.float32)
                if s == S - 1:
                    tok_out = _pick(_head_row(params, h[:, 0]), ks).astype(
                        jnp.float32)
                out = jnp.concatenate([h.reshape(b, d), tok_out[:, None]],
                                      axis=1)
                anchor = _pvary_to(jnp.float32(0.0) * (jnp.sum(wire)
                                                       + jnp.sum(row)), vary)
                return (_pvary_to(out, vary) + anchor,
                        jax.tree.map(lambda a: (_pvary_to(a, vary)
                                                + anchor.astype(a.dtype)),
                                     (kc, vc)))
            return br

        dec_branches = [decode_branch(s) for s in range(S)]

        def outer(carry, i):
            wire, kc, vc, key = carry
            # one key split per generated token (the cached decoder's
            # stream); every device splits identically
            if temperature > 0.0:
                key, ks = jax.random.split(key)
            else:
                ks = key
            # the token being consumed at position i sits in stage 0's slot
            tok_in = lax.psum(
                jnp.where(stage == 0, wire[:, -1], jnp.zeros((b,))),
                STAGE_AXIS)

            def tick(dc, t):
                wire, kc, vc = dc
                out, (kc2, vc2) = lax.switch(stage, dec_branches, wire, kc,
                                             vc, i, ks)
                active = stage == t
                wire = jnp.where(active, out, wire)
                kc = jnp.where(active, kc2, kc)
                vc = jnp.where(active, vc2, vc)
                wire = lax.ppermute(wire, STAGE_AXIS, fwd)
                return (wire, kc, vc), None

            (wire, kc, vc), _ = lax.scan(tick, (wire, kc, vc),
                                         jnp.arange(S))
            return (wire, kc, vc, key), tok_in

        # seed the decode wire: only the token slot matters and the prefill
        # left the first sampled token on stage 0's slot
        dec_wire = jnp.concatenate(
            [jnp.zeros((b, d), jnp.float32), wire[:, -1:]], axis=1)
        (wire, _, _, _), toks = lax.scan(
            outer, (_pvary_to(dec_wire, vary), kc, vc, key1),
            prompt_len + jnp.arange(n_new - 1))
        last = lax.psum(
            jnp.where(stage == 0, wire[:, -1], jnp.zeros((b,))), STAGE_AXIS)
        out = jnp.concatenate(
            [prompt.astype(jnp.int32),
             jnp.moveaxis(toks, 0, 1).astype(jnp.int32),
             last[:, None].astype(jnp.int32)], axis=1)
        # replication proof for the (size-1, anchor-typed) model/expert
        # axes: psum over a size-1 axis is the identity value-wise and
        # types the output invariant for the out_spec
        return lax.psum(lax.psum(out, MODEL_AXIS), EXPERT_AXIS)

    fn = _shard_map(
        per_device,
        mesh=pipe.mesh,
        in_specs=(pipe.param_spec(), P(DATA_AXIS), P()),
        out_specs=P(DATA_AXIS),
    )

    @jax.jit
    def decode(buf, prompt, key):
        if prompt.shape[1] != prompt_len:
            raise ValueError(
                f"prompt length {prompt.shape[1]} != built {prompt_len}")
        return fn(buf, prompt, key)

    return decode
