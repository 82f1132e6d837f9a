"""Beam-search decoding on the KV-cache infrastructure.

``make_beam_decoder(stages, cfg, prompt_len, n_new, beam_size)`` returns
``decode(params, prompt, key) -> (tokens [B, total], scores [B])``: the
highest-cumulative-log-prob continuation among ``beam_size`` beams per
sequence, decoded with the same static-shape per-layer K/V caches as
:func:`~.gpt.make_cached_decoder` (one prefill, one token per step; beams
ride the batch axis as ``B*K`` rows, and each step's beam reordering gathers
the cache rows along it).

Scoring is the plain sum of token log-probs over the generated suffix (no
length normalization — all beams have the same fixed length here, so
normalization would not change the argmax). ``beam_size=1`` is exactly
greedy decoding (pinned in tests/test_beam.py).

EOS termination (``eos_id``): a beam that emits ``eos_id`` is *finished* —
its score freezes at the log-prob of its sequence up to and including EOS,
and its only continuation is EOS itself at log-prob 0, so it rides the
remaining (static-length) scan as an eos-padded row competing on its frozen
score. The returned tokens are therefore eos-padded after the first EOS and
the score is the finished prefix's, the standard fixed-shape beam-EOS
treatment.

The reference has no inference path at all
(``/root/reference/simple_distributed.py:119-132`` is eval-only); greedy /
sampled (top-k/top-p) / beam decoding are capability extensions completing
the standard decode suite.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from simple_distributed_machine_learning_tpu.models.gpt import (
    GPTConfig,
    dense_block_prefill,
    dense_block_step,
    head_logprobs,
    validate_decode_build,
)
from simple_distributed_machine_learning_tpu.models.serving import (
    merged_stage_trees,
    storage_dtype,
)
from simple_distributed_machine_learning_tpu.ops.layers import (
    embedding_lookup,
)


def make_beam_decoder(stages, cfg: GPTConfig, prompt_len: int, n_new: int,
                      beam_size: int = 4, cache_dtype=None,
                      eos_id: int | None = None):
    """Build the jitted beam decoder. Single-device dense builds only (the
    :func:`~.gpt.make_cached_decoder` restrictions; ``cache_dtype`` as there
    — bf16 halves the K*B beam-cache memory). ``eos_id``: beams finishing on
    this token freeze their score and eos-pad (module docstring)."""
    if cfg.n_seq > 1:
        raise ValueError(
            "beam decode is single-device; rebuild the stages with n_seq=1")
    if not 1 <= beam_size <= cfg.vocab:
        raise ValueError(
            f"beam_size={beam_size} out of range [1, vocab={cfg.vocab}]")
    if eos_id is not None and not 0 <= eos_id < cfg.vocab:
        raise ValueError(
            f"eos_id={eos_id} outside [0, vocab={cfg.vocab})")
    total = validate_decode_build(stages, cfg, prompt_len, n_new,
                                  "make_beam_decoder")
    K = beam_size
    H, d = cfg.n_heads, cfg.d_model
    dh = d // H
    V = cfg.vocab
    cd = storage_dtype(cache_dtype)

    @jax.jit
    def decode(params, prompt, key):
        del key                                  # beam search is deterministic
        embed, blocks, head = merged_stage_trees(params)
        b = prompt.shape[0]
        L = len(blocks)

        # ---- prefill at batch B (beams share the prompt prefix)
        kc = jnp.zeros((L, b, H, total, dh), cd)
        vc = jnp.zeros((L, b, H, total, dh), cd)
        ids = prompt.astype(jnp.int32)
        h = embedding_lookup(embed["tok"], ids) + embed["pos"][:prompt_len]
        for li, bp in enumerate(blocks):
            h, kc, vc = dense_block_prefill(bp, h, li, kc, vc,
                                            prompt_len, H)
        row = head_logprobs(head, h[:, -1])                     # [B, V]

        # ---- beam init: top-K first tokens; caches tile to B*K rows
        # (beam-major within each sequence: row index = b*K + k)
        s0, t0 = lax.top_k(row, K)                          # [B, K] each
        scores = s0
        toks = jnp.zeros((b, K, n_new), jnp.int32)
        toks = toks.at[:, :, 0].set(t0)
        kc = jnp.repeat(kc, K, axis=1)                      # [L, B*K, ...]
        vc = jnp.repeat(vc, K, axis=1)
        done = (t0 == eos_id) if eos_id is not None else jnp.zeros((b, K),
                                                                   bool)

        def step(carry, i):
            kc, vc, toks, scores, done = carry
            # last chosen token of every beam enters at position i-? — the
            # token written at step j sits at buffer col j and global
            # position prompt_len + j; at loop index i we consume col i-1
            tok_in = lax.dynamic_index_in_dim(toks, i - 1, 2,
                                              keepdims=False)  # [B, K]
            pos_i = prompt_len + i - 1          # its global position
            pos = lax.dynamic_slice_in_dim(embed["pos"], pos_i, 1, 0)
            h = (embedding_lookup(embed["tok"],
                                  tok_in.reshape(b * K)[:, None]) + pos)
            for li, bp in enumerate(blocks):
                h, kc, vc = dense_block_step(bp, h, li, kc, vc, pos_i,
                                             total, H)
            row = head_logprobs(head, h[:, 0]).reshape(b, K, V)
            if eos_id is not None:
                # finished beams: only continuation is EOS at log-prob 0 —
                # the beam rides the rest of the scan on its frozen score
                pad = jnp.full((V,), -jnp.inf).at[eos_id].set(0.0)
                row = jnp.where(done[:, :, None], pad[None, None, :], row)
            cand = scores[:, :, None] + row                 # [B, K, V]
            scores, flat = lax.top_k(cand.reshape(b, K * V), K)
            beam_idx = flat // V                            # [B, K]
            new_tok = flat % V
            # reorder every beam-indexed structure by its source beam
            def regather(x):                                # [L, B*K, ...]
                xr = x.reshape((L, b, K) + x.shape[2:])
                xr = jnp.take_along_axis(
                    xr, beam_idx[None, :, :, None, None, None], axis=2)
                return xr.reshape((L, b * K) + x.shape[2:])
            kc = regather(kc)
            vc = regather(vc)
            toks = jnp.take_along_axis(toks, beam_idx[:, :, None], axis=1)
            toks = lax.dynamic_update_index_in_dim(
                toks, new_tok, i, 2)
            if eos_id is not None:
                done = (jnp.take_along_axis(done, beam_idx, axis=1)
                        | (new_tok == eos_id))
            return (kc, vc, toks, scores, done), None

        if n_new > 1:
            (kc, vc, toks, scores, done), _ = lax.scan(
                step, (kc, vc, toks, scores, done), 1 + jnp.arange(n_new - 1))
        best = jnp.argmax(scores, axis=1)                   # [B]
        best_toks = jnp.take_along_axis(
            toks, best[:, None, None], axis=1)[:, 0]        # [B, n_new]
        out = jnp.concatenate([prompt.astype(jnp.int32), best_toks], axis=1)
        return out, jnp.take_along_axis(scores, best[:, None], axis=1)[:, 0]

    return decode
