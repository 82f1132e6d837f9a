"""The Kimi-Linear hybrid family (``models/kimi_linear.py``) at toy size on
the CPU: the stage and the serving engine against the plain reference
(``bench_cells/reference/kimi_linear.py``: float32, ``highest``, the delta
rule a ``lax.scan`` over tokens, latent attention EXPANDED, the experts a
masked sum over the experts held, no kernel, cache or batching), on seeded
random weights. Logits are compared, not tokens.

Tolerances, each with its reason:

- ``F32`` (float32 weights, float32 cache): program and reference compute
  the same float32 expressions and differ in the order of the sums (blocked
  matmuls, the kernel's sums over the key lanes, the ABSORBED products
  ``(q W^K^T) . c`` for ``q . (c W^K)`` and ``(p . c) W^V`` for ``p . (c
  W^V)``, the softmax over gathered blocks, the grouped expert products)
  through 5 layers; logits here are of order 1-10 and the observed gap is
  under 2e-5: 3e-4 absolute and relative. A bfloat16 pass anywhere, the
  recurrence's state among them, moves the logits by 1e-2 and fails this
  (``test_a_state_held_in_bfloat16_fails_the_tolerance``).
- ``BF16`` (bfloat16 weights, the published dtype): the program rounds every
  matmul's activations to bfloat16 where the reference keeps them float32
  over the same rounded weights, and a rounded score can flip which expert
  is a token's last chosen one (with the toy's matrices at five times the
  published scale one expert's part is worth 0.2 of a logit; all but one
  element in 2,328 lie within 0.25 and that one at 0.32): 0.5 absolute, and
  0.02 for the mean. An int8 operand moves the mean by 0.05 and more.
- Runs of the SAME compiled program on the same numbers are compared bit for
  bit.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_cells.reference import kimi_linear as reference

from simple_distributed_machine_learning_tpu.models import kimi_linear
from simple_distributed_machine_learning_tpu.models.kimi_linear import (
    EXPERT_COUNTERS,
    KimiLinearConfig,
    make_kimi_linear_stages,
)
from simple_distributed_machine_learning_tpu.models.serving import (
    SEAT_NONE,
    SEAT_SAMPLE,
)
from simple_distributed_machine_learning_tpu.serve import InferenceEngine
from simple_distributed_machine_learning_tpu.telemetry import tracing

# both mixers twice over, the dense part first, in an order no period gives
CFG = KimiLinearConfig(vocab=97, seq_len=48, d_model=64, n_layers=5,
                       attn_layers=(1, 4), n_heads=4, d_nope=16, d_rope=8,
                       d_v=16, d_latent=32, kda_heads=4, kda_head_dim=16,
                       d_conv=4, d_gate=16, n_dense=1, d_ff=96, n_experts=8,
                       top_k=3, experts_held=8, n_shared=1, d_expert=48)
F32 = dict(rtol=3e-4, atol=3e-4)
BF16 = dict(rtol=0.0, atol=0.5)
BS = 4


def _ref_kw(cfg):
    return dict(n_heads=cfg.n_heads, top_k=cfg.top_k, scale=cfg.route_scale,
                first_expert=cfg.expert_offset, eps=cfg.rms_eps)


def _stages(cfg=CFG, key=0):
    """The builder's stage with its matrices scaled from normal 0.02 to 0.1
    (at width 64 the published scale leaves every activation near zero, and
    a model that is all but linear would forgive a wrong state) and a
    selection bias that is not 0, so that it is seen to steer the choice."""
    stages, _, _ = make_kimi_linear_stages(jax.random.key(key), cfg)
    dt = jnp.dtype(cfg.param_dtype)

    def scaled(path, a):
        name = path[-1].key
        if name == "bias":
            return 0.2 * jax.random.normal(jax.random.key(7), a.shape)
        if a.ndim >= 2 and not name.startswith("conv_"):
            return (5 * a.astype(jnp.float32)).astype(dt)
        return a

    params = jax.tree_util.tree_map_with_path(scaled, stages[0].params)
    return [dataclasses.replace(stages[0], params=params)]


@pytest.fixture(scope="module")
def stages():
    return _stages()


def _ref_logits(params, seq, first, n_out, cfg=CFG, **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.full_logits(
            params, jnp.asarray(seq, jnp.int32), **_ref_kw(cfg),
            **kw))[first:first + n_out]


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab, n).astype(
        np.int32)


# -- the stage ----------------------------------------------------------------


@pytest.mark.parametrize("dtype,tol", [("float32", F32), ("bfloat16", BF16)])
def test_stage_full_sequence_logits_match_the_reference(dtype, tol):
    cfg = dataclasses.replace(CFG, param_dtype=dtype)
    stage, = _stages(cfg)
    tokens = jnp.asarray(np.stack([_prompt(1, 24), _prompt(2, 24)]))
    logp = stage.apply(stage.params, tokens, jax.random.key(0), True)
    assert logp.shape == (2, 24, CFG.vocab) and logp.dtype == jnp.float32
    for b in range(2):
        want = _ref_logits(stage.params, tokens[b], 0, 24, cfg)
        want = np.asarray(jax.nn.log_softmax(want))
        np.testing.assert_allclose(np.asarray(logp[b]), want, **tol)
        assert np.abs(np.asarray(logp[b]) - want).mean() < tol["atol"] / 25


def test_a_state_held_in_bfloat16_fails_the_tolerance(stages):
    """The nearest lower precision of what this family adds: the same
    forward with the recurrence's state rounded to bfloat16 after every
    token misses ``F32`` by far (and a sound one, above, does not)."""
    seq = _prompt(1, 24)
    want = _ref_logits(stages[0].params, seq, 0, 24)
    got = _ref_logits(stages[0].params, seq, 0, 24,
                      state_dtype=jnp.bfloat16)
    assert np.abs(got - want).max() > 10 * F32["atol"]


def test_more_than_one_stage_is_refused():
    with pytest.raises(ValueError, match="builds one stage"):
        make_kimi_linear_stages(jax.random.key(0), CFG, 2)


def test_layer_kinds_and_cache_layout_follow_the_config(stages):
    """Two latent layers hold blocks alone (ONE stream: no value buffer),
    three KDA layers state alone; the row is the latent and the shared key
    lanes in whole lane tiles, its leading ``d_latent`` lanes the values."""
    blocks = stages[0].params["blocks"]
    assert ["mla" in b for b in blocks] == [False, True, False, False, True]
    assert ["mlp" in b for b in blocks] == [True] + [False] * 4
    assert all(("moe" in b) == ("shared" in b) != ("mlp" in b)
               for b in blocks)
    sv = CFG.paged_serving(stages, 48, BS)
    assert (sv.kv_layers, sv.kv_heads, sv.head_dim, sv.value_lanes) == (
        2, 1, 128, 32)
    assert CFG.d_cache == 128 and dataclasses.replace(
        CFG, d_latent=512, d_rope=64).d_cache == 640
    *layers, newest = sv.state_shapes
    assert len(layers) == 3 and all(
        [tuple(sd.shape) for sd in layer] == [(4, 16, 16)] + [(3, 64)] * 3
        and {sd.dtype for sd in layer} == {jnp.dtype("float32")}
        for layer in layers)
    assert sv.counters == EXPERT_COUNTERS and sv.windows == ()
    eng = InferenceEngine(stages, CFG, n_slots=3, max_len=48, block_size=BS,
                          cache_dtype=jnp.bfloat16)
    pool = eng.pool
    assert pool.vc == () and len(pool.kc) == 2 and pool.value_lanes == 32
    assert pool.kc[0].shape == (3 * 12 + 1, BS, 128)
    assert pool.bytes_per_block == 2 * BS * 128 * 2       # ONE stream
    assert pool.state_bytes_per_slot == 3 * 4 * (4 * 16 * 16 + 3 * 3 * 64) + (
        4 + 8)
    assert pool.recurrent


@pytest.mark.parametrize("kw,match", [
    ({"attn_layers": (5,)}, "distinct layers"),
    ({"attn_layers": (1, 1)}, "distinct layers"),
    ({"experts_held": 6, "expert_offset": 4}, "held experts"),
    ({"top_k": 9}, "held experts"),
    ({"d_conv": 1}, "d_conv"),
    ({"n_dense": 6}, "n_dense"),
])
def test_config_refuses_shapes_it_cannot_run(kw, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **kw)


def test_absorbed_attention_is_expanded_attention():
    """``q'_i = [q^n_i W^K_i^T ; q^r_i]`` over the rows ``[c^ ; r]`` and
    ``o_i = (softmax . c^) W^V_i`` against keys and values made of every
    row: the same sums in another order, at the last position of a
    sequence."""
    mp = _stages()[0].params["blocks"][1]["mla"]
    u = jax.random.normal(jax.random.key(2), (2, 11, 64))
    with jax.default_matmul_precision("highest"):
        want = kimi_linear._latent_attention(mp, u, CFG)[:, -1]
        q, row = kimi_linear._latent_qr(mp, u, CFG)
        qa = kimi_linear._absorbed_query(mp, q[:, -1:], CFG)   # [2, 1, H, D]
        assert qa.shape == (2, 1, 4, 128)
        assert not np.asarray(qa[..., 40:]).any()              # the padding
        rows = kimi_linear._cache_row(row, CFG)[:, :, 0]       # [2, 11, D]
        scores = jnp.einsum("nqhd,nkd->nhqk", qa, rows) / np.sqrt(24)
        o = jnp.einsum("nhqk,nkl->nqhl", jax.nn.softmax(scores, -1),
                       rows[..., :32])
        got = kimi_linear._absorbed_out(mp, o, CFG)[:, 0]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# -- the engine, with the logits it sampled from taken out --------------------


@functools.cache
def _twins(kernel):
    """The two programs' forwards, jitted once for every :class:`Tap`."""
    chunk = jax.jit(lambda p, kc, st, toks, p0, table, slot:
                    kimi_linear._kda_chunk_fwd(p, kc, st, toks, p0, table,
                                               slot, CFG, BS))
    step = jax.jit(lambda p, kc, st, toks, pos, tables, live:
                   kimi_linear._kda_decode_fwd(p, kc, st, toks, pos, tables,
                                               live, CFG, BS, kernel))
    return chunk, step


class Tap:
    """An engine whose two programs are jitted twins of the real ones that
    also hand out the logits they chose from (greedy: ``argmax``) and the
    first KDA layer's state of every slot after each call
    (``tests/test_nemotron_h.py::Tap``)."""

    def __init__(self, stages, kernel="fused", **kw):
        kw = {"n_slots": 2, "max_len": 48, "block_size": BS,
              "prefill_chunk": 5, **kw}
        self.eng = InferenceEngine(stages, CFG, attn_kernel=kernel, **kw)
        self.rows = []          # (kind, {rid: slot}, logits)
        self.states = []        # (kind, the first KDA layer's four leaves)
        chunk, step = _twins(kernel)

        def chunk_prefill(p, kc, vc, st, toks, p0, table, slot, seat, kd,
                          *_):
            assert vc == ()
            *st, (newest, keys) = st
            kc, st, row = chunk(p, kc, tuple(st), toks, p0, table, slot)
            self.rows.append(("chunk", {self.eng.pool.occupant(int(slot)):
                                        int(slot)}, np.asarray(row)))
            self.states.append(("chunk", [np.asarray(a) for a in st[0]]))
            tok = jnp.argmax(row).astype(jnp.int32)
            if seat != SEAT_NONE:
                newest = newest.at[int(slot)].set(
                    tok if seat == SEAT_SAMPLE else int(seat))
            return kc, vc, (*st, (newest, keys)), tok, jnp.asarray(kd)

        def decode(p, kc, vc, st, _toks, pos, tables, live, kd, *_):
            assert vc == ()
            *st, (newest, keys) = st
            kc, st, rows, counts = step(p, kc, tuple(st), newest, pos,
                                        tables, live)
            self.rows.append(("decode", {self.eng.pool.occupant(int(s_)):
                                         int(s_) for s_ in
                                         np.flatnonzero(live)},
                              np.asarray(rows)))
            self.states.append(("decode", [np.asarray(a) for a in st[0]]))
            toks = jnp.argmax(rows, -1).astype(jnp.int32)
            out = jnp.concatenate(
                [toks[:, None], jnp.zeros((toks.shape[0], 3), jnp.int32)], 1)
            return (kc, vc, (*st, (jnp.where(live, toks, newest), keys)),
                    out, jnp.asarray(kd))

        self.eng._chunk_prefill, self.eng._decode = chunk_prefill, decode
        # the twins take the host arguments one by one
        self.eng._pack_chunk = self.eng._pack_decode = None

    def logits_of(self, handle):
        """The rows ``handle``'s tokens were chosen from, in order."""
        out = []
        for kind, slots, rows in self.rows:
            if kind == "chunk" and handle.rid in slots:
                last = rows
            elif kind == "decode" and handle.rid in slots:
                out.append(rows[slots[handle.rid]])
        return np.stack([last] + out)[:len(handle.tokens)]


def _step(tap, handles):
    tap.eng.step()
    for h in handles:
        if h.slot is not None:
            h.slot_was = h.slot


def _run(tap, handles):
    while tap.eng.busy:
        _step(tap, handles)
    return handles


@pytest.mark.parametrize("kernel,chunk", [("dense", 5), ("fused", 5),
                                          ("fused", 3), ("fused", 8)])
def test_chunked_prefill_then_decode_matches_the_reference(stages, kernel,
                                                           chunk):
    """13 prompt tokens in chunks of 5, 5 and a ragged 3 (or of 3, or of 8
    and 5: the matrix state and the three convolutions' tails cross the
    boundary at other tokens, once inside a convolution's four taps), then
    decode through pool and state, a second request alongside, and a third
    that joins mid-run in the slot the second leaves: every token's logits
    against the reference's one full forward over prompt and served
    tokens."""
    tap = Tap(stages, kernel, prefill_chunk=chunk)
    prompts = [_prompt(3, 13), _prompt(4, 6), _prompt(5, 9)]
    new = [9, 3, 4]
    handles = [tap.eng.submit(p, n) for p, n in zip(prompts[:2], new)]
    for _ in range(4):
        _step(tap, handles)
    handles.append(tap.eng.submit(prompts[2], new[2]))
    _run(tap, handles)
    for p, n, h in zip(prompts, new, handles):
        assert len(h.tokens) == n
        seq = np.concatenate([p, np.asarray(h.tokens[:-1], np.int32)])
        want = _ref_logits(stages[0].params, seq, len(p) - 1, n)
        np.testing.assert_allclose(tap.logits_of(h), want, **F32)
    assert handles[2].slot_was == handles[1].slot_was    # the slot was reused


def test_slot_mid_prefill_keeps_its_state_across_decode_ticks(stages):
    """While the long prompt is between chunks, the other slot decodes: a
    decode tick must hand the prefilling slot's matrix state and its three
    tails back bit for bit."""
    tap = Tap(stages)
    a = tap.eng.submit(_prompt(5, 4), 8)
    tap.eng.step()                       # the short prompt's one chunk
    b = tap.eng.submit(_prompt(6, 14), 3)
    checked = 0
    bits = lambda x: x.view(np.int32)  # noqa: E731
    while tap.eng.busy:
        before = len(tap.states)
        tap.eng.step()
        new = tap.states[before:]
        if ([k for k, _ in new] == ["chunk", "decode"] and b.slot is not None
                and a.slot is not None and b.prefill_pos is not None):
            (_, after_chunk), (_, after_decode) = new
            for x, y in zip(after_chunk, after_decode):
                assert np.array_equal(bits(x[b.slot]), bits(y[b.slot]))
                assert not np.array_equal(x[a.slot], y[a.slot])
            checked += 1
    assert checked >= 2


def test_released_slot_bound_again_gives_a_fresh_engines_logits(stages):
    """One slot: the second request finds the first one's state in it, and
    its first chunk (``p0 == 0``) must start from zeros."""
    first, second = _prompt(7, 9), _prompt(8, 11)
    used = Tap(stages, n_slots=1)
    _run(used, [used.eng.submit(first, 5)])
    assert all(np.abs(np.asarray(a)).max() > 0
               for a in used.eng.pool.state[0])
    used.rows.clear()
    h_used, = _run(used, [used.eng.submit(second, 5)])
    fresh = Tap(stages, n_slots=1)
    h_fresh, = _run(fresh, [fresh.eng.submit(second, 5)])
    assert h_used.tokens == h_fresh.tokens
    assert np.array_equal(used.logits_of(h_used), fresh.logits_of(h_fresh))


def _engine(stages, cfg=CFG, **kw):
    kw = dict(dict(n_slots=2, max_len=48, block_size=BS, prefill_chunk=5,
                   attn_kernel="fused"), **kw)
    return InferenceEngine(stages, cfg, **kw)


def test_preempt_then_resume_reproduces_the_tokens(stages):
    prompts = [_prompt(9, 7), _prompt(10, 9)]
    plain = _engine(stages)
    want = [plain.submit(p, 8) for p in prompts]
    plain.drain()
    eng = _engine(stages)
    got = [eng.submit(p, 8) for p in prompts]
    while len(got[0].tokens) < 4:
        eng.step()
    eng.preempt(got[0].rid)
    eng.drain()
    assert got[0].n_preempted == 1
    assert [h.tokens for h in got] == [h.tokens for h in want]


def test_the_real_programs_serve_what_the_twins_serve(stages):
    """The packed host array, the seats, the empty value tuple and the
    counter row of the real programs against the twins that take their
    arguments one by one."""
    prompts = [_prompt(3, 13), _prompt(4, 6)]
    tap = Tap(stages)
    want = _run(tap, [tap.eng.submit(p, 6) for p in prompts])
    eng = _engine(stages)
    got = [eng.submit(p, 6) for p in prompts]
    eng.drain()
    assert [h.tokens for h in got] == [h.tokens for h in want]
    assert eng.pool.vc == ()


# -- the tick's counters --------------------------------------------------------


HELD = dataclasses.replace(CFG, n_experts=16, experts_held=8, expert_offset=4)


def test_a_decode_tick_counts_its_live_rows_on_the_held_experts():
    """``PagedServing.counters``: the counts ride the tokens the engine
    reads a tick late; a slot that sits the tick out is routed to no held
    expert, so a tick of one live slot counts at most its own ``top_k``
    pairs a mixture layer."""
    stages = _stages(HELD)
    eng = _engine(stages, HELD)
    mark = len(tracing.current().spans())
    hs = [eng.submit(_prompt(20, 6), 9), eng.submit(_prompt(21, 7), 3)]
    eng.drain()
    ticks = [s for s in tracing.current().spans()[mark:]
             if s.name == "engine.tick"]
    n_e, k = HELD.n_layers - HELD.n_dense, HELD.top_k
    decoded = [t.attrs for t in ticks if t.attrs["decoding"]]
    assert decoded and all(set(EXPERT_COUNTERS) <= set(t.attrs)
                           for t in ticks)
    for a in decoded:
        assert a["expert_rows"] <= n_e * k * a["decoding"]
        assert a["experts_hit"] <= min(n_e * HELD.experts_held,
                                       a["expert_rows"])
        assert a["expert_rows_max"] <= a["decoding"]
        assert {"state_slots", "kv_blocks", "kv_positions", "ahead"} <= set(a)
    assert any(a["decoding"] == 1 for a in decoded)
    assert sum(a["expert_rows"] for a in decoded) > 0
    assert all(t.attrs["experts_hit"] == 0 for t in ticks
               if not t.attrs["decoding"])
    assert sum(t.attrs["emitted"] for t in ticks) == sum(
        len(h.tokens) for h in hs)


# -- the share ------------------------------------------------------------------


SHARED = dataclasses.replace(CFG, n_experts=32, top_k=5, experts_held=32)


@pytest.mark.parametrize("shares", [4, 16])
def test_the_shares_of_a_layers_experts_add_up_to_the_whole_layer(shares):
    """For 4 shares of 8 (16 of 2) of 32 experts: the routed parts that the
    shares give, added together, plus the shared expert counted ONCE, equal
    the uncut reference's whole mixture layer; and each share's program
    equals the reference given the same share."""
    stage, = _stages(SHARED, key=3)
    whole = jax.tree.map(lambda a: a.astype(jnp.float32),
                         {k: stage.params["blocks"][2][k]
                          for k in ("moe", "shared")})
    u = jax.random.normal(jax.random.key(5), (3, 7, 64))
    flat = u.reshape(21, 64)
    per = 32 // shares
    kw = dict(top_k=SHARED.top_k, scale=SHARED.route_scale, quant=None)
    with jax.default_matmul_precision("highest"):
        want = reference.ffn_part(whole, flat, first_expert=0, **kw)
        sp = whole["shared"]
        shared = reference._expert(flat, sp["gate"], sp["up"], sp["down"],
                                   None)
        parts, rows = [], []
        for share in range(shares):
            cfg = dataclasses.replace(SHARED, experts_held=per,
                                      expert_offset=per * share)
            cut = dict(whole, moe=dict(whole["moe"], **{
                k: whole["moe"][k][per * share:per * (share + 1)]
                for k in ("gate", "up", "down")}))
            got, r = kimi_linear._ffn(cut, u, cfg)
            ref = reference.ffn_part(cut, flat,
                                     first_expert=cfg.expert_offset, **kw)
            np.testing.assert_allclose(got.reshape(21, 64), ref, **F32)
            parts.append(np.asarray(got.reshape(21, 64)) - np.asarray(shared))
            rows.append(np.asarray(r))
    np.testing.assert_allclose(sum(parts) + np.asarray(shared), want,
                               rtol=1e-3, atol=1e-3)
    # every (token, expert) pair lands on exactly one share
    assert sum(int(r.sum()) for r in rows) == 21 * SHARED.top_k
    assert not np.allclose(parts[0], parts[1], atol=1e-3)


def test_a_share_serves_and_its_absent_experts_are_left_out(stages):
    """An engine over experts 2-5 of 8: served logits follow the reference
    given the same share, and differ from the whole model's."""
    cfg = dataclasses.replace(CFG, experts_held=4, expert_offset=2)
    whole = stages[0].params
    cut = dict(whole, blocks=[
        dict(b, moe=dict(b["moe"], **{k: b["moe"][k][2:6]
                                      for k in ("gate", "up", "down")}))
        if "moe" in b else b for b in whole["blocks"]])
    stage = dataclasses.replace(stages[0], params=cut)
    tokens = jnp.asarray(_prompt(1, 20))[None]
    got = np.asarray(kimi_linear.full_logits(cut, tokens, cfg))[0]
    np.testing.assert_allclose(got, _ref_logits(cut, tokens[0], 0, 20, cfg),
                               **F32)
    assert np.abs(got - _ref_logits(whole, tokens[0], 0, 20)).max() > 0.05
    eng = _engine([stage], cfg)
    mark = len(tracing.current().spans())
    h = eng.submit(np.asarray(tokens[0, :9]), 5)
    eng.drain()
    assert len(h.tokens) == 5
    decoded = [s.attrs for s in tracing.current().spans()[mark:]
               if s.name == "engine.tick" and s.attrs["decoding"]]
    # 4 mixture layers x ONE live slot x top 3 pairs, those on held ones
    assert all(0 < a["expert_rows"] <= 4 * 3 for a in decoded)
    assert all(a["experts_hit"] <= 4 * 4 for a in decoded)


# -- what is refused ----------------------------------------------------------


@pytest.mark.parametrize("kw,name", [
    ({"host_cache_blocks": 4}, "host_cache_blocks"),
    ({"draft_stages": "d", "draft_cfg": "c", "spec_k": 2}, "draft_stages"),
    ({"adapters": type("Store", (), {"n_rows": 3})()}, "adapters"),
    ({"mesh": "m"}, "mesh"),
    ({"lint": True}, "lint=True"),
    ({"cache_dtype": "int8"}, "quantized cache_dtype"),
])
def test_mechanisms_built_for_kv_blocks_alone_are_refused_by_name(
        stages, kw, name):
    with pytest.raises(ValueError) as e:
        InferenceEngine(stages, CFG, n_slots=2, max_len=48, **kw)
    assert name in str(e.value) and "recurrent state" in str(e.value)


def test_a_model_without_a_latent_layer_is_not_served():
    cfg = dataclasses.replace(CFG, attn_layers=())
    with pytest.raises(ValueError, match="no latent-attention layer"):
        InferenceEngine(_stages(cfg), cfg, n_slots=2, max_len=48)


def test_no_prefix_is_shared_over_a_matrix_state(stages):
    """Two requests with one prompt: the second recomputes it (a slot's
    state summarises its whole prefix), and the pool counts the match it
    declined."""
    eng = _engine(stages, block_size=4)
    p = _prompt(30, 12)
    a = eng.submit(p, 3)
    eng.drain()
    b = eng.submit(p, 3)
    eng.drain()
    assert a.tokens == b.tokens
    assert eng.pool.prefix_hit_blocks_total == 0
    assert eng.pool.prefix_declined_total >= 1
