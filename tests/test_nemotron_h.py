"""The Nemotron-H hybrid family (``models/nemotron_h.py``) at toy size on the
CPU: the stage and the serving engine against the plain reference
(``bench_cells/reference/nemotron_h.py``: float32, ``highest``, the
recurrence a ``lax.scan``, the experts a masked sum over the experts held,
no kernel, cache or batching), on seeded random weights. Logits are
compared, not tokens.

Tolerances, each with its reason:

- ``F32`` (float32 weights, float32 cache): program and reference compute
  the same float32 expressions and differ in the order of the sums (blocked
  matmuls, the softmax over gathered blocks, the kernel's state sum, the
  grouped expert products) through 6 layers; logits here are of order 1-10
  and the observed gap is under 5e-5: 3e-4 absolute and relative. A bfloat16
  pass anywhere moves the logits by 1e-2 and fails this.
- ``BF16`` (bfloat16 weights, the published dtype): the program rounds every
  matmul's activations to bfloat16 where the reference keeps them float32
  over the same rounded weights, and a rounded score can flip which expert
  is a token's last chosen one (with the toy's matrices at five times the
  published scale one expert's part is worth 0.2 of a logit); all but one
  element in 2,328 lie within 0.05 and that one at 0.18: 0.25 absolute, and
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

from bench_cells.reference import nemotron_h as reference

from simple_distributed_machine_learning_tpu.models import nemotron_h
from simple_distributed_machine_learning_tpu.models.serving import (
    SEAT_NONE,
    SEAT_SAMPLE,
)
from simple_distributed_machine_learning_tpu.models.nemotron_h import (
    EXPERT_COUNTERS,
    NemotronHConfig,
    make_nemotron_h_stages,
)
from simple_distributed_machine_learning_tpu.serve import InferenceEngine
from simple_distributed_machine_learning_tpu.telemetry import tracing

# all three kinds, twice over, in an order no period describes
CFG = NemotronHConfig(vocab=97, seq_len=48, d_model=64, pattern="MEM*EE",
                      n_heads=4, n_kv_heads=2, head_dim=16, mamba_heads=8,
                      mamba_head_dim=32, n_groups=2, d_state=16, d_conv=4,
                      n_experts=8, top_k=3, experts_held=8, d_latent=32,
                      d_expert=48, d_shared=96, route_scale=2.5)
F32 = dict(rtol=3e-4, atol=3e-4)
BF16 = dict(rtol=0.0, atol=0.25)
BS = 4


def _ref_kw(cfg):
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                n_groups=cfg.n_groups, top_k=cfg.top_k,
                scale=cfg.route_scale, first_expert=cfg.expert_offset,
                eps=cfg.rms_eps)


def _stages(cfg=CFG, key=0):
    """The builder's stage with its matrices scaled from normal 0.02 to 0.1
    (at width 64 the published scale leaves every activation near zero, and
    a model that is all but linear would forgive a wrong state) and a
    selection bias that is not 0, so that it is seen to steer the choice."""
    stages, _, _ = make_nemotron_h_stages(jax.random.key(key), cfg)
    dt = jnp.dtype(cfg.param_dtype)

    def scaled(path, a):
        name = path[-1].key
        if name == "bias":
            return 0.2 * jax.random.normal(jax.random.key(7), a.shape)
        if a.ndim >= 2 and name != "conv_w":
            return (5 * a.astype(jnp.float32)).astype(dt)
        return a

    params = jax.tree_util.tree_map_with_path(scaled, stages[0].params)
    return [dataclasses.replace(stages[0], params=params)]


@pytest.fixture(scope="module")
def stages():
    return _stages()


def _ref_logits(params, seq, first, n_out, cfg=CFG):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.served_logits(
            params, jnp.asarray(seq, jnp.int32), first, n_out=n_out,
            **_ref_kw(cfg)))


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
        assert np.abs(np.asarray(logp[b]) - want).mean() < tol["atol"] / 12


def test_more_than_one_stage_is_refused():
    with pytest.raises(ValueError, match="no pipeline build"):
        make_nemotron_h_stages(jax.random.key(0), CFG, n_stages=2)


def test_layer_kinds_and_cache_layout_follow_the_pattern():
    stage, = _stages()
    kinds = ["mamba" if "mamba" in b else "attn" if "attn" in b else "moe"
             for b in stage.params["blocks"]]
    assert kinds == ["mamba", "moe", "mamba", "attn", "moe", "moe"]
    # a layer is ONE part under one norm
    assert all(set(b) == {"norm", k}
               for b, k in zip(stage.params["blocks"], kinds))
    serving = CFG.paged_serving([stage], 48, BS)
    assert (serving.kv_layers, serving.kv_heads, serving.head_dim) == (1, 2,
                                                                       16)
    # a pair per Mamba layer (the tail holds x and both groups' B and C),
    # then every slot's newest token and key
    assert [tuple(s.shape for s in pair) for pair in serving.state_shapes] \
        == [((16, 256), (3, 256 + 2 * 2 * 16))] * 2 + [((), (2,))]
    assert serving.block == 1
    assert serving.counters == EXPERT_COUNTERS
    # the published model's stage: 11 layers, 5 + 5 + 1
    real = NemotronHConfig(
        pattern="MEM*EMEMEME", d_model=4096, n_heads=32, n_kv_heads=2,
        head_dim=128, mamba_heads=128, mamba_head_dim=64, n_groups=8,
        d_state=128, n_experts=512, top_k=22, experts_held=128,
        d_latent=1024, d_expert=2688, d_shared=5376, route_scale=5.0)
    assert (real.d_inner, real.d_conv_channels, real.held) == (
        8192, 10240, (0, 128))


@pytest.mark.parametrize("kw,match", [
    ({"pattern": "MXE"}, "letters of"),
    ({"pattern": ""}, "letters of"),
    ({"n_groups": 3}, "n_groups"),
    ({"mamba_head_dim": 16}, "multiple of 128"),
    ({"top_k": 9}, "top_k"),
    ({"experts_held": 6, "expert_offset": 4}, "held experts"),
    ({"n_kv_heads": 3}, "n_kv_heads"),
])
def test_config_refuses_shapes_it_cannot_run(kw, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **kw)


# -- the engine, with the logits it sampled from taken out --------------------


@functools.cache
def _twins(kernel):
    """The two programs' forwards, jitted once for every :class:`Tap`."""
    chunk = jax.jit(lambda p, kc, vc, st, toks, p0, table, slot:
                    nemotron_h._pattern_chunk_fwd(p, kc, vc, st, toks, p0,
                                                  table, slot, CFG, BS))
    step = jax.jit(lambda p, kc, vc, st, toks, pos, tables, live:
                   nemotron_h._pattern_decode_fwd(p, kc, vc, st, toks, pos,
                                                  tables, live, CFG, BS,
                                                  kernel))
    return chunk, step


class Tap:
    """An engine whose two programs are jitted twins of the real ones that
    also hand out the logits they chose from (greedy: ``argmax``) and the
    recurrent state of every slot after each call
    (``tests/test_jamba.py::Tap``)."""

    def __init__(self, stages, kernel="fused", **kw):
        kw = {"n_slots": 2, "max_len": 48, "block_size": BS,
              "prefill_chunk": 5, **kw}
        self.eng = InferenceEngine(stages, CFG, attn_kernel=kernel, **kw)
        self.rows = []          # (kind, {rid: slot}, logits)
        self.states = []        # (kind, first Mamba layer's H for all slots)
        chunk, step = _twins(kernel)

        def chunk_prefill(p, kc, vc, st, toks, p0, table, slot, seat, kd,
                          *_):
            *st, (newest, keys) = st
            kc, vc, st, row = chunk(p, kc, vc, tuple(st), toks, p0, table,
                                    slot)
            self.rows.append(("chunk", {self.eng.pool.occupant(int(slot)):
                                        int(slot)}, np.asarray(row)))
            self.states.append(("chunk", np.asarray(st[0][0])))
            tok = jnp.argmax(row).astype(jnp.int32)
            if seat != SEAT_NONE:
                newest = newest.at[int(slot)].set(
                    tok if seat == SEAT_SAMPLE else int(seat))
            return kc, vc, (*st, (newest, keys)), tok, jnp.asarray(kd)

        def decode(p, kc, vc, st, _toks, pos, tables, live, kd, *_):
            *st, (newest, keys) = st
            kc, vc, st, rows, counts = step(p, kc, vc, tuple(st), newest,
                                            pos, tables, live)
            self.rows.append(("decode", {self.eng.pool.occupant(int(s_)):
                                         int(s_) for s_ in
                                         np.flatnonzero(live)},
                              np.asarray(rows)))
            self.states.append(("decode", np.asarray(st[0][0])))
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


def _run(tap, handles):
    while tap.eng.busy:
        tap.eng.step()
        for h in handles:
            if h.slot is not None:
                h.slot_was = h.slot
    return handles


@pytest.mark.parametrize("kernel", ["dense", "fused"])
def test_chunked_prefill_then_decode_matches_the_reference(stages, kernel):
    """13 prompt tokens in chunks of 5, 5 and a ragged 3, then decode
    through pool and state, a second request alongside, and a third that
    joins mid-run in the slot the second leaves: every token's logits
    against the reference's one full forward over prompt and served
    tokens."""
    tap = Tap(stages, kernel)
    prompts = [_prompt(3, 13), _prompt(4, 6), _prompt(5, 9)]
    new = [9, 3, 4]
    handles = [tap.eng.submit(p, n) for p, n in zip(prompts[:2], new)]
    for _ in range(4):
        tap.eng.step()
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
    decode tick must hand the prefilling slot's state back bit for bit
    (the test above then shows the logits built on it are right)."""
    tap = Tap(stages)
    a = tap.eng.submit(_prompt(5, 4), 8)
    tap.eng.step()                       # the short prompt's one chunk
    b = tap.eng.submit(_prompt(6, 14), 3)
    checked = 0
    while tap.eng.busy:
        before = len(tap.states)
        tap.eng.step()
        new = tap.states[before:]
        if ([k for k, _ in new] == ["chunk", "decode"] and b.slot is not None
                and a.slot is not None and b.prefill_pos is not None):
            (_, after_chunk), (_, after_decode) = new
            assert np.array_equal(after_chunk[b.slot], after_decode[b.slot])
            assert not np.array_equal(after_chunk[a.slot],
                                      after_decode[a.slot])
            checked += 1
    assert checked >= 2


def test_released_slot_bound_again_gives_a_fresh_engines_logits(stages):
    """One slot: the second request finds the first one's state in it, and
    its first chunk (``p0 == 0``) must start from zeros."""
    first, second = _prompt(7, 9), _prompt(8, 11)
    used = Tap(stages, n_slots=1)
    _run(used, [used.eng.submit(first, 5)])
    assert np.abs(np.asarray(used.eng.pool.state[0][0])).max() > 0
    used.rows.clear()
    h_used, = _run(used, [used.eng.submit(second, 5)])
    fresh = Tap(stages, n_slots=1)
    h_fresh, = _run(fresh, [fresh.eng.submit(second, 5)])
    assert h_used.tokens == h_fresh.tokens
    assert np.array_equal(used.logits_of(h_used), fresh.logits_of(h_fresh))


def _engine(stages, **kw):
    kw = dict(dict(n_slots=2, max_len=48, block_size=BS, prefill_chunk=5,
                   attn_kernel="fused"), **kw)
    return InferenceEngine(stages, CFG, **kw)


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
    """The packed host array, the seats and the counter row of the real
    programs against the twins that take their arguments one by one."""
    prompts = [_prompt(3, 13), _prompt(4, 6)]
    tap = Tap(stages)
    want = _run(tap, [tap.eng.submit(p, 6) for p in prompts])
    eng = _engine(stages)
    got = [eng.submit(p, 6) for p in prompts]
    eng.drain()
    assert [h.tokens for h in got] == [h.tokens for h in want]


# -- the tick's counters --------------------------------------------------------


def test_a_plain_decode_tick_hands_its_expert_counters_to_the_span(stages):
    """``PagedServing.counters`` with ``block == 1``: the counts ride the
    tokens the engine reads a tick late, and land on the tick that read
    them; a tick that ran no decode reads 0."""
    eng = _engine(stages)
    mark = len(tracing.current().spans())
    hs = [eng.submit(_prompt(20 + i, 6 + i), 7) for i in range(2)]
    eng.drain()
    ticks = [s for s in tracing.current().spans()[mark:]
             if s.name == "engine.tick"]
    n_e, held, k, slots = 3, CFG.experts_held, CFG.top_k, 2
    decoded = [t.attrs for t in ticks if t.attrs["decoding"]]
    assert decoded and all(set(EXPERT_COUNTERS) <= set(t.attrs)
                           for t in ticks)
    for a in decoded:
        # every slot's row runs, live or not, and every expert is held
        assert a["expert_rows"] == n_e * slots * k
        assert n_e * k <= a["experts_hit"] <= min(n_e * held,
                                                  a["expert_rows"])
        assert 1 <= a["expert_rows_max"] <= slots
        assert {"state_slots", "kv_blocks", "ahead"} <= set(a)
    assert all(t.attrs["experts_hit"] == 0 for t in ticks
               if not t.attrs["decoding"])
    # the dispatch ahead holds: every decode but a cold one was launched
    # by the tick before
    assert sum(a["ahead"] for a in decoded) >= len(decoded) - 2
    assert sum(t.attrs["emitted"] for t in ticks) == sum(
        len(h.tokens) for h in hs)


def test_gpt_ticks_carry_no_counters():
    from simple_distributed_machine_learning_tpu.models.gpt import (
        GPTConfig,
        make_gpt_stages,
    )
    cfg = GPTConfig(vocab=64, seq_len=32, d_model=32, n_heads=2, n_layers=2)
    gstages = make_gpt_stages(jax.random.key(0), cfg, 1)[0]
    eng = InferenceEngine(gstages, cfg, n_slots=2, block_size=4,
                          prefill_chunk=4)
    mark = len(tracing.current().spans())
    eng.submit(np.arange(6, dtype=np.int32), 3)
    eng.drain()
    ticks = [s for s in tracing.current().spans()[mark:]
             if s.name == "engine.tick"]
    assert ticks and not any(set(EXPERT_COUNTERS) & set(t.attrs)
                             for t in ticks)


# -- the share ------------------------------------------------------------------


SHARED = dataclasses.replace(CFG, n_experts=32, top_k=5, experts_held=32)


def _expert_layer(cfg, key=3):
    stage, = _stages(dataclasses.replace(cfg, pattern="E*"), key=key)
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        stage.params["blocks"][0]["moe"])


def test_four_shares_of_eight_experts_add_up_to_the_whole_layer():
    """For 4 shares of 8-of-32 experts: the up-projected routed parts that
    the four shares give, added together, plus the shared expert counted
    ONCE, equal the uncut reference's whole layer; and each share's program
    equals the reference given the same share."""
    whole = _expert_layer(SHARED)
    u = jax.random.normal(jax.random.key(5), (3, 7, 64))
    kw = dict(top_k=SHARED.top_k, scale=SHARED.route_scale, quant=None)
    with jax.default_matmul_precision("highest"):
        want = reference.experts(whole, u.reshape(21, 64), first=0, **kw)
        shared = reference.shared_expert(whole, u.reshape(21, 64), None)
        parts, rows = [], []
        for share in range(4):
            cfg = dataclasses.replace(SHARED, experts_held=8,
                                      expert_offset=8 * share)
            ep = dict(whole, w1=whole["w1"][8 * share:8 * share + 8],
                      w2=whole["w2"][8 * share:8 * share + 8])
            got, r = nemotron_h._latent_experts(ep, u, cfg)
            ref = reference.experts(ep, u.reshape(21, 64),
                                    first=cfg.expert_offset, **kw)
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
        dict(b, moe=dict(b["moe"], w1=b["moe"]["w1"][2:6],
                         w2=b["moe"]["w2"][2:6])) if "moe" in b else b
        for b in whole["blocks"]])
    stage = dataclasses.replace(stages[0], params=cut)
    tokens = jnp.asarray(_prompt(1, 20))[None]
    got = np.asarray(nemotron_h.full_logits(cut, tokens, cfg))[0]
    np.testing.assert_allclose(got, _ref_logits(cut, tokens[0], 0, 20, cfg),
                               **F32)
    assert np.abs(got - _ref_logits(whole, tokens[0], 0, 20)).max() > 0.05
    eng = InferenceEngine([stage], cfg, n_slots=2, max_len=48,
                          block_size=BS, prefill_chunk=5)
    mark = len(tracing.current().spans())
    h = eng.submit(np.asarray(tokens[0, :9]), 5)
    eng.drain()
    assert len(h.tokens) == 5
    decoded = [s.attrs for s in tracing.current().spans()[mark:]
               if s.name == "engine.tick" and s.attrs["decoding"]]
    # 3 expert layers x 2 slots x top 3 pairs, of which those on held ones
    assert all(0 < a["expert_rows"] < 3 * 2 * 3 for a in decoded)
    assert all(a["experts_hit"] <= 3 * 4 for a in decoded)


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


def test_a_pattern_without_attention_is_not_served():
    cfg = dataclasses.replace(CFG, pattern="ME")
    with pytest.raises(ValueError, match="no attention layer"):
        InferenceEngine(_stages(cfg), cfg, n_slots=2, max_len=48)
