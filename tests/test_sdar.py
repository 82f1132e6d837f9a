"""The SDAR family (``models/sdar.py``: dropless sparse experts, rotary
grouped-query attention with QK-norm, generation by diffusion over blocks)
at toy size on the CPU: the stage and the serving engine against the plain
reference (``bench_cells/reference/sdar.py``: float32, ``highest``, the
experts a masked sum over all of them, no kernel, cache or batching), on
seeded random weights.

Tolerances, each with its reason:

- ``F32`` (float32 weights, float32 cache): program and reference compute
  the same float32 expressions and differ in the order of the sums (blocked
  matmuls, the softmax over gathered blocks, the grouped product against the
  masked sum over all experts) through 2 layers; logits here are of order 1-3
  and the observed gap is under 3e-6: 1e-4 absolute and relative. A bfloat16
  pass anywhere (2**-9 relative on an operand) moves the logits by 1e-2 and
  fails this.
- ``GAP``: the engine hands out tokens and the forward that fixed each, not
  logits, so every denoising forward of every block is held to the
  reference's two-stream logits by two gaps: the served token's logit below
  the reference's best at that row, and the fixed position's confidence
  below the best still-masked one's. Both are 0 unless two candidates lie
  within the float32 noise above (observed: exactly 0): 1e-4. A reference
  that routes to other experts reads 0.05 and more.
- Runs of the SAME compiled program on the same numbers (alone or among
  neighbours, ahead or plain order, preempted or not) are compared token
  for token.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_cells.reference import sdar as reference
from bench_cells.runners.serve_diffusion import (
    _row_stats,
    step_gaps,
    two_streams,
)

from simple_distributed_machine_learning_tpu.models import sdar
from simple_distributed_machine_learning_tpu.models.sdar import (
    SdarConfig,
    denoise_forwards,
    denoise_schedule,
    make_sdar_stages,
)
from simple_distributed_machine_learning_tpu.ops import moe_experts
from simple_distributed_machine_learning_tpu.ops.layers import (
    rms_norm,
    rotary,
)
from simple_distributed_machine_learning_tpu.serve import InferenceEngine
from simple_distributed_machine_learning_tpu.serve.slots import PagedKVPool
from simple_distributed_machine_learning_tpu.telemetry import tracing

CFG = SdarConfig(vocab=256, seq_len=64, d_model=64, n_heads=4, n_kv_heads=2,
                 head_dim=16, n_layers=2, n_experts=8, top_k=2, d_expert=32,
                 block_length=4, denoising_steps=4, mask_id=255)
B = CFG.block_length
F32 = dict(rtol=1e-4, atol=1e-4)
GAP = 1e-4
REF = dict(n_heads=CFG.n_heads, n_kv_heads=CFG.n_kv_heads, top_k=CFG.top_k,
           theta=CFG.rope_theta, eps=CFG.rms_eps)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def stages():
    """The builder's stage with its matrices scaled from normal 0.02 to
    0.1: at width 64 the published scale leaves every activation near zero,
    and a model that is all but linear would forgive a wrong mask."""
    st, _, _ = make_sdar_stages(jax.random.key(0), CFG)
    params = jax.tree.map(lambda a: a * 5 if a.ndim >= 2 else a,
                          st[0].params)
    return [dataclasses.replace(st[0], params=params)]


def _engine(stages, **kw):
    kw = dict(dict(n_slots=4, max_len=64, block_size=8, prefill_chunk=8,
                   attn_kernel="fused"), **kw)
    return InferenceEngine(stages, CFG, **kw)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 250, n).astype(np.int32)


def _drain(eng):
    ticks = 0
    while eng.busy:
        eng.step()
        ticks += 1
    return ticks


def _gaps(params, r, **over):
    """``[positions, 2]``: the request's every fixed position against the
    reference's two-stream logits (``GAP``)."""
    clean, noisy, starts = two_streams(r.prompt, r.blocks, B, CFG.mask_id)
    ref = reference.noisy_logits(
        params, jnp.asarray(clean), jnp.asarray(noisy), jnp.asarray(starts),
        block=B, **dict(REF, **over))
    final = np.concatenate([np.tile(np.asarray(t, np.int32), (max(o), 1))
                            for _, t, o in r.blocks]).reshape(-1)
    st = [np.asarray(a) for a in _row_stats(ref, jnp.asarray(final))]
    served, _ = step_gaps(r.blocks, st, st[0] - st[2], st[1], B)
    return served


# -- (a) the stage under the block mask ---------------------------------------


@pytest.mark.parametrize("block", [1, 4])
def test_full_logits_match_the_reference_under_the_block_mask(stages, block):
    params = stages[0].params
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, 250)
    got = sdar.full_logits(params, toks, CFG, block=block)
    want = jnp.stack([reference.clean_logits(params, t, block=block, **REF)
                      for t in toks])
    np.testing.assert_allclose(got, want, **F32)
    if block == 4:      # the mask matters: the causal one reads otherwise
        causal = sdar.full_logits(params, toks, CFG, block=1)
        assert float(jnp.abs(causal - got).max()) > 1e-2


def test_block_mask_is_causal_between_blocks_and_full_inside():
    pos = jnp.arange(8)
    seen = np.asarray(sdar.block_mask(pos, pos, 4))
    assert seen[:4, :4].all() and seen[4:, :].all() and not seen[:4, 4:].any()
    assert (np.asarray(sdar.block_mask(pos, pos, 1))
            == np.tril(np.ones((8, 8), bool))).all()


# -- (b) the engine against the two-stream reference --------------------------

_SERVED = [
    # prompt length (its remainder), answer length, denoising steps, eos
    pytest.param(8, 8, 4, None, id="rem0"),
    pytest.param(9, 7, 4, None, id="rem1"),
    pytest.param(11, 9, 4, None, id="rem3"),
    pytest.param(21, 10, 4, None, id="two-chunks"),
    pytest.param(12, 8, 1, None, id="steps1"),
    pytest.param(12, 8, 2, None, id="steps2"),
    pytest.param(10, 5, 3, None, id="steps3-cut-inside-a-block"),
    pytest.param(3, 6, 4, None, id="shorter-than-a-block"),
    pytest.param(8, 12, 4, "second", id="eos-inside-a-block"),
]


@pytest.mark.parametrize("kernel", ["dense", "fused"])
@pytest.mark.parametrize("plen,n_new,steps,eos", _SERVED)
def test_every_denoising_forward_follows_the_reference(stages, kernel, plen,
                                                       n_new, steps, eos):
    """Chunked prefill, then denoising and committing ticks: every position
    is fixed where and as the reference's logits over the same inputs say,
    a block costs its schedule's forwards and one commit, and the tokens
    are cut at ``max_new_tokens`` or ended by ``eos_id``."""
    params = stages[0].params
    prompt = _prompt(plen, plen)
    eos_id = None
    if eos:     # a token the request is known to emit mid-block
        probe = _engine(stages, attn_kernel=kernel)
        h = probe.submit(prompt, n_new, denoising_steps=steps)
        _drain(probe)
        eos_id = h.tokens[1]
    eng = _engine(stages, attn_kernel=kernel)
    r = eng.submit(prompt, n_new, denoising_steps=steps, eos_id=eos_id)
    ticks = _drain(eng)
    if eos:
        cut = r.tokens.index(eos_id) + 1
        assert r.finish_reason == "eos" and len(r.tokens) == cut < n_new
    else:
        assert r.finish_reason == "length" and len(r.tokens) == n_new
    rem = plen % B
    assert [p for p, _, _ in r.blocks] == list(range(
        plen - rem, plen - rem + B * len(r.blocks), B))
    first = r.blocks[0]
    assert first[1][:rem] == list(prompt[plen - rem:])
    assert first[2][:rem] == [0] * rem
    forwards = 0
    for i, (_, toks, order) in enumerate(r.blocks):
        masked = B - (rem if i == 0 else 0)
        n_fwd = denoise_forwards(B, steps, masked)
        assert max(order) == n_fwd
        want = denoise_schedule(B, steps)
        fixed = [order.count(j + 1) for j in range(n_fwd)]
        assert fixed[:-1] == want[:n_fwd - 1] and sum(fixed) == masked
        forwards += n_fwd + 1
    emitted = [t for _, toks, _ in r.blocks for t in toks][rem:]
    assert emitted[:len(r.tokens)] == r.tokens
    chunks = max(-(-(plen - rem) // 8), 1)
    assert ticks == chunks + forwards
    gaps = _gaps(params, r)
    assert len(gaps) == B * len(r.blocks) - rem
    assert gaps.max() <= GAP, gaps.max(0)


def test_the_check_has_power_against_a_wrong_router(stages):
    """A reference that routes every token to ONE expert (not the two the
    model is configured for) reads far above ``GAP``."""
    eng = _engine(stages)
    r = eng.submit(_prompt(5, 12), 12)
    _drain(eng)
    assert _gaps(stages[0].params, r, top_k=1)[:, 0].mean() > 100 * GAP


def test_sampled_requests_are_reproducible_and_follow_their_seed(stages):
    runs = []
    for seed in (7, 7, 8):
        eng = _engine(stages)
        r = eng.submit(_prompt(2, 9), 10, temperature=1.0, top_k=20,
                       seed=seed)
        _drain(eng)
        runs.append(r.tokens)
    assert runs[0] == runs[1] != runs[2] and len(runs[0]) == 10


# -- (c) dropless: neighbours cannot change a request's tokens ----------------


def test_tokens_do_not_depend_on_neighbours_that_crowd_one_expert(stages):
    """Alone, and among seven others whose every prompt token is the same
    id (so they route alike and crowd the same experts): with a capacity
    the crowd would push this request's tokens out; here they are the
    same."""
    prompt = _prompt(3, 13)
    alone = _engine(stages, n_slots=8)
    want = alone.submit(prompt, 11)
    _drain(alone)
    eng = _engine(stages, n_slots=8)
    got = eng.submit(prompt, 11)
    others = [eng.submit(np.full(12, 17, np.int32), 12) for _ in range(7)]
    _drain(eng)
    assert got.tokens == want.tokens and got.blocks == want.blocks
    assert all(o.tokens == others[0].tokens for o in others)


# -- (d) the grouped expert layer ----------------------------------------------


def _moe_params(key, d=64, f=32, e=8):
    kr, kg, ku, kd = jax.random.split(key, 4)
    return {"router": jax.random.normal(kr, (d, e)),
            "gate": 0.1 * jax.random.normal(kg, (e, d, f)),
            "up": 0.1 * jax.random.normal(ku, (e, d, f)),
            "down": 0.1 * jax.random.normal(kd, (e, f, d))}


@pytest.mark.parametrize("case", ["spread", "one-gets-all", "one-token",
                                  "ragged"])
def test_grouped_experts_match_the_masked_sum(case):
    """Against the reference's plain sum over all the experts: with rows
    spread over them, with one expert that every token picks (and experts
    that get no row), with a single token, and with a row count that is no
    multiple of the tile."""
    mp = _moe_params(jax.random.key(0))
    n = {"one-token": 1, "ragged": 37}.get(case, 16)
    x = jax.random.normal(jax.random.key(1), (n, 64))
    if case == "one-gets-all":
        # expert 3 first for every token, expert 5 second: six get nothing
        col = jnp.zeros((64, 8)).at[:, 3].set(1.0).at[:, 5].set(0.5)
        mp = dict(mp, router=col)
        x = jnp.abs(x)
    got, rows = moe_experts.dropless_experts(mp, x, 2)
    np.testing.assert_allclose(got, reference.experts(mp, x, 2, None), **F32)
    assert int(rows.sum()) == 2 * n
    if case == "one-gets-all":
        assert rows.tolist() == [0, 0, 0, n, 0, n, 0, 0]


def _latent_params(key, d=64, lat=32, f=48, e=16, held=(0, 16)):
    kr, kb, k1, k2 = jax.random.split(key, 4)
    return {"router": jax.random.normal(kr, (d, e)),
            "bias": 0.3 * jax.random.normal(kb, (e,)),
            "w1": 0.2 * jax.random.normal(k1, (e, lat, f))[
                held[0]:held[0] + held[1]],
            "w2": 0.2 * jax.random.normal(k2, (e, f, lat))[
                held[0]:held[0] + held[1]]}


def _dense_masked_sum(mp, x, rows, gate, body, held):
    """``sum over the held e of gate[:, e] * E_e(rows)``, every expert
    computed for every row."""
    out = 0.0
    for j in range(held[1]):
        if body == "swiglu":
            y = (jax.nn.silu(rows @ mp["gate"][j]) * (rows @ mp["up"][j])
                 ) @ mp["down"][j]
        else:
            y = jnp.square(jax.nn.relu(rows @ mp["w1"][j])) @ mp["w2"][j]
        out = out + gate[:, held[0] + j, None] * y
    return out


@pytest.mark.parametrize("held", [(0, 16), (4, 8), (12, 4)])
@pytest.mark.parametrize("rule,body", [("softmax", "swiglu"),
                                       ("sigmoid", "relu2"),
                                       ("sigmoid", "swiglu"),
                                       ("softmax", "relu2")])
def test_each_routing_rule_and_expert_body_match_a_dense_masked_sum(
        rule, body, held):
    """The routing rule and the expert body are arguments: every pairing,
    over all the experts and over a range held, against a dense sum in
    which every held expert multiplies every row and a gate (the rule's
    weight where chosen, 0 elsewhere) masks it. The normaliser is over all
    the chosen, held or not."""
    from bench_cells.reference import nemotron_h as latent_ref
    k = 5
    x = jax.random.normal(jax.random.key(2), (37, 64))
    if body == "swiglu":
        mp = _moe_params(jax.random.key(0), e=16)
        mp = dict(mp, **{w: mp[w][held[0]:held[0] + held[1]]
                         for w in ("gate", "up", "down")})
        rows, experts = None, moe_experts.swiglu_experts
    else:
        mp = _latent_params(jax.random.key(0), held=held)
        rows = jax.random.normal(jax.random.key(3), (37, 32))
        experts = moe_experts.relu2_experts
    bias = 0.3 * jax.random.normal(jax.random.key(4), (16,))
    scores = x @ mp["router"]
    if rule == "softmax":
        route = moe_experts.softmax_top_k
        gate = reference.expert_gates(jax.nn.softmax(scores, -1), k)
    else:
        route = moe_experts.sigmoid_top_k(bias, 2.5)
        gate = latent_ref.expert_gates(scores, bias, k, 2.5)
    got, sizes = moe_experts.dropless_experts(
        mp, x, k, route=route, experts=experts, held=held, rows=rows)
    want = _dense_masked_sum(mp, x, x if rows is None else rows, gate, body,
                             held)
    np.testing.assert_allclose(got, want, **F32)
    # rows per HELD expert, and only the pairs that landed on one
    assert sizes.shape == (held[1],)
    assert sizes.tolist() == (gate[:, held[0]:held[0] + held[1]] > 0).sum(
        0).tolist()
    assert (int(sizes.sum()) == 37 * k) == (held == (0, 16))


def test_pairs_of_absent_experts_read_no_weight():
    """Every token routed to experts that are not held: no group has a row,
    the kernel visits nothing (matrices of NaN leave no trace), and the
    result is exactly zero."""
    mp = _latent_params(jax.random.key(0), held=(8, 8))
    mp = dict(mp, w1=jnp.full_like(mp["w1"], jnp.nan),
              w2=jnp.full_like(mp["w2"], jnp.nan))
    # experts 0 and 1 first for every token: both absent
    col = jnp.zeros((64, 16)).at[:, 0].set(1.0).at[:, 1].set(0.5)
    x = jnp.abs(jax.random.normal(jax.random.key(1), (16, 64)))
    got, sizes = moe_experts.dropless_experts(
        dict(mp, router=col), x, 2,
        route=moe_experts.sigmoid_top_k(jnp.zeros(16), 2.5),
        experts=moe_experts.relu2_experts, held=(8, 8),
        rows=jnp.ones((16, 32)))
    assert sizes.tolist() == [0] * 8
    assert np.array_equal(np.asarray(got), np.zeros((16, 32), np.float32))
    _, _, _, n = moe_experts._visits(sizes, 128, 128)
    assert int(n[0]) == 0
    with pytest.raises(ValueError, match="held experts"):
        moe_experts.dropless_experts(mp, x, 2, held=(12, 8))


@pytest.mark.parametrize("sizes", [[0, 16, 0, 0], [4, 4, 4, 4],
                                   [100, 0, 50, 150], [1, 0, 0, 4],
                                   [0, 0, 0, 130]])
def test_grouped_matmul_multiplies_each_group_by_its_own_matrix(sizes):
    m = sum(sizes)
    lhs = jax.random.normal(jax.random.key(0), (m, 64))
    rhs = jax.random.normal(jax.random.key(1), (len(sizes), 64, 32))
    got = moe_experts.grouped_matmul(lhs, rhs, jnp.asarray(sizes))
    gid = np.repeat(np.arange(len(sizes)), sizes)
    np.testing.assert_allclose(
        got, jnp.einsum("mk,mkn->mn", lhs, rhs[gid]), **F32)


def test_visits_skip_the_experts_that_got_no_row():
    """The kernel's walk: one visit per (group, row tile) pair of a
    non-empty group, in row order; the static rest repeats the last."""
    off, gids, tids, n = moe_experts._visits(
        jnp.asarray([0, 200, 0, 56, 0]), 256, 128)
    assert off.tolist() == [0, 0, 200, 200, 256, 256] and int(n[0]) == 3
    assert gids.tolist() == [1, 1, 3, 3, 3, 3]
    assert tids.tolist() == [0, 1, 1, 1, 1, 1]


# -- (e) rotation and QK-norm ---------------------------------------------------


def test_rotary_is_the_rotate_half_formula():
    x = jax.random.normal(jax.random.key(0), (2, 5, 3, 16))
    pos = jnp.asarray([[0, 1, 2, 3, 4], [7, 8, 9, 1000, 1001]])
    got = rotary(x, pos, 1e6)
    half = 8
    ang = (np.asarray(pos, np.float64)[..., None, None]
           * 1e6 ** (-np.arange(half) / half))
    z = (np.asarray(x[..., :half], np.float64)
         + 1j * np.asarray(x[..., half:], np.float64)) * np.exp(1j * ang)
    np.testing.assert_allclose(got, np.concatenate([z.real, z.imag], -1),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got[0, 0], x[0, 0], rtol=1e-6)   # position 0
    for b in range(2):      # and the reference's own spelling of it
        np.testing.assert_allclose(
            got[b], reference.rotate(x[b], pos[b], 1e6), **F32)


def test_queries_and_keys_are_normed_per_head_before_the_rotation(stages):
    ap = stages[0].params["blocks"][0]["attn"]
    ap = dict(ap, q_norm=jnp.linspace(0.5, 2.0, 16),
              k_norm=jnp.linspace(2.0, 0.5, 16))
    u = jax.random.normal(jax.random.key(2), (1, 6, 64))
    pos = jnp.arange(10, 16)[None]
    q, k, v = sdar._qkv(ap, u, pos, CFG)
    raw_q = (u @ ap["wq"]).reshape(1, 6, 4, 16)
    raw_k = (u @ ap["wk"]).reshape(1, 6, 2, 16)
    np.testing.assert_allclose(
        q, rotary(rms_norm(ap["q_norm"], raw_q, 1e-6), pos, 1e6), **F32)
    np.testing.assert_allclose(
        k, rotary(rms_norm(ap["k_norm"], raw_k, 1e-6), pos, 1e6), **F32)
    np.testing.assert_allclose(v, (u @ ap["wv"]).reshape(1, 6, 2, 16), **F32)
    # the rotation keeps a head's norm, the norm before it set it
    np.testing.assert_allclose(
        jnp.linalg.norm(q, axis=-1),
        jnp.linalg.norm(rms_norm(ap["q_norm"], raw_q, 1e-6), axis=-1),
        rtol=1e-4)


# -- (f) the tick dispatched ahead ----------------------------------------------

_MIX = [(9, 7, 4), (16, 8, 2), (3, 5, 1), (21, 10, 4), (8, 6, 4), (12, 9, 3)]


def _run_mix(stages, ahead=True, **kw):
    eng = _engine(stages, **kw)
    if not ahead:
        eng._dispatch_ahead = False
    hs = [eng.submit(_prompt(i, n), m, denoising_steps=st)
          for i, (n, m, st) in enumerate(_MIX)]
    mark = len(tracing.current().spans())
    _drain(eng)
    ticks = [s for s in tracing.current().spans()[mark:]
             if s.name == "engine.tick"]
    return hs, ticks, eng


def test_ahead_and_plain_order_give_the_same_tokens(stages):
    """More requests than slots, every schedule: the tick that dispatches
    the next decode before reading this one's serves the same tokens as the
    plain order, and without ``eos_id`` every decode was dispatched ahead
    (the first by the tick of its slot's last chunk)."""
    plain, _, _ = _run_mix(stages, ahead=False)
    ahead, ticks, _ = _run_mix(stages)
    assert [h.tokens for h in ahead] == [h.tokens for h in plain]
    assert [h.blocks for h in ahead] == [h.blocks for h in plain]
    decoded = [t.attrs["ahead"] for t in ticks if t.attrs["decoding"]]
    assert len(decoded) > 20 and sum(decoded) == len(decoded)


def test_tick_spans_count_forwards_commits_and_experts(stages):
    hs, ticks, _ = _run_mix(stages)
    for t in ticks:
        a = t.attrs
        assert a["forwards"] == a["decoding"] <= 4
        assert 0 <= a["commits"] <= a["forwards"]
        if a["forwards"]:
            # every slot's rows run, live or not: 4 slots x 4 rows x top 2
            assert 2 <= a["experts_hit"] <= 2 * 8
            assert 4 <= a["expert_rows_max"] <= 16
        else:
            assert a["experts_hit"] == a["expert_rows_max"] == 0
    assert sum(t.attrs["emitted"] for t in ticks) == sum(
        len(h.tokens) for h in hs)
    assert sum(t.attrs["commits"] for t in ticks) == sum(
        len(h.blocks) for h in hs)
    subs = [s for s in tracing.current().spans()
            if s.name == "engine.submit" and "blocks" in s.attrs]
    assert [s.attrs["blocks"] for s in subs[-len(_MIX):]] == [
        -(-(n % B + m) // B) for n, m, _ in _MIX]


def test_a_request_with_eos_holds_the_dispatch_back_only_at_its_commits(
        stages):
    eng = _engine(stages)
    r = eng.submit(_prompt(1, 8), 8, eos_id=254)    # never emitted
    held = []
    while eng.busy:
        eng.step()
        held.append(eng._ahead is None)
    assert len(r.tokens) == 8
    # 1 chunk, then 2 blocks x 5 forwards: nothing is dispatched ahead in
    # the tick that holds a commit in flight (ticks 6 and 11)
    assert held == [False] * 5 + [True] + [False] * 4 + [True]


# -- (g) preempt and restore mid-block ------------------------------------------


@pytest.mark.parametrize("how", ["preempt", "restore"])
@pytest.mark.parametrize("at_tick", [4, 6, 9])
def test_a_block_interrupted_midway_starts_again_from_masks(stages, how,
                                                            at_tick):
    """Preempted (back to the queue of its own engine) or restored into a
    rebuilt engine after ``at_tick`` ticks, mid-block or right after a
    commit: the committed tokens stay, the block in progress is denoised
    again from masks, and the greedy tokens are those of the uninterrupted
    run."""
    want = _engine(stages)
    w = want.submit(_prompt(4, 10), 11)
    _drain(want)
    eng = _engine(stages)
    r = eng.submit(_prompt(4, 10), 11)
    for _ in range(at_tick):
        eng.step()
    kept = list(r.tokens)
    assert len(kept) % B in (0, 2) and len(kept) < 11
    if how == "preempt":
        eng.preempt(r.rid)
    else:
        eng = _engine(stages)
        r.state = "queued"
        eng.restore(r)
    assert list(r.resume_seq) == list(r.prompt) + kept
    _drain(eng)
    assert r.tokens == w.tokens and r.tokens[:len(kept)] == kept
    assert r.blocks[-1] == w.blocks[-1]


def test_a_shared_prefix_of_whole_pool_blocks_is_reused(stages):
    """Two prompts that share 16 tokens (two pool blocks of 8): the second
    request computes from position 16 on, and its tokens are those it has
    alone."""
    head = _prompt(9, 16)
    a, b = np.concatenate([head, _prompt(10, 5)]), np.concatenate(
        [head, _prompt(11, 7)])
    alone = _engine(stages)
    want = alone.submit(b, 9)
    _drain(alone)
    eng = _engine(stages)
    first = eng.submit(a, 6)
    _drain(eng)
    got = eng.submit(b, 9)
    mark = len(tracing.current().spans())
    _drain(eng)
    chunks = [(s.attrs["p0"], s.attrs["n"])
              for s in tracing.current().spans()[mark:]
              if s.name == "engine.prefill.prepare"]
    assert eng.pool.prefix_hit_blocks_total == 2 and chunks == [(16, 4)]
    assert got.tokens == want.tokens and len(first.tokens) == 6


def test_pool_budgets_whole_blocks_and_shares_whole_pool_blocks_only():
    pool = PagedKVPool(1, 2, 2, 64, 16, block_size=8, step_rows=4)
    assert pool._rows_needed(9, 7) == 16 and pool._rows_needed(9, 8) == 20
    assert PagedKVPool(1, 2, 2, 64, 16, block_size=8)._rows_needed(9, 7) == 15
    slot = pool.acquire(0)
    seq = np.arange(22, dtype=np.int32)
    assert pool.begin_seq(slot, seq, 6) == 0
    for p in range(20):
        pool.ensure_writable(slot, p)
    pool.register_prefix(slot, seq[:20])
    # 20 rows written: two whole pool blocks are published, the tail is not
    assert pool.shared_prefix_len(seq) == 16
    assert pool.shared_prefix_len(seq[:18]) == 8    # whole steps: 16 - 1
    assert pool.shared_prefix_len(seq[:17]) == 8
    with pytest.raises(ValueError, match="multiple of 4"):
        pool.seat_block(slot, 6, 5)


# -- (h) what is refused, by name ----------------------------------------------


@pytest.mark.parametrize("kw,name", [
    (dict(host_cache_blocks=4), "host_cache_blocks"),
    (dict(lint=True), "lint=True"),
    (dict(draft_stages="d", draft_cfg="c", spec_k=2), "draft_stages"),
    (dict(adapters="bank"), "adapters"),
    (dict(mesh="mesh"), "mesh"),
    (dict(cache_dtype="int8"), "quantized cache_dtype"),
])
def test_options_built_for_one_token_a_step_are_refused_by_name(stages, kw,
                                                                name):
    if "adapters" in kw:
        kw = dict(adapters=type("Bank", (), {"n_rows": 5})())
    with pytest.raises(ValueError, match=name) as e:
        _engine(stages, **kw)
    assert "diffusion over blocks" in str(e.value)


@pytest.mark.parametrize("kw,match", [
    (dict(prefill_chunk=6), "prefill_chunk=6 must be a multiple"),
    (dict(block_size=6), "multiples of block_length 4"),
    (dict(max_len=62), "multiples of block_length 4"),
])
def test_shapes_that_would_split_a_block_are_refused(stages, kw, match):
    with pytest.raises(ValueError, match=match):
        _engine(stages, **kw)


def test_denoising_steps_are_checked_at_submit(stages):
    eng = _engine(stages)
    for bad in (0, 5):
        with pytest.raises(ValueError, match="denoising_steps"):
            eng.submit(_prompt(0, 8), 4, denoising_steps=bad)
    assert eng.submit(_prompt(0, 8), 4).denoising_steps == 4
    with pytest.raises(ValueError, match="one stage"):
        make_sdar_stages(jax.random.key(0), CFG, 2)


@pytest.mark.parametrize("block,steps,want", [
    (4, 4, [1, 1, 1, 1]), (4, 3, [2, 1, 1]), (4, 2, [2, 2]), (4, 1, [4]),
    (8, 3, [3, 3, 2])])
def test_static_schedule_gives_the_remainder_to_the_first_forwards(
        block, steps, want):
    assert denoise_schedule(block, steps) == want
    assert denoise_forwards(block, steps, block) == steps
    assert denoise_forwards(block, steps, 1) == 1
