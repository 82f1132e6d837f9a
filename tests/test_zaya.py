"""The ZAYA1 family (``models/zaya.py``: compressed convolutional attention
over a latent K/V pool with a per-slot convolution state, a top-1 router
that is an MLP carrying its state across layers) at toy size on the CPU:
the stage and the serving engine against the dense-math forward
(``full_logits``) and the plain reference (``bench_cells/reference/zaya.py``:
float32, ``highest``, the convolutions shifted sums over the whole sequence,
every expert over every row under a mask, no kernel, cache or batching), on
seeded random weights. Logits are compared, not tokens.

Tolerances, each with its reason:

- ``F32`` (float32 weights, float32 cache): program and reference compute
  the same float32 expressions and differ in the order of the sums (blocked
  matmuls, the softmax over gathered blocks, the grouped expert products)
  through 3 layers; logits here are of order 1 and the observed gap is under
  2e-5: 2e-4 absolute and relative. A bfloat16 pass anywhere moves the
  logits by 1e-2 and fails this.
- ``BF16`` (bfloat16 weights, the published dtype): the program rounds every
  matmul's activations to bfloat16 where the reference keeps them float32
  over the same rounded weights, and a rounded score can flip a token's ONE
  expert, which moves that token's logits by a whole expert's output: 0.1
  absolute for every element, 0.01 for the mean. An int8 operand moves the
  mean by 0.03 and more.
- Runs of the SAME compiled program on the same numbers are compared bit for
  bit.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_cells.reference import zaya as reference

from simple_distributed_machine_learning_tpu.models import (
    serving as serving_module,
)
from simple_distributed_machine_learning_tpu.models import zaya
from simple_distributed_machine_learning_tpu.models.serving import (
    SEAT_NONE,
    SEAT_SAMPLE,
)
from simple_distributed_machine_learning_tpu.models.zaya import (
    EXPERT_COUNTERS,
    ZayaConfig,
    make_zaya_stages,
)
from simple_distributed_machine_learning_tpu.ops import moe_experts
from simple_distributed_machine_learning_tpu.ops.layers import (
    matmul_acc32,
    rotary,
)
from simple_distributed_machine_learning_tpu.serve import InferenceEngine
from simple_distributed_machine_learning_tpu.telemetry import tracing

CFG = ZayaConfig(vocab=97, seq_len=48, d_model=64, n_layers=3, n_heads=4,
                 n_kv_heads=2, head_dim=16, n_experts=4, d_expert=48,
                 d_router=8)
F32 = dict(rtol=2e-4, atol=2e-4)
BF16 = dict(rtol=0.0, atol=0.1)
BS = 4


def _ref_kw(cfg):
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                theta=cfg.rope_theta, rotated=cfg.rotated, eps=cfg.rms_eps)


def _stages(cfg=CFG, key=0):
    """The builder's stage with its matrices scaled from normal 0.02 to 0.1
    (at width 64 the published scale leaves every activation near zero, and
    a model that is all but linear would forgive a wrong state), the
    router's MLP at unit scale (at 0.1 its softmax is flat and the choice a
    coin's), and every vector that starts at its identity (the residual
    scalings, ``tau``, ``gamma``, the biases) moved off it, so that each is
    seen to act."""
    stages, _, _ = make_zaya_stages(jax.random.key(key), cfg)
    dt = jnp.dtype(cfg.param_dtype)
    keys = iter(jax.random.split(jax.random.key(100 + key), 512))

    def moved(path, a):
        name = path[-1].key
        noise = jax.random.normal(next(keys), a.shape)
        if name == "bias":
            return 0.05 * noise
        if name in ("w1", "w2", "w3"):
            return (noise / np.sqrt(a.shape[0]) * 2).astype(dt)
        if a.ndim >= 2 and not name.startswith("conv"):
            return (5 * a.astype(jnp.float32)).astype(dt)
        if name in ("res_scale", "out_scale", "tau", "gamma", "norm"):
            return (a.astype(jnp.float32) + 0.2 * noise).astype(dt)
        if name in ("res_bias", "out_bias", "down_b", "b1", "b2"):
            return (0.1 * noise).astype(dt)
        return a

    params = jax.tree_util.tree_map_with_path(moved, stages[0].params)
    return [dataclasses.replace(stages[0], params=params)]


@pytest.fixture(scope="module")
def stages():
    return _stages()


def _ref_logits(params, seq, cfg=CFG, quant=None):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.full_logits(
            params, jnp.asarray(seq, jnp.int32), quant=quant,
            **_ref_kw(cfg)))


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab, n).astype(
        np.int32)


# -- the stage ------------------------


@pytest.mark.parametrize("dtype,tol", [("float32", F32), ("bfloat16", BF16)])
def test_stage_full_sequence_logits_match_the_reference(dtype, tol):
    cfg = dataclasses.replace(CFG, param_dtype=dtype)
    stage, = _stages(cfg)
    tokens = jnp.asarray(np.stack([_prompt(1, 24), _prompt(2, 24)]))
    logp = stage.apply(stage.params, tokens, jax.random.key(0), True)
    assert logp.shape == (2, 24, CFG.vocab) and logp.dtype == jnp.float32
    for b in range(2):
        want = np.asarray(jax.nn.log_softmax(
            _ref_logits(stage.params, tokens[b], cfg)))
        np.testing.assert_allclose(np.asarray(logp[b]), want, **tol)
        assert np.abs(np.asarray(logp[b]) - want).mean() < tol["atol"] / 10


def test_other_tap_counts_than_the_published_two_match_the_reference():
    """``cca_time0`` / ``cca_time1`` are the config's: three and four taps
    (a tail of two and of three rows a slot) against the reference."""
    cfg = dataclasses.replace(CFG, conv0=3, conv1=4)
    stage, = _stages(cfg)
    tokens = _prompt(3, 17)
    got = np.asarray(zaya.full_logits(
        stage.params, jnp.asarray(tokens)[None], cfg)[0])
    np.testing.assert_allclose(got, _ref_logits(stage.params, tokens, cfg),
                               **F32)
    eng = InferenceEngine([stage], cfg, n_slots=1, max_len=48,
                          block_size=BS, prefill_chunk=5)
    h = eng.submit(tokens[:13], 4)
    eng.drain()
    seq = np.concatenate([tokens[:13], np.asarray(h.tokens[:-1], np.int32)])
    want = _ref_logits(stage.params, seq, cfg)[12:16]
    assert h.tokens == [int(t) for t in want.argmax(-1)]


def test_a_lower_precision_fails_the_tolerances():
    """What the tolerances are for: the bfloat16 program is outside ``F32``
    of the float32 reference, and an int8-operand forward outside
    ``BF16``'s mean of it."""
    tokens = _prompt(1, 24)
    stage32, = _stages()
    want = _ref_logits(stage32.params, tokens)
    stage16, = _stages(dataclasses.replace(CFG, param_dtype="bfloat16"))
    got16 = np.asarray(zaya.full_logits(
        stage16.params, jnp.asarray(tokens)[None], CFG)[0])
    assert np.abs(got16 - want).max() > 10 * F32["atol"]
    int8 = _ref_logits(stage32.params, tokens, quant="int8")
    assert np.abs(int8 - want).mean() > BF16["atol"] / 10


def test_more_than_one_stage_is_refused():
    with pytest.raises(ValueError, match="tied head"):
        make_zaya_stages(jax.random.key(0), CFG, n_stages=2)


def test_cache_layout_two_kinds_of_state_for_every_layer():
    stage, = _stages()
    assert all(set(b) == {"attn", "moe"} for b in stage.params["blocks"])
    serving = CFG.paged_serving([stage], 48, BS)
    # every layer attends: K/V rows in the latent, 2 heads of 16 lanes
    assert (serving.kv_layers, serving.kv_heads, serving.head_dim) == (3, 2,
                                                                       16)
    # and every layer keeps the two convolutions' tails over [q~ ; k~] and
    # the shifted half of the value; then every slot's newest token and key
    c = 4 * 16 + 2 * 16
    assert [tuple(s.shape for s in leaf) for leaf in serving.state_shapes] \
        == [((1, c), (1, c), (16,))] * 3 + [((), (2,))]
    assert all(s.dtype == jnp.float32 for leaf in serving.state_shapes[:-1]
               for s in leaf)
    assert serving.block == 1
    assert serving.counters == EXPERT_COUNTERS
    assert CFG.recurrent_state
    # the published model's widths
    real = ZayaConfig(vocab=262272, seq_len=8192, d_model=2048, n_layers=20,
                      n_heads=8, n_kv_heads=2, head_dim=128, n_experts=16,
                      d_expert=2048, d_router=256)
    assert (real.d_query, real.d_kv, real.d_conv, real.rotated) == (
        1024, 256, 1280, 64)


@pytest.mark.parametrize("kw,match", [
    ({"n_kv_heads": 1}, "n_kv_heads"),
    ({"n_kv_heads": 3}, "n_kv_heads"),
    ({"conv0": 1}, "taps"),
    ({"conv1": 1}, "taps"),
    ({"rotary_fraction": 0.3}, "even number of lanes"),
    ({"rotary_fraction": 1.5}, "even number of lanes"),
])
def test_config_refuses_shapes_it_cannot_run(kw, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **kw)


# -- the layer's lines, one by one ------------------------


def _old_rotary(x, positions, theta=10000.0):
    """``ops/layers.py::rotary`` as it stood before it took ``rotated``."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def test_rotary_default_is_the_whole_head_bit_for_bit():
    x = jax.random.normal(jax.random.key(0), (2, 7, 3, 16))
    pos = jnp.broadcast_to(jnp.arange(7) * 13, (2, 7))
    want = np.asarray(_old_rotary(x, pos, 1e6))
    assert np.array_equal(np.asarray(rotary(x, pos, 1e6)), want)
    assert np.array_equal(np.asarray(rotary(x, pos, 1e6, rotated=16)), want)
    for bad in (0, 3, 18):
        with pytest.raises(ValueError, match="even number"):
            rotary(x, pos, 1e6, rotated=bad)


def test_partial_rotary_turns_the_first_lanes_and_passes_the_rest():
    """By hand: 8 lanes of which 4 turn, lane ``i`` with lane ``i + 2``, at
    angle ``t * theta ** (-i / 2)``."""
    theta, pos = 100.0, np.array([0, 1, 5])
    x = np.asarray(jax.random.normal(jax.random.key(1), (3, 1, 8)))
    want = x.copy()
    for t, p in enumerate(pos):
        for i in range(2):
            ang = p * theta ** (-i / 2)
            a, b = x[t, 0, i], x[t, 0, i + 2]
            want[t, 0, i] = a * np.cos(ang) - b * np.sin(ang)
            want[t, 0, i + 2] = b * np.cos(ang) + a * np.sin(ang)
    got = np.asarray(rotary(jnp.asarray(x), jnp.asarray(pos), theta,
                            rotated=4))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert np.array_equal(got[..., 4:], x[..., 4:])
    assert np.array_equal(got[0], x[0])                   # position 0


def test_value_shift_reads_zero_before_position_0(stages):
    """The second half of the K/V heads holds the token BEFORE's value: at
    position 0 zeros, at ``t`` what ``u_{t-1} W_v2`` gives."""
    ap = stages[0].params["blocks"][0]["attn"]
    u = jax.random.normal(jax.random.key(2), (1, 5, CFG.d_model))
    pos = jnp.arange(5)[None]
    _, _, v, tails = zaya._cca_mix(ap, u, zaya._zero_tails(CFG, 1), pos, CFG)
    both = np.asarray(matmul_acc32(u, ap["wv"]))[0]       # [5, 32]
    v = np.asarray(v)[0].reshape(5, 32)
    assert np.array_equal(v[:, :16], both[:, :16])        # head 0: current
    assert np.array_equal(v[0, 16:], np.zeros(16, np.float32))
    assert np.array_equal(v[1:, 16:], both[:-1, 16:])     # head 1: previous
    assert np.array_equal(np.asarray(tails[2])[0], both[-1, 16:])


def test_the_routers_state_is_carried_from_layer_to_layer(stages):
    """``r_l = u W + b + gamma_l * r_{l-1}``: layer 1's scores move with
    layer 0's state, a zeroed state is the first layer's formula, and the
    model's logits depend on the carry."""
    blocks = stages[0].params["blocks"]
    rp0, rp1 = blocks[0]["moe"]["router"], blocks[1]["moe"]["router"]
    u = jax.random.normal(jax.random.key(3), (6, CFG.d_model))
    _, r0 = zaya._router_scores(rp0, u, None, CFG)
    carried, r1 = zaya._router_scores(rp1, u, r0, CFG)
    zeroed, _ = zaya._router_scores(rp1, u, jnp.zeros_like(r0), CFG)
    alone, own = zaya._router_scores(rp1, u, None, CFG)
    assert np.array_equal(np.asarray(zeroed), np.asarray(alone))
    assert np.abs(np.asarray(carried) - np.asarray(alone)).max() > 1e-3
    np.testing.assert_allclose(np.asarray(r1), np.asarray(
        own + rp1["gamma"] * r0), rtol=1e-6, atol=1e-6)
    # through the whole model: no carry (gamma 0 everywhere) moves the logits
    def no_gamma(path, a):
        return jnp.zeros_like(a) if path[-1].key == "gamma" else a

    cut = jax.tree_util.tree_map_with_path(no_gamma, stages[0].params)
    tokens = jnp.asarray(_prompt(4, 12))[None]
    with_carry = zaya.full_logits(stages[0].params, tokens, CFG)
    without = zaya.full_logits(cut, tokens, CFG)
    assert np.abs(np.asarray(with_carry - without)).max() > 1e-3


def test_top_1_weight_is_the_probability_and_bias_steers_the_choice_alone():
    scores = jnp.asarray([[2.0, 1.0, 0.5, -1.0], [0.1, 0.2, 0.3, 0.25]])
    p = np.asarray(jax.nn.softmax(scores, -1))
    w, ids = moe_experts.softmax_top_1(jnp.zeros(4))(scores, 1)
    assert ids.tolist() == [[0], [2]] and ids.dtype == jnp.int32
    assert np.array_equal(np.asarray(w)[:, 0], p[[0, 1], [0, 2]])
    # a bias towards expert 3: the choice moves, the weight is that expert's
    # own probability, neither renormalised nor shifted by the bias
    w, ids = moe_experts.softmax_top_1(jnp.asarray([0., 0., 0., 1.]))(
        scores, 1)
    assert ids.tolist() == [[3], [3]]
    assert np.array_equal(np.asarray(w)[:, 0], p[:, 3])
    with pytest.raises(ValueError, match="one expert a token"):
        moe_experts.softmax_top_1(jnp.zeros(4))(scores, 2)


def test_dropless_experts_takes_ready_scores_bit_for_bit():
    """``scores=`` against the call that multiplies by ``params["router"]``
    itself, on the shapes the two families that share the layer run at toy
    size: SDAR's (softmax top 2 of 8, SwiGLU) and Nemotron-H's (sigmoid top
    3 of 8 with a bias, ``relu^2`` in a latent, a held range)."""
    k = jax.random.split(jax.random.key(5), 8)
    x = jax.random.normal(k[0], (11, 64))
    mat = lambda key, s: 0.1 * jax.random.normal(key, s)  # noqa: E731
    sdar = {"router": mat(k[1], (64, 8)), "gate": mat(k[2], (8, 64, 48)),
            "up": mat(k[3], (8, 64, 48)), "down": mat(k[4], (8, 48, 64))}
    want, rows = moe_experts.dropless_experts(sdar, x, 2)
    got, rows2 = moe_experts.dropless_experts(
        {n: sdar[n] for n in ("gate", "up", "down")}, x, 2,
        scores=matmul_acc32(x, sdar["router"]))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(rows2), np.asarray(rows))

    nemo = {"router": mat(k[1], (64, 8)), "w1": mat(k[5], (4, 32, 48)),
            "w2": mat(k[6], (4, 48, 32))}
    latent = jax.random.normal(k[7], (11, 32))
    kw = dict(route=moe_experts.sigmoid_top_k(0.2 * jnp.arange(8.0), 2.5),
              experts=moe_experts.relu2_experts, held=(2, 4), rows=latent)
    want, rows = moe_experts.dropless_experts(nemo, x, 3, **kw)
    got, rows2 = moe_experts.dropless_experts(
        {n: nemo[n] for n in ("w1", "w2")}, x, 3,
        scores=matmul_acc32(x, nemo["router"]), **kw)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(rows2), np.asarray(rows))


# -- the engine, with the logits it sampled from taken out ------------------------


@functools.cache
def _twins(kernel):
    """The two programs' forwards, jitted once for every :class:`Tap`."""
    chunk = jax.jit(lambda p, kc, vc, st, toks, p0, table, slot:
                    zaya._cca_chunk_fwd(p, kc, vc, st, toks, p0, table,
                                        slot, CFG, BS))
    step = jax.jit(lambda p, kc, vc, st, toks, pos, tables, live:
                   zaya._cca_decode_fwd(p, kc, vc, st, toks, pos, tables,
                                        live, CFG, BS, kernel))
    return chunk, step


class Tap:
    """An engine whose two programs are jitted twins of the real ones that
    also hand out the logits they chose from (greedy: ``argmax``) and the
    last layer's state of every slot after each call
    (``tests/test_jamba.py::Tap``)."""

    def __init__(self, stages, kernel="fused", **kw):
        kw = {"n_slots": 2, "max_len": 48, "block_size": BS,
              "prefill_chunk": 5, **kw}
        self.eng = InferenceEngine(stages, CFG, attn_kernel=kernel, **kw)
        self.rows = []          # (kind, {rid: slot}, logits)
        self.states = []        # (kind, the last layer's leaves, all slots)
        chunk, step = _twins(kernel)

        def chunk_prefill(p, kc, vc, st, toks, p0, table, slot, seat, kd,
                          *_):
            *st, (newest, keys) = st
            kc, vc, st, row = chunk(p, kc, vc, tuple(st), toks, p0, table,
                                    slot)
            self.rows.append(("chunk", {self.eng.pool.occupant(int(slot)):
                                        int(slot)}, np.asarray(row)))
            self.states.append(("chunk", [np.asarray(a) for a in st[-1]]))
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
            self.states.append(("decode", [np.asarray(a) for a in st[-1]]))
            toks = jnp.argmax(rows, -1).astype(jnp.int32)
            out = jnp.concatenate(
                [toks[:, None], jnp.zeros((toks.shape[0], 2), jnp.int32)], 1)
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


def _served_rows(logits, n_prompt, n_new):
    """Rows ``n_prompt - 1 .. n_prompt + n_new - 2`` of a full forward: what
    a correct server holds when it chooses each output token."""
    return logits[n_prompt - 1:n_prompt - 1 + n_new]


@pytest.mark.parametrize("kernel,chunk", [
    ("dense", 5), ("fused", 5), ("fused", 3), ("fused", 2), ("fused", 1)])
def test_chunked_prefill_then_decode_matches_the_reference(stages, kernel,
                                                           chunk):
    """13 prompt tokens in chunks of 5, 5 and a ragged 3 (of 3 and a last
    chunk of ONE; of 2; of 1: every cut the two-tap convolutions and the
    value shift reach across), then decode through pool and state, a second
    request alongside, and a third that joins mid-run in the slot the second
    leaves: every token's logits against ``full_logits`` and against the
    reference's one full forward over prompt and served tokens."""
    tap = Tap(stages, kernel, prefill_chunk=chunk)
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
        got = tap.logits_of(h)
        want = _served_rows(_ref_logits(stages[0].params, seq), len(p), n)
        np.testing.assert_allclose(got, want, **F32)
        dense = np.asarray(zaya.full_logits(
            stages[0].params, jnp.asarray(seq)[None], CFG)[0])
        np.testing.assert_allclose(got, _served_rows(dense, len(p), n),
                                   **F32)
    assert handles[2].slot_was == handles[1].slot_was    # the slot was reused


def test_slot_mid_prefill_keeps_its_state_across_decode_ticks(stages):
    """While the long prompt is between chunks, the other slot decodes: a
    decode tick must hand the prefilling slot's tails back bit for bit
    (the test above then shows the logits built on them are right)."""
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
            for x, y in zip(after_chunk, after_decode):
                assert np.array_equal(x[b.slot], y[b.slot])
                assert not np.array_equal(x[a.slot], y[a.slot])
            checked += 1
    assert checked >= 2


def test_released_slot_bound_again_starts_from_zeros(stages):
    """One slot: the second request finds the first one's tails in it, and
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


@pytest.fixture()
def small_steps(monkeypatch):
    """The chunk's attention in steps of 8 positions (two blocks), so that
    the toy's table of 48 is six steps (``tests/test_cohere2.py``'s fixture
    of the name)."""
    monkeypatch.setattr(serving_module, "ATTEND_ROWS", 8)
    _twins.cache_clear()
    yield
    _twins.cache_clear()


@pytest.mark.parametrize("dtype,tol,mean", [("float32", F32, 2e-5),
                                            ("bfloat16", BF16, 0.01)])
def test_a_chunk_walks_the_live_steps_of_its_table_alone(small_steps, dtype,
                                                         tol, mean):
    """A table of six steps and a prompt of 13 that ends inside the second:
    chunks of 5, 5 and 3 through the chunk program, each one's logits
    against the reference's full forward, over a pool of ``dtype`` in which
    ONLY the slot's four live blocks are numbers. Everything else is NaN:
    the trash block and the blocks the table's later entries name (what a
    longer occupant left there). A probability of zero times a NaN row is
    NaN, so the walk must never fetch a dead block; the dense path, which
    gathers the whole table and masks after the product, hands the NaN on
    (the decode program's ``kernel="dense"`` over the same pool)."""
    cfg = dataclasses.replace(CFG, param_dtype=dtype)
    stages = _stages(cfg)
    params = stages[0].params
    seq, live, ml = _prompt(11, 13), 4, CFG.seq_len
    table = jnp.asarray(1 + np.arange(ml // BS), jnp.int32)
    pool = tuple(jnp.full((ml // BS + 1, BS, cfg.d_kv), jnp.nan,
                          jnp.dtype(dtype)).at[1:1 + live].set(7.0)
                 for _ in range(cfg.n_layers))
    serving = cfg.paged_serving(stages, ml, BS, dtype)
    *state, _ = jax.tree.map(lambda sd: jnp.zeros((1, *sd.shape), sd.dtype),
                             tuple(serving.state_shapes))
    chunk = jax.jit(functools.partial(zaya._cca_chunk_fwd, cfg=cfg, bs=BS))
    kc, vc, state = pool, pool, tuple(state)
    want = _ref_logits(params, seq, cfg)
    for p0, c in ((0, 5), (5, 5), (10, 3)):
        kc, vc, state, row = chunk([params], kc, vc, state,
                                   jnp.asarray(seq[None, p0:p0 + c]), p0,
                                   table, 0)
        np.testing.assert_allclose(np.asarray(row), want[p0 + c - 1], **tol)
        assert np.abs(np.asarray(row) - want[p0 + c - 1]).mean() < mean
    # the rows the chunks wrote are numbers, nothing else became one
    for buf in kc + vc:
        held = np.isfinite(np.asarray(buf, np.float32)).all(axis=(1, 2))
        assert held.tolist() == [False] + [True] * live + [False] * (
            ml // BS - live)
    step = jax.jit(functools.partial(zaya._cca_decode_fwd, cfg=cfg, bs=BS,
                                     kernel="dense"))
    *_, rows, _ = step([params], kc, vc, state, jnp.asarray(seq[-1:]),
                       jnp.asarray([len(seq)]), table[None],
                       jnp.asarray([True]))
    assert np.isnan(np.asarray(rows)).all()


def test_a_slot_bound_again_reads_no_row_its_longer_occupant_left(
        stages, small_steps):
    """One slot: a request of 36 positions fills nine blocks and leaves;
    every block of the pool is then set to NaN (none is referenced), and a
    prompt of 12 in three chunks of one whole block, which ends inside the
    table's second step of six, must serve the logits a fresh pool serves,
    bit for bit: it fetches its own three blocks and no other."""
    second = _prompt(13, 12)
    used = Tap(stages, n_slots=1, prefill_chunk=BS)
    _run(used, [used.eng.submit(_prompt(12, 30), 6)])
    pool = used.eng.pool
    pool.kc, pool.vc = (tuple(jnp.full_like(b, jnp.nan) for b in bufs)
                        for bufs in (pool.kc, pool.vc))
    used.rows.clear()
    h_used, = _run(used, [used.eng.submit(second, 1)])
    fresh = Tap(stages, n_slots=1, prefill_chunk=BS)
    h_fresh, = _run(fresh, [fresh.eng.submit(second, 1)])
    got = used.logits_of(h_used)
    assert np.isfinite(got).all() and len(used.rows) == 3
    assert np.array_equal(got, fresh.logits_of(h_fresh))
    np.testing.assert_allclose(
        got[0], _ref_logits(stages[0].params, second)[-1], **F32)


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


# -- the tick's counters ------------------------


def test_a_decode_tick_hands_its_counters_and_positions_to_the_span(stages):
    """``PagedServing.counters`` and the engine's own ``kv_positions``: the
    counts ride the tokens the engine reads a tick late and land on the
    tick that read them; a tick that ran no decode reads 0."""
    eng = _engine(stages)
    mark = len(tracing.current().spans())
    prompts = [_prompt(20 + i, 6 + i) for i in range(2)]
    hs = [eng.submit(p, 7) for p in prompts]
    eng.drain()
    ticks = [s for s in tracing.current().spans()[mark:]
             if s.name == "engine.tick"]
    decoded = [t.attrs for t in ticks if t.attrs["decoding"]]
    assert decoded and all(
        {*EXPERT_COUNTERS, "kv_positions"} <= set(t.attrs) for t in ticks)
    for a in decoded:
        # every slot's row runs, live or not: 2 rows a layer, one expert each
        assert CFG.n_layers <= a["experts_hit"] <= 2 * CFG.n_layers
        assert 1 <= a["expert_rows_max"] <= 2
        # the slots' lengths, the row the step writes included
        assert a["decoding"] * 7 <= a["kv_positions"] <= sum(
            len(p) + 7 for p in prompts)
    assert all(t.attrs["experts_hit"] == 0 and t.attrs["kv_positions"] == 0
               for t in ticks if not t.attrs["decoding"])
    # alone, the first request reads its prompt and one more row a tick;
    # with both decoding the lengths grow by 2 a tick
    assert decoded[0]["kv_positions"] == len(prompts[0]) + 1
    both = [a["kv_positions"] for a in decoded if a["decoding"] == 2]
    assert len(both) > 2 and all(y - x == 2 for x, y in zip(both, both[1:]))
    assert sum(a["ahead"] for a in decoded) >= len(decoded) - 2
    assert sum(t.attrs["emitted"] for t in ticks) == sum(
        len(h.tokens) for h in hs)


def test_gpt_ticks_carry_kv_positions_too():
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
    ticks = [s.attrs for s in tracing.current().spans()[mark:]
             if s.name == "engine.tick"]
    assert [t["kv_positions"] for t in ticks if t["decoding"]] == [7, 8]
    assert all(t["kv_positions"] == 0 for t in ticks if not t["decoding"])


# -- what is refused, by name ------------------------


@pytest.mark.parametrize("kw,match", [
    ({"host_cache_blocks": 4}, "host_cache_blocks is not available"),
    ({"lint": True}, "lint=True is not available"),
    ({"cache_dtype": "int8"}, "quantized cache_dtype is not available"),
    ({"adapters": "store"}, "adapters is not available"),
    ({"mesh": "mesh"}, "mesh .tensor-parallel serving. is not available"),
    ({"draft": True}, "draft_stages .speculative decoding. is not available"),
])
def test_what_was_built_for_blocks_alone_is_refused_by_name(stages, kw,
                                                            match):
    if "adapters" in kw:
        from simple_distributed_machine_learning_tpu.serve.adapters import (
            AdapterStore,
        )
        from simple_distributed_machine_learning_tpu.models.gpt import (
            GPTConfig,
        )
        kw = {"adapters": AdapterStore(
            GPTConfig(vocab=97, seq_len=48, d_model=64, n_heads=4,
                      n_layers=3), rank=2, n_slots=2)}
    if "mesh" in kw:
        with pytest.raises(ValueError, match=match + ".*recurrent state"):
            CFG.paged_serving(stages, 48, BS, mesh=object())
        return
    if "draft" in kw:
        kw = {"draft_stages": stages, "draft_cfg": CFG, "spec_k": 2}
    with pytest.raises(ValueError, match=match + ".*recurrent state"):
        _engine(stages, **kw)


# -- the supervisor ------------------------


def test_degraded_rebuild_serves_the_family(tmp_path):
    """A supervised deployment with ``degrade_after`` set: the degraded
    rebuild constructs (the fallback keeps the paged pool, where the
    convolution state lives, and takes the dense kernel) and every request
    finishes bit-exact with the uncrashed run."""
    from simple_distributed_machine_learning_tpu.resilience import faults
    from simple_distributed_machine_learning_tpu.serve.request import DONE
    from simple_distributed_machine_learning_tpu.serve.supervisor import (
        ServeSupervisor,
        engine_factory,
    )
    stages = make_zaya_stages(jax.random.key(0), CFG)[0]

    def run(name, chaos):
        if chaos:
            faults.install(faults.FaultPlan.parse(chaos))
        try:
            sup = ServeSupervisor(
                engine_factory(stages, CFG, n_slots=2, max_len=48,
                               block_size=4, prefill_chunk=5,
                               attn_kernel="fused"),
                str(tmp_path / name), degrade_after=1, max_restarts=2)
            rng = np.random.default_rng(7)
            handles = [sup.submit(
                rng.integers(0, CFG.vocab, n).astype(np.int32),
                max_new_tokens=m, seed=70 + n)
                for n, m in ((5, 7), (9, 6), (3, 8))]
            sup.drain()
            sup.close()
        finally:
            faults.uninstall()
        return sup, [list(h.tokens) for h in handles]

    _, base = run("zbase.jsonl", None)
    sup, deg = run("zcrash.jsonl", "engine-crash@serve.tick=3")
    assert sup.restarts == 1 and sup.degraded
    assert sup.engine.pool.recurrent and sup.engine.attn_kernel == "dense"
    assert all(r.state == DONE for r in sup.requests.values())
    assert deg == base
