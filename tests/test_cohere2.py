"""The Cohere2 mixture family (``models/cohere2.py``: window and full
attention layers in ONE paged pool, a parallel attention + expert block
under one bias-free LayerNorm, sigmoid top-k routed experts of which the
build holds a share beside averaged shared ones) at toy size on the CPU:
the stage and the serving engine against the dense-math forward
(``full_logits``) and the plain reference
(``bench_cells/reference/cohere2.py``: float32, ``highest``, the window a
mask built from positions, every held expert over every row under a mask,
four... here two separate shared experts, no kernel, cache or batching), on
seeded random weights. Logits are compared, not tokens.

The toy: 4 layers ``WWWF``, hidden 64, 8 query heads over 2 K/V heads of 16,
window 8, block 4, chunk 6, 8 experts top 2 of which 4 are held, 2 shared
experts, 97 held rows.

Tolerances, each with its reason:

- ``F32`` (float32 weights, float32 cache): program and reference compute
  the same float32 expressions and differ in the order of the sums (blocked
  matmuls, the softmax over gathered spans, the grouped expert products)
  through 4 layers; logits here are of order 1-10 and the observed gap is
  under 3e-5: 2e-4 absolute and relative. A bfloat16 pass anywhere, an int8
  operand, or a window layer run as a full one moves the logits by 1e-2 and
  more and fails this.
- bfloat16 weights (the published dtype): the program rounds every matmul's
  activations to bfloat16 where the reference keeps them float32 over the
  same rounded weights, and a rounded score can flip one of a token's two
  experts, which moves that token's logits by a whole expert's output (2.6
  here, of logits up to 6): so the MEAN is held, under 0.08 (observed 0.043,
  0.011 and 0.014 over three seeds of weights), where the reference in int8
  operands over the same weights reads 0.15.
- Runs of the SAME compiled program on the same numbers are compared bit for
  bit.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_cells.reference import cohere2 as reference

from simple_distributed_machine_learning_tpu.models import cohere2
from simple_distributed_machine_learning_tpu.models import (
    serving as serving_module,
)
from simple_distributed_machine_learning_tpu.models.cohere2 import (
    EXPERT_COUNTERS,
    Cohere2Config,
    make_cohere2_stages,
)
from simple_distributed_machine_learning_tpu.models.serving import (
    SEAT_NONE,
    SEAT_SAMPLE,
)
from simple_distributed_machine_learning_tpu.ops.layers import (
    gated_mlp,
    layer_norm,
    rotary,
)
from simple_distributed_machine_learning_tpu.serve import InferenceEngine
from simple_distributed_machine_learning_tpu.serve.slots import PagedKVPool
from simple_distributed_machine_learning_tpu.telemetry import tracing

CFG = Cohere2Config(vocab=97, seq_len=64)
WINDOW, BS, CHUNK, ML = CFG.window, 4, 6, 64
NB_FULL = ML // BS
F32 = dict(rtol=2e-4, atol=2e-4)


def _ref_kw(cfg):
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                theta=cfg.rope_theta, window=cfg.window,
                full_every=cfg.full_every, top_k=cfg.top_k,
                first_expert=cfg.expert_offset, n_shared=cfg.n_shared,
                eps=cfg.ln_eps)


def _stages(cfg=CFG, key=0):
    """The builder's stage with its matrices scaled from normal 0.02 to 0.2
    (at width 64 the published scale leaves every score near zero, the
    softmax flat, and a flat softmax forgives a wrong window) and the norm
    weights moved off 1, so that they are seen to act."""
    stages, _, _ = make_cohere2_stages(jax.random.key(key), cfg)
    dt = jnp.dtype(cfg.param_dtype)
    keys = iter(jax.random.split(jax.random.key(100 + key), 256))

    def moved(a):
        if a.ndim >= 2:
            return (10 * a.astype(jnp.float32)).astype(dt)
        return (a.astype(jnp.float32) + 0.2 * jax.random.normal(
            next(keys), a.shape)).astype(dt)

    return [dataclasses.replace(
        stages[0], params=jax.tree.map(moved, stages[0].params))]


@pytest.fixture(scope="module")
def stages():
    return _stages()


def _ref_logits(params, seq, cfg=CFG, quant=None, **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.full_logits(
            params, jnp.asarray(seq, jnp.int32), quant=quant,
            **{**_ref_kw(cfg), **kw}))


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab, n).astype(
        np.int32)


def _lanes_held(dh=CFG.head_dim):
    """A head's lanes as the serving programs hold them: even, then odd."""
    return np.concatenate([np.arange(0, dh, 2), np.arange(1, dh, 2)])


# -- the stage ------------------------


@pytest.mark.parametrize("dtype,tol,mean", [
    ("float32", F32, 2e-5), ("bfloat16", dict(rtol=0.0, atol=4.0), 0.08)])
def test_stage_full_sequence_logits_match_the_reference(dtype, tol, mean):
    """48 positions, six windows deep, through ``full_logits``."""
    cfg = dataclasses.replace(CFG, param_dtype=dtype)
    params = _stages(cfg)[0].params
    seq = _prompt(1, 48)
    got = np.asarray(cohere2.full_logits(params, jnp.asarray(seq)[None],
                                         cfg)[0])
    want = _ref_logits(params, seq, cfg)
    np.testing.assert_allclose(got, want, **tol)
    assert np.abs(got - want).mean() < mean
    # the nearest precision below misses the same limit
    assert np.abs(_ref_logits(params, seq, cfg, quant="int8")
                  - want).mean() > 1.5 * mean


def test_a_lower_precision_or_a_window_run_as_full_fails_the_tolerances(
        stages):
    """What the tolerances are for: the reference in int8 operands, the
    program's float32 weights rounded to bfloat16, and the reference with
    every window layer attending the whole past each miss ``F32``."""
    params = stages[0].params
    seq = _prompt(1, 48)
    want = _ref_logits(params, seq)
    for other in (
            _ref_logits(params, seq, quant="int8"),
            _ref_logits(jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(
                jnp.float32), params), seq),
            _ref_logits(params, seq, window=10 ** 6)):
        assert np.abs(other - want).max() > 50 * F32["atol"]
        with pytest.raises(AssertionError):
            np.testing.assert_allclose(other, want, **F32)


def _qkv_of_pr44(ap, u, positions, window, cfg):
    """``models/cohere2.py::_qkv`` as it stood before the serving programs
    got a tree of their own (PR 45)."""
    from simple_distributed_machine_learning_tpu.ops.layers import (
        matmul_acc32,
    )
    n, n_tok, _ = u.shape
    dh = cfg.head_dim
    q = matmul_acc32(u, ap["wq"]).reshape(n, n_tok, cfg.n_heads, dh)
    k = matmul_acc32(u, ap["wk"]).reshape(n, n_tok, cfg.n_kv_heads, dh)
    v = matmul_acc32(u, ap["wv"]).reshape(n, n_tok, cfg.n_kv_heads, dh)
    if window is not None:
        q = rotary(q, positions, cfg.rope_theta, interleaved=True)
        k = rotary(k, positions, cfg.rope_theta, interleaved=True)
    return q, k, v


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_full_logits_on_the_stages_tree_is_bit_for_bit_what_it_was(
        dtype, monkeypatch):
    """The whole-sequence path (``full_logits``, ``Stage.apply``) reads the
    stage's own tree and keeps the neighbouring-lane rotary: the layout the
    serving programs read is theirs alone."""
    cfg = dataclasses.replace(CFG, param_dtype=dtype)
    stage = _stages(cfg)[0]
    toks = jnp.asarray(np.stack([_prompt(1, 40), _prompt(2, 40)]))
    now = np.asarray(cohere2.full_logits(stage.params, toks, cfg))
    applied = np.asarray(stage.apply(stage.params, jnp.pad(
        toks, ((0, 0), (0, cfg.seq_len - 40))), None, True))
    monkeypatch.setattr(cohere2, "_qkv", _qkv_of_pr44)
    np.testing.assert_array_equal(
        now, np.asarray(cohere2.full_logits(stage.params, toks, cfg)))
    np.testing.assert_array_equal(applied, np.asarray(stage.apply(
        stage.params, jnp.pad(toks, ((0, 0), (0, cfg.seq_len - 40))), None,
        True)))


def test_more_than_one_stage_is_refused():
    with pytest.raises(ValueError, match="builds one stage"):
        make_cohere2_stages(jax.random.key(0), CFG, n_stages=2)


@pytest.mark.parametrize("kw,match", [
    ({"n_kv_heads": 3}, "must divide n_heads"),
    ({"experts_held": 5, "expert_offset": 4}, "held experts"),
    ({"top_k": 9}, "held experts"),
    ({"window": 0}, "must be >= 1")])
def test_config_refuses_shapes_it_cannot_run(kw, match):
    with pytest.raises(ValueError, match=match):
        Cohere2Config(**kw)


def test_cache_layout_two_kinds_of_layer_in_one_pool(stages):
    serving = CFG.paged_serving(stages, ML, BS)
    assert serving.windows == (8, 8, 8, None) == CFG.windows
    assert (serving.kv_layers, serving.kv_heads, serving.head_dim) == (4, 2,
                                                                       16)
    assert serving.counters == EXPERT_COUNTERS
    assert len(serving.state_shapes) == 1 and not CFG.recurrent_state
    eng = _engine(stages)
    ring = -(-(WINDOW + CHUNK) // BS) + 1            # 5 blocks of 4
    g, = eng.pool.window_groups
    assert (g.window, g.layers, g.ring, g.n_blocks) == (8, (0, 1, 2), ring,
                                                        2 * ring)
    # no window layer's buffer is sized by max_len
    assert [k.shape for k in eng.pool.kc] == [
        (2 * ring + 1, BS, 32)] * 3 + [(2 * NB_FULL + 1, BS, 32)]
    assert eng.pool.table_width == NB_FULL + ring
    assert eng.pool.device_table(0).shape == (NB_FULL + ring,)
    assert eng.pool.device_table(0, 0).shape == (NB_FULL,)
    assert eng.pool.device_table(0, 1).shape == (ring,)


# -- the layer's lines, one by one ------------------------


def _old_rotary(x, positions, theta=10000.0):
    """``ops/layers.py::rotary`` as it stood before ``interleaved=``."""
    dh = x.shape[-1]
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


@pytest.mark.parametrize("shape,theta", [
    ((2, 9, 4, 16), 1e6),          # sdar's toy: 4 heads of 16
    ((1, 13, 2, 16), 5e6)])        # zaya's toy K/V heads
def test_rotary_default_is_what_it_was_bit_for_bit(shape, theta):
    x = jax.random.normal(jax.random.key(0), shape)
    pos = jnp.broadcast_to(jnp.arange(shape[1]) * 3, shape[:2])
    np.testing.assert_array_equal(np.asarray(rotary(x, pos, theta)),
                                  np.asarray(_old_rotary(x, pos, theta)))
    np.testing.assert_array_equal(
        np.asarray(rotary(x, pos, theta, interleaved=False)),
        np.asarray(_old_rotary(x, pos, theta)))


def test_interleaved_rotary_pairs_neighbouring_lanes_by_hand():
    """Pair ``i`` is lanes ``(2i, 2i + 1)`` at angle ``t theta^(-2i /
    dh)``: counted with Python floats at one position, and against the
    rotate-half form over the de-interleaved lanes."""
    dh, theta, t = 8, 50000.0, 5
    x = np.arange(1.0, dh + 1, dtype=np.float32)
    want = np.empty(dh)
    for i in range(dh // 2):
        a = t * theta ** (-2 * i / dh)
        want[2 * i] = x[2 * i] * np.cos(a) - x[2 * i + 1] * np.sin(a)
        want[2 * i + 1] = x[2 * i + 1] * np.cos(a) + x[2 * i] * np.sin(a)
    got = rotary(jnp.asarray(x)[None, None], jnp.asarray([t]), theta,
                 interleaved=True)[0, 0]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    y = jax.random.normal(jax.random.key(1), (3, 2, dh))
    pos = jnp.asarray([0, 4, 9])
    halves = jnp.concatenate([y[..., 0::2], y[..., 1::2]], axis=-1)
    turned = rotary(halves, pos, theta)
    back = jnp.stack([turned[..., :dh // 2], turned[..., dh // 2:]],
                     axis=-1).reshape(3, 2, dh)
    np.testing.assert_allclose(
        np.asarray(rotary(y, pos, theta, interleaved=True)),
        np.asarray(back), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dh", [16, 128])
def test_default_rotary_over_even_lanes_first_is_the_neighbouring_form(dh):
    """What the serving programs lean on (``cohere2.serve_params``): with a
    head's lanes in the order ``[0, 2, .., dh - 2, 1, 3, .., dh - 1]`` the
    neighbouring pair ``(2i, 2i + 1)`` is the pair ``(i, i + dh / 2)``, so
    the default ``rotary`` over the permuted lanes is ``rotary(...,
    interleaved=True)`` over the published ones, permuted the same way: by
    hand with Python floats at one position, and bit for bit over random
    heads (each lane is the same two products and their sum)."""
    theta, t = 50000.0, 7
    order = _lanes_held(dh)
    x = np.linspace(-1.0, 2.0, dh).astype(np.float32)
    want = np.empty(dh)
    for i in range(dh // 2):
        a = t * theta ** (-2 * i / dh)
        want[2 * i] = x[2 * i] * np.cos(a) - x[2 * i + 1] * np.sin(a)
        want[2 * i + 1] = x[2 * i + 1] * np.cos(a) + x[2 * i] * np.sin(a)
    got = rotary(jnp.asarray(x[order])[None, None], jnp.asarray([t]),
                 theta)[0, 0]
    np.testing.assert_allclose(np.asarray(got), want[order], rtol=1e-5,
                               atol=1e-5)
    y = jax.random.normal(jax.random.key(dh), (2, 5, 3, dh))
    pos = jnp.asarray([[0, 1, 4, 4095, 32767], [3, 9, 27, 81, 243]])
    np.testing.assert_array_equal(
        np.asarray(rotary(y[..., order], pos, theta)),
        np.asarray(rotary(y, pos, theta, interleaved=True)[..., order]))


def test_layer_norm_without_a_bias_is_the_formula():
    x = jax.random.normal(jax.random.key(2), (5, 64)) * 3 + 1
    g = jax.random.normal(jax.random.key(3), (64,))
    mu = x.mean(-1, keepdims=True)
    want = (x - mu) / jnp.sqrt(((x - mu) ** 2).mean(-1, keepdims=True)
                               + 1e-5) * g
    np.testing.assert_allclose(np.asarray(layer_norm({"scale": g}, x)),
                               np.asarray(want), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(layer_norm({"scale": g, "bias": g}, x)),
        np.asarray(want + g), rtol=1e-5, atol=1e-6)


def test_the_block_is_parallel_the_expert_part_never_sees_attention(
        stages, monkeypatch):
    """Zeroing the attention's output projection leaves every layer-0 input
    of the expert part as it was (it reads ``norm(h)``, not ``norm(h +
    Attn)``); layer 1's then differs, as it must."""
    seen = []
    real = cohere2._ffn

    def spy(bp, u, cfg):
        seen.append(np.asarray(u))
        return real(bp, u, cfg)

    monkeypatch.setattr(cohere2, "_ffn", spy)
    params = stages[0].params
    toks = jnp.asarray(_prompt(2, 12))[None]
    cohere2.full_logits(params, toks, CFG)
    with_attn, seen[:] = list(seen), []
    zeroed = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 0 if path[-1].key == "wo" else a, params)
    cohere2.full_logits(zeroed, toks, CFG)
    np.testing.assert_array_equal(with_attn[0], seen[0])
    h0 = params["embed"]["tok"][toks].astype(jnp.float32)
    np.testing.assert_array_equal(
        seen[0], np.asarray(cohere2._norm(params["blocks"][0]["norm"], h0,
                                          CFG)))
    assert np.abs(with_attn[1] - seen[1]).max() > 1e-3


def test_shared_experts_averaged_are_the_stacked_product_over_their_number(
        stages):
    sp = stages[0].params["blocks"][1]["shared"]
    f = CFG.d_expert
    u = jax.random.normal(jax.random.key(4), (7, CFG.d_model))
    each = [gated_mlp({"gate": sp["gate"][:, i * f:(i + 1) * f],
                       "up": sp["up"][:, i * f:(i + 1) * f],
                       "down": sp["down"][i * f:(i + 1) * f]}, u)
            for i in range(CFG.n_shared)]
    np.testing.assert_allclose(
        np.asarray(gated_mlp(sp, u) / CFG.n_shared),
        np.asarray(sum(each) / CFG.n_shared), rtol=1e-5, atol=1e-5)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Two builds that hold experts 0-3 and 4-7 of the SAME layer: their
    routed sums, with the shared average counted once, are the uncut
    reference's expert part."""
    whole = dataclasses.replace(CFG, experts_held=8)
    bp = _stages(whole)[0].params["blocks"][0]
    u = jax.random.normal(jax.random.key(5), (1, 11, CFG.d_model))
    with jax.default_matmul_precision("highest"):
        want = reference.expert_part(bp, u[0], CFG.top_k, 0, CFG.n_shared,
                                     None)
        shared = gated_mlp(bp["shared"], u[0]) / CFG.n_shared
        halves = []
        for first in (0, 4):
            cut = dataclasses.replace(CFG, expert_offset=first)
            part = dict(bp, moe={
                k: (v if k == "router" else v[first:first + 4])
                for k, v in bp["moe"].items()})
            y, rows = cohere2._ffn(part, u, cut)
            halves.append(y[0] - shared)
            assert rows.shape == (4,)
        np.testing.assert_allclose(
            np.asarray(halves[0] + halves[1] + shared), np.asarray(want),
            **F32)
        # and a half alone is not the layer
        assert np.abs(np.asarray(halves[0] + shared - want)).max() > 1e-2


# -- serving: chunks then decode through the pool ------------------------


@functools.cache
def _twins(kernel, cfg=CFG):
    chunk = jax.jit(functools.partial(
        cohere2._window_chunk_fwd, cfg=cfg, bs=BS, nb_full=NB_FULL))
    step = jax.jit(functools.partial(
        cohere2._window_decode_fwd, cfg=cfg, bs=BS, nb_full=NB_FULL,
        kernel=kernel))
    return chunk, step


class Tap:
    """An engine whose two programs are jitted twins of the real ones that
    also hand out the logits they chose from (greedy: ``argmax``)
    (``tests/test_zaya.py::Tap``), and what the pool's groups held after
    every tick."""

    def __init__(self, stages, kernel="fused", cfg=CFG, **kw):
        kw = {"n_slots": 2, "max_len": ML, "block_size": BS,
              "prefill_chunk": CHUNK, **kw}
        self.eng = InferenceEngine(stages, cfg, attn_kernel=kernel, **kw)
        self.rows = []          # (kind, {rid: slot}, logits)
        chunk, step = _twins(kernel, cfg)

        def chunk_prefill(p, kc, vc, st, toks, p0, table, slot, seat, kd,
                          *_):
            (newest, keys), = st
            kc, vc, row = chunk(p, kc, vc, toks, p0, table)
            self.rows.append(("chunk", {self.eng.pool.occupant(int(slot)):
                                        int(slot)}, np.asarray(row)))
            tok = jnp.argmax(row).astype(jnp.int32)
            if seat != SEAT_NONE:
                newest = newest.at[int(slot)].set(
                    tok if seat == SEAT_SAMPLE else int(seat))
            return kc, vc, ((newest, keys),), tok, jnp.asarray(kd)

        def decode(p, kc, vc, st, _toks, pos, tables, live, kd, *_):
            (newest, keys), = st
            kc, vc, rows, _counts = step(p, kc, vc, newest, pos, tables,
                                             live)
            self.rows.append(("decode", {self.eng.pool.occupant(int(s_)):
                                         int(s_) for s_ in
                                         np.flatnonzero(live)},
                              np.asarray(rows)))
            toks = jnp.argmax(rows, -1).astype(jnp.int32)
            out = jnp.concatenate(
                [toks[:, None], jnp.zeros((toks.shape[0], 3), jnp.int32)], 1)
            return (kc, vc, ((jnp.where(live, toks, newest), keys),), out,
                    jnp.asarray(kd))

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


def _run(tap, handles, ticks=10 ** 6):
    for h in handles:
        if h.slot is not None:
            h.slot_was = h.slot
    while tap.eng.busy and ticks:
        tap.eng.step()
        ticks -= 1
        for h in handles:
            if h.slot is not None:
                h.slot_was = h.slot
    return handles


def _served_rows(logits, n_prompt, n_new):
    """Rows ``n_prompt - 1 .. n_prompt + n_new - 2`` of a full forward: what
    a correct server holds when it chooses each output token."""
    return logits[n_prompt - 1:n_prompt - 1 + n_new]


#: sha256 of ``str(jax.make_jaxpr(...))`` of the family's two programs, made
#: on the PARENT commit (0eac28c, PR 45), where ``span_attention`` lay in
#: ``models/cohere2.py`` and took the config, by the same lines as the test
#: below: (size, kernel, program) -> what the program has to trace
_PARENT_JAXPR = {
    ("toy", "dense", "decode"):
        "1a4564248431bca5499fccde8223a0764899540cb67c51f1e15c35e6e2f7af3e",
    ("toy", "fused", "decode"):
        "38f0e44b94b2d01c7dd16481ff4ed24234b8edf6c730f9c3c9d696663c29ffbc",
    ("toy", "fused", "chunk"):
        "e9d79fb10d00895f1db583428bbc8e9b4d16f35a43f7313ff734ba6b298df4b6",
    # command-a-plus-05-2026.serve-mixed-closed's shapes
    ("cell", "dense", "decode"):
        "65a6c67da72a608f99944fcbdbecf489029bc6fcd9c6d557c2c52e5e0e3cf10e",
    ("cell", "fused", "decode"):
        "89c6bc7d203b76c857b1159843cf540b33ba24511c299b22e171e14adac4d1a7",
    ("cell", "fused", "chunk"):
        "2c75de672f0469747d159ad236870ce2f3adb0ebdec4276862ceda57dbdda95a",
}

#: the toy's build in ``test_chip_compile._window_programs``'s terms (slots,
#: max_len, block, full blocks, window blocks, chunk); the cell's are its own
_TOY_SIZES = (2, ML, BS, 2 * NB_FULL, 10, CHUNK)


@pytest.mark.parametrize("size,kernel,program", list(_PARENT_JAXPR))
def test_the_window_programs_trace_what_they_traced_before_the_move(
        monkeypatch, size, kernel, program):
    """``command-a-plus-05-2026.serve-mixed-closed`` rides on these two
    programs: with the chunk's attention lifted to ``models/serving.py::
    span_attention`` (PR 46: the long-context family's chunk calls it too,
    and it takes the K/V head count in place of the config) each program's
    jaxpr is the parent commit's to the letter, at the toy's size and at
    the cell's shapes (shapes alone: nothing is allocated). The decode
    program calls it under ``kernel="dense"``; a step of another length is
    another trace."""
    import hashlib
    import re

    from test_chip_compile import _window_programs

    bs = BS if size == "toy" else 16

    def text():
        kw = dict(cfg=CFG, sizes=_TOY_SIZES,
                  pool_dtype="float32") if size == "toy" else {}
        fn, args = _window_programs(kernel=kernel, **kw)[1][
            "window-" + program]
        return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))

    # programs built anew each time: a memoized one keeps its first trace
    monkeypatch.setattr(serving_module, "_DECODE_BUILD_CACHE", {})
    plain = text()
    assert hashlib.sha256(plain.encode()).hexdigest() == _PARENT_JAXPR[
        size, kernel, program]
    if (kernel, program) != ("fused", "decode"):
        monkeypatch.setattr(serving_module, "ATTEND_ROWS", bs)
        monkeypatch.setattr(serving_module, "_DECODE_BUILD_CACHE", {})
        assert text() != plain


@pytest.fixture()
def small_steps(monkeypatch):
    """The chunk's attention in steps of 8 positions (two blocks), so that
    the toy's 40 positions are five steps and the walk is seen to start
    behind the window, not at 0."""
    monkeypatch.setattr(serving_module, "ATTEND_ROWS", 8)
    _twins.cache_clear()
    yield
    _twins.cache_clear()


@pytest.mark.parametrize("kernel,chunk", [
    ("dense", 6), ("fused", 6), ("fused", 5), ("fused", 3), ("fused", 9)])
def test_chunked_prefill_then_decode_matches_the_reference(
        stages, small_steps, kernel, chunk):
    """Lengths below, at and five times the window of 8: prompts of 5, 8
    and 31 tokens in chunks that straddle the window's edge and the ring's
    wrap (6; 5; 3; 9, longer than the window), then decode through the
    pool's two groups, the third request joining mid-run in the slot the
    first leaves and decoding to 42 positions. Every token's logits against
    ``full_logits`` and against the reference's one full forward over prompt
    and served tokens."""
    tap = Tap(stages, kernel, prefill_chunk=chunk)
    prompts = [_prompt(3, 5), _prompt(4, 8), _prompt(5, 31)]
    new = [2, 9, 11]
    handles = [tap.eng.submit(p, n) for p, n in zip(prompts[:2], new)]
    _run(tap, handles, ticks=3)
    handles.append(tap.eng.submit(prompts[2], new[2]))
    _run(tap, handles)
    for p, n, h in zip(prompts, new, handles):
        assert len(h.tokens) == n
        seq = np.concatenate([p, np.asarray(h.tokens[:-1], np.int32)])
        got = tap.logits_of(h)
        want = _served_rows(_ref_logits(stages[0].params, seq), len(p), n)
        np.testing.assert_allclose(got, want, **F32)
        dense = np.asarray(cohere2.full_logits(
            stages[0].params, jnp.asarray(seq)[None], CFG)[0])
        np.testing.assert_allclose(got, _served_rows(dense, len(p), n),
                                   **F32)
    assert handles[2].slot_was == handles[0].slot_was    # the slot was reused
    assert tap.eng.pool.window_released_total > 0


def test_with_the_window_mask_left_out_the_comparison_fails(stages,
                                                            small_steps):
    """The second control: the SAME weights served by programs whose window
    layers attend the whole past (a window wider than the slot) miss the
    reference on a request five windows long, and by far. A check that
    cannot tell a window from none guards nothing."""
    wide = dataclasses.replace(CFG, window=ML)
    tap = Tap(stages, "fused", cfg=wide)
    p = _prompt(5, 31)
    h, = _run(tap, [tap.eng.submit(p, 9)])
    seq = np.concatenate([p, np.asarray(h.tokens[:-1], np.int32)])
    want = _served_rows(_ref_logits(stages[0].params, seq), len(p), 9)
    got = tap.logits_of(h)
    assert np.abs(got - want).max() > 50 * F32["atol"]
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(got, want, **F32)
    # below the window the two are the same model
    tap = Tap(stages, "fused", cfg=wide)
    p = _prompt(3, 5)
    h, = _run(tap, [tap.eng.submit(p, 3)])
    seq = np.concatenate([p, np.asarray(h.tokens[:-1], np.int32)])
    np.testing.assert_allclose(
        tap.logits_of(h),
        _served_rows(_ref_logits(stages[0].params, seq), len(p), 3), **F32)


def test_the_windows_edge_is_inclusive_of_its_eighth_key_and_no_further(
        small_steps):
    """One window layer, served: the first output's logits (position 19)
    move when the token at ``t - 7`` changes and stay bit for bit when the
    token at ``t - 8`` does."""
    cfg = dataclasses.replace(CFG, n_layers=1, full_every=2)
    stages = _stages(cfg)

    def first_row(prompt):
        tap = Tap(stages, "fused", cfg=cfg)
        h, = _run(tap, [tap.eng.submit(prompt, 1)])
        return tap.logits_of(h)[0]

    p = _prompt(8, 20)
    base = first_row(p)
    for back, same in ((8, True), (7, False), (12, True), (0, False)):
        q = p.copy()
        q[19 - back] = (q[19 - back] + 1) % CFG.vocab
        assert np.array_equal(first_row(q), base) == same, back


def _engine(stages, **kw):
    kw = dict(dict(n_slots=2, max_len=ML, block_size=BS,
                   prefill_chunk=CHUNK, attn_kernel="fused"), **kw)
    return InferenceEngine(stages, CFG, **kw)


def test_a_window_layers_blocks_are_handed_back_and_a_full_layers_kept(
        stages):
    """Block counts by hand after each tick of ONE request (31 prompt
    tokens in chunks of 6, then 11 tokens): the full group holds
    ``ceil(rows / 4)`` blocks and never gives one back; the window group
    holds the blocks from the one with ``oldest - 7`` to the newest, where
    ``oldest`` is the first row of the last program dispatched."""
    eng = _engine(stages)
    g, = eng.pool.window_groups
    h = eng.submit(_prompt(5, 31), 11)
    seen_release = False
    while eng.busy:
        released = eng.pool.window_released_total
        eng.step()
        if h.slot is None:
            break
        r = eng.requests[h.rid]
        if r.prefill_pos is not None:           # chunks so far: [0, pos)
            newest = r.prefill_pos - 1
            oldest = max(0, r.prefill_pos - CHUNK)
        else:           # rows so far, the decode dispatched ahead's too
            newest = oldest = int(eng.pool.positions[h.slot]) + (
                eng._ahead is not None) - 1
        first = max(0, oldest - (WINDOW - 1)) // BS
        assert len(eng.pool.tables[h.slot]) == newest // BS + 1
        assert eng.pool.blocks_in_use == newest // BS + 1
        assert g.blocks_in_use == newest // BS + 1 - first
        assert g.blocks_in_use <= g.ring
        table = eng.pool.device_table(h.slot, 1)
        live = {j % g.ring for j in range(first, newest // BS + 1)}
        assert {e for e in range(g.ring) if table[e]} == live
        seen_release |= eng.pool.window_released_total > released
    assert seen_release and eng.pool.window_released_total == (
        41 - 7) // BS                  # positions 0..33's whole blocks
    assert g.blocks_in_use == 0 and eng.pool.blocks_in_use == 0
    assert sorted(g.free) == list(range(1, g.n_blocks + 1))
    assert g.reserved == 0 and eng.pool._reserved == 0


def test_a_slot_bound_again_reads_none_of_its_last_occupants_rows(stages):
    """The second request in a slot gives the logits it gives alone, bit
    for bit, after a first one that wrapped the ring several times."""
    p = _prompt(11, 9)
    alone = Tap(stages, n_slots=1)
    a, = _run(alone, [alone.eng.submit(p, 5)])
    tap = Tap(stages, n_slots=1)
    first = tap.eng.submit(_prompt(5, 31), 11)
    b = tap.eng.submit(p, 5)
    _run(tap, [first, b])
    assert b.slot_was == first.slot_was
    np.testing.assert_array_equal(tap.logits_of(b), alone.logits_of(a))


def test_admission_reserves_in_each_group_its_own_worst_case(stages):
    """``can_admit`` asks the full group for ``ceil(rows / 4)`` blocks and
    the window group for ``min(that, ring)``: with a window group of ONE
    ring, a second long request waits though the full group has room, and
    boards when the first ends."""
    ring = -(-(WINDOW + CHUNK) // BS) + 1
    eng = _engine(stages, n_window_blocks=ring)
    g, = eng.pool.window_groups
    a = eng.submit(_prompt(1, 30), 4)
    b = eng.submit(_prompt(2, 30), 4)
    eng.step()
    assert a.slot is not None and b.slot is None
    assert g.reserved + g.blocks_in_use == ring == g.budget(33, BS)
    assert eng.pool.blocks_available >= eng.pool.blocks_for(33)
    assert not eng.pool.can_admit(eng.requests[b.rid])
    short = eng.submit(_prompt(3, 3), 2)        # 4 rows: one block a group
    assert g.budget(4, BS) == 1
    eng.drain()
    assert [len(h.tokens) for h in (a, b, short)] == [4, 4, 2]
    with pytest.raises(ValueError, match="cannot hold even one"):
        _engine(stages, n_window_blocks=ring - 1)


def test_preempt_then_resume_reproduces_the_tokens(stages):
    prompts = [_prompt(9, 17), _prompt(10, 9)]
    plain = _engine(stages)
    want = [plain.submit(p, 12) for p in prompts]
    plain.drain()
    eng = _engine(stages)
    got = [eng.submit(p, 12) for p in prompts]
    while len(got[0].tokens) < 6:
        eng.step()
    eng.preempt(got[0].rid)
    eng.drain()
    assert got[0].n_preempted == 1
    assert [h.tokens for h in got] == [h.tokens for h in want]


def test_the_real_programs_serve_what_the_twins_serve(stages):
    """The packed host array, the seats and the counter row of the real
    programs against the twins that take their arguments one by one."""
    prompts = [_prompt(3, 23), _prompt(4, 6)]
    tap = Tap(stages)
    want = _run(tap, [tap.eng.submit(p, 14) for p in prompts])
    eng = _engine(stages)
    got = [eng.submit(p, 14) for p in prompts]
    eng.drain()
    assert [h.tokens for h in got] == [h.tokens for h in want]


# -- the tree the serving programs read ------------------------


def test_serve_params_moves_a_window_layers_two_projections_and_no_more(
        stages):
    """``cohere2.serve_params``: every leaf of the stage's tree is the very
    array, but a window layer's ``wq`` / ``wk``, which give way to
    ``wq_halves`` / ``wk_halves``: the same columns, every head's even lanes
    first."""
    params = stages[0].params
    held, = cohere2.serve_params([params], CFG)
    order = _lanes_held()
    for bp, hp, window in zip(params["blocks"], held["blocks"], CFG.windows):
        if window is None:
            assert hp is bp
            continue
        assert sorted(hp["attn"]) == ["wk_halves", "wo", "wq_halves", "wv"]
        for name, heads in (("wq", CFG.n_heads), ("wk", CFG.n_kv_heads)):
            cols = (np.arange(heads)[:, None] * CFG.head_dim + order).ravel()
            np.testing.assert_array_equal(
                np.asarray(hp["attn"][name + "_halves"]),
                np.asarray(bp["attn"][name])[:, cols])
        assert all(hp["attn"][k] is bp["attn"][k] for k in ("wv", "wo"))
        assert all(hp[k] is bp[k] for k in ("norm", "moe", "shared"))
    assert held["embed"] is params["embed"] and held["head"] is params["head"]


def test_a_window_layers_k_rows_lie_in_the_held_lane_order(stages,
                                                           monkeypatch):
    """What the pool holds after a prompt's chunks and a few decode steps,
    read by hand through the slot's tables: a window layer's K rows are the
    published rows (``full_logits``' own ``k`` of the same sequence) with
    every head's even lanes first, a full layer's K rows and EVERY V row
    the published rows themselves (``serve/slots.py``, "Layer kinds")."""
    eng = _engine(stages)
    h = eng.submit(_prompt(6, 7), 6)
    while len(h.tokens) < 2:
        eng.step()
    # 8 rows lie in the pool for sure (the prompt, the first token): under
    # the window of 8, nothing handed back, no ring wrapped
    seq = np.concatenate([_prompt(6, 7), np.asarray(h.tokens[:1], np.int32)])
    seen, real = [], cohere2._qkv

    def spy(*a, **kw):
        seen.append(real(*a, **kw))
        return seen[-1]

    monkeypatch.setattr(cohere2, "_qkv", spy)
    cohere2.full_logits(stages[0].params, jnp.asarray(seq)[None], CFG)
    order = _lanes_held()
    for li, ((_, k, v), window) in enumerate(zip(seen, CFG.windows)):
        table = eng.pool.device_table(h.slot, 0 if window is None else 1)
        at = (np.arange(len(seq)) // BS) % len(table), np.arange(len(seq)) % BS
        rows_k = np.asarray(eng.pool.kc[li])[table[at[0]], at[1]]
        rows_v = np.asarray(eng.pool.vc[li])[table[at[0]], at[1]]
        k, v = np.asarray(k[0]), np.asarray(v[0])       # [T, KV, dh]
        np.testing.assert_allclose(
            rows_k, (k if window is None else k[..., order]).reshape(
                len(seq), -1), **F32)
        np.testing.assert_allclose(rows_v, v.reshape(len(seq), -1), **F32)
        if window is not None:          # and the published order is not it
            assert np.abs(rows_k - k.reshape(len(seq), -1)).max() > 0.1


def test_a_tree_that_is_not_the_programs_own_is_refused_by_name(stages):
    """The programs and their layout travel together
    (``PagedServing.serve_params``): the stage's tree handed to the serving
    programs, or the programs' tree to ``full_logits``, stops at the first
    window layer with both leaf names in the message, and serves nothing."""
    eng = _engine(stages)
    assert eng.params is not None and "wq_halves" in (
        eng.params[0]["blocks"][0]["attn"])
    eng.params = [stages[0].params]
    h = eng.submit(_prompt(3, 5), 2)
    with pytest.raises(ValueError, match="'wq_halves'.*serve_params"):
        eng.step()
    assert not h.tokens
    held = cohere2.serve_params([stages[0].params], CFG)
    with pytest.raises(ValueError, match="'wq' and 'wk'.*serve_params"):
        cohere2.full_logits(held[0], jnp.asarray(_prompt(3, 5))[None], CFG)


def _toy(family):
    """``(cfg, stages, engine keywords)`` of each served family's toy."""
    from simple_distributed_machine_learning_tpu.models import (
        gpt,
        jamba,
        nemotron_h,
        sdar,
        zaya,
    )
    key = jax.random.key(0)
    kw = dict(n_slots=2, max_len=48, block_size=4, prefill_chunk=4)
    if family == "gpt":
        cfg = gpt.GPTConfig(vocab=64, seq_len=48, d_model=32, n_heads=2,
                            n_layers=2)
        return cfg, gpt.make_gpt_stages(key, cfg, 2)[0], kw
    if family == "jamba":
        cfg = jamba.JambaConfig(vocab=97, seq_len=48, d_model=64, expand=4)
        return cfg, jamba.make_jamba_stages(key, cfg)[0], kw
    if family == "sdar":
        cfg = sdar.SdarConfig()
        return cfg, sdar.make_sdar_stages(key, cfg)[0], dict(
            n_slots=2, max_len=64, block_size=8, prefill_chunk=8)
    if family == "nemotron_h":
        cfg = nemotron_h.NemotronHConfig(pattern="ME*E", n_experts=8,
                                         experts_held=4, expert_offset=4)
        return cfg, nemotron_h.make_nemotron_h_stages(key, cfg)[0], kw
    if family == "zaya":
        cfg = zaya.ZayaConfig(vocab=97, seq_len=48)
        return cfg, zaya.make_zaya_stages(key, cfg)[0], kw
    return CFG, make_cohere2_stages(key, CFG)[0], dict(kw, max_len=ML)


@pytest.mark.parametrize("family", ["gpt", "jamba", "sdar", "nemotron_h",
                                    "zaya", "cohere2"])
def test_the_engine_holds_the_stages_tree_or_the_programs_own(family):
    """``PagedServing.serve_params``: ``None`` for five families, whose
    engine holds the very trees the stages hold (no copy, no program run at
    construction) and a ``params=`` list as it was given; Cohere2's engine
    holds what ``serve_params`` makes of either, once: the full layer, the
    embedding and the head the same objects, a window layer's two
    projections alone its own."""
    cfg, stages, kw = _toy(family)
    serving = cfg.paged_serving(stages, kw["max_len"], kw["block_size"])
    eng = InferenceEngine(stages, cfg, **kw)
    given = [s.params for s in stages]
    over = InferenceEngine(stages, cfg, params=given, **kw)
    if family != "cohere2":
        assert serving.serve_params is None
        assert len(eng.params) == len(stages) and all(
            a is s.params for a, s in zip(eng.params, stages))
        assert over.params is given
        return
    own = lambda path: path[-1].key in ("wq_halves", "wk_halves")  # noqa: E731
    for e in (eng, over):
        kept = [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(
            e.params) if not own(path)]
        theirs = {id(leaf) for leaf in jax.tree.leaves(given)}
        assert all(id(leaf) in theirs for leaf in kept)
        assert len(jax.tree.leaves(e.params)) == len(theirs) == len(kept) + 6
        assert e.params[0]["blocks"][3] is given[0]["blocks"][3]
    assert eng.stages is stages and stages[0].params is given[0]


# -- the tick's counts ------------------------


def test_a_tick_says_what_its_two_layer_kinds_read_and_hold(stages):
    """``engine.tick`` carries the program's three counters and the pool's
    counts by kind; ``engine.admit`` what was handed back since the tick
    before."""
    eng = _engine(stages)
    mark = len(tracing.current().spans())
    hs = [eng.submit(_prompt(20, 21), 9), eng.submit(_prompt(21, 5), 9)]
    eng.drain()
    spans = tracing.current().spans()[mark:]
    ticks = [s.attrs for s in spans if s.name == "engine.tick"]
    admits = [s.attrs for s in spans if s.name == "engine.admit"]
    names = {*EXPERT_COUNTERS, "kv_positions", "kv_window_positions",
             "kv_window_blocks", "kv_full_blocks", "kv_blocks"}
    assert all(names <= set(t) for t in ticks)
    decoded = [t for t in ticks if t["decoding"]]
    for t in decoded:
        assert 0 < t["kv_window_positions"] <= min(
            t["kv_positions"], t["decoding"] * WINDOW)
        # the live slots' rows alone: top 2 of 8 a row and layer
        assert 0 <= t["experts_hit"] <= 4 * 4
        assert t["experts_hit"] <= t["expert_rows"] <= (
            t["decoding"] * 2 * 4)
        assert t["kv_full_blocks"] == t["kv_blocks"]
    # both decoding, long enough: 8 + 8 positions in a window layer, and
    # fewer blocks there than in the full layer
    both = [t for t in decoded if t["decoding"] == 2]
    assert len(both) > 4 and both[-1]["kv_window_positions"] == 2 * WINDOW
    assert both[-1]["kv_window_blocks"] < both[-1]["kv_full_blocks"]
    assert all(t["kv_window_positions"] == 0 and t["experts_hit"] == 0
               for t in ticks if not t["decoding"])
    assert all("window_released" in a for a in admits)
    assert sum(a["window_released"] for a in admits) > 0
    assert sum(t["emitted"] for t in ticks) == sum(len(h.tokens) for h in hs)


def test_a_pool_without_windows_says_nothing_of_them():
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
    spans = tracing.current().spans()[mark:]
    assert not any("kv_window_blocks" in s.attrs or "window_released"
                   in s.attrs for s in spans)
    assert not eng.pool.windowed and eng.pool.table_width == 8


# -- what is refused, by name ------------------------


@pytest.mark.parametrize("kw,match", [
    ({"host_cache_blocks": 4}, "host_cache_blocks is not available"),
    ({"lint": True}, "lint=True is not available"),
    ({"cache_dtype": "int8"}, "quantized cache_dtype is not available"),
    ({"adapters": "store"}, "adapters is not available"),
    ({"mesh": "mesh"}, "mesh .tensor-parallel serving. is not available"),
    ({"draft": True}, "draft_stages .speculative decoding. is not available"),
])
def test_what_was_built_for_one_layer_kind_is_refused_by_name(stages, kw,
                                                              match):
    if "adapters" in kw:
        from simple_distributed_machine_learning_tpu.serve.adapters import (
            AdapterStore,
        )
        from simple_distributed_machine_learning_tpu.models.gpt import (
            GPTConfig,
        )
        kw = {"adapters": AdapterStore(
            GPTConfig(vocab=97, seq_len=64, d_model=64, n_heads=4,
                      n_layers=4), rank=2, n_slots=2)}
    if "mesh" in kw:
        with pytest.raises(ValueError, match=match + ".*window layers"):
            CFG.paged_serving(stages, ML, BS, mesh=object())
        return
    if "draft" in kw:
        kw = {"draft_stages": stages, "draft_cfg": CFG, "spec_k": 2}
    with pytest.raises(ValueError, match=match + ".*window layers"):
        _engine(stages, **kw)


@pytest.mark.parametrize("kw,match", [
    ({"host_cache_blocks": 4}, "host_cache_blocks is not available"),
    ({"cache_dtype": "int8"}, "quantized cache_dtype is not available"),
    ({"tp": 2}, "tp > 1 is not available"),
    ({"step_rows": 4}, "step_rows > 1 .block steps. is not available")])
def test_the_pool_refuses_them_too(kw, match):
    with pytest.raises(ValueError, match=match + ".*window layers"):
        PagedKVPool(4, 2, 2, ML, 16, block_size=BS,
                    windows=(8, 8, 8, None), chunk_rows=CHUNK, **kw)


def test_a_windowed_pool_shares_no_prefix_and_counts_what_it_declines(
        stages):
    eng = _engine(stages)
    p = _prompt(30, 12)
    eng.submit(p, 3)
    eng.drain()
    assert eng.pool.shared_prefix_len(p) == 0
    eng.submit(p, 3)
    eng.drain()
    assert eng.pool.prefix_declined_total == 1
    assert eng.pool.stats()["prefix_declined_total"] == 1
    assert eng.pool.prefix_hit_blocks_total == 0


# -- the supervisor ------------------------


def test_degraded_rebuild_serves_the_family(tmp_path):
    """A supervised deployment with ``degrade_after`` set: the degraded
    rebuild constructs (the fallback keeps the paged pool and its groups
    and takes the dense kernel, ``span_attention``) and every request
    finishes bit-exact with the uncrashed run."""
    from simple_distributed_machine_learning_tpu.resilience import faults
    from simple_distributed_machine_learning_tpu.serve.request import DONE
    from simple_distributed_machine_learning_tpu.serve.supervisor import (
        ServeSupervisor,
        engine_factory,
    )
    stages = make_cohere2_stages(jax.random.key(0), CFG)[0]

    def run(name, chaos):
        if chaos:
            faults.install(faults.FaultPlan.parse(chaos))
        try:
            sup = ServeSupervisor(
                engine_factory(stages, CFG, n_slots=2, max_len=ML,
                               block_size=BS, prefill_chunk=CHUNK,
                               attn_kernel="fused"),
                str(tmp_path / name), degrade_after=1, max_restarts=2)
            rng = np.random.default_rng(7)
            handles = [sup.submit(
                rng.integers(0, CFG.vocab, n).astype(np.int32),
                max_new_tokens=m, seed=70 + n)
                for n, m in ((5, 7), (19, 16), (3, 8))]
            sup.drain()
            sup.close()
        finally:
            faults.uninstall()
        return sup, [list(h.tokens) for h in handles]

    _, base = run("cbase.jsonl", None)
    sup, deg = run("ccrash.jsonl", "engine-crash@serve.tick=3")
    assert sup.restarts == 1 and sup.degraded
    assert sup.engine.pool.windowed and sup.engine.attn_kernel == "dense"
    assert all(r.state == DONE for r in sup.requests.values())
    assert deg == base
