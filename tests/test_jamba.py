"""The Jamba hybrid family (``models/jamba.py``) at toy size on the CPU:
the stage and the serving engine against the plain reference
(``bench_cells/reference/jamba.py``: float32, ``highest``, the recurrence a
``lax.scan``, no kernel, cache or batching), on seeded random weights.
Logits are compared, not tokens.

Tolerances, each with its reason:

- ``F32`` (float32 weights, float32 cache): program and reference compute the
  same float32 expressions and differ in the order of the sums (blocked
  matmuls, the softmax over gathered blocks, the kernel's 16-term state sum)
  through 4 layers; logits here are of order 1-10 and the observed gap is
  under 2e-5: 2e-4 absolute and relative. A bfloat16 pass anywhere (2**-9
  relative on an operand) moves the logits by 1e-2 and fails this.
- ``BF16`` (bfloat16 weights, the published dtype): the program rounds every
  matmul's activations to bfloat16 (2**-9 relative) where the reference
  keeps them float32 over the same rounded weights; through 4 layers of
  order-1 activations the logits move by up to 3e-2 observed: 0.1 absolute.
  An int8 operand (2**-7) moves them by 0.3 and more.
- Runs of the SAME compiled program on the same numbers (a slot bound
  again, preempt and resume) are compared bit for bit.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_cells.reference import jamba as reference

from simple_distributed_machine_learning_tpu.models import jamba
from simple_distributed_machine_learning_tpu.models.serving import (
    SEAT_NONE,
    SEAT_SAMPLE,
)
from simple_distributed_machine_learning_tpu.models.jamba import (
    JambaConfig,
    make_jamba_stages,
)
from simple_distributed_machine_learning_tpu.serve import InferenceEngine
from simple_distributed_machine_learning_tpu.telemetry import tracing

CFG = JambaConfig(vocab=97, seq_len=48, d_model=64, n_heads=4, n_kv_heads=1,
                  d_ff=128, n_layers=4, attn_period=2, attn_offset=1,
                  expand=4, dt_rank=8)           # d_inner 256; layers M A M A
F32 = dict(rtol=2e-4, atol=2e-4)
BF16 = dict(rtol=0.0, atol=0.1)
BS = 4
MATRICES = {"tok", "in_proj", "x_proj", "dt_proj", "out_proj", "wq", "wk",
            "wv", "wo", "gate", "up", "down"}


def _stages(cfg=CFG):
    """The builder's stage with its matrices scaled from normal 0.02 to 0.1:
    at width 64 the published scale leaves every activation near zero, and a
    model that is all but linear would forgive a wrong state."""
    stages, _, _ = make_jamba_stages(jax.random.key(0), cfg)
    dt = jnp.dtype(cfg.param_dtype)
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: (5 * a.astype(jnp.float32)).astype(dt)
        if path[-1].key in MATRICES else a, stages[0].params)
    return [dataclasses.replace(stages[0], params=params)]


@pytest.fixture(scope="module")
def stages():
    return _stages()


def _ref_logits(params, seq, first, n_out, cfg=CFG):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.served_logits(
            params, jnp.asarray(seq, jnp.int32), first, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, dt_rank=cfg.dt_rank, eps=cfg.rms_eps,
            n_out=n_out))


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
        np.testing.assert_allclose(
            np.asarray(logp[b]), np.asarray(jax.nn.log_softmax(want)), **tol)


def test_more_than_one_stage_is_refused_naming_the_tied_head():
    with pytest.raises(ValueError, match="tied head"):
        make_jamba_stages(jax.random.key(0), CFG, n_stages=2)


def test_layer_order_and_cache_layout_follow_the_config():
    assert [CFG.is_attention(i) for i in range(4)] == [False, True, False,
                                                       True]
    published = JambaConfig(n_layers=28, attn_period=14, attn_offset=7)
    assert [i for i in range(28) if published.is_attention(i)] == [7, 21]
    serving = CFG.paged_serving(_stages(), 48, BS)
    assert (serving.kv_layers, serving.kv_heads, serving.head_dim) == (2, 1,
                                                                       16)
    # a pair per Mamba layer, then every slot's newest token and key
    assert [tuple(s.shape for s in pair) for pair in serving.state_shapes] \
        == [((16, 256), (3, 256))] * 2 + [((), (2,))]


# -- the engine, with the logits it sampled from taken out --------------------


@functools.cache
def _twins(kernel):
    """The two programs' forwards, jitted once for every :class:`Tap`."""
    chunk = jax.jit(lambda p, kc, vc, st, toks, p0, table, slot:
                    jamba._hybrid_chunk_fwd(p, kc, vc, st, toks, p0, table,
                                            slot, CFG, BS))
    step = jax.jit(lambda p, kc, vc, st, toks, pos, tables, live:
                   jamba._hybrid_decode_fwd(p, kc, vc, st, toks, pos, tables,
                                            live, CFG, BS, kernel))
    return chunk, step


class Tap:
    """An engine whose two programs are jitted twins of the real ones that
    also hand out the logits they chose from (greedy: ``argmax``), and the
    recurrent state of every slot after each call. Like the real ones they
    keep every slot's newest token in the state's last pair and take no
    token from the host (``models/serving.py::PagedServing``)."""

    def __init__(self, stages, kernel="fused", **kw):
        kw = {"n_slots": 2, "max_len": 48, "block_size": BS,
              "prefill_chunk": 5, **kw}
        self.eng = InferenceEngine(stages, CFG, attn_kernel=kernel, **kw)
        self.rows = []          # (kind, slots, logits)
        self.states = []        # (kind, first Mamba layer's H for all slots)
        chunk, step = _twins(kernel)

        def chunk_prefill(p, kc, vc, st, toks, p0, table, slot, seat, kd,
                          *_):
            *st, (newest, keys) = st
            kc, vc, st, row = chunk(p, kc, vc, tuple(st), toks, p0, table,
                                    slot)
            self.rows.append(("chunk", int(slot), np.asarray(row)))
            self.states.append(("chunk", np.asarray(st[0][0])))
            tok = jnp.argmax(row).astype(jnp.int32)
            if seat != SEAT_NONE:
                newest = newest.at[int(slot)].set(
                    tok if seat == SEAT_SAMPLE else int(seat))
            return kc, vc, (*st, (newest, keys)), tok, jnp.asarray(kd)

        def decode(p, kc, vc, st, _toks, pos, tables, live, kd, *_):
            *st, (newest, keys) = st
            kc, vc, st, rows = step(p, kc, vc, tuple(st), newest, pos,
                                    tables, live)
            self.rows.append(("decode", np.flatnonzero(live),
                              np.asarray(rows)))
            self.states.append(("decode", np.asarray(st[0][0])))
            toks = jnp.argmax(rows, -1).astype(jnp.int32)
            return (kc, vc, (*st, (jnp.where(live, toks, newest), keys)),
                    toks, jnp.asarray(kd))

        self.eng._chunk_prefill, self.eng._decode = chunk_prefill, decode
        # the twins take the host arguments one by one
        self.eng._pack_chunk = self.eng._pack_decode = None

    def logits_of(self, handle):
        """The rows ``handle``'s tokens were chosen from, in order."""
        out = []
        for kind, slots, rows in self.rows:
            if kind == "chunk" and slots == handle.slot_was:
                last = rows
            elif kind == "decode" and handle.slot_was in slots:
                out.append(rows[handle.slot_was])
        return np.stack([last] + out)[:len(handle.tokens)]


def _serve(tap, prompts, n_new=5):
    handles = [tap.eng.submit(p, n_new) for p in prompts]
    while tap.eng.busy:
        tap.eng.step()
        for h in handles:
            if h.slot is not None:
                h.slot_was = h.slot
    return handles


@pytest.mark.parametrize("kernel", ["dense", "fused"])
def test_chunked_prefill_then_decode_matches_the_reference(stages, kernel):
    """13 prompt tokens in chunks of 5, 5 and a ragged 3, then decode, with
    a second request alongside: every token's logits against the
    reference's one full forward over prompt and served tokens."""
    tap = Tap(stages, kernel)
    prompts = [_prompt(3, 13), _prompt(4, 6)]
    for p, h in zip(prompts, _serve(tap, prompts)):
        assert len(h.tokens) == 5
        seq = np.concatenate([p, np.asarray(h.tokens[:-1], np.int32)])
        want = _ref_logits(stages[0].params, seq, len(p) - 1, 5)
        np.testing.assert_allclose(tap.logits_of(h), want, **F32)
    # three chunks for the first prompt, two for the second
    assert sum(kind == "chunk" for kind, *_ in tap.rows) == 5


def test_slot_mid_prefill_keeps_its_state_across_decode_ticks(stages):
    """While the long prompt is between chunks, the other slot decodes: a
    decode tick must hand the prefilling slot's state back bit for bit
    (the test above then shows the logits built on it are right)."""
    tap = Tap(stages)
    short, long_ = _prompt(5, 4), _prompt(6, 14)
    a = tap.eng.submit(short, 8)
    tap.eng.step()                       # the short prompt's one chunk
    b = tap.eng.submit(long_, 3)
    checked = 0
    while b.prefill_pos is None or b.prefill_pos < 10 or not checked:
        before = len(tap.states)
        tap.eng.step()
        new = tap.states[before:]
        if [k for k, _ in new] == ["chunk", "decode"] and b.slot is not None:
            (_, after_chunk), (_, after_decode) = new
            assert np.array_equal(after_chunk[b.slot], after_decode[b.slot])
            assert not np.array_equal(after_chunk[a.slot],
                                      after_decode[a.slot])
            checked += 1
        if not tap.eng.busy:
            break
    assert checked >= 2


def test_released_slot_bound_again_gives_a_fresh_engines_logits(stages):
    """One slot: the second request finds the first one's state in it, and
    its first chunk (``p0 == 0``) must start from zeros."""
    first, second = _prompt(7, 9), _prompt(8, 11)
    used = Tap(stages, n_slots=1)
    _serve(used, [first])
    assert np.abs(np.asarray(used.eng.pool.state[0][0])).max() > 0
    used.rows.clear()
    h_used, = _serve(used, [second])
    fresh = Tap(stages, n_slots=1)
    h_fresh, = _serve(fresh, [second])
    assert h_used.tokens == h_fresh.tokens
    assert np.array_equal(used.logits_of(h_used), fresh.logits_of(h_fresh))


def test_preempt_then_resume_reproduces_the_tokens(stages):
    """Re-admission recomputes ``resume_seq`` from position 0, which
    rebuilds the recurrent state; the tokens are the unpreempted run's."""
    prompts = [_prompt(9, 7), _prompt(10, 9)]
    plain = InferenceEngine(stages, CFG, n_slots=2, max_len=48,
                            block_size=BS, prefill_chunk=5,
                            attn_kernel="fused")
    want = [plain.submit(p, 8) for p in prompts]
    plain.drain()
    eng = InferenceEngine(stages, CFG, n_slots=2, max_len=48, block_size=BS,
                          prefill_chunk=5, attn_kernel="fused")
    got = [eng.submit(p, 8) for p in prompts]
    while len(got[0].tokens) < 4:
        eng.step()
    eng.preempt(got[0].rid)
    eng.drain()
    assert got[0].n_preempted == 1
    assert [h.tokens for h in got] == [h.tokens for h in want]
    # its own prompt is the prefix the pool had to decline on the way back
    assert eng.pool.prefix_declined_total == 1
    assert eng.pool.stats()["prefix_declined_total"] == 1


def test_sampled_requests_leave_their_greedy_neighbours_alone(stages):
    """The programs skip the sampler's sorts when every slot is greedy; a
    tick with a sampled request takes the sampler for all, and a greedy
    request's tokens are the same on both paths. Sampling is seeded."""
    def serve(temperature):
        eng = InferenceEngine(stages, CFG, n_slots=2, max_len=48,
                              block_size=BS, prefill_chunk=5,
                              attn_kernel="dense")
        greedy = eng.submit(_prompt(12, 6), 6)
        other = eng.submit(_prompt(13, 7), 6, temperature=temperature,
                           top_k=20 if temperature else None, seed=7)
        eng.drain()
        return greedy.tokens, other.tokens

    alone, argmax = serve(0.0)
    beside, sampled = serve(1.5)
    assert beside == alone
    assert sampled != argmax and serve(1.5)[1] == sampled


# -- the tick that is dispatched ahead -----------------------------------------


def _engine(stages, **kw):
    return InferenceEngine(stages, CFG, **{
        "n_slots": 3, "max_len": 48, "block_size": BS, "prefill_chunk": 5,
        "attn_kernel": "dense", **kw})


def _submit_mix(eng, eos_id=None):
    """Greedy and sampled requests, a one-token and a two-token answer
    among them, more requests than slots."""
    return [eng.submit(_prompt(20 + i, n), new, temperature=t, seed=5,
                       top_k=20 if t else None, eos_id=eos_id)
            for i, (n, new, t) in enumerate([
                (7, 6, 0.0), (11, 1, 0.0), (4, 9, 1.3), (6, 2, 0.0),
                (12, 5, 0.0)])]


def test_decode_is_dispatched_before_the_last_ones_tokens_are_read(stages):
    """With no request that can end on a token, a tick launches the next
    tick's decode and only then waits for its own: in the recorder, the
    tick's ``engine.decode.dispatch`` ends before its ``engine.decode.wait``
    starts. The decode reads ahead of the host's copy of the newest tokens,
    so right tokens show that it reads the device's."""
    eng = _engine(stages)
    mark = len(tracing.current().spans())
    handles = _submit_mix(eng)
    ahead = 0
    while eng.busy:
        eng.step()
        ahead += eng._ahead is not None
    spans = tracing.current().spans()[mark:]
    kids = {}
    for sp in spans:
        kids.setdefault(sp.parent, {})[sp.name] = sp
    both = [k for k in kids.values()
            if {"engine.decode.dispatch", "engine.decode.wait"} <= set(k)]
    assert ahead >= 10 and len(both) >= 10
    assert sum(k["engine.decode.dispatch"].end_ns
               <= k["engine.decode.wait"].start_ns for k in both) >= ahead - 1
    assert [len(h.tokens) for h in handles] == [6, 1, 9, 2, 5]
    # each alone in a one-slot engine, where no neighbour shares a tick
    solo = _engine(stages, n_slots=1)
    want = _submit_mix(solo)
    solo.drain()
    assert [h.tokens for h in handles] == [h.tokens for h in want]


def test_a_request_that_can_end_on_a_token_holds_the_dispatch_back(stages):
    """``eos_id`` makes the next tick's slots depend on tokens not yet read:
    no decode is dispatched ahead, every tick launches its own in the same
    order (decode, then the chunk), and the tokens, their ticks included,
    are those of the run that dispatches ahead."""
    def run(eos_id):
        eng = _engine(stages)
        handles = _submit_mix(eng, eos_id)
        per_tick, ahead = [], 0
        while eng.busy:
            per_tick.append(eng.step())
            ahead += eng._ahead is not None
        return [h.tokens for h in handles], per_tick, ahead

    tokens, per_tick, ahead = run(None)
    unseen = next(t for t in range(CFG.vocab)
                  if all(t not in toks for toks in tokens))
    tokens_eos, per_tick_eos, ahead_eos = run(unseen)
    assert ahead > 0 and ahead_eos == 0
    assert (tokens_eos, per_tick_eos) == (tokens, per_tick)
    # and a request does end on its token
    hit = tokens[0][2]
    tokens_hit, _, _ = run(hit)
    assert tokens_hit[0] == tokens[0][:tokens[0].index(hit) + 1]


def test_cancel_with_a_decode_in_flight_drops_its_token_alone(stages):
    """A request cancelled between two ticks has a token in flight: it is
    dropped, the slot is bound again (its first chunk zeroes the state the
    decode in flight advanced), and the neighbours' tokens do not move."""
    eng = _engine(stages, n_slots=2)
    a = eng.submit(_prompt(30, 6), 10)
    b = eng.submit(_prompt(31, 9), 10)
    while len(b.tokens) < 3:
        eng.step()
    assert eng._ahead is not None and b.rid in eng._ahead[0]
    n_b = len(b.tokens)
    eng.cancel(b.rid)
    c = eng.submit(_prompt(32, 8), 4)
    eng.drain()
    assert len(b.tokens) == n_b and b.state == "shed"
    solo = _engine(stages, n_slots=1)
    want = [solo.submit(_prompt(30, 6), 10), solo.submit(_prompt(32, 8), 4)]
    solo.drain()
    assert [a.tokens, c.tokens] == [h.tokens for h in want]


def test_host_inputs_survive_the_trip_as_one_array():
    """The programs take their host-side arguments as one int32 array
    (floats and key words by their bits): what is unpacked on the device is
    what was packed, to the bit."""
    rng = np.random.default_rng(0)
    n_slots, nb = 5, 7
    args = (rng.integers(0, 97, n_slots).astype(np.int32),
            rng.integers(0, 40, n_slots).astype(np.int32),
            rng.integers(0, 30, (n_slots, nb)).astype(np.int32),
            rng.random(n_slots) < 0.5,
            rng.integers(0, 2 ** 32, (n_slots, 2), dtype=np.uint64).astype(
                np.uint32),
            rng.random(n_slots).astype(np.float32),
            rng.integers(0, 50, n_slots).astype(np.int32),
            np.full(n_slots, 2.0, np.float32))
    host, = jamba.pack_decode_inputs(*args)
    assert host.shape == (n_slots, 5 + nb) and host.dtype == np.int32
    # the tokens and the keys stay behind: the program has its own
    sent = [a for i, a in enumerate(args) if i not in (0, 4)]
    for got, want in zip(jax.jit(jamba.unpack_decode)(host), sent):
        assert got.dtype == want.dtype and np.array_equal(got, want)
    chunk = (rng.integers(0, 97, (1, 6)).astype(np.int32), np.int32(12),
             rng.integers(0, 30, nb).astype(np.int32), np.int32(3),
             np.int32(SEAT_SAMPLE), np.array([7, 2 ** 32 - 5], np.uint32),
             np.float32(0.7), np.int32(0), np.float32(2.0))
    tokens, host = jamba.pack_chunk_inputs(*chunk)
    assert np.array_equal(tokens, chunk[0]) and host.shape == (8 + nb,)
    for got, want in zip(jax.jit(jamba.unpack_chunk)(host), chunk[1:]):
        assert got.dtype == want.dtype and np.array_equal(got, want)


def test_tick_and_admit_spans_carry_the_new_counts(stages):
    eng = InferenceEngine(stages, CFG, n_slots=2, max_len=48, block_size=BS,
                          prefill_chunk=5, attn_kernel="dense")
    mark = len(tracing.current().spans())
    same = _prompt(11, 8)
    eng.submit(same, 3)
    eng.drain()
    eng.submit(same, 3)         # the prompt a GPT pool would have shared
    eng.drain()
    spans = tracing.current().spans()[mark:]
    ticks = [s for s in spans if s.name == "engine.tick"]
    admits = [s for s in spans if s.name == "engine.admit"]
    assert {"state_slots", "kv_blocks", "chunk", "decoding"} <= set(
        ticks[0].attrs)
    # first tick: the first of two chunks ran, the slot's state is live,
    # and its 5 positions hold 2 blocks of 4
    assert ticks[0].attrs["state_slots"] == 1
    assert ticks[0].attrs["kv_blocks"] == 2
    assert ticks[-1].attrs["state_slots"] == 0      # retired
    assert sum(a.attrs["prefix_declined"] for a in admits) == 1
    assert eng.pool.shared_prefix_len(same) == 0
    # one buffer per attention layer, a position's ONE K/V head its row
    assert [b.shape for b in eng.pool.kc] == [
        (eng.pool.n_blocks + 1, BS, CFG.head_dim)] * 2


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


def test_gpt_engine_has_no_recurrent_state_and_reports_zero(stages):
    """The first model of the interface: its only state is every slot's
    newest token and key, nothing recurrent; program names as they were."""
    from simple_distributed_machine_learning_tpu.models.gpt import (
        GPTConfig,
        make_gpt_stages,
    )
    cfg = GPTConfig(vocab=64, seq_len=32, d_model=32, n_heads=2, n_layers=2)
    gstages = make_gpt_stages(jax.random.key(0), cfg, 1)[0]
    eng = InferenceEngine(gstages, cfg, n_slots=2, block_size=4,
                          prefill_chunk=4)
    assert [leaf.shape for leaf in jax.tree.leaves(eng.pool.state)] \
        == [(2,), (2, 2)]
    assert eng.pool.has_state and not eng.pool.recurrent
    assert eng._decode.__name__ == "step_paged_decode"
    assert eng._chunk_prefill.__name__ == "chunk_paged_prefill"
    mark = len(tracing.current().spans())
    eng.submit(np.arange(6, dtype=np.int32), 3)
    eng.drain()
    ticks = [s for s in tracing.current().spans()[mark:]
             if s.name == "engine.tick"]
    assert all(t.attrs["state_slots"] == 0 for t in ticks)
    assert ticks[0].attrs["kv_blocks"] == 1
    hybrid = CFG.paged_serving(stages, 48, BS)
    assert hybrid.decode.__name__ == "step_hybrid_decode"
    assert hybrid.chunk_prefill.__name__ == "chunk_hybrid_prefill"
