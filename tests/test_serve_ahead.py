"""GPT under the tick that is dispatched ahead (``serve/engine.py::
_tick_ahead``; ``tests/test_jamba.py`` holds the hybrid's twins).

GPT's two paged programs keep every slot's newest token and sampling key on
the device beside the pool (``models/serving.py::PagedServing``), so the
engine launches tick N+1's decode before it reads tick N's tokens. Nothing a
request receives may move for it: every build of the programs serves the
tokens of the solo cached decoder, whatever rides in the neighbouring slots,
through prefix hits, preemption, cancellation and requests that can end on a
token; and speculation, whose tick needs the host's tokens, keeps its own.
"""

import dataclasses

import jax
import numpy as np
import pytest

from simple_distributed_machine_learning_tpu.models import lora
from simple_distributed_machine_learning_tpu.models.gpt import (
    GPTConfig,
    make_cached_decoder,
    make_gpt_stages,
)
from simple_distributed_machine_learning_tpu.serve import InferenceEngine
from simple_distributed_machine_learning_tpu.serve.adapters import (
    AdapterStore,
)
from simple_distributed_machine_learning_tpu.telemetry import tracing

CFG = GPTConfig(vocab=32, seq_len=48, d_model=32, n_heads=2, n_layers=2)
DRAFT_CFG = dataclasses.replace(CFG, n_layers=1)
BS = 4
_STAGES = {}


def _model():
    if "target" not in _STAGES:
        _STAGES["target"] = make_gpt_stages(jax.random.key(0), CFG, 2)[0]
    return _STAGES["target"], [s.params for s in _STAGES["target"]]


def _draft():
    if "draft" not in _STAGES:
        _STAGES["draft"] = make_gpt_stages(jax.random.key(9), DRAFT_CFG, 1)[0]
    return _STAGES["draft"]


def _prompt(n, seed):
    return np.asarray(
        jax.random.randint(jax.random.key(seed), (n,), 0, CFG.vocab),
        np.int32)


def _solo(params, spec):
    """The request decoded ALONE by the one-shot cached decoder."""
    stages, _ = _model()
    prompt, n_new = spec["prompt"], spec["max_new_tokens"]
    dec = make_cached_decoder(
        stages, CFG, len(prompt), n_new,
        temperature=spec.get("temperature", 0.0), top_k=spec.get("top_k"),
        top_p=spec.get("top_p"))
    out = dec(params, prompt[None], jax.random.key(spec["seed"]))
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


def _key_after(seed: int, splits: int) -> list[int]:
    """The key data a stream seeded ``seed`` holds after ``splits`` sampled
    tokens: one split a token, none for a greedy one."""
    k = jax.random.key(seed)
    for _ in range(splits):
        k, _ = jax.random.split(k)
    return [int(w) for w in np.asarray(jax.random.key_data(k))]


def _mix(adapters=(None,)):
    """Greedy and sampled requests, a one-token and a two-token answer
    among them, more requests than slots, prompts that end mid-chunk."""
    rows = [(7, 6, 0.0, None, None), (11, 1, 0.0, None, None),
            (4, 9, 1.3, 20, None), (6, 2, 0.0, None, None),
            (12, 5, 0.9, None, 0.9), (9, 7, 0.0, None, None)]
    specs = []
    for i, (n, new, t, k, p) in enumerate(rows):
        s = dict(prompt=_prompt(n, 20 + i), max_new_tokens=new, seed=40 + i)
        if t:
            s.update(temperature=t, top_k=k, top_p=p)
        if adapters[i % len(adapters)] is not None:
            s["adapter"] = adapters[i % len(adapters)]
        specs.append(s)
    return specs


def _engine(**kw):
    stages, _ = _model()
    cfg = kw.pop("cfg", CFG)
    return InferenceEngine(stages, cfg, **{
        "n_slots": 3, "block_size": BS, "prefill_chunk": 5, **kw})


def _tp2_engine():
    from simple_distributed_machine_learning_tpu.parallel.mesh import (
        make_mesh,
    )
    return _engine(cfg=dataclasses.replace(CFG, n_tensor_parallel=2),
                   mesh=make_mesh(n_stages=1, n_data=1, n_model=2))


def _run(eng, specs, **extra):
    """Drive ``specs`` to the end: ``(handles, tokens emitted per tick,
    ticks that left a decode dispatched for the next one)``."""
    handles = [eng.submit(**s, **extra) for s in specs]
    per_tick, ahead = [], 0
    while eng.busy:
        per_tick.append(eng.step())
        ahead += eng._ahead is not None
    return handles, per_tick, ahead


def _adapter(seed, rank=2):
    """A LoRA pair that bends the logits (a fresh one's B is zero)."""
    w = dict(lora.init_lora_adapter(jax.random.key(seed), CFG, rank))
    kq, kv = jax.random.split(jax.random.key(seed + 9000))
    w["bq"] = 0.05 * jax.random.normal(kq, w["bq"].shape, w["bq"].dtype)
    w["bv"] = 0.05 * jax.random.normal(kv, w["bv"].shape, w["bv"].dtype)
    return w


# -- every build of the two programs ------------------------------------------


@pytest.mark.parametrize("build", ["single", "adapters", "tp2", "int8"])
def test_served_tokens_are_the_solo_decoders(build):
    """Greedy and sampled, more requests than slots, chunked prompts: each
    request's tokens are those of its solo decode, bit for bit, and its key
    stream has advanced once a sampled token — on a single device, with a
    LoRA bank (the solo decode through the merged weights), tensor-parallel
    over two devices, and over an int8 pool (there against a one-slot
    engine of the same pool: the solo decoder holds no quantised rows).
    The decode was dispatched ahead all the way."""
    _, params = _model()
    weights = {None: params}
    specs = _mix()
    if build == "adapters":
        eng = _engine(adapters=AdapterStore(CFG, 2, 3))
        for name, seed in (("t1", 1), ("t2", 2)):
            w = _adapter(seed)
            eng.register_adapter(name, w)
            weights[name] = lora.merge_adapter(params, w)
        specs = _mix((None, "t1", "t2"))
    elif build == "tp2":
        eng = _tp2_engine()
        assert eng.pool.tp == 2
    elif build == "int8":
        eng = _engine(cache_dtype="int8")
    else:
        eng = _engine()
    assert eng._dispatch_ahead and not eng.pool.recurrent
    if build == "int8":
        want = [h.tokens for h in _run(_engine(cache_dtype="int8",
                                               n_slots=1), specs)[0]]
    else:
        want = [_solo(weights[s.get("adapter")], s) for s in specs]
    mark = len(tracing.current().spans())
    handles, per_tick, ahead = _run(eng, specs)
    assert [len(h.tokens) for h in handles] == [6, 1, 9, 2, 5, 7]
    assert [h.tokens for h in handles] == want
    for h, s in zip(handles, specs):
        sampled = s["max_new_tokens"] if s.get("temperature") else 0
        assert [int(w) for w in h.key_data] == _key_after(s["seed"], sampled)
    # every tick that decoded found its decode dispatched by the one
    # before (the first by the tick of the chunk that seated its slot)
    ticks = [sp.attrs for sp in tracing.current().spans()[mark:]
             if sp.name == "engine.tick"]
    decoded = [t["ahead"] for t in ticks if t["decoding"]]
    assert len(ticks) == len(per_tick) and ahead >= 10
    assert all(decoded) and sum(decoded) == ahead
    assert all(t["ahead"] == 0 for t in ticks if not t["decoding"])


@pytest.mark.parametrize("build", ["single", "tp2"])
@pytest.mark.parametrize("kind", ["greedy", "sampled"])
def test_a_batch_of_one_kind_serves_the_solo_decodes_too(kind, build):
    """The programs' sampler takes one of two branches by whether any slot
    samples (``models/serving.py::sample_slots``): a run whose every decode
    is all-greedy (the branch that sorts nothing) and one whose every decode
    samples in every live slot serve each request's solo decode all the
    same, and the tick's ``sampling`` says which branch its decode took."""
    _, params = _model()
    specs = _mix()
    for i, s in enumerate(specs):
        for k in ("temperature", "top_k", "top_p"):
            s.pop(k, None)
        if kind == "sampled":
            s.update(temperature=0.6 + 0.2 * i, top_k=(None, 12)[i % 2],
                     top_p=(0.9, None, None)[i % 3])
    eng = _tp2_engine() if build == "tp2" else _engine()
    mark = len(tracing.current().spans())
    handles, _, ahead = _run(eng, specs)
    assert [h.tokens for h in handles] == [_solo(params, s) for s in specs]
    for h, s in zip(handles, specs):
        splits = s["max_new_tokens"] if kind == "sampled" else 0
        assert [int(w) for w in h.key_data] == _key_after(s["seed"], splits)
    ticks = [sp.attrs for sp in tracing.current().spans()[mark:]
             if sp.name == "engine.tick"]
    assert ahead >= 10 and sum(t["decoding"] > 0 for t in ticks) >= 10
    assert all(t["sampling"] == (t["decoding"] if kind == "sampled" else 0)
               for t in ticks)


def test_next_decode_is_dispatched_before_this_ones_tokens_are_read():
    """In the recorder a tick's ``engine.decode.dispatch`` (tick N+1's
    decode) ends before its ``engine.decode.wait`` (tick N's tokens)
    starts. The decode runs ahead of the host's copy of the newest tokens,
    so right tokens show that it reads the device's."""
    eng = _engine()
    mark = len(tracing.current().spans())
    handles, _, ahead = _run(eng, _mix())
    kids = {}
    for sp in tracing.current().spans()[mark:]:
        kids.setdefault(sp.parent, {})[sp.name] = sp
    both = [k for k in kids.values()
            if {"engine.decode.dispatch", "engine.decode.wait"} <= set(k)]
    assert ahead >= 10 and len(both) >= 10
    assert sum(k["engine.decode.dispatch"].end_ns
               <= k["engine.decode.wait"].start_ns for k in both) >= ahead - 1
    # the host's copy trails the device: what it holds for a decoding slot
    # mid-run is the token before the one in flight
    eng = _engine(n_slots=1)
    h = eng.submit(_prompt(6, 3), 8, seed=3)
    while len(h.tokens) < 4:
        eng.step()
    assert eng._ahead is not None
    assert int(eng.pool.last_token[h.slot]) == h.tokens[-1]
    on_device = int(np.asarray(eng.pool.state[0][0])[h.slot])
    eng.step()
    assert h.tokens[-1] == on_device and len(h.tokens) == 5


# -- what a recurrent model is refused, GPT keeps -----------------------------


def test_prefix_cache_still_registers_and_matches():
    """A pool with state beside the blocks is not a pool with recurrent
    state: the second request shares the first one's prompt blocks, none is
    declined, and both decode as alone."""
    _, params = _model()
    eng = _engine(n_slots=2, prefill_chunk=4)
    shared = _prompt(12, 77)
    a = dict(prompt=shared, max_new_tokens=5, seed=1)
    b = dict(prompt=np.concatenate([shared, _prompt(5, 78)]),
             max_new_tokens=6, seed=2, temperature=0.8, top_k=6)
    ha = eng.submit(**a)
    for _ in range(5):
        eng.step()              # a's prompt is registered, a still decodes
    assert eng._ahead is not None and eng.pool.prefix_hit_blocks_total == 0
    hb = eng.submit(**b)
    eng.drain()
    assert eng.pool.prefix_hit_blocks_total >= 3
    assert eng.pool.prefix_declined_total == 0
    assert eng.pool.shared_prefix_len(shared) >= 8
    assert [ha.tokens, hb.tokens] == [_solo(params, a), _solo(params, b)]


def test_host_tier_and_speculation_still_construct():
    eng = _engine(host_cache_blocks=4)
    assert eng.pool.host_cache_blocks == 4 and eng._dispatch_ahead
    spec = _engine(draft_stages=_draft(), draft_cfg=DRAFT_CFG, spec_k=3)
    assert spec.speculative and not spec._dispatch_ahead


@pytest.mark.parametrize("temperature", [0.0, 1.2])
def test_preemption_with_a_decode_in_flight_samples_the_token_again(
        temperature):
    """A request preempted between two ticks has a token in flight: it is
    dropped, the request goes back to the queue with the key the host
    kept, and when it resumes (its last chunk seats its stored token and
    that key) the same token is sampled again: the stream is the solo
    decode's, and the neighbour's does not move."""
    _, params = _model()
    eng = _engine(n_slots=2)
    kw = dict(temperature=temperature, top_k=8) if temperature else {}
    a = dict(prompt=_prompt(6, 30), max_new_tokens=10, seed=5)
    b = dict(prompt=_prompt(9, 31), max_new_tokens=10, seed=6, **kw)
    ha, hb = eng.submit(**a), eng.submit(**b)
    while len(hb.tokens) < 3:
        eng.step()
    assert eng._ahead is not None and hb.rid in eng._ahead[0]
    n_b, key_b = len(hb.tokens), [int(w) for w in hb.key_data]
    eng.preempt(hb.rid)
    eng.step()          # the dropped token's tick: b emits nothing in it
    assert len(hb.tokens) == n_b and hb.n_preempted == 1
    assert [int(w) for w in hb.key_data] == key_b
    eng.drain()
    assert [ha.tokens, hb.tokens] == [_solo(params, a), _solo(params, b)]


def test_cancel_with_a_decode_in_flight_drops_its_token_alone():
    _, params = _model()
    eng = _engine(n_slots=2)
    a = dict(prompt=_prompt(6, 30), max_new_tokens=10, seed=5)
    c = dict(prompt=_prompt(8, 32), max_new_tokens=4, seed=7,
             temperature=0.7, top_p=0.9)
    ha = eng.submit(**a)
    hb = eng.submit(_prompt(9, 31), 10, seed=6)
    while len(hb.tokens) < 3:
        eng.step()
    assert eng._ahead is not None and hb.rid in eng._ahead[0]
    n_b = len(hb.tokens)
    eng.cancel(hb.rid)
    hc = eng.submit(**c)        # boards the slot the decode in flight wrote
    eng.drain()
    assert len(hb.tokens) == n_b and hb.state == "shed"
    assert [ha.tokens, hc.tokens] == [_solo(params, a), _solo(params, c)]


def test_a_request_that_can_end_on_a_token_holds_the_dispatch_back():
    """``eos_id`` makes the next tick's slots depend on tokens not yet
    read: no decode is dispatched ahead, every tick launches its own in the
    same order, and the tokens, their ticks included, are those of the run
    that dispatches ahead. The tick span says so (``ahead`` 0 throughout),
    which is what ``engine.ahead_ticks_pct`` reads."""
    tokens, per_tick, ahead = _run(_engine(), _mix())
    tokens = [h.tokens for h in tokens]
    unseen = next(t for t in range(CFG.vocab)
                  if all(t not in toks for toks in tokens))
    mark = len(tracing.current().spans())
    handles, per_tick_eos, ahead_eos = _run(_engine(), _mix(), eos_id=unseen)
    assert ahead > 0 and ahead_eos == 0
    assert ([h.tokens for h in handles], per_tick_eos) == (tokens, per_tick)
    assert not any(sp.attrs["ahead"]
                   for sp in tracing.current().spans()[mark:]
                   if sp.name == "engine.tick")
    # and a request does end on its token
    hit = tokens[0][2]
    ended = _run(_engine(), _mix(), eos_id=hit)[0][0]
    assert ended.tokens == tokens[0][:tokens[0].index(hit) + 1]
    assert ended.finish_reason == "eos"


# -- speculation keeps its own tick -------------------------------------------

# the sampled speculative streams of `_mix()`'s requests 2 and 4 as the
# parent commit (PR 30) served them (my CPU run; residual-rejection
# sampling is deterministic per seed and NOT the solo decode's stream)
_SPEC_SAMPLED_AT_PARENT = {
    2: [12, 25, 27, 12, 25, 27, 28, 1, 9],
    4: [25, 7, 19, 30, 28],
}


def test_speculative_engine_serves_the_same_tokens_and_never_runs_ahead():
    """With a draft the engine keeps the plain tick (chunk, then the
    speculative tick over the host's tokens): the chunk program is the same
    one and the pair it seats is never read. Greedy streams are the solo
    decode's; sampled ones are what the parent commit served."""
    _, params = _model()
    eng = _engine(draft_stages=_draft(), draft_cfg=DRAFT_CFG, spec_k=3,
                  block_size=8)
    mark = len(tracing.current().spans())
    specs = _mix()
    handles, _, ahead = _run(eng, specs)
    assert ahead == 0
    assert not any(sp.attrs["ahead"]
                   for sp in tracing.current().spans()[mark:]
                   if sp.name == "engine.tick")
    for i, (h, s) in enumerate(zip(handles, specs)):
        if s.get("temperature"):
            assert h.tokens == _SPEC_SAMPLED_AT_PARENT[i], i
        else:
            assert h.tokens == _solo(params, s), i
