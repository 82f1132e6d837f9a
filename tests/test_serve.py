"""Continuous-batching serving (serve/): parity, invariants, traffic.

The load-bearing property: continuous batching is a SCHEDULING optimization,
not a math change — for a fixed seed, every request's tokens are bit-exact
vs decoding it alone through ``models.make_cached_decoder``, across mixed
prompt lengths, mid-flight admissions, EOS early exits, and every sampling
mode — and since the paged pool landed, ALSO across block-table storage,
chunked prefill boundaries, shared prefixes and copy-on-write divergence
(the engine has one pool, the paged one, so every parity test exercises
it). Plus the scheduler invariants (no
double occupancy/allocation, admission blocks on block exhaustion and
resumes, every request completes, freed slots reuse next tick, queues drain
above capacity), the serving metrics incl. the block-pool gauges, the
simulator with its shared system prefix, the checkpoint→serve path, and the
bench claims: continuous beats sequential, paged sustains more concurrency
at fixed KV bytes, chunked prefill cuts the long-prompt stall tick.
"""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from simple_distributed_machine_learning_tpu.models.gpt import (
    GPTConfig,
    make_cached_decoder,
    make_gpt_stages,
    make_paged_decode_step,
    make_slot_prefill,
)
from simple_distributed_machine_learning_tpu.serve import (
    InferenceEngine,
    ServeMetrics,
    SimConfig,
    simulate,
)
from simple_distributed_machine_learning_tpu.serve.request import (
    ACTIVE,
    DONE,
    Request,
    validate_request,
)
from simple_distributed_machine_learning_tpu.serve.slots import (
    PagedKVPool,
    PagedKVPool,
)

CFG = GPTConfig(vocab=32, seq_len=48, d_model=32, n_heads=2, n_layers=2)
_STAGES = None


def _model():
    global _STAGES
    if _STAGES is None:
        _STAGES = make_gpt_stages(jax.random.key(0), CFG, 2)[0]
    return _STAGES, [s.params for s in _STAGES]


def _solo(stages, params, prompt, n_new, seed, temperature=0.0, top_k=None,
          top_p=None):
    """The reference tokens: this request decoded ALONE through the
    one-shot KV-cache decoder with the same seed and sampling params."""
    dec = make_cached_decoder(stages, CFG, len(prompt), n_new,
                              temperature=temperature, top_k=top_k,
                              top_p=top_p)
    out = dec(params, np.asarray(prompt, np.int32)[None],
              jax.random.key(seed))
    return np.asarray(out)[0, len(prompt):]


def _prompt(n, seed):
    return np.asarray(
        jax.random.randint(jax.random.key(seed), (n,), 0, CFG.vocab),
        np.int32)


# ---------------------------------------------------------------------------
# parity: bit-exact vs solo decode


def test_single_request_matches_solo_decode():
    stages, params = _model()
    eng = InferenceEngine(stages, CFG, n_slots=3)
    r = eng.submit(_prompt(5, 1), max_new_tokens=6, seed=11)
    eng.drain()
    assert r.state == DONE and r.finish_reason == "length"
    np.testing.assert_array_equal(
        r.tokens, _solo(stages, params, r.prompt, 6, 11))


def test_mixed_prompt_lengths_and_sampling_parity():
    """5 requests, 2 slots (so queueing + mid-flight boarding happens),
    mixed prompt lengths and sampling modes — each request's tokens are
    bit-exact vs its solo decode."""
    stages, params = _model()
    eng = InferenceEngine(stages, CFG, n_slots=2)
    specs = [
        dict(prompt=_prompt(3, 2), max_new_tokens=7, seed=20),
        dict(prompt=_prompt(9, 3), max_new_tokens=5, seed=21,
             temperature=0.8, top_k=5),
        dict(prompt=_prompt(5, 4), max_new_tokens=8, seed=22,
             temperature=0.9, top_p=0.9),
        dict(prompt=_prompt(7, 5), max_new_tokens=4, seed=23),
        dict(prompt=_prompt(4, 6), max_new_tokens=6, seed=24,
             temperature=1.1, top_k=7, top_p=0.8),
    ]
    handles = [eng.submit(**s) for s in specs]
    eng.drain()
    for h, s in zip(handles, specs):
        want = _solo(stages, params, s["prompt"], s["max_new_tokens"],
                     s["seed"], temperature=s.get("temperature", 0.0),
                     top_k=s.get("top_k"), top_p=s.get("top_p"))
        np.testing.assert_array_equal(np.asarray(h.tokens), want,
                                      err_msg=f"request {h.rid}")


def test_mid_flight_admission_parity():
    """A request admitted while another is mid-decode gets the same tokens
    as its solo decode — co-residents cannot change anyone's output."""
    stages, params = _model()
    eng = InferenceEngine(stages, CFG, n_slots=2)
    r1 = eng.submit(_prompt(6, 7), max_new_tokens=10, seed=30)
    for _ in range(4):                       # r1 alone for 4 ticks
        eng.step()
    assert 0 < len(r1.tokens) < 10
    r2 = eng.submit(_prompt(4, 8), max_new_tokens=6, seed=31,
                    temperature=0.7, top_k=4)
    eng.drain()
    np.testing.assert_array_equal(
        r1.tokens, _solo(stages, params, r1.prompt, 10, 30))
    np.testing.assert_array_equal(
        r2.tokens, _solo(stages, params, r2.prompt, 6, 31,
                         temperature=0.7, top_k=4))


def test_eos_early_exit_parity_and_slot_free():
    """EOS retires the request with a PREFIX of its solo decode (up to and
    including the first EOS) and frees the slot immediately."""
    stages, params = _model()
    solo = _solo(stages, params, _prompt(5, 9), 8, 40)
    eos = int(solo[2])                       # an eos the solo decode emits
    cut = int(np.where(solo == eos)[0][0]) + 1   # ...its FIRST occurrence
    eng = InferenceEngine(stages, CFG, n_slots=1)
    r = eng.submit(_prompt(5, 9), max_new_tokens=8, seed=40, eos_id=eos)
    eng.drain()
    assert r.finish_reason == "eos"
    assert len(r.tokens) == cut < 8
    np.testing.assert_array_equal(r.tokens, solo[:cut])
    assert eng.pool.n_free == 1


# ---------------------------------------------------------------------------
# scheduler invariants


def test_queue_drains_above_capacity_no_double_occupancy():
    """9 requests through 2 slots: occupancy never exceeds capacity, a
    slot never hosts two requests (pool guards raise), every request
    completes, and a freed slot is reused on the next tick."""
    stages, params = _model()
    eng = InferenceEngine(stages, CFG, n_slots=2)
    handles = [eng.submit(_prompt(3 + i % 3, 10 + i),
                          max_new_tokens=3 + i % 4, seed=50 + i)
               for i in range(9)]
    max_active = 0
    while eng.busy:
        queued_before = eng.scheduler.queue_depth
        eng.step()
        assert eng.pool.n_active <= 2
        max_active = max(max_active, eng.pool.n_active)
        # FCFS: the queue never grows mid-run (no re-queueing); slots can
        # all retire within one decode tick, so n_active == 0 with work
        # still queued is legal — the next tick's admission boards it
        assert eng.scheduler.queue_depth <= queued_before
        occ = [eng.pool.occupant(s) for s in eng.pool.active_slots()]
        assert len(occ) == len(set(occ))     # no slot double-occupied
    assert all(h.state == DONE for h in handles)
    assert eng.scheduler.queue_depth == 0
    assert max_active == 2                   # the batch actually filled
    # each completed with its requested token budget, and parity held
    for i, h in enumerate(handles):
        assert len(h.tokens) == 3 + i % 4
        np.testing.assert_array_equal(
            h.tokens, _solo(stages, params, h.prompt, len(h.tokens),
                            50 + i))


def test_freed_slot_reusable_next_tick():
    stages, _ = _model()
    eng = InferenceEngine(stages, CFG, n_slots=1)
    r1 = eng.submit(_prompt(4, 30), max_new_tokens=1, seed=60)
    r2 = eng.submit(_prompt(6, 31), max_new_tokens=5, seed=61)
    eng.step()                    # tick 1: r1 prefills, finishes, frees
    assert r1.state == DONE and eng.pool.n_free == 1
    assert r2.state == "queued"
    eng.step()                    # tick 2: r2 boards the freed slot
    assert r2.state == "active" and r2.slot is not None
    # its prefill token; its first decode is already dispatched and is
    # read in the next tick (the tick ahead: the chunk runs after the
    # decode, and the slot it seats decodes from the tick after)
    assert len(r2.tokens) == 1 and eng._ahead is not None
    eng.step()
    assert len(r2.tokens) == 2
    eng.drain()
    assert r2.state == DONE and len(r2.tokens) == 5


def test_pool_guards():
    pool = PagedKVPool(2, 2, 2, 8, 4)
    a = pool.acquire(0)
    b = pool.acquire(1)
    assert {a, b} == {0, 1}
    with pytest.raises(RuntimeError, match="full pool"):
        pool.acquire(2)
    pool.release(a)
    with pytest.raises(RuntimeError, match="already-free"):
        pool.release(a)
    assert pool.acquire(3) == a   # freed slot comes back


def test_request_validation():
    stages, _ = _model()
    eng = InferenceEngine(stages, CFG, n_slots=1, max_len=16)
    with pytest.raises(ValueError, match="exceeds the pool"):
        eng.submit(_prompt(10, 0), max_new_tokens=7)
    with pytest.raises(ValueError, match="non-empty"):
        eng.submit(np.zeros(0, np.int32), max_new_tokens=2)
    with pytest.raises(ValueError, match="temperature > 0"):
        eng.submit(_prompt(4, 0), max_new_tokens=2, top_k=3)
    with pytest.raises(ValueError, match="top_k"):
        eng.submit(_prompt(4, 0), max_new_tokens=2, temperature=1.0,
                   top_k=999)
    with pytest.raises(ValueError, match="top_p"):
        eng.submit(_prompt(4, 0), max_new_tokens=2, temperature=1.0,
                   top_p=1.5)
    with pytest.raises(ValueError, match="max_len"):
        make_slot_prefill(stages, CFG, CFG.seq_len + 1)
    with pytest.raises(ValueError, match="max_len"):
        make_paged_decode_step(stages, CFG, 1, 4)
    # engine-independent request plumbing
    validate_request(np.zeros(3, np.int32), 2, 0.0, None, None, 32, 16)
    r = Request(rid=0, prompt=np.zeros(3, np.int32), max_new_tokens=4)
    assert r.finished_by(7) is None


def test_drain_timeout_reports_unfinished():
    """The drain cap is a loud, structured signal: hitting ``max_ticks``
    with work still in flight raises DrainTimeout naming the abandoned
    request handles (queued AND active), never a silently shorter return
    value. Requests stay live — a later full drain finishes them."""
    from simple_distributed_machine_learning_tpu.serve import DrainTimeout

    stages, params = _model()
    eng = InferenceEngine(stages, CFG, n_slots=1)
    r1 = eng.submit(_prompt(4, 13), max_new_tokens=8, seed=71)
    r2 = eng.submit(_prompt(5, 14), max_new_tokens=4, seed=72)
    with pytest.raises(DrainTimeout) as ei:
        eng.drain(max_ticks=2)
    unfinished = ei.value.unfinished
    assert {r.rid for r in unfinished} == {r1.rid, r2.rid}
    assert str(r1.rid) in str(ei.value) and "2 ticks" in str(ei.value)
    # nothing was abandoned for real: draining on finishes both, bit-exact
    eng.drain()
    np.testing.assert_array_equal(
        r1.tokens, _solo(stages, params, r1.prompt, 8, 71))
    np.testing.assert_array_equal(
        r2.tokens, _solo(stages, params, r2.prompt, 4, 72))


def test_streaming_callback_order():
    stages, params = _model()
    eng = InferenceEngine(stages, CFG, n_slots=1)
    seen = []
    r = eng.submit(_prompt(4, 12), max_new_tokens=5, seed=70,
                   on_token=lambda req, t: seen.append((req.rid, t)))
    eng.drain()
    assert seen == [(r.rid, t) for t in r.tokens]
    assert len(seen) == 5


# ---------------------------------------------------------------------------
# paged pool: chunked prefill, prefix sharing, copy-on-write, exhaustion


def test_chunked_prefill_bitexact_across_chunk_sizes():
    """Chunk boundaries are invisible in the tokens: chunk sizes 1,
    block_size and the whole prompt (None) all reproduce the solo decode
    bit for bit, greedy and sampled."""
    stages, params = _model()
    p = _prompt(13, 120)
    # the prompt_len (whole-prompt) chunk is prefill_chunk=None — the
    # default every OTHER paged test in this file already exercises — so
    # this test pins the extremes: 1-token chunks (greedy) and block_size
    # chunks (sampled, so a key-stream crosses chunk boundaries too)
    cases = [(1, 0.0, None), (4, 0.9, 5)]
    for chunk, temperature, top_k in cases:
        want = _solo(stages, params, p, 6, 77, temperature=temperature,
                     top_k=top_k)
        eng = InferenceEngine(stages, CFG, n_slots=2, block_size=4,
                              prefill_chunk=chunk)
        r = eng.submit(p, max_new_tokens=6, seed=77,
                       temperature=temperature, top_k=top_k)
        eng.drain()
        np.testing.assert_array_equal(
            r.tokens, want, err_msg=f"chunk={chunk} t={temperature}")


def test_prefix_sharing_cow_sibling_unchanged():
    """B's prompt extends A's full prompt while A is mid-decode: B boards
    referencing A's blocks (prefix hit), B's first divergent write COPIES
    the shared tail block first, and BOTH requests still match their solo
    decodes — the sibling's tokens are untouched by the share."""
    stages, params = _model()
    pa = _prompt(13, 130)                        # bs=4: 3 full + tail fill 1
    pb = np.concatenate([pa, _prompt(4, 131)])   # strict extension
    eng = InferenceEngine(stages, CFG, n_slots=2, block_size=4)
    ra = eng.submit(pa, max_new_tokens=8, seed=140)
    for _ in range(3):                           # A prefilled + decoding
        eng.step()
    assert 0 < len(ra.tokens) < 8
    rb = eng.submit(pb, max_new_tokens=6, seed=141, temperature=0.8,
                    top_k=4)
    eng.drain()
    st = eng.pool.stats()
    assert st["prefix_hit_blocks_total"] >= 4, st   # 3 full + partial tail
    assert st["cow_copies_total"] >= 1, st
    np.testing.assert_array_equal(
        ra.tokens, _solo(stages, params, pa, 8, 140))
    np.testing.assert_array_equal(
        rb.tokens, _solo(stages, params, pb, 6, 141, temperature=0.8,
                         top_k=4))


@pytest.mark.slow
def test_identical_prompt_reuses_cached_blocks():
    """A retired request's prompt blocks stay cached (reclaimable): an
    identical later prompt shares every full block and recomputes only the
    capped tail — same tokens, fewer fresh blocks."""
    stages, params = _model()
    p = _prompt(12, 150)                         # bs=4: exactly 3 full blocks
    eng = InferenceEngine(stages, CFG, n_slots=2, block_size=4)
    r1 = eng.submit(p, max_new_tokens=4, seed=160)
    eng.drain()
    hits0 = eng.pool.stats()["prefix_hit_blocks_total"]
    r2 = eng.submit(p, max_new_tokens=4, seed=160)
    eng.drain()
    st = eng.pool.stats()
    # the cap (share at most prompt_len - 1) keeps the last position's
    # forward pass real, so only the first 2 full blocks can be shared
    assert st["prefix_hit_blocks_total"] - hits0 == 2, st
    assert r1.tokens == r2.tokens
    np.testing.assert_array_equal(
        r1.tokens, _solo(stages, params, p, 4, 160))


def test_admission_blocks_on_pool_exhaustion_and_resumes():
    """4 slots but only enough blocks for ~1 fat request: admission must
    hold requests in the queue while blocks are short (even with slots
    free), board them as retirements free blocks, and every request still
    matches its solo decode."""
    stages, params = _model()
    eng = InferenceEngine(stages, CFG, n_slots=4, block_size=4, n_blocks=12)
    hs = [eng.submit(_prompt(20, 170 + i), max_new_tokens=8, seed=180 + i)
          for i in range(4)]
    blocked = False
    max_active = 0
    while eng.busy:
        eng.step()
        max_active = max(max_active, eng.pool.n_active)
        if eng.scheduler.queue_depth and eng.pool.n_free:
            blocked = True          # slot free but blocks short -> queued
    assert blocked, "admission never blocked on block exhaustion"
    assert max_active < 4            # 27 rows/request: 12 blocks can't fit 4
    for i, h in enumerate(hs):
        assert h.state == DONE
        np.testing.assert_array_equal(
            h.tokens, _solo(stages, params, h.prompt, 8, 180 + i),
            err_msg=f"request {i}")


def test_can_admit_counts_reclaimable_shared_blocks_once():
    """Regression: a request whose shared prefix blocks sit in the
    reclaimable LRU must not have them counted BOTH as free-of-charge
    (budget discount) and as allocatable headroom (blocks_available) —
    binding revives them out of the LRU, so the old double count let
    can_admit approve a request begin_seq couldn't fund (RuntimeError out
    of engine.step() mid-serve, exactly under memory pressure + a warm
    prefix cache)."""
    pool = PagedKVPool(1, 3, 1, 20, 2, block_size=4, n_blocks=6)

    class _Req:
        def __init__(self, prompt, max_new):
            self.prompt = np.asarray(prompt, np.int32)
            self.max_new_tokens = max_new
            self.slot = None
            self.prefill_pos = None

    # A: 5-token prompt, 8 rows -> 2 blocks; registers its prefix, retires
    a = _Req(np.arange(5), 4)
    a.slot = pool.acquire(0)
    pool.bind_seq(a)
    for p in range(8):
        pool.ensure_writable(a.slot, p)
    pool.register_prefix(a.slot, a.prompt)
    pool.end_seq(a.slot)
    pool.release(a.slot)
    assert pool.blocks_cached == 2 and len(pool._free_blocks) == 4
    # C: a distinct 3-block request holds a live reservation
    c = _Req(np.full(9, 31), 4)          # 12 rows -> 3 blocks
    c.slot = pool.acquire(1)
    pool.bind_seq(c)
    assert pool.blocks_available == 3
    # B shares A's full first block (which is reclaimable, ref 0): the
    # share revives it out of the LRU, so availability for B's budget is
    # really 2 — if B's budget is 3, admission must be refused, not
    # approved-then-crashed
    b = _Req(np.concatenate([np.arange(5), np.full(7, 17)]), 5)  # 16 rows
    # budget: blocks_for(16)=4 minus 1 shared full = 3 > 2 effective
    assert not pool.can_admit(b)
    # after C frees, B fits and binds cleanly — sharing A's full first
    # block AND its registered partial tail (prefix length 5)
    pool.end_seq(c.slot)
    pool.release(c.slot)
    assert pool.can_admit(b)
    b.slot = pool.acquire(2)
    assert pool.bind_seq(b) == 5


def test_paged_pool_invariants():
    """Direct block-pool discipline: no double slot occupancy (inherited),
    no allocation without budget, no double free, reservation returned at
    end_seq, cached blocks evicted LRU only under pressure."""
    pool = PagedKVPool(2, 2, 2, 16, 4, block_size=4, n_blocks=6)
    assert pool.blocks_per_seq == 4 and pool.blocks_available == 6

    class _Req:                      # what can_admit/bind_seq consume
        def __init__(self, prompt, max_new):
            self.prompt = np.asarray(prompt, np.int32)
            self.max_new_tokens = max_new
            self.slot = None
            self.prefill_pos = None

    r = _Req(np.arange(9), 8)        # 16 rows -> 4 blocks
    assert pool.can_admit(r)
    r.slot = pool.acquire(0)
    assert pool.bind_seq(r) == 0     # nothing registered yet: no sharing
    assert pool.blocks_available == 2
    with pytest.raises(RuntimeError, match="live block table or reserv"):
        pool.begin_seq(r.slot, r.prompt, 2)
    # a second fat request fits a slot but not the block budget
    r2 = _Req(np.arange(9), 8)
    assert not pool.can_admit(r2)
    # writes allocate on demand, contiguously
    first = pool.ensure_writable(r.slot, 0)
    assert first is None and len(pool.tables[r.slot]) == 1
    with pytest.raises(RuntimeError, match="contiguously"):
        pool.ensure_writable(r.slot, 9)
    for p in range(1, 9):            # the rest of the prompt's rows
        assert pool.ensure_writable(r.slot, p) is None
    assert len(pool.tables[r.slot]) == 3
    pool.register_prefix(r.slot, r.prompt)
    used = list(pool.tables[r.slot])
    pool.end_seq(r.slot)
    pool.release(r.slot)
    assert pool.blocks_available == 6        # reservation returned
    assert pool.blocks_cached == len(used)   # registered blocks reclaimable
    with pytest.raises(RuntimeError, match="double free"):
        pool._unref_block(used[0])
    # pressure evicts the cached blocks instead of failing
    r3 = _Req(np.full(9, 99), 8)             # 16 rows -> 4 blocks, no overlap
    assert pool.can_admit(r3)
    r3.slot = pool.acquire(3)
    pool.bind_seq(r3)
    for p in range(16):
        pool.ensure_writable(r3.slot, p)
    assert pool.evictions_total >= 1 and pool.blocks_cached < len(used)
    with pytest.raises(ValueError, match="n_blocks"):
        PagedKVPool(2, 2, 2, 16, 4, block_size=4, n_blocks=3)


# ---------------------------------------------------------------------------
# metrics + simulator


def test_serve_metrics_populated(tmp_path):
    stages, _ = _model()
    metrics = ServeMetrics(outdir=str(tmp_path))
    eng = InferenceEngine(stages, CFG, n_slots=2, metrics=metrics)
    for i in range(3):
        eng.submit(_prompt(4, 40 + i), max_new_tokens=4, seed=80 + i)
    eng.drain()
    s = metrics.summary()
    assert s["requests_submitted"] == s["requests_completed"] == 3
    assert s["tokens_generated"] == 12
    assert s["ttft_ms_p50"] > 0 and s["tpot_ms_p50"] is not None
    assert 0 < s["slot_occupancy_mean"] <= 1
    assert metrics.ttft_ms.count == 3        # one TTFT per request
    assert metrics.tpot_ms.count == 9        # tokens after the first
    rec = metrics.emit(extra={"n_slots": 2})
    assert rec["kind"] == "serve" and rec["schema"] == 2
    got = json.loads(open(os.path.join(tmp_path, "metrics.jsonl"))
                     .read().splitlines()[-1])
    assert got["tokens_generated"] == 12
    prom = open(os.path.join(tmp_path, "metrics.prom")).read()
    assert "serve_tokens_generated_total 12" in prom
    assert 'serve_ttft_ms{quantile="0.5"}' in prom


@pytest.mark.slow
def test_shared_prefix_simulator_deterministic_and_shared(tmp_path):
    """``shared_prefix_len``: every simulated prompt carries one common
    seeded prefix; the paged engine serves it from shared blocks (prefix
    hits observed), the block metrics land in JSONL + Prometheus, and the
    tokens stay deterministic and bit-exact vs solo decodes."""
    stages, params = _model()
    sim = SimConfig(n_requests=6, rate=200.0, seed=5, prompt_lens=(4, 7),
                    max_new_tokens=5, shared_prefix_len=9)

    def run(outdir=None):
        eng = InferenceEngine(stages, CFG, n_slots=2, block_size=4,
                              prefill_chunk=3,
                              metrics=ServeMetrics(outdir=outdir))
        report = simulate(eng, sim)
        return (eng, report,
                [eng.requests[rid].tokens for rid in sorted(eng.requests)])

    eng, rep1, toks1 = run(outdir=str(tmp_path))
    _, rep2, toks2 = run()
    assert rep1["all_completed"] and rep2["all_completed"]
    assert toks1 == toks2
    st = eng.pool.stats()
    assert st["prefix_hit_blocks_total"] > 0, st
    # parity: the shared-prefix workload still matches per-request solo
    from simple_distributed_machine_learning_tpu.serve.simulator import (
        build_workload,
    )
    _, specs = build_workload(sim, CFG.vocab)
    for i, sp in enumerate(specs):
        assert int(sp["prompt"].shape[0]) in (13, 16)   # prefix + bucket
        want = _solo(stages, params, sp["prompt"], sp["max_new_tokens"],
                     sp["seed"], temperature=sp["temperature"],
                     top_k=sp["top_k"])
        np.testing.assert_array_equal(toks1[i], want, err_msg=f"req {i}")
    # block metrics made it into the summary, the record and the exposition
    s = eng.metrics.summary()
    for k in ("blocks_total", "blocks_in_use", "kv_bytes_resident",
              "prefix_hit_blocks", "cow_copies", "prefill_chunk_ms_p50"):
        assert k in s, k
    assert s["blocks_total"] > 0 and s["prefix_hit_blocks"] > 0
    assert s["prefill_chunk_ms_p50"] is not None   # chunk histogram fed
    rec = eng.metrics.emit()
    assert rec["prefix_hit_blocks"] == s["prefix_hit_blocks"]
    prom = open(os.path.join(tmp_path, "metrics.prom")).read()
    for name in ("serve_blocks_in_use", "serve_kv_bytes_resident",
                 "serve_prefix_hit_blocks_total",
                 'serve_prefill_chunk_ms{quantile="0.5"}'):
        assert name in prom, name
    with pytest.raises(ValueError, match="shared_prefix_len"):
        SimConfig(shared_prefix_len=-1)


def test_simulator_completes_and_is_deterministic():
    """Open-loop Poisson trace: all requests complete, and per-request
    tokens are identical across runs (scheduling cannot change outputs,
    so wall-clock admission jitter is invisible in the tokens)."""
    stages, _ = _model()
    sim = SimConfig(n_requests=6, rate=200.0, seed=5, prompt_lens=(4, 7),
                    max_new_tokens=5)

    def run():
        eng = InferenceEngine(stages, CFG, n_slots=2,
                              metrics=ServeMetrics())
        report = simulate(eng, sim)
        json.dumps(report)           # the report is pure JSON
        return report, [eng.requests[rid].tokens
                        for rid in sorted(eng.requests)]

    rep1, toks1 = run()
    rep2, toks2 = run()
    assert rep1["all_completed"] and rep2["all_completed"]
    assert toks1 == toks2
    assert rep1["tokens_generated"] == 6 * 5
    assert all(r["ttft_s"] is not None for r in rep1["requests"])
    # duration form: rate x duration expected arrivals
    assert SimConfig.from_duration(8.0, 2.0).n_requests == 16
    assert SimConfig.from_duration(1.0, 0.1).n_requests == 1
    with pytest.raises(ValueError, match="duration_s"):
        SimConfig.from_duration(8.0, 0.0)


# ---------------------------------------------------------------------------
# checkpoint -> serve, and the bench claim


def test_checkpoint_to_serve_cli(tmp_path, capsys):
    """Train a few steps, save, then --serve-sim --checkpoint-dir restores
    and serves from the trained params without retraining."""
    from simple_distributed_machine_learning_tpu.cli import main

    ckpt = str(tmp_path / "ck")
    tele = str(tmp_path / "tele")
    main(["--rank", "0", "--world_size", "1", "--model", "gpt",
          "--stages", "2", "--epochs", "1", "--dryrun", "2",
          "--batch-size", "8", "--microbatches", "2",
          "--checkpoint-dir", ckpt])
    capsys.readouterr()
    main(["--rank", "0", "--world_size", "1", "--model", "gpt",
          "--stages", "2", "--serve-sim", "4", "--serve-rate", "100",
          "--serve-slots", "2", "--serve-max-new", "4",
          "--checkpoint-dir", ckpt, "--telemetry-dir", tele])
    out = capsys.readouterr().out
    assert "| serve: restored params from" in out
    assert "Train Epoch" not in out           # no retraining
    assert "| serve: 4/4 requests completed" in out
    recs = [json.loads(ln) for ln in
            open(os.path.join(tele, "metrics.jsonl")).read().splitlines()]
    assert recs[-1]["kind"] == "serve" and recs[-1]["completed"] == 4


def test_serve_sim_fresh_init_cli(capsys):
    from simple_distributed_machine_learning_tpu.cli import main

    main(["--rank", "0", "--world_size", "1", "--model", "gpt",
          "--serve-sim", "3", "--serve-rate", "100", "--serve-slots", "2",
          "--serve-max-new", "3"])
    out = capsys.readouterr().out
    assert "| serve: fresh-initialized params" in out
    assert "| serve: 3/3 requests completed" in out


@pytest.mark.slow
def test_serve_sim_paged_flags_cli(capsys):
    """The paged serving flags end-to-end: small blocks, chunked prefill
    and a shared prefix through --serve-sim; the block-stats line reports
    prefix-share hits (> 0 — every prompt shares the system prefix)."""
    from simple_distributed_machine_learning_tpu.cli import main

    main(["--rank", "0", "--world_size", "1", "--model", "gpt",
          "--serve-sim", "4", "--serve-rate", "100", "--serve-slots", "2",
          "--serve-max-new", "3", "--serve-block-size", "4",
          "--serve-prefill-chunk", "3", "--serve-shared-prefix", "9"])
    out = capsys.readouterr().out
    assert "| serve: 4/4 requests completed" in out
    assert "prefix-share hits" in out
    hits = int(out.split(" prefix-share hits")[0].split(",")[-1].strip())
    assert hits > 0, out


def test_serve_cli_flag_validation():
    from simple_distributed_machine_learning_tpu.cli import main

    base = ["--rank", "0", "--world_size", "1", "--model", "gpt",
            "--serve-sim", "2"]
    with pytest.raises(SystemExit, match="serve-block-size"):
        main(base + ["--serve-block-size", "0"])
    with pytest.raises(SystemExit, match="serve-prefill-chunk"):
        main(base + ["--serve-prefill-chunk", "-1"])
    with pytest.raises(SystemExit, match="serve-shared-prefix"):
        main(base + ["--serve-shared-prefix", "-2"])
    with pytest.raises(SystemExit, match="leaves no room"):
        main(base + ["--serve-shared-prefix", "60"])
    with pytest.raises(SystemExit, match="serve-tp"):
        main(base + ["--serve-tp", "0"])
    with pytest.raises(SystemExit, match="divide"):
        main(base + ["--serve-tp", "3"])
    with pytest.raises(SystemExit, match="serve-spec-k"):
        main(base + ["--serve-spec-k", "1"])


def test_serve_sim_rejects_sharded_builds():
    from simple_distributed_machine_learning_tpu.cli import main

    with pytest.raises(SystemExit, match="dense single-device"):
        main(["--rank", "0", "--model", "gpt", "--serve-sim", "2",
              "--experts", "4"])
    with pytest.raises(SystemExit, match="only supported with"):
        main(["--rank", "0", "--model", "mlp", "--serve-sim", "2"])


def test_bench_continuous_beats_sequential():
    """The acceptance anchor: batched continuous decoding sustains higher
    aggregate tokens/sec than sequential one-request-at-a-time decode at
    the same model size, with TTFT/TPOT quantiles reported."""
    import bench
    from bench import measure_serving

    artifact = os.path.join(bench.REPO, "benchmarks", "serving.json")
    existed = os.path.exists(artifact)
    # rate far above service capacity so the continuous batch actually
    # fills (at low offered load both engines are arrival-bound and tie);
    # compare=False: the comparison rows have their own tests
    rows = measure_serving(rates=(2000.0,), n_requests=12, slots=4,
                           max_new=12, cfg=CFG, prompt_lens=(4, 8),
                           compare=False)
    seq = next(r for r in rows if r["config"] == "gpt_serve_sequential")
    cont = next(r for r in rows if r["config"] == "gpt_serve")
    assert seq["completed"] == cont["completed"] == 12
    assert cont["tokens_per_sec"] > seq["tokens_per_sec"], (cont, seq)
    for r in (seq, cont):
        for k in ("ttft_ms_p50", "ttft_ms_p95", "tpot_ms_p50",
                  "tpot_ms_p95"):
            assert r[k] is not None and r[k] > 0, (k, r)
    # CPU smoke shapes never write the TPU sweep's artifact
    assert os.path.exists(artifact) == existed


# ---------------------------------------------------------------------------
# speculative decoding (draft/verify) + tensor-parallel serving (ISSUE 9)
#
# The PR-5 anchor extends: a GREEDY request served speculatively emits
# bit-exactly its solo make_cached_decoder tokens — the verify rows are the
# same math the plain decode tick computes, and greedy acceptance emits the
# target's own argmaxes. TP=2 must reproduce TP=1 token-for-token (the
# all-reduce + pmean row-closing makes every shard sample identical rows).


DRAFT_CFG = dataclasses.replace(CFG, n_layers=1)
_DRAFT_STAGES = None


def _draft_model():
    global _DRAFT_STAGES
    if _DRAFT_STAGES is None:
        _DRAFT_STAGES = make_gpt_stages(jax.random.key(9), DRAFT_CFG, 1)[0]
    return _DRAFT_STAGES


def _spec_engine(slots=3, spec_k=4, draft_stages=None, draft_cfg=None,
                 **kw):
    stages, _ = _model()
    kw.setdefault("block_size", 8)
    return InferenceEngine(
        stages, CFG, n_slots=slots,
        draft_stages=(_draft_model() if draft_stages is None
                      else draft_stages),
        draft_cfg=draft_cfg or DRAFT_CFG, spec_k=spec_k, **kw)


def test_spec_greedy_bitexact_mixed_and_midflight():
    """Greedy speculative decode: mixed prompt lengths with
    queueing plus a mid-flight admission — every request's tokens equal
    its solo decode exactly (the acceptance rule's bit-exactness pin)."""
    stages, params = _model()
    eng = _spec_engine(slots=2)
    specs = [
        dict(prompt=_prompt(3, 60), max_new_tokens=9, seed=70),
        dict(prompt=_prompt(9, 61), max_new_tokens=5, seed=71),
        dict(prompt=_prompt(5, 62), max_new_tokens=8, seed=72),
    ]
    handles = [eng.submit(**s) for s in specs]
    for _ in range(3):                  # first requests mid-stream
        eng.step()
    late = dict(prompt=_prompt(6, 63), max_new_tokens=7, seed=73)
    handles.append(eng.submit(**late))
    specs.append(late)
    eng.drain()
    for h, s in zip(handles, specs):
        np.testing.assert_array_equal(
            h.tokens, _solo(stages, params, s["prompt"],
                            s["max_new_tokens"], s["seed"]))


def test_spec_eos_early_exit_parity():
    """EOS mid-verify: the emitted tokens stop at (and include) the first
    EOS even when the tick accepted a longer prefix — the retired slot's
    already-written tail K/V is unreachable (trailing-write/trash-page
    discipline), so co-residents stay bit-exact."""
    stages, params = _model()
    solo = _solo(stages, params, _prompt(5, 64), 8, 74)
    eos = int(solo[2])
    cut = int(np.where(solo == eos)[0][0]) + 1
    eng = _spec_engine(slots=2)
    r = eng.submit(_prompt(5, 64), max_new_tokens=8, seed=74, eos_id=eos)
    r2 = eng.submit(_prompt(4, 65), max_new_tokens=6, seed=75)
    eng.drain()
    assert r.finish_reason == "eos"
    assert len(r.tokens) == cut < 8
    np.testing.assert_array_equal(r.tokens, solo[:cut])
    np.testing.assert_array_equal(
        r2.tokens, _solo(stages, params, r2.prompt, 6, 75))


@pytest.mark.slow
def test_spec_preemption_parity():
    """PR-7 preemption composes with speculative decoding: a victim
    requeues mid-stream, re-prefills (target AND draft caches rebuilt) and
    continues bit-exact vs its solo decode."""
    stages, params = _model()
    eng = _spec_engine(slots=2, prefill_chunk=8)
    r1 = eng.submit(_prompt(4, 90), max_new_tokens=10, seed=90)
    r2 = eng.submit(_prompt(6, 91), max_new_tokens=8, seed=91)
    for _ in range(3):
        eng.step()
    assert 0 < len(r1.tokens) < 10
    eng.preempt(r1.rid)
    assert r1.n_preempted == 1
    eng.drain()
    np.testing.assert_array_equal(
        r1.tokens, _solo(stages, params, r1.prompt, 10, 90))
    np.testing.assert_array_equal(
        r2.tokens, _solo(stages, params, r2.prompt, 8, 91))


def test_spec_accept_all_rate_and_tokens_per_tick():
    """draft == target: every greedy proposal verifies — accept_rate pins
    at 1.0, a full-budget tick emits spec_k tokens, and the spec counters
    + shape gauges land in the metrics record."""
    stages, params = _model()
    metrics = ServeMetrics()
    eng = _spec_engine(slots=2, spec_k=4, draft_stages=stages,
                       draft_cfg=CFG, metrics=metrics)
    r = eng.submit(_prompt(5, 95), max_new_tokens=8, seed=95)
    eng.step()                               # admit + prefill + first tick
    ticks = 1
    while r.state != DONE:
        eng.step()
        ticks += 1
    np.testing.assert_array_equal(
        r.tokens, _solo(stages, params, r.prompt, 8, 95))
    # 8 tokens at 4/tick: the first tick prefills AND verifies (paged
    # whole-prompt chunk), so the whole request takes exactly 2 ticks
    assert ticks == 2, ticks
    s = metrics.summary()
    assert s["spec_accept_rate"] == 1.0
    assert s["spec_proposed_tokens"] == s["spec_accepted_tokens"] > 0
    assert s["spec_rejected_tokens"] == 0
    assert s["tp"] == 1 and s["spec_k"] == 4


@pytest.mark.slow
def test_spec_sampled_deterministic_per_seed():
    """Sampled speculative streams are deterministic per seed (the
    residual-rejection draws come from the request's own key streams) and
    a greedy co-resident still matches its solo decode exactly."""
    stages, params = _model()

    def run():
        eng = _spec_engine(slots=2, spec_k=3)
        h1 = eng.submit(_prompt(5, 96), max_new_tokens=7, seed=96,
                        temperature=0.9, top_k=6)
        h2 = eng.submit(_prompt(4, 97), max_new_tokens=6, seed=97)
        eng.drain()
        return list(h1.tokens), list(h2.tokens)

    a1, a2 = run()
    b1, b2 = run()
    assert a1 == b1
    np.testing.assert_array_equal(
        a2, _solo(stages, params, _prompt(4, 97), 6, 97))
    assert a2 == b2


def _tp_engine(tp, spec=False, **kw):
    from simple_distributed_machine_learning_tpu.parallel.mesh import (
        make_mesh,
    )
    stages, _ = _model()
    cfg = dataclasses.replace(CFG, n_tensor_parallel=tp)
    mesh = make_mesh(n_stages=1, n_data=1, n_model=tp) if tp > 1 else None
    kw.setdefault("block_size", 8)
    if spec:
        kw.update(draft_stages=_draft_model(), draft_cfg=DRAFT_CFG,
                  spec_k=4)
    return InferenceEngine(stages, cfg, n_slots=2, mesh=mesh, **kw)


def test_tp2_matches_tp1():
    """TP=2 serving on a 2-CPU-device model mesh reproduces the TP=1
    stream token-for-token: head-sharded QKV/O over the head-sharded pool
    + the collective-matmul MLP + the pmean row-closing are the same
    math."""
    stages, params = _model()
    eng = _tp_engine(2)
    assert eng.pool.tp == 2
    handles = [eng.submit(_prompt(n, 100 + n), max_new_tokens=6,
                          seed=100 + n) for n in (4, 7)]
    eng.drain()
    for h in handles:
        np.testing.assert_array_equal(
            h.tokens, _solo(stages, params, h.prompt, 6, h.seed))


@pytest.mark.slow
def test_tp2_matches_tp1_paged_and_gauge_per_shard():
    """TP=2 parity mid-stream, plus the byte accounting: the pool's
    serve_kv_bytes_resident gauge reports PER-SHARD bytes and equals the
    analyzer's per-shard prediction exactly."""
    from simple_distributed_machine_learning_tpu.analysis.programs import (
        ServeSpec,
        predict_kv_bytes_resident,
    )
    stages, params = _model()
    eng = _tp_engine(2)
    handles = [eng.submit(_prompt(n, 110 + n), max_new_tokens=6,
                          seed=110 + n) for n in (4, 7)]
    for _ in range(4):
        eng.step()
    rows = []
    for h in handles:
        if h.state != ACTIVE:
            continue
        rows.append(h.prefill_pos if h.prefill_pos is not None
                    else int(h.prompt.shape[0]) + len(h.tokens) - 1)
    sspec = ServeSpec(dataclasses.replace(CFG, n_tensor_parallel=2),
                      n_slots=2, block_size=8)
    assert (predict_kv_bytes_resident(sspec, [r for r in rows if r > 0])
            == eng.pool.stats()["kv_bytes_resident"] > 0)
    eng.drain()
    for h in handles:
        np.testing.assert_array_equal(
            h.tokens, _solo(stages, params, h.prompt, 6, h.seed))


@pytest.mark.slow
def test_tp2_with_speculation_matches_solo():
    """Both tentpole axes at once: a TP=2 target verifying a replicated
    draft's proposals still reproduces the solo stream exactly."""
    stages, params = _model()
    eng = _tp_engine(2, spec=True)
    handles = [eng.submit(_prompt(n, 120 + n), max_new_tokens=6,
                          seed=120 + n) for n in (3, 6)]
    eng.drain()
    for h in handles:
        np.testing.assert_array_equal(
            h.tokens, _solo(stages, params, h.prompt, 6, h.seed))


def test_spec_and_tp_engine_validation():
    """Constructor contracts: the half-configured speculative/TP states
    all refuse loudly (no compiles happen on these paths)."""
    stages, _ = _model()
    with pytest.raises(ValueError, match="spec_k >= 2"):
        InferenceEngine(stages, CFG, n_slots=2,
                        draft_stages=_draft_model(), draft_cfg=DRAFT_CFG,
                        spec_k=1)
    with pytest.raises(ValueError, match="BOTH draft_stages"):
        InferenceEngine(stages, CFG, n_slots=2, draft_stages=stages,
                        spec_k=4)
    with pytest.raises(ValueError, match="without draft_stages"):
        InferenceEngine(stages, CFG, n_slots=2, spec_k=4)
    with pytest.raises(ValueError, match="vocab"):
        bad = dataclasses.replace(DRAFT_CFG, vocab=CFG.vocab + 1)
        InferenceEngine(stages, CFG, n_slots=2,
                        draft_stages=_draft_model(), draft_cfg=bad,
                        spec_k=4)
    with pytest.raises(ValueError, match="mesh"):
        InferenceEngine(stages,
                        dataclasses.replace(CFG, n_tensor_parallel=2),
                        n_slots=2)
    from simple_distributed_machine_learning_tpu.models.gpt import (
        make_slot_propose,
    )
    with pytest.raises(ValueError, match="single-device"):
        make_slot_propose(stages,
                          dataclasses.replace(CFG, n_tensor_parallel=2),
                          16, 4)


def test_one_layout_no_dense_name_left():
    """The engine has one KV layout and no knob for it: ``kv_layout`` is an
    unknown keyword (a ``TypeError``, not accepted and ignored), and
    neither package exports a name of the deleted slot-row layout."""
    import inspect

    import simple_distributed_machine_learning_tpu.models as models
    import simple_distributed_machine_learning_tpu.serve as serve
    from simple_distributed_machine_learning_tpu.models import gpt
    from simple_distributed_machine_learning_tpu.serve import slots

    stages, _ = _model()
    for layout in ("paged", "dense"):
        with pytest.raises(TypeError, match="kv_layout"):
            InferenceEngine(stages, CFG, n_slots=2, kv_layout=layout)
    assert "kv_layout" not in inspect.signature(
        InferenceEngine.__init__).parameters
    gone = {"KVCachePool", "make_slot_decode_step", "make_slot_verify_step",
            "make_slot_spec_tick", "make_slot_prefill"}
    for mod in (serve, models):
        assert not gone & set(getattr(mod, "__all__", dir(mod))), mod
    assert not (gone - {"make_slot_prefill"}) & set(dir(gpt))
    # one pool class, with no base it shares with another
    pools = [c for c in vars(slots).values() if inspect.isclass(c)]
    assert pools == [PagedKVPool] and PagedKVPool.__bases__ == (object,)


def test_draft_prefill_is_single_device_and_takes_no_target_options():
    """``make_slot_prefill`` is the speculative DRAFT's program now: no
    ``mesh=`` / ``adapters=`` (the served target's options), and a
    tensor-parallel ``cfg`` refused in ``make_slot_propose``'s words."""
    import inspect

    from simple_distributed_machine_learning_tpu.models.gpt import (
        make_slot_propose,
    )
    stages, _ = _model()
    assert list(inspect.signature(make_slot_prefill).parameters) == [
        "stages", "cfg", "max_len", "cache_dtype"]
    for kw in ({"mesh": None}, {"adapters": False}):
        with pytest.raises(TypeError):
            make_slot_prefill(stages, CFG, 16, **kw)
    tp_cfg = dataclasses.replace(CFG, n_tensor_parallel=2)
    said = []
    for make, args in ((make_slot_prefill, (16,)),
                       (make_slot_propose, (16, 4))):
        with pytest.raises(ValueError, match="single-device") as e:
            make(stages, tp_cfg, *args)
        said.append(str(e.value).replace(make.__name__, "<builder>"))
    assert said[0] == said[1]
    assert make_slot_prefill(stages, CFG, 16) is make_slot_prefill(
        stages, CFG, 16)


def test_draft_programs_greedy_tokens_match_the_cached_decoder():
    """The draft's two programs over its slot rows (prefill a row, then
    ``spec_k`` scanned decode steps) propose, greedy, exactly the tokens
    ``make_cached_decoder`` decodes on the draft model, in the slot's own
    row."""
    from simple_distributed_machine_learning_tpu.models.gpt import (
        make_slot_propose,
    )
    dstages = _draft_model()
    dparams = [s.params for s in dstages]
    S, ml, K, slot = 2, 24, 4, 1
    prompt = _prompt(6, 77)
    t0 = len(prompt)
    dec = make_cached_decoder(dstages, DRAFT_CFG, t0, K + 1)
    want = np.asarray(dec(dparams, prompt[None], jax.random.key(0)))[0, t0:]
    shape = (DRAFT_CFG.n_layers, S, DRAFT_CFG.n_heads, ml,
             DRAFT_CFG.d_model // DRAFT_CFG.n_heads)
    kc, vc = jax.numpy.zeros(shape), jax.numpy.zeros(shape)
    greedy = (np.float32(0.0), np.int32(0), np.float32(2.0))
    kc, vc, tok, _ = make_slot_prefill(dstages, DRAFT_CFG, ml)(
        dparams, kc, vc, prompt[None], np.int32(slot),
        np.zeros(2, np.uint32), *greedy)
    assert int(tok) == want[0]
    toks = np.zeros(S, np.int32)
    pos = np.zeros(S, np.int32)
    toks[slot], pos[slot] = int(tok), t0
    kd = np.zeros((S, 2), np.uint32)
    kc, vc, drafts, rows, kd2 = make_slot_propose(dstages, DRAFT_CFG, ml, K)(
        dparams, kc, vc, toks, pos, kd, np.zeros(S, np.float32),
        np.zeros(S, np.int32), np.full(S, 2.0, np.float32))
    np.testing.assert_array_equal(np.asarray(drafts)[slot], want[1:])
    assert rows.shape == (S, K, DRAFT_CFG.vocab)
    np.testing.assert_array_equal(np.asarray(kd2), kd)   # greedy: no draws
    # the written rows are the slot's own: positions [0, t0 + K)
    live = np.abs(np.asarray(kc)).sum(axis=(0, 2, 4))     # [S, ml]
    assert (live[slot, :t0 + K] > 0).all() and not live[slot, t0 + K:].any()


def test_bench_spec_beats_plain_2x():
    """The acceptance gate: with draft == target (accept-all) the
    speculative engine serves >= 2x the plain engine's aggregate
    tokens-per-tick on the identical workload — deterministic tick
    counts, not wall clock, so a loaded CI box cannot flake it."""
    from bench import _measure_spec_vs_plain
    stages, _ = _model()
    [row] = _measure_spec_vs_plain(stages, CFG, slots=3, n_requests=8,
                                   max_new=16, prompt_lens=(4, 8),
                                   block_size=8)
    assert row["accept_rate"] == 1.0
    assert row["speedup_vs_plain"] >= 2.0, row
    assert row["ticks_spec"] < row["ticks_plain"]
    for k in ("wall_tokens_per_sec_spec", "wall_tokens_per_sec_plain"):
        assert row[k] > 0


def test_a_pool_without_windows_is_the_pool_it_always_was():
    """``PagedKVPool(windows=)`` (PR 44: layer kinds): without the
    argument, with ``()`` and with every layer named full the pool is one
    group and, through a request's whole life (admission, allocation on
    demand, a shared prefix and its copy-on-write, the end), field for
    field and allocation for allocation the same; the numbers below are
    the parent commit's, by hand."""
    import types

    from simple_distributed_machine_learning_tpu.serve.slots import (
        kv_block_bytes,
    )

    def drive(**kw):
        pool = PagedKVPool(2, 2, 2, 16, 4, block_size=4, n_blocks=6, **kw)
        log = []
        req = types.SimpleNamespace(prompt=np.arange(6, dtype=np.int32),
                                    max_new_tokens=3)
        log.append(pool.can_admit(req))
        s = pool.acquire(7)
        log.append(pool.begin_seq(s, req.prompt, 3))
        for p in range(6):
            log.append(pool.ensure_writable(s, p))
        pool.register_prefix(s, req.prompt)
        log.append((list(pool.tables[s]), pool.device_table(s).tolist(),
                    pool.blocks_in_use, pool.blocks_available,
                    int(pool._resv[s]), pool.bytes_resident()))
        s2 = pool.acquire(8)
        log.append(pool.begin_seq(s2, req.prompt, 3))
        log.append(pool.ensure_writable(s2, 5, oldest=5))
        log.append((list(pool.tables[s2]), pool.ref.tolist()))
        pool.end_seq(s)
        pool.end_seq(s2)
        log.append((sorted(pool._free_blocks), pool.blocks_cached,
                    pool.stats()))
        return pool, log

    plain, want = drive()
    assert want[0] is True and want[1] == 0 and want[2:8] == [None] * 6
    assert want[8] == ([1, 2], [1, 2, 0, 0], 2, 4, 0, 2 * 512)
    # the second request shares the first block and takes a tail of its own
    assert want[9] == 4 and want[10] is None
    assert want[11] == ([1, 3], [0, 2, 1, 1, 0, 0, 0])
    assert want[12] == ([3, 4, 5, 6], 2, {
        "blocks_total": 6, "blocks_in_use": 0, "blocks_cached": 2,
        "blocks_free": 4, "kv_bytes_resident": 0,
        "prefix_hit_blocks_total": 1, "cow_copies_total": 0,
        "evictions_total": 0})
    assert not plain.windowed and plain.window_groups == []
    assert plain.table_width == plain.blocks_per_seq == 4
    assert plain.bytes_per_block == kv_block_bytes(2, 2, 4, 4) == 512
    assert [k.shape for k in plain.kc] == [(7, 4, 8)] * 2
    for kw in ({"windows": ()}, {"windows": (None, None)},
               {"windows": (None, None), "n_window_blocks": 3,
                "chunk_rows": 2}):
        pool, got = drive(**kw)
        assert got == want
        assert [k.shape for k in pool.kc] == [(7, 4, 8)] * 2
        assert pool.bytes_per_block == 512 and not pool.window_groups
    with pytest.raises(ValueError, match="windows must name each"):
        PagedKVPool(2, 2, 2, 16, 4, windows=(None,))


# -- a pool without a value buffer (PagedServing.value_lanes, ISSUE 49) -------


def test_a_pool_without_a_value_buffer_holds_one_stream():
    """``PagedKVPool(value_lanes=)``: a K/V layer is ONE buffer whose row
    holds a position's values too. No value buffer is allocated (``vc`` is
    the empty tuple, which the programs donate and hand back as it is), a
    block bills one stream, and the block discipline is the pool's own:
    allocation on demand, the reservation returned, a double free raising."""
    import types

    import jax

    from simple_distributed_machine_learning_tpu.models.gpt import (
        make_paged_block_copy,
    )
    from simple_distributed_machine_learning_tpu.serve.slots import (
        kv_block_bytes,
    )
    two = PagedKVPool(2, 2, 1, 16, 128, "bfloat16", block_size=4, n_blocks=6)
    one = PagedKVPool(2, 2, 1, 16, 128, "bfloat16", block_size=4, n_blocks=6,
                      value_lanes=96, recurrent=True)
    assert one.vc == () and len(one.kc) == 2 and one.value_lanes == 96
    assert [k.shape for k in one.kc] == [k.shape for k in two.kc] == [
        (7, 4, 128)] * 2
    assert kv_block_bytes(2, 1, 4, 128, "bfloat16") == 2 * two.kc[0][0].nbytes * 2
    assert kv_block_bytes(2, 1, 4, 128, "bfloat16", streams=1) == 2048
    assert (one.bytes_per_block, two.bytes_per_block) == (2048, 4096)
    assert sum(b.nbytes for b in (*one.kc, *one.vc)) * 2 == sum(
        b.nbytes for b in (*two.kc, *two.vc))
    req = types.SimpleNamespace(prompt=np.arange(9, dtype=np.int32),
                                max_new_tokens=8)
    assert one.can_admit(req)
    s = one.acquire(0)
    assert one.begin_seq(s, req.prompt, 8) == 0
    assert one.blocks_available == 2
    for p in range(9):
        assert one.ensure_writable(s, p) is None
    assert len(one.tables[s]) == 3 and one.bytes_resident() == 3 * 2048
    used = list(one.tables[s])
    one.end_seq(s)
    one.release(s)
    assert one.blocks_available == 6 and one.bytes_resident() == 0
    with pytest.raises(RuntimeError, match="double free"):
        one._unref_block(used[0])
    # the copy-on-write program and donation take the empty tuple as it is
    kc, vc = make_paged_block_copy()(one.kc, one.vc, np.int32(2), np.int32(1))
    assert vc == () and len(kc) == 2
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(one.kc))


@pytest.mark.parametrize("kw,match", [
    ({"cache_dtype": "int8"}, "QuantKV's scale planes"),
    ({"windows": (8, None)}, "window group"),
    ({"host_cache_blocks": 2}, "no host tier"),
    ({"value_lanes": 0}, "without a value buffer"),
    ({"value_lanes": 129}, "without a value buffer"),
])
def test_a_pool_without_a_value_buffer_refuses_by_name(kw, match):
    kw = {"value_lanes": 96, **kw}
    with pytest.raises(ValueError, match=match):
        PagedKVPool(2, 2, 1, 16, 128, block_size=4, **kw)


def test_the_analyzers_resident_bytes_take_the_caches_row():
    """``predict_kv_bytes_resident`` reads the row it is handed (the
    CACHE's heads, lanes and streams), and by default GPT's."""
    from simple_distributed_machine_learning_tpu.analysis.programs import (
        ServeSpec,
        predict_kv_bytes_resident,
    )
    from simple_distributed_machine_learning_tpu.serve.slots import (
        kv_block_bytes,
    )
    sspec = ServeSpec(CFG, n_slots=2, block_size=8, cache_dtype="bfloat16")
    rows = [9, 16, 17]                      # 2 + 2 + 3 blocks
    dh = CFG.d_model // CFG.n_heads
    assert predict_kv_bytes_resident(sspec, rows) == 7 * kv_block_bytes(
        CFG.n_layers, CFG.n_heads, 8, dh, "bfloat16")
    # one latent row of 640 lanes in ONE stream over 7 layers
    assert predict_kv_bytes_resident(
        sspec, rows, n_layers=7, kv_heads=1, head_dim=640, streams=1
    ) == 7 * 7 * 8 * 640 * 2
    pool = PagedKVPool(7, 2, 1, 32, 640, "bfloat16", block_size=8,
                       value_lanes=512, recurrent=True)
    assert pool.bytes_for_rows(17) == predict_kv_bytes_resident(
        sspec, [17], n_layers=7, kv_heads=1, head_dim=640, streams=1)
