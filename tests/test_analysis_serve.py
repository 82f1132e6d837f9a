"""Serve-path preflight (analysis/programs.py + bounds.py + hostlint.py).

Four contracts pin the whole-program gate:

1. the serving-program registry lints CLEAN — and not
   vacuously: the interval pass must PROVE every PROMISE_IN_BOUNDS gather
   (zero ``unproven-promise`` findings), and the trace recursion must reach
   every program (zero ``trace.failed``);
2. contract violations the host-side pool guards against are flagged when
   declared possible — block-table entries past the pool, position counters
   past ``max_len`` — each as a ``scatter-bounds`` ERROR;
3. the retrace policy and ``_DECODE_BUILD_CACHE`` memo discipline are
   machine-checked (jaxpr-invisible, so checked at the builder/AST level);
4. the HBM model's resident-bytes prediction equals the live pool's
   ``serve_kv_bytes_resident`` gauge on multiple occupancy/block shapes.

Everything except the HBM cross-check is trace-only.
"""

import ast
import dataclasses
import functools
import os

import jax
import numpy as np
import pytest

from simple_distributed_machine_learning_tpu.analysis import analyze, spec
from simple_distributed_machine_learning_tpu.analysis.bounds import (
    Interval,
    _cmp_iv,
    _floordiv_iv,
    _mod_iv,
)
from simple_distributed_machine_learning_tpu.analysis.programs import (
    ServeSpec,
    build_registry,
    check_builder_memo,
    hbm_tick_costs,
    lint_engine,
    lint_serve,
    predict_kv_bytes_resident,
)
from simple_distributed_machine_learning_tpu.analysis.trace import (
    all_primitives,
    trace_to_jaxpr,
)
from simple_distributed_machine_learning_tpu.models.gpt import (
    DECODE_BUILDERS,
    GPTConfig,
    make_gpt_stages,
    make_paged_decode_step,
)

CFG = GPTConfig(vocab=32, seq_len=24, d_model=16, n_heads=2, n_layers=2)
BUCKETS = (4, 6, 9)


@pytest.fixture(scope="module")
def stages():
    return make_gpt_stages(jax.random.key(0), CFG, 1)[0]


def _specs():
    return [
        ServeSpec(CFG, n_slots=3, max_len=16, block_size=4, prefill_chunk=3,
                  prompt_lens=BUCKETS),
        ServeSpec(CFG, n_slots=3, max_len=16, block_size=8,
                  prefill_chunk=None, prompt_lens=BUCKETS),
        ServeSpec(CFG, n_slots=3, max_len=16, block_size=4, prefill_chunk=3,
                  prompt_lens=BUCKETS, attn_kernel="fused",
                  cache_dtype="bfloat16"),
    ]


# ---- 1. the registry lints clean at every pool shape ----------------------

@pytest.mark.parametrize("i", range(3))
def test_registry_clean_at_every_pool_shape(stages, i):
    report = lint_serve(stages, _specs()[i])
    assert report.ok(fail_on="warning"), report.format()
    # the clean pass is a PROOF, not silence: the paged gathers run in
    # PROMISE_IN_BOUNDS mode, so an unproven interval would have warned
    rules = {f.rule for f in report.findings}
    assert "scatter-bounds.unproven-promise" not in rules
    assert "trace.failed" not in rules


def test_registry_covers_every_decode_builder(stages):
    # a plain, a speculative and an adapter deployment together enumerate
    # every memoized decode builder the engine calls (plus the composite
    # ticks); make_paged_block_write is the host tier's, built by the pool
    draft_stages, dcfg = _draft()
    names = set()
    for s, draft in ((_specs()[0], None),
                     (dataclasses.replace(_specs()[0], spec_k=4,
                                          draft_cfg=dcfg), draft_stages),
                     (dataclasses.replace(_specs()[0], n_adapters=4,
                                          adapter_rank=2), None)):
        programs, _ = build_registry(stages, s, draft_stages=draft)
        names.update(p.name for p in programs)
    assert {"cached_decoder", "paged_prefill_chunk", "paged_decode",
            "paged_block_copy", "paged_tick", "draft_prefill",
            "paged_propose", "paged_verify", "paged_spec_tick",
            "adapter_bank_update", "paged_prefill_chunk_adapter",
            "paged_decode_adapter"} == names
    assert len(DECODE_BUILDERS) == 10


def test_trace_recursion_reaches_serve_primitives(stages):
    """The trace.py audit, pinned: the generic sub-jaxpr recursion reaches
    the index-bearing primitives the serve programs actually emit —
    including the scatter/gather/dynamic_update_slice INSIDE the cached
    decoder's scan — and no program fails to trace."""
    prims = set()
    for s in _specs():
        programs, _ = build_registry(stages, s)
        for prog in programs:
            plain = jax.tree.map(
                lambda a: a.sds if hasattr(a, "sds") else a, prog.args,
                is_leaf=lambda a: hasattr(a, "sds"))
            prims |= all_primitives(trace_to_jaxpr(prog.fn, *plain))
    assert {"scatter", "gather", "dynamic_update_slice", "dynamic_slice",
            "scan", "jit", "argmax", "concatenate", "iota"} <= prims


def test_hbm_table_present_and_ranked(stages):
    report = lint_serve(stages, _specs()[0])
    assert report.hbm, "HBM cost table empty"
    ops = {h.op for h in report.hbm}
    assert {"decode.kv_gather", "decode.kv_scatter",
            "prefill.kv_scatter", "cow.block_copy"} <= ops
    gather = next(h for h in report.hbm if h.op == "decode.kv_gather")
    scatter = next(h for h in report.hbm if h.op == "decode.kv_scatter")
    # the per-tick gather (full table span, every slot) dominates the
    # one-position scatter — the ratio IS the span
    assert gather.bytes_per_tick == scatter.bytes_per_tick * 16
    assert "HBM bytes per serve tick" in report.format()


def test_hbm_prefill_chunk_matches_registry_resolution():
    """The HBM table's prefill row must describe the chunk the registry
    actually built — ONE resolution rule (ServeSpec.resolved_chunk) for
    both, including the no-chunk/no-buckets default every
    ``InferenceEngine(lint=True)`` deployment hits."""
    for s in (_specs()[0], _specs()[1],
              ServeSpec(CFG, n_slots=2, max_len=16, block_size=4)):
        row = next(h for h in hbm_tick_costs(s)
                   if h.op == "prefill.kv_scatter")
        assert f"{s.resolved_chunk}-token" in row.note, (row.note, s)
    # the default deployment lints an 8-token chunk, not a 1-token one
    assert ServeSpec(CFG, n_slots=2, max_len=16,
                     block_size=4).resolved_chunk == 8


# ---- 2. contract violations are flagged ----------------------------------

def _paged_decode_args(stages, tables_hi, pos_hi, S=2, ml=16, bs=4):
    nb = -(-ml // bs) * S
    params = [jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), s.params)
        for s in stages]
    kc = (jax.ShapeDtypeStruct((nb + 1, bs, CFG.d_model),
                               np.float32),) * CFG.n_layers
    # every slot's newest token and key ride beside the pool (the
    # programs' ``state``); ``live`` says whose the step writes back
    state = ((spec((S,), np.int32, 0, CFG.vocab - 1),
              jax.ShapeDtypeStruct((S, 2), np.uint32)),)
    return (params, kc, kc, state,
            spec((S,), np.int32, 0, pos_hi),
            spec((S, -(-ml // bs)), np.int32, 0, tables_hi),
            jax.ShapeDtypeStruct((S,), np.bool_),
            jax.ShapeDtypeStruct((S,), np.float32),
            spec((S,), np.int32, 0, CFG.vocab),
            jax.ShapeDtypeStruct((S,), np.float32)), nb


def test_oob_table_and_position_flagged(stages):
    step = make_paged_decode_step(stages, CFG, 16, 4)
    args, nb = _paged_decode_args(stages, tables_hi=None, pos_hi=15)
    args = list(args)
    good_tables = spec((2, 4), np.int32, 0, nb)
    args[5] = good_tables
    assert analyze(step, *args).ok(fail_on="warning")
    # table entries one past the pool: the K/V scatter lands in (or the
    # PROMISE gather reads) someone else's block
    args[5] = spec((2, 4), np.int32, 0, nb + 1)
    report = analyze(step, *args)
    oob = [f for f in report.findings
           if f.rule == "scatter-bounds.out-of-range"]
    assert oob and not report.ok(), report.format()
    # position one past max_len: the pos-table gather and block math break
    args[5] = good_tables
    args[4] = spec((2,), np.int32, 0, 16)
    report = analyze(step, *args)
    assert not report.ok(), report.format()


def test_unbounded_inputs_warn_on_promise_gathers(stages):
    # no declared contract at all: the PROMISE_IN_BOUNDS block gathers
    # cannot be proven — the analyzer must say so rather than stay silent
    step = make_paged_decode_step(stages, CFG, 16, 4)
    args, _ = _paged_decode_args(stages, tables_hi=None, pos_hi=15)
    args = list(args)
    args[5] = jax.ShapeDtypeStruct((2, 4), np.int32)   # tables: no contract
    args[4] = spec((2,), np.int32, 0, 15)
    report = analyze(step, *args)
    assert any(f.rule == "scatter-bounds.unproven-promise"
               for f in report.findings), report.format()
    assert report.ok()      # WARNING: unproven, not proven-broken


def test_double_donation_flagged():
    # one buffer aliased into two parameters of a call that donates one of
    # them: the non-donated alias reads pages the donation may reuse
    import functools

    @functools.partial(jax.jit, donate_argnums=(0,))
    def inner(a, b):
        return a + b

    def tick(x):
        return inner(x, x)

    report = analyze(tick, jax.ShapeDtypeStruct((4,), np.float32))
    assert any(f.rule == "donation.double-donation"
               for f in report.findings), report.format()
    # distinct buffers: clean
    clean = analyze(lambda x, y: inner(x, y),
                    jax.ShapeDtypeStruct((4,), np.float32),
                    jax.ShapeDtypeStruct((4,), np.float32))
    assert not any(f.rule == "donation.double-donation"
                   for f in clean.findings), clean.format()


def test_while_cond_gathers_not_vacuously_clean(stages):
    # an index-bearing PROMISE read in a while-loop PREDICATE is a program
    # too: the bounds pass must walk cond_jaxpr, not just the body
    def f(table, idx):
        def cond(c):
            i, _ = c
            return table.at[i].get(mode="promise_in_bounds") > 0
        def body(c):
            i, s = c
            return i + 1, s + 1
        return jax.lax.while_loop(cond, body, (idx, 0))

    t = jax.ShapeDtypeStruct((8,), np.int32)
    unproven = analyze(f, t, jax.ShapeDtypeStruct((), np.int32))
    assert any(f_.rule == "scatter-bounds.unproven-promise"
               for f_ in unproven.findings), unproven.format()


def test_no_contracts_at_all_still_runs_bounds(stages):
    # zero analysis.spec args anywhere: the bounds pass must still walk
    # the program (rules.py runs check_bounds unconditionally) — a
    # PROMISE_IN_BOUNDS gather in a spec-free analyze() call is the
    # vacuously-clean hole, not a clean proof
    step = make_paged_decode_step(stages, CFG, 16, 4)
    args, _ = _paged_decode_args(stages, tables_hi=None, pos_hi=15)
    plain = jax.tree.map(
        lambda a: (jax.ShapeDtypeStruct(a.sds.shape, a.sds.dtype)
                   if hasattr(a, "sds") else a),
        list(args), is_leaf=lambda a: hasattr(a, "sds"))
    report = analyze(step, *plain)
    assert any(f.rule == "scatter-bounds.unproven-promise"
               for f in report.findings), report.format()
    assert report.ok(), report.format()


# ---- 3. retrace policy + memo discipline ---------------------------------

def test_real_builders_are_memoized(stages):
    # the speculative builders take the draft build (same tiny model here)
    extra = {
        "make_slot_propose": lambda m: m(stages, CFG, 16, 4),
        "make_paged_verify_step": lambda m: m(stages, CFG, 16, 4, 4),
        "make_paged_spec_tick": lambda m: m(stages, CFG, stages, CFG, 16,
                                            4, 4),
    }
    for name, make in DECODE_BUILDERS.items():
        if name in extra:
            build = functools.partial(extra[name], make)
        elif name == "make_cached_decoder":
            def build():
                return make(stages, CFG, 4, 4)
        elif name in ("make_paged_block_copy", "make_paged_block_write",
                      "make_adapter_bank_update"):
            build = make
        elif "paged" in name:
            def build():
                return make(stages, CFG, 16, 4)
        else:
            def build():
                return make(stages, CFG, 16)
        assert check_builder_memo(name, build) == [], name


def test_unbounded_retrace_flagged_bounded_clean(stages):
    # no chunk: the final (= whole-prompt) chunk's length is the trace key
    unbounded = ServeSpec(CFG, n_slots=2, max_len=16, block_size=4,
                          prefill_chunk=None)
    report = lint_serve(stages, unbounded)
    assert "make_paged_prefill_chunk" in [
        f.where for f in report.findings
        if f.rule == "retrace-explosion.unbounded-trace-key"], report.format()
    assert report.ok()                      # WARNING-level: gates don't trip
    bounded = dataclasses.replace(unbounded, prompt_lens=BUCKETS)
    assert lint_serve(stages, bounded).ok(fail_on="warning")
    # a prefill_chunk bounds the SERVING shapes even with no
    # buckets — the only remaining warning is the cached (solo-parity)
    # decoder, whose per-(prompt, n_new) retrace is caller-owned
    chunked = ServeSpec(CFG, n_slots=2, max_len=16, block_size=4,
                        prefill_chunk=4)
    report = lint_serve(stages, chunked)
    assert report.ok()
    unbounded_rules = [f for f in report.findings
                       if f.rule == "retrace-explosion.unbounded-trace-key"]
    assert [f.where for f in unbounded_rules] == ["make_cached_decoder"]


def test_hostlint_clean_and_pinned_to_gpt():
    from simple_distributed_machine_learning_tpu.analysis.hostlint import (
        DECODE_BUILDER_NAMES,
        lint_repo,
    )
    assert set(DECODE_BUILDER_NAMES) == set(DECODE_BUILDERS)
    report = lint_repo()
    assert report.ok(fail_on="warning"), report.format()


def test_hostlint_flags_bypass_and_unmemoized(tmp_path):
    from simple_distributed_machine_learning_tpu.analysis.hostlint import (
        _lint_call_sites,
        lint_builder_definitions,
    )
    bad = tmp_path / "bad_site.py"
    bad.write_text(
        "import jax\n"
        "from simple_distributed_machine_learning_tpu.models.gpt import (\n"
        "    _build_cached_decoder, _DECODE_BUILD_CACHE)\n"
        "dec = _build_cached_decoder(8, 4, 4, 2, 8, None, 0.0, None, None)\n"
        "_DECODE_BUILD_CACHE.clear()\n"
        "step = jax.jit(lambda x: x)\n")
    rules = {f.rule for f in _lint_call_sites(str(bad), allow_jit=False)}
    assert {"hostlint.builder-bypass", "hostlint.cache-poke",
            "hostlint.raw-jit-in-serve"} <= rules
    # every other spelling of a raw jit must be caught too — aliased
    # module, from-import, renamed from-import, pjit
    for src in ("from jax import jit\nstep = jit(lambda x: x)\n",
                "from jax import jit as q\nstep = q(lambda x: x)\n",
                "import jax as j\nstep = j.jit(lambda x: x)\n",
                "from jax.experimental.pjit import pjit\n"
                "step = pjit(lambda x: x)\n"):
        aliased = tmp_path / "aliased_site.py"
        aliased.write_text(src)
        got = {f.rule for f in _lint_call_sites(str(aliased),
                                                allow_jit=False)}
        assert "hostlint.raw-jit-in-serve" in got, src
    # a gpt.py whose builder dropped the memo
    fake_gpt = tmp_path / "gpt.py"
    fake_gpt.write_text(
        "def make_cached_decoder(stages, cfg):\n"
        "    import jax\n"
        "    return jax.jit(lambda p: p)\n")
    findings = lint_builder_definitions(str(fake_gpt))
    assert any(f.rule == "hostlint.unmemoized-builder" for f in findings)


def test_hostlint_cli_exit_codes():
    from simple_distributed_machine_learning_tpu.analysis.__main__ import (
        main,
    )
    assert main(["--hostlint"]) == 0


def test_hostlint_runs_without_jax():
    """The AST lint's reason to exist is running when jax is broken or
    absent (the CI hostlint step sets no backend): importing and running
    it must not pull jax through the package __init__ chain. Simulated by
    purging jax from sys.modules and blocking any re-import."""
    import subprocess
    import sys

    prog = (
        "import sys\n"
        "for m in [k for k in sys.modules"
        " if k == 'jax' or k.startswith(('jax.', 'jaxlib'))]:\n"
        "    del sys.modules[m]\n"
        "class B:\n"  # find_spec: the one meta-path hook every
        "    def find_spec(self, name, path=None, target=None):\n"
        "        if name == 'jax' or name.startswith(('jax.', 'jaxlib')):\n"
        "            raise ImportError('blocked: ' + name)\n"
        "sys.meta_path.insert(0, B())\n"
        "try:\n"           # the blocker must itself work on this python,
        "    import jax\n"  # or the test is vacuous
        "except ImportError:\n"
        "    pass\n"
        "else:\n"
        "    print('BLOCKER INERT'); sys.exit(3)\n"
        "from simple_distributed_machine_learning_tpu.analysis.__main__ "
        "import main\n"
        "sys.exit(main(['--hostlint']))\n"
    )
    proc = subprocess.run([sys.executable, "-c", prog],
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)


# ---- 4. HBM model vs the live pool's gauge -------------------------------

@pytest.mark.parametrize("block_size,n_reqs,prompts", [
    (4, 2, (5, 9)),
    (8, 3, (4, 6, 9)),
    (4, 3, (3, 3, 11)),
])
def test_predicted_resident_bytes_match_gauge(stages, block_size, n_reqs,
                                              prompts):
    from simple_distributed_machine_learning_tpu.serve import (
        InferenceEngine,
    )
    rng = np.random.default_rng(3)
    ml = 20
    engine = InferenceEngine(stages, CFG, n_slots=n_reqs, max_len=ml,
                             block_size=block_size)
    handles = []
    for i, plen in enumerate(prompts):
        # distinct first tokens: no prefix sharing, so the no-sharing
        # model is exact
        prompt = rng.integers(0, CFG.vocab, plen).astype(np.int32)
        prompt[0] = i
        handles.append(engine.submit(prompt, max_new_tokens=6, seed=i))
    sspec = ServeSpec(CFG, n_slots=n_reqs, max_len=ml,
                      block_size=block_size)
    for _ in range(n_reqs + 2):      # prefills (one per tick) + decodes
        engine.step()
        # the next tick's decode is already dispatched (the engine's tick
        # ahead): its slots have that position's row allocated
        in_flight = engine._ahead[0] if engine._ahead else ()
        rows = []
        for h in handles:
            if h.state != "active":
                continue
            if h.prefill_pos is not None:        # mid-prefill
                rows.append(h.prefill_pos)
            else:
                rows.append(int(h.prompt.shape[0]) + len(h.tokens) - 1
                            + (h.rid in in_flight))
        predicted = predict_kv_bytes_resident(sspec,
                                              [r for r in rows if r > 0])
        assert predicted == engine.pool.stats()["kv_bytes_resident"], (
            block_size, rows, engine.pool.stats())
    assert engine.pool.stats()["kv_bytes_resident"] > 0
    # the static per-tick model agrees with the pool's block geometry
    gather = next(h for h in hbm_tick_costs(sspec)
                  if h.op == "decode.kv_gather")
    span = -(-ml // block_size) * block_size
    assert gather.bytes_per_tick == (
        n_reqs * engine.pool.bytes_per_block * span // block_size)


# ---- sharded + speculative registry (ISSUE 9) ----------------------------

def _draft():
    dcfg = dataclasses.replace(CFG, n_layers=1)
    return make_gpt_stages(jax.random.key(1), dcfg, 1)[0], dcfg


def test_registry_clean_speculative_both_layouts(stages):
    """The draft propose scan, the batched verify and the FUSED composite
    tick join the registry and lint clean — the proof, not silence, rule
    of contract 1 extends to every speculative program."""
    draft_stages, dcfg = _draft()
    base = ServeSpec(CFG, n_slots=3, max_len=16, block_size=4,
                     prefill_chunk=3, prompt_lens=BUCKETS, spec_k=4,
                     draft_cfg=dcfg)
    for s in (base, dataclasses.replace(base, attn_kernel="fused",
                                        cache_dtype="int8")):
        report = lint_serve(stages, s, draft_stages=draft_stages)
        assert report.ok(fail_on="warning"), report.format()
        rules = {f.rule for f in report.findings}
        assert "trace.failed" not in rules
        assert "scatter-bounds.unproven-promise" not in rules
        programs, _ = build_registry(stages, s, draft_stages=draft_stages)
        names = {p.name for p in programs}
        assert {"draft_prefill", "paged_propose", "paged_verify",
                "paged_spec_tick"} <= names, names
    # the draft prefills a whole sequence at once: with no buckets declared
    # that trace key is flagged like the cached decoder's
    report = lint_serve(stages, dataclasses.replace(base, prompt_lens=None),
                        draft_stages=draft_stages)
    assert "make_slot_prefill" in [
        f.where for f in report.findings
        if f.rule == "retrace-explosion.unbounded-trace-key"]


def test_lint_serve_requires_the_draft_build():
    _, dcfg = _draft()
    s = ServeSpec(CFG, n_slots=2, max_len=16, block_size=4,
                  prompt_lens=BUCKETS, spec_k=4, draft_cfg=dcfg)
    with pytest.raises(ValueError, match="draft_stages"):
        lint_serve(None, s)


def test_registry_clean_tp2(stages):
    """TP-sharded serving programs on a live 2-device model mesh: the
    mesh-axis and scatter-bounds rules walk the sharded block gathers of
    the exact shard_map twins the TP engine runs — clean under both
    attention kernels, and TP without the mesh is refused."""
    from simple_distributed_machine_learning_tpu.parallel.mesh import (
        make_mesh,
    )
    cfg2 = dataclasses.replace(CFG, n_tensor_parallel=2)
    mesh = make_mesh(n_stages=1, n_data=1, n_model=2)
    base = ServeSpec(cfg2, n_slots=3, max_len=16, block_size=4,
                     prefill_chunk=3, prompt_lens=BUCKETS)
    for s in (base, dataclasses.replace(base, attn_kernel="fused")):
        report = lint_serve(stages, s, mesh=mesh)
        assert report.ok(fail_on="warning"), report.format()
        assert "trace.failed" not in {f.rule for f in report.findings}
    with pytest.raises(ValueError, match="mesh"):
        lint_serve(stages, base)


def test_hbm_per_shard_bytes(stages):
    """Under TP the HBM model reports PER-SHARD bytes: every K/V stream
    row halves at tp=2, the resident-bytes prediction halves, and the
    prediction still equals a live tp-declared pool's gauge exactly."""
    from simple_distributed_machine_learning_tpu.serve.slots import (
        PagedKVPool,
    )
    cfg2 = dataclasses.replace(CFG, n_tensor_parallel=2)
    s1 = ServeSpec(CFG, n_slots=3, max_len=16, block_size=4,
                   prefill_chunk=3)
    s2 = dataclasses.replace(s1, cfg=cfg2)
    c1 = {h.op: h.bytes_per_tick for h in hbm_tick_costs(s1)}
    c2 = {h.op: h.bytes_per_tick for h in hbm_tick_costs(s2)}
    assert set(c1) == set(c2)
    for op in c1:
        assert c2[op] * 2 == c1[op], op
    rows = [5, 9]
    assert (predict_kv_bytes_resident(s2, rows) * 2
            == predict_kv_bytes_resident(s1, rows))
    # the pool's own per-shard accounting is the same rule, so the gauge
    # parity of contract 4 carries over shard-for-shard (a LIVE tp=2
    # engine's gauge is cross-checked in tests/test_serve.py)
    kw = dict(n_layers=CFG.n_layers, n_slots=3, n_heads=CFG.n_heads,
              max_len=16, head_dim=CFG.d_model // CFG.n_heads,
              block_size=4)
    assert (PagedKVPool(**kw, tp=2).bytes_per_block * 2
            == PagedKVPool(**kw).bytes_per_block)
    with pytest.raises(ValueError, match="divide"):
        PagedKVPool(**kw, tp=3)


# ---- engine + CLI wiring -------------------------------------------------

def test_engine_lint_true_constructs_and_gates(stages, monkeypatch):
    from simple_distributed_machine_learning_tpu.serve import (
        InferenceEngine,
    )
    eng = InferenceEngine(stages, CFG, n_slots=2, max_len=16, block_size=4,
                          prefill_chunk=3, lint=True)
    assert lint_engine(eng, prompt_lens=BUCKETS).ok()
    monkeypatch.setenv("SDML_LINT_INJECT", "unit")
    with pytest.raises(RuntimeError, match="preflight found ERROR"):
        InferenceEngine(stages, CFG, n_slots=2, max_len=16, block_size=4,
                        prefill_chunk=3, lint=True)


def test_serve_cli_gate_exit_codes(monkeypatch):
    from simple_distributed_machine_learning_tpu.analysis.__main__ import (
        main,
    )
    assert main(["--serve"]) == 0
    monkeypatch.setenv("SDML_LINT_INJECT", "unit")
    assert main(["--serve"]) == 1


# ---- bounds arithmetic unit checks ---------------------------------------

def test_interval_arithmetic_corners():
    assert _floordiv_iv(Interval(-5, 11), Interval(4, 4)) == Interval(-2, 2)
    assert _mod_iv(Interval(-5, 11), Interval(4, 4)) == Interval(0, 3)
    assert _cmp_iv("lt", Interval(0, 3), Interval(4, 9)) == Interval(1, 1)
    assert _cmp_iv("lt", Interval(4, 9), Interval(0, 4)) == Interval(0, 0)
    assert _cmp_iv("lt", Interval(0, 5), Interval(3, 4)) == Interval(0, 1)
    assert _cmp_iv("ge", Interval(0, 5), Interval(0, 0)) == Interval(1, 1)


def test_narrowing_cast_drops_the_proof():
    # int32 -> int8 WRAPS at runtime for values past 127: the declared
    # interval must not survive the cast and falsely certify a PROMISE
    # gather — a fitting cast keeps the proof
    def f(x, i):
        j = jax.lax.convert_element_type(i, np.int8)
        return x.at[j].get(mode="promise_in_bounds")

    x = jax.ShapeDtypeStruct((100,), np.float32)
    wrapping = analyze(f, x, spec((), np.int32, 0, 200))
    assert any(f_.rule == "scatter-bounds.unproven-promise"
               for f_ in wrapping.findings), wrapping.format()
    fitting = analyze(f, x, spec((), np.int32, 0, 90))
    assert fitting.ok(fail_on="warning"), fitting.format()


def test_bounds_prove_simple_program():
    def f(table, idx):
        return table[idx // 4]

    t = spec((3,), np.int32, 0, 2)
    good = analyze(f, t, spec((3,), np.int32, 0, 11))
    assert good.ok(fail_on="warning"), good.format()
    bad = analyze(f, t, spec((3,), np.int32, 0, 12))
    assert any(f_.rule == "scatter-bounds.out-of-range"
               for f_ in bad.findings), bad.format()


def test_half_declared_contract_degrades_to_unproven():
    """A one-sided spec (only ``lo`` or only ``hi``) proves nothing about
    the unbounded side, so it must get the same not-proven treatment as no
    contract at all — a WARNING at worst, never a gating ERROR. A finite
    bound that puts the WHOLE interval outside the operand is still a
    provable violation."""
    def f(x, i):
        return x[i]

    x = jax.ShapeDtypeStruct((4, 8), np.float32)
    half = analyze(f, x, spec((3,), np.int32, lo=0))
    assert half.ok(), half.format()
    assert any(f_.rule == "scatter-bounds.unproven-promise"
               for f_ in half.findings), half.format()
    # lo=10 into a 4-row operand: every possible value is out of range,
    # provable even though hi is unbounded
    beyond = analyze(f, x, spec((3,), np.int32, lo=10))
    assert any(f_.rule == "scatter-bounds.out-of-range"
               for f_ in beyond.findings), beyond.format()


def test_scatter_variant_primitives_checked():
    """``.at[].min()``/``.at[].max()`` lower to scatter-min/scatter-max
    (hyphenated primitive names) — they must hit the same bounds check as
    plain scatter, not fall through to the generic unknown handler."""
    x = jax.ShapeDtypeStruct((4,), np.float32)
    for op in ("min", "max"):
        def f(x, i, _op=op):
            return getattr(x.at[i], _op)(3.0)

        bad = analyze(f, x, spec((), np.int32, 0, 9))
        assert any(f_.rule == "scatter-bounds.out-of-range"
                   for f_ in bad.findings), (op, bad.format())
        good = analyze(f, x, spec((), np.int32, 0, 3))
        assert good.ok(fail_on="warning"), (op, good.format())


# ---- the serve supervisor's degraded-fallback layout ----------------------

def test_degraded_spec_matches_engine_factory_rule(stages):
    """``degraded_spec`` and ``serve/supervisor.py::engine_factory`` must
    apply the SAME fallback transform (spec off, tp 1, the kernel, the
    quantised cache and the host tier off, the pool kept) — the
    registry's degraded entry is only a proof if it describes the engine a
    chaos-stressed supervisor actually rebuilds."""
    from simple_distributed_machine_learning_tpu.analysis.programs import (
        degraded_spec,
        engine_spec,
    )
    from simple_distributed_machine_learning_tpu.serve.supervisor import (
        engine_factory,
    )

    draft_stages, draft_cfg = _draft()
    full = ServeSpec(CFG, n_slots=3, max_len=16, block_size=4, n_blocks=9,
                     prefill_chunk=3, prompt_lens=BUCKETS, spec_k=4,
                     draft_cfg=draft_cfg, attn_kernel="fused",
                     cache_dtype="int8", host_cache_blocks=4,
                     prefetch_ticks=2)
    d = degraded_spec(full)
    eng = engine_factory(stages, CFG, n_slots=3, max_len=16, block_size=4,
                         n_blocks=9, prefill_chunk=3, attn_kernel="fused",
                         cache_dtype="int8", host_cache_blocks=4,
                         prefetch_ticks=2, draft_stages=draft_stages,
                         draft_cfg=draft_cfg, spec_k=4)(True)
    assert not eng.speculative and eng.tp == 1 and eng.pool.n_slots == 3
    # field for field: the spec the analyzer derives is the engine's own
    assert dataclasses.replace(
        engine_spec(eng, BUCKETS), cache_dtype=None) == dataclasses.replace(
            d, cache_dtype=None)
    assert d.cache_dtype is None and eng.pool.cache_dtype == np.float32
    # and the degraded ENGINE's own lint (the exact programs it built)
    # is clean: zero trace.failed, zero unproven-promise
    report = lint_engine(eng, prompt_lens=BUCKETS)
    assert report.ok(fail_on="warning"), report.format()
    rules = {f.rule for f in report.findings}
    assert "trace.failed" not in rules
    assert "scatter-bounds.unproven-promise" not in rules


def test_degraded_spec_of_a_fused_paged_spec_lints_clean(stages):
    """The fallback of a plain (recurrent-free) deployment on the fused
    kernel: the kernel is dropped, the pool's geometry is kept, and the
    registry proves that layout clean — no kernel rows left to reconcile."""
    from simple_distributed_machine_learning_tpu.analysis.programs import (
        degraded_spec,
    )
    full = ServeSpec(CFG, n_slots=3, max_len=16, block_size=4, n_blocks=10,
                     prefill_chunk=3, prompt_lens=BUCKETS,
                     attn_kernel="fused", cache_dtype="bfloat16")
    d = degraded_spec(full)
    assert d == dataclasses.replace(full, attn_kernel="dense")
    report = lint_serve(stages, d)
    assert report.ok(fail_on="warning"), report.format()
    rules = {f.rule for f in report.findings}
    assert "trace.failed" not in rules
    assert "scatter-bounds.unproven-promise" not in rules
    assert not [h for h in report.hbm if h.op == "kernel.kv_stream"]
    assert "decode.kv_attn_reread" in {h.op for h in report.hbm}


def test_engine_imports_only_what_speculation_and_cow_need():
    """``serve/engine.py`` reaches a target model's programs through
    ``cfg.paged_serving()`` alone. What it still imports from ``models/`` by
    name is pinned here: from ``models/serving.py`` the seat constants and
    two dtype helpers, from ``models/gpt.py`` the block copy
    (copy-on-write), the speculative verify / tick / draft builders and
    the TP weight packing. A decode or prefill builder for the target
    cannot come back unnoticed."""
    import simple_distributed_machine_learning_tpu.serve.engine as engine
    with open(engine.__file__) as f:
        tree = ast.parse(f.read())
    names = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and ".models" in (
                node.module or ""):
            names.setdefault(node.module.rsplit(".", 1)[1], set()).update(
                a.name for a in node.names)
    assert names == {
        "serving": {"SEAT_NONE", "SEAT_SAMPLE", "storage_dtype",
                    "is_quantized_dtype"},
        "gpt": {"make_paged_block_copy", "make_paged_verify_step",
                "make_paged_spec_tick", "make_slot_prefill",
                "make_slot_propose", "pack_tp_serve_params"}}
    builders = names["gpt"] & set(DECODE_BUILDERS)
    assert builders == {"make_paged_block_copy", "make_paged_verify_step",
                        "make_paged_spec_tick", "make_slot_prefill",
                        "make_slot_propose"}


def _package_imports():
    """``(importing module, imported module, name)`` of every ``from ...
    import name`` inside the package, modules as dotted paths below it
    (``models.gpt``, ``serve.engine``), by ``ast``."""
    import simple_distributed_machine_learning_tpu as package
    root = os.path.dirname(package.__file__)
    prefix = package.__name__ + "."
    found = []
    for folder, _dirs, files in sorted(os.walk(root)):
        for fname in sorted(f for f in files if f.endswith(".py")):
            path = os.path.join(folder, fname)
            here = os.path.relpath(path, root)[:-3].replace(os.sep, ".")
            with open(path) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if isinstance(node, ast.ImportFrom) and (
                        node.module or "").startswith(prefix):
                    there = node.module[len(prefix):]
                    found += [(here, there, a.name) for a in node.names]
    return found


@pytest.mark.parametrize("rule", ["no-private-name", "families-serving-only",
                                  "serving-imports-no-family"])
def test_direction_of_imports_around_the_serving_contract(rule):
    """What a paged serving program is made of lives in
    ``models/serving.py``, and the imports say so: (a) nothing in the
    package imports an underscored name from a module of ``models/`` (a
    name two modules need is an interface and loses the underscore; the
    three names ``tests/bench_cells/`` patches are imported public and
    bound private), (b) the five families after GPT import from
    ``models/`` nothing but ``models.serving``, (c) ``models/serving.py``
    imports no family and nothing from ``serve/``."""
    imports = _package_imports()
    assert len(imports) > 500        # the walk found the package
    if rule == "no-private-name":
        assert [(here, there, name) for here, there, name in imports
                if there.startswith("models.") and name.startswith("_")] == []
    elif rule == "families-serving-only":
        for family in ("jamba", "sdar", "nemotron_h", "zaya", "cohere2"):
            took = {there for here, there, _ in imports
                    if here == f"models.{family}"
                    and (there + ".").startswith("models.")}
            assert took == {"models.serving"}, family
    else:
        took = {there for here, there, _ in imports
                if here == "models.serving"}
        assert took and not [t for t in took if t.startswith(
            ("models.", "serve.")) or t in ("models", "serve")], took


def test_default_registry_includes_clean_degraded_entry():
    """The CI ``--serve`` sweep carries an explicitly named degraded-
    fallback report, and it is clean — the fallback that only exists on
    the worst day is proven on every PR."""
    from simple_distributed_machine_learning_tpu.analysis.programs import (
        default_registry_reports,
    )

    reports = default_registry_reports()
    degraded = [r for r in reports if "degraded" in r.name]
    assert len(degraded) == 1
    r = degraded[0]
    assert r.ok(fail_on="warning"), r.format()
    rules = {f.rule for f in r.findings}
    assert "trace.failed" not in rules
    assert "scatter-bounds.unproven-promise" not in rules


# ---- ISSUE 16: inside the kernel box ------------------------------------
#
# The pallas_call rule family (analysis/kernels.py). The serve registry
# already proves the REAL kernels clean above; here the corners of the
# index-map interval arithmetic, the scalar-prefetch contract seeding and
# the hostlint/fault-drill satellites get their own pins.


def _lint_pallas(kernel, grid, in_specs, out_specs, out_shape, *args,
                 **contracts):
    from jax.experimental import pallas as pl

    def prog(*a):
        return pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                              out_specs=out_specs, out_shape=out_shape,
                              interpret=True, **contracts.pop("pl_kw", {}))(*a)

    return analyze(prog, *args, name="kernel_corner")


def test_kernel_floordiv_and_rem_index_maps_prove_clean():
    """i//2 and i%3 over grid axes: the interval corners PR 8 pinned on
    gather indices must also carry proofs THROUGH BlockSpec index maps —
    both derived maps stay inside a (3, 6) block grid for grid=(6,)."""
    from jax.experimental import pallas as pl

    def kern(x_ref, y_ref, o_ref):
        o_ref[...] = x_ref[...] + y_ref[...]

    x = jax.ShapeDtypeStruct((3, 8), np.float32)   # blocks (1,8), rows i//2
    y = jax.ShapeDtypeStruct((3, 8), np.float32)   # rows i%3
    report = _lint_pallas(
        kern, (6,),
        [pl.BlockSpec((1, 8), lambda i: (i // 2, 0)),
         pl.BlockSpec((1, 8), lambda i: (i % 3, 0))],
        pl.BlockSpec((1, 8), lambda i: (i % 3, 0)),
        jax.ShapeDtypeStruct((3, 8), np.float32),
        x, y)
    bad = [f for f in report.findings if f.family.startswith("kernel-")]
    assert not bad, report.format()


def test_kernel_oob_floordiv_index_map_is_proved_escaping():
    """grid=(8,) with rows i//2 over a 3-row operand REACHES row 3: a
    finite counterexample, so kernel-oob (ERROR), not merely unproven."""
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    report = _lint_pallas(
        kern, (8,),
        [pl.BlockSpec((1, 8), lambda i: (i // 2, 0))],
        pl.BlockSpec((1, 8), lambda i: (i % 8, 0)),
        jax.ShapeDtypeStruct((8, 8), np.float32),
        jax.ShapeDtypeStruct((3, 8), np.float32))
    assert any(f.rule == "kernel-oob.index-map" for f in report.findings), (
        report.format())
    assert not report.ok()


def test_kernel_scalar_prefetch_contract_seeds_the_proof():
    """A PrefetchScalarGridSpec block-table deref is only provable when the
    caller DECLARES the table's range (analysis.spec): with the contract
    the map proves clean, without it the same kernel is kernel-unproven —
    never silently ok."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kern(tbl_ref, x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def prog(tbl, x):
        gspec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(4,),
            in_specs=[pl.BlockSpec((1, 8), lambda i, tbl: (tbl[i], 0))],
            out_specs=pl.BlockSpec((1, 8), lambda i, tbl: (i, 0)))
        return pl.pallas_call(
            kern, grid_spec=gspec, interpret=True,
            out_shape=jax.ShapeDtypeStruct((4, 8), np.float32))(tbl, x)

    x = jax.ShapeDtypeStruct((5, 8), np.float32)
    proven = analyze(prog, spec((4,), np.int32, 0, 4), x)
    assert not [f for f in proven.findings
                if f.family.startswith("kernel-")], proven.format()
    unproven = analyze(prog, jax.ShapeDtypeStruct((4,), np.int32), x)
    assert any(f.rule == "kernel-unproven.index-map"
               for f in unproven.findings), unproven.format()
    # a contract that ADMITS escape is an ERROR, not just unproven
    escaping = analyze(prog, spec((4,), np.int32, 0, 9), x)
    assert any(f.rule == "kernel-oob.index-map"
               for f in escaping.findings), escaping.format()


def test_a_kernels_own_copies_are_proven_from_the_table_contract():
    """``paged_attention`` takes the pool unblocked and copies a slot's
    blocks itself: no index map to evaluate, so the body is walked and
    every ``dma_start`` that reads the pool is held to its shape. With the
    table's contract the copies prove clean; without it the kernel is
    reported BY NAME as unproven, never silently ok; a contract that
    admits a block past the pool is an ERROR."""
    from simple_distributed_machine_learning_tpu.ops.paged_attention import (
        paged_attention,
    )
    S, H, dh, bs, NB, n_blocks = 2, 2, 64, 8, 3, 5

    def attend(q, kc, vc, tables, qpos):
        return paged_attention(q, kc, vc, tables, qpos, block_size=bs)

    q = jax.ShapeDtypeStruct((S, H, 1, dh), np.float32)
    kv = jax.ShapeDtypeStruct((n_blocks + 1, bs, H * dh), np.float32)
    qpos = spec((S, 1), np.int32, 0, NB * bs - 1)
    proven = analyze(attend, q, kv, kv,
                     spec((S, NB), np.int32, 0, n_blocks), qpos)
    assert not [f for f in proven.findings
                if f.family.startswith("kernel-")], proven.format()
    unproven = analyze(attend, q, kv, kv,
                       jax.ShapeDtypeStruct((S, NB), np.int32), qpos)
    found = [f for f in unproven.findings
             if f.rule == "kernel-unproven.dma-source"]
    assert found and all("paged_attention" in f.message for f in found), (
        unproven.format())
    assert not unproven.errors
    escaping = analyze(attend, q, kv, kv,
                       spec((S, NB), np.int32, 0, n_blocks + 1), qpos)
    assert any(f.rule == "kernel-oob.dma-source"
               for f in escaping.errors), escaping.format()


def test_a_kernels_declared_bytes_are_its_kv_stream():
    """What a kernel copies itself no BlockSpec shows: ``kernel_hbm_costs``
    takes the stream from the call's declared ``cost_estimate`` less the
    blocks it can see, which for ``paged_attention`` is every slot's whole
    table span of K and V, once."""
    from simple_distributed_machine_learning_tpu.analysis.kernels import (
        kernel_hbm_costs,
    )
    from simple_distributed_machine_learning_tpu.ops.paged_attention import (
        paged_attention,
    )
    S, H, dh, bs, NB, n_phys = 3, 2, 64, 8, 4, 9
    jaxpr = jax.make_jaxpr(lambda *a: paged_attention(*a, block_size=bs))(
        jax.ShapeDtypeStruct((S, H, 1, dh), np.float32),
        jax.ShapeDtypeStruct((n_phys, bs, H * dh), "bfloat16"),
        jax.ShapeDtypeStruct((n_phys, bs, H * dh), "bfloat16"),
        jax.ShapeDtypeStruct((S, NB), np.int32),
        jax.ShapeDtypeStruct((S, 1), np.int32))
    rows = {h.op: h.bytes_per_tick for h in kernel_hbm_costs(jaxpr)}
    assert rows["kernel.kv_stream"] == S * NB * bs * H * dh * 2 * 2
    # the query block in, the output block back: a head's row is the whole
    # pool row wide (its own lanes of a zeroed row)
    assert rows["kernel.io"] == 2 * S * H * (H * dh) * 4


def test_kernel_narrowing_cast_drops_the_proof():
    """An i32->i8 cast inside the index map forgets the interval when the
    grid axis provably overflows int8 (wrap semantics): the proof must
    degrade to kernel-unproven, never claim clean. A grid that FITS the
    narrow dtype keeps its proof — same contract PR 8 pinned on gather
    indices, now through BlockSpec index maps."""
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def build(grid_n):
        return _lint_pallas(
            kern, (grid_n,),
            [pl.BlockSpec((1, 8),
                          lambda i: (i.astype(np.int8).astype(np.int32),
                                     0))],
            pl.BlockSpec((1, 8), lambda i: (i, 0)),
            jax.ShapeDtypeStruct((grid_n, 8), np.float32),
            jax.ShapeDtypeStruct((grid_n, 8), np.float32))

    # [0, 299] wraps in int8 -> interval lost -> unproven
    report = build(300)
    assert any(f.rule == "kernel-unproven.index-map"
               for f in report.findings), report.format()
    # [0, 3] is representable -> interval survives -> proof holds
    assert not [f for f in build(4).findings
                if f.family.startswith("kernel-")]


def test_serve_kernel_cli_gate():
    from simple_distributed_machine_learning_tpu.analysis.__main__ import (
        main,
    )
    assert main(["--serve-kernel"]) == 0
    os.environ["SDML_LINT_INJECT"] = "drill"
    try:
        assert main(["--serve-kernel"]) == 1
    finally:
        del os.environ["SDML_LINT_INJECT"]


# ---- satellite: wall-clock/random hostlint rule -------------------------


def test_hostlint_flags_wallclock_and_random_in_serve(tmp_path):
    from simple_distributed_machine_learning_tpu.analysis.hostlint import (
        _lint_call_sites,
    )
    bad = tmp_path / "clocky.py"
    bad.write_text(
        "import time\n"
        "import random\n"
        "from datetime import datetime\n"
        "t0 = time.monotonic()\n"
        "jitter = random.random()\n"
        "stamp = datetime.now()\n")
    rules = [f for f in _lint_call_sites(str(bad), allow_jit=False)
             if f.rule == "hostlint.wall-clock-in-serve"]
    assert len(rules) == 3, [f.message for f in rules]
    # the sanctioned idiom — injectable default args REFERENCING the clock
    # (no call) plus calls through the injected parameter — stays clean
    good = tmp_path / "injected.py"
    good.write_text(
        "import time\n"
        "def tick(clock=time.monotonic):\n"
        "    return clock()\n")
    assert not [f for f in _lint_call_sites(str(good), allow_jit=False)
                if f.rule == "hostlint.wall-clock-in-serve"]


# ---- satellite: fault-drill coverage lint -------------------------------


def test_fault_drill_coverage_clean_and_detects_gaps(tmp_path):
    from simple_distributed_machine_learning_tpu.resilience.faults import (
        KINDS,
        SITES,
        drill_coverage,
    )
    # the repo itself: every kind and site fired somewhere in tests/ or CI
    assert drill_coverage() == []
    # a synthetic tree that only ever drills one pair
    tree = tmp_path / "repo"
    (tree / "tests").mkdir(parents=True)
    (tree / "tests" / "test_x.py").write_text(
        'SCENARIO = "slow-tick@serve.tick"\n')
    gaps = drill_coverage(root=str(tree))
    assert any("kind" in g and "host-kill" in g for g in gaps)
    assert any("site" in g and "train.step" in g for g in gaps)
    assert any("nan-grad@train.grad" in g for g in gaps)  # pinned pair
    # injected kinds/sites localize the check (pure-unit path)
    gaps = drill_coverage(root=str(tree), kinds=("slow-tick",),
                          sites=("serve.tick",), pairs=())
    assert gaps == []
    assert "slow-tick" in KINDS and "serve.tick" in SITES


# ---- satellite: metric-catalog coverage lint (ISSUE 19) -----------------


def test_metric_catalog_rule_repo_clean():
    """Every metric constant registered in serve/metrics.py,
    telemetry/slo.py and telemetry/attribution.py resolves to HELP text —
    the repo's own catalog has no undocumented instrument."""
    from simple_distributed_machine_learning_tpu.analysis.hostlint import (
        lint_metric_catalog,
    )
    assert lint_metric_catalog() == []


def test_metric_catalog_rule_flags_undocumented(tmp_path):
    """The seeded defect: a registering module with a metric name the
    catalog has never heard of must ERROR (path injection mirrors the
    journal-grammar lint's writer/reader seeding)."""
    from simple_distributed_machine_learning_tpu.analysis.hostlint import (
        Severity,
        lint_metric_catalog,
    )
    bad = tmp_path / "metrics_like.py"
    bad.write_text(
        'DOCUMENTED = "serve_blocks_in_use"\n'
        'UNDOCUMENTED = "serve_bogus_flux_capacitor_total"\n'
        'NOT_A_METRIC = "some random string"\n')
    findings = lint_metric_catalog(metric_files=[str(bad)])
    assert [f.rule for f in findings] == ["metric-catalog.undocumented"]
    assert findings[0].severity is Severity.ERROR
    assert "serve_bogus_flux_capacitor_total" in findings[0].message


def test_metric_catalog_covers_slo_and_attribution_instruments():
    """The new ISSUE-19 instruments resolve through the catalog (their
    HELP bullets live in their own modules' docstrings)."""
    from simple_distributed_machine_learning_tpu.telemetry.catalog import (
        metric_help,
    )
    helps = metric_help()
    for name in ("serve_slo_burn_rate", "serve_alerts_firing",
                 "serve_ttft_component_ms",
                 "serve_route_alert_demotions_total"):
        assert name in helps, name


def test_hostlint_wall_clock_rule_covers_slo_pipeline(tmp_path):
    """The zero-wall-clock-reads pin, hostlint-enforced: the clock rule
    now runs over telemetry/{slo,alerts,attribution}.py exactly as over
    serve/ (check_clock decouples it from the jit gate), and a seeded
    clock read in an SLO-pipeline-like module is flagged."""
    from simple_distributed_machine_learning_tpu.analysis.hostlint import (
        _lint_call_sites,
    )
    bad = tmp_path / "slo_like.py"
    bad.write_text(
        "import time\n"
        "def evaluate(tick):\n"
        "    return time.monotonic()\n")
    # telemetry modules lint with the clock rule ON but raw-jit OFF
    flagged = [f.rule for f in _lint_call_sites(str(bad), allow_jit=True,
                                                check_clock=True)]
    assert flagged == ["hostlint.wall-clock-in-serve"]
    assert not _lint_call_sites(str(bad), allow_jit=True)


def test_hostlint_cli_inject_drill(monkeypatch, capsys):
    """SDML_LINT_INJECT trips the --hostlint gate: the negative test
    proving the CI lint job's preflight actually fails on an ERROR."""
    from simple_distributed_machine_learning_tpu.analysis.__main__ import (
        main,
    )
    monkeypatch.setenv("SDML_LINT_INJECT", "drill")
    assert main(["--hostlint"]) == 1
    out = capsys.readouterr().out
    assert "injected.drill" in out and "FLAGGED" in out
    monkeypatch.delenv("SDML_LINT_INJECT")
    assert main(["--hostlint"]) == 0
    capsys.readouterr()
