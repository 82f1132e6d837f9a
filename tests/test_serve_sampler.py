"""The ONE row-batch sampler of the serve programs (``models/serving.py::
sample_slots``): ``vmap`` of ``sample_dyn`` behind a batch-level
``lax.cond``, so that a tick whose slots are all greedy sorts nothing.

What it must not move: any token or key of any program, for any batch. The
programs of before called ``jax.vmap(sample_dyn)`` (a row batch) and
``sample_dyn`` (one row) directly; here every GPT serve program is built
both ways and the two builds' outputs are compared bit for bit over a
whole serve, all-greedy, all-sampled and mixed, with a slot left free
(temperature 0, as the engine hands inactive slots in). And what it must
do: hold every vocabulary-wide ``sort`` of a lowered program inside a
conditional's branch.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simple_distributed_machine_learning_tpu.models import (
    gpt,
    jamba,
    lora,
    nemotron_h,
    sdar,
    serving,
)
from simple_distributed_machine_learning_tpu.models.gpt import (
    GPTConfig,
    make_gpt_stages,
)
from simple_distributed_machine_learning_tpu.serve import InferenceEngine
from simple_distributed_machine_learning_tpu.serve.adapters import (
    AdapterStore,
)

CFG = GPTConfig(vocab=61, seq_len=48, d_model=32, n_heads=2, n_layers=2)
DRAFT_CFG = dataclasses.replace(CFG, n_layers=1)
N_SLOTS = 4         # one more than the requests of a mix: a slot stays free
# (temperature, top_k, top_p) of a mix's three requests
MIXES = {
    "greedy": [(0.0, None, None)] * 3,
    "sampled": [(1.3, 20, None), (0.9, None, 0.9), (0.7, 8, 0.8)],
    "mixed": [(0.0, None, None), (1.3, 20, 0.95), (0.0, None, None)],
}


@functools.cache
def _stages(which="target"):
    if which == "draft":
        return make_gpt_stages(jax.random.key(9), DRAFT_CFG, 1)[0]
    return make_gpt_stages(jax.random.key(0), CFG, 2)[0]


def _prompt(n, seed):
    return np.asarray(
        jax.random.randint(jax.random.key(seed), (n,), 0, CFG.vocab),
        np.int32)


def _as_before(patch):
    """The programs' sampling as the parent commit wrote it, and a memo of
    their builds that holds none built otherwise."""
    patch.setattr(serving, "_DECODE_BUILD_CACHE", {})
    patch.setattr(gpt, "sample_slots", jax.vmap(serving.sample_dyn))
    patch.setattr(gpt, "sample_slot", serving.sample_dyn)


def _host(tree):
    return jax.tree.map(np.asarray, tree)


def _same(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.dtype == y.dtype and np.array_equal(x, y) for x, y in zip(la, lb))


# -- the two paged programs, as the engine runs them ---------------------------


def _engine(variant):
    kw = {}
    cfg = CFG
    if variant == "adapters":
        kw["adapters"] = AdapterStore(CFG, 2, N_SLOTS)
    elif variant == "tp2":
        from simple_distributed_machine_learning_tpu.parallel.mesh import (
            make_mesh,
        )
        cfg = dataclasses.replace(CFG, n_tensor_parallel=2)
        kw["mesh"] = make_mesh(n_stages=1, n_data=1, n_model=2)
    eng = InferenceEngine(_stages(), cfg, n_slots=N_SLOTS, block_size=4,
                          prefill_chunk=5, **kw)
    if variant == "adapters":
        w = dict(lora.init_lora_adapter(jax.random.key(1), CFG, 2))
        w["bq"] = 0.05 * jax.random.normal(jax.random.key(2), w["bq"].shape,
                                           w["bq"].dtype)
        eng.register_adapter("t1", w)
    return eng


def _serve(variant, mix):
    """Serve ``mix`` to the end: what every run of the decode and of the
    chunk program took as temperatures and gave back beside the pool (the
    state's pair, the tokens, the keys), in order."""
    eng = _engine(variant)
    runs = {"decode": [], "chunk": []}

    def tap(name, kind, temps_at, live_at):
        program = getattr(eng, name)

        def tapped(*args):
            out = program(*args)
            runs[kind].append((_host(args[temps_at]), _host(args[live_at]),
                               _host(out[2:])))
            return out

        setattr(eng, name, tapped)

    # (params, kc, vc, state, pos, tables, live, temps, ...) and (params,
    # kc, vc, state, tokens, p0, table, slot, seat, key_data, temperature,
    # ...): the decode's live slots, the chunk's slot
    tap("_decode", "decode", 7, 6)
    tap("_chunk_prefill", "chunk", 10, 7)
    handles = []
    for i, (t, k, p) in enumerate(MIXES[mix]):
        kw = dict(temperature=t, top_k=k, top_p=p) if t else {}
        if variant == "adapters" and i == 1:
            kw["adapter"] = "t1"
        handles.append(eng.submit(_prompt(6 + 3 * i, 20 + i), 5 + i,
                                  seed=40 + i, **kw))
    eng.drain()
    assert [len(h.tokens) for h in handles] == [5, 6, 7]
    return runs


@functools.cache
def _both_ways(variant, mix):
    with pytest.MonkeyPatch.context() as patch:
        _as_before(patch)
        before = _serve(variant, mix)
    return _serve(variant, mix), before


@pytest.mark.parametrize("mix", list(MIXES))
@pytest.mark.parametrize("variant", ["plain", "tp2", "adapters"])
@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_paged_program_gives_what_vmap_of_sample_dyn_gave(program, variant,
                                                          mix):
    """Every run of the program over a whole serve: state pair, tokens and
    keys bit for bit those of the build that calls ``sample_dyn`` without
    the ``cond``; and the batches were of the kind the mix says, with the
    free slot at temperature 0."""
    now, before = _both_ways(variant, mix)
    assert len(now[program]) == len(before[program]) >= 3
    for (temps, _, out), (temps_b, _, out_b) in zip(now[program],
                                                    before[program]):
        assert np.array_equal(temps, temps_b)
        assert _same(out, out_b)
    if program == "decode":
        hot = [int((t > 0).sum()) for t, _, _ in now[program]]
        live = [int(l.sum()) for _, l, _ in now[program]]
        assert all(n < N_SLOTS for n in live)
        assert all((t[~l] == 0).all() for t, l, _ in now[program])
        if mix == "greedy":
            assert not any(hot)
        elif mix == "sampled":
            assert hot == live
        else:
            assert any(0 < h < l for h, l in zip(hot, live))
    else:
        hot = {float(t) for t, _, _ in now[program]}
        want = {t for t, _, _ in MIXES[mix]}
        assert hot == {np.float32(t) for t in want}


# -- the draft's two programs, called as the speculative engine calls them -----


def _draft_programs(mix):
    """The draft's prefill into each slot, then one propose over them all,
    slot ``N_SLOTS - 1`` left out of the mix (greedy, as a free slot)."""
    ml, K = CFG.seq_len, 3
    eng = InferenceEngine(_stages(), CFG, n_slots=N_SLOTS, block_size=4,
                          prefill_chunk=5, draft_stages=_stages("draft"),
                          draft_cfg=DRAFT_CFG, spec_k=K)
    assert eng.max_len == ml and eng._spec_fused is not None
    kc, vc = jax.tree.map(jnp.copy, (eng._dkc, eng._dvc))
    rows = MIXES[mix] + [(0.0, None, None)]
    temps = np.asarray([t for t, _, _ in rows], np.float32)
    top_ks = np.asarray([k or 0 for _, k, _ in rows], np.int32)
    top_ps = np.asarray([p or 2.0 for _, _, p in rows], np.float32)
    keys = np.stack([np.asarray(jax.random.key_data(jax.random.key(70 + s)))
                     for s in range(N_SLOTS)])
    out = {"slot_prefill": [], "propose": []}
    toks = np.zeros(N_SLOTS, np.int32)
    pos = np.zeros(N_SLOTS, np.int32)
    for s in range(N_SLOTS - 1):
        prompt = _prompt(5 + 2 * s, 50 + s)
        kc, vc, tok, kd = eng._draft_prefill(
            eng._draft_params, kc, vc, prompt[None], np.int32(s), keys[s],
            temps[s], top_ks[s], top_ps[s])
        out["slot_prefill"].append(_host((tok, kd)))
        toks[s], pos[s], keys[s] = int(tok), len(prompt), np.asarray(kd)
    kc, vc, drafts, qrows, kd2 = eng._propose(
        eng._draft_params, kc, vc, toks, pos, keys, temps, top_ks, top_ps)
    out["propose"].append(_host((drafts, qrows, kd2)))
    return out


@functools.cache
def _draft_both_ways(mix):
    with pytest.MonkeyPatch.context() as patch:
        _as_before(patch)
        before = _draft_programs(mix)
    return _draft_programs(mix), before


@pytest.mark.parametrize("mix", list(MIXES))
@pytest.mark.parametrize("program", ["slot_prefill", "propose"])
def test_draft_program_gives_what_vmap_of_sample_dyn_gave(program, mix):
    now, before = _draft_both_ways(mix)
    assert len(now[program]) == len(before[program]) > 0
    assert all(_same(a, b) for a, b in zip(now[program], before[program]))
    if program == "propose" and mix != "greedy":
        # a sampled slot's draft key stream moved once a proposal, a greedy
        # slot's not at all
        (_, _, kd2), = now[program]
        keys = np.stack([np.asarray(jax.random.key_data(
            jax.random.key(70 + s))) for s in range(N_SLOTS)])
        assert (kd2[-1] == keys[-1]).all() and (kd2[1] != keys[1]).any()


def test_the_comparison_would_see_a_sampler_that_moved_a_key():
    """The comparison reads what it says it reads: a sampler that advances
    a greedy row's key shows in the decode runs' outputs."""
    real = serving.sample_slots

    def moved(*args):
        toks, kd = real(*args)
        return toks, kd + jnp.uint32(1)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(serving, "_DECODE_BUILD_CACHE", {})
        patch.setattr(gpt, "sample_slots", moved)
        off = _serve("plain", "greedy")
    now, _ = _both_ways("plain", "greedy")
    assert not any(_same(a[2], b[2])
                   for a, b in zip(now["decode"], off["decode"]))


# -- where the sorts are --------------------------------------------------------


def _unguarded_sorts(text: str) -> tuple[int, int]:
    """``(sorts in all, sorts that run whatever a conditional chooses)`` of
    a lowered module's text, by ``stablehlo.sort`` operation (jax outlines
    ``sort`` as one function however often it is called): one counts as
    guarded where it stands inside a region of a ``stablehlo.case`` /
    ``stablehlo.if``, or in a function that ``main`` reaches through such
    regions only."""
    funcs, name, open_ = {}, None, []
    for line in text.splitlines():
        m = re.match(r"\s*func\.func (?:public |private )?@([\w.]+)\(", line)
        if m:
            name, open_ = m.group(1), []
            funcs[name] = {"sorts": [], "calls": []}
        if name is None:
            continue
        guarded = any(open_)
        if "stablehlo.sort" in line:
            funcs[name]["sorts"].append(guarded)
        for callee in re.findall(r"call @([\w.]+)", line):
            funcs[name]["calls"].append((callee, guarded))
        net = line.count("{") - line.count("}")
        if net > 0:
            open_ += [bool(re.search(r"stablehlo\.(case|if)\b", line))] * net
        elif net < 0:
            del open_[net:]
    bare, todo = set(), ["main"]
    while todo:
        f = todo.pop()
        if f not in bare:
            bare.add(f)
            todo += [c for c, guarded in funcs[f]["calls"] if not guarded]
    total = sum(len(f["sorts"]) for f in funcs.values())
    return total, sum(not g for f in bare for g in funcs[f]["sorts"])


def _lowered(which: str) -> str:
    """The lowered text of one of the engine's two programs, from the
    arguments the engine calls it with."""
    eng = InferenceEngine(_stages(), CFG, n_slots=N_SLOTS, block_size=4,
                          prefill_chunk=5)
    name = {"decode": "_decode", "chunk": "_chunk_prefill"}[which]
    program, seen = getattr(eng, name), []

    def tapped(*args):
        if not seen:
            seen.append(program.lower(*args).as_text())
        return program(*args)

    setattr(eng, name, tapped)
    eng.submit(_prompt(7, 1), 3)
    eng.drain()
    return seen[0]


@pytest.mark.parametrize("which", ["decode", "chunk"])
def test_every_sort_of_a_lowered_program_is_inside_a_conditional(which):
    text = _lowered(which)
    assert {"decode": "step_paged_decode",
            "chunk": "chunk_paged_prefill"}[which] in text
    total, bare = _unguarded_sorts(text)
    assert total >= 1 and bare == 0


def test_the_reading_of_the_text_finds_a_sort_that_always_runs():
    rows = jnp.zeros((N_SLOTS, CFG.vocab))
    args = (rows, jnp.zeros((N_SLOTS, 2), jnp.uint32), jnp.zeros(N_SLOTS),
            jnp.zeros(N_SLOTS, jnp.int32), jnp.full(N_SLOTS, 2.0))
    always = jax.jit(jax.vmap(serving.sample_dyn)).lower(*args).as_text()
    total, bare = _unguarded_sorts(always)
    assert total == bare >= 1       # one outlined function, called twice
    behind = jax.jit(serving.sample_slots).lower(*args).as_text()
    assert _unguarded_sorts(behind) == (total, 0)


# -- one definition -------------------------------------------------------------


@pytest.mark.parametrize("module", [jamba, nemotron_h])
def test_the_other_families_call_gpts_sampler(module):
    assert module.sample_slots is gpt.sample_slots is serving.sample_slots
    assert module.sample_slot is gpt.sample_slot is serving.sample_slot
    own = [n for n, f in vars(module).items()
           if "sample" in n and callable(f)
           and getattr(f, "__module__", None) == module.__name__]
    assert own == [] and not hasattr(module, "_sample")


def test_the_block_family_keeps_its_own_cond_and_says_where_the_other_is():
    assert not hasattr(sdar, "sample_slots")
    assert "models/serving.py::sample_slots" in " ".join(
        sdar._sample_block.__doc__.split())
