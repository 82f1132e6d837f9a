"""What PR 44 adds to the benchmark, off the chip: the manifest walk finds
the new cell, its files and its readers BY NAME (membership, never a list's
position or exact length); ``counts_cohere2`` against hand counts (the
published 218,254,938,112 / 24.98 B active and the cut's 4,733,292,544); the
mix's two classes in one queue; the five new readers over hand-made records
and a hand-made trace, and ``None`` from each on records without ``cohere2``
sizes and on ticks without the counts; and ``runners/serve_cohere2.py``
driven past the harness's look for a chip at toy size, as
``test_bench_cells_zaya.py`` drives its own: a sound run comes out correct,
the int8 control does not.

The toy's limit is set by the real mix's rule (above what sound runs of the
toy read, below what its control reads); its weights are drawn at normal 0.2
instead of 0.02, because at width 64 the published scale leaves the scores
all but flat, a wrong window would hardly show and an int8 forward would
mostly put the same token first (at 0.1 the control read 0 on one seed of
three); and they and the pool
are float32, not the cell's bfloat16: with eight experts of a 64-wide toy
one token whose expert flips on a bfloat16 rounding moves its logits by a
whole expert's output (``tests/test_cohere2.py`` holds the bfloat16 program
to the reference).
"""

import copy
import json
import os
import re
import types

import pytest

from bench_cells import (
    check,
    counts_cohere2,
    harness,
    manifest,
    weights_cohere2,
)
from bench_cells import run as benchrun
from bench_cells.reduce import xplane

CELL = "command-a-plus-05-2026.serve-mixed-closed"
CONFIG = "command-a-plus-05-2026"
NEW = ("cache.window_released_pct", "moe.ep8_held_experts_hit_pct",
       "kernel.mixed_attention_roofline_pct",
       "kernel.ep8_experts_roofline_pct", "model.chunk_device_ms")
DEVICE = {"platform": "cpu", "kind": "test", "count": 1}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
TOY = {"vocab": 128, "seq_len": 96, "d_model": 64, "n_layers": 4,
       "n_heads": 8, "n_kv_heads": 2, "head_dim": 16, "window": 8,
       "full_every": 4, "rope_theta": 50000.0, "n_experts": 8, "top_k": 2,
       "experts_held": 4, "expert_offset": 0, "n_shared": 2, "d_expert": 48,
       "ln_eps": 1e-5, "logit_scale": 1.0, "param_dtype": "float32"}
# sound toy runs read 0 over three seeds (float32 throughout: the served
# token is the reference's best), the int8 control 0.022 to 0.070: the limit
# lies 10 x below the control's least
LIMITS = {"gap_mean": 0.002, "compiles_in_window": 0}


@pytest.fixture(scope="module")
def bench():
    return manifest.load_manifest()


@pytest.fixture(scope="module")
def arch():
    return manifest.load_cell(CELL).config["cohere2_config"]


def _by_name(entries):
    return {e["name"]: e for e in entries}


# -- the manifest walk ------------------------


def test_the_cell_its_files_and_its_readers_are_found(bench):
    cell = manifest.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, CONFIG, "serve-mixed-closed")
    assert cell.traffic["runner"] == "serve_cohere2"
    for rel in ("runners/serve_cohere2.py", "reference/cohere2.py",
                "weights_cohere2.py", "counts_cohere2.py"):
        assert os.path.isfile(os.path.join(manifest.HERE, rel)), rel
    # the rate is NOT the cell's (a pause of the machine is over half the
    # rate's bound, PERF.md Open question 9): the tail alone holds it
    e2e = {m["name"] for m in cell.end_to_end}
    assert e2e == {"tpot_p95_ms", "setup_s"}
    layer = {m["name"] for m in cell.per_layer}
    assert set(NEW) | {
        "entry.trace_lower_s", "engine.tick_ms_p50", "engine.chunk_ticks_pct",
        "engine.host_ms_per_tick", "engine.host_admit_ms",
        "engine.host_prepare_ms", "engine.host_dispatch_ms",
        "engine.host_emit_ms", "engine.readback_ms_p50",
        "model.decode_device_ms"} <= layer
    moves = {m["name"]: m["moves"] for m in bench["per_layer"]}
    assert {moves[n] for n in layer} == {"tpot_p95_ms", "setup_s"}
    # readers that take another runner's records stay with their own cells
    assert not {"moe.experts_hit_pct", "moe.held_experts_hit_pct",
                "moe.top1_experts_hit_pct", "cache.state_live_pct",
                "kernel.cca_attention_roofline_pct",
                "kernel.paged_attention_roofline_pct"} & layer
    for name in layer | e2e:
        assert callable(manifest.load_reader(name))
    per_layer = _by_name(bench["per_layer"])
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL], name
        assert per_layer[name]["moves"] == "tpot_p95_ms"
    assert {per_layer[n]["layer"] for n in NEW} == {
        "serve engine", "kernels", "model programs"}
    assert all(per_layer[n]["unit"] == "%" for n in NEW[:4])
    assert per_layer["model.chunk_device_ms"]["unit"] == "ms"
    cells = [w["name"] for w in bench["workloads"]]
    assert CELL in cells
    assert set(per_layer["entry.trace_lower_s"]["workloads"]) == set(cells)
    w = _by_name(bench["workloads"])[CELL]
    c = _by_name(bench["configs"])[CONFIG]
    assert len(w["why"]) <= 200 and len(c["why"]) <= 200
    assert len(c["source"]) <= 200 and c["source"] == cell.config["source"]
    assert c["reduced"] == cell.config["reduced"] == [
        "num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
    # one four-chip cell in the benchmark, as before
    assert sum(x["chips"] == 4 for x in bench["workloads"]) == 1


def test_configuration_file_holds_the_published_widths(arch):
    cfg = manifest.load_cell(CELL).config
    pub = cfg["published"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row, = (r for r in map(json.loads, f) if r["name"] == CONFIG)
    assert pub == row["config"] and cfg["source"] == row["source_url"]
    # every published key at the top level too, unchanged but the four cuts
    for k, v in pub.items():
        assert (cfg[k] == v) == (k not in cfg["reduced"]), k
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 16, 32768)
    assert cfg["layer_types"] == pub["layer_types"][:4] == [
        "sliding_attention"] * 3 + ["full_attention"]
    assert arch == {
        "vocab": 32768, "seq_len": 32768, "d_model": pub["hidden_size"],
        "n_layers": 4, "n_heads": pub["num_attention_heads"],
        "n_kv_heads": pub["num_key_value_heads"],
        "head_dim": pub["head_dim"], "window": pub["sliding_window"],
        "full_every": pub["layer_switch"],
        "rope_theta": float(pub["rope_theta"]),
        "n_experts": pub["num_experts"],
        "top_k": pub["num_experts_per_tok"], "experts_held": 16,
        "expert_offset": 0, "n_shared": pub["num_shared_experts"],
        "d_expert": pub["intermediate_size"],
        "ln_eps": pub["layer_norm_eps"],
        "logit_scale": float(pub["logit_scale"]), "param_dtype": "bfloat16"}
    assert pub["use_parallel_block"] and pub["tie_word_embeddings"]
    assert pub["position_embedding_type"] == "rope_gptj"
    assert pub["expert_selection_fn"] == "sigmoid" and pub["norm_topk_prob"]
    assert pub["first_k_dense_replace"] == 0
    assert cfg["reference"] == "cohere2"
    said = " ".join(cfg["assumed"])
    for what in ("ONE expert's width", "window's edge", "no position",
                 "no selection bias", "OUTPUTS that are averaged",
                 "no dense leading layer",
                 "NOT BUILT", "vision tower"):
        assert what in said, what
    assert "W_q and W_k are drawn at 2 x 0.02" in " ".join(cfg["departures"])
    dep = cfg["deployment"]
    for what in ("eight pipeline stages", "eight chips", "FIRST", "0-3",
                 "experts 0-15", "rows 0-32,767", "4,733,292,544",
                 "218,254,938,112"):
        assert what in dep, what
    assert "int8" in cfg["precision"]["control"]


def test_the_traffic_is_the_issues_to_the_letter():
    from bench_cells.runners import serve_cohere2
    mix = manifest.load_cell(CELL).traffic
    e = mix["engine"]
    assert e == {"n_slots": 16, "max_len": 32768, "block_size": 16,
                 "prefill_chunk": 512, "attn_kernel": "fused",
                 "cache_dtype": "bfloat16", "n_blocks": 32768,
                 "n_window_blocks": 4624}
    # every slot can reach max_len in the full group, and hold its window,
    # the chunk in flight and one block more in the window group
    assert e["n_blocks"] == e["n_slots"] * e["max_len"] // e["block_size"]
    assert e["n_window_blocks"] == e["n_slots"] * (
        (4096 + e["prefill_chunk"]) // e["block_size"] + 1) == 16 * 289
    # the pool: a full layer and three window layers, K and V, 2,048 B a row
    pool = (32768 + 3 * 4624) * 16 * 2 * 2048
    assert 3.05e9 < pool < 3.07e9
    assert (mix["loop"], mix["clients"], mix["round_size"],
            mix["rounds"]) == ("closed", 16, 32, 4)
    short, long_ = mix["classes"]
    assert short == {"name": "short", "share": 0.5, "prompt_lengths": {
        "min": 512, "max": 2048, "multiple_of": 512,
        "weight": "inverse_length"}}
    assert long_ == {"name": "long", "share": 0.5, "prompt_lengths": {
        "min": 8192, "max": 24576, "multiple_of": 512,
        "weight": "inverse_length"}}
    assert mix["answer_lengths"] == {"law": "log_uniform", "min": 128,
                                     "max": 1024}
    assert mix["tokens"] == {"distribution": "zipf", "exponent": 1.0}
    assert mix["sampling"] == {"temperature": 0.0}
    assert mix["trace_seconds"] == 5 and mix["check"]["requests"] == 6
    assert mix["check"]["of_each_class"] == 2
    assert set(mix["check"]["limits"]) == {"gap_mean", "compiles_in_window"}
    assert mix["check"]["limits"]["compiles_in_window"] == 0
    sizes = serve_cohere2.class_sizes(mix)
    assert len(sizes) == 32
    assert all(p % e["prefill_chunk"] == 0 for p, _ in sizes)
    shorts = [p for p, _ in sizes if p <= 2048]
    longs = [p for p, _ in sizes if p >= 8192]
    assert len(shorts) == len(longs) == 16
    assert 900 < sum(shorts) / 16 < 1100            # mean 983 by the law
    assert 13_500 < sum(longs) / 16 < 16_000        # mean 14,800
    assert 400 < sum(a for _, a in sizes) / 32 < 460        # mean 431
    assert max(p + a for p, a in sizes) <= 25_600 < e["max_len"]
    # one queue: every client's queue holds both classes, in an order the
    # mix fixes, and the seed draws the tokens alone
    q1 = serve_cohere2.client_queues(1, mix, 32768, 2)
    q2 = serve_cohere2.client_queues(2, mix, 32768, 2)
    assert [[(len(p), a) for p, a in q] for q in q1] == [
        [(len(p), a) for p, a in q] for q in q2]
    assert any((a[0][0] != b[0][0]).any() for a, b in zip(q1, q2))
    assert all(len(q) == 4 for q in q1)
    first = [len(q[0][0]) for q in q1]
    assert any(n <= 2048 for n in first) and any(n >= 8192 for n in first)
    assert all(p.max() < 32768 for q in q1 for p, _ in q)
    plain, paged = (re.compile(mix["kernels"][k]) for k in (
        "moe_experts", "paged_attention"))
    for line, want in {
            '%moe_experts.3 = f32[128,4096]{1,0} custom-call(': (True, False),
            '%paged_attention.1 = f32[16,1,128,1024]{3,2,1,0} custom-call(':
                (False, True),
            '%fusion.3 = f32[1] fusion(%moe_experts.7)': (False, False),
    }.items():
        assert (bool(plain.search(line)), bool(paged.search(line))) == want
    assert re.search(mix["programs"]["decode_tick"],
                     "jit_step_window_decode")
    assert re.search(mix["programs"]["prefill_chunk"],
                     "jit_chunk_window_prefill")


# -- counts against hand counts ------------------------


def test_parameter_counts_by_hand(arch):
    d = 4096
    attention = 2 * d * 128 * 128 + 2 * d * 8 * 128
    assert counts_cohere2.attention_params(arch) == attention == 142_606_336
    assert counts_cohere2.router_params(arch) == d * 128 == 524_288
    assert counts_cohere2.expert_params(arch) == 3 * d * d == 50_331_648
    assert 4 * counts_cohere2.expert_params(arch) == 201_326_592
    pub = dict(arch, n_layers=32, vocab=262144)
    layer = attention + d + 524_288 + 201_326_592 + 128 * 50_331_648
    assert counts_cohere2.layer_params(pub) == layer == 6_786_912_256
    assert counts_cohere2.embedding_params(pub) == 1_073_741_824
    assert counts_cohere2.total_params(pub) == 32 * layer \
        + 1_073_741_824 + d == 218_254_938_112          # the published "218B"
    active = attention + d + 524_288 + (4 + 8) * 50_331_648
    assert counts_cohere2.active_layer_params(pub) == active == 747_114_496
    assert 32 * active + 1_073_741_824 == 24_981_405_696        # "A25B"
    # the cut: four layers of 16 held experts, 32,768 held rows
    held = attention + d + 524_288 + 201_326_592 + 16 * 50_331_648
    assert counts_cohere2.layer_params(arch, 16) == held == 1_149_767_680
    assert held - 16 * 50_331_648 == 344_461_312
    assert counts_cohere2.held_params(arch) == 4 * held + 32768 * d + d \
        == 4_733_292_544
    assert 9.46e9 < 2 * counts_cohere2.held_params(arch) < 9.47e9
    assert counts_cohere2.window_layers(arch) == 3


def test_kernel_bytes_by_hand(arch):
    # a position of one layer: K and V, 8 heads of 128, bfloat16
    # 16 slots, 131,000 cached positions of which a window layer sees
    # 43,000: one full and three window layers, and 16 x 4 query and output
    # rows of 16,384 float32
    assert counts_cohere2.kv_bytes(arch, 131_000, 43_000, 16) == (
        (131_000 + 3 * 43_000) * 4096 + 2 * 16 * 4 * 16384 * 4) \
        == 1_073_348_608
    assert counts_cohere2.kv_bytes(arch, 0, 0, 0) == 0
    # one layer kind would read every position in all four layers
    assert counts_cohere2.kv_bytes(arch, 131_000, 131_000, 16) > 2.1e9
    # 41 of the 64 (layer, held expert) pairs hit by 66 rows: 4.13 GB
    assert counts_cohere2.held_experts_bytes(arch, 41, 66) == (
        41 * 50_331_648 * 2 + 66 * 4096 * (2 + 4)) == 4_128_817_152
    assert counts_cohere2.held_experts_bytes(arch, 0, 0) == 0


def test_seeded_weights_have_the_programs_layout_and_count():
    import jax
    import numpy as np

    from bench_cells.runners import serve_cohere2
    from simple_distributed_machine_learning_tpu.models.cohere2 import (
        Cohere2Config,
    )
    toy = dict(TOY, param_dtype="bfloat16")
    tree = weights_cohere2.init_cohere2(2 ** 31 + 5, toy)
    again = weights_cohere2.init_cohere2(2 ** 31 + 5, toy)
    leaves = jax.tree.leaves(tree)
    assert {str(a.dtype) for a in leaves} == {"bfloat16"}
    assert sum(a.size for a in leaves) == counts_cohere2.held_params(toy)
    assert all((a == b).all() for a, b in zip(leaves, jax.tree.leaves(again)))
    bp = tree["blocks"][1]
    assert bp["moe"]["gate"].shape == (4, 64, 48)
    assert bp["moe"]["router"].shape == (64, 8)        # all the experts
    assert bp["shared"]["down"].shape == (2 * 48, 64)
    # a layer alone is the same layer, and the ends the same ends
    alone = weights_cohere2.init_layer(2 ** 31 + 5, toy, 1)
    assert all((a == b).all() for a, b in zip(
        jax.tree.leaves(alone), jax.tree.leaves(bp)))
    assert (weights_cohere2.init_ends(2 ** 31 + 5, toy)["embed"]["tok"]
            == tree["embed"]["tok"]).all()
    # the stated departure: W_q and W_k at twice the scale, nothing else
    std = lambda a: float(np.asarray(a, np.float32).std())  # noqa: E731
    assert weights_cohere2.ATTN_GAIN == 2.0
    for name, gain in (("wq", 2), ("wk", 2), ("wv", 1), ("wo", 1)):
        assert abs(std(bp["attn"][name]) / (0.02 * gain) - 1) < 0.1, name
    assert abs(std(bp["moe"]["gate"]) / 0.02 - 1) < 0.1
    stage, = serve_cohere2.cohere2_stage(Cohere2Config(**toy), tree)
    assert stage.params is tree
    with pytest.raises(SystemExit, match="parameter layout"):
        serve_cohere2.cohere2_stage(
            Cohere2Config(**dict(toy, d_expert=64)), tree)


# -- the readers over hand-made records and a hand-made trace -------------------


def _hand_ctx(monkeypatch, attrs, arch, with_trace=True):
    """A window of three ticks (two decoded) whose spans carry ``attrs``,
    and a trace of two decode runs of 10 ms and two chunk runs: 2 ms of
    ``moe_experts`` and 4 ms of ``paged_attention`` inside each decode run,
    ``moe_experts`` inside the chunks' too (which no roofline reader may
    count); the chunk runs are busy 30 and 40 ms."""
    from bench_cells import program_spans

    ticks = [types.SimpleNamespace(attrs=dict(a), id=i)
             for i, a in enumerate(attrs)]
    window = types.SimpleNamespace(ticks=ticks, spans=ticks, kids={})
    ev = xplane.Event
    ops = []
    for t0 in (0.0, 0.020):
        ops += [ev("moe", t0 + 0.001, t0 + 0.003,
                   "%moe_experts.3 = f32[128,4096]{1,0} custom-call("),
                ev("attn", t0 + 0.004, t0 + 0.008,
                   "%paged_attention.1 = f32[16,1,128,1024]{3,2,1,0} "
                   "custom-call("),
                ev("rest", t0 + 0.008, t0 + 0.010, "%fusion.9 = fusion(")]
    for t0, busy in ((0.040, 0.030), (0.100, 0.040)):
        ops += [ev("moe", t0, t0 + 0.010,
                   "%moe_experts.5 = f32[4096,4096]{1,0} custom-call("),
                ev("rest", t0 + 0.015, t0 + 0.005 + busy,
                   "%fusion.11 = fusion(")]
    dev = xplane.Device(0, ops, [
        ev("jit_step_window_decode", 0.0, 0.010),
        ev("jit_step_window_decode", 0.020, 0.030),
        ev("jit_chunk_window_prefill", 0.040, 0.080),
        ev("jit_chunk_window_prefill", 0.100, 0.150)])
    monkeypatch.setattr(program_spans, "serve_window", lambda run: window)
    monkeypatch.setattr(program_spans, "window_ticks",
                        lambda r, spans: list(spans))
    return {"records": {"kind": "serve", "n_slots": 16, "cache_itemsize": 2,
                        "traced_ticks": [0, 3], "cohere2": arch},
            "trace": xplane.Trace([dev], []) if with_trace else None,
            "mix": manifest.load_cell(CELL).traffic, "peaks": PEAKS}


SPANS = [{"decoding": 16, "experts_hit": 40, "expert_rows": 64,
          "expert_rows_max": 4, "kv_positions": 130_000,
          "kv_window_positions": 42_000, "kv_window_blocks": 2700,
          "kv_full_blocks": 9000},
         {"decoding": 0, "experts_hit": 0, "expert_rows": 0,
          "expert_rows_max": 0, "kv_positions": 0, "kv_window_positions": 0,
          "kv_window_blocks": 2700, "kv_full_blocks": 9000},
         {"decoding": 16, "experts_hit": 42, "expert_rows": 68,
          "expert_rows_max": 5, "kv_positions": 132_000,
          "kv_window_positions": 44_000, "kv_window_blocks": 2800,
          "kv_full_blocks": 7000}]


def test_the_five_readers_by_hand(monkeypatch, arch):
    ctx = _hand_ctx(monkeypatch, SPANS, arch)
    read = {n: manifest.load_reader(n)(ctx) for n in NEW}
    assert read["cache.window_released_pct"] == pytest.approx(
        100 * ((1 - 2700 / 9000) + (1 - 2800 / 7000)) / 2)
    assert read["moe.ep8_held_experts_hit_pct"] == pytest.approx(
        100 * 41 / 64)
    # two decode runs, each the mean tick's bytes, over 2 x 2 ms at 1e11 B/s
    experts = counts_cohere2.held_experts_bytes(arch, 41, 66)
    assert read["kernel.ep8_experts_roofline_pct"] == pytest.approx(
        100 * 2 * experts / 1e11 / 0.004)
    kv = counts_cohere2.kv_bytes(arch, 131_000, 43_000, 16)
    assert read["kernel.mixed_attention_roofline_pct"] == pytest.approx(
        100 * 2 * kv / 1e11 / 0.008)
    # the chunk runs' busy time (10 + 20 and 10 + 30 ms), their median
    assert read["model.chunk_device_ms"] == pytest.approx(35.0)


def test_the_trace_readers_find_no_kernel_is_an_error(monkeypatch, arch):
    ctx = _hand_ctx(monkeypatch, SPANS, arch)
    ctx["trace"].devices[0].ops[:] = [
        e for e in ctx["trace"].devices[0].ops if e.name == "rest"]
    for name in NEW[2:4]:
        with pytest.raises(SystemExit, match="no device operation"):
            manifest.load_reader(name)(ctx)


@pytest.mark.parametrize("name", NEW)
def test_readers_give_nothing_on_a_program_without_the_counts(
        monkeypatch, name, arch):
    """The parent commit's ticks carry no ``kv_window_blocks``, another
    family's no ``expert_rows``, and another runner's records no
    ``cohere2``: every new reader returns ``None`` and does not raise; nor
    on an untraced run for those that read the trace."""
    read = manifest.load_reader(name)
    bare = [{"chunk": 0, "decoding": 1, "kv_positions": 5}]
    if name != "model.chunk_device_ms":     # it reads no count
        assert read(_hand_ctx(monkeypatch, bare, arch)) is None
    for attrs, with_trace in ((SPANS, True), (bare, False)):
        ctx = _hand_ctx(monkeypatch, attrs, arch, with_trace)
        del ctx["records"]["cohere2"]
        ctx["records"]["zaya"] = {}
        assert read(ctx) is None
    ctx = _hand_ctx(monkeypatch, SPANS, arch, with_trace=False)
    assert (read(ctx) is None) == name.startswith(("kernel.", "model."))
    ctx["records"] = {"kind": "train"}
    assert read(ctx) is None
    if name == "model.chunk_device_ms":     # a traced stretch with no chunk
        ctx = _hand_ctx(monkeypatch, SPANS, arch)
        del ctx["trace"].devices[0].modules[2:]
        assert read(ctx) is None


# -- the runner at toy size ------------------------


def toy_cell(limits=LIMITS, arch=TOY, requests=6):
    real = manifest.load_cell(CELL)
    mix = copy.deepcopy(real.traffic)
    lengths = lambda lo, hi: {"min": lo, "max": hi,  # noqa: E731
                              "multiple_of": 8, "weight": "inverse_length"}
    mix.update(
        engine={"n_slots": 4, "max_len": 96, "block_size": 4, "n_blocks": 96,
                "n_window_blocks": 24, "prefill_chunk": 8,
                "attn_kernel": "fused", "cache_dtype": "float32"},
        clients=4, round_size=8, rounds=400,
        classes=[{"name": "short", "share": 0.5,
                  "prompt_lengths": lengths(8, 16)},
                 {"name": "long", "share": 0.5,
                  "prompt_lengths": lengths(40, 72)}],
        answer_lengths={"law": "log_uniform", "min": 3, "max": 8})
    mix["check"] = {"requests": requests, "of_each_class": 2,
                    "limits": limits}
    return manifest.Cell(CELL, 1, real.config_name, real.traffic_name,
                         dict(real.config, cohere2_config=arch), mix,
                         real.end_to_end, real.per_layer)


@pytest.fixture()
def toy_conditions(monkeypatch):
    from bench_cells.reference import cohere2 as reference
    from bench_cells.runners import serve_cohere2
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: 1)
    monkeypatch.setattr(weights_cohere2, "STD", 0.2)
    # the reference's shapes at the toy's lengths
    monkeypatch.setattr(serve_cohere2, "_SHORT_T", 32)
    monkeypatch.setattr(serve_cohere2, "_LONG_STEP", 32)
    monkeypatch.setattr(reference, "_Q_BLOCK", 16)


def test_sound_run_is_correct_and_its_records_feed_the_readers(
        toy_conditions):
    result = benchrun.run_cell(toy_cell(), 2 ** 31 + 5, 2.0, False, DEVICE,
                               PEAKS)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 4
    assert set(result["metrics"]) == {"tpot_p95_ms", "setup_s"}
    assert result["compared"]["compiles_in_window"] == {"value": 0,
                                                        "limit": 0}
    json.dumps(result)


class _NoTrace:
    enabled, dir, running = False, None, False


def test_control_is_not_correct_and_the_counters_are_read(toy_conditions):
    """The same comparison, the reference in int8 operands in the program's
    place; the sample holds both classes; and the two counter metrics over
    the toy window's own spans."""
    from bench_cells.runners import serve_cohere2

    cell = toy_cell(requests=8)
    run = serve_cohere2.Run(cell, 1, harness.Spans())
    run.setup()
    run.window(2.0, _NoTrace())
    lengths = [len(p) for p, _ in run.sample]
    assert len(lengths) == 8
    assert sum(n <= 16 for n in lengths) >= 2 <= sum(n >= 40 for n in lengths)
    assert max(len(p) + len(t) for p, t in run.sample) == max(
        len(r["prompt"]) + r["n_new"] for r in run.sent
        if len(r["stamps"]) >= r["n_new"])
    ctx = {"records": run.records, "trace": None, "mix": cell.traffic,
           "peaks": PEAKS}
    hit = manifest.load_reader("moe.ep8_held_experts_hit_pct")(ctx)
    released = manifest.load_reader("cache.window_released_pct")(ctx)
    # 4 layers x 4 held experts; 4 rows x top 2 of 8 a layer
    assert 5.0 <= hit <= 100.0
    # long requests are five to ten windows deep: most of a window layer's
    # share of their positions has been handed back
    assert 20.0 <= released <= 95.0
    for name in NEW[2:]:
        assert manifest.load_reader(name)(ctx) is None   # no trace, no share
    assert run.records["cohere2"] == TOY
    program, control = run.check(), run.control()
    assert check.compare(program, LIMITS)[0], program
    ok, compared = check.compare(control, LIMITS)
    assert not ok and compared["gap_mean"]["value"] > 2 * LIMITS["gap_mean"]
