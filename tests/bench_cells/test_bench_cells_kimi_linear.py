"""What PR 49 adds to the benchmark, off the chip: the manifest walk finds the
new cell, its files and its readers BY NAME (membership, never a list's
position or exact length); ``counts_kimi_linear`` against hand counts (the
published 49,122,681,728 / 3,106,972,544 active and the cut's
4,296,057,728); the five new readers over hand-made records and a hand-made
trace, and ``None`` from each on records without ``kimi_linear`` sizes and on
ticks without the counts; and ``runners/serve_kimi_linear.py`` driven past
the harness's look for a chip at toy size, as ``test_bench_cells_nemotron_h.
py`` drives its own: a sound run comes out correct, the int8 control does
not.

The toy's limit is set by the real mix's rule (above what sound runs of the
toy read, below what its control reads); its weights are drawn at normal 0.1
instead of 0.02, because at width 64 the published scale leaves the layers
all but linear and a wrong state would hardly show; and they and the pool are
float32, not the cell's bfloat16: with a few dozen tokens a sample, one token
whose third expert flips on a bfloat16 rounding moves the mean by as much as
the int8 control's least (``tests/test_kimi_linear.py`` holds the bfloat16
program to the reference).
"""

import copy
import json
import os
import re
import types

import pytest

from bench_cells import (
    check,
    counts_kimi_linear,
    harness,
    manifest,
    weights_kimi_linear,
)
from bench_cells import run as benchrun
from bench_cells.reduce import xplane

CELL = "kimi-linear-48b-a3b.serve-think-closed"
CONFIG = "kimi-linear-48b-a3b"
NEW = ("moe.ep16_held_experts_hit_pct", "kernel.kda_recurrence_roofline_pct",
       "kernel.latent_attention_roofline_pct",
       "kernel.ep16_experts_roofline_pct", "model.kda_share_pct")
DEVICE = {"platform": "cpu", "kind": "test", "count": 1}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
TOY = {"vocab": 128, "seq_len": 64, "d_model": 64, "n_layers": 5,
       "attn_layers": [2, 4], "n_heads": 4, "d_nope": 16, "d_rope": 8,
       "d_v": 16, "d_latent": 32, "kda_heads": 4, "kda_head_dim": 16,
       "d_conv": 4, "d_gate": 16, "n_dense": 1, "d_ff": 128,
       "n_experts": 16, "top_k": 3, "experts_held": 8, "expert_offset": 4,
       "n_shared": 1, "d_expert": 48, "route_scale": 2.446,
       "rms_eps": 1e-5, "param_dtype": "float32"}
# sound toy runs read 0 over three seeds (float32 throughout: the served
# token is the reference's best), the int8 control over 40 requests 0.025 to
# 0.16: the limit lies 10 x below the control's least
LIMITS = {"gap_mean": 0.0025, "compiles_in_window": 0}


@pytest.fixture(scope="module")
def bench():
    return manifest.load_manifest()


@pytest.fixture(scope="module")
def arch():
    return manifest.load_cell(CELL).config["kimi_linear_config"]


def _by_name(entries):
    return {e["name"]: e for e in entries}


# -- the manifest walk ---------------------------------------------------------


def test_the_cell_its_files_and_its_readers_are_found(bench):
    cell = manifest.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, CONFIG, "serve-think-closed")
    assert cell.traffic["runner"] == "serve_kimi_linear"
    for rel in ("runners/serve_kimi_linear.py", "reference/kimi_linear.py",
                "weights_kimi_linear.py", "counts_kimi_linear.py"):
        assert os.path.isfile(os.path.join(manifest.HERE, rel)), rel
    e2e = {m["name"] for m in cell.end_to_end}
    assert e2e == {"tpot_p95_ms", "setup_s"}
    layer = {m["name"] for m in cell.per_layer}
    assert layer == set(NEW) | {
        "entry.trace_lower_s", "engine.tick_ms_p50", "model.decode_device_ms",
        "engine.host_ms_per_tick", "engine.host_admit_ms",
        "engine.host_prepare_ms", "engine.host_dispatch_ms",
        "engine.host_emit_ms", "engine.chunk_ticks_pct",
        "engine.readback_ms_p50"}
    for name in layer | e2e:
        assert callable(manifest.load_reader(name))
    per_layer = _by_name(bench["per_layer"])
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL], name
        assert per_layer[name]["moves"] == "tpot_p95_ms"
        assert per_layer[name]["unit"] == "%"
        assert per_layer[name]["layer"] in ("kernels", "model programs")
    # nothing that moves the rate lists the cell, the rate itself neither
    assert not any(CELL in m.get("workloads", ()) for m in bench["per_layer"]
                   if m["moves"] == "serve_tokens_per_s")
    assert CELL not in _by_name(bench["end_to_end"])[
        "serve_tokens_per_s"]["workloads"]
    # model.chunk_device_ms reads another runner's records: not this cell's
    assert CELL not in per_layer["model.chunk_device_ms"]["workloads"]
    cells = [w["name"] for w in bench["workloads"]]
    assert CELL in cells
    assert set(per_layer["entry.trace_lower_s"]["workloads"]) == set(cells)
    w = _by_name(bench["workloads"])[CELL]
    c = _by_name(bench["configs"])[CONFIG]
    assert len(w["why"]) <= 200 and len(c["why"]) <= 200
    assert len(c["source"]) <= 200 and c["source"] == cell.config["source"]
    assert c["reduced"] == cell.config["reduced"] == ["num_experts",
                                                      "vocab_size"]
    # one four-chip cell in the benchmark, as before
    assert sum(x["chips"] == 4 for x in bench["workloads"]) == 1


def test_configuration_file_holds_the_published_widths(arch):
    cfg = manifest.load_cell(CELL).config
    pub = cfg["published"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row, = (r for r in map(json.loads, f)
                if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert pub == row["config"] and cfg["source"] == row["source_url"]
    # every published key at the top level too, unchanged but the two cuts
    for k, v in pub.items():
        assert (cfg[k] == v) == (k not in cfg["reduced"]), k
    assert (cfg["num_experts"], cfg["vocab_size"]) == (16, 20480)
    lin = pub["linear_attn_config"]
    assert arch == {
        "vocab": 20480, "seq_len": 4096, "d_model": pub["hidden_size"],
        "n_layers": pub["num_hidden_layers"],
        "attn_layers": [l - 1 for l in lin["full_attn_layers"]],
        "n_heads": pub["num_attention_heads"],
        "d_nope": pub["qk_nope_head_dim"], "d_rope": pub["qk_rope_head_dim"],
        "d_v": pub["v_head_dim"], "d_latent": pub["kv_lora_rank"],
        "kda_heads": lin["num_heads"], "kda_head_dim": lin["head_dim"],
        "d_conv": lin["short_conv_kernel_size"], "d_gate": lin["head_dim"],
        "n_dense": pub["first_k_dense_replace"],
        "d_ff": pub["intermediate_size"], "n_experts": pub["num_experts"],
        "top_k": pub["num_experts_per_token"], "experts_held": 16,
        "expert_offset": 0, "n_shared": pub["num_shared_experts"],
        "d_expert": pub["moe_intermediate_size"],
        "route_scale": pub["routed_scaling_factor"],
        "rms_eps": pub["rms_norm_eps"], "param_dtype": "bfloat16"}
    assert sorted(arch["attn_layers"] + [l - 1 for l in lin["kda_layers"]]
                  ) == list(range(27))
    assert pub["mla_use_nope"] and pub["q_lora_rank"] is None
    assert pub["moe_router_activation_func"] == "sigmoid"
    assert pub["moe_renormalize"] and not pub["tie_word_embeddings"]
    assert cfg["reference"] == "kimi_linear"
    said = " ".join(cfg["assumed"])
    for what in ("NO BIAS [a]", "sqrt(sum x^2 + 1e-6)", "128^-0.5",
                 "the rank of the two-matrix projections",
                 "NO rotation of any lane", "ABSORBED", "width 1 x 1024",
                 "NOT BUILT"):
        assert what in said, what
    assert "640 lanes, not 576" in " ".join(cfg["departures"])
    assert "CENTRED" in " ".join(cfg["departures"])
    dep = cfg["deployment"]
    for what in ("sixteen chips", "ALL 27 layers", "experts 0-15",
                 "rows 0-20,479", "4,296,057,728", "49,122,681,728",
                 "3,106,972,544"):
        assert what in dep, what
    assert "int8" in cfg["precision"]["control"]


def test_the_traffic_is_the_issues_to_the_letter():
    mix = manifest.load_cell(CELL).traffic
    e = mix["engine"]
    assert e == {"n_slots": 64, "max_len": 4096, "block_size": 16,
                 "prefill_chunk": 512, "attn_kernel": "fused",
                 "cache_dtype": "bfloat16", "n_blocks": 16384}
    # every slot can reach max_len
    assert e["n_blocks"] == e["n_slots"] * e["max_len"] // e["block_size"]
    assert (mix["loop"], mix["clients"], mix["round_size"],
            mix["rounds"]) == ("closed", 64, 128, 4)
    assert mix["prompt_lengths"] == {"min": 512, "max": 2048,
                                     "multiple_of": 512,
                                     "weight": "inverse_length"}
    assert mix["answer_lengths"] == {"law": "log_uniform", "min": 512,
                                     "max": 2048}
    assert mix["tokens"] == {"distribution": "zipf", "exponent": 1.0}
    assert mix["sampling"] == {"temperature": 0.0}
    assert mix["trace_seconds"] == 5 and mix["check"]["requests"] == 6
    assert (mix["prompt_lengths"]["max"] + mix["answer_lengths"]["max"]
            <= e["max_len"])
    from bench_cells.traffic import generate
    sizes = generate.request_sizes(mix)
    assert len(sizes) == 128
    assert all(p % e["prefill_chunk"] == 0 for p, _ in sizes)
    assert 970 < sum(p for p, _ in sizes) / 128 < 995       # mean 983
    assert 1095 < sum(a for _, a in sizes) / 128 < 1120     # mean 1,108
    # a 30 s window at 60 tokens a second a client cannot exhaust a queue
    per_client = mix["rounds"] * mix["round_size"] // mix["clients"]
    assert per_client * mix["answer_lengths"]["min"] > 30 * 60
    kda, experts, paged = (re.compile(mix["kernels"][k]) for k in (
        "kda_recurrence", "moe_experts", "paged_attention"))
    for line, want in {
            '%kda_recurrence.7 = (f32[64,32,128]{2,1,0}, f32[64,32,128,128]'
            '{3,2,1,0}) custom-call(': (True, False, False),
            '%moe_experts.3 = f32[512,1024]{1,0} custom-call(':
                (False, True, False),
            '%paged_attention.1 = f32[64,1,32,512]{3,2,1,0} custom-call(':
                (False, False, True),
            '%fusion.3 = f32[1] fusion(%kda_recurrence.7)':
                (False, False, False),
    }.items():
        assert (bool(kda.search(line)), bool(experts.search(line)),
                bool(paged.search(line))) == want, line


# -- counts against hand counts ------------------------------------------------


def test_parameter_counts_by_hand(arch):
    c = counts_kimi_linear
    assert c.kda_mixer_params(arch) == (
        3 * 2304 * 4096 + 3 * 4 * 4096 + 2304 * 128 + 128 * 4096 + 32 + 4096
        + 2304 * 32 + 2304 * 128 + 128 * 4096 + 128 + 4096 * 2304
    ) == 39_514_272
    assert c.latent_mixer_params(arch) == (
        2304 * 32 * 192 + 2304 * 576 + 512 + 512 * 32 * 256 + 4096 * 2304
    ) == 29_114_880
    assert c.expert_params(arch) == 3 * 2304 * 1024 == 7_077_888
    assert c.dense_params(arch) == 3 * 2304 * 9216 == 63_700_992
    assert c.router_params(arch) == 2304 * 256 + 256 == 590_080
    # the published model: 256 experts a mixture layer, 163,840 rows
    assert c.total_params(arch, 256, 163_840) == (
        20 * 39_514_272 + 7 * 29_114_880 + 63_700_992 + 27 * 4_608
        + 26 * (257 * 7_077_888 + 590_080) + 2 * 377_487_360 + 2_304
    ) == 49_122_681_728
    assert c.active_params_per_token(arch, 163_840) == (
        20 * 39_514_272 + 7 * 29_114_880 + 63_700_992 + 27 * 4_608
        + 26 * (9 * 7_077_888 + 590_080) + 377_487_360 + 2_304
    ) == 3_106_972_544
    # the cut this repo runs
    assert c.total_params(arch) == (
        20 * 39_514_272 + 7 * 29_114_880 + 63_700_992 + 27 * 4_608
        + 26 * (16 * 7_077_888 + 7_077_888 + 590_080) + 2 * 20_480 * 2_304
        + 2_304) == 4_296_057_728
    assert 2 * c.total_params(arch) / 1e9 == pytest.approx(8.59, abs=0.005)


def test_state_and_kernel_bytes_by_hand(arch):
    c = counts_kimi_linear
    assert c.state_bytes_per_slot(arch) == 20 * 4 * (
        32 * 128 * 128 + 3 * 3 * 4096) == 44_892_160
    assert c.d_cache(arch) == 640
    assert c.kv_bytes_per_position(arch) == 7 * 640 * 2 == 8_960
    # 64 slots: 20 layers x 64 x (4 MiB of state + 6 rows of 4096 + 32)
    assert c.kda_bytes(arch, 64) == 20 * 64 * 4 * (
        2 * 32 * 128 * 128 + 5 * 4096 + 32) == 5_473_730_560
    assert c.kda_bytes(arch, 1) * 64 == c.kda_bytes(arch, 64)
    assert c.latent_kv_bytes(arch, 98_560, 64) == (
        98_560 * 8_960 + 7 * 64 * 32 * (640 + 512) * 4)
    assert c.held_experts_bytes(arch, 360, 1024) == (
        360 * 7_077_888 * 2 + 1024 * 2304 * 6)


def test_seeded_weights_have_the_programs_layout_and_count():
    import jax

    from bench_cells.runners import serve_kimi_linear
    from simple_distributed_machine_learning_tpu.models.kimi_linear import (
        KimiLinearConfig,
    )
    tree = weights_kimi_linear.init_kimi_linear(2 ** 31 + 5, TOY)
    again = weights_kimi_linear.init_kimi_linear(2 ** 31 + 5, TOY)
    leaves = jax.tree.leaves(tree)
    assert {str(a.dtype) for a in leaves} == {"float32"}
    assert sum(a.size for a in leaves) == counts_kimi_linear.total_params(TOY)
    half = weights_kimi_linear.init_layer(7, dict(TOY, param_dtype="bfloat16"),
                                          1)
    assert {str(a.dtype) for a in jax.tree.leaves(half)} == {"bfloat16",
                                                              "float32"}
    assert all((a == b).all() for a, b in zip(leaves, jax.tree.leaves(again)))
    # the experts' matrices are those of the experts held, no more
    assert tree["blocks"][1]["moe"]["gate"].shape == (8, 64, 48)
    assert tree["blocks"][1]["moe"]["router"].shape == (64, 16)
    assert ["mla" in b for b in tree["blocks"]] == [False, False, True,
                                                    False, True]
    # the stated departure: the matrices back to the model's width have no
    # column mean, the others keep theirs
    import numpy as np
    kda, moe = tree["blocks"][0]["kda"], tree["blocks"][1]
    for w in (kda["wo"], tree["blocks"][2]["mla"]["wo"],
              tree["blocks"][0]["mlp"]["down"], moe["moe"]["down"],
              moe["shared"]["down"]):
        assert abs(np.asarray(w, np.float32).mean(-2)).max() < 1e-6
    for w in (kda["wq"], moe["shared"]["gate"], moe["moe"]["router"]):
        assert abs(np.asarray(w, np.float32).mean(-2)).max() > 2e-3
    # one layer drawn alone is the layer of the whole tree
    alone = weights_kimi_linear.init_layer(2 ** 31 + 5, TOY, 3)
    assert all((a == b).all() for a, b in zip(
        jax.tree.leaves(alone), jax.tree.leaves(tree["blocks"][3])))
    stage, = serve_kimi_linear.kimi_linear_stage(KimiLinearConfig(**TOY),
                                                 tree)
    assert stage.params is tree
    with pytest.raises(SystemExit, match="parameter layout"):
        serve_kimi_linear.kimi_linear_stage(
            KimiLinearConfig(**dict(TOY, d_expert=64)), tree)


# -- the readers over hand-made records and a hand-made trace -------------------


def _hand_ctx(monkeypatch, attrs, arch, with_trace=True):
    """A window of three ticks (two decoded) whose spans carry ``attrs``,
    and a trace of two decode runs of 10 ms and a chunk run: 5 ms of
    ``kda_recurrence``, 2 ms of ``moe_experts`` and 1 ms of
    ``paged_attention`` inside each decode run, the first two inside the
    chunk's too (which no reader here may count)."""
    from bench_cells import program_spans

    ticks = [types.SimpleNamespace(attrs=dict(a), id=i)
             for i, a in enumerate(attrs)]
    window = types.SimpleNamespace(ticks=ticks, spans=ticks, kids={})
    ev = xplane.Event
    ops = []
    for t0 in (0.0, 0.020):
        ops += [ev("kda", t0, t0 + 0.005,
                   "%kda_recurrence.2 = (f32[64,32,128]{2,1,0}, f32[64,32,"
                   "128,128]{3,2,1,0}) custom-call("),
                ev("moe", t0 + 0.005, t0 + 0.007,
                   "%moe_experts.3 = f32[512,1024]{1,0} custom-call("),
                ev("attn", t0 + 0.007, t0 + 0.008,
                   "%paged_attention.1 = f32[64,1,32,512]{3,2,1,0} "
                   "custom-call("),
                ev("rest", t0 + 0.008, t0 + 0.010, "%fusion.9 = fusion(")]
    ops += [ev("kda", 0.040, 0.060,
               "%kda_recurrence.4 = (f32[32,512,128]{2,1,0}, f32[1,32,128,"
               "128]{3,2,1,0}) custom-call("),
            ev("moe", 0.060, 0.070,
               "%moe_experts.5 = f32[4096,1024]{1,0} custom-call(")]
    dev = xplane.Device(0, ops, [
        ev("jit_step_kda_decode", 0.0, 0.010),
        ev("jit_step_kda_decode", 0.020, 0.030),
        ev("jit_chunk_kda_prefill", 0.040, 0.080)])
    monkeypatch.setattr(program_spans, "serve_window", lambda run: window)
    monkeypatch.setattr(program_spans, "window_ticks",
                        lambda r, spans: list(spans))
    return {"records": {"kind": "serve", "n_slots": 64, "cache_itemsize": 2,
                        "traced_ticks": [0, 3], "kimi_linear": arch},
            "trace": xplane.Trace([dev], []) if with_trace else None,
            "mix": manifest.load_cell(CELL).traffic, "peaks": PEAKS}


SPANS = [{"decoding": 64, "experts_hit": 360, "expert_rows": 1020,
          "expert_rows_max": 9, "kv_positions": 98_000},
         {"decoding": 0, "experts_hit": 0, "expert_rows": 0,
          "expert_rows_max": 0, "kv_positions": 0},
         {"decoding": 62, "experts_hit": 364, "expert_rows": 1028,
          "expert_rows_max": 8, "kv_positions": 99_000}]


def test_the_five_readers_by_hand(monkeypatch, arch):
    ctx = _hand_ctx(monkeypatch, SPANS, arch)
    read = {n: manifest.load_reader(n)(ctx) for n in NEW}
    assert read["moe.ep16_held_experts_hit_pct"] == pytest.approx(
        100 * 362 / (26 * 16))
    # two decode runs, each the mean tick's bytes, over 2 x 5 ms at 1e11 B/s
    c = counts_kimi_linear
    assert read["kernel.kda_recurrence_roofline_pct"] == pytest.approx(
        100 * 2 * c.kda_bytes(arch, 63) / 1e11 / 0.010)
    assert read["kernel.ep16_experts_roofline_pct"] == pytest.approx(
        100 * 2 * c.held_experts_bytes(arch, 362, 1024) / 1e11 / 0.004)
    assert read["kernel.latent_attention_roofline_pct"] == pytest.approx(
        100 * 2 * c.latent_kv_bytes(arch, 98_500, 64) / 1e11 / 0.002)
    # 5 of a decode run's 10 busy ms; the chunk's 20 ms are nobody's
    assert read["model.kda_share_pct"] == pytest.approx(50.0)


def test_the_trace_readers_find_no_kernel_is_an_error(monkeypatch, arch):
    ctx = _hand_ctx(monkeypatch, SPANS, arch)
    ctx["trace"].devices[0].ops[:] = [
        e for e in ctx["trace"].devices[0].ops if e.name == "rest"]
    for name in NEW[1:]:
        with pytest.raises(SystemExit, match="no device operation"):
            manifest.load_reader(name)(ctx)


@pytest.mark.parametrize("name", NEW)
def test_readers_give_nothing_on_a_program_without_the_counts(
        monkeypatch, name, arch):
    """Another family's ticks carry no ``expert_rows`` and another runner's
    records no ``kimi_linear`` (the parent commit runs no such cell at
    all): every new reader returns ``None`` and does not raise; nor on an
    untraced run for those that read the trace."""
    read = manifest.load_reader(name)
    bare = [{"chunk": 0, "decoding": 1}]
    if name not in ("model.kda_share_pct",          # they read no count
                    "kernel.kda_recurrence_roofline_pct"):
        assert read(_hand_ctx(monkeypatch, bare, arch)) is None
    for attrs, with_trace in ((SPANS, True), (bare, False)):
        ctx = _hand_ctx(monkeypatch, attrs, arch, with_trace)
        del ctx["records"]["kimi_linear"]
        ctx["records"]["cohere2"] = {}
        assert read(ctx) is None
    ctx = _hand_ctx(monkeypatch, SPANS, arch, with_trace=False)
    assert (read(ctx) is None) == name.startswith(("kernel.", "model."))
    ctx["records"] = {"kind": "train"}
    assert read(ctx) is None


# -- the runner at toy size ----------------------------------------------------


def toy_cell(limits=LIMITS, arch=TOY, requests=6):
    real = manifest.load_cell(CELL)
    mix = copy.deepcopy(real.traffic)
    mix.update(
        engine={"n_slots": 4, "max_len": 64, "block_size": 4, "n_blocks": 64,
                "prefill_chunk": 8, "attn_kernel": "fused",
                "cache_dtype": "float32"},
        clients=4, round_size=8, rounds=400,
        prompt_lengths={"min": 8, "max": 24, "multiple_of": 8,
                        "weight": "inverse_length"},
        answer_lengths={"law": "log_uniform", "min": 3, "max": 8})
    mix["check"] = {"requests": requests, "limits": limits}
    return manifest.Cell(CELL, 1, real.config_name, real.traffic_name,
                         dict(real.config, kimi_linear_config=arch), mix,
                         real.end_to_end, real.per_layer)


@pytest.fixture()
def toy_conditions(monkeypatch):
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: 1)
    monkeypatch.setattr(weights_kimi_linear, "STD", 0.1)


def test_sound_run_is_correct_and_its_records_feed_the_readers(
        toy_conditions):
    result = benchrun.run_cell(toy_cell(requests=24), 2 ** 31 + 5, 2.0,
                               False, DEVICE, PEAKS)
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
    place; and the counter metric over the toy window's own spans."""
    from bench_cells.runners import serve_kimi_linear

    cell = toy_cell(requests=40)
    run = serve_kimi_linear.Run(cell, 1, harness.Spans())
    run.setup()
    run.window(2.0, _NoTrace())
    ctx = {"records": run.records, "trace": None, "mix": cell.traffic,
           "peaks": PEAKS}
    hit = manifest.load_reader("moe.ep16_held_experts_hit_pct")(ctx)
    # 4 mixture layers x 8 held of 16; 2-3 live rows x top 3: a few pairs
    # land on held experts, and a slot that sits out lands on none
    assert 5.0 < hit <= 60.0
    for name in NEW[1:]:
        assert manifest.load_reader(name)(ctx) is None   # no trace, no share
    assert run.records["kimi_linear"] == TOY
    program, control = run.check(), run.control()
    assert check.compare(program, LIMITS)[0], program
    ok, compared = check.compare(control, LIMITS)
    assert not ok and compared["gap_mean"]["value"] > 4 * LIMITS["gap_mean"]


class _FakeTracer:
    """What ``runners/serve.py::Run.window`` asks of ``harness.Tracer``,
    without a profiler."""
    enabled = True

    def __init__(self, run):
        self.run, self.dir, self.started_at, self.window_s = run, None, None, None
        self.finished_at_start = None

    def start(self):
        import time
        self.dir, self.started_at = "fake", time.perf_counter()
        self.finished_at_start = sum(
            len(r["stamps"]) >= r["n_new"] for r in self.run.sent)

    def stop(self):
        self.window_s = 0.0

    @property
    def running(self):
        return self.started_at is not None and self.window_s is None


def test_a_traced_window_begins_once_a_request_has_finished(toy_conditions):
    """The base window would start the trace at 40 % of the window; this
    runner holds it back until something has finished (a traced window ends
    with the profiler's stop, and this mix's shortest answer outlasts the
    base runner's mark), and the traced ticks are marked from there."""
    from bench_cells.runners import serve_kimi_linear

    run = serve_kimi_linear.Run(toy_cell(), 3, harness.Spans())
    run.setup()
    # answers long enough that nothing has finished at 40 % of 1 s
    for q in run.queues:
        q[:] = [(p, 40) for p, _ in q]
    tracer = _FakeTracer(run)
    run.mix["trace_seconds"] = 0.2
    run.window(3.0, tracer)
    assert tracer.finished_at_start >= 1
    first, last = run.records["traced_ticks"]
    assert 0 < first < last <= len(run.records["ticks"])
    # held back: asked to start while nothing has finished, it does not
    fresh = _FakeTracer(run)
    held = serve_kimi_linear._HeldBack(fresh, lambda: False)
    held.start()
    assert held.enabled and held.dir is None and not held.running
    serve_kimi_linear._HeldBack(fresh, lambda: True).start()
    assert fresh.dir == "fake" and held.running
