"""What PR 32 adds to the benchmark, off the chip: the manifest walk finds the
new cell, its files and its readers (entries found BY NAME, never by their
place in a list); the configuration against the catalog's keys;
``counts_sdar`` against hand counts; the three new readers on fixture records
and a fixture trace; and ``runners/serve_diffusion.py`` driven past the
harness's look for a chip at toy size, as ``test_bench_cells_jamba.py`` drives
the hybrid's: a sound run comes out correct, the int8 control and a run whose
mask is causal inside a block do not.

The toy's limits are set by the real mix's rule (above what sound runs of the
toy read, below what its control reads); its weights are drawn at normal 0.1
instead of 0.02, because at width 64 the published scale leaves the layers
all but linear and a wrong mask would hardly show, and held in float32 (see
``LIMITS``).
"""

import copy
import json
import os
import re
import types

import pytest

from bench_cells import check, counts_sdar, harness, manifest, weights_sdar
from bench_cells import run as benchrun
from bench_cells.reduce import xplane

CELL = "sdar-30b-a3b.serve-diffuse-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
DEVICE = {"platform": "cpu", "kind": "test", "count": 1}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
TOY = {"vocab": 256, "seq_len": 64, "d_model": 64, "n_heads": 4,
       "n_kv_heads": 2, "head_dim": 16, "n_layers": 2, "n_experts": 8,
       "top_k": 2, "d_expert": 32, "rope_theta": 1e6, "rms_eps": 1e-6,
       "block_length": 4, "denoising_steps": 4, "mask_id": 255,
       "param_dtype": "float32"}
# the toy runs in float32 (weights and pool), so that what the tests hold is
# the check's logic and not a window's luck: over 3 seeds and windows of 0.6
# to 2 s on the CPU sound runs read gap_mean and pick_gap_mean 0 exactly (a
# sample of 30 requests, some 300 fixed positions), the int8 control 1.3e-3 to
# 5.3e-3 and 2.3e-3 to 4.2e-3, a mask that is causal inside a block 0.1 and
# 0.03 and more. In bfloat16 at width 64 the toy's sound and control runs lie
# 1.4 x apart and a sample's mean swings 4 x: the real cell's limits are set
# on the chip (PERF.md section 2)
LIMITS = {"gap_mean": 3e-4, "pick_gap_mean": 3e-4, "compiles_in_window": 0}
NEW = {"diffusion.tokens_per_forward": ("tokens", "higher", "serve engine",
                                        "serve_tokens_per_s"),
       "moe.experts_hit_pct": ("%", "lower", "model programs", "tpot_p95_ms"),
       "kernel.moe_experts_roofline_pct": ("%", "higher", "kernels",
                                           "tpot_p95_ms")}


@pytest.fixture(scope="module")
def bench():
    return manifest.load_manifest()


@pytest.fixture(scope="module")
def arch():
    return manifest.load_cell(CELL).config["sdar_config"]


def _entry(entries, name):
    found, = (m for m in entries if m["name"] == name)
    return found


# -- the manifest walk ---------------------------------------------------------


def test_the_cell_its_files_and_its_readers_are_found(bench):
    cell = manifest.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "sdar-30b-a3b", "serve-diffuse-closed")
    assert cell.traffic["runner"] == "serve_diffusion"
    for rel in ("runners/serve_diffusion.py", "reference/sdar.py",
                "weights_sdar.py", "counts_sdar.py"):
        assert os.path.isfile(os.path.join(manifest.HERE, rel)), rel
    e2e = {m["name"] for m in cell.end_to_end}
    assert e2e == {"serve_tokens_per_s", "tpot_p95_ms", "setup_s"}
    layer = {m["name"] for m in cell.per_layer}
    assert layer >= set(NEW) | {
        "entry.trace_lower_s", "engine.tick_ms_p50", "engine.ttft_p50_ms",
        "model.decode_device_ms", "device.idle_pct.serve",
        "engine.host_ms_per_tick", "engine.host_admit_ms",
        "engine.host_prepare_ms", "engine.host_dispatch_ms",
        "engine.host_emit_ms", "engine.ttft_queue_ms_p50",
        "engine.chunk_ticks_pct", "engine.tick_ms_max",
        "engine.idle_explained_pct", "engine.ahead_ticks_pct"}
    # one counts a token a decoding slot, the other multi-head GPT's bytes
    assert not layer & {"engine.occupancy_pct",
                        "kernel.paged_attention_roofline_pct",
                        "cache.state_live_pct"}
    for name in layer | e2e:
        assert callable(manifest.load_reader(name))


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_entry_by_name(bench, name):
    m = _entry(bench["per_layer"], name)
    unit, better, layer, moves = NEW[name]
    assert (m["unit"], m["better"], m["layer"], m["moves"]) == (
        unit, better, layer, moves)
    assert CELL in m["workloads"]
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    # the end-to-end metric it moves is one the cell reports
    assert CELL in _entry(bench["end_to_end"], moves)["workloads"]


def test_every_cell_still_lists_the_trace_lower_metric(bench):
    lower = _entry(bench["per_layer"], "entry.trace_lower_s")
    assert set(lower["workloads"]) == {w["name"] for w in bench["workloads"]}
    w = _entry(bench["workloads"], CELL)
    assert len(w["why"]) <= 200 and w["chips"] == 1
    c = _entry(bench["configs"], "sdar-30b-a3b")
    assert c["reduced"] == ["num_hidden_layers"] and len(c["why"]) <= 200


# -- the configuration ----------------------------------------------------------


def test_configuration_file_holds_the_published_config(arch):
    cfg = manifest.load_cell(CELL).config
    pub = cfg["published"]
    if os.path.isfile(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            row, = (r for r in map(json.loads, f)
                    if r["name"] == "SDAR-30B-A3B-Chat")
        assert pub == row["config"] and cfg["source"] == row["source_url"]
    # depth alone is cut; every other key stands at the top level as published
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["reference"] == "sdar"
    assert {k: cfg[k] for k in pub if k != "num_hidden_layers"} == {
        k: v for k, v in pub.items() if k != "num_hidden_layers"}
    assert (cfg["num_hidden_layers"], pub["num_hidden_layers"]) == (7, 48)
    assert len(cfg["assumed"]) >= 6 and cfg["departures"]
    assert "seven pipeline stages" in cfg["deployment"]
    assert arch == {
        "vocab": pub["vocab_size"], "seq_len": 1024,
        "d_model": pub["hidden_size"], "n_heads": pub["num_attention_heads"],
        "n_kv_heads": pub["num_key_value_heads"],
        "head_dim": pub["head_dim"], "n_layers": 7,
        "n_experts": pub["num_experts"], "top_k": pub["num_experts_per_tok"],
        "d_expert": pub["moe_intermediate_size"],
        "rope_theta": pub["rope_theta"], "rms_eps": pub["rms_norm_eps"],
        "block_length": 4, "denoising_steps": 4, "mask_id": 151669,
        "param_dtype": "bfloat16"}
    assert (pub["num_experts"], pub["num_experts_per_tok"],
            pub["vocab_size"], pub["tie_word_embeddings"]) == (
        128, 8, 151936, False)


def test_traffic_file_is_the_issues_mix(arch):
    from bench_cells.traffic import generate

    mix = manifest.load_cell(CELL).traffic
    e = mix["engine"]
    assert e == {"n_slots": 64, "max_len": 1024, "block_size": 16,
                 "prefill_chunk": 256, "attn_kernel": "fused",
                 "cache_dtype": "bfloat16", "n_blocks": 4096}
    # every slot can reach max_len
    assert e["n_blocks"] == e["n_slots"] * e["max_len"] // e["block_size"]
    assert (mix["clients"], mix["round_size"], mix["loop"]) == (
        64, 128, "closed")
    assert mix["diffusion"] == {"block_length": 4, "denoising_steps": 4,
                                "remasking": "low_confidence_static"}
    assert mix["sampling"] == {"temperature": 0.0}
    sizes = generate.request_sizes(mix)
    prompts, answers = zip(*sizes)
    assert min(prompts) == 64 and max(prompts) == 512
    assert all(p % 64 == 0 for p in prompts)
    assert 185 < sum(prompts) / len(prompts) < 192
    assert (min(answers), max(answers)) == (129, 509)
    assert 274 < sum(answers) / len(answers) < 280
    assert any(a % 4 for a in answers)          # cut inside the last block
    assert max(prompts) + max(answers) <= e["max_len"]
    # rounds enough that no client runs dry in a window of 450 requests
    assert mix["rounds"] * mix["round_size"] >= 1000
    moe, paged = (re.compile(mix["kernels"][k])
                  for k in ("moe_experts", "paged_attention"))
    for line, (is_moe, is_paged) in {
            '%moe_experts.7 = f32[2048,768]{1,0} custom-call(':
                (True, False),
            'ROOT %moe_experts = f32[2048,2048]{1,0} custom-call(':
                (True, False),
            '%paged_attention.1 = f32[64,1,128,512]{3,2,1,0} custom-call(':
                (False, True),
            '%fusion.3 = f32[1] fusion(%moe_experts.7)': (False, False),
    }.items():
        assert (bool(moe.search(line)), bool(paged.search(line))) == (
            is_moe, is_paged), line
    assert set(mix["check"]["limits"]) == {"gap_mean", "pick_gap_mean",
                                           "compiles_in_window"}


# -- counts against hand counts ------------------------------------------------


def test_parameter_count_by_hand(arch):
    attention = (2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048) + 256
    assert counts_sdar.attention_params(arch) == attention == 18_874_624
    assert counts_sdar.expert_params(arch) == 3 * 2048 * 768 == 4_718_592
    layer = attention + 4096 + 2048 * 128 + 128 * 3 * 2048 * 768
    assert counts_sdar.layer_params(arch) == layer == 623_120_640
    ends = 2 * 151_936 * 2048 + 2048
    assert counts_sdar.total_params(arch) == 7 * layer + ends \
        == 4_984_176_384
    assert counts_sdar.total_params(arch, 48) == 48 * layer + ends \
        == 30_532_122_624
    # the "A3B": what one token multiplies by over the published 48 layers
    assert counts_sdar.active_params_per_token(arch, 48) == 48 * (
        attention + 4096 + 2048 * 128 + 8 * 4_718_592) + ends \
        == 3_353_032_704
    assert counts_sdar.kv_bytes_per_position(arch) == 14 * 1024


def test_expert_bytes_by_hand(arch):
    # a tick's 64 x 4 rows x 8 experts: 2,048 routed rows a layer
    rows = 2048
    per_row = 2 * 2048 * 2 + 2 * 768 * 4 + 768 * 2 + 2048 * 4
    one_layer = 128 * 4_718_592 * 2 + rows * per_row
    assert counts_sdar.moe_experts_bytes(arch, 128, rows) == one_layer \
        == 1_257_242_624
    # linear in both: seven layers' hits and rows in one call
    assert counts_sdar.moe_experts_bytes(arch, 7 * 128, 7 * rows) \
        == 7 * one_layer
    assert 8.7e9 < 7 * one_layer < 8.9e9
    # an expert that got no row costs nothing
    assert counts_sdar.moe_experts_bytes(arch, 0, 0) == 0
    assert counts_sdar.moe_experts_flops(arch, rows) == 2 * rows * 4_718_592


def test_seeded_weights_have_the_programs_layout_and_count():
    import jax

    from bench_cells.runners import serve_diffusion
    from simple_distributed_machine_learning_tpu.models.sdar import SdarConfig
    tree = weights_sdar.init_sdar(2 ** 31 + 5, TOY)
    again = weights_sdar.init_sdar(2 ** 31 + 5, TOY)
    leaves = jax.tree.leaves(tree)
    assert all(a.dtype == "float32" for a in leaves)
    assert sum(a.size for a in leaves) == counts_sdar.total_params(TOY)
    assert all((a == b).all() for a, b in zip(leaves, jax.tree.leaves(again)))
    stage, = serve_diffusion.sdar_stage(SdarConfig(**TOY), tree)
    assert stage.params is tree
    with pytest.raises(SystemExit, match="parameter layout"):
        serve_diffusion.sdar_stage(SdarConfig(**dict(TOY, d_expert=64)),
                                   tree)


# -- the readers on fixture records and a fixture trace -----------------------


def _tick(**attrs):
    return types.SimpleNamespace(attrs=attrs, id=id(attrs))


def _with_window(ticks, fn):
    from bench_cells import program_spans

    window = types.SimpleNamespace(ticks=ticks, spans=[], kids={})
    orig = (program_spans.serve_window, program_spans.window_ticks)
    program_spans.serve_window = lambda run: window
    program_spans.window_ticks = lambda records, spans: ticks
    try:
        return fn()
    finally:
        program_spans.serve_window, program_spans.window_ticks = orig


def test_tokens_per_forward_and_experts_hit_on_fixture_ticks():
    ticks = [_tick(forwards=4, emitted=0, experts_hit=16, decoding=4),
             _tick(forwards=4, emitted=8, experts_hit=12, decoding=4),
             _tick(forwards=0, emitted=0, experts_hit=0, decoding=0),
             _tick(forwards=2, emitted=0, experts_hit=8, decoding=2)]
    ctx = {"records": {"kind": "serve", "n_slots": 4, "sdar": TOY},
           "trace": None, "mix": {}, "peaks": PEAKS}
    read = manifest.load_reader
    assert _with_window(ticks, lambda: read(
        "diffusion.tokens_per_forward")(ctx)) == pytest.approx(0.8)
    # of 2 layers x 8 experts, over the ticks that ran a forward
    assert _with_window(ticks, lambda: read("moe.experts_hit_pct")(
        ctx)) == pytest.approx(100 * 12 / 16)
    assert _with_window(ticks, lambda: read(
        "kernel.moe_experts_roofline_pct")(ctx)) is None    # no trace


def test_expert_roofline_share_on_a_fixture_trace():
    """Two decode runs and a chunk run: the share counts the expert
    operations inside the decode runs only, against the bytes of the traced
    ticks' mean run."""
    mix = manifest.load_cell(CELL).traffic
    kernel = ('%moe_experts.3 = f32[32,32]{1,0} custom-call(%a), '
              'custom_call_target="tpu_custom_call"')
    other = '%fusion.1 = f32[4] fusion(%b)'
    ops = [xplane.Event("moe_experts.3", 0.10, 0.12, kernel),
           xplane.Event("fusion.1", 0.12, 0.13, other),
           xplane.Event("moe_experts.3", 0.30, 0.32, kernel),
           # inside the chunk's run: on neither side of the share
           xplane.Event("moe_experts.3", 0.50, 0.59, kernel)]
    modules = [xplane.Event("jit_step_block_denoise(1)", 0.10, 0.20),
               xplane.Event("jit_step_block_denoise(1)", 0.30, 0.40),
               xplane.Event("jit_chunk_block_prefill(2)", 0.50, 0.60)]
    trace = xplane.Trace([xplane.Device(0, ops, modules)], [])
    ticks = [_tick(forwards=4, experts_hit=16, emitted=0),
             _tick(forwards=0, experts_hit=0, emitted=0),
             _tick(forwards=4, experts_hit=12, emitted=4)]
    ctx = {"records": {"kind": "serve", "n_slots": 4, "sdar": TOY,
                       "traced_ticks": [0, 3]},
           "trace": trace, "mix": mix, "peaks": PEAKS}
    rows = 2 * 2 * 4 * 4
    a_run = (counts_sdar.moe_experts_bytes(TOY, 16, rows)
             + counts_sdar.moe_experts_bytes(TOY, 12, rows)) / 2
    got = _with_window(ticks, lambda: manifest.load_reader(
        "kernel.moe_experts_roofline_pct")(ctx))
    assert got == pytest.approx(100 * 2 * a_run / 1e11 / 0.04)
    trace.devices[0].ops[:] = [ops[1]]
    with pytest.raises(SystemExit, match="expert products were not found"):
        _with_window(ticks, lambda: manifest.load_reader(
            "kernel.moe_experts_roofline_pct")(ctx))


def test_readers_give_nothing_on_a_program_without_the_counts():
    """The parent commit's ticks carry no ``forwards`` or ``experts_hit`` and
    another runner's records no ``sdar``: the three readers return ``None``
    and do not raise."""
    ticks = [_tick(chunk=0, decoding=1, emitted=1)]
    for records in ({"kind": "serve", "n_slots": 4, "traced_ticks": [0, 1]},
                    {"kind": "serve", "n_slots": 4, "traced_ticks": [0, 1],
                     "sdar": TOY}):
        ctx = {"records": records, "trace": object(), "mix": {},
               "peaks": PEAKS}
        for name in NEW:
            assert _with_window(ticks, lambda: manifest.load_reader(name)(
                ctx)) is None, name


# -- the runner at toy size ----------------------------------------------------


def toy_cell(limits=LIMITS, arch=TOY):
    real = manifest.load_cell(CELL)
    mix = copy.deepcopy(real.traffic)
    mix.update(
        engine={"n_slots": 4, "max_len": 64, "block_size": 8, "n_blocks": 32,
                "prefill_chunk": 16, "attn_kernel": "fused",
                "cache_dtype": "float32"},
        clients=4, round_size=8, rounds=400,
        prompt_lengths={"min": 8, "max": 24, "multiple_of": 8,
                        "weight": "inverse_length"},
        answer_lengths={"law": "log_uniform", "min": 5, "max": 14})
    mix["check"] = {"requests": 30, "limits": limits}
    return manifest.Cell(CELL, 1, real.config_name, real.traffic_name,
                         dict(real.config, sdar_config=arch), mix,
                         real.end_to_end, real.per_layer)


@pytest.fixture(autouse=True)
def toy_conditions(monkeypatch):
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: 1)
    monkeypatch.setattr(weights_sdar, "STD", 0.1)


def _run(cell, seconds=2.0, seed=2 ** 31 + 5):
    return benchrun.run_cell(cell, seed, seconds, False, DEVICE, PEAKS)


def test_sound_run_is_correct_and_its_records_feed_the_readers():
    result = _run(toy_cell())
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 4
    assert set(result["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms",
                                      "setup_s"}
    assert set(result["compared"]) == {"gap_mean", "pick_gap_mean",
                                       "compiles_in_window"}
    assert result["compared"]["compiles_in_window"] == {"value": 0,
                                                        "limit": 0}
    json.dumps(result)


def test_a_mask_that_is_causal_inside_a_block_is_not_correct(monkeypatch):
    """The timed path broken underneath: a block's queries see only the
    rows up to their own position, as a one-token decoder's would."""
    from simple_distributed_machine_learning_tpu.models import sdar

    orig = sdar._paged_attend

    def causal(kc, vc, li, q, tables, qpos, bs):
        return orig(kc, vc, li, q, tables, qpos - (
            qpos.shape[1] - 1 - sdar.jnp.arange(qpos.shape[1])), bs)

    monkeypatch.setattr(sdar, "_paged_attend", causal)
    # a width of its own: the programs are memoized by configuration, and
    # the broken one must neither find the sound one nor be found later
    result = _run(toy_cell(arch=dict(TOY, d_expert=48)))
    assert result["correct"] is False
    assert result["compared"]["gap_mean"]["value"] > 10 * LIMITS["gap_mean"]


class _NoTrace:
    enabled, dir, running = False, None, False


def test_control_is_not_correct_and_the_toy_window_is_read():
    """The same comparison, the reference in int8 operands in the program's
    place; and the window's own spans: every decode dispatched ahead, 0.7
    to 0.8 tokens a forward, most of the toy's 16 (layer, expert) pairs
    hit."""
    from bench_cells.runners import serve_diffusion

    cell = toy_cell()
    run = serve_diffusion.Run(cell, 1, harness.Spans())
    run.setup()
    run.window(2.0, _NoTrace())
    ctx = {"records": run.records, "trace": None, "mix": cell.traffic,
           "peaks": PEAKS}
    assert manifest.load_reader("engine.ahead_ticks_pct")(ctx) > 99.0
    per_forward = manifest.load_reader("diffusion.tokens_per_forward")(ctx)
    assert 0.6 < per_forward <= 0.8
    assert 50.0 < manifest.load_reader("moe.experts_hit_pct")(ctx) <= 100.0
    assert manifest.load_reader("kernel.moe_experts_roofline_pct")(
        ctx) is None            # no trace, no share
    assert run.records["sdar"] == TOY
    program, control = run.check(), run.control()
    assert check.compare(program, LIMITS)[0], program
    ok, compared = check.compare(control, LIMITS)
    assert not ok
    assert compared["gap_mean"]["value"] > 3 * LIMITS["gap_mean"]
    assert compared["pick_gap_mean"]["value"] > 3 * LIMITS["pick_gap_mean"]


def test_a_mix_whose_schedule_is_not_the_configurations_ends_the_run():
    from bench_cells.runners import serve_diffusion

    cell = toy_cell()
    cell.traffic["diffusion"]["denoising_steps"] = 2
    with pytest.raises(SystemExit, match="schedule"):
        serve_diffusion.Run(cell, 1, harness.Spans()).setup()
