"""What PR 35 adds to the benchmark, off the chip: the manifest walk finds
the new cell, its files and its readers BY NAME (membership, never a list's
position or exact length); ``counts_nemotron_h`` against hand counts; and
``runners/serve_nemotron_h.py`` driven past the harness's look for a chip at
toy size, as ``test_bench_cells_jamba.py`` drives the hybrid's: a sound run
comes out correct, the int8 control and a run that skips the state reset on
bind do not, and the new counter readers read the toy window's own spans.

The toy's limit is set by the real mix's rule (above what sound runs of the
toy read, below what its control reads); its weights are drawn at normal 0.1
instead of 0.02, because at width 64 the published scale leaves the layers
all but linear and a wrong state would hardly show.
"""

import copy
import json
import os
import re
import types

import pytest

from bench_cells import (
    check,
    counts_nemotron_h,
    harness,
    manifest,
    weights_nemotron_h,
)
from bench_cells import run as benchrun

CELL = "nemotron3-super-120b-a12b.serve-agent-closed"
CONFIG = "nemotron3-super-120b-a12b"
NEW = ("kernel.latent_experts_roofline_pct", "kernel.mamba2_scan_roofline_pct",
       "moe.held_experts_hit_pct", "moe.rows_per_held_expert")
DEVICE = {"platform": "cpu", "kind": "test", "count": 1}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
TOY = {"vocab": 128, "seq_len": 64, "d_model": 64, "pattern": "MEM*E",
       "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "mamba_heads": 8,
       "mamba_head_dim": 32, "n_groups": 2, "d_state": 16, "d_conv": 4,
       "n_experts": 16, "top_k": 3, "experts_held": 8, "expert_offset": 4,
       "d_latent": 32, "d_expert": 48, "d_shared": 96, "route_scale": 2.5,
       "rms_eps": 1e-5, "param_dtype": "bfloat16"}
# sound toy runs read 1e-5 to 6e-5 over 3 seeds (bfloat16 operands and pool
# against the float32 reference, a flipped last expert now and then), the
# int8 control 4.7e-4 to 1.1e-3, a leaked state more: the limit lies 2.5 x
# above the one range and 3 x below the other
LIMITS = {"gap_mean": 0.00015, "compiles_in_window": 0}


@pytest.fixture(scope="module")
def bench():
    return manifest.load_manifest()


@pytest.fixture(scope="module")
def arch():
    return manifest.load_cell(CELL).config["nemotron_h_config"]


def _by_name(entries):
    return {e["name"]: e for e in entries}


# -- the manifest walk ---------------------------------------------------------


def test_the_cell_its_files_and_its_readers_are_found(bench):
    cell = manifest.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, CONFIG, "serve-agent-closed")
    assert cell.traffic["runner"] == "serve_nemotron_h"
    for rel in ("runners/serve_nemotron_h.py", "reference/nemotron_h.py",
                "weights_nemotron_h.py", "counts_nemotron_h.py"):
        assert os.path.isfile(os.path.join(manifest.HERE, rel)), rel
    e2e = {m["name"] for m in cell.end_to_end}
    assert e2e == {"serve_tokens_per_s", "tpot_p95_ms", "setup_s"}
    layer = {m["name"] for m in cell.per_layer}
    assert set(NEW) | {
        "entry.trace_lower_s", "engine.tick_ms_p50", "engine.occupancy_pct",
        "engine.ahead_ticks_pct", "engine.chunk_ticks_pct",
        "engine.idle_explained_pct", "model.decode_device_ms",
        "device.idle_pct.serve"} <= layer
    # readers that take another runner's records stay with their own cells
    assert not {"moe.experts_hit_pct", "kernel.moe_experts_roofline_pct",
                "kernel.selective_scan_roofline_pct", "cache.state_live_pct",
                "diffusion.tokens_per_forward",
                "kernel.paged_attention_roofline_pct"} & layer
    for name in layer | e2e:
        assert callable(manifest.load_reader(name))
    per_layer = _by_name(bench["per_layer"])
    for name in NEW:
        assert CELL in per_layer[name]["workloads"], name
        assert per_layer[name]["layer"] in ("kernels", "model programs")
    assert per_layer["moe.held_experts_hit_pct"]["better"] == "lower"
    assert per_layer["moe.rows_per_held_expert"]["moves"] == \
        "serve_tokens_per_s"
    cells = [w["name"] for w in bench["workloads"]]
    assert CELL in cells
    assert set(per_layer["entry.trace_lower_s"]["workloads"]) == set(cells)
    w = _by_name(bench["workloads"])[CELL]
    c = _by_name(bench["configs"])[CONFIG]
    assert len(w["why"]) <= 200 and len(c["why"]) <= 200
    assert len(c["source"]) <= 200 and c["source"] == cell.config["source"]
    assert sorted(c["reduced"]) == sorted(cell.config["reduced"])


def test_configuration_file_holds_the_published_widths(arch):
    cfg = manifest.load_cell(CELL).config
    pub = cfg["published"]
    assert sorted(cfg["reduced"]) == [
        "hybrid_override_pattern", "n_routed_experts", "num_hidden_layers",
        "vocab_size"]
    # every published key at the top level too, unchanged but the reduced
    for k, v in pub.items():
        assert (cfg[k] == v) == (k not in cfg["reduced"]), k
    assert (cfg["num_hidden_layers"], cfg["hybrid_override_pattern"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (
        11, "MEM*EMEMEME", 128, 32768)
    # the kept slice is the published layers 33-43, a whole period
    assert pub["hybrid_override_pattern"][33:44] == arch["pattern"]
    assert (pub["num_hidden_layers"], len(pub["hybrid_override_pattern"])) \
        == (88, 88)
    assert all(pub["hybrid_override_pattern"][i:i + 11].count("M") == 5
               and pub["hybrid_override_pattern"][i:i + 11].count("*") == 1
               for i in range(0, 88, 11))
    assert arch == {
        "vocab": 32768, "seq_len": 2048, "d_model": pub["hidden_size"],
        "pattern": "MEM*EMEMEME",
        "n_heads": pub["num_attention_heads"],
        "n_kv_heads": pub["num_key_value_heads"],
        "head_dim": pub["head_dim"],
        "mamba_heads": pub["mamba_num_heads"],
        "mamba_head_dim": pub["mamba_head_dim"],
        "n_groups": pub["n_groups"], "d_state": pub["ssm_state_size"],
        "d_conv": pub["conv_kernel"],
        "n_experts": pub["n_routed_experts"],
        "top_k": pub["num_experts_per_tok"], "experts_held": 128,
        "expert_offset": 0, "d_latent": pub["moe_latent_size"],
        "d_expert": pub["moe_intermediate_size"],
        "d_shared": pub["moe_shared_expert_intermediate_size"],
        "route_scale": float(pub["routed_scaling_factor"]),
        "rms_eps": pub["layer_norm_epsilon"], "param_dtype": "bfloat16"}
    assert arch["mamba_heads"] * arch["mamba_head_dim"] == (
        pub["expand"] * pub["hidden_size"])
    assert cfg["reference"] == "nemotron_h"
    said = " ".join(cfg["assumed"])
    for what in ("no position encoding", "float32", "clamp",
                 "multi-token-prediction"):
        assert what in said, what
    assert len(cfg["departures"]) >= 2 and "four" in cfg["deployment"].lower()
    assert "int8" in cfg["precision"]["control"]


def test_the_traffic_is_the_issues_to_the_letter():
    mix = manifest.load_cell(CELL).traffic
    e = mix["engine"]
    assert e == {"n_slots": 96, "max_len": 2048, "block_size": 16,
                 "prefill_chunk": 256, "attn_kernel": "fused",
                 "cache_dtype": "bfloat16", "n_blocks": 12288}
    # every slot can reach max_len
    assert e["n_blocks"] == e["n_slots"] * e["max_len"] // e["block_size"]
    assert (mix["loop"], mix["clients"], mix["round_size"]) == ("closed", 96,
                                                                192)
    assert mix["prompt_lengths"] == {"min": 256, "max": 1024,
                                     "multiple_of": 256,
                                     "weight": "inverse_length"}
    assert mix["answer_lengths"] == {"law": "log_uniform", "min": 128,
                                     "max": 1024}
    assert mix["tokens"] == {"distribution": "zipf", "exponent": 1.0}
    assert mix["sampling"] == {"temperature": 0.0}
    assert mix["trace_seconds"] == 5
    assert (mix["prompt_lengths"]["max"] + mix["answer_lengths"]["max"]
            <= e["max_len"])
    from bench_cells.traffic import generate
    sizes = generate.request_sizes(mix)
    assert len(sizes) == 192
    assert all(p % e["prefill_chunk"] == 0 for p, _ in sizes)
    assert 480 < sum(p for p, _ in sizes) / 192 < 500       # mean 492
    assert 420 < sum(a for _, a in sizes) / 192 < 440       # mean 431
    # a 30 s window at 40 tokens a second a client cannot exhaust a queue
    per_client = mix["rounds"] * mix["round_size"] // mix["clients"]
    assert per_client * mix["answer_lengths"]["min"] > 30 * 40
    grouped, plain, paged = (re.compile(mix["kernels"][k]) for k in (
        "selective_scan_grouped", "moe_experts", "paged_attention"))
    for line, want in {
            '%selective_scan_grouped.7 = (f32[12,8,8192]{2,1,0}) custom-call(':
                (True, False, False),
            '%moe_experts.3 = f32[2176,2688]{1,0} custom-call(':
                (False, True, False),
            '%paged_attention.1 = f32[96,32,1,128]{3,2,1,0} custom-call(':
                (False, False, True),
            '%selective_scan.7 = (f32[16,8,5120]{2,1,0}) custom-call(':
                (False, False, False),
            '%fusion.3 = f32[1] fusion(%selective_scan_grouped.7)':
                (False, False, False),
    }.items():
        assert (bool(grouped.search(line)), bool(plain.search(line)),
                bool(paged.search(line))) == want, line


# -- counts against hand counts ------------------------------------------------


def test_parameter_counts_by_hand(arch):
    d = 4096
    mamba = (d * (8192 + 10240 + 128) + 4 * 10240 + 10240 + 3 * 128 + 8192
             + 8192 * d + d)
    assert counts_nemotron_h.mamba_layer_params(arch) == mamba == 109_640_064
    attention = 2 * d * d + 2 * d * 256 + d
    assert counts_nemotron_h.attention_layer_params(arch) == attention \
        == 35_655_680
    rest = d * 512 + 512 + 2 * d * 1024 + 2 * d * 5376 + d
    assert counts_nemotron_h.expert_layer_rest_params(arch) == rest \
        == 54_530_560
    assert counts_nemotron_h.expert_params(arch) == 2 * 1024 * 2688 \
        == 5_505_024
    cut = (5 * mamba + attention + 5 * (128 * 5_505_024 + rest)
           + 2 * 32768 * d + d)
    assert counts_nemotron_h.total_params(arch) == cut == 4_648_163_712
    assert 9.29e9 < 2 * cut < 9.31e9
    pub = manifest.load_cell(CELL).config["published"]
    whole = counts_nemotron_h.total_params(
        arch, pub["hybrid_override_pattern"], 512, 131072)
    assert whole == 120_668_707_840             # the published "120B"
    assert counts_nemotron_h.active_params_per_token(
        arch, pub["hybrid_override_pattern"], 131072) == 12_770_237_440
    # what a layer holds beside its routed experts, in the mean: "about 78 M"
    assert round((40 * mamba + 8 * attention + 40 * rest) / 88 / 1e6, 1) \
        == 77.9


def test_state_and_kernel_bytes_by_hand(arch):
    # per slot: 5 x ([128, 8192] f32 + [3, 10240] bf16)
    assert counts_nemotron_h.state_bytes_per_slot(arch) == 5 * (
        128 * 8192 * 4 + 3 * 10240 * 2) == 21_278_720
    assert counts_nemotron_h.kv_bytes_per_position(arch) == 1024
    # a decode tick's call: 96 states in and out, x and y [96, 8192], 128
    # deltas and two [8, 128] a slot
    tick = 4 * (2 * 96 * 128 * 8192 + 2 * 96 * 8192 + 96 * 128
                + 2 * 96 * 8 * 128)
    assert counts_nemotron_h.mamba2_scan_bytes(arch, 96, 1) == tick \
        == 812_433_408
    chunk = 4 * (2 * 128 * 8192 + 2 * 256 * 8192 + 256 * 128
                 + 2 * 256 * 8 * 128)
    assert counts_nemotron_h.mamba2_scan_bytes(arch, 1, 256) == chunk
    # one layer with every held expert hit by 528 pairs: 1.41 GB of weights
    assert counts_nemotron_h.latent_experts_bytes(arch, 128, 528) == (
        128 * 5_505_024 * 2 + 528 * 1024 * (2 + 4)) == 1_412_530_176
    assert counts_nemotron_h.latent_experts_bytes(arch, 0, 0) == 0
    assert counts_nemotron_h.latent_experts_flops(arch, 528) == (
        2 * 528 * 5_505_024)


def test_seeded_weights_have_the_programs_layout_and_count():
    import jax
    import numpy as np

    from bench_cells.runners import serve_nemotron_h
    from simple_distributed_machine_learning_tpu.models.nemotron_h import (
        NemotronHConfig,
    )
    tree = weights_nemotron_h.init_nemotron_h(2 ** 31 + 5, TOY)
    again = weights_nemotron_h.init_nemotron_h(2 ** 31 + 5, TOY)
    leaves = jax.tree.leaves(tree)
    assert {str(a.dtype) for a in leaves} == {"bfloat16", "float32"}
    assert sum(a.size for a in leaves) == counts_nemotron_h.total_params(TOY)
    assert all((a == b).all() for a, b in zip(leaves, jax.tree.leaves(again)))
    # the experts' matrices are those of the experts held, no more
    assert tree["blocks"][1]["moe"]["w1"].shape == (8, 32, 48)
    assert tree["blocks"][1]["moe"]["router"].shape == (64, 16)
    # the stated departure: the matrices back to the model's width have no
    # column mean (to bfloat16's rounding), the others keep theirs
    moe, mamba = tree["blocks"][1]["moe"], tree["blocks"][0]["mamba"]
    for w in (moe["shared_out"], moe["w2"], mamba["out_proj"]):
        assert abs(np.asarray(w, np.float32).mean(-2)).max() < 2e-4
    assert abs(np.asarray(moe["shared_in"], np.float32).mean(-2)).max() > 2e-3
    stage, = serve_nemotron_h.nemotron_h_stage(NemotronHConfig(**TOY), tree)
    assert stage.params is tree
    with pytest.raises(SystemExit, match="parameter layout"):
        serve_nemotron_h.nemotron_h_stage(
            NemotronHConfig(**dict(TOY, d_expert=64)), tree)


# -- the runner at toy size ----------------------------------------------------


def toy_cell(limits=LIMITS, arch=TOY, requests=6):
    real = manifest.load_cell(CELL)
    mix = copy.deepcopy(real.traffic)
    mix.update(
        engine={"n_slots": 4, "max_len": 64, "block_size": 4, "n_blocks": 64,
                "prefill_chunk": 8, "attn_kernel": "fused",
                "cache_dtype": "bfloat16"},
        clients=4, round_size=8, rounds=400,
        prompt_lengths={"min": 8, "max": 24, "multiple_of": 8,
                        "weight": "inverse_length"},
        answer_lengths={"law": "log_uniform", "min": 3, "max": 8})
    mix["check"] = {"requests": requests, "limits": limits}
    return manifest.Cell(CELL, 1, real.config_name, real.traffic_name,
                         dict(real.config, nemotron_h_config=arch), mix,
                         real.end_to_end, real.per_layer)


@pytest.fixture(autouse=True)
def toy_conditions(monkeypatch):
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: 1)
    monkeypatch.setattr(weights_nemotron_h, "STD", 0.1)


def _run(cell, seconds=2.0, seed=2 ** 31 + 5):
    return benchrun.run_cell(cell, seed, seconds, False, DEVICE, PEAKS)


def test_sound_run_is_correct_and_its_records_feed_the_readers():
    result = _run(toy_cell())
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 4
    assert set(result["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms",
                                      "setup_s"}
    assert result["compared"]["compiles_in_window"] == {"value": 0,
                                                        "limit": 0}
    json.dumps(result)


def test_state_left_by_the_last_occupant_is_not_correct(monkeypatch):
    """The timed path broken underneath: a chunk at position 0 that carries
    the slot's old state on instead of zeroing it."""
    import jax

    from simple_distributed_machine_learning_tpu.models import nemotron_h

    def kept(ssm, tail, slot, fresh):
        return (jax.lax.dynamic_slice_in_dim(ssm, slot, 1, 0),
                jax.lax.dynamic_slice_in_dim(tail, slot, 1, 0))

    monkeypatch.setattr(nemotron_h, "_slot_pair", kept)
    # a width of its own: the programs are memoized by configuration, and
    # the broken pair must neither find the sound one nor be found later
    # and a sample of 24: which six of the finished requests a window's
    # luck drew decided the reading (1.2e-3 to 6.3e-3, and once in four
    # under the limit); over 24 it reads 1.1e-3 to 3.9e-3 (my CPU runs, PR 41)
    result = _run(toy_cell(arch=dict(TOY, d_shared=80), requests=24))
    assert result["correct"] is False
    assert result["compared"]["gap_mean"]["value"] > 5 * LIMITS["gap_mean"]


class _NoTrace:
    enabled, dir, running = False, None, False


def test_control_is_not_correct_and_the_counters_are_read():
    """The same comparison, the reference in int8 operands in the program's
    place; and the two counter metrics over the toy window's own spans."""
    from bench_cells.runners import serve_nemotron_h

    cell = toy_cell()
    cell.traffic["check"]["requests"] = 40
    run = serve_nemotron_h.Run(cell, 1, harness.Spans())
    run.setup()
    run.window(2.0, _NoTrace())
    ctx = {"records": run.records, "trace": None, "mix": cell.traffic,
           "peaks": PEAKS}
    hit = manifest.load_reader("moe.held_experts_hit_pct")(ctx)
    rows = manifest.load_reader("moe.rows_per_held_expert")(ctx)
    # 2 expert layers x 8 held of 16, 4 slots x top 3: about half the pairs
    # land on held experts, one or two rows each
    assert 10.0 < hit <= 100.0 and 1.0 <= rows <= 4.0
    for name in ("kernel.latent_experts_roofline_pct",
                 "kernel.mamba2_scan_roofline_pct"):
        assert manifest.load_reader(name)(ctx) is None   # no trace, no share
    assert run.records["nemotron_h"] == TOY
    program, control = run.check(), run.control()
    assert check.compare(program, LIMITS)[0], program
    ok, compared = check.compare(control, LIMITS)
    assert not ok and compared["gap_mean"]["value"] > 2 * LIMITS["gap_mean"]


@pytest.mark.parametrize("name", NEW)
def test_readers_give_nothing_on_a_program_without_the_counts(name):
    """The parent commit's ticks carry no ``expert_rows`` and another
    runner's records no ``nemotron_h``: every new reader returns ``None``
    and does not raise."""
    from bench_cells import program_spans

    tick = types.SimpleNamespace(attrs={"chunk": 0, "decoding": 1}, id=1)
    window = types.SimpleNamespace(ticks=[tick], spans=[], kids={})
    orig = program_spans.serve_window
    program_spans.serve_window = lambda run: window
    try:
        for records in ({"kind": "serve", "n_slots": 4},
                        {"kind": "serve", "n_slots": 4, "jamba": {}},
                        {"kind": "train"}):
            ctx = {"records": records, "trace": None, "mix": {},
                   "peaks": PEAKS}
            assert manifest.load_reader(name)(ctx) is None
    finally:
        program_spans.serve_window = orig
