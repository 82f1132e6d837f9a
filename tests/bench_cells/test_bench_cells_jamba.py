"""What PR 28 adds to the benchmark, off the chip: the manifest walk finds
the new cell, its files and its readers; ``counts_jamba`` against hand
counts; and ``runners/serve_hybrid.py`` driven past the harness's look for a
chip at toy size, as ``test_bench_cells_run.py`` drives the GPT runners: a
sound run comes out correct, the int8 control and a run that skips the state
reset on bind do not.

The toy's limits are set by the real mix's rule (above what sound runs of the
toy read, below what its control reads); its weights are drawn at normal 0.1
instead of 0.02, because at width 64 the published scale leaves the layers
all but linear and a wrong state would hardly show.
"""

import copy
import json
import os

import pytest

from bench_cells import check, counts_jamba, harness, manifest, weights_jamba
from bench_cells import run as benchrun

CELL = "jamba2-3b.serve-reason-closed"
DEVICE = {"platform": "cpu", "kind": "test", "count": 1}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
TOY = {"vocab": 128, "seq_len": 64, "d_model": 64, "n_heads": 4,
       "n_kv_heads": 1, "d_ff": 128, "n_layers": 4, "attn_period": 2,
       "attn_offset": 1, "d_state": 16, "d_conv": 4, "expand": 4,
       "dt_rank": 8, "rms_eps": 1e-6, "param_dtype": "bfloat16"}
# sound toy runs read 1.4e-5 to 1.6e-4 over 3 seeds (bfloat16 operands and
# pool against the float32 reference), the int8 control 3.1e-3 to 5.6e-3, a
# leaked state 0.1 and more: the limit lies 6 x above the one range and 3 x
# below the other
LIMITS = {"gap_mean": 0.001, "compiles_in_window": 0}


@pytest.fixture(scope="module")
def bench():
    return manifest.load_manifest()


@pytest.fixture(scope="module")
def arch():
    return manifest.load_cell(CELL).config["jamba_config"]


# -- the manifest walk ---------------------------------------------------------


def test_the_cell_its_files_and_its_readers_are_found(bench):
    cell = manifest.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "jamba2-3b", "serve-reason-closed")
    assert cell.traffic["runner"] == "serve_hybrid"
    for rel in ("runners/serve_hybrid.py", "reference/jamba.py",
                "weights_jamba.py", "counts_jamba.py"):
        assert os.path.isfile(os.path.join(manifest.HERE, rel)), rel
    e2e = {m["name"] for m in cell.end_to_end}
    assert e2e == {"serve_tokens_per_s", "tpot_p95_ms", "setup_s"}
    layer = {m["name"] for m in cell.per_layer}
    assert {"kernel.selective_scan_roofline_pct", "cache.state_live_pct",
            "entry.trace_lower_s", "model.decode_device_ms",
            "device.idle_pct.serve", "engine.idle_explained_pct"} <= layer
    # its byte count is multi-head GPT's
    assert "kernel.paged_attention_roofline_pct" not in layer
    for name in layer | e2e:
        assert callable(manifest.load_reader(name))
    # by name and by membership: a later metric or cell trips nothing
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ("kernel.selective_scan_roofline_pct",
                 "cache.state_live_pct"):
        assert CELL in by_name[name]["workloads"], name
    assert set(by_name["entry.trace_lower_s"]["workloads"]) \
        == {w["name"] for w in bench["workloads"]}


def test_configuration_file_holds_the_published_config(arch):
    cfg = manifest.load_cell(CELL).config
    assert cfg["reduced"] == [] and cfg["reference"] == "jamba"
    assert len(cfg["assumed"]) >= 2 and len(cfg["departures"]) >= 2
    # the source's keys at the top level too, where a catalog check reads
    assert {k: cfg[k] for k in cfg["published"]} == cfg["published"]
    pub = cfg["published"]
    assert arch == {
        "vocab": pub["vocab_size"], "seq_len": 1024,
        "d_model": pub["hidden_size"], "n_heads": pub["num_attention_heads"],
        "n_kv_heads": pub["num_key_value_heads"],
        "d_ff": pub["intermediate_size"],
        "n_layers": pub["num_hidden_layers"],
        "attn_period": pub["attn_layer_period"],
        "attn_offset": pub["attn_layer_offset"],
        "d_state": pub["mamba_d_state"], "d_conv": pub["mamba_d_conv"],
        "expand": pub["mamba_expand"], "dt_rank": pub["mamba_dt_rank"],
        "rms_eps": pub["rms_norm_eps"], "param_dtype": "bfloat16"}
    mix = manifest.load_cell(CELL).traffic
    e = mix["engine"]
    # every slot can reach max_len
    assert e["n_blocks"] == e["n_slots"] * e["max_len"] // e["block_size"]
    assert (mix["prompt_lengths"]["max"] <= e["prefill_chunk"]
            and mix["prompt_lengths"]["max"] + mix["answer_lengths"]["max"]
            <= e["max_len"])
    import re
    scan, paged = (re.compile(mix["kernels"][k])
                   for k in ("selective_scan", "paged_attention"))
    for line, (is_scan, is_paged) in {
            '%selective_scan.7 = (f32[16,8,5120]{2,1,0}) custom-call(':
                (True, False),
            '%paged_attention.1 = f32[128,1,20,128]{3,2,1,0} custom-call(':
                (False, True),
            '%fusion.3 = f32[1] fusion(%selective_scan.7)': (False, False),
    }.items():
        assert (bool(scan.search(line)), bool(paged.search(line))) == (
            is_scan, is_paged), line


# -- counts against hand counts ------------------------------------------------


def test_parameter_count_by_hand(arch):
    d, di, ff, v = 2560, 5120, 8192, 65536
    mamba_mixer = (d * 2 * di + 4 * di + di + di * (160 + 16 + 16)
                   + (160 + 16 + 16) + 160 * di + di + 16 * di + di
                   + di * d)
    assert counts_jamba.mamba_mixer_params(arch) == mamba_mixer == 41_241_792
    attention = d * d + d * 128 + d * 128 + d * d
    assert counts_jamba.attention_mixer_params(arch) == attention
    mlp = 3 * d * ff
    mamba_layer, attn_layer = (mamba_mixer + mlp + 2 * d,
                               attention + mlp + 2 * d)
    assert (mamba_layer, attn_layer) == (104_161_472, 76_682_240)
    assert counts_jamba.n_attention_layers(arch) == 2
    assert counts_jamba.total_params(arch) == (
        26 * mamba_layer + 2 * attn_layer + v * d + d) == 3_029_337_472


def test_state_and_kernel_bytes_by_hand(arch):
    # per slot: 26 x ([16, 5120] f32 + [3, 5120] bf16) = 9.32 MB
    assert counts_jamba.state_bytes_per_slot(arch) == 26 * (
        16 * 5120 * 4 + 3 * 5120 * 2) == 9_318_400
    # a decode tick's call: 128 states in and out, four [128, 5120] f32
    # vectors, two [128, 16]
    tick = 4 * (2 * 128 * 16 * 5120 + 4 * 128 * 5120 + 2 * 128 * 16)
    assert counts_jamba.selective_scan_bytes(arch, 128, 1) == tick \
        == 94_388_224
    # a 256-token chunk's call: one state in and out, four [256, 5120]
    chunk = 4 * (2 * 16 * 5120 + 4 * 256 * 5120 + 2 * 256 * 16)
    assert counts_jamba.selective_scan_bytes(arch, 1, 256) == chunk
    weights = 2 * 3_029_337_472
    kv = 2 * 2 * 50_000 * 128 * 2
    assert counts_jamba.decode_tick_bytes(arch, 128, 50_000) == (
        weights + 26 * tick + kv)
    assert 8.4e9 < weights + 26 * tick + kv < 8.6e9
    assert counts_jamba.chunk_bytes(arch, 256, 256) == (
        weights + 26 * chunk + 2 * 2 * 256 * 128 * 2)


def test_seeded_weights_have_the_programs_layout_and_count():
    import jax

    from bench_cells.runners import serve_hybrid
    from simple_distributed_machine_learning_tpu.models.jamba import (
        JambaConfig,
    )
    tree = weights_jamba.init_jamba(2 ** 31 + 5, TOY)
    again = weights_jamba.init_jamba(2 ** 31 + 5, TOY)
    leaves = jax.tree.leaves(tree)
    assert all(a.dtype == "bfloat16" for a in leaves)
    assert sum(a.size for a in leaves) == counts_jamba.total_params(TOY)
    assert all((a == b).all() for a, b in zip(leaves, jax.tree.leaves(again)))
    stage, = serve_hybrid.jamba_stage(JambaConfig(**TOY), tree)
    assert stage.params is tree
    with pytest.raises(SystemExit, match="parameter layout"):
        serve_hybrid.jamba_stage(JambaConfig(**dict(TOY, d_ff=64)), tree)


# -- the runner at toy size ----------------------------------------------------


def toy_cell(limits=LIMITS, arch=TOY):
    real = manifest.load_cell(CELL)
    mix = copy.deepcopy(real.traffic)
    mix.update(
        engine={"n_slots": 4, "max_len": 64, "block_size": 4, "n_blocks": 64,
                "prefill_chunk": 24, "attn_kernel": "fused",
                "cache_dtype": "bfloat16"},
        clients=4, round_size=8, rounds=400,
        prompt_lengths={"min": 8, "max": 24, "multiple_of": 8,
                        "weight": "inverse_length"},
        answer_lengths={"law": "log_uniform", "min": 3, "max": 8})
    mix["check"] = {"requests": 6, "limits": limits}
    return manifest.Cell(CELL, 1, real.config_name, real.traffic_name,
                         dict(real.config, jamba_config=arch), mix,
                         real.end_to_end, real.per_layer)


@pytest.fixture(autouse=True)
def toy_conditions(monkeypatch):
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: 1)
    monkeypatch.setattr(weights_jamba, "STD", 0.1)


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

    from simple_distributed_machine_learning_tpu.models import jamba

    def kept(ssm, tail, slot, fresh):
        return (jax.lax.dynamic_slice_in_dim(ssm, slot, 1, 0),
                jax.lax.dynamic_slice_in_dim(tail, slot, 1, 0))

    monkeypatch.setattr(jamba, "_slot_pair", kept)
    # a width of its own: the programs are memoized by configuration, and
    # the broken pair must neither find the sound one nor be found later
    result = _run(toy_cell(arch=dict(TOY, d_ff=96)))
    assert result["correct"] is False
    assert result["compared"]["gap_mean"]["value"] > 10 * LIMITS["gap_mean"]


class _NoTrace:
    enabled, dir, running = False, None, False


def test_control_is_not_correct_and_state_live_is_read():
    """The same comparison, the reference in int8 operands in the program's
    place; and ``cache.state_live_pct`` over the toy window's own spans."""
    from bench_cells.runners import serve_hybrid

    cell = toy_cell()
    cell.traffic["check"]["requests"] = 40
    run = serve_hybrid.Run(cell, 1, harness.Spans())
    run.setup()
    run.window(2.0, _NoTrace())
    ctx = {"records": run.records, "trace": None, "mix": cell.traffic,
           "peaks": PEAKS}
    live = manifest.load_reader("cache.state_live_pct")(ctx)
    assert 25.0 < live <= 100.0
    assert manifest.load_reader("kernel.selective_scan_roofline_pct")(
        ctx) is None            # no trace, no share
    assert run.records["jamba"] == TOY
    program, control = run.check(), run.control()
    assert check.compare(program, LIMITS)[0], program
    ok, compared = check.compare(control, LIMITS)
    assert not ok and compared["gap_mean"]["value"] > 2 * LIMITS["gap_mean"]


def test_readers_give_nothing_on_a_program_without_the_counts():
    """The parent commit's ticks carry no ``state_slots`` and its runner's
    records no ``jamba``: both readers return ``None`` and do not raise."""
    import types

    from bench_cells import program_spans

    tick = types.SimpleNamespace(attrs={"chunk": 0, "decoding": 1})
    window = types.SimpleNamespace(ticks=[tick])
    ctx = {"records": {"kind": "serve", "n_slots": 4}, "trace": object(),
           "mix": {}, "peaks": PEAKS}
    orig = program_spans.serve_window
    program_spans.serve_window = lambda run: window
    try:
        assert manifest.load_reader("cache.state_live_pct")(ctx) is None
        assert manifest.load_reader(
            "kernel.selective_scan_roofline_pct")(ctx) is None
    finally:
        program_spans.serve_window = orig
