"""What PR 42 adds to the benchmark, off the chip: the manifest walk finds
the new cell, its files and its readers BY NAME (membership, never a list's
position or exact length); ``counts_zaya`` against hand counts; the five new
readers over hand-made records and a hand-made trace, and ``None`` from each
on records without ``zaya`` sizes and on ticks without the counts; and
``runners/serve_zaya.py`` driven past the harness's look for a chip at toy
size, as ``test_bench_cells_nemotron_h.py`` drives its own: a sound run
comes out correct, the int8 control does not.

The toy's limit is set by the real mix's rule (above what sound runs of the
toy read, below what its control reads); its weights are drawn at normal 0.1
instead of 0.02, because at width 64 the published scale leaves the layers
all but linear and a wrong state would hardly show; and they and the pool
are float32, not the cell's bfloat16: with four experts of a 64-wide toy one
token whose ONE expert flips on a bfloat16 rounding moves its logits by 0.2
to 0.5, and a window's luck in drawing such a token would decide the mean
(``tests/test_zaya.py`` holds the bfloat16 program to the reference).
"""

import copy
import json
import os
import re
import types

import pytest

from bench_cells import check, counts_zaya, harness, manifest, weights_zaya
from bench_cells import run as benchrun
from bench_cells.reduce import xplane

CELL = "zaya1-8b.serve-context-closed"
CONFIG = "zaya1-8b"
NEW = ("moe.top1_experts_hit_pct", "moe.top1_fullest_expert_rows",
       "kernel.top1_experts_roofline_pct",
       "kernel.cca_attention_roofline_pct", "model.attend_share_pct")
DEVICE = {"platform": "cpu", "kind": "test", "count": 1}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
TOY = {"vocab": 128, "seq_len": 64, "d_model": 64, "n_layers": 3,
       "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "conv0": 2, "conv1": 2,
       "rotary_fraction": 0.5, "rope_theta": 5e6, "n_experts": 4,
       "d_expert": 48, "d_router": 8, "rms_eps": 1e-5,
       "param_dtype": "float32"}
# sound toy runs read 0 over 5 seeds (float32 throughout: the served token
# is the reference's best), the int8 control 2.9e-3 to 1.1e-2: the limit
# lies 6 x below the control's least
LIMITS = {"gap_mean": 0.0005, "compiles_in_window": 0}


@pytest.fixture(scope="module")
def bench():
    return manifest.load_manifest()


@pytest.fixture(scope="module")
def arch():
    return manifest.load_cell(CELL).config["zaya_config"]


def _by_name(entries):
    return {e["name"]: e for e in entries}


# -- the manifest walk ------------------------


def test_the_cell_its_files_and_its_readers_are_found(bench):
    cell = manifest.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, CONFIG, "serve-context-closed")
    assert cell.traffic["runner"] == "serve_zaya"
    for rel in ("runners/serve_zaya.py", "reference/zaya.py",
                "weights_zaya.py", "counts_zaya.py", "decode_runs.py"):
        assert os.path.isfile(os.path.join(manifest.HERE, rel)), rel
    # the rate is NOT the cell's: a pause of the machine (110-130 ms, none
    # to four a window) is 0.4 % of 30 s each, over half the rate's bound
    # (PERF.md Open question 9), so the cell is held by the tail alone
    e2e = {m["name"] for m in cell.end_to_end}
    assert e2e == {"tpot_p95_ms", "setup_s"}
    layer = {m["name"] for m in cell.per_layer}
    assert set(NEW) | {
        "entry.trace_lower_s", "engine.tick_ms_p50", "engine.chunk_ticks_pct",
        "engine.host_ms_per_tick", "engine.readback_ms_p50",
        "model.decode_device_ms"} <= layer
    # every engine.* metric that moves the tail is read in this cell too,
    # and none that moves the rate the cell does not report
    moves = {m["name"]: m["moves"] for m in bench["per_layer"]}
    assert {n for n, e in moves.items() if n.startswith("engine.")
            and e == "tpot_p95_ms"} <= layer
    assert {moves[n] for n in layer} == {"tpot_p95_ms", "setup_s"}
    # readers that take another runner's records stay with their own cells
    assert not {"moe.experts_hit_pct", "kernel.moe_experts_roofline_pct",
                "moe.held_experts_hit_pct", "cache.state_live_pct",
                "kernel.latent_experts_roofline_pct",
                "kernel.paged_attention_roofline_pct"} & layer
    for name in layer | e2e:
        assert callable(manifest.load_reader(name))
    per_layer = _by_name(bench["per_layer"])
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL], name
        assert per_layer[name]["layer"] in ("kernels", "model programs")
        assert per_layer[name]["moves"] == "tpot_p95_ms"
    assert per_layer["kernel.cca_attention_roofline_pct"]["unit"] == "%"
    assert per_layer["moe.top1_fullest_expert_rows"]["unit"] == "rows"
    cells = [w["name"] for w in bench["workloads"]]
    assert CELL in cells
    assert set(per_layer["entry.trace_lower_s"]["workloads"]) == set(cells)
    w = _by_name(bench["workloads"])[CELL]
    c = _by_name(bench["configs"])[CONFIG]
    assert len(w["why"]) <= 200 and len(c["why"]) <= 200
    assert len(c["source"]) <= 200 and c["source"] == cell.config["source"]
    assert c["reduced"] == cell.config["reduced"] == ["num_hidden_layers"]


def test_configuration_file_holds_the_published_widths(arch):
    cfg = manifest.load_cell(CELL).config
    pub = cfg["published"]
    # every published key at the top level too, unchanged but the depth
    for k, v in pub.items():
        assert (cfg[k] == v) == (k != "num_hidden_layers"), k
    assert (pub["num_hidden_layers"], cfg["num_hidden_layers"]) == (40, 20)
    assert set(pub["layer_types"]) == {"hybrid"}       # no window layers
    rope = pub["rope_parameters"]["hybrid"]
    assert arch == {
        "vocab": pub["vocab_size"], "seq_len": 8192,
        "d_model": pub["hidden_size"], "n_layers": 20,
        "n_heads": pub["num_attention_heads"],
        "n_kv_heads": pub["num_key_value_heads"],
        "head_dim": pub["head_dim"], "conv0": pub["cca_time0"],
        "conv1": pub["cca_time1"],
        "rotary_fraction": rope["partial_rotary_factor"],
        "rope_theta": float(rope["rope_theta"]),
        "n_experts": pub["num_experts"],
        "d_expert": pub["moe_intermediate_size"],
        "d_router": pub["router_hidden_size"],
        "rms_eps": pub["rms_norm_eps"], "param_dtype": "bfloat16"}
    assert pub["num_experts_per_tok"] == 1 and pub["tie_word_embeddings"]
    assert cfg["reference"] == "zaya"
    said = " ".join(cfg["assumed"])
    for what in ("value shift", "q-k mean", "temperature", "GELU",
                 "residual scaling", "NOT BUILT", "mixture of depths",
                 "window layers"):
        assert what in said, what
    departed = " ".join(cfg["departures"])
    for what in ("CENTRED", "selection bias", "tau is 4"):
        assert what in departed, what
    assert "FIRST" in cfg["deployment"] and "0-19" in cfg["deployment"]
    assert "int8" in cfg["precision"]["control"]


def test_the_traffic_is_the_issues_to_the_letter():
    mix = manifest.load_cell(CELL).traffic
    e = mix["engine"]
    assert e == {"n_slots": 24, "max_len": 8192, "block_size": 16,
                 "prefill_chunk": 512, "attn_kernel": "fused",
                 "cache_dtype": "bfloat16", "n_blocks": 12288}
    # every slot can reach max_len
    assert e["n_blocks"] == e["n_slots"] * e["max_len"] // e["block_size"]
    assert (mix["loop"], mix["clients"], mix["round_size"],
            mix["rounds"]) == ("closed", 24, 48, 4)
    assert mix["prompt_lengths"] == {"min": 2048, "max": 6144,
                                     "multiple_of": 512,
                                     "weight": "inverse_length"}
    assert mix["answer_lengths"] == {"law": "log_uniform", "min": 256,
                                     "max": 2048}
    assert mix["tokens"] == {"distribution": "zipf", "exponent": 1.0}
    assert mix["sampling"] == {"temperature": 0.0}
    assert mix["trace_seconds"] == 5 and mix["check"]["requests"] == 6
    # between the sound runs' 0.0199-0.0344 and the int8 control's
    # 0.1351-0.1497 (PERF.md section 2)
    assert mix["check"]["limits"] == {"gap_mean": 0.065,
                                      "compiles_in_window": 0}
    assert (mix["prompt_lengths"]["max"] + mix["answer_lengths"]["max"]
            == e["max_len"])
    from bench_cells.traffic import generate
    sizes = generate.request_sizes(mix)
    assert len(sizes) == 48
    assert all(p % e["prefill_chunk"] == 0 for p, _ in sizes)
    assert 3500 < sum(p for p, _ in sizes) / 48 < 3750      # mean 3,629
    assert 840 < sum(a for _, a in sizes) / 48 < 880        # mean 862
    # a 30 s window at 80 tokens a second a client cannot exhaust a queue
    per_client = mix["rounds"] * mix["round_size"] // mix["clients"]
    assert per_client * mix["answer_lengths"]["min"] > 30 * 60
    plain, paged = (re.compile(mix["kernels"][k]) for k in (
        "moe_experts", "paged_attention"))
    for line, want in {
            '%moe_experts.3 = f32[24,2048]{1,0} custom-call(': (True, False),
            '%paged_attention.1 = f32[24,1,8,256]{3,2,1,0} custom-call(':
                (False, True),
            '%fusion.3 = f32[1] fusion(%moe_experts.7)': (False, False),
    }.items():
        assert (bool(plain.search(line)), bool(paged.search(line))) == want


# -- counts against hand counts ------------------------


def test_parameter_counts_by_hand(arch):
    d = 2048
    attention = (d * 1280 + d * 256 + 2 * 1280 + 1280
                 + 2 * 1280 * 128 + 1280 + 2 + 1024 * d)
    assert counts_zaya.attention_part_params(arch) == attention == 5_575_682
    router = (d * 256 + 256 + 256 + 256 + 2 * (256 * 256 + 256) + 256 * 16
              + 16)
    assert counts_zaya.router_params(arch) == router == 660_752
    assert counts_zaya.expert_params(arch) == 3 * d * d == 12_582_912
    assert 16 * counts_zaya.expert_params(arch) == 201_326_592
    assert counts_zaya.norm_and_scaling_params(arch) == 20_480
    layer = attention + router + 201_326_592 + 20_480
    assert counts_zaya.layer_params(arch) == layer == 207_583_506
    assert 40 * layer == 8_303_340_240                 # the published "8.3B"
    assert 40 * counts_zaya.active_layer_params(arch) == 753_593_040
    assert counts_zaya.embedding_params(arch) == 262_272 * d == 537_133_056
    assert counts_zaya.total_params(arch) == 20 * layer + 537_133_056 + d \
        == 4_688_805_224
    assert 9.37e9 < 2 * counts_zaya.total_params(arch) < 9.39e9
    assert counts_zaya.total_params(arch, layers=40) == 8_840_475_344


def test_state_and_kernel_bytes_by_hand(arch):
    # a position: 20 layers x (K + V) x 256 lanes x 2 B
    assert counts_zaya.kv_bytes_per_position(arch) == 20_480
    # the pool the mix reserves: 196,608 positions
    assert 12288 * 16 * 20_480 == 4_026_531_840
    # a slot: 20 x (1280 + 1280 + 128) float32
    assert counts_zaya.state_bytes_per_slot(arch) == 20 * 2688 * 4 == 215_040
    # a decode run over 24 slots of 4,200 positions: 2.06 GB of latent K/V
    # and 24 x 20 query and output rows of 1,024 float32
    assert counts_zaya.latent_kv_bytes(arch, 24 * 4200, 24) == (
        100_800 * 20_480 + 2 * 24 * 20 * 1024 * 4) == 2_068_316_160
    assert counts_zaya.latent_kv_bytes(arch, 0, 0) == 0
    # 252 of the 320 (layer, expert) pairs hit by 480 rows: 6.34 GB
    assert counts_zaya.top1_experts_bytes(arch, 252, 480) == (
        252 * 12_582_912 * 2 + 480 * 2048 * (2 + 4)) == 6_347_685_888
    assert counts_zaya.top1_experts_bytes(arch, 0, 0) == 0


def test_seeded_weights_have_the_programs_layout_and_count():
    import jax
    import numpy as np

    from bench_cells.runners import serve_zaya
    from simple_distributed_machine_learning_tpu.models.zaya import ZayaConfig
    toy = dict(TOY, param_dtype="bfloat16")
    tree = weights_zaya.init_zaya(2 ** 31 + 5, toy)
    again = weights_zaya.init_zaya(2 ** 31 + 5, toy)
    leaves = jax.tree.leaves(tree)
    assert {str(a.dtype) for a in leaves} == {"bfloat16", "float32"}
    assert sum(a.size for a in leaves) == counts_zaya.total_params(toy)
    assert all((a == b).all() for a, b in zip(leaves, jax.tree.leaves(again)))
    moe, attn = tree["blocks"][1]["moe"], tree["blocks"][1]["attn"]
    assert moe["gate"].shape == (4, 64, 48)
    assert attn["conv1_w"].shape == (2, 6, 16, 16)
    # the stated departures: the router's MLP has no column mean (to
    # bfloat16's rounding) and unit scale, a fitted bias, tau 4
    router = moe["router"]
    for w in (router["w1"], router["w2"], router["w3"]):
        w = np.asarray(w, np.float32)
        assert abs(w.mean(-2)).max() < 5e-3 and 0.4 < w.std() < 1.0
    assert router["bias"].dtype == np.float32
    assert 0 < abs(np.asarray(router["bias"])).max() <= 0.4 + 1e-6
    assert (np.asarray(attn["tau"], np.float32) == weights_zaya.TAU).all()
    assert abs(np.asarray(moe["down"], np.float32).mean(-2)).max() > 2e-3
    stage, = serve_zaya.zaya_stage(ZayaConfig(**toy), tree)
    assert stage.params is tree
    with pytest.raises(SystemExit, match="parameter layout"):
        serve_zaya.zaya_stage(ZayaConfig(**dict(toy, d_expert=64)), tree)


# -- the readers over hand-made records and a hand-made trace -------------------


def _hand_ctx(monkeypatch, attrs, arch, with_trace=True):
    """A window of three ticks (two decoded) whose spans carry ``attrs``,
    and a trace of two decode runs of 10 ms and a chunk run: 2 ms of
    ``moe_experts`` and 4 ms of ``paged_attention`` inside each decode run,
    the same kernels inside the chunk's (which no reader may count)."""
    from bench_cells import program_spans

    ticks = [types.SimpleNamespace(attrs=dict(a), id=i)
             for i, a in enumerate(attrs)]
    window = types.SimpleNamespace(ticks=ticks, spans=ticks, kids={})
    ev = xplane.Event
    ops = []
    for t0 in (0.0, 0.020, 0.040):
        ops += [ev("moe", t0 + 0.001, t0 + 0.003,
                   "%moe_experts.3 = f32[24,2048]{1,0} custom-call("),
                ev("attn", t0 + 0.004, t0 + 0.008,
                   "%paged_attention.1 = f32[24,1,8,256]{3,2,1,0} "
                   "custom-call("),
                ev("rest", t0 + 0.008, t0 + 0.010, "%fusion.9 = fusion(")]
    dev = xplane.Device(0, ops, [
        ev("jit_step_cca_decode", 0.0, 0.010),
        ev("jit_step_cca_decode", 0.020, 0.030),
        ev("jit_chunk_cca_prefill", 0.040, 0.050)])
    monkeypatch.setattr(program_spans, "serve_window", lambda run: window)
    monkeypatch.setattr(program_spans, "window_ticks",
                        lambda r, spans: list(spans))
    return {"records": {"kind": "serve", "n_slots": 24, "cache_itemsize": 2,
                        "traced_ticks": [0, 3], "zaya": arch},
            "trace": xplane.Trace([dev], []) if with_trace else None,
            "mix": manifest.load_cell(CELL).traffic, "peaks": PEAKS}


SPANS = [{"decoding": 24, "experts_hit": 250, "expert_rows_max": 6,
          "kv_positions": 100_000},
         {"decoding": 0, "experts_hit": 0, "expert_rows_max": 0,
          "kv_positions": 0},
         {"decoding": 24, "experts_hit": 254, "expert_rows_max": 8,
          "kv_positions": 101_600}]


def test_the_five_readers_by_hand(monkeypatch, arch):
    ctx = _hand_ctx(monkeypatch, SPANS, arch)
    read = {n: manifest.load_reader(n)(ctx) for n in NEW}
    assert read["moe.top1_experts_hit_pct"] == pytest.approx(
        100 * 252 / 320)
    assert read["moe.top1_fullest_expert_rows"] == pytest.approx(7.0)
    # two decode runs, each the mean tick's bytes, over 2 x 2 ms at 1e11 B/s
    experts = counts_zaya.top1_experts_bytes(arch, 252, 480)
    assert read["kernel.top1_experts_roofline_pct"] == pytest.approx(
        100 * 2 * experts / 1e11 / 0.004)
    latent = counts_zaya.latent_kv_bytes(arch, 100_800, 24)
    assert read["kernel.cca_attention_roofline_pct"] == pytest.approx(
        100 * 2 * latent / 1e11 / 0.008)
    # 4 of a decode run's 8 busy milliseconds; the chunk's run is not read
    assert read["model.attend_share_pct"] == pytest.approx(50.0)


def test_the_trace_readers_find_no_kernel_is_an_error(monkeypatch, arch):
    ctx = _hand_ctx(monkeypatch, SPANS, arch)
    ctx["trace"].devices[0].ops[:] = [
        e for e in ctx["trace"].devices[0].ops if e.name == "rest"]
    for name in NEW[2:]:
        with pytest.raises(SystemExit, match="no device operation"):
            manifest.load_reader(name)(ctx)


@pytest.mark.parametrize("name", NEW)
def test_readers_give_nothing_on_a_program_without_the_counts(
        monkeypatch, name, arch):
    """The parent commit's ticks carry no ``kv_positions``, another
    family's no ``expert_rows_max``, and another runner's records no
    ``zaya``: every new reader returns ``None`` and does not raise; nor on
    an untraced run for those that read the trace."""
    read = manifest.load_reader(name)
    bare = [{"chunk": 0, "decoding": 1}]
    if name != "model.attend_share_pct":    # it reads no count
        assert read(_hand_ctx(monkeypatch, bare, arch)) is None
    for attrs, with_trace in ((SPANS, True), (bare, False)):
        ctx = _hand_ctx(monkeypatch, attrs, arch, with_trace)
        del ctx["records"]["zaya"]
        ctx["records"]["jamba"] = {}
        assert read(ctx) is None
    ctx = _hand_ctx(monkeypatch, SPANS, arch, with_trace=False)
    assert (read(ctx) is None) == (not name.startswith("moe."))
    ctx["records"] = {"kind": "train"}
    assert read(ctx) is None


# -- the runner at toy size ------------------------


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
                         dict(real.config, zaya_config=arch), mix,
                         real.end_to_end, real.per_layer)


@pytest.fixture()
def toy_conditions(monkeypatch):
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: 1)
    monkeypatch.setattr(weights_zaya, "STD", 0.1)


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
    place; and the two counter metrics over the toy window's own spans."""
    from bench_cells.runners import serve_zaya

    cell = toy_cell()
    cell.traffic["check"]["requests"] = 40
    run = serve_zaya.Run(cell, 1, harness.Spans())
    run.setup()
    run.window(2.0, _NoTrace())
    ctx = {"records": run.records, "trace": None, "mix": cell.traffic,
           "peaks": PEAKS}
    hit = manifest.load_reader("moe.top1_experts_hit_pct")(ctx)
    most = manifest.load_reader("moe.top1_fullest_expert_rows")(ctx)
    # 3 layers x 4 experts, 4 rows a layer: one to four experts a layer
    assert 25.0 <= hit <= 100.0 and 1.0 <= most <= 4.0
    for name in NEW[2:]:
        assert manifest.load_reader(name)(ctx) is None   # no trace, no share
    assert run.records["zaya"] == TOY
    program, control = run.check(), run.control()
    assert check.compare(program, LIMITS)[0], program
    ok, compared = check.compare(control, LIMITS)
    assert not ok and compared["gap_mean"]["value"] > 2 * LIMITS["gap_mean"]


def test_a_stalled_tick_gets_its_record_on_stderr(capsys):
    """Every run says where the host stood in each tick over four times
    the median (a pause of the machine is 0.4 % of this cell's window), and
    nothing where no tick is."""
    from bench_cells.runners import serve_zaya

    def tick(i, start_ms, ms):
        return types.SimpleNamespace(
            id=i, start_ns=int(start_ms * 1e6), end_ns=int((start_ms + ms)
                                                           * 1e6),
            attrs={"tick": i, "chunk": 0, "decoding": 24, "runs": 1})

    wait = types.SimpleNamespace(
        id=99, parent=5, name="engine.decode.wait", start_ns=int(1101e6),
        end_ns=int(1229e6), attrs={"run": 7, "ready": 0})
    ticks = [tick(i, 1000 + 20 * i, 20) for i in range(5)]
    ticks.append(tick(5, 1100, 130))
    run = types.SimpleNamespace(records={"t0": 1.0})
    say = serve_zaya.Run._say_which_ticks_stalled
    say(run, types.SimpleNamespace(ticks=ticks[:5], kids={}))
    assert capsys.readouterr().err == ""
    say(run, types.SimpleNamespace(ticks=ticks, kids={5: [wait]}))
    err = capsys.readouterr().err.splitlines()
    assert len(err) == 1 and err[0].startswith("0.100 s into the window")
    assert "stall record: tick 5 130.000 ms (median 20.000)" in err[0]
    assert "engine.decode.wait 128.000 [run 7 ready 0" in err[0]
