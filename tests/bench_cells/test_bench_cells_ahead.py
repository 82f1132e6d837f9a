"""``engine.ahead_ticks_pct`` over the hand-made spans of
``test_bench_cells_program_spans.py`` (with the ``ahead`` attribute, and
nothing without it), once over the real recorder on the toy serve cell, and
the traffic files' program patterns against GPT's two programs as they are
called since they keep the newest tokens on the device (PR 31).
"""

import importlib
import json
import os
import re

import jax
import numpy as np
import pytest

from bench_cells import harness, manifest, program_spans

NAME = "engine.ahead_ticks_pct"


def _spans():
    return importlib.import_module("test_bench_cells_program_spans")


def _run(monkeypatch, ahead):
    """The hand-made window; ``ahead``: the attribute of its three ticks in
    order (``None``: a program whose ticks do not carry it)."""
    hm = _spans()
    records, spans = hm.hand_made()
    ticks = [s for s in spans if s.name == "engine.tick"
             and s.start_ns >= hm.at(0)]
    assert [t.attrs["decoding"] for t in ticks] == [2, 2, 3]
    if ahead is not None:
        for t, a in zip(ticks, ahead):
            t.attrs["ahead"] = a
    recorder = hm.Recorder(spans)
    monkeypatch.setattr(program_spans, "recorder", lambda: recorder)
    return {"records": records, "trace": None}, ticks


def read(run):
    return manifest.load_reader(NAME)(run)


@pytest.mark.parametrize("ahead,value", [
    ((1, 1, 1), 100.0), ((0, 1, 1), 200.0 / 3), ((0, 0, 0), 0.0)])
def test_mean_of_ahead_over_the_ticks_that_decoded(monkeypatch, ahead,
                                                   value):
    run, _ = _run(monkeypatch, ahead)
    assert read(run) == pytest.approx(value)


def test_a_tick_that_did_not_decode_is_not_counted(monkeypatch):
    run, ticks = _run(monkeypatch, (0, 1, 1))
    ticks[0].attrs["decoding"] = 0       # a chunk alone: nothing to be ahead of
    assert read(run) == pytest.approx(100.0)
    for t in ticks:
        t.attrs["decoding"] = 0
    assert read(run) is None


def test_nothing_where_a_tick_carries_no_such_attribute(monkeypatch):
    """The parent commit's ticks: no ``ahead``, so no reading and no
    error; the same for a program without the recorder and for a train
    cell."""
    run, ticks = _run(monkeypatch, None)
    assert read(run) is None
    ticks[0].attrs["ahead"] = 1          # some but not all: still nothing
    assert read(run) is None
    assert read(dict(run, records=dict(run["records"], kind="train"))) is None
    monkeypatch.setattr(program_spans, "recorder", lambda: None)
    assert read(run) is None


def test_manifest_entry_lists_both_serve_cells():
    entry, = (m for m in manifest.load_manifest()["per_layer"]
              if m["name"] == NAME)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "serve engine",
        "moves": "serve_tokens_per_s"}
    # by membership: every serving cell a later PR adds lists it too
    assert {"gpt2-large.serve-closed",
            "jamba2-3b.serve-reason-closed"} <= set(entry["workloads"])


def test_toy_serve_cell_runs_ahead_nearly_every_tick():
    """The real recorder on the toy GPT serve cell: its requests end by
    length, so every tick that decodes finds its decode dispatched."""
    from simple_distributed_machine_learning_tpu.telemetry import tracing

    toy = importlib.import_module("test_bench_cells_run")
    previous = tracing.install(tracing.Tracer())
    try:
        cell = toy.serve_cell()
        runner = importlib.import_module("bench_cells.runners.serve")
        run = runner.Run(cell, 2 ** 31 + 11, harness.Spans())
        run.setup()
        run.window(1.0, toy._NoTrace())
        got = read({"records": run.records, "trace": None})
    finally:
        tracing.install(previous)
    assert got == pytest.approx(100.0)


# -- the names the traffic files find GPT's programs by --------------------------


def _module_name(jitted, *args) -> str:
    return re.search(r"module @(\S+)", jitted.lower(*args).as_text()).group(1)


def test_program_patterns_find_the_programs_that_keep_the_newest_tokens():
    """``jit_step_paged_decode`` / ``jit_chunk_paged_prefill`` still: the
    names the trace is read by did not move with the signature (state after
    the pool; the chunk told its slot and what to seat, the decode which
    slots are live)."""
    from simple_distributed_machine_learning_tpu.models.gpt import (
        SEAT_SAMPLE,
        GPTConfig,
        make_gpt_stages,
    )
    from simple_distributed_machine_learning_tpu.serve import InferenceEngine

    cfg = GPTConfig(vocab=32, seq_len=16, d_model=32, n_heads=2, n_layers=2)
    stages, _, _ = make_gpt_stages(jax.random.key(0), cfg, 1)
    eng = InferenceEngine(stages, cfg, n_slots=2, block_size=4,
                          prefill_chunk=4, attn_kernel="fused")
    S, nb, pool = 2, eng.pool.blocks_per_seq, eng.pool
    names = {
        "decode_tick": _module_name(
            eng._decode, eng.params, pool.kc, pool.vc, pool.state,
            np.zeros(S, np.int32), np.zeros((S, nb), np.int32),
            np.zeros(S, bool), np.zeros(S, np.float32),
            np.zeros(S, np.int32), np.full(S, 2.0, np.float32)),
        "prefill_chunk": _module_name(
            eng._chunk_prefill, eng.params, pool.kc, pool.vc, pool.state,
            np.zeros((1, 4), np.int32), np.int32(0), pool.device_table(0),
            np.int32(0), np.int32(SEAT_SAMPLE), np.zeros(2, np.uint32),
            np.float32(0), np.int32(0), np.float32(2))}
    assert names == {"decode_tick": "jit_step_paged_decode",
                     "prefill_chunk": "jit_chunk_paged_prefill"}
    mix_path = os.path.join(manifest.HERE, "traffic", "serve-closed.json")
    with open(mix_path, encoding="utf-8") as f:
        patterns = json.load(f)["programs"]
    for key, mine in names.items():
        other, = (v for k, v in names.items() if k != key)
        assert re.search(patterns[key], mine)
        assert not re.search(patterns[key], other)
    assert os.path.exists(manifest.metric_path(NAME))
