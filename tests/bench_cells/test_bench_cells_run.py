"""The rest of a run, driven past the harness's look for a chip at a size a
test can hold (CPU, seconds): a sound run comes out correct; the timed path
broken underneath comes out not correct; and the control (the reference in
the precision below the configuration's, put in the program's place) comes
out not correct by the same comparison.

The cells here are the real traffic mixes with their sizes cut down. The
limits of the real mixes were set on the chip at the real sizes (PERF.md,
section 2); a toy model on the CPU backend rounds differently (XLA:CPU
rounds every bfloat16 operation, the TPU keeps float32 inside a fusion), so
each test states the limits it holds its toy cell to, set by the same rule:
above what sound runs of the toy read, below what its control reads.
"""

import copy
import json

import jax
import pytest

from bench_cells import check, harness, manifest
from bench_cells import run as benchrun

DEVICE = {"platform": "cpu", "kind": "test", "count": 1}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
GPT = {"vocab": 128, "seq_len": 64, "d_model": 64, "n_heads": 4,
       "n_layers": 4, "mlp_ratio": 4, "dropout_rate": 0.0}


def _mix(name):
    with open(manifest.traffic_path(name), encoding="utf-8") as f:
        return json.load(f)


def _cell(name, mix, limits):
    mix = copy.deepcopy(mix)
    mix["check"]["limits"] = limits
    real = manifest.load_cell(name)
    config = dict(real.config, gpt_config=GPT)
    return manifest.Cell(name, 1, real.config_name, real.traffic_name,
                         config, mix, real.end_to_end, real.per_layer)


TRAIN_LIMITS = {"loss_step1": 0.01, "grad_norm": 0.02, "update_norm": 0.05,
                "compiles_in_window": 0}
SERVE_LIMITS = {"gap_mean": 0.002, "compiles_in_window": 0}


def train_cell(limits=TRAIN_LIMITS):
    mix = _mix("train-1chip")
    mix.update(batch=4, seq_len=64)
    return _cell("gpt2-medium.train-1chip", mix, limits)


def serve_cell(limits=SERVE_LIMITS):
    mix = _mix("serve-closed")
    mix.update(
        engine={"n_slots": 4, "max_len": 64, "block_size": 4, "n_blocks": 40,
                "prefill_chunk": 8, "attn_kernel": "fused",
                "cache_dtype": "bfloat16"},
        clients=4, round_size=8, rounds=120,
        prompt_lengths={"min": 4, "max": 24, "multiple_of": 4,
                        "weight": "inverse_length"},
        answer_lengths={"law": "log_uniform", "min": 3, "max": 8})
    mix["check"]["requests"] = 6
    return _cell("gpt2-large.serve-closed", mix, limits)


@pytest.fixture(autouse=True)
def no_chip(monkeypatch):
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: 1)


def _run(cell, seconds, seed=2 ** 31 + 5):
    return benchrun.run_cell(cell, seed, seconds, False, DEVICE, PEAKS)


@pytest.mark.parametrize("make,seconds,metrics", [
    (train_cell, 0.5, {"train_tokens_per_s", "setup_s"}),
    (serve_cell, 2.0, {"serve_tokens_per_s", "tpot_p95_ms", "setup_s"}),
])
def test_sound_run_is_correct_and_prints_the_contracts_line(
        make, seconds, metrics, capsys):
    result = _run(make(), seconds)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == metrics
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    assert result["compared"]["compiles_in_window"] == {"value": 0,
                                                        "limit": 0}
    err = capsys.readouterr().err
    for name, c in result["compared"].items():
        assert f"compared {name}: {c['value']!r} (limit {c['limit']!r})" \
            in err
    json.dumps(result)


def test_train_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    from bench_cells.runners import train as runner

    real = runner.make_train_step

    def broken(pipe, opt):
        step = real(pipe, opt)

        class Lowered:
            def __init__(self, *args):
                self.compiled = step.lower(*args).compile()

            def compile(self):
                def call(buf, state, x, t, key):
                    copies = jax.tree.map(lambda a: a.copy(), (buf, state))
                    _, _, loss = self.compiled(buf, state, x, t, key)
                    return copies[0], copies[1], loss
                return call

        class Step:
            lower = staticmethod(lambda *args: Lowered(*args))

        return Step()

    monkeypatch.setattr(runner, "make_train_step", broken)
    result = _run(train_cell(), 0.3)
    assert result["correct"] is False
    c = result["compared"]
    # no leaf moved: the change's gap is the whole of the reference's norm,
    # over the real mix's limit as well as the toy's
    assert c["update_norm"]["value"] == pytest.approx(1.0, abs=1e-3)
    real_limits = _mix("train-1chip")["check"]["limits"]
    assert c["update_norm"]["value"] > real_limits["update_norm"]
    assert c["grad_norm"]["value"] > real_limits["grad_norm"]


def test_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from simple_distributed_machine_learning_tpu.serve.request import Request

    real = Request.emit

    def emit(self, token):
        # every third token of a request leaves the engine off by one
        off = 1 if len(self.tokens) % 3 == 2 else 0
        real(self, (int(token) + off) % GPT["vocab"])

    monkeypatch.setattr(Request, "emit", emit)
    result = _run(serve_cell(), 2.0)
    assert result["correct"] is False
    c = result["compared"]
    real_limits = _mix("serve-closed")["check"]["limits"]
    assert c["gap_mean"]["value"] > real_limits["gap_mean"]


class _NoTrace:
    enabled, dir, running = False, None, False


def _program_and_control(cell, seconds, seed):
    import importlib

    runner = importlib.import_module(
        f"bench_cells.runners.{cell.traffic['runner']}")
    run = runner.Run(cell, seed, harness.Spans())
    run.setup()
    run.window(seconds, _NoTrace())
    return run.check(), run.control()


def test_control_is_not_correct_train():
    """A float32 toy of the train cell, and its control in the precision
    below (bfloat16 compute): by the gradient's norm alone."""
    import dataclasses

    limits = {"loss_step1": 0.01, "grad_norm": 1e-5, "update_norm": 0.05}
    cell = train_cell(limits)
    cell = dataclasses.replace(
        cell, traffic=dict(cell.traffic, compute_dtype="float32"),
        config=dict(cell.config, control={"train": {"compute": "bfloat16"}}))
    program, control = _program_and_control(cell, 0.3, seed=1)
    assert check.compare(program, limits)[0]
    ok, compared = check.compare(control, limits)
    assert not ok
    assert compared["grad_norm"]["value"] > 30 * program["grad_norm"]
    # the two numbers a lower precision hardly moves stay inside
    assert compared["loss_step1"]["value"] < limits["loss_step1"]
    assert compared["update_norm"]["value"] < limits["update_norm"]


def test_control_is_not_correct_serve():
    """The toy serve cell (float32 matmuls on the CPU, bfloat16 pool) and
    its control: the token an int8-operand forward puts first."""
    limits = {"gap_mean": 1e-6}
    cell = serve_cell(limits)
    cell.traffic["check"]["requests"] = 100     # some 500 served tokens
    program, control = _program_and_control(cell, 2.0, seed=1)
    assert check.compare(program, limits)[0]
    assert not check.compare(control, limits)[0]
