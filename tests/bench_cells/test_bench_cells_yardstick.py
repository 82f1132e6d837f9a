"""The yardstick's arithmetic against hand counts: FLOPs and bytes, the
traffic generators, the percentile, the comparison."""

import json
import os

import numpy as np
import pytest

from bench_cells import check, flops, manifest, readings
from bench_cells.traffic import generate

TINY = {"vocab": 100, "seq_len": 16, "d_model": 8, "n_heads": 2,
        "n_layers": 3, "mlp_ratio": 4}


def test_flops_per_token_by_hand():
    # per block: q, k, v, o are 8x8 each; the MLP 8x32 and 32x8
    per_block = 4 * 64 + 2 * 8 * 32
    matrices = 3 * per_block + 8 * 100          # + the untied head
    assert flops.matrix_params(TINY) == matrices == 3104
    # attention: QK^T and PV, 2*T*d each forward, x3 with the backward
    assert flops.train_flops_per_token(TINY, 16) == 6 * 3104 + 12 * 3 * 16 * 8


def test_total_params_counts_every_leaf():
    per_block = 4 * 64 + 2 * 8 * 32 + 32 + 8 + 4 * 8
    assert flops.total_params(TINY) == (3 * per_block + 100 * 8 + 16 * 8
                                        + 2 * 8 + 8 * 100 + 100)


@pytest.mark.parametrize("name,million", [("gpt2-medium", 406),
                                          ("gpt2-large", 838)])
def test_published_sizes(name, million):
    with open(os.path.join(manifest.HERE, "configs", name + ".json")) as f:
        cfg = json.load(f)
    assert round(flops.total_params(cfg["gpt_config"]) / 1e6) == million
    pub, run = cfg["published"], cfg["gpt_config"]
    assert (pub["n_layer"], pub["n_embd"], pub["n_head"], pub["vocab_size"],
            pub["n_positions"]) == (run["n_layers"], run["d_model"],
                                    run["n_heads"], run["vocab"],
                                    run["seq_len"])
    assert run["d_model"] // run["n_heads"] == 64 and run["mlp_ratio"] == 4


def test_paged_attention_bytes_by_hand():
    # 3 layers, K and V, 8 values a position, 2 bytes each, 10 positions
    assert flops.paged_attention_bytes(TINY, 10, 2) == 2 * 3 * 10 * 8 * 2


def test_live_positions_and_decode_tokens_by_tick():
    records = {"requests": [
        {"prompt_len": 5, "ticks": [2, 3, 4], "stamps": [0.1, 0.2, 0.4],
         "t_submit": 0.0},
        {"prompt_len": 7, "ticks": [3, 4], "stamps": [0.3, 0.45],
         "t_submit": 0.1}]}
    assert readings.decode_tokens_by_tick(records) == {3: 1, 4: 2}
    assert readings.live_positions_by_tick(records) == {3: 6, 4: 7 + 8}
    assert readings.tokens_received(records) == 5
    assert readings.token_gaps_s(records) == pytest.approx(
        [0.1, 0.2, 0.15])
    assert readings.ttfts_s(records) == pytest.approx([0.1, 0.2])


def test_percentile_is_numpys():
    xs = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.0]
    for q in (0, 50, 95, 100):
        assert readings.percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)))


MIX = {"clients": 4, "round_size": 8, "pairing_seed": 3,
       "prompt_lengths": {"min": 4, "max": 24, "multiple_of": 4,
                          "weight": "inverse_length"},
       "answer_lengths": {"law": "log_uniform", "min": 3, "max": 8},
       "tokens": {"distribution": "zipf", "exponent": 1.0}}


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11])
def test_same_seed_same_requests(seed):
    a = generate.client_queues(seed, MIX, 100, rounds=3)
    b = generate.client_queues(seed, MIX, 100, rounds=3)
    assert [len(q) for q in a] == [6, 6, 6, 6]
    for qa, qb in zip(a, b):
        for (pa, na), (pb, nb) in zip(qa, qb):
            assert na == nb and np.array_equal(pa, pb)
    x = generate.token_batches(seed, {"batch": 2, "seq_len": 9,
                                      "tokens": MIX["tokens"]}, 100, 3)
    y = generate.token_batches(seed, {"batch": 2, "seq_len": 9,
                                      "tokens": MIX["tokens"]}, 100, 3)
    assert x.shape == (3, 2, 10) and np.array_equal(x, y)
    assert x.min() >= 0 and x.max() < 100


def test_every_seed_sends_the_same_sizes_in_the_same_order():
    sizes = sorted(generate.request_sizes(MIX))
    runs = []
    for seed in (1, 2):
        queues = generate.client_queues(seed, MIX, 100, rounds=2)
        got = [[(len(p), n) for p, n in q] for q in queues]
        assert sorted(x for q in got for x in q[:2]) == sizes   # one round
        runs.append((got, queues[0][0][0]))
    assert runs[0][0] == runs[1][0]                    # the work is the mix's
    assert not np.array_equal(runs[0][1], runs[1][1])  # the tokens the seed's
    first, second = ([q[i:i + 2] for q in runs[0][0]] for i in (0, 2))
    assert first != second                             # rounds differ in order
    assert all(4 <= p <= 24 and p % 4 == 0 and 3 <= n <= 8
               for p, n in sizes)


def test_zipf_tokens_are_skewed():
    ids = generate.zipf_tokens(np.random.default_rng(0), 1000, 20000)
    counts = np.bincount(ids, minlength=1000)
    assert counts[0] > counts[9] > counts[99]
    assert counts[0] / counts[9] == pytest.approx(10, rel=0.3)


def test_leaf_gaps_use_the_median_leaf_as_floor():
    ref = [1.0, 2.0, 1e-9, 4.0, 3.0]
    gaps = check.leaf_gaps([1.1, 2.0, 2e-9, 4.0, 6.0], ref)
    # median is 2: the small leaf is held against it, not against itself
    assert gaps == pytest.approx([0.1 / 2.0, 0.0, 1e-9 / 2.0, 0.0, 1.0])
    with pytest.raises(ValueError):
        check.leaf_gaps([1.0], ref)


def test_compare_holds_each_number_to_its_own_limit():
    ok, out = check.compare({"a": 0.5, "b": 0}, {"a": 1.0, "b": 0})
    assert ok and out["a"] == {"value": 0.5, "limit": 1.0}
    assert not check.compare({"a": 1.5, "b": 0}, {"a": 1.0, "b": 0})[0]
    assert not check.compare({"a": 0.5, "b": 1}, {"a": 1.0, "b": 0})[0]
    assert not check.compare({"a": float("nan")}, {"a": 1.0})[0]
    with pytest.raises(SystemExit):
        check.compare({"c": 0.0}, {"a": 1.0})
