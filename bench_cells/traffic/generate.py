"""The one general traffic generator: a mix is a JSON file of parameters
beside this module, and everything drawn comes from ``--seed``.

Training mixes draw batches of token ids. Serving mixes draw, for each of
the mix's clients, the queue of requests it will send. The request sizes and
their order are fixed by the mix alone (stratified over its length
distributions, shuffled by its own ``pairing_seed``); the seed decides the
tokens. A window of fixed length then holds the same work for every seed: on
the chip the order alone moved tokens per second by 2.5 % either way, five
times what two runs of one seed differ by (PERF.md, Findings, PR 25).
"""

from __future__ import annotations

import math

import numpy as np


def _zipf_cdf(vocab: int, exponent: float) -> np.ndarray:
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    return np.cumsum(p / p.sum())


def zipf_tokens(rng: np.random.Generator, vocab: int, size,
                exponent: float = 1.0) -> np.ndarray:
    """Token ids whose frequency falls as ``1 / rank**exponent`` (id 0 the
    most frequent), the skew of natural text."""
    ids = np.searchsorted(_zipf_cdf(vocab, exponent), rng.random(size))
    return np.minimum(ids, vocab - 1).astype(np.int32)


def token_batches(seed: int, mix: dict, vocab: int, n_batches: int):
    """``n_batches`` batches ``[batch, seq_len + 1]`` of token ids: columns
    ``[:-1]`` are the inputs and ``[1:]`` the next-token targets."""
    rng = np.random.default_rng(seed)
    toks = mix["tokens"]
    if toks["distribution"] != "zipf":
        raise ValueError(f"unknown token distribution {toks!r}")
    return zipf_tokens(rng, vocab,
                       (n_batches, mix["batch"], mix["seq_len"] + 1),
                       toks.get("exponent", 1.0))


def _stratified(values, weights, n: int) -> list:
    """``n`` draws at the mid-quantiles of a weighted discrete law."""
    cdf = np.cumsum(np.asarray(weights, np.float64))
    cdf /= cdf[-1]
    q = (np.arange(n) + 0.5) / n
    return [values[i] for i in np.searchsorted(cdf, q)]


def request_sizes(mix: dict) -> list[tuple[int, int]]:
    """The mix's fixed set of ``(prompt length, answer length)`` pairs, one
    round of ``round_size`` requests. Independent of the seed."""
    n = mix["round_size"]
    pl = mix["prompt_lengths"]
    lengths = list(range(pl["min"], pl["max"] + 1, pl["multiple_of"]))
    if pl["weight"] == "inverse_length":
        weights = [1.0 / x for x in lengths]
    elif pl["weight"] == "uniform":
        weights = [1.0] * len(lengths)
    else:
        raise ValueError(f"unknown prompt length weight {pl['weight']!r}")
    prompts = _stratified(lengths, weights, n)
    al = mix["answer_lengths"]
    if al["law"] != "log_uniform":
        raise ValueError(f"unknown answer length law {al['law']!r}")
    lo, hi = math.log(al["min"]), math.log(al["max"])
    answers = [int(round(math.exp(lo + (hi - lo) * (i + 0.5) / n)))
               for i in range(n)]
    # pair long prompts with long and short answers alike: a fixed shuffle
    order = np.random.default_rng(mix["pairing_seed"]).permutation(n)
    return [(prompts[i], answers[int(order[i])]) for i in range(n)]


def client_queues(seed: int, mix: dict, vocab: int, rounds: int):
    """For each client, the requests it sends one after another:
    ``queues[c] = [(prompt ids, answer length), ...]``. Every round deals
    the mix's whole set of sizes out over the clients, in an order that the
    mix fixes (another one each round); the seed draws the tokens."""
    rng = np.random.default_rng(seed)
    order_rng = np.random.default_rng(mix["pairing_seed"] + 1)
    sizes = request_sizes(mix)
    n_clients = mix["clients"]
    if len(sizes) % n_clients:
        raise ValueError("round_size must be a multiple of clients")
    queues: list[list] = [[] for _ in range(n_clients)]
    exponent = mix["tokens"].get("exponent", 1.0)
    for _ in range(rounds):
        order = order_rng.permutation(len(sizes))
        for j, idx in enumerate(order):
            plen, alen = sizes[int(idx)]
            queues[j % n_clients].append(
                (zipf_tokens(rng, vocab, plen, exponent), alen))
    return queues
