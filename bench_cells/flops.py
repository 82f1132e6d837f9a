"""Operations and bytes the algorithm needs, from shapes alone.

``train_flops_per_token``: forward and backward of a dense GPT, nothing
recomputed: 6 per matrix parameter (the embedding lookup is a gather, not a
matmul, and is left out) plus ``12 * n_layers * seq_len * d_model`` for the
attention scores and their use (``QK^T`` and ``PV`` are ``2 * T * d`` each
per token and layer forward, times 3 for forward + backward; the PaLM
convention, which counts the full ``T x T`` square although half is
masked).

``paged_attention_bytes``: the K and V rows one decode tick makes the paged
kernel read: for every decoding slot its live positions, in every layer,
both K and V, in the pool's storage type. The queries, the tables and the
output are left out (under 1 % of it at these sizes).
"""

from __future__ import annotations


def matrix_params(cfg: dict) -> int:
    d, hidden = cfg["d_model"], cfg.get("mlp_ratio", 4) * cfg["d_model"]
    per_block = 4 * d * d + 2 * d * hidden
    return cfg["n_layers"] * per_block + d * cfg["vocab"]


def total_params(cfg: dict) -> int:
    d, hidden = cfg["d_model"], cfg.get("mlp_ratio", 4) * cfg["d_model"]
    per_block = 4 * d * d + 2 * d * hidden + hidden + d + 4 * d
    return (cfg["n_layers"] * per_block + cfg["vocab"] * d
            + cfg["seq_len"] * d + 2 * d + d * cfg["vocab"] + cfg["vocab"])


def train_flops_per_token(cfg: dict, seq_len: int) -> int:
    return (6 * matrix_params(cfg)
            + 12 * cfg["n_layers"] * seq_len * cfg["d_model"])


def paged_attention_bytes(cfg: dict, live_positions: int,
                          cache_itemsize: int) -> int:
    """``live_positions``: the sum over decoding slots of the positions
    each attends to in one tick (its position + 1)."""
    return (2 * cfg["n_layers"] * live_positions * cfg["d_model"]
            * cache_itemsize)
