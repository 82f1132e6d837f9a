"""Parameters and bytes a ZAYA1 configuration needs, from shapes alone
(``cfg`` is the configuration file's ``zaya_config``).

The parameter counts follow ``bench_cells/reference/zaya.py`` line by line;
with the published widths a layer is 207,583,506, forty layers 8,303,340,240
and 753,593,040 of them active a token, beside the 537,133,056 of the tied
embedding: the family's published "8.3B-A0.8B".

``latent_kv_bytes``: what the attention of ONE decode run has to move over
``kv_positions`` cached positions (the slots' lengths summed) in ``slots``
slots: every layer's K and V row of each position once, at the pool's own
width (``n_kv_heads x head_dim`` lanes: the latent), the queries in and the
outputs out in float32.

``top1_experts_bytes``: what the grouped expert products of one decode run
have to move where ``experts_hit`` (layer, expert) pairs got a row and
``rows`` (token, layer) pairs were routed: each hit expert's three matrices
once, the rows in (the weights' dtype) and their float32 results out at the
model's width. Both count the work, whatever implements it: an expert that
got no row costs nothing, and what lies between an expert's products need
never leave the chip.
"""

from __future__ import annotations

F32 = 4


def _itemsize(dtype: str) -> int:
    return {"bfloat16": 2, "float32": 4}[dtype]


def d_query(cfg: dict) -> int:
    return cfg["n_heads"] * cfg["head_dim"]


def d_kv(cfg: dict) -> int:
    """The pool's row: one position's K (or V) heads, side by side."""
    return cfg["n_kv_heads"] * cfg["head_dim"]


def conv_channels(cfg: dict) -> int:
    return d_query(cfg) + d_kv(cfg)


def attention_part_params(cfg: dict) -> int:
    d, dh, c = cfg["d_model"], cfg["head_dim"], conv_channels(cfg)
    return (d * c                           # W_q, W_k
            + d * d_kv(cfg)                 # W_v1, W_v2
            + cfg["conv0"] * c + c          # the depthwise convolution
            + cfg["conv1"] * c * dh + c     # the per-head convolution
            + cfg["n_kv_heads"]             # tau
            + d_query(cfg) * d)             # W_o


def router_params(cfg: dict) -> int:
    d, r, e = cfg["d_model"], cfg["d_router"], cfg["n_experts"]
    return (d * r + r                       # W_down, b_down
            + r                             # gamma
            + r                             # the state's norm
            + 2 * (r * r + r)               # W_1, b_1, W_2, b_2
            + r * e                         # W_3
            + e)                            # the selection bias


def expert_params(cfg: dict) -> int:
    """One expert's three matrices."""
    return 3 * cfg["d_model"] * cfg["d_expert"]


def norm_and_scaling_params(cfg: dict) -> int:
    """A layer's two norms and, a part, the four residual-scaling vectors."""
    return 10 * cfg["d_model"]


def layer_params(cfg: dict, experts: int | None = None) -> int:
    experts = cfg["n_experts"] if experts is None else experts
    return (attention_part_params(cfg) + router_params(cfg)
            + experts * expert_params(cfg) + norm_and_scaling_params(cfg))


def embedding_params(cfg: dict) -> int:
    return cfg["vocab"] * cfg["d_model"]


def total_params(cfg: dict, layers: int | None = None) -> int:
    """The tied embedding, ``layers`` layers (the configuration's own cut
    where none is given) and the final norm."""
    layers = cfg["n_layers"] if layers is None else layers
    return embedding_params(cfg) + layers * layer_params(cfg) + cfg["d_model"]


def active_layer_params(cfg: dict) -> int:
    """What one token's forward multiplies by in a layer: one expert."""
    return layer_params(cfg, experts=1)


def kv_bytes_per_position(cfg: dict, itemsize: int = 2) -> int:
    return 2 * cfg["n_layers"] * d_kv(cfg) * itemsize


def state_bytes_per_slot(cfg: dict) -> int:
    """One slot's convolution tails and shifted value over all the layers,
    float32."""
    return cfg["n_layers"] * F32 * (
        (cfg["conv0"] - 1 + cfg["conv1"] - 1) * conv_channels(cfg)
        + d_kv(cfg) // 2)


def latent_kv_bytes(cfg: dict, kv_positions: int, slots: int,
                    itemsize: int = 2) -> int:
    return (kv_positions * kv_bytes_per_position(cfg, itemsize)
            + 2 * slots * cfg["n_layers"] * d_query(cfg) * F32)


def top1_experts_bytes(cfg: dict, experts_hit: int, rows: int) -> int:
    w = _itemsize(cfg["param_dtype"])
    return (experts_hit * expert_params(cfg) * w
            + rows * cfg["d_model"] * (w + F32))
