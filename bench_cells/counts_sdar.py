"""Parameters, bytes and operations an SDAR configuration needs, from shapes
alone (``cfg`` is the configuration file's ``sdar_config``).

``moe_experts_bytes``: what the grouped expert products of ONE layer have
to move in one program run over ``rows`` routed (token, expert) pairs that
hit ``experts_hit`` experts: each hit expert's three matrices once, the
rows into the gate and the up product (the weights' dtype) and their
float32 results out, the gated rows into the down product and its float32
rows out. It counts the work, whatever implements it; an expert that got no
row costs nothing.
"""

from __future__ import annotations

F32 = 4


def _itemsize(dtype: str) -> int:
    return {"bfloat16": 2, "float32": 4}[dtype]


def attention_params(cfg: dict) -> int:
    d, dh = cfg["d_model"], cfg["head_dim"]
    return (2 * d * cfg["n_heads"] * dh          # q and o
            + 2 * d * cfg["n_kv_heads"] * dh     # k and v
            + 2 * dh)                            # the two head norms


def expert_params(cfg: dict) -> int:
    """One expert's three matrices."""
    return 3 * cfg["d_model"] * cfg["d_expert"]


def layer_params(cfg: dict) -> int:
    d = cfg["d_model"]
    return (attention_params(cfg) + 2 * d        # + the two layer norms
            + d * cfg["n_experts"]               # the router
            + cfg["n_experts"] * expert_params(cfg))


def total_params(cfg: dict, n_layers: int | None = None) -> int:
    """Embedding, ``n_layers`` layers (the configuration's own where not
    given), final norm and the untied head."""
    n = cfg["n_layers"] if n_layers is None else n_layers
    d = cfg["d_model"]
    return n * layer_params(cfg) + 2 * cfg["vocab"] * d + d


def active_params_per_token(cfg: dict, n_layers: int | None = None) -> int:
    """What one token's forward multiplies by: ``top_k`` experts a layer."""
    n = cfg["n_layers"] if n_layers is None else n_layers
    d = cfg["d_model"]
    return (n * (attention_params(cfg) + 2 * d + d * cfg["n_experts"]
                 + cfg["top_k"] * expert_params(cfg))
            + 2 * cfg["vocab"] * d + d)


def kv_bytes_per_position(cfg: dict, itemsize: int = 2) -> int:
    return (2 * cfg["n_layers"] * cfg["n_kv_heads"] * cfg["head_dim"]
            * itemsize)


def moe_experts_bytes(cfg: dict, experts_hit: int, rows: int) -> int:
    w = _itemsize(cfg["param_dtype"])
    d, f = cfg["d_model"], cfg["d_expert"]
    return (experts_hit * expert_params(cfg) * w
            + rows * (2 * d * w + 2 * f * F32      # gate and up
                      + f * w + d * F32))          # down


def moe_experts_flops(cfg: dict, rows: int) -> int:
    return 2 * rows * expert_params(cfg)
