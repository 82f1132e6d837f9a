"""Parameters and bytes a Cohere2 mixture configuration needs, from shapes
alone (``cfg`` is the configuration file's ``cohere2_config``).

The parameter counts follow ``bench_cells/reference/cohere2.py`` line by
line; with the published widths a layer is 6,786,912,256 (attention
142,606,336, norm 4,096, router 524,288, four shared experts 201,326,592,
128 routed experts of 50,331,648), 32 layers with the tied embedding
(1,073,741,824) and the final norm 218,254,938,112, and a token is
multiplied by 747,114,496 a layer, 32 layers and the head 24.98 B: the
family's published "218B-A25B". The cut this repo runs (four layers, 16
held experts a layer, 32,768 held rows) is 4,733,292,544.

``kv_bytes``: what the attention of ONE decode run has to move over
``kv_positions`` cached positions (the slots' lengths summed) of which a
window layer sees ``kv_window_positions`` (each length cut to the window):
a full layer's K and V row of every position once, a window layer's of the
positions inside the window alone, at the pool's width of ``n_kv_heads x
head_dim`` lanes, the queries in and the outputs out in float32. A window
layer that fetched what lies behind its window moved more than this and
reads LOW against it.

``held_experts_bytes``: what the grouped products of the HELD routed experts
of one decode run have to move where ``experts_hit`` (layer, held expert)
pairs got a row and ``rows`` (token, expert) pairs landed on a held expert:
each hit expert's three matrices once, the rows in (the weights' dtype) and
their float32 results out at the model's width. Both count the work,
whatever implements it.
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


def attention_params(cfg: dict) -> int:
    d = cfg["d_model"]
    return 2 * d * d_query(cfg) + 2 * d * d_kv(cfg)     # W_q, W_o; W_k, W_v


def router_params(cfg: dict) -> int:
    return cfg["d_model"] * cfg["n_experts"]


def expert_params(cfg: dict) -> int:
    """One expert's three matrices, routed or shared."""
    return 3 * cfg["d_model"] * cfg["d_expert"]


def layer_params(cfg: dict, experts: int | None = None) -> int:
    """A layer with ``experts`` routed experts (all of them where none is
    given), its shared experts, router, attention and one norm."""
    experts = cfg["n_experts"] if experts is None else experts
    return (attention_params(cfg) + cfg["d_model"] + router_params(cfg)
            + (cfg["n_shared"] + experts) * expert_params(cfg))


def embedding_params(cfg: dict, rows: int | None = None) -> int:
    return (cfg["vocab"] if rows is None else rows) * cfg["d_model"]


def total_params(cfg: dict, layers: int | None = None,
                 experts: int | None = None, rows: int | None = None) -> int:
    """The tied embedding (``rows`` of it), ``layers`` layers of ``experts``
    routed experts and the final norm."""
    layers = cfg["n_layers"] if layers is None else layers
    return (embedding_params(cfg, rows) + layers * layer_params(cfg, experts)
            + cfg["d_model"])


def held_params(cfg: dict) -> int:
    """What this build holds: its layers with the held experts, the held
    rows, the final norm."""
    return total_params(cfg, experts=cfg["experts_held"])


def active_layer_params(cfg: dict) -> int:
    """What one token's forward multiplies by in a layer: ``top_k`` routed
    experts beside everything that is not routed."""
    return layer_params(cfg, experts=cfg["top_k"])


def window_layers(cfg: dict) -> int:
    return sum(l % cfg["full_every"] != cfg["full_every"] - 1
               for l in range(cfg["n_layers"]))


def kv_bytes(cfg: dict, kv_positions: int, kv_window_positions: int,
             slots: int, itemsize: int = 2) -> int:
    n_window = window_layers(cfg)
    n_full = cfg["n_layers"] - n_window
    return ((n_full * kv_positions + n_window * kv_window_positions)
            * 2 * d_kv(cfg) * itemsize
            + 2 * slots * cfg["n_layers"] * d_query(cfg) * F32)


def held_experts_bytes(cfg: dict, experts_hit: int, rows: int) -> int:
    w = _itemsize(cfg["param_dtype"])
    return (experts_hit * expert_params(cfg) * w
            + rows * cfg["d_model"] * (w + F32))
