"""Parameters and bytes a Kimi-Linear configuration needs, from shapes alone
(``cfg`` is the configuration file's ``kimi_linear_config``).

The parameter counts follow ``bench_cells/reference/kimi_linear.py`` line by
line; with the published widths a KDA layer's mixer is 39,514,272 (three
projections of 2304 x 4096, three convolutions of 4 x 4096, the decay's 2304
-> 128 -> 4096 with ``A_log [32]`` and ``dt_bias [4096]``, ``beta``'s 2304 x
32, the output gate's 2304 -> 128 -> 4096, the head norm's 128, ``W_o``), a
latent layer's 29,114,880 (``W_q`` 2304 x 32 x 192, ``W_kva`` 2304 x 576,
the latent norm's 512, ``W_kvb`` 512 x 32 x 256, ``W_o``), one expert
7,077,888, the leading dense part 63,700,992, a router with its selection
bias 590,080, two norms a layer 4,608. All 27 layers with 256 experts in the
26 mixture layers, the embedding and the untied head (2 x 377,487,360) and
the final norm are 49,122,681,728, and a token is multiplied by
3,106,972,544 of them (8 experts and the shared one a mixture layer, the
head but not the embedding's other rows): the published "48B-A3B". The cut
this repo runs (16 held experts a layer, 20,480 held rows) is 4,296,057,728.

``kda_bytes``: what the recurrence of ONE decode run has to move over
``slots`` live sequences: per KDA layer each one's state in and out, ``q``,
``k``, ``g`` and ``v`` in at a head's width, ``beta`` in, ``o`` out, all
float32. Whatever layout an implementation gives them (columns, tiles) is
left out, as is a slot that sits the tick out.

``latent_kv_bytes``: what the attention of one decode run has to move over
``kv_positions`` cached positions (the slots' lengths summed): per latent
layer each position's ONE row AS HELD (``d_cache`` lanes: the 576 of latent
and shared key lanes in whole lane tiles), ONCE, the absorbed queries in and
the latent-wide outputs out in float32. A kernel that copied the row twice
(as keys and as values) moved twice this and reads half.

``held_experts_bytes``: what the grouped products of the HELD routed experts
of one decode run have to move where ``experts_hit`` (layer, held expert)
pairs got a row and ``rows`` (token, expert) pairs landed on a held expert:
each hit expert's three matrices once, the rows in (the weights' dtype) and
their float32 results out at the model's width. All three count the work,
whatever implements it.
"""

from __future__ import annotations

F32 = 4
_LANES = 128


def _itemsize(dtype: str) -> int:
    return {"bfloat16": 2, "float32": 4}[dtype]


def d_kda(cfg: dict) -> int:
    return cfg["kda_heads"] * cfg["kda_head_dim"]


def d_cache(cfg: dict) -> int:
    """The pool's row as held: latent and shared key lanes in whole tiles."""
    return -(-(cfg["d_latent"] + cfg["d_rope"]) // _LANES) * _LANES


def kda_mixer_params(cfg: dict) -> int:
    d, c, r, nh = cfg["d_model"], d_kda(cfg), cfg["d_gate"], cfg["kda_heads"]
    return (3 * d * c + 3 * cfg["d_conv"] * c       # q, k, v and their taps
            + d * r + r * c + nh + c                # decay: f_a, f_b, A, dt
            + d * nh                                # beta
            + d * r + r * c                         # output gate: g_a, g_b
            + cfg["kda_head_dim"] + c * d)          # head norm, W_o


def latent_mixer_params(cfg: dict) -> int:
    d, h = cfg["d_model"], cfg["n_heads"]
    return (d * h * (cfg["d_nope"] + cfg["d_rope"])             # W_q
            + d * (cfg["d_latent"] + cfg["d_rope"])             # W_kva
            + cfg["d_latent"]                                   # w_kv
            + cfg["d_latent"] * h * (cfg["d_nope"] + cfg["d_v"])    # W_kvb
            + h * cfg["d_v"] * d)                               # W_o


def expert_params(cfg: dict) -> int:
    """One expert's three matrices, routed or shared."""
    return 3 * cfg["d_model"] * cfg["d_expert"]


def dense_params(cfg: dict) -> int:
    return 3 * cfg["d_model"] * cfg["d_ff"]


def router_params(cfg: dict) -> int:
    """The router's matrix and its selection bias."""
    return cfg["d_model"] * cfg["n_experts"] + cfg["n_experts"]


def layers_params(cfg: dict, experts: int) -> int:
    """Every layer's mixer, two norms and feed-forward part with
    ``experts`` routed experts in each mixture layer."""
    n, n_attn = cfg["n_layers"], len(cfg["attn_layers"])
    return ((n - n_attn) * kda_mixer_params(cfg)
            + n_attn * latent_mixer_params(cfg)
            + n * 2 * cfg["d_model"]
            + cfg["n_dense"] * dense_params(cfg)
            + (n - cfg["n_dense"]) * (
                (experts + cfg["n_shared"]) * expert_params(cfg)
                + router_params(cfg)))


def total_params(cfg: dict, experts: int | None = None,
                 vocab: int | None = None) -> int:
    """Embedding, the layers, final norm and the untied head: the
    configuration's own cut where nothing else is given; the published
    model with 256 and 163,840."""
    experts = cfg["experts_held"] if experts is None else experts
    vocab = cfg["vocab"] if vocab is None else vocab
    return (layers_params(cfg, experts) + 2 * vocab * cfg["d_model"]
            + cfg["d_model"])


def active_params_per_token(cfg: dict, vocab: int) -> int:
    """What one token's forward multiplies by: ``top_k`` experts a mixture
    layer, the head, and of the embedding nothing (a row is looked up)."""
    return (layers_params(cfg, cfg["top_k"]) + vocab * cfg["d_model"]
            + cfg["d_model"])


def state_bytes_per_slot(cfg: dict) -> int:
    """One slot's recurrent state over all the KDA layers: ``[heads, dk,
    dk]`` and three convolutions' ``d_conv - 1`` remembered inputs,
    float32."""
    n_kda = cfg["n_layers"] - len(cfg["attn_layers"])
    return n_kda * F32 * (cfg["kda_heads"] * cfg["kda_head_dim"] ** 2
                          + 3 * (cfg["d_conv"] - 1) * d_kda(cfg))


def kv_bytes_per_position(cfg: dict, itemsize: int = 2) -> int:
    """ONE row a latent layer, as held."""
    return len(cfg["attn_layers"]) * d_cache(cfg) * itemsize


def kda_bytes(cfg: dict, slots: int) -> int:
    n_kda = cfg["n_layers"] - len(cfg["attn_layers"])
    nh, dk = cfg["kda_heads"], cfg["kda_head_dim"]
    return n_kda * slots * F32 * (2 * nh * dk * dk      # the state in, out
                                  + 5 * nh * dk         # q, k, g, v in, o out
                                  + nh)                 # beta


def latent_kv_bytes(cfg: dict, kv_positions: int, slots: int,
                    itemsize: int = 2) -> int:
    n_attn, h = len(cfg["attn_layers"]), cfg["n_heads"]
    return (kv_positions * kv_bytes_per_position(cfg, itemsize)
            + n_attn * slots * h * (d_cache(cfg) + cfg["d_latent"]) * F32)


def held_experts_bytes(cfg: dict, experts_hit: int, rows: int) -> int:
    w = _itemsize(cfg["param_dtype"])
    return (experts_hit * expert_params(cfg) * w
            + rows * cfg["d_model"] * (w + F32))
