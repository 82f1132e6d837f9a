"""Parameters, bytes and operations a Nemotron-H configuration needs, from
shapes alone (``cfg`` is the configuration file's ``nemotron_h_config``).

``mamba2_scan_bytes``: what one call of the grouped selective scan has to
move: the state in and out, ``x`` in and ``y`` out at the inner width, one
``delta`` a head and ``B`` and ``C`` a group in, all float32. ``A``, ``D``
(a head's value each) and whatever layout an implementation gives ``delta``,
``B`` and ``C`` are left out.

``latent_experts_bytes``: what the grouped expert products of ONE layer have
to move in one program run over ``rows`` routed (token, expert) pairs that
landed on ``experts_hit`` held experts: each hit expert's two matrices once,
the rows in (the weights' dtype) and their float32 results out, both at the
latent's width. It counts the work, whatever implements it: an expert that
got no row costs nothing, and the ``relu^2`` rows between the two products
need never leave the chip.
"""

from __future__ import annotations

F32 = 4


def _itemsize(dtype: str) -> int:
    return {"bfloat16": 2, "float32": 4}[dtype]


def d_inner(cfg: dict) -> int:
    return cfg["mamba_heads"] * cfg["mamba_head_dim"]


def conv_channels(cfg: dict) -> int:
    return d_inner(cfg) + 2 * cfg["n_groups"] * cfg["d_state"]


def mamba_layer_params(cfg: dict) -> int:
    d, di, ch, nh = (cfg["d_model"], d_inner(cfg), conv_channels(cfg),
                     cfg["mamba_heads"])
    return (d * (di + ch + nh)          # in_proj: z, xBC, dt
            + cfg["d_conv"] * ch + ch   # convolution weight and bias
            + 3 * nh                    # A_log, D, dt_bias
            + di                        # the gated norm
            + di * d                    # out_proj
            + d)                        # the layer's norm


def attention_layer_params(cfg: dict) -> int:
    d, dh = cfg["d_model"], cfg["head_dim"]
    return (2 * d * cfg["n_heads"] * dh + 2 * d * cfg["n_kv_heads"] * dh
            + d)


def expert_params(cfg: dict) -> int:
    """One routed expert's two matrices."""
    return 2 * cfg["d_latent"] * cfg["d_expert"]


def expert_layer_rest_params(cfg: dict) -> int:
    """What an expert layer holds beside its routed experts: the router and
    its selection bias, the latent's two projections, the shared expert,
    the layer's norm."""
    d = cfg["d_model"]
    return (d * cfg["n_experts"] + cfg["n_experts"]
            + 2 * d * cfg["d_latent"] + 2 * d * cfg["d_shared"] + d)


def total_params(cfg: dict, pattern: str | None = None,
                 experts: int | None = None, vocab: int | None = None) -> int:
    """Embedding, the pattern's layers with ``experts`` routed experts in
    each expert layer, final norm and the untied head: the configuration's
    own cut where nothing else is given, the published model with its
    pattern, 512 and 131,072."""
    pattern = cfg["pattern"] if pattern is None else pattern
    experts = cfg["experts_held"] if experts is None else experts
    vocab = cfg["vocab"] if vocab is None else vocab
    d = cfg["d_model"]
    return (pattern.count("M") * mamba_layer_params(cfg)
            + pattern.count("*") * attention_layer_params(cfg)
            + pattern.count("E") * (experts * expert_params(cfg)
                                    + expert_layer_rest_params(cfg))
            + 2 * vocab * d + d)


def active_params_per_token(cfg: dict, pattern: str, vocab: int) -> int:
    """What one token's forward multiplies by: ``top_k`` experts a layer."""
    return total_params(cfg, pattern, cfg["top_k"], vocab)


def state_bytes_per_slot(cfg: dict, tail_itemsize: int = 2) -> int:
    """One slot's recurrent state over all the Mamba layers: ``[d_state,
    d_inner]`` float32 and the ``d_conv - 1`` remembered convolution inputs
    in the pool's dtype."""
    return cfg["pattern"].count("M") * (
        cfg["d_state"] * d_inner(cfg) * F32
        + (cfg["d_conv"] - 1) * conv_channels(cfg) * tail_itemsize)


def kv_bytes_per_position(cfg: dict, itemsize: int = 2) -> int:
    return (2 * cfg["pattern"].count("*") * cfg["n_kv_heads"]
            * cfg["head_dim"] * itemsize)


def mamba2_scan_bytes(cfg: dict, n: int, n_tok: int) -> int:
    di, s = d_inner(cfg), cfg["d_state"]
    return F32 * (2 * n * s * di                        # the state in, out
                  + 2 * n * n_tok * di                  # x in, y out
                  + n * n_tok * cfg["mamba_heads"]      # delta, one a head
                  + 2 * n * n_tok * cfg["n_groups"] * s)    # B and C


def mamba2_scan_flops(cfg: dict, n: int, n_tok: int) -> int:
    """Decay, update and read of every state element of every token."""
    return 6 * n * n_tok * cfg["d_state"] * d_inner(cfg)


def latent_experts_bytes(cfg: dict, experts_hit: int, rows: int) -> int:
    w = _itemsize(cfg["param_dtype"])
    return (experts_hit * expert_params(cfg) * w
            + rows * cfg["d_latent"] * (w + F32))


def latent_experts_flops(cfg: dict, rows: int) -> int:
    return 2 * rows * expert_params(cfg)
