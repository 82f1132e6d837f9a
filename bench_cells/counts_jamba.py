"""Parameters and bytes a Jamba configuration needs, from shapes alone
(``cfg`` is the configuration file's ``jamba_config``).

``selective_scan_bytes``: what one call of the selective-scan kernel has to
move: the state in and out, ``x``, ``delta`` and ``z`` in, ``B`` and ``C``
in, ``y`` out, all float32. ``A`` and ``D`` (one layer's, under 0.4 MB) and
the padding of ``B`` / ``C`` to whole tiles are left out.

``decode_tick_bytes`` / ``chunk_bytes``: the least HBM traffic of one decode
tick over ``n_slots`` slots and of one prefill chunk of ``n_tok`` tokens:
every weight read once (the embedding counts once: it is read as the head),
the recurrent state read and written (all slots for a tick, one slot for a
chunk), and the K/V rows attended. Activations are left out (a tick's are
under 1 % of it).
"""

from __future__ import annotations

F32 = 4


def _itemsize(dtype: str) -> int:
    return {"bfloat16": 2, "float32": 4}[dtype]


def _is_attention(cfg: dict, layer: int) -> bool:
    return layer % cfg["attn_period"] == cfg["attn_offset"]


def n_attention_layers(cfg: dict) -> int:
    return sum(_is_attention(cfg, i) for i in range(cfg["n_layers"]))


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["d_model"] * cfg["d_ff"]


def mamba_mixer_params(cfg: dict) -> int:
    d, di = cfg["d_model"], cfg["expand"] * cfg["d_model"]
    n, r, k = cfg["d_state"], cfg["dt_rank"], cfg["d_conv"]
    return (d * 2 * di              # in_proj
            + k * di + di           # convolution weight and bias
            + di * (r + 2 * n)      # x_proj
            + r + 2 * n             # the inner norms
            + r * di + di           # dt_proj and its bias
            + n * di + di           # A_log and D
            + di * d)               # out_proj


def attention_mixer_params(cfg: dict) -> int:
    d = cfg["d_model"]
    kv_dim = cfg["n_kv_heads"] * (d // cfg["n_heads"])
    return 2 * d * d + 2 * d * kv_dim


def total_params(cfg: dict) -> int:
    d = cfg["d_model"]
    n_attn = n_attention_layers(cfg)
    per_layer = mlp_params(cfg) + 2 * d             # + the two norms
    return ((cfg["n_layers"] - n_attn) * (mamba_mixer_params(cfg)
                                          + per_layer)
            + n_attn * (attention_mixer_params(cfg) + per_layer)
            + cfg["vocab"] * d + d)                 # tied embedding, norm_f


def state_bytes_per_slot(cfg: dict, tail_itemsize: int = 2) -> int:
    """One slot's recurrent state over all the Mamba layers: ``[d_state,
    d_inner]`` float32 and the ``d_conv - 1`` remembered convolution inputs
    in the pool's dtype."""
    di = cfg["expand"] * cfg["d_model"]
    n_mamba = cfg["n_layers"] - n_attention_layers(cfg)
    return n_mamba * (cfg["d_state"] * di * F32
                      + (cfg["d_conv"] - 1) * di * tail_itemsize)


def selective_scan_bytes(cfg: dict, n: int, n_tok: int) -> int:
    di, n_state = cfg["expand"] * cfg["d_model"], cfg["d_state"]
    return F32 * (2 * n * n_state * di          # the state in and out
                  + 4 * n * n_tok * di          # x, delta, z in; y out
                  + 2 * n * n_tok * n_state)    # B and C in


def kv_bytes(cfg: dict, positions: int, cache_itemsize: int) -> int:
    """K and V rows of ``positions`` attended positions in every attention
    layer."""
    kv_dim = cfg["n_kv_heads"] * (cfg["d_model"] // cfg["n_heads"])
    return 2 * n_attention_layers(cfg) * positions * kv_dim * cache_itemsize


def decode_tick_bytes(cfg: dict, n_slots: int, live_positions: int,
                      cache_itemsize: int = 2) -> int:
    n_mamba = cfg["n_layers"] - n_attention_layers(cfg)
    return (total_params(cfg) * _itemsize(cfg["param_dtype"])
            + n_mamba * selective_scan_bytes(cfg, n_slots, 1)
            + kv_bytes(cfg, live_positions, cache_itemsize))


def chunk_bytes(cfg: dict, n_tok: int, positions: int,
                cache_itemsize: int = 2) -> int:
    n_mamba = cfg["n_layers"] - n_attention_layers(cfg)
    return (total_params(cfg) * _itemsize(cfg["param_dtype"])
            + n_mamba * selective_scan_bytes(cfg, 1, n_tok)
            + kv_bytes(cfg, positions, cache_itemsize))
