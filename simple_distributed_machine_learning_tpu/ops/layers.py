"""Core NN layer ops as pure functions (explicit params, explicit RNG).

Capability parity with the reference's layer set — ``Conv2d``, ``Linear``,
``Dropout2d``, ``F.relu``, ``F.max_pool2d``, ``F.dropout``
(``/root/reference/simple_distributed.py:29-31,:42-46,:63-64,:75``) — rebuilt
TPU-first:

- convs run in NHWC / HWIO layout (the TPU-preferred layout; XLA tiles the
  contraction onto the MXU without transposes);
- linear weights are stored ``[in, out]`` so ``x @ w`` is a row-major matmul;
- dropout takes an explicit PRNG key and a ``deterministic`` flag instead of
  torch's global RNG + implicit ``module.training`` state (the reference's eval
  path famously leaves worker-side dropout on — ``simple_distributed.py:75``
  with ``model.eval()`` never crossing RPC at ``:120``; here eval is simply
  ``deterministic=True``);
- initializers reproduce torch's defaults (kaiming-uniform with a=sqrt(5),
  i.e. U(-1/sqrt(fan_in), 1/sqrt(fan_in)) for both weight and bias) so loss
  curves are distributionally comparable with the reference.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax


def _torch_uniform_bound(fan_in: int) -> float:
    # torch nn.Linear / nn.Conv2d default init: kaiming_uniform_(a=sqrt(5))
    # reduces to U(-1/sqrt(fan_in), +1/sqrt(fan_in)); bias uses the same bound.
    return 1.0 / math.sqrt(fan_in)


def linear_init(key: jax.Array, in_features: int, out_features: int,
                dtype=jnp.float32) -> dict:
    """Params for a dense layer: ``{'w': [in, out], 'b': [out]}``."""
    kw, kb = jax.random.split(key)
    bound = _torch_uniform_bound(in_features)
    return {
        "w": jax.random.uniform(kw, (in_features, out_features), dtype,
                                minval=-bound, maxval=bound),
        "b": jax.random.uniform(kb, (out_features,), dtype,
                                minval=-bound, maxval=bound),
    }


def linear(params: dict, x: jax.Array) -> jax.Array:
    """``x @ w + b``. x: [..., in] -> [..., out]."""
    return jnp.matmul(x, params["w"]) + params["b"]


def conv2d_init(key: jax.Array, in_channels: int, out_channels: int,
                kernel_size: int | Sequence[int], dtype=jnp.float32) -> dict:
    """Params for a 2-D conv in HWIO layout: ``{'w': [kh, kw, in, out], 'b': [out]}``."""
    if isinstance(kernel_size, int):
        kh = kw = kernel_size
    else:
        kh, kw = kernel_size
    kkey, bkey = jax.random.split(key)
    fan_in = in_channels * kh * kw
    bound = _torch_uniform_bound(fan_in)
    return {
        "w": jax.random.uniform(kkey, (kh, kw, in_channels, out_channels), dtype,
                                minval=-bound, maxval=bound),
        "b": jax.random.uniform(bkey, (out_channels,), dtype,
                                minval=-bound, maxval=bound),
    }


def conv2d(params: dict, x: jax.Array, stride: int = 1,
           padding: str = "VALID") -> jax.Array:
    """2-D convolution, NHWC activations / HWIO weights (TPU-native layout).

    x: [N, H, W, C_in] -> [N, H', W', C_out]. The reference's convs are NCHW
    torch modules (``simple_distributed.py:29-30``); NHWC is the layout the TPU
    MXU wants, so the framework standardizes on it end to end.
    """
    y = lax.conv_general_dilated(
        x, params["w"],
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y + params["b"]


def max_pool2d(x: jax.Array, window: int = 2, stride: int | None = None) -> jax.Array:
    """Max pooling over H, W of an NHWC tensor (``F.max_pool2d`` equivalent)."""
    stride = window if stride is None else stride
    return lax.reduce_window(
        x, -jnp.inf, lax.max,
        window_dimensions=(1, window, window, 1),
        window_strides=(1, stride, stride, 1),
        padding="VALID",
    )


def relu(x: jax.Array) -> jax.Array:
    return jax.nn.relu(x)


def layer_norm_init(d: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layer_norm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """LayerNorm over the trailing feature axis. ``params["bias"]`` may be
    absent (a bias-free norm, ``models/cohere2.py``): the result is then
    ``(x - mean) / sqrt(var + eps) * scale`` and nothing is added."""
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps) * params["scale"]
    return y + params["bias"] if "bias" in params else y


def embedding_init(key: jax.Array, vocab: int, d: int,
                   dtype=jnp.float32) -> jax.Array:
    """Token-embedding table [vocab, d] (normal 0.02, GPT convention)."""
    return 0.02 * jax.random.normal(key, (vocab, d), dtype)


def embedding_lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    return jnp.take(table, ids, axis=0)


def dropout(key: jax.Array, x: jax.Array, rate: float = 0.5,
            deterministic: bool = False) -> jax.Array:
    """Inverted dropout (``F.dropout`` equivalent, explicit key & mode)."""
    if deterministic or rate == 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0)


def dropout2d(key: jax.Array, x: jax.Array, rate: float = 0.5,
              deterministic: bool = False) -> jax.Array:
    """Channel dropout (``nn.Dropout2d`` equivalent): zeroes whole channels.

    x is NHWC, so the mask is drawn per (sample, channel) and broadcast over
    H and W — same semantics as torch's NCHW Dropout2d.
    """
    if deterministic or rate == 0.0:
        return x
    keep = 1.0 - rate
    n, _, _, c = x.shape
    mask = jax.random.bernoulli(key, keep, (n, 1, 1, c))
    return jnp.where(mask, x / keep, 0.0)


def rms_norm(weight: jax.Array, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm over the trailing feature axis, computed in float32:
    ``weight * x / sqrt(mean(x**2) + eps)`` (no mean subtraction, no bias)."""
    x = x.astype(jnp.float32)
    return (x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * weight.astype(jnp.float32))


def matmul_acc32(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x @ w`` with both operands in the WEIGHT's dtype and a float32
    accumulator: bfloat16 weights are read as stored (half the HBM bytes of
    an upcast copy), float32 weights give the plain float32 product."""
    return jnp.matmul(x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def gated_mlp(params: dict, x: jax.Array) -> jax.Array:
    """SwiGLU feed-forward, no biases: ``down(silu(gate x) * (up x))`` with
    ``params = {'gate': [d, ff], 'up': [d, ff], 'down': [ff, d]}``."""
    mid = jax.nn.silu(matmul_acc32(x, params["gate"])) * matmul_acc32(
        x, params["up"])
    return matmul_acc32(mid, params["down"])


def rotary(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
           rotated: int | None = None, interleaved: bool = False) -> jax.Array:
    """Rotary position embedding over the first ``rotated`` lanes of every
    head (the whole head where ``None``; a model with a
    ``partial_rotary_factor`` rotates that share and passes the rest
    through), rotate-half convention (the two halves of the rotated lanes
    are the rotation's pairs): ``x [..., T, H, dh]`` at ``positions [...,
    T]`` becomes ``x * cos + (-x2, x1) * sin`` there, with angle ``positions
    * theta ** (-2i / rotated)`` for pair ``i``. ``interleaved``
    (``rope_gptj``): pair ``i`` is the NEIGHBOURING lanes ``(2i, 2i + 1)``
    instead of ``(i, i + rotated / 2)``, same angles. Float32; no scaling of
    the frequencies."""
    dh = x.shape[-1]
    rotated = dh if rotated is None else rotated
    if not 2 <= rotated <= dh or rotated % 2:
        raise ValueError(
            f"rotary: rotated={rotated} must be an even number of the "
            f"head's {dh} lanes")
    half = rotated // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * inv    # [..., T, r/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x = x.astype(jnp.float32)
    if interleaved:
        pairs = x[..., :rotated].reshape(*x.shape[:-1], half, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        parts = [jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).reshape(*x.shape[:-1], rotated)]
    else:
        x1, x2 = x[..., :half], x[..., half:rotated]
        parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if rotated < dh:
        parts.append(x[..., rotated:])
    return jnp.concatenate(parts, axis=-1)
