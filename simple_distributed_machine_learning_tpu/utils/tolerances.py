"""Dtype-aware comparison tolerances for attention/decode outputs.

One rule for every comparison of outputs whose K/V round-tripped a storage
dtype (flash kernel vs dense, bf16 caches vs f32, quantized paged blocks vs
wide): the tolerance is a property of the STORAGE dtype, not of the
individual comparison. The tests read it through ``tests/tolerances.py``;
``chip_smoke.py`` holds the chip to the same pins.
"""

import jax.numpy as jnp


def attn_tol(dtype) -> tuple[float, float]:
    """``(rtol, atol)`` for outputs computed through K/V stored as
    ``dtype``. f32 allows accumulation-order ulps only; bf16 allows its
    ~3-decimal-bit rounding through one attention round trip; quantized
    dtypes allow their per-row amax/qmax quantization step."""
    d = jnp.dtype(dtype)
    if d == jnp.dtype(jnp.float32):
        return (1e-5, 1e-5)
    if d == jnp.dtype(jnp.float16):
        return (2e-3, 2e-3)
    if d == jnp.dtype(jnp.bfloat16):
        return (5e-2, 5e-2)
    if d == jnp.dtype(jnp.int8):
        return (6e-2, 6e-2)
    if d.name.startswith("float8"):
        return (1.5e-1, 1.5e-1)
    raise ValueError(f"no pinned attention tolerance for dtype {d.name}")
