"""Pallas selective scan: the Mamba-1 recurrence with its state in VMEM.

Per sequence ``n`` and token ``t`` (``Di`` channels, ``S`` states a channel)::

    H_t = exp(delta_t[None, :] * A) * H_{t-1} + (delta_t * x_t)[None, :] * B_t[:, None]
    y_t = (sum_s H_t[s, :] * C_t[s] + D * x_t) * silu(z_t)

``H`` is ``[S, Di]``: the channels lie in the 128-lane dimension and the
``S = 16`` states in two sublane groups, so a block of it is whole vregs
(the transposed ``[Di, S]`` would pad 16 to 128 lanes: eight times the
bytes). The kernel keeps a block of ``H`` on chip and walks the tokens, so
the state crosses HBM once in and once out whatever the length; nothing of
size ``[L, S, Di]`` ever exists.

One kernel for both serving shapes, as ``ops/paged_attention.py`` is one
kernel for ``K = 1`` and ``K`` tokens: the prefill chunk is ``N = 1``
sequence of ``L`` tokens (a grid step walks ``L`` tokens of one sequence),
the decode tick ``N = n_slots`` sequences of ``L = 1`` token (a grid step
takes 8 sequences, so its blocks are whole ``(8, 128)`` tiles). The state
goes in and comes out through one aliased buffer.

On backends other than a TPU the kernel runs in Pallas interpret mode
(``flash_attention._interpret``); its plain ``lax.scan`` twin lives in
``tests/test_selective_scan.py``, not in the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from simple_distributed_machine_learning_tpu.ops.flash_attention import (
    _LANES,
    _interpret,
    _struct,
    _vma_of,
    pltpu,
)

#: channels one inner pass holds in registers: ``[16, 512]`` f32 is 8 vregs
_COLS = 512
#: sequences a decode grid step takes (the f32 sublane quantum)
_ROWS = 8
#: VMEM the pipelined blocks may take before the scoped limit is raised
_VMEM_BUDGET = 12 * 2 ** 20


def _scan_kernel(x_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref, h0_ref,
                 y_ref, h_ref, *, nb: int, n_tok: int, cols: int):
    """One (sequence block, channel block) grid cell.

    ``x_ref`` / ``dt_ref`` / ``z_ref`` / ``y_ref``: ``[1, n_tok * nb, bd]``,
    row ``t * nb + i`` is token ``t`` of the block's sequence ``i``;
    ``b_ref`` / ``c_ref``: ``[1, n_tok * nb, S, 1]`` (a token's ``S`` values
    as a sublane column, so it broadcasts along the lanes);
    ``a_ref``: ``[S, bd]``; ``d_ref``: ``[1, bd]``; ``h0_ref`` / ``h_ref``:
    ``[nb, S, bd]``."""
    bd = x_ref.shape[-1]
    for c0 in range(0, bd, cols):
        cs = slice(c0, c0 + cols)
        a = a_ref[:, cs]
        d = d_ref[:, cs]
        for i in range(nb):
            def token(t, h, i=i, cs=cs, a=a, d=d):
                row = t * nb + i
                at = (0, pl.ds(row, 1), cs)
                x, dt, z = x_ref[at], dt_ref[at], z_ref[at]   # [1, cols]
                h = jnp.exp(dt * a) * h + (dt * x) * b_ref[0, row]
                y = jnp.sum(h * c_ref[0, row], axis=0, keepdims=True) + d * x
                y_ref[at] = y * (z / (1.0 + jnp.exp(-z)))
                return h

            h = h0_ref[i, :, cs]
            # a decode tick's one token is a straight line, not a loop
            h_ref[i, :, cs] = (token(0, h) if n_tok == 1
                               else lax.fori_loop(0, n_tok, token, h))


def _channel_block(di: int, rows: int, nb: int, n_state: int) -> int:
    """The widest lane-multiple divisor of ``di`` whose double-buffered
    blocks (x, delta, z, y and the state in and out) fit the budget."""
    best = min(di, _LANES)
    for bd in range(_LANES, di + 1, _LANES):
        if di % bd == 0 and 8 * bd * (4 * rows + 2 * nb * n_state) \
                <= _VMEM_BUDGET * 2 // 3:
            best = bd
    return best


def selective_scan(x: jax.Array, delta: jax.Array, z: jax.Array,
                   b: jax.Array, c: jax.Array, a: jax.Array, d: jax.Array,
                   h0: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(y [N, L, Di], h [N, S, Di])`` of the recurrence above, float32.

    ``x`` / ``delta`` / ``z``: ``[N, L, Di]``; ``b`` / ``c``: ``[N, L, S]``;
    ``a``: ``[S, Di]`` (the negative ``-exp(A_log)``); ``d``: ``[Di]``;
    ``h0``: ``[N, S, Di]``, the state before the first token (donate it:
    the result's state is written over it). A sequence whose ``delta`` is 0
    keeps its state bit for bit (``exp(0) * H + 0``): how a decode tick
    leaves the slots that sit it out untouched."""
    n, n_tok, di = x.shape
    n_state = a.shape[0]
    if b.shape != (n, n_tok, n_state) or h0.shape != (n, n_state, di):
        raise ValueError(
            f"selective_scan: x {x.shape} wants b/c [{n}, {n_tok}, "
            f"{n_state}] and h0 [{n}, {n_state}, {di}]; got {b.shape}, "
            f"{c.shape}, {h0.shape}")
    # a step over many sequences takes them 8 at a time; a walk over many
    # tokens takes one sequence
    nb = next(k for k in range(_ROWS, 0, -1) if n % k == 0) \
        if n_tok == 1 else 1
    g, rows = n // nb, n_tok * nb
    f32 = jnp.float32

    def rows_of(v):                       # [N, L, ...] -> [G, L * nb, ...]
        v = v.astype(f32).reshape(g, nb, n_tok, *v.shape[2:])
        return jnp.swapaxes(v, 1, 2).reshape(g, rows, *v.shape[3:])

    bd = _channel_block(di, rows, nb, n_state)
    cols = _COLS if bd % _COLS == 0 else bd
    wide = pl.BlockSpec((1, rows, bd), lambda i, j: (i, 0, j))
    column = pl.BlockSpec((1, rows, n_state, 1), lambda i, j: (i, 0, 0, 0))
    state = pl.BlockSpec((nb, n_state, bd), lambda i, j: (i, 0, j))
    # the b/c columns pad their one lane to 128: count them as laid out
    vmem = (8 * bd * (4 * rows + 2 * nb * n_state)
            + 4 * rows * n_state * _LANES * 4)
    params = dict(dimension_semantics=("parallel", "parallel"))
    if vmem > _VMEM_BUDGET:
        params["vmem_limit_bytes"] = min(2 * vmem, 96 * 2 ** 20)
    vma = _vma_of(x, delta, z, h0)
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, nb=nb, n_tok=n_tok, cols=cols),
        grid=(g, di // bd),
        in_specs=[wide, wide, wide, column, column,
                  pl.BlockSpec((n_state, bd), lambda i, j: (0, j)),
                  pl.BlockSpec((1, bd), lambda i, j: (0, j)),
                  state],
        out_specs=[wide, state],
        out_shape=[_struct((g, rows, di), f32, vma),
                   _struct((n, n_state, di), f32, vma)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(**params),
        interpret=_interpret(),
        name="selective_scan",
    )(rows_of(x), rows_of(delta), rows_of(z), rows_of(b)[..., None],
      rows_of(c)[..., None], a.astype(f32), d.astype(f32)[None],
      h0.astype(f32))
    y = jnp.swapaxes(y.reshape(g, n_tok, nb, di), 1, 2)
    return y.reshape(n, n_tok, di), h
