"""Pallas gated delta rule: the recurrence of a KDA (Kimi Delta Attention)
layer with its matrix state in VMEM.

Per sequence ``n``, head ``h`` and token ``t``, with the state ``S [dk, dv]``
float32 (key lanes down, value lanes across)::

    S' = diag(alpha_t) S_{t-1}                       alpha_t = exp(g_t) in (0, 1)^dk
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

(the same as ``S_t = (I - beta_t k_t k_t^T) diag(alpha_t) S_{t-1} + beta_t
k_t v_t^T``): the state is decayed by a vector a token, corrected by what it
already predicts for the key, and read by the query. Neither kernel of
``ops/selective_scan.py`` computes it: their state is ``[states, channels]``
under a diagonal decay, with nothing that reads the state back into its own
update.

One Pallas call (``name="kda_recurrence"``) for both serving shapes, as
``ops/selective_scan.py`` has one for its two; the state goes in and comes
out through one aliased buffer.

A STEP (the decode tick: ``N`` sequences, one token each): a grid cell is
one sequence's block of heads, its ``[heads, dk, dv]`` of state in and out
once (2 MiB at 32 heads of 128 x 128: the cell moves 4 MiB, so the step
overhead is nothing beside it); a sequence whose ``live`` is 0 copies its
block through and computes nothing, so its state comes back bit for bit.
What the update needs as COLUMNS (``alpha``, ``k``, ``q`` and ``beta k``,
each ``[dk]`` down the sublanes so that it spreads across the value lanes)
arrives as rows, the 32 heads' four kinds to a ``[128, dk]`` tile, and is
turned once inside the kernel (one 128 x 128 transpose at ``dk`` 128). A
column laid out in HBM (one value a sublane row, the lanes padded from 1 to
128) would be as many bytes as the state itself. ``v`` and ``o`` are rows
as they come.

A WALK (the prefill chunk: one sequence, ``L`` tokens) takes a BLOCK of 64
tokens at a time, as matrix products. A grid cell is a block of heads and
one block of tokens; ``q``, ``k``, ``g``, ``beta k``, ``v`` and ``o`` are
ROWS by head (``[heads, L, .]``, no tile is stacked and nothing transposed
on the way in); the state block stays in VMEM over the token axis
(``"arbitrary"``) and crosses HBM once in and once out whatever the length.
With ``G_t = g_1 + .. + g_t`` the running log-decay inside the block (a
value a token and LANE, never above 0) and rows ``K, Q, G [64, dk]``, ``V
[64, dv]``, from the state ``S_0`` before the block::

    A[t, j]  = sum_d K[t,d] beta_j K[j,d] exp(G[t,d] - G[j,d])      j <  t
    Aq[t, j] = sum_d Q[t,d] beta_j K[j,d] exp(G[t,d] - G[j,d])      j <= t
    U    = (I + A)^-1 (V - (K exp(G)) S_0)            [64, dv]
    O    = (Q exp(G)) S_0 + Aq U                      [64, dv]
    S_64 = diag(exp(G_64)) S_0 + (beta K exp(G_64 - G))^T U

which is the recurrence rearranged and nothing else: row ``t`` of ``U`` is
the correction ``v_t - S'^T k_t`` of token ``t``. EVERY EXPONENT IS OF A
NUMBER ``<= 0``. Folding ``exp(G)`` into one side and ``exp(-G)`` into the
other, so that ``A`` were one product, is not safe here: the served
model's decay passes ``exp(-88)`` on some lanes well inside a block. So
the block is cut into four SUB-BLOCKS of 16 tokens, and ``G`` is held as
the running sum inside a sub-block (a product with a 0/1 matrix; sums of at
most 16 terms, so nothing large cancels) plus whole sub-blocks' sums. For
rows of sub-block ``I`` against columns of EARLIER sub-blocks both sides
are normalised at ``I``'s start, ``exp(G_t - G_I) exp(G_I - G_j)``, two
factors ``<= 1`` and a matrix product of two scaled row blocks. Inside a
sub-block a token's pairs are formed on the vector unit, one token ``t`` at
a time against the (at most 16) rows before it: ``exp(min(G_t - G_j, 0))``
(the rows after ``t`` are formed too and never used), a lane reduction to
a COLUMN over ``j``, which is at once what weighs the rows of ``U`` found
so far: forward substitution, ``u_t = r_t - sum_j A[t, j] u_j``, with no
triangular matrix inverted and nothing transposed. The query's columns
``Aq[t, .]`` are kept and meet ``U`` in one product at the block's end. An
``exp`` that underflows to 0 is the true value. Eight heads go through one
pass of the loop body together (``[8, ., .]`` operands, the products
batched by head): one head's substitution is a chain of 64 dependent
steps, and eight chains fill the slots one leaves empty (the compiler's
schedule: 17.8 instruction bundles a token and head at eight heads, 35 at
one; 135 for the token-by-token walk this replaced, PR 50).

THE PRODUCTS KEEP FLOAT32: every ``dot_general`` of the walk asks for
``Precision.HIGHEST``, which Mosaic lowers to six bfloat16 passes over
operands it splits in three. At default precision Mosaic multiplies
float32 operands in ONE bfloat16 pass (``ops/paged_attention.py``, measured
in PR 43): three decimal digits in every term of a recurrence whose state
is float32 and whose token-by-token form is float32 throughout, a
different result and not a faster one (``tests/test_kda.py`` shows the
tolerance tells it apart). ``exp``, the sums, the masks and the state are
float32. A ragged tail is padded with ``g = 0``, ``k = 0``, ``beta = 0``
(it decays by 1 and adds nothing); a walk shorter than a block is one
padded block. There is no other walk.

On backends other than a TPU the kernel runs in Pallas interpret mode
(``flash_attention._interpret``); its plain ``lax.scan`` twin lives in
``tests/test_kda.py`` and in ``bench_cells/reference/kimi_linear.py``, not
in the program.
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from simple_distributed_machine_learning_tpu.ops.flash_attention import (
    _interpret,
    _struct,
    _vma_of,
    pltpu,
)

#: a step's heads whose four kinds of column share one tile: 4 x 32 rows
#: turn into the 128 lanes of ``[dk, 128]``
_PACK = 32
#: the float32 sublane quantum: rows that one register holds
_ROWS = 8
#: the most bytes of state one grid cell holds
_STATE_BLOCK_BYTES = 2 * 2 ** 20
#: tokens a walk's grid cell takes as matrix products, the sub-blocks inside
#: which a token's pairs are formed on the vector unit, and the most heads
#: that go through one pass of the loop body together (chosen on the chip,
#: PR 50: 0.38 ms a walk of 512 x 32 x 128 at 8 heads, 0.41 at 4, 0.42 at
#: 2; sub-blocks of 8 are no faster)
_BLOCK = 64
_SUB = 16
_TOGETHER = 8


def _token(s, cols, unit: int, v_row):
    """One token of one head: ``s [dk, dv]`` and the unit's four columns of
    ``cols [dk, 4 * _PACK]`` (``alpha``, ``k``, ``q``, ``beta k`` at lanes
    ``unit``, ``_PACK + unit``, ...) with ``v_row [1, dv]`` -> ``(s, o [1,
    dv])``."""
    col = lambda kind: cols[:, kind * _PACK + unit:  # noqa: E731
                            kind * _PACK + unit + 1]
    s = s * col(0)
    err = v_row - jnp.sum(s * col(1), axis=0, keepdims=True)
    s = s + col(3) * err
    return s, jnp.sum(s * col(2), axis=0, keepdims=True)


def _columns(x):
    """``x [4, _PACK, dk]`` (the four kinds' rows) -> ``[dk, 4 * _PACK]``."""
    return x.reshape(4 * _PACK, x.shape[-1]).T


def _step_kernel(live_ref, x_ref, v_ref, h0_ref, o_ref, h_ref, *, hb: int):
    """One (sequence, head block) cell of a step. ``live_ref``: ``[N]``
    int32 in SMEM; ``x_ref``: ``[1, 1, 4, _PACK, dk]``, unit ``i`` the
    block's head ``i``; ``v_ref`` / ``o_ref``: ``[1, hb, dv]``; ``h0_ref`` /
    ``h_ref``: ``[1, hb, dk, dv]``."""
    live = live_ref[pl.program_id(0)] != 0

    @pl.when(live)
    def _advance():
        cols = _columns(x_ref[0, 0])
        for h in range(hb):
            h_ref[0, h], o_ref[0, h:h + 1, :] = _token(
                h0_ref[0, h], cols, h, v_ref[0, h:h + 1, :])

    @pl.when(jnp.logical_not(live))
    def _sit_out():
        h_ref[...] = h0_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def _dot(a, b, contract=(2, 1)):
    """Float32 products a head, ``[m, .., ..]`` by ``[m, .., ..]`` over the
    axes ``contract``: on the chip ``HIGHEST`` is what keeps Mosaic from
    rounding the operands to bfloat16."""
    return lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((0,), (0,))),
        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def _block(q, k, g, bk, v, s, sums):
    """A block of ``_BLOCK`` tokens of ``m`` heads: rows ``q`` / ``k`` /
    ``g`` / ``bk [m, _BLOCK, dk]``, ``v [m, _BLOCK, dv]``, ``s [m, dk, dv]``
    -> ``(o [m, _BLOCK, dv], s)``. ``sums [_BLOCK, _BLOCK]``: the 0/1
    matrix whose product with ``g`` is the running sum inside each
    sub-block, a token's own ``g`` and those before it."""
    n_sub, c = _BLOCK // _SUB, _SUB

    def cut(a, i, j=None):      # sub-block i of the rows, or sub-blocks i .. j
        return a[:, i * c:(i + 1 if j is None else j) * c]

    rows = functools.partial(jnp.concatenate, axis=1)
    before = jnp.minimum(
        _dot(jnp.broadcast_to(sums, (len(g), *sums.shape)), g), 0.0)
    whole = [cut(before, i)[:, c - 1:] for i in range(n_sub)]   # [m, 1, dk]
    after = rows([jnp.minimum(whole[i] - cut(before, i), 0.0)
                  for i in range(n_sub)])
    # the log-decay from the block's start to a sub-block's start (the last
    # entry: to the block's end), and from a sub-block's end to the block's
    none = jnp.zeros_like(whole[0])
    head = list(itertools.accumulate(whole, initial=none))
    tail = list(itertools.accumulate(whole[:0:-1], initial=none))[::-1]
    from_start = jnp.exp(rows([cut(before, i) + head[i]
                               for i in range(n_sub)]))
    to_end = jnp.exp(rows([cut(after, i) + tail[i] for i in range(n_sub)]))
    carried = _dot(rows([k * from_start, q * from_start]), s)
    rest, o_rows = v - carried[:, :_BLOCK], carried[:, _BLOCK:]

    row = lax.broadcasted_iota(jnp.int32, (1, c, 1), 1)
    lane = lax.broadcasted_iota(jnp.int32, (1, 1, _BLOCK), 2)
    u_subs, reads, reach, o_subs = [], [], [], []
    for i in range(n_sub):
        k_i, q_i, bk_i, p_i, rest_i, o_i = (
            cut(a, i) for a in (k, q, bk, before, rest, o_rows))
        if i:
            # earlier sub-blocks: both sides normalised at this one's start
            reach = [r + whole[i - 1] for r in reach] + [cut(after, i - 1)]
            left = jnp.exp(p_i)
            pairs = _dot(rows([k_i * left, q_i * left]),
                         cut(bk, 0, i) * jnp.exp(rows(reach)), (2, 2))
            both = _dot(pairs, rows(u_subs))
            rest_i, o_i = rest_i - both[:, :c], o_i + both[:, c:]
        # inside the sub-block, a token at a time: token t against those
        # up to it (a column over them), the correction it leaves, and the
        # column its query reads them by, kept for one product at the end;
        # rows of u_i from t on are still zero, so no column needs a mask
        u_i = jnp.zeros_like(rest_i)
        read = jnp.zeros((len(g), c, _BLOCK), jnp.float32)
        for t in range(c):
            m = -(-(t + 1) // _ROWS) * _ROWS       # the rows that hold 0..t
            at = slice(t, t + 1)
            w = bk_i[:, :m] * jnp.exp(
                jnp.minimum(p_i[:, at] - p_i[:, :m], 0.0))
            a = jnp.sum(w * k_i[:, at], axis=2, keepdims=True)
            aq = jnp.sum(w * q_i[:, at], axis=2, keepdims=True)
            u_t = rest_i[:, at] - jnp.sum(a * u_i[:, :m], axis=1,
                                          keepdims=True)
            u_i = jnp.where(row == t, u_t, u_i)
            read = rows([jnp.where(
                (lane == i * c + t) & (row[:, :m] <= t), aq, read[:, :m]),
                *([read[:, m:]] if m < c else [])])
        u_subs.append(u_i)
        o_subs.append(o_i)
        reads.append(read)
    u = rows(u_subs)
    o_rows = rows(o_subs) + _dot(rows(reads), u, (1, 1))
    # the state's decay runs down its sublanes: the row of lanes is turned
    decay = jnp.swapaxes(jnp.broadcast_to(
        jnp.exp(head[-1]), (len(g), s.shape[2], s.shape[1])),
        1, 2)
    s = decay * s + _dot(bk * to_end, u, (1, 1))
    return o_rows, s


def _walk_kernel(q_ref, k_ref, g_ref, bk_ref, v_ref, h0_ref, o_ref, h_ref):
    """One (head block, ``_BLOCK`` tokens) cell of a walk. ``q_ref`` /
    ``k_ref`` / ``g_ref`` / ``bk_ref``: ``[hb, _BLOCK, dk]``; ``v_ref`` /
    ``o_ref``: ``[hb, _BLOCK, dv]``; ``h0_ref`` / ``h_ref``: ``[1, hb, dk,
    dv]``, the latter kept from one token block to the next."""
    @pl.when(pl.program_id(1) == 0)
    def _first():
        h_ref[...] = h0_ref[...]

    at = lax.broadcasted_iota(jnp.int32, (_BLOCK, _BLOCK), 0)
    other = lax.broadcasted_iota(jnp.int32, (_BLOCK, _BLOCK), 1)
    sums = jnp.where((at // _SUB == other // _SUB) & (other <= at), 1.0, 0.0)
    hb = q_ref.shape[0]
    m = math.gcd(hb, _TOGETHER)

    def heads(i, _):
        at = pl.ds(i * m, m)
        o_ref[at], h_ref[0, at] = _block(
            q_ref[at], k_ref[at], g_ref[at], bk_ref[at], v_ref[at],
            h_ref[0, at], sums)

    lax.fori_loop(0, hb // m, heads, None)


def _tiles(g, k, q, bk, units: int):
    """The four kinds ``[..., units, dk]`` as tiles ``[..., ceil(units /
    _PACK), 4, _PACK, dk]``: ``alpha = exp(g)``, ``k``, ``q``, ``beta k``;
    the units past the real ones decay by 1 and add nothing."""
    pad = -units % _PACK
    kinds = []
    for i, a in enumerate((g, k, q, bk)):
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 2) + [(0, pad), (0, 0)])
        a = jnp.exp(a) if i == 0 else a
        kinds.append(a.reshape(*a.shape[:-2], -1, _PACK, a.shape[-1]))
    return jnp.stack(kinds, axis=-3)


def _call(kernel, grid, in_specs, out_specs, out_shape, state_at: int,
          semantics, args):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, input_output_aliases={state_at: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=8 * _STATE_BLOCK_BYTES + 16 * 2 ** 20),
        interpret=_interpret(), name="kda_recurrence")(*args)


def kda_recurrence(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                   beta: jax.Array, state: jax.Array,
                   live: jax.Array | None = None
                   ) -> tuple[jax.Array, jax.Array]:
    """``(o [N, L, H, dv], state [N, H, dk, dv])`` of the recurrence above,
    float32.

    ``q`` / ``k`` / ``g``: ``[N, L, H, dk]`` (the query already scaled, ``g``
    the LOG of the decay, ``<= 0``); ``v``: ``[N, L, H, dv]``; ``beta``:
    ``[N, L, H]``; ``state``: ``[N, H, dk, dv]`` float32, the state before
    the first token (donate it: the result's state is written over it).
    ``live [N]`` (a step, ``L = 1``, alone): the sequences that advance; the
    others' state comes back bit for bit and their ``o`` is zero. ``H`` is at
    most 32 or a multiple of 32. Several sequences of several tokens are
    walked one sequence after the other."""
    f32 = jnp.float32
    n, n_tok, heads, dk = q.shape
    dv = v.shape[-1]
    if (k.shape != q.shape or g.shape != q.shape
            or v.shape != (n, n_tok, heads, dv)
            or beta.shape != (n, n_tok, heads)
            or state.shape != (n, heads, dk, dv)):
        raise ValueError(
            f"kda_recurrence: q {q.shape} wants k and g alike, v [{n}, "
            f"{n_tok}, {heads}, dv], beta [{n}, {n_tok}, {heads}] and state "
            f"[{n}, {heads}, {dk}, dv]; got k {k.shape}, g {g.shape}, v "
            f"{v.shape}, beta {beta.shape}, state {state.shape}")
    if heads > _PACK and heads % _PACK:
        raise ValueError(f"kda_recurrence: {heads} heads are neither at most "
                         f"{_PACK} nor a multiple of it")
    if live is not None and n_tok != 1:
        raise ValueError("kda_recurrence: live belongs to a step of one "
                         "token a sequence")
    q, k, v, g, state = (a.astype(f32) for a in (q, k, v, g, state))
    bk = beta.astype(f32)[..., None] * k
    # the heads of one cell: all of them up to 32, and no more state than
    # the budget (a divisor of the heads, so that no block is ragged)
    hb = next(h for h in range(min(heads, _PACK), 0, -1)
              if heads % h == 0 and (h == 1 or h * dk * dv * 4
                                     <= _STATE_BLOCK_BYTES))
    if n_tok == 1:
        return _step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], bk[:, 0], state,
                     live, hb)
    outs, states = zip(*(
        _walk(q[i], k[i], v[i], g[i], bk[i], state[i:i + 1], hb)
        for i in range(n)))
    return jnp.stack(outs), jnp.concatenate(states)


def _step(q, k, v, g, bk, state, live, hb: int):
    n, heads, dk = q.shape
    dv = v.shape[-1]
    groups = heads // hb

    def by_group(a):        # [N, H, dk] -> [N, groups, hb, dk]
        return a.reshape(n, groups, hb, dk)

    x = _tiles(*(by_group(a) for a in (g, k, q, bk)), hb)[:, :, 0]
    live = (jnp.ones((n,), jnp.int32) if live is None
            else live.astype(jnp.int32))
    vma = _vma_of(q, v, state)
    rows = pl.BlockSpec((1, hb, dv), lambda i, j: (i, j, 0))
    block = pl.BlockSpec((1, hb, dk, dv), lambda i, j: (i, j, 0, 0))
    o, state = _call(
        functools.partial(_step_kernel, hb=hb), (n, groups),
        [pl.BlockSpec(memory_space=pltpu.SMEM),
         pl.BlockSpec((1, 1, 4, _PACK, dk), lambda i, j: (i, j, 0, 0, 0)),
         rows, block],
        [rows, block],
        [_struct((n, heads, dv), jnp.float32, vma),
         _struct(state.shape, jnp.float32, vma)],
        3, ("parallel", "parallel"), (live, x, v, state))
    return o[:, None], state


# a program's layers share ONE trace and lowering of the walk (its body is
# 64 unrolled token steps: traced a layer it was 0.6 s of set-up each, PR 50)
@functools.partial(jax.jit, static_argnames=("hb",))
def _walk(q, k, v, g, bk, state, hb: int):
    n_tok, heads, dk = q.shape
    dv = v.shape[-1]
    # rows by head; the tokens past the last decay by 1 and add nothing
    q, k, g, bk, v = (
        jnp.pad(jnp.swapaxes(a, 0, 1), ((0, 0), (0, -n_tok % _BLOCK), (0, 0)))
        for a in (q, k, g, bk, v))
    vma = _vma_of(q, v, state)

    def rows(d):
        return pl.BlockSpec((hb, _BLOCK, d), lambda i, j: (i, j, 0))

    block = pl.BlockSpec((1, hb, dk, dv), lambda i, j: (0, i, 0, 0))
    o, state = _call(
        _walk_kernel, (heads // hb, v.shape[1] // _BLOCK),
        [rows(dk)] * 4 + [rows(dv), block],
        [rows(dv), block],
        [_struct(v.shape, jnp.float32, vma),
         _struct(state.shape, jnp.float32, vma)],
        5, ("parallel", "arbitrary"), (q, k, g, bk, v, state))
    return jnp.swapaxes(o[:, :n_tok], 0, 1), state
