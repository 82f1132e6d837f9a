"""The Pallas gated-delta-rule kernel (``ops/kda.py``) in interpret mode on
the CPU, against its plain ``lax.scan`` twin (kept here, not in the
program): the step (one token of many sequences, some of them sitting out),
the walk (many tokens of one sequence a block of tokens at a time: lengths
on and around the edges of a block and of a sub-block, a decay as strong and
as uneven as the served model draws), a walk cut in two against the whole,
and several sequences of several tokens.

Tolerance: the step computes the twin's float32 expressions in another
order of the ``dk``-term sums; the walk computes the recurrence REARRANGED
(a block's corrections from one triangular system, its decays as ``exp`` of
running sums of ``g`` where the twin multiplies ``exp(g_t)`` token by
token), every product in float32. Both differ from the twin by a few ulps
of values of order 1, which a recurrence carries for some hundred tokens
under a decay below 1: 2e-5 absolute and relative, the state as tightly (the
walk cases read 1e-7 to 2e-6 here). One bfloat16 pass over a block's
products is three decimal digits: the last test shows the tolerance tells
it apart.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simple_distributed_machine_learning_tpu.ops.kda import (
    _BLOCK,
    _SUB,
    kda_recurrence,
)

TOL = dict(rtol=2e-5, atol=2e-5)


def twin(q, k, v, g, beta, state):
    """The recurrence as a ``lax.scan`` over the tokens of each sequence and
    head, line for line as the module's docstring writes it."""
    def one(q, k, v, g, beta, s0):          # [L, dk] .. [L], [dk, dv]
        def step(s, inputs):
            q_t, k_t, v_t, g_t, b_t = inputs
            s = jnp.exp(g_t)[:, None] * s
            s = s + b_t * jnp.outer(k_t, v_t - s.T @ k_t)
            return s, s.T @ q_t

        s, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
        return o, s

    heads = jax.vmap(one, in_axes=(1, 1, 1, 1, 1, 0), out_axes=(1, 0))
    with jax.default_matmul_precision("highest"):
        return jax.vmap(heads)(q, k, v, g, beta, state)


def _inputs(seed, n, n_tok, heads, dk, dv):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    return dict(
        q=unit(jax.random.normal(ks[0], (n, n_tok, heads, dk))) * dk ** -0.5,
        k=unit(jax.random.normal(ks[1], (n, n_tok, heads, dk))),
        v=jax.random.normal(ks[2], (n, n_tok, heads, dv)),
        g=-jax.nn.softplus(jax.random.normal(ks[3], (n, n_tok, heads, dk))),
        beta=jax.nn.sigmoid(jax.random.normal(ks[4], (n, n_tok, heads))),
        state=jax.random.normal(ks[5], (n, heads, dk, dv)))


@pytest.mark.parametrize("n,heads,dk,dv", [(5, 4, 16, 24), (3, 64, 8, 8),
                                           (2, 1, 128, 128)])
def test_step_matches_the_scan(n, heads, dk, dv):
    a = _inputs(0, n, 1, heads, dk, dv)
    want_o, want_s = twin(**a)
    got_o, got_s = kda_recurrence(**a)
    np.testing.assert_allclose(got_o, want_o, **TOL)
    np.testing.assert_allclose(got_s, want_s, **TOL)


@pytest.mark.parametrize("n_tok", [
    1, 7, 32, 45, _SUB - 1, _SUB + 1, _BLOCK - 1, _BLOCK, _BLOCK + 1,
    2 * _BLOCK + 5, 512])
def test_walk_matches_the_scan(n_tok):
    a = _inputs(1, 1, n_tok, 3, 16, 8)
    want_o, want_s = twin(**a)
    got_o, got_s = kda_recurrence(**a)
    np.testing.assert_allclose(got_o, want_o, **TOL)
    np.testing.assert_allclose(got_s, want_s, **TOL)


def _span(a, lo, hi):
    """The tokens ``lo .. hi`` of every input but the state."""
    return {key: val[:, lo:hi] for key, val in a.items() if key != "state"}


def test_several_sequences_of_several_tokens():
    a = _inputs(2, 3, 5, 2, 8, 16)
    want_o, want_s = twin(**a)
    got_o, got_s = kda_recurrence(**a)
    np.testing.assert_allclose(got_o, want_o, **TOL)
    np.testing.assert_allclose(got_s, want_s, **TOL)


def test_a_walk_cut_in_two_is_the_whole_walk():
    """The state carries everything across a chunk boundary."""
    a = _inputs(3, 1, 40, 2, 16, 16)
    whole_o, whole_s = kda_recurrence(**a)
    o1, s1 = kda_recurrence(**_span(a, 0, 13), state=a["state"])
    o2, s2 = kda_recurrence(**_span(a, 13, 40), state=s1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], axis=1), whole_o,
                               **TOL)
    np.testing.assert_allclose(s2, whole_s, **TOL)


def test_a_walk_cut_inside_a_block_is_the_whole_walk():
    """Neither piece is whole blocks, and the cut is no sub-block's edge."""
    n_tok, at = 2 * _BLOCK + 5, _BLOCK + 6
    a = _inputs(7, 1, n_tok, 2, 16, 16)
    whole_o, whole_s = kda_recurrence(**a)
    o1, s1 = kda_recurrence(**_span(a, 0, at), state=a["state"])
    o2, s2 = kda_recurrence(**_span(a, at, n_tok), state=s1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], axis=1), whole_o,
                               **TOL)
    np.testing.assert_allclose(s2, whole_s, **TOL)


def _strong_decay(seed, shape):
    """``g`` as ``kimi-linear-48b-a3b``'s weights draw it: ``-A softplus``
    with ``A`` uniform in (1, 16) a head and the softplus from 1e-3 to
    above 1 a LANE, so that inside one block of tokens some lanes have
    decayed past what float32 can hold (``exp(-88)``) and others hardly
    at all."""
    ka, kx = jax.random.split(jax.random.key(seed))
    a = jax.random.uniform(ka, (shape[2], 1), minval=1.0, maxval=16.0)
    lanes = jnp.linspace(jnp.log(3e-4), jnp.log(1.25), shape[-1])
    return -a * jnp.exp(lanes + 0.3 * jax.random.normal(kx, shape))


def test_a_walk_under_strong_decay_is_finite_and_matches_the_scan():
    a = _inputs(8, 1, 2 * _BLOCK + 5, 4, 16, 8)
    a["g"] = _strong_decay(8, a["g"].shape)
    assert float(a["g"].min()) < -20 and float(a["g"].max()) > -3e-3
    assert float(a["g"][0, :_BLOCK].sum(0).min()) < -88     # exp underflows
    want_o, want_s = twin(**a)
    got_o, got_s = kda_recurrence(**a)
    assert np.isfinite(got_o).all() and np.isfinite(got_s).all()
    np.testing.assert_allclose(got_o, want_o, **TOL)
    np.testing.assert_allclose(got_s, want_s, **TOL)


def test_a_sequence_that_sits_out_keeps_its_state_bit_for_bit():
    a = _inputs(4, 6, 1, 4, 16, 16)
    # negative zeros and denormals too: a state that was multiplied by 1 and
    # had 0 added would lose the sign of a zero
    state = a["state"].at[:, :, 0, :4].set(
        jnp.asarray([-0.0, 0.0, 1e-42, -1e-42], jnp.float32))
    a["state"] = state
    live = jnp.asarray([True, False, True, False, False, True])
    got_o, got_s = kda_recurrence(**a, live=live)
    want_o, want_s = twin(**a)
    bits = lambda x: np.asarray(x).view(np.int32)  # noqa: E731
    np.testing.assert_array_equal(bits(got_s)[~np.asarray(live)],
                                  bits(state)[~np.asarray(live)])
    np.testing.assert_array_equal(np.asarray(got_o)[~np.asarray(live)], 0.0)
    np.testing.assert_allclose(got_s[live], want_s[live], **TOL)
    np.testing.assert_allclose(got_o[live], want_o[live], **TOL)


def test_a_lower_precision_state_is_told_apart():
    """The tolerance above would catch a state carried in bfloat16."""
    a = _inputs(5, 1, 64, 2, 16, 16)
    want_o, _ = twin(**a)
    o, s = [], a["state"]
    for t in range(64):
        o_t, s = kda_recurrence(**_span(a, t, t + 1), state=s)
        s = s.astype(jnp.bfloat16).astype(jnp.float32)
        o.append(o_t)
    assert not np.allclose(jnp.concatenate(o, axis=1), want_o, **TOL)


def _blocked(q, k, v, g, beta, s0, rounded: bool, block: int = 16):
    """One head's walk by the block form of the module's docstring, in
    plain ``jax.numpy`` and in blocks short enough for a mild decay to be
    folded into ``k`` (a copy of the MATH, not of the kernel's sub-blocks):
    ``q`` .. ``g [L, dk]``, ``v [L, dv]``, ``beta [L]``, ``s0 [dk, dv]``.
    ``rounded``: every product's operands pass bfloat16 first, which is
    what ONE pass of the chip's matrix unit would make of them."""
    f32 = jnp.float32
    r = (lambda x: x.astype(jnp.bfloat16).astype(f32)) if rounded else (
        lambda x: x)
    dot = lambda x, y: jnp.dot(r(x), r(y), precision="highest")  # noqa: E731
    s, outs = s0, []
    for lo in range(0, len(q), block):
        qb, kb, vb, gb = (x[lo:lo + block] for x in (q, k, v, g))
        bk = beta[lo:lo + block, None] * kb
        run = jnp.cumsum(gb, axis=0)
        lower = jnp.tril(jnp.ones((len(qb),) * 2, f32))
        kn = bk * jnp.exp(-run)
        a = dot(kb * jnp.exp(run), kn.T) * (lower - jnp.eye(len(qb)))
        aq = dot(qb * jnp.exp(run), kn.T) * lower
        u = jax.scipy.linalg.solve_triangular(
            jnp.eye(len(qb)) + a, vb - dot(kb * jnp.exp(run), s), lower=True)
        outs.append(dot(qb * jnp.exp(run), s) + dot(aq, u))
        s = jnp.exp(run[-1])[:, None] * s + dot(
            (bk * jnp.exp(run[-1] - run)).T, u)
    return jnp.concatenate(outs), s


@pytest.mark.parametrize("rounded", [False, True])
def test_one_bfloat16_pass_over_the_blocks_products_is_told_apart(rounded):
    """The block form in float32 is the recurrence; with its products'
    operands rounded to bfloat16 the tolerance above refuses it, so a walk
    whose products took one pass of the matrix unit would not pass here."""
    a = _inputs(9, 1, 2 * _BLOCK, 2, 16, 16)
    want_o, want_s = twin(**a)
    heads = jax.vmap(lambda *x: _blocked(*x, rounded=rounded),
                     in_axes=(1, 1, 1, 1, 1, 0), out_axes=(1, 0))
    got_o, got_s = heads(*(a[key][0] for key in
                           ("q", "k", "v", "g", "beta", "state")))
    close = (np.allclose(got_o, want_o[0], **TOL)
             and np.allclose(got_s, want_s[0], **TOL))
    assert close == (not rounded)


def test_shapes_are_refused_by_name():
    a = _inputs(6, 2, 1, 4, 8, 8)
    with pytest.raises(ValueError, match="kda_recurrence: q"):
        kda_recurrence(**{**a, "state": a["state"][:, :2]})
    with pytest.raises(ValueError, match="neither at most 32"):
        kda_recurrence(**_inputs(6, 1, 1, 48, 8, 8))
    with pytest.raises(ValueError, match="live belongs to a step"):
        kda_recurrence(**_inputs(6, 1, 3, 2, 8, 8), live=jnp.ones((1,), bool))
