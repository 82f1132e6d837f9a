"""The Pallas gated-delta-rule kernel (``ops/kda.py``) in interpret mode on
the CPU, against its plain ``lax.scan`` twin (kept here, not in the
program): the step (one token of many sequences, some of them sitting out),
the walk (many tokens of one sequence, lengths that are no whole tiles of
32), a walk cut in two against the whole, and several sequences of several
tokens.

Tolerance: both sides compute the same float32 expressions token by token;
they differ in the order of the ``dk``-term sums over the key lanes and in
how XLA:CPU and the interpreter contract fused multiply-adds, a few ulps of
values of order 1, which a walk's recurrence carries for some hundred tokens
under a decay below 1: 2e-5 absolute and relative, the state as tightly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simple_distributed_machine_learning_tpu.ops.kda import kda_recurrence

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


@pytest.mark.parametrize("n_tok", [1, 7, 32, 45])
def test_walk_matches_the_scan(n_tok):
    a = _inputs(1, 1, n_tok, 3, 16, 8)
    want_o, want_s = twin(**a)
    got_o, got_s = kda_recurrence(**a)
    np.testing.assert_allclose(got_o, want_o, **TOL)
    np.testing.assert_allclose(got_s, want_s, **TOL)


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
    cut = lambda lo, hi: {  # noqa: E731
        key: val[:, lo:hi] for key, val in a.items() if key != "state"}
    o1, s1 = kda_recurrence(**cut(0, 13), state=a["state"])
    o2, s2 = kda_recurrence(**cut(13, 40), state=s1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], axis=1), whole_o,
                               **TOL)
    np.testing.assert_allclose(s2, whole_s, **TOL)


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
        step = {key: val[:, t:t + 1] for key, val in a.items()
                if key != "state"}
        o_t, s = kda_recurrence(**step, state=s)
        s = s.astype(jnp.bfloat16).astype(jnp.float32)
        o.append(o_t)
    assert not np.allclose(jnp.concatenate(o, axis=1), want_o, **TOL)


def test_shapes_are_refused_by_name():
    a = _inputs(6, 2, 1, 4, 8, 8)
    with pytest.raises(ValueError, match="kda_recurrence: q"):
        kda_recurrence(**{**a, "state": a["state"][:, :2]})
    with pytest.raises(ValueError, match="neither at most 32"):
        kda_recurrence(**_inputs(6, 1, 1, 48, 8, 8))
    with pytest.raises(ValueError, match="live belongs to a step"):
        kda_recurrence(**_inputs(6, 1, 3, 2, 8, 8), live=jnp.ones((1,), bool))
