"""The Pallas selective-scan kernels (``ops/selective_scan.py``) in interpret
mode on the CPU, against their plain ``lax.scan`` twins (kept here, not in
the program): the Mamba-1 recurrence (a decay per state and channel, one
``B`` / ``C`` for all channels, the gate inside) and the Mamba-2 one through
the same entry (one decay a channel, ``B`` / ``C`` by group, no gate).

Tolerance: both sides compute the same float32 expressions token by token;
they differ only in how XLA:CPU and the interpreter contract ``exp`` / fused
multiply-adds and in the order of the 16-term sum over the states, a few
ulps of values of order 1-10: 2e-5 absolute and relative. The state is
compared as tightly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simple_distributed_machine_learning_tpu.ops.selective_scan import (
    selective_scan,
)

S = 16
TOL = dict(rtol=2e-5, atol=2e-5)


def twin(x, delta, z, b, c, a, d, h0):
    """The recurrence as a ``lax.scan`` over the tokens of each sequence."""
    def one(x, delta, z, b, c, h0):
        def step(h, inputs):
            x_t, dt, z_t, b_t, c_t = inputs
            h = jnp.exp(dt[None] * a) * h + (dt * x_t)[None] * b_t[:, None]
            y = (h * c_t[:, None]).sum(0) + d * x_t
            return h, y * jax.nn.silu(z_t)

        h, y = jax.lax.scan(step, h0, (x, delta, z, b, c))
        return y, h

    return jax.vmap(one)(x, delta, z, b, c, h0)


def twin_grouped(x, delta, z, b, c, a, d, h0):
    """The second recurrence as a ``lax.scan``: channel ``i`` reads the
    ``B_t`` / ``C_t`` of group ``i // (Di / G)``."""
    del z
    width = x.shape[-1] // b.shape[2]

    def one(x, delta, b, c, h0):
        def step(h, inputs):
            x_t, dt, b_t, c_t = inputs
            b_i = jnp.repeat(b_t.T, width, axis=1)          # [S, Di]
            c_i = jnp.repeat(c_t.T, width, axis=1)
            h = jnp.exp(dt * a)[None] * h + (dt * x_t)[None] * b_i
            return h, (h * c_i).sum(0) + d * x_t

        h, y = jax.lax.scan(step, h0, (x, delta, b, c))
        return y, h

    return jax.vmap(one)(x, delta, b, c, h0)


G, S2 = 2, 32       # the grouped cases: 2 groups of 128 channels, 32 states


def _grouped_inputs(seed, n, n_tok, di):
    ks = jax.random.split(jax.random.key(seed), 8)
    return dict(
        x=jax.random.normal(ks[0], (n, n_tok, di)),
        delta=jax.nn.softplus(jax.random.normal(ks[1], (n, n_tok, di)) - 2),
        z=None,
        b=jax.random.normal(ks[3], (n, n_tok, G, S2)),
        c=jax.random.normal(ks[4], (n, n_tok, G, S2)),
        a=-jnp.exp(jax.random.normal(ks[5], (di,))),
        d=jax.random.normal(ks[6], (di,)),
        h0=jax.random.normal(ks[7], (n, S2, di)))


def _inputs(seed, n, n_tok, di):
    ks = jax.random.split(jax.random.key(seed), 8)
    return dict(
        x=jax.random.normal(ks[0], (n, n_tok, di)),
        delta=jax.nn.softplus(jax.random.normal(ks[1], (n, n_tok, di)) - 2),
        z=jax.random.normal(ks[2], (n, n_tok, di)),
        b=jax.random.normal(ks[3], (n, n_tok, S)),
        c=jax.random.normal(ks[4], (n, n_tok, S)),
        a=-jnp.exp(jax.random.normal(ks[5], (S, di))),
        d=jax.random.normal(ks[6], (di,)),
        h0=jax.random.normal(ks[7], (n, S, di)))


scan = jax.jit(selective_scan)
FAMILY = {"mamba1": (_inputs, twin), "mamba2-groups": (_grouped_inputs,
                                                       twin_grouped)}


@pytest.mark.parametrize("n,n_tok", [(16, 1), (5, 1), (3, 7), (1, 128)])
@pytest.mark.parametrize("family", list(FAMILY))
def test_kernel_matches_the_scan_twin(family, n, n_tok):
    """Both recurrences at both serving shapes: the decode shape (many
    sequences, one token: 8 to a grid step, and a count 8 does not divide),
    a ragged walk and a long one."""
    inputs, twin = FAMILY[family]
    v = inputs(n_tok, n, n_tok, 256)
    y, h = scan(**v)
    y_ref, h_ref = twin(**v)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), **TOL)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), **TOL)


@pytest.mark.parametrize("family", list(FAMILY))
def test_one_long_scan_equals_two_chunks_carrying_state(family):
    """What chunked prefill relies on: the state a chunk returns is all the
    next chunk needs. Tighter than against the twin: the same kernel runs
    the same operations on the same numbers, only cut at token 48."""
    v = FAMILY[family][0](3, 2, 80, 384 if family == "mamba1" else 512)
    y, h = scan(**v)
    cut = lambda t, lo, hi: {k: (a[:, lo:hi] if k in "x delta z b c".split()  # noqa: E731
                                 and a is not None else a)
                             for k, a in t.items()}
    y1, h1 = scan(**cut(v, 0, 48))
    y2, h2 = scan(**dict(cut(v, 48, 80), h0=h1))
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h), rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("family", list(FAMILY))
def test_a_sequence_with_delta_zero_keeps_its_state_bit_for_bit(family):
    """How a decode tick leaves the slots that sit it out untouched."""
    v = FAMILY[family][0](4, 16, 1, 256)
    still = np.arange(16) % 3 == 0
    v["delta"] = jnp.where(still[:, None, None], 0.0, v["delta"])
    _, h = scan(**v)
    h, h0 = np.asarray(h), np.asarray(v["h0"])
    assert np.array_equal(h[still], h0[still])
    assert not np.array_equal(h[~still], h0[~still])


@pytest.mark.parametrize("family", list(FAMILY))
def test_mismatched_shapes_are_refused_by_name(family):
    v = FAMILY[family][0](5, 2, 3, 256)
    with pytest.raises(ValueError, match="selective_scan"):
        selective_scan(**dict(v, h0=v["h0"][:1]))


def test_the_error_names_both_sets_of_shapes_the_entry_accepts():
    v, g = _inputs(5, 2, 3, 256), _grouped_inputs(5, 2, 3, 256)
    with pytest.raises(ValueError, match=r"by group.*no z"):
        selective_scan(**dict(v, z=None))       # the first without its gate
    with pytest.raises(ValueError, match="no z"):
        selective_scan(**dict(g, z=g["x"]))     # the second with one
    with pytest.raises(ValueError, match="a \\[256\\]"):
        selective_scan(**dict(g, a=v["a"]))     # a decay per state
    with pytest.raises(ValueError, match="multiple of 128"):
        selective_scan(**dict(g, b=jnp.zeros((2, 3, 4, S2)),
                              c=jnp.zeros((2, 3, 4, S2))))
