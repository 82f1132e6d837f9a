"""Stage packing and wire codec round-trips."""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simple_distributed_machine_learning_tpu.analysis.trace import subjaxprs
from simple_distributed_machine_learning_tpu.parallel.staging import (
    pack_stage_grads,
    pack_stage_params,
    unpack_stage_params,
    wire_decode,
    wire_encode,
)


def test_pack_unpack_roundtrip_heterogeneous():
    p0 = {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones((4,))}
    p1 = [{"w": jnp.full((2, 2), 2.0)}, {"b": jnp.zeros((5,))}]
    buf, metas = pack_stage_params([p0, p1])
    assert buf.shape == (2, 16)  # max(12+4, 4+5) = 16
    r0 = unpack_stage_params(buf[0], metas[0])
    r1 = unpack_stage_params(buf[1], metas[1])
    jax.tree.map(lambda a, b: np.testing.assert_allclose(np.asarray(a),
                                                         np.asarray(b)), p0, r0)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(np.asarray(a),
                                                         np.asarray(b)), p1, r1)


def test_wire_roundtrip():
    x = jnp.arange(24.0).reshape(2, 3, 4)  # batch 2, per-sample (3, 4)
    wire = wire_encode(x, 20)
    assert wire.shape == (2, 20)
    back = wire_decode(wire, (3, 4))
    np.testing.assert_allclose(np.asarray(back), np.asarray(x))
    np.testing.assert_allclose(np.asarray(wire[:, 12:]), 0.0)


# ---- the gradient with respect to the packed row ---------------------------
# unpack_stage_params is differentiated through by every GPipe train step.
# One lax.split transposes to ONE concatenate; a slice per leaf transposed to
# a pad to the row's width per leaf plus an add per pair, O(leaves x row).


def _leafy_trees(n_leaves):
    """Two stages: ``n_leaves`` leaves of mixed rank (a scalar among them)
    and a wider one-leaf stage, so the first stage's row is zero-padded."""
    ks = jax.random.split(jax.random.key(0), n_leaves)
    shapes = [(), (3,), (2, 5), (4, 1, 3), (7,)]
    p0 = {f"l{i:02d}": jax.random.normal(k, shapes[i % len(shapes)])
          for i, k in enumerate(ks)}
    size0 = sum(int(np.prod(a.shape)) for a in p0.values())
    return p0, {"w": jnp.ones((size0 + 13,))}


def _objective(tree, dtype):
    """A function of the leaves whose cotangents all differ."""
    leaves = [a.astype(dtype) for a in jax.tree.leaves(tree)]
    return sum(jnp.sum(jnp.sin(a) * (i + 1.5)).astype(jnp.float32)
               for i, a in enumerate(leaves))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_row_gradient_is_the_packed_leaf_cotangents(dtype):
    p0, p1 = _leafy_trees(11)
    buf, metas = pack_stage_params([p0, p1])
    row, meta, width = buf[0], metas[0], buf.shape[1]
    assert width > meta.total                    # the row is padded
    got = jax.grad(
        lambda r: _objective(unpack_stage_params(r, meta), dtype))(row)
    leaf_cts = jax.grad(lambda t: _objective(t, dtype))(
        unpack_stage_params(row, meta))
    want = pack_stage_grads(leaf_cts, meta, width)
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.count_nonzero(np.asarray(got)) == meta.total   # not vacuous


def _row_wide_eqns(jaxpr, width):
    """Every equation, nested jaxprs included, with an output of the row's
    width: ``(primitive name, ...)``."""
    found = []
    for eqn in jaxpr.eqns:
        if any(getattr(v.aval, "shape", None) == (width,)
               for v in eqn.outvars):
            found.append(eqn.primitive.name)
        for _key, _i, sub in subjaxprs(eqn):
            found += _row_wide_eqns(sub, width)
    return found


@pytest.mark.parametrize("n_leaves", [5, 50])
def test_unpack_backward_writes_the_row_once(n_leaves):
    """The structural pin: differentiating through the unpack must not cost
    a row-wide operation per leaf."""
    p0, p1 = _leafy_trees(n_leaves)
    buf, metas = pack_stage_params([p0, p1])
    meta, width = metas[0], buf.shape[1]
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda r: _objective(unpack_stage_params(r, meta), jnp.bfloat16))
    )(buf[0]).jaxpr
    # the one concatenate of the leaf cotangents, whatever the leaf count:
    # no pad, no add_any
    assert _row_wide_eqns(jaxpr, width) == ["concatenate"]


def _compiled_mlp_engine(n_data, n_micro, batch, **pipeline_kw):
    """The 2-stage GPipe engine's ``loss_and_grads`` over small MLP stages
    of four leaves and more, compiled: ``(step, buf, key, mesh)``."""
    from simple_distributed_machine_learning_tpu import make_mesh
    from simple_distributed_machine_learning_tpu.models import make_mlp_stages
    from simple_distributed_machine_learning_tpu.parallel import Pipeline

    stages, wire_dim, out_dim = make_mlp_stages(
        jax.random.key(0), [9, 23, 17, 11, 5], n_stages=2)
    mesh = make_mesh(n_stages=2, n_data=n_data)
    pipe = Pipeline(stages, mesh, wire_dim, out_dim, n_microbatches=n_micro,
                    **pipeline_kw)
    buf = pipe.init_params()
    assert all(len(m.sizes) >= 4 for m in pipe.metas)
    x = jax.random.normal(jax.random.key(1), (batch, 9))
    y = jax.random.randint(jax.random.key(2), (batch,), 0, 5)
    key = jax.random.key(3)
    step = jax.jit(lambda b, k: pipe.loss_and_grads(b, x, y, k)).lower(
        buf, key).compile()
    return step, buf, key, mesh


@pytest.mark.parametrize("remat", [False, True])
def test_gpipe_engine_has_no_row_wide_pad(remat):
    """The same on the compiled engine: the unpack sits inside the switch
    branch inside the GPipe scan (under jax.checkpoint with remat), and the
    reversed scan must not pad a leaf's cotangent to the row's width."""
    step, buf, key, _ = _compiled_mlp_engine(1, 2, 4, remat=remat)
    width = buf.shape[-1]
    pads = re.findall(
        rf"^.*= f32\[(?:1,1,1,)?{width}\]\S* pad\(.*$", step.as_text(), re.M)
    assert not pads, pads[:3]
    loss, grads = step(buf, key)
    assert np.isfinite(float(loss)) and grads.shape == buf.shape


AllReduce = collections.namedtuple(
    "AllReduce", "computation in_entry shapes groups")


def _all_reduces(hlo_text):
    """Every ``all-reduce`` of a compiled module's text: the computation it
    sits in, whether that is the entry, its result shapes as written, and
    its replica groups as sorted tuples."""
    found, comp, entry = [], None, False
    for line in hlo_text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            comp, entry = head.group(2), bool(head.group(1))
            continue
        op = re.match(r"^\s*(?:ROOT )?\S+ = (.*?) all-reduce(?:-start)?\(", line)
        if not op:
            continue
        groups = re.search(r"replica_groups=\{(\{[\d,{} ]*\})\}", line)
        assert groups, line      # an iota-form group list would need expanding
        found.append(AllReduce(comp, entry, op.group(1), sorted(
            tuple(int(i) for i in g.split(","))
            for g in re.findall(r"\{([\d, ]+)\}", groups.group(1)))))
    return found


@pytest.mark.parametrize("remat", [False, True])
def test_gpipe_engine_reduces_the_row_over_data_once(remat):
    """On the compiled 2-stage x 2-data engine (bf16 compute) the parameter
    row is typed data-varying before the scan, so the one reduction its
    gradient needs over the data axis is ONE all-reduce of the accumulated
    f32 row after the reversed scan. An invariant row met data-varying
    activations inside the switch inside the scan, and the transpose put an
    all-reduce of every leaf's cotangent into each branch of the backward
    loop: the whole stage's gradient crossed the data axis once a scan step."""
    step, buf, key, mesh = _compiled_mlp_engine(
        2, 4, 16, remat=remat, compute_dtype=jnp.bfloat16)
    width = buf.shape[-1]
    # a device's place in the program is its place in the mesh's grid
    # [data, stage]: the data peers of stage s are s and n_stages + s
    data_peers = [(0, 2), (1, 3)]
    assert [[d.id for d in r] for r in mesh.devices[:, :, 0, 0, 0]] == [
        [0, 1], [2, 3]]
    over_data = [r for r in _all_reduces(step.as_text())
                 if r.groups == data_peers]
    inside = [r for r in over_data if not r.in_entry]
    assert not inside, inside[:3]
    row_wide = [r for r in over_data if re.fullmatch(
        rf"f32\[(?:1,1,1,)?{width}\]\S*", r.shapes)]
    assert len(row_wide) == 1, over_data
    # every other reduction over the data axis is a scalar (the loss's)
    assert all(re.fullmatch(r"\(?f32\[\]\S*(?:, f32\[\]\S*)*\)?", r.shapes)
               for r in over_data if r not in row_wide), over_data
    loss, grads = step(buf, key)
    assert np.isfinite(float(loss)) and grads.shape == buf.shape
