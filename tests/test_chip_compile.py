"""The Pallas kernels of the main path, compiled by the TPU compiler for a
described (not attached) ``v5e:2x2`` chip at the widths the chip smoke runs.

Nothing executes: a pass says Mosaic accepts the kernel at that shape, dtype
and layout — what interpret mode (every other kernel test here) cannot say.
The whole train step and serve tick programs are compiled the same way by a
scratch script before a chip call; they are too slow to keep as tests.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and every xdist worker imports this
file. ``_interpret()`` asks ``jax.default_backend()`` and would answer
"interpret" on the CPU the suite runs on, so the test steers it (monkeypatch).
"""

import json
import math
import os
import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from simple_distributed_machine_learning_tpu.ops import (
    flash_attention as fa,
    kda,
    moe_experts as me,
    paged_attention as pa,
    selective_scan as ss,
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described-topology compile can be written to the persistent cache
    # but never read back without a chip: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Make the kernels take their on-chip branch (compiled, not interpret).
    ``paged_attention`` imported the function by name, so both are set."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(pa, "_interpret", lambda: False)
    monkeypatch.setattr(ss, "_interpret", lambda: False)
    monkeypatch.setattr(me, "_interpret", lambda: False)
    monkeypatch.setattr(kda, "_interpret", lambda: False)


def _compile(fn, one_chip, *shapes, kernels):
    """Compile ``fn`` and return the HLO lines of its Mosaic kernels, which
    must be the ones called ``kernels`` (the ``name=`` of each Pallas call:
    what a device trace shows the kernel as)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    lines = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    for name in kernels:
        assert any(name in ln.split(" = ")[0] for ln in lines), (
            name, [ln.split(" = ")[0] for ln in lines])
    assert len(lines) == len(kernels)
    return lines


# -- paged attention: the serve tick's kernel -------------------------------

H = 16          # chip_smoke's model: 16 heads
SLOTS = 8
SEQ = 512

_PAGED = ([(dh, bs, 1, pool)
           for dh in (64, 128) for bs in (4, 16, 128)
           for pool in ("float32", "bfloat16", "int8")]
          # the speculative verify width on the deployment block size
          + [(dh, 16, 4, pool)
             for dh in (64, 128) for pool in ("float32", "bfloat16", "int8")])


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its nested jaxprs included."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("dh,bs,k,pool", _PAGED)
def test_paged_attention_compiles_for_v5e(one_chip, mosaic, dh, bs, k, pool):
    """The shapes and dtypes ``models/serving.py::paged_attend`` passes: f32
    queries (serving computes in f32), one layer's pool buffer
    ``[n_blocks+1, bs, H*dh]`` in the cache dtype, and for a quantized pool
    the ``QuantKV`` scale plane ``[n_blocks+1, bs, H]`` in f32."""
    nb = SEQ // bs
    n_phys = SLOTS * nb + 1
    quant = pool == "int8"
    shapes = [((SLOTS, H, k, dh), jnp.float32),
              ((n_phys, bs, H * dh), jnp.dtype(pool)),
              ((n_phys, bs, H * dh), jnp.dtype(pool)),
              ((SLOTS, nb), jnp.int32),
              ((SLOTS, k), jnp.int32)]
    if quant:
        shapes += [((n_phys, bs, H), jnp.float32)] * 2

    def fn(q, kc, vc, tables, qpos, *scales):
        kw = dict(kscale=scales[0], vscale=scales[1]) if quant else {}
        return pa.paged_attention(q, kc, vc, tables, qpos, block_size=bs,
                                  **kw)

    # the layer goes to the kernel as the pool holds it, whatever the head
    # dim: nothing of its size is transposed or padded on the way. The one
    # exception is a quantized pool with several heads in a row, laid out
    # head-major for the call (ops/paged_attention.py says why)
    jaxpr = jax.make_jaxpr(fn)(
        *[jax.ShapeDtypeStruct(s, d) for s, d in shapes]).jaxpr
    relaid = [e for e in _eqns(jaxpr)
              if e.primitive.name in ("transpose", "pad")
              and any(math.prod(v.aval.shape) >= n_phys * bs * H * dh
                      for v in e.outvars)]
    assert bool(relaid) == quant, relaid
    (line,) = _compile(fn, one_chip, *shapes, kernels=["paged_attention"])
    # the benchmark finds the kernel's events by this pattern
    # (bench_cells/traffic/serve-closed.json); a name leaves it in the line
    with open(os.path.join(os.path.dirname(__file__), os.pardir,
                           "bench_cells", "traffic",
                           "serve-closed.json")) as f:
        pattern = json.load(f)["kernels"]["paged_attention"]
    assert re.search(pattern, line)


def test_paged_attention_compiles_with_bf16_queries(one_chip, mosaic):
    """A bf16 query against a bf16 pool (what a bf16-compute serve build
    would pass): the kernel widens both in VMEM."""
    bs, dh = 16, 64
    nb = SEQ // bs
    shapes = [((SLOTS, H, 1, dh), jnp.bfloat16),
              ((SLOTS * nb + 1, bs, H * dh), jnp.bfloat16),
              ((SLOTS * nb + 1, bs, H * dh), jnp.bfloat16),
              ((SLOTS, nb), jnp.int32), ((SLOTS, 1), jnp.int32)]
    _compile(lambda q, kc, vc, t, p: pa.paged_attention(
        q, kc, vc, t, p, block_size=bs), one_chip, *shapes,
        kernels=["paged_attention"])


def _kernel_pattern(mix: str, kernel: str) -> str:
    with open(os.path.join(os.path.dirname(__file__), os.pardir,
                           "bench_cells", "traffic", mix + ".json")) as f:
        return json.load(f)["kernels"][kernel]


# the three serve cells' decode calls as they are: slots, query heads,
# queries a slot, head dim, K/V heads in a pool row, blocks in the pool (the
# trash block included), the traffic file whose pattern finds the kernel
_CELLS = {
    "gpt2-large.serve-closed": (16, 20, 1, 64, 20, 513, "serve-closed"),
    "jamba2-3b.serve-reason-closed": (128, 20, 1, 128, 1, 8193,
                                      "serve-reason-closed"),
    "sdar-30b-a3b.serve-diffuse-closed": (64, 32, 4, 128, 4, 4097,
                                          "serve-diffuse-closed"),
    # max_len 2,048: 128 blocks a slot, not 64
    "nemotron3-super-120b-a12b.serve-agent-closed": (
        96, 32, 1, 128, 2, 12289, "serve-agent-closed"),
    # max_len 8,192: 512 blocks a slot, 32 spans of 16; the pool's row is
    # the latent's 2 K/V heads of 128 under 8 query heads
    "zaya1-8b.serve-context-closed": (24, 8, 1, 128, 2, 12289,
                                      "serve-context-closed"),
}


@pytest.mark.parametrize("cell", list(_CELLS))
def test_paged_attention_compiles_at_the_serve_cells_real_shapes(
        one_chip, mosaic, cell):
    """Every serve cell's call at its REAL size (``max_len`` 1,024 in
    bfloat16 blocks of 16): 20 K/V heads of 64 in a 1,280-lane row under
    one query each; 20 query heads riding the hybrid's ONE K/V head of 128
    (the case where the pool's row is one head wide); 4 K/V heads under 32
    query heads with the 4 queries of a block a slot. Mosaic's tiling and
    fast-memory limits for the double-buffered spans (16 blocks each here)
    are what interpret mode cannot see."""
    slots, heads, k, dh, kvh, n_phys, mix = _CELLS[cell]
    bs, nb = 16, (n_phys - 1) // slots
    shapes = [((slots, heads, k, dh), jnp.float32),
              ((n_phys, bs, kvh * dh), jnp.bfloat16),
              ((n_phys, bs, kvh * dh), jnp.bfloat16),
              ((slots, nb), jnp.int32), ((slots, k), jnp.int32)]
    assert pa._span_blocks(bs, bs * kvh * dh * 2, nb, 2) == 16
    (line,) = _compile(lambda q, kc, vc, t, p: pa.paged_attention(
        q, kc, vc, t, p, block_size=bs), one_chip, *shapes,
        kernels=["paged_attention"])
    assert re.search(_kernel_pattern(mix, "paged_attention"), line.strip())
    for other in ("selective_scan", "moe_experts", "selective_scan_grouped"):
        with open(os.path.join(os.path.dirname(__file__), os.pardir,
                               "bench_cells", "traffic",
                               mix + ".json")) as f:
            pattern = json.load(f)["kernels"].get(other)
        assert not (pattern and re.search(pattern, line.strip()))


# -- the serve programs: the pool stays where it is ----------------------------


def _on_chip(tree, one_chip):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), tree)


def _sd(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _gpt_programs():
    """``gpt2-large.serve-closed``'s decode and prefill-chunk programs at 2
    of its 36 layers: d 1280, 20 heads of 64, 16 slots, max_len 1024, blocks
    of 16, 512 of them, chunk 128, bf16 pool, fused kernel (the vocabulary
    cut to 512: the head is not what is looked at)."""
    from simple_distributed_machine_learning_tpu.models.gpt import (
        GPTConfig,
        make_gpt_stages,
    )
    L, d, heads, bs, nb, S, ml, c = 2, 1280, 20, 16, 512, 16, 1024, 128
    cfg = GPTConfig(vocab=512, seq_len=ml, d_model=d, n_heads=heads,
                    n_layers=L)
    params = jax.eval_shape(
        lambda k: make_gpt_stages(k, cfg, 1)[0][0].params, jax.random.key(0))
    serving = cfg.paged_serving([types.SimpleNamespace(params=params)], ml,
                                bs, "bfloat16", kernel="fused")
    pool = (_sd((nb + 1, bs, d), jnp.bfloat16),) * L
    # every slot's newest token and key, donated with the pool
    state = jax.tree.map(lambda sd: _sd((S, *sd.shape), sd.dtype),
                         serving.state_shapes)
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    return pool, state, {
        "decode": (serving.decode, (
            [params], pool, pool, state, _sd((S,), i32),
            _sd((S, ml // bs), i32), _sd((S,), jnp.bool_), _sd((S,), f32),
            _sd((S,), i32), _sd((S,), f32))),
        "chunk": (serving.chunk_prefill, (
            [params], pool, pool, state, _sd((1, c), i32), _sd((), i32),
            _sd((ml // bs,), i32), _sd((), i32), _sd((), i32),
            _sd((2,), u32), _sd((), f32), _sd((), i32), _sd((), f32)))}


def _hybrid_programs():
    """The hybrid decode at a toy with its two attention layers (M A M A),
    4 query heads over ONE K/V head of 128 like the published model's."""
    from simple_distributed_machine_learning_tpu.models.jamba import (
        JambaConfig,
        make_jamba_stages,
        pack_decode_inputs,
    )
    import numpy as np
    S, ml, bs, nb = 8, 128, 16, 2048    # a layer's buffer outweighs a weight
    cfg = JambaConfig(vocab=512, seq_len=ml, d_model=512, n_heads=4,
                      n_kv_heads=1, d_ff=1024, n_layers=4, attn_period=2,
                      attn_offset=1, expand=2, dt_rank=32,
                      param_dtype="bfloat16")
    params = jax.eval_shape(
        lambda k: make_jamba_stages(k, cfg)[0][0].params, jax.random.key(0))
    serving = cfg.paged_serving([types.SimpleNamespace(params=params)], ml,
                                bs, "bfloat16", kernel="fused")
    pool = (_sd((nb + 1, bs, cfg.head_dim), jnp.bfloat16),
            ) * serving.kv_layers
    state = jax.tree.map(lambda sd: _sd((S, *sd.shape), sd.dtype),
                         serving.state_shapes)
    z = np.zeros(S, np.int32)
    host, = pack_decode_inputs(z, z, np.zeros((S, ml // bs), np.int32), z,
                               None, z.astype(np.float32), z,
                               z.astype(np.float32))
    # its state is mostly the recurrent buffers, which this test leaves
    # alone: no pair to hold to the byte here
    return pool, (), {"hybrid-decode": (serving.decode, (
        [params], pool, pool, state, _sd(host.shape, host.dtype)))}


def _block_programs():
    """``sdar-30b-a3b.serve-diffuse-closed``'s block tick at 2 of its 7
    layers and 16 of its 128 experts (8 a token), the published widths
    otherwise: hidden 2048, 32 query heads over 4 K/V heads of 128, experts
    of 768, 64 slots, blocks of 16, bf16 pool, fused kernel (the vocabulary
    cut to 512: the head is not what is looked at)."""
    from simple_distributed_machine_learning_tpu.models.sdar import (
        SdarConfig,
        make_sdar_stages,
        pack_decode_inputs,
    )
    import numpy as np
    S, ml, bs, nb = 64, 1024, 16, 4096
    cfg = SdarConfig(vocab=512, seq_len=ml, d_model=2048, n_heads=32,
                     n_kv_heads=4, head_dim=128, n_layers=2, n_experts=16,
                     top_k=8, d_expert=768, mask_id=511,
                     param_dtype="bfloat16")
    params = jax.eval_shape(
        lambda k: make_sdar_stages(k, cfg)[0][0].params, jax.random.key(0))
    serving = cfg.paged_serving([types.SimpleNamespace(params=params)], ml,
                                bs, "bfloat16", kernel="fused")
    assert serving.block == 4
    pool = (_sd((nb + 1, bs, 4 * 128), jnp.bfloat16),) * serving.kv_layers
    state = jax.tree.map(lambda sd: _sd((S, *sd.shape), sd.dtype),
                         serving.state_shapes)
    z = np.zeros(S, np.int32)
    host, = pack_decode_inputs(z, z, np.zeros((S, ml // bs), np.int32), z, z,
                               None, z.astype(np.float32), z,
                               z.astype(np.float32))
    return pool, (), {"block-decode": (serving.decode, (
        [params], pool, pool, state, _sd(host.shape, host.dtype)))}


def _bytes(shape_text: str) -> int:
    """The largest array a result type names, ``bf16[513,16,1280]{...}`` or
    a tuple of such, in bytes."""
    best = 0
    for dt, dims in re.findall(r"\b([a-z]+\d+)\[([\d,]*)\]", shape_text):
        n = math.prod(int(x) for x in dims.split(",") if x)
        best = max(best, n * int(re.sub(r"\D", "", dt)) // 8)
    return best


@pytest.mark.parametrize("program", ["decode", "chunk", "hybrid-decode",
                                     "block-decode"])
def test_serve_programs_leave_the_pool_where_it_is(one_chip, mosaic,
                                                   program):
    """The compiled entry computation writes the donated per-layer buffers
    in place and hands them to the kernel untouched: nothing as large as
    one layer's K buffer is copied, sliced, transposed or padded, every
    pool byte is aliased input to output, and the temporaries are smaller
    than one layer's K buffer. GPT's two programs hold every slot's newest
    token and key beside the pool (``PagedServing``): every byte of
    that pair is aliased too.

    What the compiler may still do on its own: stage a buffer in its on-chip
    memory around a kernel call and write it back (an asynchronous
    ``copy-start`` between two memory spaces, ``S(1)`` in its layout). At
    512 blocks it does that to one of the four buffers of the GPT decode
    (two of 72 at 36 layers; none at 1,024 blocks; sandbox compile, PR 29).
    That is no re-layout of the pool and is left to it; a ``copy-start``
    that stays in one memory space is refused like a ``copy``."""
    pool, pair, programs = {"hybrid-decode": _hybrid_programs,
                            "block-decode": _block_programs}.get(
                                program, _gpt_programs)()
    fn, args = programs[program]
    compiled = fn.lower(*_on_chip(args, one_chip)).compile()
    layer = math.prod(pool[0].shape) * pool[0].dtype.itemsize
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", compiled.as_text(),
                      re.S | re.M).group(1)
    moved = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?[\w.\-]+ = (.*?) "
                     r"(copy|copy-start|slice|transpose|pad)\(", line)
        if not m or _bytes(m.group(1)) < layer:
            continue
        spaces = re.findall(r"\[[\d,]*\]\{[^}]*?(S\(\d+\))?\}", m.group(1))
        if m.group(2) == "copy-start" and len(set(spaces[:2])) == 2:
            continue                    # between memory spaces: see above
        moved.append(line.strip()[:200])
    assert not moved, moved
    mem = compiled.memory_analysis()
    pair_bytes = sum(math.prod(sd.shape) * sd.dtype.itemsize
                     for sd in jax.tree.leaves(pair))
    assert pair_bytes == (192 if pair else 0)
    assert mem.alias_size_in_bytes >= 2 * len(pool) * layer + pair_bytes
    assert mem.temp_size_in_bytes < layer, (mem.temp_size_in_bytes, layer)


# -- selective scan: the hybrid serve programs' kernel ------------------------


@pytest.mark.parametrize("n,n_tok", [(128, 1), (1, 256), (1, 64), (1, 192),
                                     (3, 7)])
def test_selective_scan_compiles_for_v5e(one_chip, mosaic, n, n_tok):
    """The published widths (``d_inner`` 5120, 16 states) at the shapes the
    hybrid cell runs: the decode tick over 128 slots, the prefill chunks of
    its prompt lengths; and a ragged walk."""
    di, n_state, f32 = 5120, 16, jnp.float32
    wide, narrow = ((n, n_tok, di), f32), ((n, n_tok, n_state), f32)
    (line,) = _compile(
        ss.selective_scan, one_chip, wide, wide, wide, narrow, narrow,
        ((n_state, di), f32), ((di,), f32), ((n, n_state, di), f32),
        kernels=["selective_scan"])
    assert re.search(_kernel_pattern("serve-reason-closed",
                                     "selective_scan"), line.strip())
    assert not re.search(_kernel_pattern("serve-reason-closed",
                                         "paged_attention"), line.strip())


@pytest.mark.parametrize("n,n_tok", [(96, 1), (1, 256), (3, 7)])
def test_grouped_selective_scan_compiles_for_v5e(one_chip, mosaic, n, n_tok):
    """The second recurrence at the published widths (128 heads of 64
    channels, 8 groups, 128 states) at the shapes
    ``nemotron3-super-120b-a12b.serve-agent-closed`` runs: the decode tick
    over 96 slots and the one 256-token chunk; and a ragged walk."""
    di, groups, n_state, f32 = 8192, 8, 128, jnp.float32
    wide, narrow = ((n, n_tok, di), f32), ((n, n_tok, groups, n_state), f32)
    (line,) = _compile(
        lambda x, dt, b, c, a, d, h0: ss.selective_scan(
            x, dt, None, b, c, a, d, h0),
        one_chip, wide, wide, narrow, narrow, ((di,), f32), ((di,), f32),
        ((n, n_state, di), f32), kernels=["selective_scan_grouped"])
    assert re.search(_kernel_pattern("serve-agent-closed",
                                     "selective_scan_grouped"), line.strip())
    # the hybrid cell's pattern must not count this kernel as its own
    assert not re.search(_kernel_pattern("serve-reason-closed",
                                         "selective_scan"), line.strip())


# -- grouped expert products: the sparse serve programs' kernel ---------------


@pytest.mark.parametrize("m,k,n", [(2048, 2048, 768), (2048, 768, 2048),
                                   (512, 2048, 768), (1536, 768, 2048)])
def test_expert_products_compile_for_v5e(one_chip, mosaic, m, k, n):
    """The published widths (128 experts, hidden 2048, expert width 768) at
    the rows ``sdar-30b-a3b.serve-diffuse-closed`` runs: 8 pairs a row of a
    tick's 256 rows or of a 64- to 256-token chunk; the gate / up shape and
    the down shape, each expert's matrix one block."""
    bf16 = jnp.bfloat16
    (line,) = _compile(
        me.grouped_matmul, one_chip, ((m, k), bf16), ((128, k, n), bf16),
        ((128,), jnp.int32), kernels=["moe_experts"])
    assert re.search(_kernel_pattern("serve-diffuse-closed", "moe_experts"),
                     line.strip())
    assert not re.search(_kernel_pattern("serve-diffuse-closed",
                                         "paged_attention"), line.strip())


@pytest.mark.parametrize("m,k,n", [(2112, 1024, 2688), (2112, 2688, 1024),
                                   (5632, 1024, 2688), (5632, 2688, 1024)])
def test_latent_expert_products_compile_for_v5e(one_chip, mosaic, m, k, n):
    """The published widths (latent 1,024, expert width 2,688) over the 128
    experts held, at the rows ``nemotron3-super-120b-a12b.serve-agent-
    closed`` runs: 22 pairs a row of a tick's 96 rows or of a 256-token
    chunk (the pairs of absent experts lie past the last group); the first
    product's matrix in three blocks of 896 columns, the second's in two."""
    bf16 = jnp.bfloat16
    (line,) = _compile(
        me.grouped_matmul, one_chip, ((m, k), bf16), ((128, k, n), bf16),
        ((128,), jnp.int32), kernels=["moe_experts"])
    assert re.search(_kernel_pattern("serve-agent-closed", "moe_experts"),
                     line.strip())


@pytest.mark.parametrize("m,k,n", [(24, 2048, 2048), (512, 2048, 2048)])
def test_top1_expert_products_compile_for_v5e(one_chip, mosaic, m, k, n):
    """The published widths (16 experts of 2048 x 2048) at the rows
    ``zaya1-8b.serve-context-closed`` runs: ONE pair a row of a tick's 24
    rows (a row tile of 24, 1.9 rows a hit expert) or of a 512-token chunk;
    gate, up and down are the same shape, each expert's matrix in two
    blocks of 1,024 columns, 4 MiB each: the largest block the layer
    fetches in any cell."""
    bf16 = jnp.bfloat16
    (line,) = _compile(
        me.grouped_matmul, one_chip, ((m, k), bf16), ((16, k, n), bf16),
        ((16,), jnp.int32), kernels=["moe_experts"])
    assert re.search(_kernel_pattern("serve-context-closed", "moe_experts"),
                     line.strip())
    assert not re.search(_kernel_pattern("serve-context-closed",
                                         "paged_attention"), line.strip())


def _cca_programs():
    """``zaya1-8b.serve-context-closed``'s two programs at 2 of its 20
    layers and everything else as the cell runs it: hidden 2048, 8 query
    heads over 2 K/V heads of 128 in the latent, 16 experts of 2048, the
    router's 256, the whole vocabulary of 262,272 under the tied head, 24
    slots of 8,192 positions, 12,288 bf16 blocks of 16, chunks of 512, the
    fused kernel."""
    from simple_distributed_machine_learning_tpu.models.zaya import (
        ZayaConfig,
        make_zaya_stages,
        pack_chunk_inputs,
        pack_decode_inputs,
    )
    import numpy as np
    S, ml, bs, nb, c = 24, 8192, 16, 12288, 512
    cfg = ZayaConfig(vocab=262272, seq_len=ml, d_model=2048, n_layers=2,
                     n_heads=8, n_kv_heads=2, head_dim=128, n_experts=16,
                     d_expert=2048, d_router=256, param_dtype="bfloat16")
    params = jax.eval_shape(
        lambda k: make_zaya_stages(k, cfg)[0][0].params, jax.random.key(0))
    serving = cfg.paged_serving([types.SimpleNamespace(params=params)], ml,
                                bs, "bfloat16", kernel="fused")
    pool = (_sd((nb + 1, bs, cfg.d_kv), jnp.bfloat16),) * serving.kv_layers
    state = jax.tree.map(lambda sd: _sd((S, *sd.shape), sd.dtype),
                         serving.state_shapes)
    z = np.zeros(S, np.int32)
    host, = pack_decode_inputs(z, z, np.zeros((S, ml // bs), np.int32), z,
                               None, z.astype(np.float32), z,
                               z.astype(np.float32))
    tokens, chost = pack_chunk_inputs(
        np.zeros((1, c), np.int32), 0, np.zeros(ml // bs, np.int32), 0, -1,
        np.zeros(2, np.uint32), 0.0, 0, 1.0)
    return pool, {
        "cca-decode": (serving.decode, (
            [params], pool, pool, state, _sd(host.shape, host.dtype))),
        "cca-chunk": (serving.chunk_prefill, (
            [params], pool, pool, state, _sd(tokens.shape, tokens.dtype),
            _sd(chost.shape, chost.dtype)))}


@pytest.mark.parametrize("program,kernels", [
    ("cca-decode", {"paged_attention", "moe_experts"}),
    ("cca-chunk", {"moe_experts"})])
def test_cca_programs_compile_at_the_cells_real_sizes(one_chip, mosaic,
                                                      program, kernels):
    """The decode step and the prefill chunk of the fifth family, handed to
    the chip's compiler whole: a slot of 8,192 positions (512 table
    entries), rows of 262,272 logits under the sampler, the 4 MiB expert
    blocks at a row tile of 24. The pool and the per-slot state are
    donated: every byte of both is aliased input to output, and what the
    program holds beside its arguments stays under two layers' K buffers,
    the chunk's under ONE: it attends over the slot's live positions a step
    of blocks at a time (``models/serving.py::span_attention``), so no
    instruction of its compiled text has the table's 8,192 positions beside
    the chunk's 512 rows in one array (until PR 46 a layer's scores were
    ``f32[1,2,4,512,8192]``, 134 MB written, masked and read back)."""
    pool, programs = _cca_programs()
    fn, args = programs[program]
    compiled = fn.lower(*_on_chip(args, one_chip)).compile()
    text = compiled.as_text()
    found = {ln.split(" = ")[0].strip().lstrip("%").split(".")[0]
             for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln}
    assert found == kernels
    layer = math.prod(pool[0].shape) * pool[0].dtype.itemsize
    state_bytes = sum(math.prod(sd.shape) * sd.dtype.itemsize
                      for sd in jax.tree.leaves(args[3]))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * len(pool) * layer + state_bytes
    limit = layer if program == "cca-chunk" else 2 * layer
    assert mem.temp_size_in_bytes < limit, (mem.temp_size_in_bytes, layer)
    if program == "cca-chunk":
        table_wide = [
            m.group(0) for m in re.finditer(r"\w+\[([\d,]+)\]", text)
            if {"512", "8192"} <= set(m.group(1).split(","))]
        assert not table_wide, sorted(set(table_wide))


def _window_programs(cfg=None, sizes=(16, 32768, 16, 32768, 4624, 512),
                     pool_dtype="bfloat16", kernel="fused"):
    """``command-a-plus-05-2026.serve-mixed-closed``'s two programs as the
    cell runs them: four layers (window, window, window, full), hidden
    4096, 128 query heads over 8 K/V heads of 128, window 4,096, 16 held
    experts of 128 beside four shared ones of 4096, 32,768 held rows under
    the tied head, 16 slots of 32,768 positions, 32,768 full and 4,624
    window bf16 blocks of 16, chunks of 512, the fused kernel. Another
    ``cfg`` with its ``sizes`` (slots, max_len, block, full blocks, window
    blocks, chunk) gives that build's programs the same way
    (``tests/test_cohere2.py`` traces the toy's)."""
    from simple_distributed_machine_learning_tpu.models.cohere2 import (
        Cohere2Config,
        make_cohere2_stages,
        pack_chunk_inputs,
        pack_decode_inputs,
    )
    import numpy as np
    S, ml, bs, nb, nwb, c = sizes
    cfg = cfg or Cohere2Config(
        vocab=32768, seq_len=ml, d_model=4096, n_layers=4, n_heads=128,
        n_kv_heads=8, head_dim=128, window=4096, n_experts=128, top_k=8,
        experts_held=16, n_shared=4, d_expert=4096, param_dtype="bfloat16")
    params = jax.eval_shape(
        lambda k: make_cohere2_stages(k, cfg)[0][0].params, jax.random.key(0))
    serving = cfg.paged_serving([types.SimpleNamespace(params=params)], ml,
                                bs, pool_dtype, kernel=kernel)
    # the tree the programs read (PagedServing.serve_params), as shapes
    params, = jax.eval_shape(serving.serve_params, [params])
    pool = tuple(_sd(((nb if w is None else nwb) + 1, bs, cfg.d_kv),
                     jnp.dtype(pool_dtype)) for w in serving.windows)
    state = jax.tree.map(lambda sd: _sd((S, *sd.shape), sd.dtype),
                         serving.state_shapes)
    # a slot's ring: window, chunk and a block (289 entries in the cell)
    width = ml // bs + -(-(cfg.window + c) // bs) + 1
    z = np.zeros(S, np.int32)
    host, = pack_decode_inputs(z, z, np.zeros((S, width), np.int32), z,
                               None, z.astype(np.float32), z,
                               z.astype(np.float32))
    tokens, chost = pack_chunk_inputs(
        np.zeros((1, c), np.int32), 0, np.zeros(width, np.int32), 0, -1,
        np.zeros(2, np.uint32), 0.0, 0, 1.0)
    return pool, {
        "window-decode": (serving.decode, (
            [params], pool, pool, state, _sd(host.shape, host.dtype))),
        "window-chunk": (serving.chunk_prefill, (
            [params], pool, pool, state, _sd(tokens.shape, tokens.dtype),
            _sd(chost.shape, chost.dtype)))}


_WINDOW_COMPILED = {}


def _window_compiled(program, one_chip):
    """One compile a program for the tests below (25 s each)."""
    if program not in _WINDOW_COMPILED:
        fn, args = _window_programs()[1][program]
        _WINDOW_COMPILED[program] = fn.lower(
            *_on_chip(args, one_chip)).compile()
    return _WINDOW_COMPILED[program]


@pytest.mark.parametrize("program,kernels", [
    ("window-decode", {"paged_attention", "moe_experts"}),
    ("window-chunk", {"moe_experts"})])
def test_window_programs_compile_at_the_cells_real_sizes(one_chip, mosaic,
                                                         program, kernels):
    """The decode step and the prefill chunk of the sixth family, handed to
    the chip's compiler whole: the kernel over a full layer's table of
    2,048 entries and a window layer's ring of 289 at 16 query heads to a
    K/V head (a query row block of 128 rows by 1,024 lanes), the 32 MiB
    expert matrices, rows of 32,768 logits under the sampler. The pool and
    the newest pair are donated: every byte is aliased input to output.
    The chunk attends 512 positions a step over the live positions alone,
    so what it holds beside its arguments stays under 1.5 GB (scores of
    128 heads x 512 rows x 32,768 positions would be 8.6 GB)."""
    pool, programs = _window_programs()
    _, args = programs[program]
    compiled = _window_compiled(program, one_chip)
    found = {ln.split(" = ")[0].strip().lstrip("%").split(".")[0]
             for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln}
    assert found == kernels
    held = sum(math.prod(b.shape) * b.dtype.itemsize for b in pool)
    state_bytes = sum(math.prod(sd.shape) * sd.dtype.itemsize
                      for sd in jax.tree.leaves(args[3]))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * held + state_bytes
    assert mem.temp_size_in_bytes < 1.5e9, mem.temp_size_in_bytes


@pytest.mark.parametrize("program", ["window-decode", "window-chunk"])
def test_window_programs_leave_the_projections_where_they_lie(
        one_chip, mosaic, program):
    """Nothing but a product reads a layer's query or key matrix: the
    compiled text holds no operation that MOVES an array of either one's
    size (``bf16[4096, 16384]`` is 134 MB, ``bf16[4096, 1024]`` 8.4 MB) in
    whatever shape: no copy, transpose, reshape, slice, pad, concatenate or
    convert. Until PR 45 every window layer's run transposed ``W_q``,
    reshaped it to ``[128, 64, 2, 4096]`` (the neighbouring-lane rotary's
    view, answered on the weight), and the full layer's transposed it
    too: 3.3 ms of a 14.9 ms decode run on the
    chip (``PERF.md`` section 6, PR 45). The compiler's own prefetches of a
    matrix into faster memory (``copy-start``, ``slice-start``) are not
    passes of the program's and are let be."""
    moved = []
    sizes = {4096 * 16384, 4096 * 1024}
    for ln in _window_compiled(program, one_chip).as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = bf16\[([\d,]+)\]\S* "
                     r"(copy|transpose|reshape|slice|dynamic-slice|pad|"
                     r"concatenate|convert)\(", ln)
        if m and math.prod(int(n) for n in m.group(2).split(",")) in sizes:
            moved.append(f"{m.group(3)} {m.group(1)} bf16[{m.group(2)}]")
    assert not moved, moved


# -- the seventh family: the delta rule, one stream, both programs -----------


@pytest.mark.parametrize("n,n_tok", [(64, 1), (1, 512), (1, 40)])
def test_kda_recurrence_compiles_for_v5e(one_chip, mosaic, n, n_tok):
    """The cell's two calls (a decode tick's 64 slots of one token, a
    chunk's 512 tokens of one slot) and a ragged walk, at 32 heads of 128 x
    128 float32 state: the state is aliased in place, and nothing beside
    the arguments is held."""
    H, dk = 32, 128
    f32 = jnp.float32
    shapes = ([((n, n_tok, H, dk), f32)] * 4 + [((n, n_tok, H), f32),
                                                ((n, H, dk, dk), f32)])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    live = {"live": jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip)
            } if n_tok == 1 else {}
    compiled = jax.jit(kda.kda_recurrence, donate_argnums=(5,)).lower(
        *args, **live).compile()
    lines = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(lines) == 1 and "kda_recurrence" in lines[0].split(" = ")[0]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == n * H * dk * dk * 4


def test_one_stream_attention_compiles_at_the_cells_real_shape(one_chip,
                                                               mosaic):
    """``paged_attention(vc=None)`` as ``kimi-linear-48b-a3b.serve-think-
    closed`` calls it: 64 slots, 32 query heads over ONE row of 640 lanes
    (512 latent, 64 shared key lanes, 64 zeros) whose leading 512 are the
    values, 16,384 bf16 blocks of 16, tables of 256."""
    fn = lambda q, k, t, p: pa.paged_attention(  # noqa: E731
        q, k, None, t, p, block_size=16, v_lanes=512, scale=192 ** -0.5)
    lines = _compile(fn, one_chip, ((64, 32, 1, 640), jnp.float32),
                     ((16385, 16, 640), jnp.bfloat16),
                     ((64, 256), jnp.int32), ((64, 1), jnp.int32),
                     kernels=["paged_attention"])
    assert "f32[64,1,32,512]" in lines[0].replace(" ", "")


def _kda_programs():
    """``kimi-linear-48b-a3b.serve-think-closed``'s two programs as the cell
    runs them, but for depth: one period and the dense layer (layers 0-3:
    KDA with the dense part, two KDA layers and a latent layer with the
    mixture), hidden 2304, 32 KDA heads of 128 and 32 latent heads over one
    640-lane row, 16 held experts of 256, 20,480 held rows, 64 slots of
    4,096, 16,384 bf16 blocks of 16, chunks of 512, the fused kernel."""
    from simple_distributed_machine_learning_tpu.models.kimi_linear import (
        KimiLinearConfig,
        make_kimi_linear_stages,
        pack_chunk_inputs,
        pack_decode_inputs,
    )
    import numpy as np
    S, ml, bs, nb, c = 64, 4096, 16, 16384, 512
    cfg = KimiLinearConfig(
        vocab=20480, seq_len=ml, d_model=2304, n_layers=4, attn_layers=(3,),
        n_heads=32, d_nope=128, d_rope=64, d_v=128, d_latent=512,
        kda_heads=32, kda_head_dim=128, d_conv=4, d_gate=128, n_dense=1,
        d_ff=9216, n_experts=256, top_k=8, experts_held=16, n_shared=1,
        d_expert=1024, param_dtype="bfloat16")
    params = jax.eval_shape(
        lambda k: make_kimi_linear_stages(k, cfg)[0][0].params,
        jax.random.key(0))
    serving = cfg.paged_serving([types.SimpleNamespace(params=params)], ml,
                                bs, "bfloat16", kernel="fused")
    pool = tuple(_sd((nb + 1, bs, cfg.d_cache), jnp.bfloat16)
                 for _ in range(serving.kv_layers))
    state = jax.tree.map(lambda sd: _sd((S, *sd.shape), sd.dtype),
                         serving.state_shapes)
    z = np.zeros(S, np.int32)
    host, = pack_decode_inputs(z, z, np.zeros((S, ml // bs), np.int32), z,
                               None, z.astype(np.float32), z,
                               z.astype(np.float32))
    tokens, chost = pack_chunk_inputs(
        np.zeros((1, c), np.int32), 0, np.zeros(ml // bs, np.int32), 0, -1,
        np.zeros(2, np.uint32), 0.0, 0, 1.0)
    return pool, {
        "kda-decode": (serving.decode, (
            [params], pool, (), state, _sd(host.shape, host.dtype))),
        "kda-chunk": (serving.chunk_prefill, (
            [params], pool, (), state, _sd(tokens.shape, tokens.dtype),
            _sd(chost.shape, chost.dtype)))}


@pytest.mark.parametrize("program,kernels", [
    ("kda-decode", {"kda_recurrence", "paged_attention", "moe_experts"}),
    ("kda-chunk", {"kda_recurrence", "moe_experts"})])
def test_kda_programs_compile_at_the_cells_real_sizes(one_chip, mosaic,
                                                      program, kernels):
    """The decode step and the prefill chunk of the seventh family, handed
    to the chip's compiler whole at the cell's widths (four of its 27
    layers, every kind among them). The pool's one stream and the slots'
    state are donated: every byte of both is aliased input to output, the
    64 x 2 MiB of every KDA layer's matrix state included, and what a
    program holds beside its arguments stays under 0.5 GB."""
    pool, programs = _kda_programs()
    fn, args = programs[program]
    compiled = fn.lower(*_on_chip(args, one_chip)).compile()
    found = {ln.split(" = ")[0].strip().lstrip("%").split(".")[0]
             for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln}
    assert found == kernels
    held = sum(math.prod(b.shape) * b.dtype.itemsize for b in pool)
    state_bytes = sum(math.prod(sd.shape) * sd.dtype.itemsize
                      for sd in jax.tree.leaves(args[3]))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= held + state_bytes
    assert mem.temp_size_in_bytes < 0.5e9, mem.temp_size_in_bytes



# -- flash attention: the train step's kernel -------------------------------

_FLASH = [((8, 16, 512, 64), "bfloat16"),      # chip_smoke's train step
          ((8, 16, 512, 64), "float32"),
          ((2, 8, 2048, 128), "bfloat16"),
          ((2, 8, 2048, 128), "float32")]


@pytest.mark.parametrize("shape,dtype", _FLASH)
def test_flash_attention_forward_compiles_for_v5e(one_chip, mosaic, shape,
                                                  dtype):
    _compile(fa.flash_attention, one_chip, *[(shape, jnp.dtype(dtype))] * 3,
             kernels=["flash_attention_fwd"])


@pytest.mark.parametrize("shape,dtype", _FLASH)
def test_flash_attention_gradient_compiles_for_v5e(one_chip, mosaic, shape,
                                                   dtype):
    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v).astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
             *[(shape, jnp.dtype(dtype))] * 3,
             kernels=["flash_attention_fwd", "flash_attention_dq",
                      "flash_attention_dkv"])
