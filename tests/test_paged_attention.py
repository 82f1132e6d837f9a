"""Fused Pallas paged-attention kernels + the int8-quantized KV pool.

Runs the real kernel code path in Pallas interpret mode on CPU (the same
kernel lowers through Mosaic on TPU), pinned against the gather-then-dense
attention math every serving program used before ISSUE 15:

- kernel-level parity: the flash-decode (K=1) and K-token verify variants
  vs the dense masked-softmax reference over the gathered span, including
  the fused-dequant int8 path against the SAME dequantized rows (tight
  tolerance: identical effective K/V, only accumulation order differs);
- engine-level bit-exactness: greedy token streams through
  ``attn_kernel="fused"`` equal the ``"dense"`` path's EXACTLY (f32, bf16,
  int8; plain and speculative ticks) — the ISSUE-15 acceptance anchor;
- quantized pool coverage: quantize→dequantize round-trip error bounds,
  ``kv_block_bytes`` scale-plane accounting, copy-on-write + prefix
  sharing refcounts over quantized blocks, TP=2 vs TP=1 token parity,
  and the fixed-KV-bytes >= 2x resident-request win vs bf16;
- the analyzer's HBM-bytes-per-tick model: the dense path carries the
  ``kv_attn_reread`` pass, the fused path is single-pass, quantized rows
  bill their scale bytes.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tolerances import attn_tol

from simple_distributed_machine_learning_tpu.models.gpt import (
    GPTConfig,
    make_gpt_stages,
    make_paged_block_copy,
)
from simple_distributed_machine_learning_tpu.models.serving import (
    QuantKV,
    paged_scatter,
    quantize_rows,
)
from simple_distributed_machine_learning_tpu.ops.paged_attention import (
    _attend_blocks,
    _span_blocks,
    paged_attention,
    paged_flash_decode,
)
from simple_distributed_machine_learning_tpu.serve import InferenceEngine
from simple_distributed_machine_learning_tpu.serve.slots import (
    PagedKVPool,
    kv_block_bytes,
    n_blocks_for_bytes,
)

CFG = GPTConfig(vocab=64, seq_len=32, d_model=32, n_heads=2, n_layers=2)
# several heads of the deployed head dim in one pool row (4 x 64 lanes)
CFG_DH64 = GPTConfig(vocab=64, seq_len=32, d_model=256, n_heads=4,
                     n_layers=2)


@pytest.fixture(scope="module")
def stages():
    return make_gpt_stages(jax.random.key(0), CFG, 1)[0]


@pytest.fixture(scope="module")
def stages_dh64():
    return make_gpt_stages(jax.random.key(0), CFG_DH64, 1)[0]


def _rows(kc):
    """A head-major layer ``[n, H, bs, dh]`` (what the dense reference
    here reads) as the pool holds it: ``[n, bs, H*dh]``, a position's
    heads side by side. Scale planes ``[n, H, bs]`` -> ``[n, bs, H]``."""
    kc = jnp.moveaxis(kc, 1, 2)
    return kc.reshape(*kc.shape[:2], -1) if kc.ndim == 4 else kc


def _dense_paged_reference(q, kc, vc, tables, qpos):
    """Gather-then-dense masked attention over the table span — exactly
    the serving programs' pre-kernel math (``models/gpt.py``)."""
    S, H, K, dh = q.shape
    NB = tables.shape[1]
    bs = kc.shape[-2]
    span = NB * bs
    outs = []
    for s in range(S):
        krow = np.moveaxis(np.asarray(kc, np.float32)[tables[s]], 0,
                           1).reshape(H, span, dh)
        vrow = np.moveaxis(np.asarray(vc, np.float32)[tables[s]], 0,
                           1).reshape(H, span, dh)
        sc = jnp.einsum("hqd,hkd->hqk", q[s].astype(jnp.float32),
                        krow) / math.sqrt(dh)
        live = np.arange(span)[None, None, :] <= qpos[s][None, :, None]
        sc = jnp.where(live, sc, -jnp.inf)
        outs.append(jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(sc, -1),
                               vrow))
    return jnp.stack(outs)


def _toy_pool(key, S=3, H=2, dh=16, bs=4, NB=6, NBtot=12):
    kq, kk, kv = jax.random.split(key, 3)
    kc = jax.random.normal(kk, (NBtot, H, bs, dh))
    vc = jax.random.normal(kv, (NBtot, H, bs, dh))
    tables = np.zeros((S, NB), np.int32)
    tables[0, :4] = [2, 5, 7, 8]
    tables[1, :2] = [1, 3]
    tables[2, :1] = [9]
    pos = np.array([10, 4, 0], np.int32) * (bs // 4)
    return kq, kc, vc, tables, pos


def test_paged_flash_decode_matches_dense_gather():
    kq, kc, vc, tables, pos = _toy_pool(jax.random.key(0))
    q = jax.random.normal(kq, (3, 2, 1, 16))
    out = jax.jit(lambda *a: paged_flash_decode(*a, block_size=4))(
        q, _rows(kc), _rows(vc), jnp.asarray(tables), jnp.asarray(pos))
    ref = _dense_paged_reference(q, kc, vc, tables, pos[:, None])
    rtol, atol = attn_tol(jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=rtol, atol=atol)


@pytest.mark.parametrize("bs,NB", [(4, 6), (8, 4), (8, 6)])
def test_paged_attention_verify_variant_matches_dense_gather(bs, NB):
    """The K-token variant: per-query masks at qpos = pos + j. Blocks of 8
    float32 rows are whole sublane tiles, so a trip attends a span of 4 of
    them at once (the largest power of two either table holds); a span's
    blocks past a slot's newest position are fetched as its last live
    block and masked."""
    kq, kc, vc, tables, pos = _toy_pool(jax.random.key(1), bs=bs, NB=NB)
    K = 4
    q = jax.random.normal(kq, (3, 2, K, 16))
    qpos = np.minimum(pos[:, None] + np.arange(K)[None, :],
                      NB * bs - 1).astype(np.int32)
    out = jax.jit(lambda *a: paged_attention(*a, block_size=bs))(
        q, _rows(kc), _rows(vc), jnp.asarray(tables), jnp.asarray(qpos))
    ref = _dense_paged_reference(q, kc, vc, tables, qpos)
    rtol, atol = attn_tol(jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=rtol, atol=atol)


@pytest.mark.parametrize("H", [2, 1])
def test_paged_attention_fused_dequant_matches_dequantized_rows(H):
    """int8 blocks + per-row scales through the kernel == dense attention
    over the EXPLICITLY dequantized rows — same effective K/V, so the
    comparison is tight (accumulation order only), proving dequantize is
    fused faithfully rather than approximated. Two heads in a row take the
    head-major call (a row's heads carry different scales), one head the
    pool as it lies."""
    kq, kc, vc, tables, pos = _toy_pool(jax.random.key(2), H=H)
    K = 2
    q = jax.random.normal(kq, (3, H, K, 16))
    qpos = np.minimum(pos[:, None] + np.arange(K)[None, :],
                      23).astype(np.int32)
    kd, ks = quantize_rows(kc, jnp.int8)
    vd, vs = quantize_rows(vc, jnp.int8)
    out = jax.jit(lambda *a: paged_attention(
        *a[:5], block_size=4, kscale=a[5], vscale=a[6]))(
        q, _rows(kd), _rows(vd), jnp.asarray(tables), jnp.asarray(qpos),
        _rows(ks), _rows(vs))
    deq_k = kd.astype(jnp.float32) * ks[..., None]
    deq_v = vd.astype(jnp.float32) * vs[..., None]
    ref = _dense_paged_reference(q, deq_k, deq_v, tables, qpos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # and the quantized result tracks the UNQUANTIZED one inside the
    # pinned int8 tolerance (the round-trip error budget)
    full = _dense_paged_reference(q, kc, vc, tables, qpos)
    rtol, atol = attn_tol(jnp.int8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               rtol=rtol, atol=atol)


# the served families' calls, scaled down: (query heads, K/V heads in a pool
# row, queries a slot, head dim, positions a table holds). A row is 128
# lanes in the first three
_FAMILIES = {
    "rows-in-lanes": (4, 4, 1, 32, 192),     # GPT: every head a K/V head
    "one-kv-head": (5, 1, 1, 128, 192),      # the hybrid: multi-query
    "grouped-4-queries": (8, 2, 4, 64, 192),  # block diffusion: a block a slot
    # the latent cache: 4 query heads to each of 2 K/V heads of 128 in a
    # 256-lane row, a table of several spans
    "long-narrow": (8, 2, 1, 128, 1024),
}


def _ragged_call(family, quant, seed=11):
    """Five slots of one call over a pool of ``family``'s shape: a seat at
    position 0 of an all-trash table (one trip), a slot that ends on a
    span's last position, one a position past it (a further trip for one
    row), one at ``max_len - 1``, and one whose table is a permutation with
    a block repeated. Returns ``(q, kc, vc, tables, qpos, bs)``, the pool
    head-major float32 ``[n, KVH, bs, dh]``."""
    H, KVH, K, dh, positions = _FAMILIES[family]
    bs = 32 if quant else 16
    NB = positions // bs
    n_phys = max(40, 2 * NB)
    span = bs * _span_blocks(bs, KVH * bs * dh * (1 if quant else 2), NB,
                             1 if quant else 2)
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    kc = jax.random.normal(kk, (n_phys, KVH, bs, dh))
    vc = jax.random.normal(kv, (n_phys, KVH, bs, dh))
    last = np.array([0, span - 1, span, NB * bs - 1, 100], np.int32)
    rng = np.random.default_rng(3)
    tables = np.zeros((5, NB), np.int32)
    for s in (1, 2, 3):
        live = last[s] // bs + 1
        tables[s, :live] = rng.permutation(np.arange(1, n_phys))[:live]
    live = last[4] // bs + 1
    tables[4, :live] = rng.permutation(np.arange(1, n_phys))[:live]
    tables[4, live - 1] = tables[4, 0]          # a block referenced twice
    q = jax.random.normal(kq, (5, H, K, dh))
    qpos = np.repeat(last[:, None], K, axis=1)  # a block's rows: one position
    return q, kc, vc, tables, qpos, bs


@pytest.mark.parametrize("pool", ["bfloat16", "int8"])
@pytest.mark.parametrize("family", list(_FAMILIES))
def test_ragged_slots_in_one_call_match_dense_gather(family, pool):
    """The loop over a slot's own spans: :func:`_ragged_call`'s five slots
    of one call. A span is 128 positions (8 bfloat16 blocks of 16, 4 int8
    blocks of 32: whole sublane tiles) in a table of 192, and for the long
    narrow cache 256 and 512 in a table of 1,024."""
    H, KVH = _FAMILIES[family][:2]
    quant = pool == "int8"
    q, kc, vc, tables, qpos, bs = _ragged_call(family, quant)
    if quant:
        kd, ks = quantize_rows(kc, jnp.int8)
        vd, vs = quantize_rows(vc, jnp.int8)
        kw = dict(kscale=_rows(ks), vscale=_rows(vs))
        kc = kd.astype(jnp.float32) * ks[..., None]
        vc = vd.astype(jnp.float32) * vs[..., None]
    else:
        kd = kc = kc.astype(jnp.bfloat16)
        vd = vc = vc.astype(jnp.bfloat16)
        kw = {}
    out = jax.jit(lambda q, k, v, t, p, **kw: paged_attention(
        q, k, v, t, p, block_size=bs, **kw))(
        q, _rows(kd), _rows(vd), jnp.asarray(tables), jnp.asarray(qpos),
        **kw)
    group = H // KVH
    ref = _dense_paged_reference(q, jnp.repeat(kc, group, axis=1),
                                 jnp.repeat(vc, group, axis=1), tables, qpos)
    assert out.shape == q.shape and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_a_span_is_chosen_from_what_the_call_can_see():
    """No knob: whole sublane tiles of the pool's dtype join into a span
    (as many blocks as the table holds and the buffers fit), anything else
    is attended a block at a time."""
    # the three cells: 40 KB, 4 KB and 16 KB bfloat16 blocks, tables of 64
    assert _span_blocks(16, 16 * 1280 * 2, 64, 2) == 16
    assert _span_blocks(16, 16 * 128 * 2, 64, 2) == 16
    assert _span_blocks(16, 16 * 512 * 2, 64, 2) == 16
    assert _span_blocks(16, 16 * 1280 * 2, 12, 2) == 8      # a short table
    assert _span_blocks(16, 1 << 20, 64, 2) == 1            # no room for two
    assert _span_blocks(128, 128 * 2048 * 2, 8, 2) == 2
    # not whole tiles: 4 rows of anything, 16 rows of one byte
    assert _span_blocks(4, 4 * 1024 * 2, 128, 2) == 1
    assert _span_blocks(16, 16 * 1024, 32, 1) == 1
    assert _span_blocks(32, 32 * 1024, 32, 1) == 16
    assert _span_blocks(8, 8 * 1024 * 4, 64, 4) == 16


def _dense_reference_f64(q, kc, vc, tables, qpos):
    """:func:`_dense_paged_reference` in numpy float64, grouped queries
    included (query head ``h`` reads K/V head ``h // group``)."""
    q, kc, vc = (np.asarray(a, np.float64) for a in (q, kc, vc))
    S, H, K, dh = q.shape
    KVH, bs = kc.shape[1], kc.shape[2]
    span = tables.shape[1] * bs
    out = np.zeros(q.shape)
    for s in range(S):
        k = np.moveaxis(kc[tables[s]], 0, 1).reshape(KVH, span, dh)
        v = np.moveaxis(vc[tables[s]], 0, 1).reshape(KVH, span, dh)
        for h in range(H):
            sc = q[s, h] @ k[h // (H // KVH)].T / math.sqrt(dh)
            sc = np.where(np.arange(span)[None] <= qpos[s][:, None], sc,
                          -np.inf)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            out[s, h] = (p / p.sum(-1, keepdims=True)) @ v[h // (H // KVH)]
    return out


@pytest.mark.parametrize("family", list(_FAMILIES))
def test_bfloat16_pool_is_float32_attention_over_its_values(family):
    """The five ragged slots over a bfloat16 pool (an idle seat among
    them) against the float64 dense reference at the float32 tolerance,
    and against the same call over a float32 pool holding the same
    values: widening a bfloat16 row changes nothing, so the two are one
    sum."""
    q, kc, vc, tables, qpos, bs = _ragged_call(family, False, seed=12)
    kc, vc = kc.astype(jnp.bfloat16), vc.astype(jnp.bfloat16)
    call = jax.jit(lambda *a: paged_attention(*a, block_size=bs))
    t, p = jnp.asarray(tables), jnp.asarray(qpos)
    out = call(q, _rows(kc), _rows(vc), t, p)
    ref = _dense_reference_f64(q, kc, vc, tables, qpos)
    rtol, atol = attn_tol(jnp.float32)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=rtol, atol=atol)
    wide = call(q, _rows(kc.astype(jnp.float32)),
                _rows(vc.astype(jnp.float32)), t, p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(wide),
                               rtol=1e-6, atol=1e-6)


def _kernel_eqns(jaxpr, inside=()):
    """``(primitive name, the loops and branches it lies in)`` of every
    equation of a jaxpr, those of its nested jaxprs included."""
    for e in jaxpr.eqns:
        yield e, inside
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _kernel_eqns(sub, inside + (e,))


@pytest.mark.parametrize("pool,bs", [("bfloat16", 16), ("float32", 8),
                                     ("int8", 32)])
def test_a_span_is_one_wait_a_stream_and_its_copies_lowered_unrolled(pool,
                                                                     bs):
    """What the chip showed binds the kernel is the scalar core issuing
    copies: a span's blocks are started in a loop traced once and lowered
    unrolled, at three sites (the call's first span, and the next span
    into either buffer half, the half static), and waited for ONCE a
    stream, through the one semaphore a stream and half they all signal."""
    quant = pool == "int8"
    sd = jax.ShapeDtypeStruct
    args = [sd((3, 8, 1, 128), jnp.float32),
            sd((40, bs, 256), jnp.dtype(pool)),
            sd((40, bs, 256), jnp.dtype(pool)),
            sd((3, 512 // bs), jnp.int32), sd((3, 1), jnp.int32)]
    if quant:
        args += [sd((40, bs, 2), jnp.float32)] * 2

    def fn(q, kc, vc, t, p, *scales):
        kw = dict(kscale=scales[0], vscale=scales[1]) if quant else {}
        return paged_attention(q, kc, vc, t, p, block_size=bs, **kw)

    eqns = list(_kernel_eqns(jax.make_jaxpr(fn)(*args).jaxpr))
    (call,) = [e for e, _ in eqns if e.primitive.name == "pallas_call"]
    streams = 4 if quant else 2
    # the semaphores: one a stream and half
    sems = [v.aval for v in call.params["jaxpr"].invars
            if "sem" in str(v.aval).lower()]
    assert [tuple(a.shape) for a in sems] == [(streams, 2)]
    waits = [inside for e, inside in eqns if e.primitive.name == "dma_wait"]
    assert len(waits) == streams            # once a stream, in the trip
    assert all(not any(i.primitive.name == "scan" for i in inside)
               for inside in waits)
    starts = [inside for e, inside in eqns
              if e.primitive.name == "dma_start"]
    assert len(starts) == 3 * streams
    for inside in starts:
        (loop,) = [i for i in inside if i.primitive.name == "scan"]
        assert loop.params["length"] == loop.params["unroll"] == 16


def test_quantize_roundtrip_error_bound():
    """|x - dequant(quant(x))| <= amax_row / (2 * qmax) elementwise — the
    per-row scale scheme's analytic bound (int8 qmax = 127)."""
    x = jax.random.normal(jax.random.key(3), (5, 4, 8, 32)) * 3.0
    qd, sc = quantize_rows(x, jnp.int8)
    assert qd.dtype == jnp.int8 and sc.dtype == jnp.float32
    deq = qd.astype(jnp.float32) * sc[..., None]
    amax = np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True)
    bound = amax / (2 * 127.0) + 1e-6
    assert np.all(np.abs(np.asarray(deq - x)) <= bound)
    # all-zero rows stay finite and decode to zero
    z = jnp.zeros((2, 4))
    zd, zs = quantize_rows(z, jnp.int8)
    assert np.all(np.asarray(zd) == 0) and np.all(np.isfinite(zs))


def test_kv_block_bytes_accounts_scale_planes():
    L, H, bs, dh = 2, 2, 4, 16
    f32 = kv_block_bytes(L, H, bs, dh)
    bf16 = kv_block_bytes(L, H, bs, dh, "bfloat16")
    i8 = kv_block_bytes(L, H, bs, dh, "int8")
    assert f32 == 2 * L * H * bs * dh * 4
    assert bf16 == f32 // 2
    # int8 data + one f32 scale per (position, head) row, K and V
    assert i8 == 2 * L * H * bs * dh * 1 + 2 * L * H * bs * 4
    assert i8 < bf16 < f32
    # the pool's bytes_per_block uses the same formula (scales included)
    pool = PagedKVPool(L, 2, H, 16, dh, cache_dtype="int8", block_size=bs)
    assert pool.bytes_per_block == i8
    # one QuantKV a layer: data rows [n_blocks+1, bs, H*dh], a scale per
    # (position, head); what the pool allocates is what a block is billed
    assert len(pool.kc) == L and isinstance(pool.kc[0], QuantKV)
    assert pool.kc[0].data.shape == (pool.n_blocks + 1, bs, H * dh)
    assert pool.kc[0].scale.shape == (pool.n_blocks + 1, bs, H)
    assert (sum(c.nbytes for c in pool.kc + pool.vc)
            == (pool.n_blocks + 1) * i8)
    # fixed-byte sizing: the int8 budget funds strictly more blocks
    budget = 10 * bf16
    assert (n_blocks_for_bytes(budget, L, H, bs, dh, "int8")
            > n_blocks_for_bytes(budget, L, H, bs, dh, "bfloat16"))


def test_quantized_cache_is_paged_only(stages):
    """What keeps contiguous rows (the speculative draft's programs, the
    solo cached decoder) has no scale planes and refuses a quantized
    dtype; the paged pool takes it."""
    from simple_distributed_machine_learning_tpu.models.gpt import (
        make_cached_decoder,
        make_slot_prefill,
        make_slot_propose,
    )

    for build in (lambda: make_slot_prefill(stages, CFG, 16, "int8"),
                  lambda: make_slot_propose(stages, CFG, 16, 4, "int8"),
                  lambda: make_cached_decoder(stages, CFG, 4, 4,
                                              cache_dtype="int8")):
        with pytest.raises(ValueError, match="paged-pool feature"):
            build()
    PagedKVPool(2, 2, 2, 16, 16, cache_dtype="int8")


def test_engine_knob_validation(stages):
    with pytest.raises(ValueError, match="attn_kernel"):
        InferenceEngine(stages, CFG, attn_kernel="magic")
    with pytest.raises(ValueError, match="prefill_chunk"):
        InferenceEngine(stages, CFG, prefill_chunk=0)
    with pytest.raises(ValueError, match="n_blocks"):
        InferenceEngine(stages, CFG, max_len=16, block_size=4, n_blocks=3)
    with pytest.raises(ValueError, match="host_cache_blocks"):
        InferenceEngine(stages, CFG, host_cache_blocks=-1)


def _drain_tokens(stages, cfg, prompts, max_new=8, block_size=4, **kw):
    engine = InferenceEngine(stages, cfg, n_slots=3, block_size=block_size,
                             **kw)
    handles = [engine.submit(p, max_new_tokens=max_new, seed=100 + i)
               for i, p in enumerate(prompts)]
    engine.drain()
    return engine, [list(h.tokens) for h in handles]


def _prompts(n=4, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab, t).astype(np.int32)
            for t in (5, 9, 13, 7)[:n]]


@pytest.mark.parametrize("model,cache_dtype", [
    ("dh16", None), ("dh16", "bfloat16"), ("dh16", "int8"),
    ("dh64", None), ("dh64", "bfloat16"), ("dh64", "int8")])
def test_engine_greedy_fused_bit_exact_vs_dense_path(stages, stages_dh64,
                                                     model, cache_dtype):
    """THE acceptance anchor: greedy decode through attn_kernel='fused'
    emits the exact token stream of the gather-then-dense path — per
    storage dtype (f32/bf16 bit-exact vs their own dense path; the int8
    pool vs ITS dense path, quantization identical on both sides), and
    with four heads of 64 in one pool row (and blocks of 8, which the
    f32 pool's kernel attends four to a span) as with two of 16 (blocks of
    4, one to a span)."""
    st, cfg, bs = ((stages, CFG, 4) if model == "dh16"
                   else (stages_dh64, CFG_DH64, 8))
    prompts = _prompts()
    _, dense = _drain_tokens(st, cfg, prompts, block_size=bs,
                             cache_dtype=cache_dtype)
    _, fused = _drain_tokens(st, cfg, prompts, block_size=bs,
                             cache_dtype=cache_dtype, attn_kernel="fused")
    assert dense == fused


@pytest.mark.parametrize("spec_k", [3, 4])
def test_engine_speculative_fused_bit_exact(stages, spec_k):
    """The K-token verify variant through the engine: fused speculative
    greedy streams equal the dense-path speculative ones AND the plain
    decode's (the existing spec-decode bit-exactness contract composes
    with the kernel)."""
    prompts = _prompts()
    kw = dict(draft_stages=stages, draft_cfg=CFG, spec_k=spec_k)
    _, plain = _drain_tokens(stages, CFG, prompts)
    _, sp_dense = _drain_tokens(stages, CFG, prompts, **kw)
    _, sp_fused = _drain_tokens(stages, CFG, prompts,
                                attn_kernel="fused", **kw)
    assert sp_dense == sp_fused == plain
    # and over the quantized pool (fused vs dense, both int8)
    _, q_dense = _drain_tokens(stages, CFG, prompts, cache_dtype="int8",
                               **kw)
    _, q_fused = _drain_tokens(stages, CFG, prompts, cache_dtype="int8",
                               attn_kernel="fused", **kw)
    assert q_dense == q_fused


def test_quantized_pool_prefix_sharing_cow_refcounts(stages):
    """Copy-on-write + prefix sharing over int8 blocks: shared prompts
    reference the same physical blocks (prefix hits), divergence copies
    data AND scale planes (CoW counter), refcounts release cleanly, and
    sharing cannot change anyone's tokens vs an unshared run."""
    rng = np.random.default_rng(7)
    common = rng.integers(0, CFG.vocab, 9).astype(np.int32)
    prompts = [common,
               np.concatenate([common, [3, 5]]).astype(np.int32),
               np.concatenate([common, [11]]).astype(np.int32)]

    def serial_tokens(**kw):
        """One at a time through a fresh engine each — sharing impossible."""
        toks = []
        for i, p in enumerate(prompts):
            engine = InferenceEngine(stages, CFG, n_slots=3, block_size=4,
                                     **kw)
            h = engine.submit(p, max_new_tokens=6, seed=100 + i)
            engine.drain()
            toks.append(list(h.tokens))
        return toks

    engine = InferenceEngine(stages, CFG, n_slots=3, block_size=4,
                             cache_dtype="int8")
    # r0 boards and registers its prompt blocks; r1/r2 then share them
    # while r0 is STILL LIVE (ref >= 2), so their divergent writes into
    # the shared partial tail block must copy-on-write
    handles = [engine.submit(prompts[0], max_new_tokens=6, seed=100)]
    engine.step()               # r0's prefill completes + registry publish
    for i, p in enumerate(prompts[1:], start=1):
        handles.append(engine.submit(p, max_new_tokens=6, seed=100 + i))
    engine.drain()
    stats = engine.pool.stats()
    assert stats["prefix_hit_blocks_total"] > 0, "no prefix sharing fired"
    assert stats["cow_copies_total"] > 0, "no copy-on-write fired"
    # refcount discipline: nothing live after drain; cached blocks are
    # reclaimable, the rest free; the trash block is never referenced
    assert engine.pool.blocks_in_use == 0
    assert int(engine.pool.ref[PagedKVPool.TRASH]) == 0
    assert (stats["blocks_free"] + stats["blocks_cached"]
            == engine.pool.n_blocks)
    # sharing + CoW changed nothing about the streams
    assert [list(h.tokens) for h in handles] == serial_tokens(
        cache_dtype="int8")


@pytest.mark.parametrize("quant", [True, False])
def test_block_copy_then_divergent_write_on_the_per_layer_pool(quant):
    """The CoW device op on the pool's per-layer buffers: the copy moves a
    block's rows in every layer (a QuantKV block's data AND its scale
    plane — rows without their scales decode to a different value), and
    the sharer's divergent write then lands in the copy alone."""
    L, H, bs, dh, NB = 2, 2, 4, 8, 3
    data = jnp.arange(L * (NB + 1) * bs * H * dh,
                      dtype=jnp.float32).reshape(L, NB + 1, bs, H, dh)

    def pool(offset):
        rows = data + offset
        if not quant:
            return tuple(r.reshape(NB + 1, bs, H * dh) for r in rows)
        qd, sc = quantize_rows(rows, jnp.int8)
        return tuple(QuantKV(d.reshape(NB + 1, bs, H * dh), s_)
                     for d, s_ in zip(qd, sc))

    def host(cache):
        return jax.tree.map(np.asarray, cache)

    # the copy op DONATES its buffers: snapshot host copies first
    kc, vc = pool(0.0), pool(1.0)
    k0, v0 = host(kc), host(vc)
    kc, vc = make_paged_block_copy()(kc, vc, jnp.int32(1), jnp.int32(3))
    for got, was in ((host(kc), k0), (host(vc), v0)):
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(was)):
            np.testing.assert_array_equal(g[1], w[3])      # dst = src
            np.testing.assert_array_equal(g[[0, 2, 3]], w[[0, 2, 3]])
    # the divergent write: layer 0, block 1, offset 2
    new = jnp.full((1, H, dh), -7.0)
    k1 = host(kc)
    kc = jax.jit(lambda c: paged_scatter(
        c, 0, jnp.array([1]), jnp.array([2]), new))(kc)
    k2 = host(kc)
    for layer, (g, w) in enumerate(zip(k2, k1)):
        for gl, wl in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
            changed = np.argwhere((gl != wl).reshape(NB + 1, bs, -1).any(-1))
            assert changed.tolist() == ([[1, 2]] if layer == 0 else [])
    row = k2[0].data[1, 2] * np.repeat(k2[0].scale[1, 2], dh) \
        if quant else k2[0][1, 2]
    np.testing.assert_allclose(row, -7.0, rtol=1e-6)


def test_tp2_quantized_pool_token_parity(stages):
    """TP=2 over the head-sharded int8 pool (data + scale planes both
    split on the head axis) emits TP=1's exact tokens — fused kernel
    included (the kernel runs per shard inside shard_map)."""
    from simple_distributed_machine_learning_tpu.parallel.mesh import (
        make_mesh,
    )

    prompts = _prompts(3)
    _, base = _drain_tokens(stages, CFG, prompts, cache_dtype="int8")
    tp_cfg = dataclasses.replace(CFG, n_tensor_parallel=2)
    mesh = make_mesh(n_stages=1, n_data=1, n_model=2)
    _, tp_dense = _drain_tokens(stages, tp_cfg, prompts,
                                cache_dtype="int8", mesh=mesh)
    assert tp_dense == base
    _, tp_fused = _drain_tokens(stages, tp_cfg, prompts,
                                cache_dtype="int8", mesh=mesh,
                                attn_kernel="fused")
    assert tp_fused == base


def test_int8_pool_doubles_resident_requests_at_fixed_bytes(stages):
    """The ISSUE-15 capacity gate, engine-level: at the SAME KV byte
    budget (scale planes billed), an int8 pool sustains >= 2x the
    simultaneously resident requests of the bf16 pool under a burst."""
    L = sum(len(p["blocks"]) for p in (s.params for s in stages))
    dh = CFG.d_model // CFG.n_heads
    bs, max_new, plen = 4, 8, 13
    ml = plen + max_new
    bpr = -(-ml // bs)
    budget = (2 * bpr + 1) * kv_block_bytes(L, CFG.n_heads, bs, dh,
                                            "bfloat16")
    rng = np.random.default_rng(5)
    peaks = {}
    for cd in ("bfloat16", "int8"):
        nb = n_blocks_for_bytes(budget, L, CFG.n_heads, bs, dh, cd)
        engine = InferenceEngine(stages, CFG, n_slots=nb // bpr + 1,
                                 max_len=ml, block_size=bs, n_blocks=nb,
                                 cache_dtype=cd)
        for i in range(3 * (nb // bpr + 1)):
            engine.submit(rng.integers(0, CFG.vocab, plen).astype(np.int32),
                          max_new_tokens=max_new, seed=i)
        peak = 0
        while engine.busy:
            engine.step()
            peak = max(peak, engine.pool.n_active)
        peaks[cd] = peak
    assert peaks["int8"] >= 2 * peaks["bfloat16"], peaks


def test_hbm_model_matches_kernel_single_pass(stages):
    """The analyzer's per-tick model: dense path = gather + attn reread
    (two passes), fused = the gather pass alone; quantized rows bill
    data + scale bytes via the same kv_block_bytes rule the pool uses."""
    from simple_distributed_machine_learning_tpu.analysis.programs import (
        ServeSpec,
        hbm_tick_costs,
    )

    def costs(**kw):
        s = ServeSpec(CFG, n_slots=4, block_size=4,
                      **kw)
        return {h.op: h.bytes_per_tick for h in hbm_tick_costs(s)}

    cd = costs()
    cf = costs(attn_kernel="fused")
    assert "decode.kv_attn_reread" in cd
    assert "decode.kv_attn_reread" not in cf
    assert cd["decode.kv_gather"] == cf["decode.kv_gather"]
    assert (cd["decode.kv_gather"] + cd["decode.kv_attn_reread"]
            == 2 * cf["decode.kv_gather"])
    # quantized traffic: per-position bytes == the pool's per-row bytes
    dh = CFG.d_model // CFG.n_heads
    cq = costs(cache_dtype="int8")
    per_pos = kv_block_bytes(1, CFG.n_heads, 1, dh, "int8")
    span = -(-CFG.seq_len // 4) * 4
    assert cq["decode.kv_gather"] == 4 * CFG.n_layers * span * per_pos
    # the speculative verify mirrors the decode rule
    draft_cfg = dataclasses.replace(CFG, n_layers=1)
    cv = costs(spec_k=3, draft_cfg=draft_cfg)
    cvf = costs(spec_k=3, draft_cfg=draft_cfg, attn_kernel="fused")
    assert "verify.kv_attn_reread" in cv
    assert "verify.kv_attn_reread" not in cvf


def test_engine_lint_covers_fused_quantized(stages):
    """InferenceEngine(lint=True) preflights the EXACT fused + int8
    programs (QuantKV abstract buffers, kernel path) without ERROR
    findings, and the drift gauge's prediction matches the pool."""
    engine = InferenceEngine(stages, CFG, n_slots=2, block_size=4,
                             cache_dtype="int8", attn_kernel="fused",
                             lint=True)
    h = engine.submit(np.arange(5, dtype=np.int32), max_new_tokens=4)
    engine.step()
    live, predicted = engine.kv_drift()
    assert live == predicted > 0
    engine.drain()
    assert h.state == "done"


@pytest.mark.skipif(not hasattr(jnp, "float8_e4m3fn"),
                    reason="no fp8 in this jnp build")
def test_fp8_cache_roundtrip_and_engine(stages):
    """fp8 (e4m3) where available: round-trip inside the pinned fp8
    tolerance and engine greedy parity fused-vs-dense."""
    x = jax.random.normal(jax.random.key(9), (4, 8, 16))
    qd, sc = quantize_rows(x, jnp.float8_e4m3fn)
    deq = np.asarray(qd.astype(jnp.float32) * sc[..., None])
    rtol, atol = attn_tol(jnp.float8_e4m3fn)
    np.testing.assert_allclose(deq, np.asarray(x), rtol=rtol, atol=atol)
    prompts = _prompts(2)
    _, dense = _drain_tokens(stages, CFG, prompts,
                             cache_dtype=jnp.float8_e4m3fn)
    _, fused = _drain_tokens(stages, CFG, prompts,
                             cache_dtype=jnp.float8_e4m3fn,
                             attn_kernel="fused")
    assert dense == fused


# ---- the pool as it lies against the head-major call; kernel-derived HBM ----

def _head_major_call(q, kc, vc, tables, qpos, ks=None, vs=None):
    """The kernel called with every head a stream of its own over a
    head-major pool ``[n, H, bs, dh]``: what the wrapper did before the
    pool held a position's heads in one row."""
    return _attend_blocks(q, kc, vc, jnp.asarray(tables), jnp.asarray(qpos),
                          4, 1.0 / math.sqrt(q.shape[-1]), ks, vs)


@pytest.mark.parametrize("dh", [4, 8, 16])
def test_rows_in_lanes_call_matches_head_major_call(dh):
    """One stream whose row is all the heads, each head's query in its own
    lanes of a zeroed row and its output that lane block of its row, is
    the head-major call: the zeros add exactly."""
    key, kc, vc, tables, pos = _toy_pool(jax.random.key(3), dh=dh)
    S, H = tables.shape[0], kc.shape[1]
    q = jax.random.normal(key, (S, H, 2, dh))
    qpos = np.stack([np.maximum(pos - 1, 0), pos], axis=1).astype(np.int32)
    nat = _head_major_call(q, kc, vc, tables, qpos)
    row = paged_attention(q, _rows(kc), _rows(vc), tables, qpos,
                          block_size=4)
    np.testing.assert_allclose(np.asarray(row), np.asarray(nat),
                               rtol=1e-6, atol=1e-6)


def test_quantized_rows_pool_matches_head_major_call():
    key, kc, vc, tables, pos = _toy_pool(jax.random.key(4), dh=4)
    kq, ks = quantize_rows(kc, jnp.int8)
    vq, vs = quantize_rows(vc, jnp.int8)
    S, H = tables.shape[0], kc.shape[1]
    q = jax.random.normal(key, (S, H, 1, 4))
    nat = _head_major_call(q, kq, vq, tables, pos[:, None], ks, vs)
    row = paged_attention(q, _rows(kq), _rows(vq), tables, pos[:, None],
                          block_size=4, kscale=_rows(ks), vscale=_rows(vs))
    np.testing.assert_allclose(np.asarray(row), np.asarray(nat),
                               rtol=1e-6, atol=1e-6)


def test_paged_attention_rejects_a_head_major_pool():
    key, kc, vc, tables, pos = _toy_pool(jax.random.key(5))
    q = jax.random.normal(key, (3, 2, 1, 16))
    with pytest.raises(ValueError, match="KVH\\*dh"):
        paged_attention(q, kc, vc, tables, pos[:, None], block_size=4)


@pytest.mark.parametrize("cache_dtype", [None, "int8"])
def test_kernel_hbm_rows_reconcile_with_tick_model(stages, cache_dtype):
    """ISSUE 16 acceptance: the kernel-DERIVED K/V stream bytes (block
    shapes x grid trips, from the traced pallas_calls' own BlockSpecs)
    agree EXACTLY with the tick model's ``decode.kv_gather`` row — which
    equals the dense twin's ``kv_attn_reread`` delta (the pass the fused
    kernel deletes)."""
    from simple_distributed_machine_learning_tpu.analysis.programs import (
        ServeSpec,
        hbm_tick_costs,
        lint_serve,
    )
    sspec = ServeSpec(CFG, n_slots=2, block_size=4,
                      cache_dtype=cache_dtype, attn_kernel="fused",
                      prompt_lens=(4,))
    report = lint_serve(stages, sspec)
    assert report.ok(fail_on="warning"), report.format()
    derived = {}
    for h in report.hbm:
        if h.op == "kernel.kv_stream":
            derived[h.program] = derived.get(h.program, 0) + h.bytes_per_tick
    model = {(h.program, h.op): h.bytes_per_tick
             for h in report.hbm if not h.op.startswith("kernel.")}
    assert derived["paged_decode"] == model[("paged_decode",
                                             "decode.kv_gather")]
    # the dense twin pays the SAME bytes again as the attn reread: the
    # kernel-derived stream equals that deleted delta exactly
    dense = {h.op: h.bytes_per_tick
             for h in hbm_tick_costs(dataclasses.replace(
                 sspec, attn_kernel="dense"))}
    assert derived["paged_decode"] == dense["decode.kv_attn_reread"]


def test_kernel_hbm_mismatch_is_flagged():
    """Seeded drift between the tick model and the traced kernels must
    produce the kernel-hbm.mismatch ERROR (the reconciliation is a gate,
    not a report)."""
    from simple_distributed_machine_learning_tpu.analysis.programs import (
        ServeSpec,
        _reconcile_kernel_hbm,
        hbm_tick_costs,
    )
    from simple_distributed_machine_learning_tpu.analysis.report import (
        HBMCost,
    )
    sspec = ServeSpec(CFG, n_slots=2, block_size=4,
                      attn_kernel="fused")
    model = hbm_tick_costs(sspec)
    want = next(h.bytes_per_tick for h in model
                if h.op == "decode.kv_gather")
    bad = [HBMCost("kernel.kv_stream", "paged_decode", want + 64)]
    findings = _reconcile_kernel_hbm(bad, model, sspec)
    assert any(f.rule == "kernel-hbm.mismatch" for f in findings)
    # and a fused spec whose programs traced NO kernel at all is flagged
    findings = _reconcile_kernel_hbm([], model, sspec)
    assert any(f.rule == "kernel-hbm.mismatch" for f in findings)
    # exact agreement is silent
    good = [HBMCost("kernel.kv_stream", "paged_decode", want)]
    assert not _reconcile_kernel_hbm(good, model, sspec)


# -- grouped-query attention: fewer K/V heads in the pool than query heads ----


@pytest.mark.parametrize("n_q_heads,n_kv_heads,K,dh", [
    (4, 1, 1, 16), (6, 2, 1, 16), (4, 1, 3, 16),
    # the verify width over several heads of 64 in a row, grouped and not
    (8, 2, 4, 64), (4, 4, 4, 64)])
def test_grouped_query_pool_matches_dense_over_a_repeated_head(
        n_q_heads, n_kv_heads, K, dh):
    """The pool holds ``n_kv_heads``; the kernel must give what dense
    attention gives over a pool in which every K/V head is repeated for
    the query heads of its group (head ``h`` reads K/V head ``h //
    group``). Same tolerance as the multi-head test: identical K/V, only
    the accumulation order differs."""
    kq, kc, vc, tables, pos = _toy_pool(jax.random.key(5), H=n_kv_heads,
                                        dh=dh)
    q = jax.random.normal(kq, (3, n_q_heads, K, dh))
    qpos = np.minimum(pos[:, None] + np.arange(K)[None, :],
                      6 * 4 - 1).astype(np.int32)
    out = jax.jit(lambda *a: paged_attention(*a, block_size=4))(
        q, _rows(kc), _rows(vc), jnp.asarray(tables), jnp.asarray(qpos))
    group = n_q_heads // n_kv_heads
    ref = _dense_paged_reference(q, jnp.repeat(kc, group, axis=1),
                                 jnp.repeat(vc, group, axis=1), tables, qpos)
    rtol, atol = attn_tol(jnp.float32)
    assert out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=rtol, atol=atol)


def test_query_heads_must_be_a_multiple_of_the_pools_heads():
    kq, kc, vc, tables, pos = _toy_pool(jax.random.key(6), H=2)
    q = jax.random.normal(kq, (3, 3, 1, 16))
    with pytest.raises(ValueError, match="do not divide"):
        paged_attention(q, _rows(kc), _rows(vc), jnp.asarray(tables),
                        jnp.asarray(pos[:, None]), block_size=4)


# -- a window layer's walk (PR 44) ------------------------


def _ring_call(seed, S, H, KVH, dh, bs, window, ring, K=1, extra=0):
    """Slots of one call over a window layer's RING: slot ``s`` has written
    positions ``0 .. last[s]`` of its own sequence into blocks of a pool of
    ``1 + S * ring`` blocks (logical block ``j`` at entry ``j % ring``; a
    block that lies wholly behind the oldest query's window has been handed
    back: its entry reads TRASH, or already names the block of ``j +
    ring``), and the whole sequence's rows for the dense reference."""
    rng = np.random.default_rng(seed)
    n_phys = 1 + S * ring
    kc = np.zeros((n_phys, bs, KVH * dh), np.float32)
    vc = np.zeros_like(kc)
    # lengths below, at and many times the window, a whole ring and more
    last = np.array(([0, window - 1, window, 3 * window + 1,
                      ring * bs + window // 2, 9 * ring * bs + 5] * S)[:S],
                    np.int64) + extra
    n_seq = int(last.max()) + 1
    keys = rng.standard_normal((S, n_seq, KVH * dh)).astype(np.float32)
    vals = rng.standard_normal((S, n_seq, KVH * dh)).astype(np.float32)
    tables = np.zeros((S, ring), np.int32)
    qpos = np.stack([last - (K - 1 - j) for j in range(K)], axis=1)
    qpos = np.maximum(qpos, 0).astype(np.int32)
    for s in range(S):
        first_live = max(int(qpos[s, 0]) - window + 1, 0) // bs
        for j in range(first_live, int(last[s]) // bs + 1):
            blk = 1 + s * ring + j % ring
            tables[s, j % ring] = blk
            n = min(bs, int(last[s]) + 1 - j * bs)
            kc[blk, :n] = keys[s, j * bs:j * bs + n]
            vc[blk, :n] = vals[s, j * bs:j * bs + n]
    q = rng.standard_normal((S, H, K, dh)).astype(np.float32)
    return q, kc, vc, tables, qpos, keys, vals


def _dense_window_reference(q, keys, vals, qpos, window, KVH):
    """Masked attention over each slot's whole sequence, the window a mask
    built from positions: query ``t`` sees ``t - window < j <= t``."""
    S, H, K, dh = q.shape
    n_seq = keys.shape[1]
    k = keys.reshape(S, n_seq, KVH, dh)
    v = vals.reshape(S, n_seq, KVH, dh)
    group = H // KVH
    out = np.zeros((S, H, K, dh), np.float64)
    for s in range(S):
        back = qpos[s][:, None] - np.arange(n_seq)[None, :]
        seen = (back >= 0) & (back < window)
        for h in range(H):
            sc = q[s, h].astype(np.float64) @ k[s, :, h // group].T.astype(
                np.float64) / math.sqrt(dh)
            sc = np.where(seen, sc, -np.inf)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            out[s, h] = (p / p.sum(-1, keepdims=True)) @ v[s, :, h // group]
    return out


@pytest.mark.parametrize("H,KVH,dh,bs,window,ring,K", [
    (4, 2, 16, 4, 8, 5, 1),        # the toy family of tests/test_cohere2.py
    (4, 2, 16, 4, 8, 5, 3),        # several query rows: the oldest's window
    (32, 2, 8, 16, 64, 6, 1),      # 16 query heads to a K/V head
    (16, 1, 8, 8, 40, 7, 1),       # a window that is no whole blocks
])
def test_window_walk_matches_the_dense_mask_over_a_ring(H, KVH, dh, bs,
                                                        window, ring, K):
    """``window=``: slots below, at and many rings past the window in one
    call, each against attention over its WHOLE sequence under the
    position mask; what lies behind the window is not in the pool at all
    (its entries read TRASH or a newer block), so a kernel that looked any
    of it up would read zeros or the wrong rows."""
    q, kc, vc, tables, qpos, keys, vals = _ring_call(
        5, 6, H, KVH, dh, bs, window, ring, K)
    out = paged_attention(jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
                          jnp.asarray(tables), jnp.asarray(qpos),
                          block_size=bs, window=window)
    want = _dense_window_reference(q, keys, vals, qpos, window, KVH)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)
    # the window's edge: one key more and the comparison fails
    wider = _dense_window_reference(q, keys, vals, qpos, window + 1, KVH)
    assert np.abs(wider[3:] - want[3:]).max() > 1e-3


def test_window_walk_over_a_full_layers_table_of_2048_entries():
    """A table as long as the cell's full layer has (2,048 entries of 16,
    here a window layer whose ring is the whole table: ``window + chunk``
    past ``max_len``), 16 query heads to a K/V head and 8 K/V heads, a slot
    30,000 positions deep: the walk starts 1,800 blocks in, and ``window=
    None`` over the same table reads every position (the full layer)."""
    H, KVH, dh, bs, NB, window = 128, 8, 8, 16, 2048, 4096
    rng = np.random.default_rng(9)
    last = np.array([29_999, 100, 4_095], np.int32)
    S = len(last)
    n_phys = 1 + int(sum(p // bs + 1 for p in last))
    kc = rng.standard_normal((n_phys, bs, KVH * dh)).astype(np.float32)
    vc = rng.standard_normal((n_phys, bs, KVH * dh)).astype(np.float32)
    tables = np.zeros((S, NB), np.int32)
    keys = np.zeros((S, int(last.max()) + 1, KVH * dh), np.float32)
    vals = np.zeros_like(keys)
    at = 1
    for s in range(S):
        n = int(last[s]) // bs + 1
        tables[s, :n] = np.arange(at, at + n)
        keys[s, :n * bs] = kc[at:at + n].reshape(n * bs, -1)[
            :keys.shape[1]] if n * bs <= keys.shape[1] else kc[
                at:at + n].reshape(n * bs, -1)[:keys.shape[1]]
        vals[s, :min(n * bs, vals.shape[1])] = vc[at:at + n].reshape(
            n * bs, -1)[:vals.shape[1]]
        at += n
    q = rng.standard_normal((S, H, 1, dh)).astype(np.float32)
    qpos = last[:, None]
    for w in (window, None):
        out = paged_attention(jnp.asarray(q), jnp.asarray(kc),
                              jnp.asarray(vc), jnp.asarray(tables),
                              jnp.asarray(qpos), block_size=bs, window=w)
        want = _dense_window_reference(q, keys, vals, qpos, w or 10 ** 9,
                                       KVH)
        np.testing.assert_allclose(np.asarray(out), want, rtol=3e-5,
                                   atol=3e-5)


#: sha256 of ``str(jax.make_jaxpr(...))`` of the kernel at two cells' decode
#: shapes, made on the PARENT commit (2681516, PR 43) by the same lines as
#: the test below: what ``window=None`` has to trace, operation for operation
_PARENT_JAXPR = {
    # gpt2-large.serve-closed: 16 slots, 20 heads of 64, tables of 64
    (16, 20, 20, 64, 64, 513):
        "df8aaecb2ac48f57c07a21d3b90d6676de46ffc4dc26fef8480aaf4f1fe24698",
    # zaya1-8b.serve-context-closed: 24 slots, 8 heads over 2 of 128, 512
    (24, 8, 2, 128, 512, 12289):
        "8e165732e7aa69a58dba5d80505cc2d534dc2dc8097d2210935f69eadaf78d5f",
}


@pytest.mark.parametrize("shape", list(_PARENT_JAXPR))
def test_without_a_window_the_kernel_traces_what_the_parent_traced(shape):
    """The five serve cells' ``setup_s`` and ``tpot_p95_ms`` ride on this
    kernel: with ``window=None`` its jaxpr (the Pallas call's body
    included) is the parent commit's to the letter, at the shapes of the
    GPT cell and of the long narrow cache. And with a window it is not."""
    import hashlib
    import re

    from simple_distributed_machine_learning_tpu.ops import (
        paged_attention as pa,
    )
    S, H, KVH, dh, NB, n_phys = shape
    sd = jax.ShapeDtypeStruct
    args = (sd((S, H, 1, dh), jnp.float32),
            sd((n_phys, 16, KVH * dh), jnp.bfloat16),
            sd((n_phys, 16, KVH * dh), jnp.bfloat16),
            sd((S, NB), jnp.int32), sd((S, 1), jnp.int32))

    def text(**kw):
        fn = lambda q, k, v, t, p: pa._paged_attention.__wrapped__(  # noqa: E731
            q, k, v, t, p, None, None, bs=16, interpret=False, **kw)
        return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))

    plain = text()
    assert hashlib.sha256(plain.encode()).hexdigest() == _PARENT_JAXPR[shape]
    assert text(window=None) == plain
    assert text(window=64) != plain


# -- one stream: an absorbed latent cache's row (ISSUE 49) --------------------


def _one_stream_call(seed, H, D, v_lanes, bs, NB, last, K=1, dtype=np.float32):
    """A pool of rows ``[n_phys, bs, D]``, every slot's blocks in a shuffled
    order, and queries ``[S, H, K, D]`` at the ``K`` newest positions."""
    rng = np.random.default_rng(seed)
    last = np.asarray(last, np.int32)
    S = len(last)
    n_phys = 1 + S * NB
    kc = rng.standard_normal((n_phys, bs, D)).astype(dtype)
    tables = 1 + rng.permutation(S * NB).reshape(S, NB).astype(np.int32)
    q = rng.standard_normal((S, H, K, D)).astype(np.float32)
    qpos = last[:, None] - np.arange(K - 1, -1, -1, dtype=np.int32)[None]
    return q, kc, tables, qpos


@pytest.mark.parametrize("H,D,v_lanes,bs,NB,K,pool", [
    (4, 48, 32, 4, 6, 1, "float32"),       # the toy family's row: 32 + 16
    (32, 640, 512, 16, 8, 1, "bfloat16"),  # the cell's: 32 heads, 576 in 640
    (2, 128, 128, 16, 3, 3, "float32"),    # every lane a value; three rows
])
def test_one_stream_equals_two_streams_over_the_same_rows(H, D, v_lanes, bs,
                                                          NB, K, pool):
    """``vc=None``: the values are the leading ``v_lanes`` lanes of the key
    rows. The same call with a SECOND buffer that holds those lanes (what a
    pool with a value buffer would copy beside the keys) gives the same
    numbers: as multi-query attention of ``H`` heads over one K/V head of
    ``D`` lanes, whose output's leading ``v_lanes`` lanes are kept."""
    last = [bs * NB - 1, 0, bs + 1, 2 * bs - 1][:3]
    q, kc, tables, qpos = _one_stream_call(11, H, D, v_lanes, bs, NB, last, K,
                                           jnp.dtype(pool))
    qpos = np.maximum(qpos, 0)
    scale = 0.37
    one = paged_attention(jnp.asarray(q), jnp.asarray(kc), None,
                          jnp.asarray(tables), jnp.asarray(qpos),
                          block_size=bs, v_lanes=v_lanes, scale=scale)
    assert one.shape == (len(last), H, K, v_lanes)
    # two streams: the kernel's own scale is 1 / sqrt(D)
    vc = jnp.asarray(kc).at[..., v_lanes:].set(0)
    two = paged_attention(jnp.asarray(q) * (scale * math.sqrt(D)),
                          jnp.asarray(kc), vc, jnp.asarray(tables),
                          jnp.asarray(qpos), block_size=bs)[..., :v_lanes]
    np.testing.assert_allclose(np.asarray(one), np.asarray(two), rtol=2e-5,
                               atol=2e-5)
    # and both are the dense softmax over each slot's own positions
    rows = np.asarray(jnp.asarray(kc).astype(jnp.float32))
    for s in range(len(last)):
        seq = rows[tables[s]].reshape(NB * bs, D)
        for j in range(K):
            n = int(qpos[s, j]) + 1
            sc = q[s, :, j] @ seq[:n].T * scale
            p = np.exp(sc - sc.max(-1, keepdims=True))
            want = (p / p.sum(-1, keepdims=True)) @ seq[:n, :v_lanes]
            np.testing.assert_allclose(np.asarray(one)[s, :, j], want,
                                       rtol=3e-5, atol=3e-5)


def test_one_stream_copies_one_stream():
    """The Pallas call of ``vc=None`` has ONE semaphore row and starts and
    waits for one stream's copies: handing the same buffer over twice (as
    ``kc`` and ``vc``) has two and copies every block twice."""
    q, kc, tables, qpos = _one_stream_call(12, 4, 128, 64, 16, 4, [40, 3])

    def counts(fn):
        eqns = list(_kernel_eqns(jax.make_jaxpr(fn)(q, kc, tables,
                                                    qpos).jaxpr))
        (call,) = [e for e, _ in eqns if e.primitive.name == "pallas_call"]
        sems = [tuple(v.aval.shape) for v in call.params["jaxpr"].invars
                if "sem" in str(v.aval).lower()]
        return sems, *(sum(e.primitive.name == name for e, _ in eqns)
                       for name in ("dma_start", "dma_wait"))

    assert counts(lambda q, k, t, p: paged_attention(
        q, k, None, t, p, block_size=16, v_lanes=64, scale=1.0)) == (
            [(1, 2)], 3, 1)
    assert counts(lambda q, k, t, p: paged_attention(
        q, k, k, t, p, block_size=16)) == ([(2, 2)], 6, 2)


def test_one_stream_refuses_what_it_does_not_take():
    q, kc, tables, qpos = _one_stream_call(13, 2, 128, 64, 16, 2, [5])
    with pytest.raises(ValueError, match="takes v_lanes= and scale="):
        paged_attention(q, kc, None, tables, qpos, block_size=16)
    with pytest.raises(ValueError, match="takes v_lanes= and scale="):
        paged_attention(q, kc, None, tables, qpos, block_size=16, v_lanes=64,
                        scale=1.0, window=8)
    with pytest.raises(ValueError, match="belong to one stream"):
        paged_attention(q, kc, kc, tables, qpos, block_size=16, v_lanes=64)
    with pytest.raises(ValueError, match="one stream: kc must be"):
        paged_attention(q[..., :64], kc, None, tables, qpos, block_size=16,
                        v_lanes=64, scale=1.0)
