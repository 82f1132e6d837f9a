"""The paged KV-cache pool: the serving engine's one device-resident state.

:class:`PagedKVPool` is the block-table paged layout (vLLM-style). A *slot*
is the home of one in-flight sequence: its position counter, its pending
token and its block table. K/V lives in a global pool of fixed-size
*blocks*, one buffer per layer (``[n_blocks+1, block_size, H*dh]``: a
position's heads side by side in one row, the layout the programs scatter,
gather and attend in, so a tick writes its rows in place on the donated
buffers and hands the attention kernel a layer's buffer untouched; physical
block 0 is the trash block inactive slots write into). A per-slot block
table maps logical block ``j`` (positions ``[j*bs, (j+1)*bs)``) to a
physical block, blocks are allocated on demand as positions advance, and
prefixes are shared copy-on-write: requests with a common prompt prefix
reference the same physical blocks until they diverge, and the first write
into a shared block copies it first. A sequence's memory footprint is
``ceil(rows/block_size)`` blocks, not a ``max_len`` row reserved whether the
sequence uses it or not, so concurrency is a function of the tokens
actually resident.

Per-slot state (``state_shapes``): device buffers beside the blocks
(``pool.state``, one ``[n_slots, ...]`` array per leaf, indexed by slot,
donated through the model's programs with the blocks). Two things live
there, and they are two facts. Every model of this package keeps each
slot's newest token and sampling key there
(``models/serving.py::PagedServing``; ``last_token`` here then trails the
device by the tick in flight): a token and a key are a request's own, a
chunk seats them, and nothing about the blocks changes. ``recurrent`` says
the other thing: a model with state-space layers (``models/jamba.py``)
also keeps, beside the K/V blocks of its few attention layers, a
fixed-size recurrent buffer per slot and layer — no blocks, no length,
nothing to share. Such state summarises a whole prefix in place, so a
``recurrent`` pool matches and registers NO prompt prefix (a shared block
would come without the state that belongs to it), has no host tier and no
tensor-parallel placement: every bind starts at position 0, where the
model's prefill zeroes the slot's rows — a bound slot never sees its last
occupant's state. Requests that WOULD have matched are counted
(``prefix_declined_total``).

A model whose step works on a BLOCK of positions (``step_rows > 1``,
``models/serving.py::PagedServing.block``: generation by diffusion over
blocks) writes whole blocks of ``step_rows`` rows: a sequence's budget is
its length rounded up to one, the host keeps for each slot how many forwards
its block in progress has had and how many it takes (``block_fwd`` /
``block_total``), and a K/V row depends on the tokens up to the END of its
block, so only prefixes of whole pool blocks (a multiple of ``step_rows``)
are registered and matched.

Layer kinds (``windows``, ``models/serving.py::PagedServing.windows``): a
model whose attention layers do not all look back equally far has layers of
several KINDS in one pool. Layers of one window value are a GROUP; the
layers that attend every earlier position are the FULL group, and it is
everything said above (a pool without windows has that group alone and is
the pool as it always was). A window group (``PagedKVPool._WindowGroup``)
has its own block count (``n_window_blocks``: its layers' buffers are sized
by the window, never by ``max_len``), its own free list, its own reservation
at admission (``min(rows, window + chunk_rows)`` positions and a block) and
per slot a table of its own, a RING: logical block ``j`` of the sequence
lives at entry ``j % ring``, ``ring`` the blocks that ``window +
chunk_rows`` positions can touch. A position ``p`` is dead in the group once
``p <= q - window`` for the OLDEST query ``q`` still to run (a chunk's
first row, a decode's one row); a block whose positions are all dead is
handed back (its ring entry reads TRASH) before the slot's next allocation
in the group, so that no program is handed a block that lies wholly behind
its window. A donor's window blocks are gone when a second request could
share them, so such a pool registers and matches no prefix (counted, as
for recurrent state), and has no host tier, no quantized dtype, no
tensor-parallel placement and no block steps. What a row HOLDS is the
programs' affair, and the one model with window layers does not store the
published lanes there: ``models/cohere2.py``'s programs write a window
group's K rows with every head's lanes in the order their rotation reads
(even lanes first, then odd: its docstring, "Serving"), while V rows and
the full group's K rows are the published ones. The programs are the only
readers of a row; whoever reads the pool by hand has to know.

The slot free list is invariant-guarded: acquiring an occupied slot or
releasing a free one raises instead of silently corrupting a neighbor's
cache, and the same discipline covers blocks — no double allocation, no
double free, no write into a block another sequence still references (the
scheduler invariants pinned in tests/test_serve.py).

Stale-write safety: the batched decode step writes K/V for EVERY slot,
occupied or not, and a retired slot's stale block-table entries may point
at physical blocks REUSED by a live request, so a garbage write there
would corrupt a neighbor. The engine therefore routes every non-decoding
slot's tick write to the trash block (``PagedKVPool.TRASH``, position 0),
which no block table ever references.

Host offload tier (``host_cache_blocks > 0``): LRU eviction of a
cached prefix block demotes its rows to host RAM instead of discarding
them, growing the effective prefix cache past HBM. The router probes the
host registry too (:meth:`PagedKVPool.host_prefix_len`), and an affinity
hit on a host-resident prefix starts an **async upload**
(:meth:`PagedKVPool.prefetch`) that lands after ``prefetch_ticks`` engine
ticks (:meth:`PagedKVPool.advance_transfers`). Safety model: the uploaded
keys are registered — and therefore visible to admission's prefix probe —
only at COMPLETION, and ``can_admit`` additionally blocks a request whose
prefix an in-flight upload covers, so a request can never board against
half-uploaded blocks (it waits one or two ticks and then shares the real
ones). Uploads draw from the FREE list only, never by evicting live or
cached blocks, and never below the pool's outstanding reservations — a
prefetch can be refused (a miss), but it can never thrash the working set
or strand an admitted sequence's allocation.
"""

from __future__ import annotations

import collections
import math

import numpy as np


def kv_block_bytes(n_layers: int, n_heads: int, block_size: int,
                   head_dim: int, cache_dtype=None, streams: int = 2) -> int:
    """Bytes one physical K/V block pins across every layer (K and V).
    ``n_layers`` / ``n_heads`` are the CACHE's: the layers that attend and
    their K/V heads (a grouped-query model's query heads are more).
    ``streams``: 2, a key and a value buffer a layer; 1 where a layer's one
    row holds both (``PagedServing.value_lanes``).

    The ONE copy of the formula: :class:`PagedKVPool` sizes its
    ``bytes_per_block`` (and therefore the ``serve_kv_bytes_resident``
    gauge) from it, and the analyzer's HBM-bytes-per-tick model
    (``analysis/programs.py``) predicts against it — the cross-check in
    tests/test_analysis_serve.py holds because both sides share this.

    QUANTIZED dtypes (int8/fp8, ``models/serving.py::is_quantized_dtype``)
    add the per-block scale planes to the bill: one f32 scale per
    (position, head) row, for K and for V — the honest block footprint,
    so a fixed-byte pool sizing (``n_blocks_for_bytes``) and the
    resident-bytes gauge can never claim the scale planes are free."""
    import jax.numpy as jnp

    from simple_distributed_machine_learning_tpu.models.serving import (
        is_quantized_dtype,
        storage_dtype,
    )
    cd = storage_dtype(cache_dtype)
    bytes_ = (streams * n_layers * n_heads * block_size * head_dim
              * jnp.dtype(cd).itemsize)
    if is_quantized_dtype(cache_dtype):
        bytes_ += streams * n_layers * n_heads * block_size * 4  # f32 scales
    return int(bytes_)


def n_blocks_for_bytes(budget_bytes: int, n_layers: int, n_heads: int,
                       block_size: int, head_dim: int,
                       cache_dtype=None) -> int:
    """Physical blocks a ``budget_bytes`` K/V budget funds — the
    fixed-KV-bytes sizing rule the ``bench.py --serve`` quantized
    concurrency sweep uses (an int8 pool fits ~4x the f32 blocks of the
    same budget, scale planes already billed)."""
    per = kv_block_bytes(n_layers, n_heads, block_size, head_dim,
                         cache_dtype)
    return max(1, budget_bytes // per)


def _bind_seq_of(request) -> np.ndarray:
    """The sequence admission must budget/prefill for: ``resume_seq`` when
    the request tracks preemption state, its plain prompt otherwise (raw
    duck-typed requests in tests)."""
    seq = getattr(request, "resume_seq", None)
    return request.prompt if seq is None else seq


def _bind_budget_of(request) -> int:
    budget = getattr(request, "resume_max_new", None)
    return request.max_new_tokens if budget is None else budget


def _ns_of(request) -> bytes:
    """The request's prefix-cache NAMESPACE: K/V computed under one LoRA
    adapter is wrong for every other, so registry keys are scoped by the
    request's adapter. The engine resolves the VERSION-QUALIFIED
    namespace onto ``_prefix_ns`` (AdapterStore.namespace_of — a hot-swap
    changes it, orphaning the old version's keys); a pool driven without
    the engine's adapter plumbing falls back to the bare name
    (serve/adapters.py::adapter_namespace, imported lazily so a pool
    without adapters never touches the adapter module). Base-model
    requests get the EMPTY namespace: their keys stay byte-identical to
    the pre-adapter registry."""
    ns = getattr(request, "_prefix_ns", None)
    if ns is not None:
        return ns
    adapter = getattr(request, "adapter", None)
    if adapter is None:
        return b""
    from simple_distributed_machine_learning_tpu.serve.adapters import (
        adapter_namespace,
    )
    return adapter_namespace(adapter)


def _check_tp(n_heads: int, tp: int) -> int:
    """Pool-side TP validation: the K/V head axis is what the serving
    shard_map splits, so ``tp`` must divide ``n_heads``. Byte accounting
    (``bytes_per_block``, ``serve_kv_bytes_resident``) is PER SHARD —
    the per-chip resident bytes, the number TP exists to shrink."""
    if tp < 1 or n_heads % tp:
        raise ValueError(
            f"tp={tp} must be >= 1 and divide the K/V head axis "
            f"(n_heads={n_heads})")
    return tp


class PagedKVPool:
    """Block-table paged K/V pool with prefix sharing; see module docstring.

    Slot accounting: the free-slot list with invariant guards, and the
    per-slot decode state (position counters and last-token values — tiny
    host arrays fed into every compiled tick; the authoritative copy lives
    here, not on device).

    Block lifecycle: a physical block is *free* (on the free list), *live*
    (``ref > 0`` request references), or *cached* (``ref == 0`` but holding
    registered prefix content — reclaimable, evicted LRU when the free list
    runs dry). ``ref`` counts live REQUEST references only; the registry's
    interest is the cached flag, so a block can outlive its last request
    exactly as long as the pool isn't under pressure.

    Copy-on-write: writers must call :meth:`ensure_writable` before landing
    K/V at a position. A block referenced by more than one request is copied
    first (the caller performs the device copy of the ``(src, dst)`` pair
    this returns) — UNLESS the writing slot is the block's original
    allocator: sharers trust only the rows below their registered fill and
    copy before their own first write, so the allocator's tail rows land in
    place even while shared (no copy, and no unbudgeted reservation draw).
    A block referenced once is written in place, dropping any registered
    prefix whose covered rows the write would clobber.

    Reservation accounting makes on-demand allocation safe: admission
    reserves this sequence's worst-case block budget (its total rows minus
    fully-shared blocks, which are never written), and every later
    allocation draws from that reservation — so a decode tick can never find
    the pool empty, and admission (``can_admit``) blocks exactly while
    ``free + reclaimable - reserved`` is short.
    """

    TRASH = 0   # physical block 0: the garbage sink for non-decoding slots

    class _WindowGroup:
        """The layers of one window value in a :class:`PagedKVPool` (module
        docstring, "Layer kinds"): which of the pool's buffers they are, their
        blocks (``1 .. n_blocks``; block 0 is the group's trash block too),
        and per slot the ring table, the first live and the next logical
        block, and what is left of the admission's reservation."""

        def __init__(self, window: int, layers: tuple, ring: int,
                     n_blocks: int, n_slots: int,
                     bytes_per_block: int) -> None:
            self.window, self.layers, self.ring = window, layers, ring
            self.n_blocks = n_blocks
            self.bytes_per_block = bytes_per_block
            self.free: list[int] = list(range(1, n_blocks + 1))[::-1]
            self.tables = np.full((n_slots, ring), PagedKVPool.TRASH, np.int32)
            # logical blocks [first, next) of a slot's sequence hold a block
            self.first = np.zeros(n_slots, np.int64)
            self.next = np.zeros(n_slots, np.int64)
            self.resv = np.zeros(n_slots, np.int64)
            self.reserved = 0
            self.released_total = 0

        @property
        def blocks_in_use(self) -> int:
            return self.n_blocks - len(self.free)

        @property
        def blocks_available(self) -> int:
            return len(self.free) - self.reserved

        def budget(self, rows: int, block_size: int) -> int:
            """The most blocks a sequence of ``rows`` positions holds at
            once."""
            return min(math.ceil(rows / block_size), self.ring)

        def begin(self, slot: int, budget: int) -> None:
            if budget > self.blocks_available:
                raise RuntimeError(
                    f"begin_seq short of window blocks (need {budget}, have "
                    f"{self.blocks_available}) — the scheduler must check "
                    f"can_admit first")
            self.resv[slot] = budget
            self.reserved += budget

        def _hand_back(self, slot: int, upto: int) -> int:
            """Free ``slot``'s logical blocks before ``upto``; how many."""
            first = int(self.first[slot])
            for j in range(first, upto):
                e = j % self.ring
                self.free.append(int(self.tables[slot, e]))
                self.tables[slot, e] = PagedKVPool.TRASH
            self.first[slot] = max(first, upto)
            return max(0, upto - first)

        def end(self, slot: int) -> None:
            self._hand_back(slot, int(self.next[slot]))
            self.first[slot] = self.next[slot] = 0
            self.reserved -= int(self.resv[slot])
            self.resv[slot] = 0

        def ensure(self, slot: int, position: int, oldest: int,
                   block_size: int) -> None:
            """Hand back ``slot``'s blocks that lie wholly behind the window of
            the query at ``oldest``, then give ``position``'s block a home."""
            n = self._hand_back(slot, min(
                (oldest - self.window + 1) // block_size,
                int(self.next[slot])))
            # what is handed back may be allocated again: the budget is the
            # most the sequence holds at once
            self.resv[slot] += n
            self.reserved += n
            self.released_total += n
            j = position // block_size
            if j > self.next[slot]:     # pragma: no cover - guard
                raise RuntimeError(
                    f"slot {slot} write at position {position} skips logical "
                    f"block {self.next[slot]} — positions must advance "
                    f"contiguously")
            if j < self.next[slot]:
                return
            if self.resv[slot] <= 0 or j - self.first[slot] >= self.ring:
                raise RuntimeError(     # pragma: no cover - guard
                    f"slot {slot} allocates past its window reservation: the "
                    f"queries of one program reach back over more than "
                    f"window + chunk_rows positions")
            self.tables[slot, j % self.ring] = self.free.pop()
            self.next[slot] = j + 1
            self.resv[slot] -= 1
            self.reserved -= 1

    def __init__(self, n_layers: int, n_slots: int, n_heads: int,
                 max_len: int, head_dim: int, cache_dtype=None,
                 block_size: int = 16, n_blocks: int | None = None,
                 tp: int = 1, host_cache_blocks: int = 0,
                 prefetch_ticks: int = 1, state_shapes=(),
                 recurrent: bool = False, step_rows: int = 1,
                 windows: tuple = (), n_window_blocks: int | None = None,
                 chunk_rows: int | None = None,
                 value_lanes: int | None = None) -> None:
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2 (a prompt token plus a "
                             f"generated one), got {max_len}")
        self.n_slots = n_slots
        self.max_len = max_len
        self.positions = np.zeros(n_slots, np.int32)
        self.last_token = np.zeros(n_slots, np.int32)
        # positions a slot's step works on (module docstring); with more
        # than one: the forwards the slot's block has had and takes
        self.step_rows = int(step_rows)
        self.block_fwd = np.zeros(n_slots, np.int32)
        self.block_total = np.zeros(n_slots, np.int32)
        self._occupant: list[int | None] = [None] * n_slots
        self._free: list[int] = list(range(n_slots))[::-1]   # pop() -> slot 0
        self.prefix_declined_total = 0
        self.tp = _check_tp(n_heads, tp)
        import jax

        # per-slot buffers beside the blocks (module docstring, "Per-slot
        # state"): one array per leaf, not one over the layers, so a
        # layer's update never copies its neighbours. ``recurrent``: some
        # of them summarise the slot's whole prefix (the model's
        # ``cfg.recurrent_state``)
        leaves = jax.tree.leaves(state_shapes)
        self.has_state = bool(leaves)
        self.recurrent = bool(recurrent)
        if self.recurrent and (host_cache_blocks or self.tp > 1):
            raise ValueError(
                "recurrent state has no host offload tier and no "
                "tensor-parallel placement (host_cache_blocks / tp)")
        if host_cache_blocks < 0:
            raise ValueError(
                f"host_cache_blocks must be >= 0, got {host_cache_blocks}")
        if host_cache_blocks and self.tp > 1:
            raise ValueError(
                "host_cache_blocks with tp > 1 is not supported: demotion "
                "copies device rows to host per pool, and a sharded pool "
                "would demote per-shard fragments the prefetch upload "
                "cannot re-place")
        if prefetch_ticks < 1:
            raise ValueError(
                f"prefetch_ticks must be >= 1, got {prefetch_ticks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.block_size = block_size
        self.blocks_per_seq = math.ceil(max_len / block_size)
        if n_blocks is None:
            # default: every slot can reach max_len
            n_blocks = n_slots * self.blocks_per_seq
        if n_blocks < self.blocks_per_seq:
            raise ValueError(
                f"n_blocks={n_blocks} cannot hold even one full sequence "
                f"({self.blocks_per_seq} blocks of {block_size} for "
                f"max_len={max_len})")
        self.n_blocks = n_blocks
        import jax.numpy as jnp

        from simple_distributed_machine_learning_tpu.models.serving import (
            QuantKV,
            check_cache_quantization,
            is_quantized_dtype,
            storage_dtype,
        )
        check_cache_quantization(cache_dtype, "PagedKVPool", paged=True)
        cd = storage_dtype(cache_dtype)
        self.cache_dtype = cd
        self.quantized = is_quantized_dtype(cache_dtype)
        # one stream a layer (``PagedServing.value_lanes``): a position's
        # one row holds its values too, and there is no value buffer
        self.value_lanes = value_lanes
        streams = 2 if value_lanes is None else 1
        if value_lanes is not None and (
                self.quantized or any(w is not None for w in windows)
                or host_cache_blocks or self.tp > 1
                or not 0 < value_lanes <= n_heads * head_dim):
            raise ValueError(
                f"a pool without a value buffer (value_lanes={value_lanes} "
                f"of the row's {n_heads * head_dim}) takes no quantized "
                f"cache_dtype (QuantKV's scale planes are a head's), no "
                f"window group, no host tier and no tensor-parallel "
                f"placement")
        # layer kinds (module docstring): ``windows[li]`` is layer li's
        # window in positions, None (or no entry) a full layer
        windows = tuple(windows) or (None,) * n_layers
        if len(windows) != n_layers or any(
                w is not None and w < 1 for w in windows):
            raise ValueError(
                f"windows must name each of the {n_layers} K/V layers: "
                f"None (full) or a window of >= 1 positions, got {windows}")
        self.windows = windows
        self.windowed = any(w is not None for w in windows)
        # neither kind of pool registers or matches a prompt prefix
        self._no_prefix = self.recurrent or self.windowed
        if self.windowed:
            self._refuse_for_window_layers(host_cache_blocks, cache_dtype,
                                           self.tp, self.step_rows)
        # the most positions one program writes, so the most its queries
        # reach back beyond a window: a prefill chunk's rows
        chunk_rows = max_len if chunk_rows is None else min(chunk_rows,
                                                            max_len)
        self.window_groups: list[PagedKVPool._WindowGroup] = []
        for w in sorted({w for w in windows if w is not None}):
            layers = tuple(li for li, x in enumerate(windows) if x == w)
            ring = min(self.blocks_per_seq,
                       math.ceil((w + chunk_rows) / block_size) + 1)
            n = n_slots * ring if n_window_blocks is None else n_window_blocks
            if n < ring:
                raise ValueError(
                    f"n_window_blocks={n} cannot hold even one sequence's "
                    f"window ({ring} blocks of {block_size} for window {w} "
                    f"and chunks of {chunk_rows})")
            self.window_groups.append(self._WindowGroup(
                w, layers, ring, n, n_slots, kv_block_bytes(
                    len(layers), n_heads, block_size, head_dim, cd,
                    streams)))
        n_full = sum(w is None for w in windows)

        # +1: physical block 0 is the trash block, never allocated. One
        # buffer a layer, a position's heads in one row: a layer's write
        # touches no other layer, and no program re-lays a buffer out. A
        # window layer's buffer has its group's blocks, not the pool's
        def layer(li: int):
            g = self._group_of(li)
            shape = ((n_blocks if g is None else g.n_blocks) + 1, block_size,
                     n_heads * head_dim)
            if not self.quantized:
                return jnp.zeros(shape, cd)
            # narrow block data + per-(position, head) f32 scale planes as
            # ONE pytree buffer per layer (models/serving.py::QuantKV): every
            # compiled step, the CoW copy, donation and TP placement
            # thread the pair together
            return QuantKV(jnp.zeros(shape, cd),
                           jnp.zeros((*shape[:2], n_heads), jnp.float32))

        self.kc = tuple(layer(li) for li in range(n_layers))
        self.vc = (tuple(layer(li) for li in range(n_layers))
                   if value_lanes is None else ())
        self.state = jax.tree.map(
            lambda sd: jnp.zeros((n_slots, *sd.shape), sd.dtype),
            state_shapes)
        self.state_bytes_per_slot = sum(
            math.prod(sd.shape) * jnp.dtype(sd.dtype).itemsize
            for sd in leaves)
        # first-block keys of prompts this pool would have registered,
        # newest last and no more of them than blocks: what
        # ``prefix_declined_total`` is counted against
        self._would_be: collections.OrderedDict[bytes, None] = (
            collections.OrderedDict())
        # PER-SHARD bytes (heads split tp ways by the TP serving programs):
        # the gauge tracks what one chip actually pins, which is the number
        # TP sharding exists to shrink — and what the analyzer's
        # predict_kv_bytes_resident must agree with per shard
        # (the FULL group's layers: a window group bills its own)
        self.bytes_per_block = kv_block_bytes(n_full, n_heads // self.tp,
                                              block_size, head_dim, cd,
                                              streams)
        # block bookkeeping (host-side, authoritative)
        self.ref = np.zeros(n_blocks + 1, np.int64)
        self._free_blocks: list[int] = list(range(1, n_blocks + 1))[::-1]
        self._cached: dict[int, set[bytes]] = {}       # block -> prefix keys
        self._prefix: dict[bytes, tuple[int, int]] = {}  # key -> (block, fill)
        # bumped on every _prefix mutation (register/drop/evict): versions
        # the per-request probe memo in _probe_cached
        self._registry_epoch = 0
        # block -> the slot that ALLOCATED it and may still write it in
        # place while sharers hold references (see ensure_writable):
        # sharers only ever trust rows below their registered fill, and
        # they copy-on-write before their own first write, so the
        # writer's tail rows can land in place without a copy — and
        # without consuming a reservation its admission budget never
        # included (the overrun guard tests/test_paged_attention.py's
        # mid-decode sharing scenario exposed)
        self._block_writer: dict[int, int] = {}
        self._lru: collections.OrderedDict[int, None] = (
            collections.OrderedDict())                 # reclaimable, LRU order
        self._reserved = 0
        # per-slot sequence state
        self.tables: list[list[int]] = [[] for _ in range(n_slots)]
        self._resv = np.zeros(n_slots, np.int64)
        # per-slot prefix-cache namespace, set at bind: register_prefix
        # publishes this slot's blocks under the SAME adapter scope its
        # probe matched in, so cross-tenant K/V sharing is structurally
        # impossible (serve/adapters.py)
        self._slot_ns: list[bytes] = [b""] * n_slots
        # lifetime counters (ServeMetrics reads the deltas)
        self.prefix_hit_blocks_total = 0
        self.cow_copies_total = 0
        self.evictions_total = 0
        # -- host offload tier (module docstring, "Host offload tier") ----
        self.host_cache_blocks = host_cache_blocks
        self.prefetch_ticks = prefetch_ticks
        # host_id -> {"keys": {key: fill}, "kc": ..., "vc": ...} where
        # kc/vc are host (numpy) pytrees of one block's rows, LRU order
        self._host: collections.OrderedDict[int, dict] = (
            collections.OrderedDict())
        self._host_prefix: dict[bytes, tuple[int, int]] = {}
        self._next_host_id = 0
        # in-flight uploads: {"entries": [(key, fill, host_id)],
        # "blocks": [phys], "ticks_left": int}
        self._inflight: list[dict] = []
        self.host_demotes_total = 0
        self.host_promotes_total = 0
        self.host_evictions_total = 0
        self.host_prefetch_hits_total = 0
        self.host_prefetch_misses_total = 0
        self.host_transfer_bytes_total = 0
        if host_cache_blocks:
            from simple_distributed_machine_learning_tpu.models.gpt import (
                make_paged_block_write,
            )
            self._write_block = make_paged_block_write()

    # -- layer kinds -------------------------------------------------------

    @staticmethod
    def _refuse_for_window_layers(host_cache_blocks, cache_dtype, tp,
                                  step_rows) -> None:
        """What a pool with a window group refuses, by name (module
        docstring, "Layer kinds")."""
        from simple_distributed_machine_learning_tpu.models.serving import (
            is_quantized_dtype,
        )
        for name, asked, reason in (
                ("host_cache_blocks", bool(host_cache_blocks),
                 "the host offload tier demotes prefix blocks, and a window "
                 "layer has handed its share of a prefix back"),
                ("a quantized cache_dtype", is_quantized_dtype(cache_dtype),
                 "the window walk of ops/paged_attention.py has no scale "
                 "planes: use float32 or bfloat16"),
                ("tp > 1", tp > 1,
                 "the groups' buffers have no sharded placement"),
                ("step_rows > 1 (block steps)", step_rows > 1,
                 "a block in progress is rewritten, and a window group "
                 "hands blocks back by the oldest query alone")):
            if asked:
                raise ValueError(
                    f"{name} is not available with a pool that has window "
                    f"layers: {reason}")

    def _group_of(self, layer: int):
        """Layer ``layer``'s window group, ``None`` for a full layer."""
        w = self.windows[layer]
        return None if w is None else next(
            g for g in self.window_groups if g.window == w)

    @property
    def table_width(self) -> int:
        """Entries of :meth:`device_table`'s row: the full group's table,
        then each window group's ring."""
        return self.blocks_per_seq + sum(g.ring for g in self.window_groups)

    @property
    def window_released_total(self) -> int:
        """Blocks handed back behind a window in the pool's life."""
        return sum(g.released_total for g in self.window_groups)

    # -- occupancy accounting ---------------------------------------------

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_active(self) -> int:
        return self.n_slots - len(self._free)

    def active_slots(self) -> list[int]:
        return [s for s, r in enumerate(self._occupant) if r is not None]

    def occupant(self, slot: int) -> int | None:
        return self._occupant[slot]

    def acquire(self, rid: int) -> int:
        """Claim a free slot for request ``rid``; raises when full or on a
        double-occupancy attempt (the invariant, not a best-effort)."""
        if not self._free:
            raise RuntimeError("slot acquire on a full pool — the scheduler "
                               "must check can_admit first")
        slot = self._free.pop()
        if self._occupant[slot] is not None:     # pragma: no cover - guard
            raise RuntimeError(
                f"slot {slot} already occupied by request "
                f"{self._occupant[slot]} — free-list corruption")
        self._occupant[slot] = rid
        return slot

    def release(self, slot: int) -> None:
        if self._occupant[slot] is None:
            raise RuntimeError(f"release of already-free slot {slot}")
        self._occupant[slot] = None
        self._free.append(slot)

    # -- per-slot decode state --------------------------------------------

    def seat(self, slot: int, prompt_len: int, first_token: int) -> None:
        """Post-prefill seating: the slot's next write position is
        ``prompt_len`` (the first generated token's position) and its
        pending input token is the freshly sampled one."""
        if not 0 < prompt_len < self.max_len:
            raise ValueError(f"prompt_len {prompt_len} outside (0, "
                             f"{self.max_len})")
        self.positions[slot] = prompt_len
        self.last_token[slot] = int(first_token)

    def advance(self, slot: int, next_token: int) -> None:
        self.positions[slot] += 1
        self.last_token[slot] = int(next_token)

    def seat_block(self, slot: int, position: int, forwards: int) -> None:
        """``slot``'s next block starts at ``position`` and takes
        ``forwards`` forwards, the commit included (``step_rows > 1``):
        after the prefill, and after every commit."""
        if position % self.step_rows or not (
                0 <= position <= self.max_len - self.step_rows):
            raise ValueError(
                f"block start {position} is no multiple of "
                f"{self.step_rows} inside [0, {self.max_len})")
        self.positions[slot] = position
        self.block_fwd[slot] = 0
        self.block_total[slot] = forwards

    # -- capacity ----------------------------------------------------------

    @property
    def blocks_in_use(self) -> int:
        """Blocks referenced by live requests (cached-only blocks excluded —
        they are reclaimable memory, not working-set)."""
        return int((self.ref[1:] > 0).sum())

    @property
    def blocks_cached(self) -> int:
        return len(self._lru)

    @property
    def blocks_available(self) -> int:
        """Blocks a NEW sequence could still claim: free + reclaimable
        (cached, ref 0) minus outstanding reservations."""
        return len(self._free_blocks) + len(self._lru) - self._reserved

    def bytes_resident(self) -> int:
        return self.blocks_in_use * self.bytes_per_block + sum(
            g.blocks_in_use * g.bytes_per_block for g in self.window_groups)

    def bytes_for_rows(self, rows: int) -> int:
        """The most bytes a sequence of ``rows`` written positions pins:
        its blocks in the full group and, in a window group, no more than
        the ring."""
        n = self.blocks_for(rows)
        return n * self.bytes_per_block + sum(
            min(n, g.ring) * g.bytes_per_block for g in self.window_groups)

    def _rows_needed(self, prompt_len: int, max_new_tokens: int) -> int:
        # positions written: prefill [0, prompt_len) + one decode write per
        # consumed token — the final emitted token is never consumed, so the
        # highest written position is prompt_len + max_new - 2
        if self.step_rows == 1:
            return prompt_len + max_new_tokens - 1
        # whole blocks, the last one to its end
        return -(-(prompt_len + max_new_tokens) // self.step_rows
                 ) * self.step_rows

    def blocks_for(self, rows: int) -> int:
        return math.ceil(rows / self.block_size)

    # -- admission ---------------------------------------------------------

    def can_admit(self, request) -> bool:
        """The admission gate: a free slot AND enough blocks for this
        request's worst-case budget after prefix sharing (shared FULL blocks
        are never written, so they cost nothing; a shared partial tail still
        budgets one block for its copy-on-write).

        Chain blocks sitting in the reclaimable LRU (cached, ref 0) are
        counted OUT of availability here: sharing them revives them
        (``_ref_block`` pulls them from the LRU), which shrinks
        ``blocks_available`` without consuming reservation — counting them
        as both "shared, free of charge" and "reclaimable headroom" would
        approve a request ``begin_seq`` cannot actually fund.

        A request whose prefix an in-flight host->HBM upload covers is
        additionally held back (:meth:`prefetch_blocked`): boarding now
        would recompute — or worse, share half-uploaded rows — instead of
        waiting the tick or two for the registered blocks to land."""
        return (bool(self._free) and self.admit_shortfall(request) == 0
                and not self.prefetch_blocked(request))

    def admit_shortfall(self, request) -> int:
        """Blocks ``request`` is short of admission (0 = the block budget
        fits; a free slot is checked separately). The PriorityScheduler's
        preemption precheck compares this against the victims' guaranteed
        :meth:`freeable_blocks` so eviction never discards work that could
        not possibly let the requester board."""
        _shared_len, chain = self._probe_cached(request)
        n_shared_full = sum(1 for _, fill in chain if fill == self.block_size)
        n_shared_reclaimable = sum(1 for b, _ in chain if self.ref[b] == 0)
        rows = self._rows_needed(
            int(np.asarray(_bind_seq_of(request)).shape[0]),
            _bind_budget_of(request))
        budget = self.blocks_for(rows) - n_shared_full
        # each window group is asked for its own worst case
        return max(0, budget - (self.blocks_available - n_shared_reclaimable)
                   ) + sum(max(0, g.budget(rows, self.block_size)
                               - g.blocks_available)
                           for g in self.window_groups)

    def freeable_blocks(self, slot: int) -> int:
        """Blocks GUARANTEED back into availability-for-an-admission if
        ``slot``'s sequence ends now: its unused reservation plus its
        solely-referenced UNCACHED table blocks (ref drops to 0, straight
        to the free list). Shared blocks stay with their referents, and
        cached (registered-prefix) blocks are deliberately excluded even at
        ref 1: they land on the reclaimable LRU, where an admission probe
        that SHARES them re-discounts them as reclaimable chain blocks
        (``admit_shortfall``'s n_shared_reclaimable) — counting them here
        would let the preemption precheck approve evictions that cannot
        actually fund the requester. Conservative: may under-report (a
        missed preemption), never over-report (work destroyed for
        nothing)."""
        return int(self._resv[slot]) + sum(
            1 for b in self.tables[slot]
            if self.ref[b] == 1 and not self._cached.get(b)) + sum(
            int(g.resv[slot] + g.next[slot] - g.first[slot])
            for g in self.window_groups)

    def begin_seq(self, slot: int, prompt: np.ndarray,
                  max_new_tokens: int, ns: bytes = b"") -> int:
        """Attach a sequence to an acquired slot: match the longest
        registered prompt prefix (incref'ing the shared blocks into this
        slot's table) and reserve the worst-case budget for the rest.
        Returns ``shared_len`` — the first prompt position the engine's
        chunked prefill must actually compute (always < prompt_len: at
        least the last prompt position is recomputed so the first token is
        sampled from a real forward pass)."""
        if self.tables[slot] or self._resv[slot]:
            raise RuntimeError(
                f"begin_seq on slot {slot} with a live block table or "
                f"reservation — the previous sequence was never ended")
        prompt = np.asarray(prompt)
        self._slot_ns[slot] = ns
        if self._no_prefix and self._first_block_key(ns, prompt) \
                in self._would_be:
            self.prefix_declined_total += 1
        shared_len, chain = self._probe_prefix(prompt, ns)
        for block, _fill in chain:
            self._ref_block(block)
            self.tables[slot].append(block)
        n_shared_full = sum(1 for _, fill in chain if fill == self.block_size)
        rows = self._rows_needed(int(prompt.shape[0]), max_new_tokens)
        budget = self.blocks_for(rows) - n_shared_full
        for g in self.window_groups:
            g.begin(slot, g.budget(rows, self.block_size))
        if budget > self.blocks_available:
            raise RuntimeError(
                f"begin_seq short of blocks (need {budget}, have "
                f"{self.blocks_available}) — the scheduler must check "
                f"can_admit first")
        self._reserved += budget
        self._resv[slot] = budget
        self.prefix_hit_blocks_total += len(chain)
        return shared_len

    def bind_seq(self, request) -> int:
        """Attach an admitted request's sequence to its slot
        (:meth:`begin_seq`): the first prompt position prefill must
        compute. MUST run inside the admission loop, immediately after the
        slot acquire — the next head-of-line ``can_admit`` probe has to see
        this request's reservation, or a burst admits past the pool's
        capacity."""
        # resume_seq/resume_max_new: identical to prompt/max_new_tokens for
        # fresh requests; after a preemption they cover the already-emitted
        # tokens whose K/V re-admission must recompute (serve/request.py)
        return self.begin_seq(request.slot, _bind_seq_of(request),
                              _bind_budget_of(request), ns=_ns_of(request))

    def unbind_seq(self, slot: int) -> None:
        """Release the slot's sequence state at retirement (before the slot
        itself frees)."""
        self.end_seq(slot)

    def end_seq(self, slot: int) -> None:
        """Detach the slot's sequence: decref every table block (cached
        blocks become reclaimable, uncached ones free) and return the unused
        reservation. The slot itself is released separately (scheduler)."""
        for block in self.tables[slot]:
            # surviving sharers lose the in-place-writer privilege with
            # the allocator gone (they fall back to plain CoW-at-ref>1)
            if self._block_writer.get(block) == slot:
                del self._block_writer[block]
            self._unref_block(block)
        self.tables[slot] = []
        self._slot_ns[slot] = b""
        self._reserved -= int(self._resv[slot])
        self._resv[slot] = 0
        for g in self.window_groups:
            g.end(slot)

    # -- write-path allocation + copy-on-write -----------------------------

    def ensure_writable(self, slot: int, position: int,
                        oldest: int | None = None
                        ) -> tuple[int, int] | None:
        """Make ``position``'s block privately writable by ``slot``'s
        sequence, allocating on demand as positions advance. Returns a
        ``(src, dst)`` physical pair when copy-on-write fired — the CALLER
        must copy the device block rows before writing — else ``None``.
        ``oldest``: the position of the oldest query of the program that
        will write ``position`` (a chunk's first row; ``position`` itself
        where not given): a window group hands back what lies behind that
        query's window before it allocates.

        In-place writes into a singly-referenced block drop any registered
        prefix whose covered rows extend past the write offset (the write
        would silently corrupt what the registry promises future sharers).
        """
        if not 0 <= position < self.max_len:
            raise ValueError(f"position {position} outside [0, "
                             f"{self.max_len})")
        for g in self.window_groups:
            g.ensure(slot, position, position if oldest is None else oldest,
                     self.block_size)
        table = self.tables[slot]
        j = position // self.block_size
        if j > len(table):          # pragma: no cover - guard
            raise RuntimeError(
                f"slot {slot} write at position {position} skips logical "
                f"block {len(table)} — positions must advance contiguously")
        if j == len(table):
            table.append(self._alloc_block(slot))
            return None
        phys = table[j]
        if self.ref[phys] > 1 and self._block_writer.get(phys) != slot:
            # a SHARED-IN block: this slot referenced it through the
            # prefix registry, so its own rows must land in a private copy
            dst = self._alloc_block(slot)
            table[j] = dst
            self._unref_block(phys)
            self.cow_copies_total += 1
            return (phys, dst)
        # singly-referenced, or shared but THIS slot allocated it (sharers
        # trust only rows below their registered fill and copy before
        # writing, so the allocator's tail writes are invisible to them):
        # in-place, but invalidate stale prefix promises
        off = position % self.block_size
        for key in list(self._cached.get(phys, ())):
            if self._prefix[key][1] > off:
                self._drop_key(key)
        return None

    def _alloc_block(self, slot: int) -> int:
        if self._resv[slot] <= 0:   # pragma: no cover - guard
            raise RuntimeError(
                f"slot {slot} allocates past its reservation — the "
                f"admission budget was computed wrong")
        if self._free_blocks:
            block = self._free_blocks.pop()
        elif self._lru:
            block, _ = self._lru.popitem(last=False)   # evict LRU cached
            if self.host_cache_blocks:
                # demote-to-host BEFORE the keys drop: the evicted prefix
                # survives in the offload tier instead of dying
                self._demote(block)
            for key in list(self._cached.get(block, ())):
                del self._prefix[key]
            self._cached.pop(block, None)
            self._registry_epoch += 1
            self.evictions_total += 1
        else:                       # pragma: no cover - guard
            raise RuntimeError(
                "block pool exhausted despite reservation accounting — "
                "free/reserve bookkeeping corrupted")
        if self.ref[block] != 0:    # pragma: no cover - guard
            raise RuntimeError(
                f"allocated block {block} has ref {self.ref[block]} — "
                f"double allocation")
        if block == self.TRASH:     # pragma: no cover - guard
            raise RuntimeError("the trash block leaked into the free list")
        self.ref[block] = 1
        self._block_writer[block] = slot
        self._resv[slot] -= 1
        self._reserved -= 1
        return block

    def _ref_block(self, block: int) -> None:
        if self.ref[block] == 0:
            # was cached-reclaimable; sharing revives it
            self._lru.pop(block, None)
        self.ref[block] += 1

    def _unref_block(self, block: int) -> None:
        if self.ref[block] <= 0:
            raise RuntimeError(f"unref of unreferenced block {block} — "
                               f"double free")
        self.ref[block] -= 1
        if self.ref[block] == 0:
            self._block_writer.pop(block, None)
            if self._cached.get(block):
                self._lru[block] = None        # reclaimable, newest last
            else:
                self._free_blocks.append(block)

    # -- prefix registry ---------------------------------------------------

    def shared_prefix_len(self, prompt, ns: bytes = b"") -> int:
        """The fleet router's affinity signal (``serve/router.py``): longest
        registered prefix of ``prompt`` (in positions) this pool already
        holds in namespace ``ns``. A pure probe — no referencing, no memo,
        no registry mutation — so the router may ask every replica without
        perturbing any pool."""
        return self._probe_prefix(np.asarray(prompt, np.int32), ns)[0]

    def _probe_cached(self, request) -> tuple[int, list[tuple[int, int]]]:
        """Probe memoized on the request, keyed by the registry epoch AND
        the bind sequence's length — a blocked head-of-line request is
        re-probed every tick by ``can_admit``, and without the memo each
        probe re-hashes up to ``block_size`` prompt prefixes per block. The
        epoch bumps on every registry mutation, and a preemption grows the
        request's bind sequence, so a stale chain can never be returned."""
        seq = np.asarray(_bind_seq_of(request))
        key = (self._registry_epoch, int(seq.shape[0]))
        memo = getattr(request, "_prefix_probe", None)
        if memo is not None and memo[0] == key:
            return memo[1], memo[2]
        shared_len, chain = self._probe_prefix(seq, _ns_of(request))
        request._prefix_probe = (key, shared_len, chain)
        return shared_len, chain

    def _probe_prefix(self, prompt: np.ndarray, ns: bytes = b""
                      ) -> tuple[int, list[tuple[int, int]]]:
        """Longest registered chain prefixing ``prompt`` within namespace
        ``ns`` (capped at ``prompt_len - 1`` so at least one position is
        always recomputed). Returns ``(shared_len, [(block, fill), ...])``
        without mutating."""
        if self._no_prefix:
            # a block without its state, or without the window layers'
            # share of the same positions, is no prefix
            return 0, []
        prompt = np.asarray(prompt, np.int32)
        cap = int(prompt.shape[0]) - 1
        bs = self.block_size
        # a row of a block-step model is its whole block's: only whole pool
        # blocks of the prompt's whole steps can be another prompt's too
        whole = self.step_rows > 1
        if whole:
            cap = int(prompt.shape[0]) // self.step_rows * self.step_rows - 1
        chain: list[tuple[int, int]] = []
        shared = 0
        j = 0
        while True:
            hit = None
            # the longest key covering block j that still prefixes prompt:
            # full block first, then partial fills from longest down
            for length in range(min(cap, (j + 1) * bs),
                                (j + 1) * bs - 1 if whole else j * bs, -1):
                entry = self._prefix.get(ns + prompt[:length].tobytes())
                if entry is not None:
                    hit = (entry[0], length - j * bs)
                    break
            if hit is None:
                break
            chain.append(hit)
            shared = j * bs + hit[1]
            if hit[1] < bs:         # partial tail ends the chain
                break
            j += 1
        return shared, chain

    def _first_block_key(self, ns: bytes, prompt) -> bytes | None:
        """The registry key a prompt's first FULL block would have had."""
        if len(prompt) < self.block_size:
            return None
        return ns + np.asarray(prompt[:self.block_size], np.int32).tobytes()

    def register_prefix(self, slot: int, prompt: np.ndarray) -> None:
        """Publish ``slot``'s freshly prefilled prompt blocks to the
        registry: one key per full block boundary plus the partial tail, so
        later requests with the same prefix share instead of recompute.
        First writer wins — an existing key keeps its block. Keys are
        published under the slot's bind-time namespace, so an identical
        prompt under a DIFFERENT adapter probes past them — cross-tenant
        K/V sharing is the one bug this scoping makes impossible."""
        prompt = np.asarray(prompt, np.int32)
        ns = self._slot_ns[slot]
        bs = self.block_size
        if self._no_prefix:
            # nothing is published; only remembered, to count the matches
            # this pool has to decline
            key = self._first_block_key(ns, prompt)
            if key is not None:
                self._would_be[key] = None
                self._would_be.move_to_end(key)
                if len(self._would_be) > self.n_blocks:
                    self._would_be.popitem(last=False)
            return
        table = self.tables[slot]
        plen = int(prompt.shape[0])
        # a block-step model publishes whole pool blocks only (_probe_prefix)
        n_blocks = (plen // bs if self.step_rows > 1
                    else self.blocks_for(plen))
        for j in range(n_blocks):
            fill = min(plen - j * bs, bs)
            key = ns + prompt[:j * bs + fill].tobytes()
            if key in self._prefix:
                continue
            block = table[j]
            self._prefix[key] = (block, fill)
            self._cached.setdefault(block, set()).add(key)
            self._registry_epoch += 1

    def _drop_key(self, key: bytes) -> None:
        block, _ = self._prefix.pop(key)
        self._registry_epoch += 1
        keys = self._cached.get(block)
        if keys:
            keys.discard(key)
            if not keys:
                del self._cached[block]
                if self.ref[block] == 0 and block in self._lru:
                    # was reclaimable via the registry alone — hand the
                    # block back outright
                    del self._lru[block]
                    self._free_blocks.append(block)

    # -- host offload tier -------------------------------------------------

    def _block_to_host(self, cache, block: int):
        """One physical block's rows as a host (numpy) pytree — a QuantKV
        cache's narrow data and f32 scale planes travel together."""
        import jax
        return jax.tree.map(lambda a: np.asarray(a[block]), cache)

    def _demote(self, block: int) -> None:
        """Copy an evicted cached block's rows (and its registered prefix
        keys) into the host tier before the device registry forgets them.
        A key already host-resident is re-pointed at the fresh copy (the
        content is identical — the key IS the token prefix, which fully
        determines the block's K/V); capacity overflow drops the LRU host
        entry (``host_evictions_total`` — the tier's true end of life)."""
        keys = {k: self._prefix[k][1] for k in self._cached.get(block, ())}
        if not keys:                # pragma: no cover - LRU blocks are cached
            return
        hid = self._next_host_id
        self._next_host_id += 1
        for key in keys:
            old = self._host_prefix.get(key)
            if old is not None:
                self._drop_host_key(key, old[0])
        self._host[hid] = {"keys": keys,
                           "kc": self._block_to_host(self.kc, block),
                           "vc": self._block_to_host(self.vc, block)}
        for key, fill in keys.items():
            self._host_prefix[key] = (hid, fill)
        self.host_demotes_total += 1
        self.host_transfer_bytes_total += self.bytes_per_block
        while len(self._host) > self.host_cache_blocks:
            ev_id, ev = self._host.popitem(last=False)
            for key in ev["keys"]:
                if self._host_prefix.get(key, (None, 0))[0] == ev_id:
                    del self._host_prefix[key]
            self.host_evictions_total += 1

    def _drop_host_key(self, key: bytes, hid: int) -> None:
        entry = self._host.get(hid)
        if entry is None:           # pragma: no cover - guard
            return
        entry["keys"].pop(key, None)
        if not entry["keys"]:
            del self._host[hid]

    def host_prefix_len(self, prompt, ns: bytes = b"") -> int:
        """The router's second affinity signal (a hit here starts the async
        prefetch upload): longest host-resident prefix of ``prompt`` (in
        positions) under the ``ns`` adapter namespace; 0 without a host
        tier. A pure probe, like :meth:`shared_prefix_len` — the router
        may ask freely."""
        return self._probe_host(np.asarray(prompt, np.int32), ns)[0]

    def _probe_host(self, prompt: np.ndarray, ns: bytes = b""
                    ) -> tuple[int, list[tuple[bytes, int, int]]]:
        """:meth:`_probe_prefix`'s walk against the HOST registry. Host
        keys are the demoted device-registry keys, so they already carry
        the adapter namespace — probing just prepends the same ``ns``.
        Returns ``(shared_len, [(key, fill, host_id), ...])`` without
        mutating."""
        prompt = np.asarray(prompt, np.int32)
        cap = int(prompt.shape[0]) - 1
        bs = self.block_size
        chain: list[tuple[bytes, int, int]] = []
        shared = 0
        j = 0
        while True:
            hit = None
            for length in range(min(cap, (j + 1) * bs), j * bs, -1):
                key = ns + prompt[:length].tobytes()
                entry = self._host_prefix.get(key)
                if entry is not None:
                    hit = (key, length - j * bs, entry[0])
                    break
            if hit is None:
                break
            chain.append(hit)
            shared = j * bs + hit[1]
            if hit[1] < bs:         # partial tail ends the chain
                break
            j += 1
        return shared, chain

    def prefetch(self, prompt, ns: bytes = b"") -> bool:
        """Routing-time async upload: start moving ``prompt``'s
        host-resident prefix blocks back into HBM so they are registered
        (and shareable) before the request's slot boards. Returns True on
        a prefetch HIT — a new upload started, or the same keys are
        already in flight; False (a MISS) when the host tier adds nothing
        past the device registry or availability cannot fund the upload
        without touching reservations. Free blocks fund first; reclaimable
        LRU blocks fund the rest by the allocator's own evict path — WITH
        demotion, so the displaced prefix moves to host instead of dying
        (the offload-thrash cycle under hot-prefix churn).

        The uploaded keys stay INVISIBLE until :meth:`advance_transfers`
        completes them; until then :meth:`can_admit` blocks any request
        the in-flight keys prefix (``prefetch_blocked``) — boarding
        against half-uploaded rows is the one way this tier could corrupt
        a stream, so it is structurally impossible."""
        if not self.host_cache_blocks:
            return False
        prompt = np.asarray(prompt, np.int32)
        host_len, chain = self._probe_host(prompt, ns)
        dev_len = self._probe_prefix(prompt, ns)[0]
        chain = [(k, f, hid) for (k, f, hid) in chain
                 if k not in self._prefix]
        if host_len <= dev_len or not chain:
            self.host_prefetch_misses_total += 1
            return False
        inflight_keys = {k for t in self._inflight
                         for (k, _f, _hk, _hv) in t["entries"]}
        fresh = [(k, f, hid) for (k, f, hid) in chain
                 if k not in inflight_keys]
        if not fresh:
            return True             # already on its way; counted at start
        n = len(fresh)
        if n > self.blocks_available:
            self.host_prefetch_misses_total += 1
            return False
        # capture the host arrays BEFORE claiming device blocks: claiming
        # may evict-and-demote LRU victims, and the demotion's host-LRU
        # overflow could drop the very entries this upload reads from
        entries = []
        for key, fill, hid in fresh:
            e = self._host[hid]
            self._host.move_to_end(hid)        # a prefetch touch is a use
            entries.append((key, fill, e["kc"], e["vc"]))
        blocks = []
        for _ in range(n):
            if self._free_blocks:
                blocks.append(self._free_blocks.pop())
                continue
            # _alloc_block's eviction path, verbatim: oldest cached block
            # demotes to host, its device keys drop, the block funds the
            # upload (blocks_available already proved reservations survive)
            block, _ = self._lru.popitem(last=False)
            self._demote(block)
            for k in list(self._cached.get(block, ())):
                del self._prefix[k]
            self._cached.pop(block, None)
            self._registry_epoch += 1
            self.evictions_total += 1
            blocks.append(block)
        self._inflight.append({"entries": entries, "blocks": blocks,
                               "ticks_left": self.prefetch_ticks})
        self.host_prefetch_hits_total += 1
        return True

    def prefetch_blocked(self, request) -> bool:
        """True while an in-flight host->HBM upload covers a prefix of
        ``request``'s bind sequence — the one ``can_admit`` failure that
        preemption can NEVER fix (the PriorityScheduler must not evict
        work for it; the request boards when the upload lands)."""
        if not self._inflight:
            return False
        seq_b = _ns_of(request) + np.asarray(
            _bind_seq_of(request), np.int32).tobytes()
        for t in self._inflight:
            for key, _f, _hk, _hv in t["entries"]:
                if len(key) < len(seq_b) and seq_b.startswith(key):
                    return True
        return False

    def advance_transfers(self) -> None:
        """One engine tick of upload progress: decrement every in-flight
        countdown and COMPLETE the ones that reach zero — device rows land,
        the keys register (epoch bump), the blocks join the reclaimable LRU
        as cached ref-0 blocks exactly as if a local request had registered
        them. The paged engine calls this at the top of every step, BEFORE
        admission, so a request blocked on its upload boards the same tick
        the blocks become real. A key registered on-device while the upload
        flew wins (first writer, the registry's one rule) and the upload's
        block goes straight back to the free list."""
        if not self._inflight:
            return
        done = [t for t in self._inflight if t["ticks_left"] <= 1]
        for t in self._inflight:
            t["ticks_left"] -= 1
        self._inflight = [t for t in self._inflight if t["ticks_left"] > 0]
        for t in done:
            blocks = list(t["blocks"])
            for key, fill, hk, hv in t["entries"]:
                block = blocks.pop(0)
                if key in self._prefix:
                    self._free_blocks.append(block)
                    continue
                self.kc, self.vc = self._write_block(
                    self.kc, self.vc, np.int32(block), hk, hv)
                self._prefix[key] = (block, fill)
                self._cached.setdefault(block, set()).add(key)
                self._lru[block] = None        # cached ref-0, reclaimable
                self._registry_epoch += 1
                self.host_promotes_total += 1
                self.host_transfer_bytes_total += self.bytes_per_block

    def host_bytes_resident(self) -> int:
        """Host-tier mirror of :meth:`bytes_resident`: bytes the offload
        tier pins in host RAM, ``host blocks x bytes_per_block`` — the
        same :func:`kv_block_bytes` formula, so the analyzer's host-tier
        prediction reconciles exactly (``analysis/programs.py``)."""
        return len(self._host) * self.bytes_per_block

    # -- tick inputs -------------------------------------------------------

    def device_table(self, slot: int, group: int | None = None
                     ) -> np.ndarray:
        """This slot's block table padded to the static program width with
        trash entries (masked out by position in the compiled step).
        ``group``: 0 the full group's table (logical block ``j`` at entry
        ``j``), ``g >= 1`` window group ``g - 1``'s ring (logical block
        ``j`` at entry ``j % ring``, TRASH where the block is not yet
        written or already handed back); ``None``: all of them side by
        side, ``table_width`` entries, which is the full group's table
        alone in a pool without windows."""
        if group:
            return self.window_groups[group - 1].tables[slot].copy()
        t = np.full(self.blocks_per_seq, self.TRASH, np.int32)
        table = self.tables[slot]
        t[:len(table)] = table
        if group == 0 or not self.window_groups:
            return t
        return np.concatenate([t, *(g.tables[slot]
                                    for g in self.window_groups)])

    def stats(self) -> dict:
        s = {
            "blocks_total": self.n_blocks,
            "blocks_in_use": self.blocks_in_use,
            "blocks_cached": self.blocks_cached,
            "blocks_free": len(self._free_blocks),
            "kv_bytes_resident": self.bytes_resident(),
            "prefix_hit_blocks_total": self.prefix_hit_blocks_total,
            "cow_copies_total": self.cow_copies_total,
            "evictions_total": self.evictions_total,
        }
        if self.recurrent:
            s.update({
                "state_bytes_resident":
                    self.n_active * self.state_bytes_per_slot,
                "prefix_declined_total": self.prefix_declined_total,
            })
        if self.windowed:
            s.update({
                "window_blocks_total": [g.n_blocks
                                        for g in self.window_groups],
                "window_blocks_in_use": [g.blocks_in_use
                                         for g in self.window_groups],
                "window_released_total": self.window_released_total,
                "prefix_declined_total": self.prefix_declined_total,
            })
        if self.host_cache_blocks:
            s.update({
                "host_blocks": len(self._host),
                "host_bytes_resident": self.host_bytes_resident(),
                "host_inflight_blocks": sum(
                    len(t["blocks"]) for t in self._inflight),
                "host_demotes_total": self.host_demotes_total,
                "host_promotes_total": self.host_promotes_total,
                "host_evictions_total": self.host_evictions_total,
                "host_prefetch_hits_total": self.host_prefetch_hits_total,
                "host_prefetch_misses_total":
                    self.host_prefetch_misses_total,
                "host_transfer_bytes_total": self.host_transfer_bytes_total,
            })
        return s
