"""Paged KV cache + block-table decode attention for continuous batching.

Serving real mixed-length traffic is the paper's *irregular* access pattern
as a system: decode attention over a paged KV cache is an indirect,
block-table-addressed gather, not a contiguous scan. This module rebuilds
the serving cache around fixed-size KV blocks (pages):

  * :class:`BlockAllocator` / :class:`PagedKVCache` — a host-side free-list
    allocator over a device-resident block pool
    ``[L, n_blocks, J, 2, page, lanes]`` (axis 3: k=0 / v=1), with
    per-request block tables. The KV heads are lane-packed
    (:func:`~repro.core.emitter.lane_pack`): each of the ``J`` lane words
    holds ``p = KVH / J`` adjacent heads side by side, ``lanes = p * hd``
    (128 at hd 64), so one page of one lane word — its K rows then its V
    rows — is a contiguous ``(2*page, lanes)`` block that a single DMA of
    whole 128-lane rows moves. Admission reserves ``ceil(prompt+max_new /
    page)`` blocks; retirement recycles them, so KV memory scales with the
    *live* token count instead of ``B * S_max``.
  * :func:`gather_indices` — flattens a block table into the page-index
    stream an ``ff_gather`` producer walks: word ``w = (b*J + j)*n_pages +
    kj`` fetches page ``kj`` of lane word ``j`` for request ``b``.
  * ``paged_decode_attention`` StreamGraph — the registered two-node graph
    (block-table page gather producer → online-softmax decode-attention
    consumer). The gather moves one ``(2*page, lanes)`` page per word, its
    out blocks line up word-for-word with the consumer's kv pipe, and
    ``check_fusion`` legalizes the edge with wpb=1: the gathered pages
    stream through a VMEM ring and never round-trip HBM. Tuned jointly via
    :func:`repro.core.autotune.resolve_graph`.

The consumer's softmax math is identical to the contiguous
``ff_decode_attention`` kernel at ``block_kv == page`` (same lane packing,
same tile order, same f32 accumulation), so paged decode is
*bitwise-equal* to the contiguous-cache path — rows past ``length`` (zero
fill or stale recycled block contents) mask to ``-1e30`` and their ``exp``
underflows to exactly 0.0. ``tests/test_serving_paged.py`` asserts this.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autotune
from repro.core.emitter import lane_pack, pack_blockdiag, unpack_blockdiag
from repro.core.program import current_policy


# ---------------------------------------------------------------------------
# Pool layout: lane-packed heads
# ---------------------------------------------------------------------------


def pool_lanes(kv_heads: int, head_dim: int) -> Tuple[int, int]:
    """``(J, lanes)``: the pool's lane words per page and their width."""
    p = lane_pack(kv_heads, head_dim)
    return kv_heads // p, p * head_dim


def pack_pool(kv: jnp.ndarray) -> jnp.ndarray:
    """``[..., 2, page, KVH, hd]`` (per-head) -> ``[..., J, 2, page, lanes]``
    (the pool layout)."""
    *lead, two, page, kvh, hd = kv.shape
    j, lanes = pool_lanes(kvh, hd)
    return jnp.moveaxis(kv.reshape(*lead, two, page, j, lanes), -2, -4)


def unpack_pool(pool: jnp.ndarray, kv_heads: int) -> jnp.ndarray:
    """Inverse of :func:`pack_pool`: ``[..., J, 2, page, lanes]`` ->
    ``[..., 2, page, KVH, hd]``."""
    *lead, j, two, page, lanes = pool.shape
    x = jnp.moveaxis(pool, -4, -2)
    return x.reshape(*lead, two, page, kv_heads, j * lanes // kv_heads)


def dense_kv(kv_pool, block_tables, kv_heads: int):
    """Dereference a layer's pool through the block tables densely:
    ``(k, v)``, each ``[B, n_pages*page, KVH, hd]`` (sentinel entries clip
    to a real block; callers mask by length)."""
    nb, _, _, page, _ = kv_pool.shape
    b, npg = block_tables.shape
    bt = jnp.clip(jnp.asarray(block_tables, jnp.int32), 0, nb - 1)
    kv = unpack_pool(kv_pool[bt], kv_heads)   # [B, npg, 2, page, KVH, hd]
    k = kv[:, :, 0].reshape(b, npg * page, kv_heads, -1)
    v = kv[:, :, 1].reshape(b, npg * page, kv_heads, -1)
    return k, v


# ---------------------------------------------------------------------------
# Block-table -> gather page indexing
# ---------------------------------------------------------------------------


def gather_indices(block_tables, *, lane_words: int,
                   n_blocks: int) -> jnp.ndarray:
    """Page indices into the pool viewed as ``[nb*J, 2*page, lanes]`` for
    one decode step.

    ``block_tables``: [B, n_pages] int32 (entries >= ``n_blocks`` are
    sentinels for unallocated pages; they clip to a real block and the
    consumer's length mask discards whatever they fetch). Returns the
    [B*J*n_pages] index stream in ``ff_gather`` word order: word
    ``(b*J + j)*n_pages + kj`` reads lane word ``j`` of page ``kj``.
    """
    bt = jnp.clip(jnp.asarray(block_tables, jnp.int32), 0, n_blocks - 1)
    j = jnp.arange(lane_words, dtype=jnp.int32)
    idx = bt[:, None, :] * lane_words + j[None, :, None]   # [B, J, n_pages]
    return idx.reshape(-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Pure-jnp oracle
# ---------------------------------------------------------------------------


def paged_decode_attention_ref(q, kv_pool, block_tables, lengths, *,
                               p_dtype=None):
    """XLA oracle: dereference the block table densely, then masked softmax.
    q: [B, H, hd]; kv_pool: [nb, J, 2, page, lanes]; block_tables:
    [B, n_pages]; lengths: [B]. Returns [B, H, hd] (zeros for length-0
    rows). ``p_dtype`` rounds the softmax weights to that dtype before the
    PV product, as the kernel does for its matmul (None: kept in f32)."""
    b, h, d = q.shape
    _, j, _, _, lanes = kv_pool.shape
    kvh = j * lanes // d
    group = h // kvh
    k, v = dense_kv(kv_pool, block_tables, kvh)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    qg = q.reshape(b, kvh, group, d).astype(jnp.float32)
    s_ = jnp.einsum("bhgd,bhsd->bhgs", qg,
                    k.astype(jnp.float32)) * (1.0 / (d ** 0.5))
    cols = jnp.arange(k.shape[2])
    s_ = jnp.where(cols[None, None, None] < lengths[:, None, None, None],
                   s_, -1e30)
    if p_dtype is None:
        p = jax.nn.softmax(s_, axis=-1)
    else:       # as the kernel rounds them: unnormalized, the max at 1
        e = jnp.exp(s_ - jnp.max(s_, axis=-1, keepdims=True))
        p = e.astype(p_dtype).astype(jnp.float32) / jnp.sum(
            e, axis=-1, keepdims=True)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32))
    out = jnp.where(lengths[:, None, None, None] > 0, out, 0.0)
    return out.reshape(b, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# The two-node StreamGraph
# ---------------------------------------------------------------------------


def build_paged_decode_graph(*, b: int, kvh: int, g_pad: int, n_pages: int,
                             page: int, d: int, dtype=jnp.float32,
                             kv_dtype=None, depth: int = 2,
                             streams: int = 1):
    """Declare the paged-decode StreamGraph at one shape point (``kvh``
    KV heads of ``d`` lanes, ``g_pad`` padded query heads each): an
    ``ff_gather`` producer walking the block-table page stream feeding the
    paged online-softmax consumer through a fusable ``(2*page, lanes)``
    edge.

    Both nodes run on lane-packed heads (:func:`pool_lanes`): the gather
    moves one whole ``(2*page, lanes)`` page block per word, so its out
    blocks coincide with the consumer's kv words — the geometry
    ``check_fusion`` needs for wpb=1.
    """
    from repro.core.graph import GraphEdge, GraphNode, StreamGraph
    from repro.kernels.ff_decode_attention.kernel import build_paged_program
    from repro.kernels.ff_decode_attention.ops import \
        paged_decode_attention_workload
    from repro.kernels.ff_gather.kernel import build_program as gather_prog
    from repro.kernels.ff_gather.ops import gather_workload

    kv_dtype = kv_dtype or dtype
    j, lanes = pool_lanes(kvh, d)
    p = kvh // j
    n = b * j * n_pages
    gather = gather_prog(n, lanes, block_rows=2 * page, blocks_per_word=1,
                         dtype=kv_dtype, depth=depth)
    attn = build_paged_program(b, j, p * g_pad, n_pages, page, lanes,
                               dtype=dtype, kv_dtype=kv_dtype, depth=depth,
                               streams=streams, scale=1.0 / (d ** 0.5),
                               lane_heads=p)
    w_g, t_g = gather_workload(n, lanes, block_rows=2 * page,
                               blocks_per_word=1, dtype=kv_dtype)
    w_a, t_a = paged_decode_attention_workload(
        b, kvh * g_pad, kvh, n_pages, page, d, dtype=kv_dtype)
    return StreamGraph(
        name="paged_decode_attention",
        nodes=(
            GraphNode("gather", gather, workload=w_g, plan_tile=t_g),
            GraphNode("attn", attn, workload=w_a, plan_tile=t_a),
        ),
        edges=(
            GraphEdge("gather", "attn", "kv", reshape=(n * 2 * page, lanes)),
        ),
    )


def paged_decode_attention(q, kv_pool, block_tables, lengths, *,
                           policy=None) -> jnp.ndarray:
    """Decode attention for one new token through the block table.

    q: [B, H, hd]; kv_pool: [n_blocks, J, 2, page, lanes] (one layer's
    pool); block_tables: [B, n_pages] int32; lengths: [B] int32 (0 =
    inactive slot). Returns [B, H, hd].
    """
    policy = current_policy() if policy is None else policy
    b, h, d = q.shape
    nb, j, _, page, lanes = kv_pool.shape
    kvh = j * lanes // d
    n_pages = block_tables.shape[-1]
    assert h % kvh == 0, (h, kvh)
    group = h // kvh
    lens = lengths.astype(jnp.int32)
    if policy.mode == "ref":
        return paged_decode_attention_ref(q, kv_pool, block_tables, lens)
    g_pad = -(-group // 8) * 8
    qg = q.reshape(b, kvh, group, d)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - group), (0, 0)))
    qp = pack_blockdiag(qg, kvh // j)
    idx = gather_indices(block_tables, lane_words=j, n_blocks=nb)
    table = kv_pool.reshape(nb * j, 2 * page, lanes)

    def build(depth=2, streams=1):
        return build_paged_decode_graph(
            b=b, kvh=kvh, g_pad=g_pad, n_pages=n_pages, page=page, d=d,
            dtype=qp.dtype, kv_dtype=kv_pool.dtype, depth=depth,
            streams=streams)

    from repro.core import graph as graphlib
    g0 = build()
    w, tile = graphlib.graph_workload(g0)
    sig = graphlib.graph_signature(g0)

    def runner(tk, depth, streams):
        cg = graphlib.compile_graph(
            build(depth=depth, streams=streams),
            policy=policy.replace(mode="ff", depth=depth, streams=streams))
        return lambda: cg(idx, table, lens, qp)

    choice = autotune.resolve_graph(
        "paged_decode_attention", policy, workload=w, tile=tile,
        dtype=kv_pool.dtype, signature=sig,
        workload_fn=lambda tk: graphlib.graph_workload(build()),
        runner=None if autotune.has_tracers(q, kv_pool, block_tables, lens)
        else runner,
        site={"b": b, "h": h, "kvh": kvh, "n_pages": n_pages, "page": page,
              "d": d, "n_blocks": nb, "q_dtype": str(q.dtype)},
        site_dynamic=("b", "n_pages", "n_blocks"))
    # compiled fresh per call: the graph closure may capture trace-scoped
    # constants, so it must never be reused across jit traces (the outer
    # jitted decode step already amortizes the rebuild)
    mode = "ff" if policy.mode == "autotune" else policy.mode
    cg = graphlib.compile_graph(
        build(depth=choice.depth, streams=choice.streams),
        policy=policy.replace(mode=mode, depth=choice.depth,
                              streams=choice.streams))
    out = unpack_blockdiag(cg(idx, table, lens, qp), kvh // j)
    return out[:, :, :group, :].reshape(b, h, d)


# ---------------------------------------------------------------------------
# Device-side scatter helpers (prefill admission, per-step token append)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("page", "n_blocks"))
def scatter_prefill(pool, k, v, block_tables, lengths, *, page: int,
                    n_blocks: int):
    """Write prefill KV into the pool through the block tables.

    pool: [L, nb, J, 2, page, lanes]; k, v: [L, B, S_p, KVH, hd];
    block_tables: [B, n_pages]; lengths: [B]. Positions past ``lengths``
    route to the sentinel block id ``n_blocks`` and drop.
    """
    n_l, b, s_p = k.shape[:3]
    j, lanes = pool.shape[2], pool.shape[-1]
    pos = jnp.arange(s_p)
    bt = jnp.asarray(block_tables, jnp.int32)
    blk = bt[:, jnp.clip(pos // page, 0, bt.shape[1] - 1)]     # [B, S_p]
    blk = jnp.where(pos[None] < lengths[:, None], blk, n_blocks)
    off = jnp.broadcast_to(pos % page, blk.shape)

    def rows(x):    # [L, B, S_p, KVH, hd] -> [B, S_p, L, J, lanes]
        return x.reshape(n_l, b, s_p, j, lanes).transpose(1, 2, 0, 3, 4)

    pool = pool.at[:, blk, :, 0, off].set(rows(k), mode="drop")
    pool = pool.at[:, blk, :, 1, off].set(rows(v), mode="drop")
    return pool


def scatter_token(pool_layer, block_tables, lengths, k_new, v_new,
                  n_blocks: int):
    """Append one token's K/V at position ``lengths`` (per row) into one
    layer's pool. pool_layer: [nb, J, 2, page, lanes]; k_new, v_new:
    [B, KVH, hd]. Sentinel table entries (>= n_blocks) drop the write."""
    _, j, _, page, lanes = pool_layer.shape
    b = k_new.shape[0]
    bt = jnp.asarray(block_tables, jnp.int32)
    blk = bt[jnp.arange(b), jnp.clip(lengths // page, 0, bt.shape[1] - 1)]
    off = lengths % page
    pool_layer = pool_layer.at[blk, :, 0, off].set(
        k_new.reshape(b, j, lanes), mode="drop")
    pool_layer = pool_layer.at[blk, :, 1, off].set(
        v_new.reshape(b, j, lanes), mode="drop")
    return pool_layer


# ---------------------------------------------------------------------------
# Host-side allocator + cache
# ---------------------------------------------------------------------------


class OutOfBlocks(RuntimeError):
    """Raised when an admission asks for more KV blocks than are free."""


class BlockAllocator:
    """LIFO free-list allocator over ``n_blocks`` page-sized KV blocks.

    LIFO recycling keeps the hot end of the pool dense: freshly retired
    blocks are reissued first, so the working set stays compact regardless
    of retirement order (external fragmentation is impossible — any
    ``k <= len(free)`` allocation succeeds; the only waste is *internal*:
    at most ``page - 1`` unused rows in each request's last block).
    """

    def __init__(self, n_blocks: int):
        self.n_blocks = int(n_blocks)
        self._free: List[int] = list(range(self.n_blocks - 1, -1, -1))

    @property
    def n_free(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> List[int]:
        """Pop ``n`` block ids, or raise :class:`OutOfBlocks` leaving the
        free list untouched (admission is all-or-nothing)."""
        if n > len(self._free):
            raise OutOfBlocks(
                f"need {n} KV blocks, {len(self._free)} free "
                f"(pool has {self.n_blocks})")
        ids = [self._free.pop() for _ in range(n)]
        return ids

    def free(self, ids) -> None:
        for i in ids:
            self._free.append(int(i))


class PagedKVCache:
    """Device-resident paged KV pool + host-side slot/block bookkeeping.

    The pool is one array ``[L, n_blocks, J, 2, page, lanes]`` (lane-packed
    heads, :func:`pool_lanes`) shared by all decode slots; each slot owns a
    block table (host list of block ids). :meth:`cache_view` materializes
    the per-layer view the model consumes: ``{"kv_pool": [L, nb, J, 2,
    page, lanes], "block_tables": [L, B, n_pages]}``. Unallocated table
    entries hold the sentinel id ``n_blocks`` (scatters drop, gathers clip
    + mask).
    """

    def __init__(self, *, n_layers: int, n_blocks: int, page: int,
                 kv_heads: int, head_dim: int, n_slots: int,
                 n_pages_max: int, dtype=jnp.float32):
        self.n_layers = n_layers
        self.n_blocks = n_blocks
        self.page = page
        self.kv_heads = kv_heads
        self.head_dim = head_dim
        self.n_slots = n_slots
        self.n_pages_max = n_pages_max
        j, lanes = pool_lanes(kv_heads, head_dim)
        self.pool = jnp.zeros((n_layers, n_blocks, j, 2, page, lanes), dtype)
        self.allocator = BlockAllocator(n_blocks)
        # host bookkeeping: per-slot block ids / lengths (sentinel-filled)
        self._tables = np.full((n_slots, n_pages_max), n_blocks, np.int32)
        self._owned: List[List[int]] = [[] for _ in range(n_slots)]
        self.lengths = np.zeros((n_slots,), np.int32)
        self._live_tokens = 0

    # -- admission / retirement ---------------------------------------------

    def admit(self, slot: int, k_seq, v_seq, length: int,
              reserve_tokens: int) -> None:
        """Claim ``ceil(reserve_tokens / page)`` blocks for ``slot`` and
        scatter the prompt KV (``k_seq``/``v_seq``: [L, S_p, KVH, hd],
        valid prefix ``length``). Raises :class:`OutOfBlocks` atomically
        (no partial allocation) when the pool cannot hold the reservation.
        """
        assert not self._owned[slot], f"slot {slot} already occupied"
        n_pages = -(-int(reserve_tokens) // self.page)
        if n_pages > self.n_pages_max:
            raise ValueError(
                f"reservation {reserve_tokens} tokens = {n_pages} pages "
                f"exceeds n_pages_max={self.n_pages_max}")
        ids = self.allocator.alloc(n_pages)
        self._owned[slot] = ids
        self._tables[slot, :] = self.n_blocks
        self._tables[slot, :n_pages] = ids
        self.lengths[slot] = length
        self._live_tokens += int(length)
        bt = jnp.asarray(self._tables[slot:slot + 1])
        lens = jnp.asarray([length], jnp.int32)
        self.pool = scatter_prefill(
            self.pool, k_seq[:, None], v_seq[:, None], bt, lens,
            page=self.page, n_blocks=self.n_blocks)

    def append(self, n_per_slot) -> None:
        """Host bookkeeping after a decode step appended tokens on device:
        bump lengths for the slots that wrote (device scatter already
        happened inside the jitted step)."""
        self.lengths = self.lengths + np.asarray(n_per_slot, np.int32)
        self._live_tokens += int(np.sum(n_per_slot))

    def retire(self, slot: int) -> None:
        """Free ``slot``'s blocks back to the pool."""
        self._live_tokens -= int(self.lengths[slot])
        self.allocator.free(self._owned[slot])
        self._owned[slot] = []
        self._tables[slot, :] = self.n_blocks
        self.lengths[slot] = 0

    # -- device views --------------------------------------------------------

    def device_tables(self) -> jnp.ndarray:
        """Block tables broadcast over layers: [L, n_slots, n_pages_max]
        (every layer shares one table — the pool's L axis separates them).
        """
        bt = jnp.asarray(self._tables)
        return jnp.broadcast_to(bt, (self.n_layers, *bt.shape))

    def cache_view(self) -> Dict[str, jnp.ndarray]:
        """The paged decode cache pytree ``attn_apply`` consumes (leading
        L axis on every leaf, matching the scanned layer stack)."""
        return {"kv_pool": self.pool, "block_tables": self.device_tables()}

    def update_pool(self, new_pool) -> None:
        self.pool = new_pool

    # -- metrics -------------------------------------------------------------

    def utilization(self) -> Dict[str, float]:
        """KV-memory utilization: live tokens vs. allocated block capacity
        vs. whole-pool capacity."""
        alloc_blocks = self.n_blocks - self.allocator.n_free
        alloc_tokens = alloc_blocks * self.page
        pool_tokens = self.n_blocks * self.page
        return {
            "live_tokens": float(self._live_tokens),
            "allocated_tokens": float(alloc_tokens),
            "pool_tokens": float(pool_tokens),
            "util_vs_allocated": (self._live_tokens / alloc_tokens
                                  if alloc_tokens else 0.0),
            "util_vs_pool": self._live_tokens / pool_tokens,
        }


# ---------------------------------------------------------------------------
# Graph registration (smoke point for BENCH_graph / test_graphs)
# ---------------------------------------------------------------------------

# b=2 kv_heads=2 g_pad=8 n_pages=4 page=16 d=64 over a 12-block pool (the
# two hd-64 heads share one 128-lane word); block tables drawn from a
# permutation so the gather is genuinely non-contiguous, lengths mixed (one
# partial page, one full table)
_SMOKE = dict(b=2, kvh=2, g_pad=8, n_pages=4, page=16, d=64, nb=12)


def _paged_build(*, depth: int = 2, streams: int = 1):
    c = _SMOKE
    return build_paged_decode_graph(
        b=c["b"], kvh=c["kvh"], g_pad=c["g_pad"], n_pages=c["n_pages"],
        page=c["page"], d=c["d"], dtype=jnp.float32, depth=depth,
        streams=streams)


def _paged_inputs(key):
    """Operands in CompiledGraph.arg_names order:
    (gather.idx, gather.table, attn.lengths, attn.q) — the pool viewed as
    ``[nb*J, 2*page, lanes]`` pages, q lane-packed block-diagonally."""
    c = _SMOKE
    j, lanes = pool_lanes(c["kvh"], c["d"])
    table = jax.random.normal(key, (c["nb"] * j, 2 * c["page"], lanes),
                              jnp.float32)
    perm = jax.random.permutation(
        jax.random.fold_in(key, 1), c["nb"])[:c["b"] * c["n_pages"]]
    bt = perm.reshape(c["b"], c["n_pages"]).astype(jnp.int32)
    idx = gather_indices(bt, lane_words=j, n_blocks=c["nb"])
    lens = jnp.array([37, c["n_pages"] * c["page"]], jnp.int32)
    q = 0.3 * jax.random.normal(jax.random.fold_in(key, 2),
                                (c["b"], c["kvh"], c["g_pad"], c["d"]),
                                jnp.float32)
    return (idx, table, lens, pack_blockdiag(q, c["kvh"] // j))


def _paged_heads(idx, table):
    """Per-head dense K/V [b, KVH, S, d] of the gathered page stream."""
    c = _SMOKE
    j, lanes = pool_lanes(c["kvh"], c["d"])
    s = c["n_pages"] * c["page"]
    kv = table[idx].reshape(c["b"], j, c["n_pages"], 2, c["page"], lanes)
    kv = kv.reshape(c["b"], j, c["n_pages"], 2, c["page"], c["kvh"] // j,
                    c["d"])
    kv = kv.transpose(0, 1, 5, 3, 2, 4, 6)      # [b, j, p, 2, npg, page, d]
    kv = kv.reshape(c["b"], c["kvh"], 2, s, c["d"])
    return kv[:, :, 0], kv[:, :, 1]


def _paged_ref(idx, table, lengths, q):
    """Masked-softmax oracle over the gathered page stream, in the graph's
    lane-packed output layout."""
    c = _SMOKE
    p = c["kvh"] // pool_lanes(c["kvh"], c["d"])[0]
    k, v = _paged_heads(idx, table)
    qh = unpack_blockdiag(q, p)                  # [b, KVH, g_pad, d]
    s_ = jnp.einsum("bhgd,bhsd->bhgs", qh.astype(jnp.float32),
                    k.astype(jnp.float32)) * (1.0 / (c["d"] ** 0.5))
    cols = jnp.arange(k.shape[2])
    s_ = jnp.where(cols[None, None, None] < lengths[:, None, None, None],
                   s_, -1e30)
    pr = jax.nn.softmax(s_, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", pr, v.astype(jnp.float32))
    return pack_blockdiag(out.astype(q.dtype), p)


def _paged_unfused(idx, table, lengths, q):
    """Gather then decode-attention as two separate repro.ops calls — the
    gathered page stream round-trips HBM (the BENCH_graph staged
    baseline). block_kv is pinned to the page size so the comparison
    isolates the lowering, not the tiling."""
    import repro

    c = _SMOKE
    p = c["kvh"] // pool_lanes(c["kvh"], c["d"])[0]
    n_tab, rows, lanes = table.shape
    pages = repro.ops.gather(table.reshape(n_tab, rows * lanes), idx)
    k, v = _paged_heads(jnp.arange(idx.shape[0]),
                        pages.reshape(-1, rows, lanes))
    qh = unpack_blockdiag(q, p)
    b, kvh, g_pad, d = qh.shape
    out = repro.ops.decode_attention(
        qh.reshape(b, kvh * g_pad, d), k, v, lengths, block_kv=c["page"])
    return pack_blockdiag(out.reshape(b, kvh, g_pad, d), p)


def _paged_sweep_inputs(key, site):
    """Rebuild paged_decode_attention operands at a recorded call-site
    shape (plan sweep). ``dtype`` is the resolve dtype (the KV pool's);
    ``q_dtype`` rides along in the recorded site dict."""
    b, h, kvh = int(site["b"]), int(site["h"]), int(site["kvh"])
    n_pages, page = int(site["n_pages"]), int(site["page"])
    d, nb = int(site["d"]), int(site["n_blocks"])
    kv_dt = jnp.dtype(site.get("dtype", "float32"))
    q_dt = jnp.dtype(site.get("q_dtype", "float32"))
    j, lanes = pool_lanes(kvh, d)
    q = 0.3 * jax.random.normal(key, (b, h, d), q_dt)
    pool = jax.random.normal(jax.random.fold_in(key, 1),
                             (nb, j, 2, page, lanes), kv_dt)
    bt = (jax.random.permutation(jax.random.fold_in(key, 2),
                                 max(nb, b * n_pages))[:b * n_pages]
          % nb).reshape(b, n_pages).astype(jnp.int32)
    lens = jnp.full((b,), n_pages * page, jnp.int32)
    return (q, pool, bt, lens), {}


def _register_paged_graph():
    from repro.kernels.registry import register_graph

    register_graph(
        name="paged_decode_attention",
        build=_paged_build,
        make_inputs=_paged_inputs,
        ref=_paged_ref,
        unfused=_paged_unfused,
        # no tile candidates: the page size is the pool's storage layout,
        # not a per-call knob — the joint tuner still searches (depth,
        # streams) for the fused pair
        tile_options=(),
        tol=2e-4,
        doc="block-table KV page gather -> paged decode attention; the "
            "gathered pages stream through a VMEM ring (continuous-"
            "batching serving's irregular decode path)",
        # plan-service sweep: resolve at call-site shapes through the real
        # entrypoint, not run_graph's fixed smoke point
        op=paged_decode_attention,
        sweep_inputs=_paged_sweep_inputs,
    )


_register_paged_graph()
