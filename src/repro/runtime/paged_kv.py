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
    stream an ``ff_gather`` producer walks, each row's table padded with
    sentinels to whole words of ``ppw`` pages: entry ``(b*J + j)*P + kj``
    (``P`` the padded pages per row) fetches page ``kj`` of lane word
    ``j`` for request ``b``.
  * ``paged_decode_attention`` StreamGraph — the registered two-node graph
    (block-table page gather producer → online-softmax decode-attention
    consumer). Each grid word moves ``ppw`` ``(2*page, lanes)`` pages of
    one (row, lane word) in one bundle of DMAs (:func:`pages_per_word`
    picks ``ppw`` from the call's shape), its out blocks line up
    word-for-word with the consumer's kv pipe, and ``check_fusion``
    legalizes the edge with wpb=1: the gathered pages stream through a
    VMEM ring and never round-trip HBM. Tuned jointly via
    :func:`repro.core.autotune.resolve_graph`.

A second, *latent* layout serves MLA (deepseek-v2): ``[L, n_blocks,
page, W]``, one ``W``-lane row per token (the ``kv_lora_rank`` latent,
then the rotated RoPE key, zero-padded to whole 128-lane words) shared by
every head, with its own scatters and the registered
``paged_latent_attention`` graph (the same ``ff_gather`` page producer
feeding an absorbed-form consumer that scores all query heads of a row
against one page stream and sums its latent lanes).

The consumer's softmax math is identical to the contiguous
``ff_decode_attention`` kernel at ``block_kv == ppw * page`` (same lane
packing, same tile order, same f32 accumulation), so paged decode is
*bitwise-equal* to the contiguous-cache path — rows past ``length`` (zero
fill, stale recycled block contents or sentinel padding) mask to ``-1e30``
and their ``exp`` underflows to exactly 0.0.
``tests/test_serving_paged.py`` asserts this.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import autotune
from repro.core.emitter import lane_pack, pack_blockdiag, unpack_blockdiag
from repro.core.pipe import DEFAULT_VMEM_BUDGET_BYTES
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
# Grid words: pages per word
# ---------------------------------------------------------------------------

# K and V bytes one grid word of the paged graph aims to move: 512 tokens
# of a 128-lane bf16 lane word. A grid step has a fixed cost and its
# bundle of page DMAs is all that is in flight, so one page per word
# (8 KiB) leaves the kernel bound by its step count, far below HBM rate.
_WORD_BYTES = 256 * 1024
# ring slots a word may occupy in the fused kernel: the deepest producer
# ring the planner picks (``planner.plan_pipe``'s depth cap) plus the
# fused edge's two-slot intermediate ring, within one node's half of the
# planner's VMEM budget
_RING_SLOTS = 17 + 2


def pages_per_word(n_pages: int, page: int, lanes: int, dtype, *,
                   page_streams: int = 2) -> int:
    """``ppw``: KV pages per grid word of the ``paged_decode_attention``
    graph for rows of ``n_pages`` pages of ``page`` tokens in ``lanes``-wide
    lane words of ``dtype``; a page holds ``page_streams`` blocks of
    ``page`` rows (K and V; 1 for a latent page).

    Words hold at most ``cap`` pages, about :data:`_WORD_BYTES` of K and
    V; a row's pages spread evenly over ``ceil(n_pages / cap)`` words (under
    one page of padding a word). ``ppw * page`` then rounds up to a
    multiple of 128 tokens (whole lanes of the score block) where the row
    still needs as many words. The row's grid is ``ceil(n_pages / ppw)``
    words, its last padded with sentinel pages.
    """
    assert n_pages >= 1, n_pages
    page_bytes = page_streams * page * lanes * jnp.dtype(dtype).itemsize
    word_bytes = min(_WORD_BYTES,
                     DEFAULT_VMEM_BUDGET_BYTES // 2 // _RING_SLOTS)
    cap = max(1, word_bytes // page_bytes)
    n_words = -(-n_pages // cap)
    ppw = -(-n_pages // n_words)
    step = 128 // math.gcd(page, 128)
    up = -(-ppw // step) * step
    if up <= cap and -(-n_pages // up) == n_words:
        ppw = up
    return ppw


# ---------------------------------------------------------------------------
# Block-table -> gather page indexing
# ---------------------------------------------------------------------------


def gather_indices(block_tables, *, lane_words: int, n_blocks: int,
                   ppw: int = 1) -> jnp.ndarray:
    """Page indices into the pool viewed as ``[nb*J, 2*page, lanes]`` for
    one decode step.

    ``block_tables``: [B, n_pages] int32 (entries >= ``n_blocks`` are
    sentinels for unallocated pages; they clip to a real block and the
    consumer's length mask discards whatever they fetch). Each row is
    padded with sentinels to ``P = ceil(n_pages / ppw) * ppw`` pages.
    Returns the [B*J*P] index stream in ``ff_gather`` order: entry
    ``(b*J + j)*P + kj`` reads lane word ``j`` of page ``kj``, and each
    run of ``ppw`` entries is one grid word.
    """
    bt = jnp.asarray(block_tables, jnp.int32)
    bt = jnp.pad(bt, ((0, 0), (0, -bt.shape[1] % ppw)),
                 constant_values=n_blocks)
    bt = jnp.clip(bt, 0, n_blocks - 1)
    j = jnp.arange(lane_words, dtype=jnp.int32)
    idx = bt[:, None, :] * lane_words + j[None, :, None]   # [B, J, P]
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
                             streams: int = 1, ppw: Optional[int] = None):
    """Declare the paged-decode StreamGraph at one shape point (``kvh``
    KV heads of ``d`` lanes, ``g_pad`` padded query heads each): an
    ``ff_gather`` producer walking the block-table page stream feeding the
    paged online-softmax consumer through a fusable ``(ppw, 2*page,
    lanes)`` edge.

    Both nodes run on lane-packed heads (:func:`pool_lanes`): the gather
    moves ``ppw`` whole ``(2*page, lanes)`` page blocks per word (default
    :func:`pages_per_word`), so its out blocks coincide with the
    consumer's kv words — the geometry ``check_fusion`` needs for wpb=1.
    The grid runs ``b * J * ceil(n_pages / ppw)`` words.
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
    if ppw is None:
        ppw = pages_per_word(n_pages, page, lanes, kv_dtype)
    n_words = -(-n_pages // ppw)
    n = b * j * n_words * ppw
    gather = gather_prog(n, lanes, block_rows=2 * page, blocks_per_word=ppw,
                         dtype=kv_dtype, depth=depth)
    attn = build_paged_program(b, j, p * g_pad, n_words, ppw, page, lanes,
                               dtype=dtype, kv_dtype=kv_dtype, depth=depth,
                               streams=streams, scale=1.0 / (d ** 0.5),
                               lane_heads=p)
    w_g, t_g = gather_workload(n, lanes, block_rows=2 * page,
                               blocks_per_word=ppw, dtype=kv_dtype)
    w_a, t_a = paged_decode_attention_workload(
        b, kvh * g_pad, kvh, n_pages, page, d, ppw=ppw, dtype=kv_dtype)
    return StreamGraph(
        name="paged_decode_attention",
        nodes=(
            GraphNode("gather", gather, workload=w_g, plan_tile=t_g),
            GraphNode("attn", attn, workload=w_a, plan_tile=t_a),
        ),
        edges=(
            GraphEdge("gather", "attn", "kv", reshape=(n, 2 * page, lanes)),
        ),
    )


def paged_decode_attention(q, kv_pool, block_tables, lengths, *,
                           policy=None, ppw: Optional[int] = None
                           ) -> jnp.ndarray:
    """Decode attention for one new token through the block table.

    q: [B, H, hd]; kv_pool: [n_blocks, J, 2, page, lanes] (one layer's
    pool); block_tables: [B, n_pages] int32; lengths: [B] int32 (0 =
    inactive slot). Returns [B, H, hd]. ``ppw`` (pages per grid word)
    defaults to :func:`pages_per_word` at this shape; a plan sweep that
    replays a recorded call site at a bucketed ``n_pages`` passes the
    recorded value, so it measures the grid the site ran.
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
    if ppw is None:
        ppw = pages_per_word(n_pages, page, lanes, kv_pool.dtype)
    g_pad = -(-group // 8) * 8
    qg = q.reshape(b, kvh, group, d)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - group), (0, 0)))
    qp = pack_blockdiag(qg, kvh // j)
    idx = gather_indices(block_tables, lane_words=j, n_blocks=nb, ppw=ppw)
    table = kv_pool.reshape(nb * j, 2 * page, lanes)

    def build(depth=2, streams=1):
        return build_paged_decode_graph(
            b=b, kvh=kvh, g_pad=g_pad, n_pages=n_pages, page=page, d=d,
            dtype=qp.dtype, kv_dtype=kv_pool.dtype, depth=depth,
            streams=streams, ppw=ppw)

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
              "d": d, "n_blocks": nb, "q_dtype": str(q.dtype), "ppw": ppw},
        site_dynamic=("b", "n_pages", "n_blocks"))
    # compiled fresh per call: the graph closure may capture trace-scoped
    # constants, so it must never be reused across jit traces (the outer
    # jitted decode step already amortizes the rebuild)
    mode = "ff" if policy.mode == "autotune" else policy.mode
    n_words = -(-n_pages // ppw)
    # the grid this call runs, in the trace of the build
    with obs.span("paged_attn_grid", grid_words=b * j * n_words, ppw=ppw,
                  words_per_row=n_words, rows=b, lane_words=j,
                  n_pages=n_pages, page=page):
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
# Latent layout (MLA): one shared row per token
# ---------------------------------------------------------------------------


def latent_lanes(latent_dim: int) -> int:
    """``W``: a latent row's width, rounded up to whole 128-lane words."""
    return -(-latent_dim // 128) * 128


def _latent_rows(c, k_rope, w: int):
    """``[..., r]`` latent and ``[..., rope]`` key -> ``[..., W]`` rows."""
    pad = w - c.shape[-1] - k_rope.shape[-1]
    return jnp.concatenate(
        [c, k_rope, jnp.zeros((*c.shape[:-1], pad), c.dtype)], axis=-1)


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("page", "n_blocks"))
def scatter_latent_prefill(pool, c, k_rope, block_tables, lengths, *,
                           page: int, n_blocks: int):
    """Write prefill latents into the latent pool through the block
    tables. pool: [L, nb, page, W]; c: [L, B, S_p, r]; k_rope: [L, B, S_p,
    rope] (rotated); positions past ``lengths`` drop."""
    s_p = c.shape[2]
    pos = jnp.arange(s_p)
    bt = jnp.asarray(block_tables, jnp.int32)
    blk = bt[:, jnp.clip(pos // page, 0, bt.shape[1] - 1)]     # [B, S_p]
    blk = jnp.where(pos[None] < lengths[:, None], blk, n_blocks)
    off = jnp.broadcast_to(pos % page, blk.shape)
    rows = _latent_rows(c, k_rope, pool.shape[-1]).astype(pool.dtype)
    return pool.at[:, blk, off].set(rows, mode="drop")


def scatter_latent_token(pool_layer, block_tables, lengths, c_new,
                         k_rope_new):
    """Append one token's latent row at position ``lengths`` (per row)
    into one layer's latent pool [nb, page, W]; c_new: [B, r], k_rope_new:
    [B, rope]. Sentinel table entries (>= nb) drop the write."""
    nb, page, w = pool_layer.shape
    b = c_new.shape[0]
    bt = jnp.asarray(block_tables, jnp.int32)
    blk = bt[jnp.arange(b), jnp.clip(lengths // page, 0, bt.shape[1] - 1)]
    rows = _latent_rows(c_new, k_rope_new, w).astype(pool_layer.dtype)
    return pool_layer.at[blk, lengths % page].set(rows, mode="drop")


def paged_latent_attention_ref(q, pool, block_tables, lengths, *,
                               v_dim: int, scale: float):
    """XLA oracle of the absorbed form: every head of row ``b`` scores the
    row's cached latent rows over q's lanes and sums their first
    ``v_dim`` lanes. q: [B, H, Dq] (Dq <= W); pool: [nb, page, W]."""
    nb, page, _ = pool.shape
    b, _, dq = q.shape
    bt = jnp.clip(jnp.asarray(block_tables, jnp.int32), 0, nb - 1)
    rows = pool[bt].reshape(b, -1, pool.shape[-1]).astype(jnp.float32)
    s_ = jnp.einsum("bhd,bsd->bhs", q.astype(jnp.float32),
                    rows[..., :dq]) * scale
    cols = jnp.arange(rows.shape[1])
    s_ = jnp.where(cols[None, None] < lengths[:, None, None], s_, -1e30)
    out = jnp.einsum("bhs,bsd->bhd", jax.nn.softmax(s_, axis=-1),
                     rows[..., :v_dim])
    out = jnp.where(lengths[:, None, None] > 0, out, 0.0)
    return out.astype(q.dtype)


def build_paged_latent_graph(*, b: int, g_pad: int, n_pages: int, page: int,
                             w: int, v_dim: int, scale: float,
                             dtype=jnp.float32, kv_dtype=None,
                             depth: int = 2, streams: int = 1,
                             ppw: Optional[int] = None):
    """Declare the paged latent StreamGraph at one shape point: the
    ``ff_gather`` producer moving ``ppw`` ``(page, W)`` latent pages per
    word, fused into the absorbed-form consumer over a ``(ppw, page, W)``
    edge. The grid runs ``b * ceil(n_pages / ppw)`` words."""
    from repro.core.graph import GraphEdge, GraphNode, StreamGraph
    from repro.kernels.ff_decode_attention.kernel import \
        build_latent_program
    from repro.kernels.ff_decode_attention.ops import \
        paged_latent_attention_workload
    from repro.kernels.ff_gather.kernel import build_program as gather_prog
    from repro.kernels.ff_gather.ops import gather_workload

    kv_dtype = kv_dtype or dtype
    if ppw is None:
        ppw = pages_per_word(n_pages, page, w, kv_dtype, page_streams=1)
    n_words = -(-n_pages // ppw)
    n = b * n_words * ppw
    gather = gather_prog(n, w, block_rows=page, blocks_per_word=ppw,
                         dtype=kv_dtype, depth=depth)
    attn = build_latent_program(b, g_pad, n_words, ppw, page, w, v_dim,
                                scale=scale, dtype=dtype, kv_dtype=kv_dtype,
                                depth=depth, streams=streams)
    w_g, t_g = gather_workload(n, w, block_rows=page, blocks_per_word=ppw,
                               dtype=kv_dtype)
    w_a, t_a = paged_latent_attention_workload(b, g_pad, n_pages, page, w,
                                               ppw=ppw, dtype=kv_dtype)
    return StreamGraph(
        name="paged_latent_attention",
        nodes=(
            GraphNode("gather", gather, workload=w_g, plan_tile=t_g),
            GraphNode("attn", attn, workload=w_a, plan_tile=t_a),
        ),
        edges=(
            GraphEdge("gather", "attn", "kv", reshape=(n, page, w)),
        ),
    )


def paged_latent_attention(q, pool, block_tables, lengths, *, v_dim: int,
                           scale: float, policy=None,
                           ppw: Optional[int] = None) -> jnp.ndarray:
    """MLA decode attention for one new token per row, absorbed form,
    through the latent pool's block table (the fused gather -> attention
    graph; a ``ref`` policy runs the oracle).

    q: [B, H, Dq] (``[q_nope W_UK^T, q_rope]``, Dq <= W); pool: [nb,
    page, W] (one layer); block_tables: [B, n_pages]; lengths: [B] (0 =
    idle slot). Returns [B, H, v_dim]: each head's softmax-weighted sum of
    the latent lanes. ``ppw`` defaults to :func:`pages_per_word` at this
    shape.
    """
    policy = current_policy() if policy is None else policy
    lens = lengths.astype(jnp.int32)
    if policy.mode == "ref":
        return paged_latent_attention_ref(q, pool, block_tables, lens,
                                          v_dim=v_dim, scale=scale)
    b, h, dq = q.shape
    nb, page, w = pool.shape
    n_pages = block_tables.shape[-1]
    if ppw is None:
        ppw = pages_per_word(n_pages, page, w, pool.dtype, page_streams=1)
    g_pad = -(-h // 8) * 8
    qp = jnp.pad(q, ((0, 0), (0, g_pad - h), (0, w - dq)))
    idx = gather_indices(block_tables, lane_words=1, n_blocks=nb, ppw=ppw)

    def build(depth=2, streams=1):
        return build_paged_latent_graph(
            b=b, g_pad=g_pad, n_pages=n_pages, page=page, w=w, v_dim=v_dim,
            scale=scale, dtype=qp.dtype, kv_dtype=pool.dtype, depth=depth,
            streams=streams, ppw=ppw)

    from repro.core import graph as graphlib
    g0 = build()
    wl, tile = graphlib.graph_workload(g0)

    def runner(tk, depth, streams):
        cg = graphlib.compile_graph(
            build(depth=depth, streams=streams),
            policy=policy.replace(mode="ff", depth=depth, streams=streams))
        return lambda: cg(idx, pool, lens, qp)

    choice = autotune.resolve_graph(
        "paged_latent_attention", policy, workload=wl, tile=tile,
        dtype=pool.dtype, signature=graphlib.graph_signature(g0),
        workload_fn=lambda tk: graphlib.graph_workload(build()),
        runner=None if autotune.has_tracers(q, pool, block_tables, lens)
        else runner,
        site={"b": b, "h": h, "dq": dq, "n_pages": n_pages, "page": page,
              "w": w, "v_dim": v_dim, "n_blocks": nb, "ppw": ppw,
              "q_dtype": str(q.dtype)},
        site_dynamic=("b", "n_pages", "n_blocks"))
    mode = "ff" if policy.mode == "autotune" else policy.mode
    n_words = -(-n_pages // ppw)
    # the grid this call runs, in the trace of the build
    with obs.span("latent_attn_grid", grid_words=b * n_words, ppw=ppw,
                  words_per_row=n_words, rows=b, n_pages=n_pages, page=page,
                  lanes=w):
        cg = graphlib.compile_graph(
            build(depth=choice.depth, streams=choice.streams),
            policy=policy.replace(mode=mode, depth=choice.depth,
                                  streams=choice.streams))
    return cg(idx, pool, lens, qp)[:, :h]


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

    With ``latent_dim`` (MLA) the pool is the latent layout ``[L,
    n_blocks, page, W]`` (:func:`latent_lanes`) and ``kv_heads`` /
    ``head_dim`` are not used. ``streams`` names the prefill cache entries
    :meth:`admit` takes, in order: ``("k", "v")`` or ``("c", "k_rope")``.
    """

    def __init__(self, *, n_layers: int, n_blocks: int, page: int,
                 n_slots: int, n_pages_max: int, kv_heads: int = 0,
                 head_dim: int = 0, latent_dim: int = 0,
                 dtype=jnp.float32):
        self.n_layers = n_layers
        self.n_blocks = n_blocks
        self.page = page
        self.kv_heads = kv_heads
        self.head_dim = head_dim
        self.latent_dim = latent_dim
        self.n_slots = n_slots
        self.n_pages_max = n_pages_max
        if latent_dim:
            self.streams = ("c", "k_rope")
            shape = (n_layers, n_blocks, page, latent_lanes(latent_dim))
        else:
            self.streams = ("k", "v")
            j, lanes = pool_lanes(kv_heads, head_dim)
            shape = (n_layers, n_blocks, j, 2, page, lanes)
        self.pool = jnp.zeros(shape, dtype)
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
        scatter the prompt KV (``k_seq``/``v_seq``: [L, S_p, KVH, hd];
        in the latent layout the latent [L, S_p, r] and the rotated RoPE
        key [L, S_p, rope]; valid prefix ``length``). Raises
        :class:`OutOfBlocks` atomically (no partial allocation) when the
        pool cannot hold the reservation.
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
        scatter = scatter_latent_prefill if self.latent_dim \
            else scatter_prefill
        self.pool = scatter(
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
# partial page, one full table). In f32 a word holds 8 pages: each row is
# one word, its last 4 pages sentinel padding.
_SMOKE = dict(b=2, kvh=2, g_pad=8, n_pages=4, page=16, d=64, nb=12)


def _smoke_ppw() -> int:
    c = _SMOKE
    return pages_per_word(c["n_pages"], c["page"],
                          pool_lanes(c["kvh"], c["d"])[1], jnp.float32)


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
    idx = gather_indices(bt, lane_words=j, n_blocks=c["nb"],
                         ppw=_smoke_ppw())
    lens = jnp.array([37, c["n_pages"] * c["page"]], jnp.int32)
    q = 0.3 * jax.random.normal(jax.random.fold_in(key, 2),
                                (c["b"], c["kvh"], c["g_pad"], c["d"]),
                                jnp.float32)
    return (idx, table, lens, pack_blockdiag(q, c["kvh"] // j))


def _paged_heads(idx, table):
    """Per-head dense K/V [b, KVH, S, d] of the gathered page stream (S
    counts the sentinel pages that pad each row to whole words)."""
    c = _SMOKE
    j = pool_lanes(c["kvh"], c["d"])[0]
    npg = idx.shape[0] // (c["b"] * j)
    s = npg * c["page"]
    kv = table[idx].reshape(c["b"], j, npg, 2, c["page"], c["kvh"] // j,
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
    baseline). block_kv is pinned to the graph's word (``ppw`` pages) so
    the comparison isolates the lowering, not the tiling."""
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
        qh.reshape(b, kvh * g_pad, d), k, v, lengths,
        block_kv=_smoke_ppw() * c["page"])
    return pack_blockdiag(out.reshape(b, kvh, g_pad, d), p)


def _paged_sweep_inputs(key, site):
    """Rebuild paged_decode_attention operands at a recorded call-site
    shape (plan sweep). ``dtype`` is the resolve dtype (the KV pool's);
    ``q_dtype`` and the pages per word the site ran (``ppw``, not
    bucketed) ride along in the recorded site dict, so the replay at the
    bucketed ``n_pages`` keeps the site's word size."""
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
    kw = {"ppw": int(site["ppw"])} if "ppw" in site else {}
    return (q, pool, bt, lens), kw


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


# b=2 rows of 4 query heads (padded to 8) over a 12-block latent pool of
# 128-lane rows (a 96-lane latent and a 32-lane key), page 16, 4 pages a
# row; the tables come from a permutation, one row one page short
_LATENT_SMOKE = dict(b=2, h=4, n_pages=4, page=16, w=128, v_dim=96, nb=12,
                     scale=0.125)


def _latent_build(*, depth: int = 2, streams: int = 1):
    c = _LATENT_SMOKE
    return build_paged_latent_graph(
        b=c["b"], g_pad=8, n_pages=c["n_pages"], page=c["page"], w=c["w"],
        v_dim=c["v_dim"], scale=c["scale"], depth=depth, streams=streams)


def _latent_inputs(key):
    """Operands in CompiledGraph.arg_names order: (gather.idx,
    gather.table, attn.lengths, attn.q) — q padded to 8 heads."""
    c = _LATENT_SMOKE
    pool = jax.random.normal(key, (c["nb"], c["page"], c["w"]), jnp.float32)
    bt = jax.random.permutation(jax.random.fold_in(key, 1), c["nb"])[
        :c["b"] * c["n_pages"]].reshape(c["b"], c["n_pages"])
    ppw = pages_per_word(c["n_pages"], c["page"], c["w"], jnp.float32,
                         page_streams=1)
    idx = gather_indices(bt.astype(jnp.int32), lane_words=1,
                         n_blocks=c["nb"], ppw=ppw)
    lens = jnp.array([41, c["n_pages"] * c["page"]], jnp.int32)
    q = 0.3 * jax.random.normal(jax.random.fold_in(key, 2),
                                (c["b"], 8, c["w"]), jnp.float32)
    return (idx, pool, lens, q)


def _latent_ref(idx, table, lengths, q):
    c = _LATENT_SMOKE
    n = idx.shape[0] // c["b"]
    bt = jnp.arange(c["b"] * n, dtype=jnp.int32).reshape(c["b"], n)
    return paged_latent_attention_ref(
        q, table[idx], bt, lengths, v_dim=c["v_dim"], scale=c["scale"])


def _latent_unfused(idx, table, lengths, q):
    """The page gather as its own ``repro.ops`` call (the pages round-trip
    HBM), then the absorbed attention on XLA."""
    import repro

    n_tab, rows, lanes = table.shape
    pages = repro.ops.gather(table.reshape(n_tab, rows * lanes), idx)
    return _latent_ref(jnp.arange(idx.shape[0], dtype=jnp.int32),
                       pages.reshape(-1, rows, lanes), lengths, q)


def _latent_sweep_inputs(key, site):
    """Rebuild paged_latent_attention operands at a recorded call-site
    shape (plan sweep), with the site's pages per word."""
    b, h, dq = int(site["b"]), int(site["h"]), int(site["dq"])
    n_pages, page = int(site["n_pages"]), int(site["page"])
    w, nb = int(site["w"]), int(site["n_blocks"])
    kv_dt = jnp.dtype(site.get("dtype", "float32"))
    q = 0.3 * jax.random.normal(key, (b, h, dq),
                                jnp.dtype(site.get("q_dtype", "float32")))
    pool = jax.random.normal(jax.random.fold_in(key, 1), (nb, page, w),
                             kv_dt)
    bt = (jax.random.permutation(jax.random.fold_in(key, 2),
                                 max(nb, b * n_pages))[:b * n_pages]
          % nb).reshape(b, n_pages).astype(jnp.int32)
    lens = jnp.full((b,), n_pages * page, jnp.int32)
    return (q, pool, bt, lens), {"v_dim": int(site["v_dim"]),
                                 "scale": dq ** -0.5,
                                 "ppw": int(site["ppw"])}


def _register_latent_graph():
    from repro.kernels.registry import register_graph

    register_graph(
        name="paged_latent_attention",
        build=_latent_build,
        make_inputs=_latent_inputs,
        ref=_latent_ref,
        unfused=_latent_unfused,
        tile_options=(),
        tol=2e-4,
        doc="block-table latent page gather -> MLA absorbed decode "
            "attention: every query head of a row against one shared "
            "latent stream (MQA with one wide KV head)",
        op=paged_latent_attention,
        sweep_inputs=_latent_sweep_inputs,
    )


_register_latent_graph()
