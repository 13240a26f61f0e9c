"""Feed-forward decode attention as a StreamProgram: one new token vs. a
long KV cache.

The decode step is the paper's favourable case par excellence: a huge,
perfectly *regular* stream (the KV cache) consumed by a tiny reduction with
a loop-carried softmax state. The cache stream is DLCD-free, so the memory
kernel prefetches KV tiles at full pipe depth while the consumer folds the
online softmax — the whole kernel runs at HBM bandwidth (roofline-memory
bound), which is exactly what the roofline table shows for decode cells.

Layout: q is [B, KVH, G, D] (G = padded query-head group per KV head, GQA),
cache k/v are [B, KVH, S, D], ``lengths[B]`` is scalar-prefetched and gives
the live cache prefix. Grid: 1-D over (b*kvh, kv_block), kv innermost.
The op wrapper hands the kernel lane-packed heads
(:func:`~repro.core.emitter.lane_pack`): "KVH" counts lane words of
``lane_heads`` heads, D is ``lane_heads * hd`` lanes and q is
block-diagonal, so ``scale`` (``1/sqrt(hd)``) is passed in rather than
derived from D, and the output keeps only its diagonal blocks (each head's
rows against its own lanes; the rest is zero).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.pipe import Pipe
from repro.core.program import BlockIn, ScalarIn, ScratchSpec, Stream, \
    StreamProgram, compile_program

_NEG_INF = -1e30


def _finalize(ctx, acc, l_sc, lane_heads: int, out_dtype) -> None:
    """Normalize the accumulator into the output block; with lane-packed
    heads keep only the diagonal (head-own-lanes) blocks."""
    l = l_sc[:, :1]
    l = jnp.where(l == 0.0, 1.0, l)
    out = acc[...] / l
    if lane_heads > 1:
        rows, lanes = out.shape
        r = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
        c = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
        out = jnp.where(r // (rows // lane_heads) == c // (lanes // lane_heads),
                        out, 0.0)
    ctx.out[0, 0] = out.astype(out_dtype)


def build_program(b: int, kvh: int, g_pad: int, s: int, d: int, *,
                  block_kv: int = 128, dtype=jnp.float32, k_dtype=None,
                  v_dtype=None, out_dtype=None,
                  depth: int = 2, streams: int = 1,
                  scale: Optional[float] = None,
                  lane_heads: int = 1) -> StreamProgram:
    """Declare the decode-attention stream program at one shape point.
    ``dtype`` is the q/out element type; ``k_dtype``/``v_dtype`` (default
    ``dtype``) size their own cache pipe edges; ``scale`` defaults to
    ``1/sqrt(d)``; ``lane_heads`` is the heads packed per lane word."""
    assert s % block_kv == 0, (s, block_kv)
    nkv = s // block_kv
    scale = 1.0 / (d ** 0.5) if scale is None else scale
    out_dtype = out_dtype or dtype
    k_spec = Pipe(tile=(block_kv, d), dtype=k_dtype or dtype, depth=depth,
                  streams=streams)
    v_spec = Pipe(tile=(block_kv, d), dtype=v_dtype or dtype, depth=depth,
                  streams=streams)

    def kv_slicer(name):
        def f(ctx, word):
            w_kj = word % nkv
            w_bh = word // nkv
            return ctx.ref(name).at[w_bh // kvh, w_bh % kvh,
                                    pl.ds(w_kj * block_kv, block_kv), :]
        return f

    def consumer(ctx):
        kj = ctx.g % nkv
        b_idx = (ctx.g // nkv) // kvh
        length = ctx.ref("lengths")[b_idx]
        m_sc, l_sc = ctx.scratch("m"), ctx.scratch("l")
        acc = ctx.scratch("acc")

        @pl.when(kj == 0)
        def _():
            m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
            l_sc[...] = jnp.zeros_like(l_sc)
            acc[...] = jnp.zeros_like(acc)

        kv_start = kj * block_kv

        @pl.when(kv_start < length)
        def _():
            q = ctx.ref("q")[0, 0]                     # [g_pad, d]
            k = ctx.word("k")[...]                     # [bkv, d]
            v = ctx.word("v")[...]                     # [bkv, d]
            s_ = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [g_pad, bkv]
            cols = kv_start + jax.lax.broadcasted_iota(
                jnp.int32, (g_pad, block_kv), 1)
            s_ = jnp.where(cols < length, s_, _NEG_INF)
            m_prev = m_sc[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s_, axis=1, keepdims=True))
            p = jnp.exp(s_ - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_sc[...] = jnp.broadcast_to(
                l_sc[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
                l_sc.shape)
            acc[...] = acc[...] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)

        @pl.when(kj == nkv - 1)
        def _():
            _finalize(ctx, acc, l_sc, lane_heads, out_dtype)

    q_index_map = lambda g, lens: ((g // nkv) // kvh, (g // nkv) % kvh, 0, 0)
    return StreamProgram(
        name="ff_decode_attention",
        n_words=b * kvh * nkv,
        inputs=(
            ScalarIn("lengths"),
            BlockIn("q", (1, 1, g_pad, d), q_index_map, dtype=dtype),
            # kv block schedule in the pipe's (block_kv, d) blocking of the
            # row-flattened [B*KVH*S, d] cache view (a fused producer edge
            # declares reshape=(b*kvh*s, d)): the word order is exactly
            # (b, h, kj)-major, so word w reads row block w
            Stream("k", k_spec, kv_slicer("k"), index=lambda w: (w, 0)),
            Stream("v", v_spec, kv_slicer("v"), index=lambda w: (w, 0)),
        ),
        consumer=consumer,
        out_shape=(b, kvh, g_pad, d),
        out_dtype=out_dtype,
        out_block=(1, 1, g_pad, d),
        out_index_map=q_index_map,
        scratch=(
            ScratchSpec("m", (g_pad, 128), jnp.float32),
            ScratchSpec("l", (g_pad, 128), jnp.float32),
            ScratchSpec("acc", (g_pad, d), jnp.float32),
        ),
    )


def build_paged_program(b: int, kvh: int, g_pad: int, n_pages: int,
                        page: int, d: int, *, dtype=jnp.float32,
                        kv_dtype=None, out_dtype=None,
                        depth: int = 2, streams: int = 1,
                        scale: Optional[float] = None,
                        lane_heads: int = 1) -> StreamProgram:
    """Paged-KV decode attention: the consumer half of the
    ``paged_decode_attention`` StreamGraph.

    The KV operand is the *gathered* row stream ``[B*KVH*n_pages*2*page, d]``
    produced by an ``ff_gather`` node walking the block table — each word is
    one page's K rows followed by its V rows (a merged ``(2*page, d)`` tile),
    so the producer's 8-row DMA bundles line up word-for-word with this
    stream and the edge fuses into a single ``pallas_call``. The online
    softmax is *identical* to :func:`build_program` at ``block_kv == page``
    (same tile order, same f32 accumulation), which is what makes the paged
    path bitwise-equal to the contiguous cache path.
    """
    scale = 1.0 / (d ** 0.5) if scale is None else scale
    out_dtype = out_dtype or dtype
    kv_spec = Pipe(tile=(2 * page, d), dtype=kv_dtype or dtype, depth=depth,
                   streams=streams)

    def kv_slicer(ctx, word):
        return ctx.ref("kv").at[pl.ds(word * 2 * page, 2 * page), :]

    def consumer(ctx):
        kj = ctx.g % n_pages
        b_idx = (ctx.g // n_pages) // kvh
        length = ctx.ref("lengths")[b_idx]
        m_sc, l_sc = ctx.scratch("m"), ctx.scratch("l")
        acc = ctx.scratch("acc")

        @pl.when(kj == 0)
        def _():
            m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
            l_sc[...] = jnp.zeros_like(l_sc)
            acc[...] = jnp.zeros_like(acc)

        kv_start = kj * page

        @pl.when(kv_start < length)
        def _():
            q = ctx.ref("q")[0, 0]                     # [g_pad, d]
            kv = ctx.word("kv")[...]                   # [2*page, d]
            k = kv[:page]
            v = kv[page:]
            s_ = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [g_pad, page]
            cols = kv_start + jax.lax.broadcasted_iota(
                jnp.int32, (g_pad, page), 1)
            # rows past `length` (zero padding or stale recycled-block
            # contents) mask to -inf, so their exp underflows to exactly
            # 0.0 — recycled garbage cannot perturb even the last bit
            s_ = jnp.where(cols < length, s_, _NEG_INF)
            m_prev = m_sc[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s_, axis=1, keepdims=True))
            p = jnp.exp(s_ - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_sc[...] = jnp.broadcast_to(
                l_sc[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
                l_sc.shape)
            acc[...] = acc[...] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)

        @pl.when(kj == n_pages - 1)
        def _():
            _finalize(ctx, acc, l_sc, lane_heads, out_dtype)

    q_index_map = lambda g, lens: ((g // n_pages) // kvh,
                                   (g // n_pages) % kvh, 0, 0)
    return StreamProgram(
        name="ff_paged_decode_attention",
        n_words=b * kvh * n_pages,
        inputs=(
            ScalarIn("lengths"),
            BlockIn("q", (1, 1, g_pad, d), q_index_map, dtype=dtype),
            # word w reads row block w of the gathered [n_words*2*page, d]
            # stream — the identity schedule an ff_gather producer writes,
            # so check_fusion legalizes the edge with wpb=1
            Stream("kv", kv_spec, kv_slicer, index=lambda w: (w, 0)),
        ),
        consumer=consumer,
        out_shape=(b, kvh, g_pad, d),
        out_dtype=out_dtype,
        out_block=(1, 1, g_pad, d),
        out_index_map=q_index_map,
        scratch=(
            ScratchSpec("m", (g_pad, 128), jnp.float32),
            ScratchSpec("l", (g_pad, 128), jnp.float32),
            ScratchSpec("acc", (g_pad, d), jnp.float32),
        ),
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_kv", "depth", "streams", "scale", "lane_heads",
                     "interpret"))
def decode_attention_ff(
    q: jnp.ndarray,           # [B, KVH, G_pad, D]
    k: jnp.ndarray,           # [B, KVH, S, D]
    v: jnp.ndarray,           # [B, KVH, S, D]
    lengths: jnp.ndarray,     # [B] int32
    *,
    block_kv: int = 128,
    depth: int = 2,
    streams: int = 1,
    scale: Optional[float] = None,
    lane_heads: int = 1,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    b, kvh, g_pad, d = q.shape
    _, _, s, _ = k.shape
    program = build_program(b, kvh, g_pad, s, d, block_kv=block_kv,
                            dtype=q.dtype, k_dtype=k.dtype, v_dtype=v.dtype,
                            depth=depth, streams=streams, scale=scale,
                            lane_heads=lane_heads)
    return compile_program(program, interpret=interpret)(lengths, q, k, v)
