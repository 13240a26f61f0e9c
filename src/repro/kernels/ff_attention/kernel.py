"""Feed-forward flash attention (prefill), GQA-aware, as a StreamProgram.

Paper mapping: XLA's *un-fused* attention materializes the [S, S] score
matrix in HBM — the TPU analogue of the baseline kernel whose loads round-
trip global memory. The feed-forward version streams K/V tiles through VMEM
ring pipes (two producer stages) while the online-softmax consumer never
touches HBM for intermediates. The softmax running state (m, l, acc) is the
DLCD of the paper's Fig. 3: it is loop-carried in the *consumer only*, so
the K/V stream pipelines at full depth regardless.

Layout: q,k,v are [BH, S, D] with KV heads already broadcast-indexed by the
wrapper (GQA: q head h reads kv head h // group). Grid is 1-D over
(bh, qi, kj), kj innermost, causal blocks skipped via predication. The op
wrapper hands the kernel lane-packed KV rows
(:func:`~repro.core.emitter.lane_pack`): D is ``p * hd`` lanes, each q row
is zero outside its own head's lane block, and ``scale`` (``1/sqrt(hd)``)
is passed in rather than derived from D.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.pipe import Pipe
from repro.core.program import BlockIn, ScratchSpec, Stream, StreamProgram, \
    compile_program

_NEG_INF = -1e30


def build_program(bh: int, s: int, skv: int, d: int, *,
                  kv_groups: int = 1, block_q: int = 128, block_kv: int = 128,
                  causal: bool = True, dtype=jnp.float32, k_dtype=None,
                  v_dtype=None, out_dtype=None,
                  depth: int = 2, streams: int = 1,
                  scale: Optional[float] = None) -> StreamProgram:
    """Declare the prefill-attention stream program at one shape point.
    ``dtype`` is the q/out element type; ``k_dtype``/``v_dtype`` (default
    ``dtype``) size their own pipe edges; ``scale`` defaults to
    ``1/sqrt(d)``."""
    assert s % block_q == 0 and skv % block_kv == 0, (s, skv, block_q, block_kv)
    nq, nkv = s // block_q, skv // block_kv
    scale = 1.0 / (d ** 0.5) if scale is None else scale
    out_dtype = out_dtype or dtype
    k_spec = Pipe(tile=(block_kv, d), dtype=k_dtype or dtype, depth=depth,
                  streams=streams)
    v_spec = Pipe(tile=(block_kv, d), dtype=v_dtype or dtype, depth=depth,
                  streams=streams)

    def kv_slicer(name):
        def f(ctx, word):
            w_kj = word % nkv
            w_bh = (word // (nkv * nq)) // kv_groups
            return ctx.ref(name).at[w_bh, pl.ds(w_kj * block_kv, block_kv), :]
        return f

    def consumer(ctx):
        kj = ctx.g % nkv
        qi = (ctx.g // nkv) % nq
        m_sc, l_sc = ctx.scratch("m"), ctx.scratch("l")
        acc = ctx.scratch("acc")

        @pl.when(kj == 0)
        def _():
            m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
            l_sc[...] = jnp.zeros_like(l_sc)
            acc[...] = jnp.zeros_like(acc)

        q_end = (qi + 1) * block_q - 1
        kv_start = kj * block_kv
        live = (kv_start <= q_end) if causal else True

        @pl.when(live)
        def _():
            q = ctx.ref("q")[0]                       # [bq, d]
            k = ctx.word("k")[...]                    # [bkv, d]
            v = ctx.word("v")[...]                    # [bkv, d]
            s_ = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [bq, bkv]
            if causal:
                rows = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_kv), 0)
                cols = kv_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_kv), 1)
                s_ = jnp.where(rows >= cols, s_, _NEG_INF)
            m_prev = m_sc[:, :1]                      # [bq, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s_, axis=1, keepdims=True))
            p = jnp.exp(s_ - m_new)                   # [bq, bkv]
            alpha = jnp.exp(m_prev - m_new)           # [bq, 1]
            l_new = l_sc[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc[...] = acc[...] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
            l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)

        @pl.when(kj == nkv - 1)
        def _():
            l = l_sc[:, :1]
            l = jnp.where(l == 0.0, 1.0, l)           # fully-masked rows -> 0
            ctx.out[0] = (acc[...] / l).astype(out_dtype)

    q_index_map = lambda g: (g // (nkv * nq), (g // nkv) % nq, 0)
    # k/v block schedule in the pipe's own (block_kv, d) blocking of the
    # row-flattened [BKVH*Skv, d] operand view (an upstream producer edge
    # must declare reshape=(bkvh*skv, d)); matches kv_slicer exactly
    kv_index = lambda w: (((w // (nkv * nq)) // kv_groups) * nkv + w % nkv,
                          0)
    return StreamProgram(
        name="ff_attention",
        n_words=bh * nq * nkv,
        inputs=(
            # dtype on the BlockIn sizes its ring when a fused graph
            # promotes q to a stream; index declares the k/v schedules
            BlockIn("q", (1, block_q, d), q_index_map, dtype=dtype),
            Stream("k", k_spec, kv_slicer("k"), index=kv_index),
            Stream("v", v_spec, kv_slicer("v"), index=kv_index),
        ),
        consumer=consumer,
        out_shape=(bh, s, d),
        out_dtype=out_dtype,
        out_block=(1, block_q, d),
        out_index_map=q_index_map,
        scratch=(
            ScratchSpec("m", (block_q, 128), jnp.float32),
            ScratchSpec("l", (block_q, 128), jnp.float32),
            ScratchSpec("acc", (block_q, d), jnp.float32),
        ),
    )


@functools.partial(
    jax.jit,
    static_argnames=("kv_groups", "block_q", "block_kv", "depth", "streams",
                     "causal", "scale", "interpret"))
def flash_attention_ff(
    q: jnp.ndarray,               # [BH, S, D]
    k: jnp.ndarray,               # [BKVH, S, D]
    v: jnp.ndarray,               # [BKVH, S, D]
    *,
    kv_groups: int = 1,
    block_q: int = 128,
    block_kv: int = 128,
    depth: int = 2,
    streams: int = 1,
    causal: bool = True,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    bh, s, d = q.shape
    kvbh, skv, dk = k.shape
    assert d == dk and v.shape == k.shape and bh == kvbh * kv_groups
    program = build_program(bh, s, skv, d, kv_groups=kv_groups,
                            block_q=block_q, block_kv=block_kv, causal=causal,
                            dtype=q.dtype, k_dtype=k.dtype, v_dtype=v.dtype,
                            depth=depth, streams=streams, scale=scale)
    return compile_program(program, interpret=interpret)(q, k, v)
