"""Feed-forward chunked gated-linear-attention scan (Mamba2 / RWKV6
family), as a StreamProgram.

This kernel is the paper's Figure-3 move (DLCD -> compute kernel) made
literal. The recurrence

    h_t = diag(w_t) h_{t-1} + k_t (x) v_t            (true data LCD)
    y_t = q_t . h_t              (inclusive; Mamba2:  w scalar per head)
    y_t = q_t . (h_{t-1} + diag(u) k_t (x) v_t)      (exclusive+bonus; RWKV6)

serializes a naive implementation at II = chain length. The feed-forward
split streams the *LCD-free* operands (q,k,v,w chunks) through four ring-
pipe edges at full depth, while the consumer carries the only true
dependency — the O(N*P) chunk-boundary state — in VMEM across grid steps.

Numerics: all decay exponents are arranged to be <= 0 ("decay-to-boundary"
factorization), so every exp() is in (0,1] and f32-stable:

* inter-chunk:   q_t * exp(cw_t [- lw_t])                  (<= 0)
* intra, tile pair J<I with boundary b = start(I)-1:
      A_ts = (q_t e^{cw_t - cw_b [- lw_t]}) . (k_s e^{cw_b - cw_s})
  both exponents are sums of log-decays over non-empty ranges   (<= 0)
* diagonal tile: exact pairwise exponent, clamped at 0 under the mask
* state update:  k_s * exp(cw_last - cw_s)                  (<= 0)
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


def _chunk_body(q, k, v, lw, u, h_prev, *, subtile: int, inclusive: bool):
    """One chunk of the scan. All f32. Shapes: q,k,lw [L,N]; v [L,P];
    u [N] or None; h_prev [N,P]. Returns (y [L,P], h_new [N,P])."""
    L, n = q.shape
    p = v.shape[1]
    t = subtile
    nt = L // t
    cw = jnp.cumsum(lw, axis=0)                       # inclusive cumsum [L,N]
    q_decay = cw - lw if not inclusive else cw        # exponent for q side

    # ---- inter-chunk: contribution of the carried state ------------------
    qd = q * jnp.exp(q_decay)                         # [L,N], exp<=0
    y = jnp.dot(qd, h_prev, preferred_element_type=jnp.float32)   # [L,P]

    # ---- intra-chunk: tile-pair matmuls (J < I) + exact diagonal ---------
    for i in range(nt):
        t0 = i * t
        cw_b = cw[t0 - 1] if t0 > 0 else jnp.zeros((n,), jnp.float32)
        qt = q[t0:t0 + t]
        cwt = cw[t0:t0 + t]
        lwt = lw[t0:t0 + t]
        q_exp = cwt - cw_b[None, :] - (0.0 if inclusive else lwt)
        q_i = qt * jnp.exp(q_exp)                     # [t,N], exp<=0
        acc = jnp.zeros((t, p), jnp.float32)
        for j in range(i):
            s0 = j * t
            k_j = k[s0:s0 + t] * jnp.exp(cw_b[None, :] - cw[s0:s0 + t])
            a = jax.lax.dot_general(
                q_i, k_j, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # [t,t]
            acc += jnp.dot(a, v[s0:s0 + t], preferred_element_type=jnp.float32)
        # diagonal tile: exact pairwise exponents
        cws = cwt
        e = cwt[:, None, :] - cws[None, :, :]
        if not inclusive:
            e = e - lwt[:, None, :]
        e = jnp.minimum(e, 0.0)                       # masked entries clamped
        a_diag = jnp.sum(qt[:, None, :] * jnp.exp(e) * k[t0:t0 + t][None, :, :],
                         axis=-1)                     # [t,t]
        rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
        keep = (rows >= cols) if inclusive else (rows > cols)
        a_diag = jnp.where(keep, a_diag, 0.0)
        acc += jnp.dot(a_diag, v[t0:t0 + t], preferred_element_type=jnp.float32)
        y = jax.lax.dynamic_update_slice_in_dim(y, y[t0:t0 + t] + acc, t0, 0)

    # ---- bonus (RWKV6 u-term): current token, undecayed -------------------
    if u is not None:
        c = jnp.sum(q * u[None, :] * k, axis=1, keepdims=True)   # [L,1]
        y = y + c * v

    # ---- state update ------------------------------------------------------
    k2 = k * jnp.exp(cw[-1][None, :] - cw)            # [L,N], exp<=0
    h_new = jnp.exp(cw[-1])[:, None] * h_prev + jax.lax.dot_general(
        k2, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return y, h_new


def build_program(bh: int, s: int, n: int, p: int, *,
                  chunk: int = 64, subtile: int = 16, inclusive: bool = True,
                  has_u: bool = False, dtype=jnp.float32, k_dtype=None,
                  v_dtype=None, w_dtype=None, out_dtype=None,
                  depth: int = 2, streams: int = 1) -> StreamProgram:
    """Declare the chunked-scan stream program at one shape point.
    ``dtype`` is the q/out element type; ``k_dtype``/``v_dtype``/``w_dtype``
    (default ``dtype``) size their own pipe edges."""
    assert s % chunk == 0 and chunk % subtile == 0, (s, chunk, subtile)
    nc = s // chunk
    out_dtype = out_dtype or dtype
    q_spec = Pipe(tile=(chunk, n), dtype=dtype, depth=depth, streams=streams)
    k_spec = Pipe(tile=(chunk, n), dtype=k_dtype or dtype, depth=depth,
                  streams=streams)
    w_spec = Pipe(tile=(chunk, n), dtype=w_dtype or dtype, depth=depth,
                  streams=streams)
    v_spec = Pipe(tile=(chunk, p), dtype=v_dtype or dtype, depth=depth,
                  streams=streams)

    def slicer(name):
        def f(ctx, word):
            w_c = word % nc
            w_bh = word // nc
            return ctx.ref(name).at[w_bh, pl.ds(w_c * chunk, chunk), :]
        return f

    def consumer(ctx):
        c = ctx.g % nc
        h_sc = ctx.scratch("h")

        @pl.when(c == 0)
        def _():
            h_sc[...] = jnp.zeros_like(h_sc)

        q = ctx.word("q")[...].astype(jnp.float32)
        k = ctx.word("k")[...].astype(jnp.float32)
        v = ctx.word("v")[...].astype(jnp.float32)
        lw = jnp.minimum(ctx.word("w")[...].astype(jnp.float32), 0.0)
        u = ctx.ref("u")[0].astype(jnp.float32) if has_u else None

        y, h_new = _chunk_body(q, k, v, lw, u, h_sc[...],
                               subtile=subtile, inclusive=inclusive)
        h_sc[...] = h_new
        ctx.out[0] = y.astype(out_dtype)

    return StreamProgram(
        name="ff_chunk_scan",
        n_words=bh * nc,
        inputs=(
            # all four streams walk (bh, chunk)-major; the index declares
            # that schedule in each pipe's (chunk, cols) blocking of the
            # row-flattened [BH*S, cols] operand view (a fused producer
            # edge declares reshape=(bh*s, cols)), matching the slicer
            Stream("q", q_spec, slicer("q"), index=lambda w: (w, 0)),
            Stream("k", k_spec, slicer("k"), index=lambda w: (w, 0)),
            Stream("v", v_spec, slicer("v"), index=lambda w: (w, 0)),
            Stream("w", w_spec, slicer("w"), index=lambda w: (w, 0)),
            BlockIn("u", (1, n), lambda g: (g // nc, 0), dtype=dtype),
        ),
        consumer=consumer,
        out_shape=(bh, s, p),
        out_dtype=out_dtype,
        out_block=(1, chunk, p),
        out_index_map=lambda g: (g // nc, g % nc, 0),
        scratch=(ScratchSpec("h", (n, p), jnp.float32),),
    )


@functools.partial(
    jax.jit,
    static_argnames=("chunk", "subtile", "inclusive", "depth", "streams",
                     "interpret"))
def chunk_scan_ff(
    q: jnp.ndarray,               # [BH, S, N]
    k: jnp.ndarray,               # [BH, S, N]
    v: jnp.ndarray,               # [BH, S, P]
    log_w: jnp.ndarray,           # [BH, S, N] log-decay (<= 0)
    u: jnp.ndarray = None,        # [BH, N] bonus (RWKV6) or None
    *,
    chunk: int = 64,
    subtile: int = 16,
    inclusive: bool = True,
    depth: int = 2,
    streams: int = 1,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    bh, s, n = q.shape
    p = v.shape[2]
    has_u = u is not None
    program = build_program(bh, s, n, p, chunk=chunk, subtile=subtile,
                            inclusive=inclusive, has_u=has_u, dtype=q.dtype,
                            k_dtype=k.dtype, v_dtype=v.dtype,
                            w_dtype=log_w.dtype, depth=depth, streams=streams)
    u_arg = u if has_u else jnp.zeros((bh, n), q.dtype)
    return compile_program(program, interpret=interpret)(
        q, k, v, log_w, u_arg)
