"""Feed-forward (DAE) blocked matmul: C = A @ B, as a StreamProgram.

The paper's transformation, applied to the canonical MXU workload:

* producer stages = the A and B tile streams (two ring-pipe edges), issued
  ``depth-1`` words ahead; ``streams`` splits each tile copy into parallel
  sub-DMAs (multi-producer M2C2 analogue);
* consumer       = MXU dot over the landed tiles, accumulating in VMEM f32;
* ``depth=1`` degenerates to synchronous copy-then-compute — the "single
  work-item" baseline used by the Table-2 benchmark.

Word schedule: 1-D grid over (mi, ni, ki) with k innermost; the output block
(mi, ni) is revisited for nK consecutive steps and written on the last.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.pipe import Pipe
from repro.core.program import ScratchSpec, Stream, StreamProgram, \
    compile_program


def build_program(m: int, n: int, k: int, *,
                  block: Tuple[int, int, int] = (128, 128, 128),
                  dtype=jnp.float32, b_dtype=None, out_dtype=None,
                  depth: int = 2, streams: int = 1) -> StreamProgram:
    """Declare the matmul stream program at one (block-aligned) shape.
    ``dtype`` sizes the A pipe, ``b_dtype`` (default ``dtype``) the B pipe —
    each operand streams through a ring of its own element type."""
    bm, bn, bk = block
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, ((m, n, k), block)
    nm, nn, nk = m // bm, n // bn, k // bk
    b_dtype = b_dtype or dtype
    out_dtype = out_dtype or dtype

    def a_slicer(ctx, word):
        w_ki = word % nk
        w_mi = word // (nk * nn)
        return ctx.ref("a").at[pl.ds(w_mi * bm, bm), pl.ds(w_ki * bk, bk)]

    def b_slicer(ctx, word):
        w_ki = word % nk
        w_ni = (word // nk) % nn
        return ctx.ref("b").at[pl.ds(w_ki * bk, bk), pl.ds(w_ni * bn, bn)]

    def consumer(ctx):
        ki = ctx.g % nk
        acc = ctx.scratch("acc")

        @pl.when(ki == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += jnp.dot(ctx.word("a")[...], ctx.word("b")[...],
                            preferred_element_type=jnp.float32)

        @pl.when(ki == nk - 1)
        def _():
            ctx.out[...] = acc[...].astype(out_dtype)

    return StreamProgram(
        name="ff_matmul",
        n_words=nm * nn * nk,
        inputs=(
            # index declares each stream's block schedule (the address
            # stream as pure int arithmetic) so the graph fuser can match
            # an upstream producer's output schedule against it
            Stream("a", Pipe(tile=(bm, bk), dtype=dtype, depth=depth,
                             streams=streams), a_slicer,
                   index=lambda w: (w // (nk * nn), w % nk)),
            Stream("b", Pipe(tile=(bk, bn), dtype=b_dtype, depth=depth,
                             streams=streams), b_slicer,
                   index=lambda w: (w % nk, (w // nk) % nn)),
        ),
        consumer=consumer,
        out_shape=(m, n),
        out_dtype=out_dtype,
        out_block=(bm, bn),
        out_index_map=lambda g: (g // (nn * nk), (g // nk) % nn),
        scratch=(ScratchSpec("acc", (bm, bn), jnp.float32),),
    )


@functools.partial(
    jax.jit,
    static_argnames=("block", "depth", "streams", "out_dtype", "interpret"))
def matmul_ff(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    block: Tuple[int, int, int] = (128, 128, 128),
    depth: int = 2,
    streams: int = 1,
    out_dtype=None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """DAE-pipelined matmul. Shapes must be multiples of ``block`` (use
    ops.matmul for auto-padding)."""
    (m, k), (k2, n) = a.shape, b.shape
    assert k == k2, (a.shape, b.shape)
    program = build_program(m, n, k, block=block, dtype=a.dtype,
                            b_dtype=b.dtype, out_dtype=out_dtype, depth=depth,
                            streams=streams)
    return compile_program(program, interpret=interpret)(a, b)
