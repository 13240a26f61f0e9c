"""Feed-forward irregular gather as a StreamProgram: rows = table[idx].

The paper's *irregular memory access* case (Table 3, M-AI10-IR; MoE
dispatch / embedding lookup in our models). The index stream is scalar-
prefetched (TPU analogue of the FPGA burst-coalesced LSU's request buffer),
and each pipe word is a bundle of DMAs issued ``depth-1`` words ahead —
memory-level parallelism for a pattern the MXU pipeline cannot prefetch on
its own. The bundle is emitted through the shared
:class:`~repro.core.emitter.GatherRingPipe`: the chunks *are* the stream
decomposition, so the planned ``streams`` value widens the bundle
(``8 * streams`` concurrent DMAs per word, the multi-producer analogue for
irregular access) instead of being dropped.

Two table layouts:

* ``block_rows=None`` — a ``[R, C]`` table, one row per index (the form
  graph nodes such as MoE dispatch chain into row-tiled matmuls). The
  interpreter runs it; the TPU compiler refuses single-row DMAs of a 2-D
  array.
* ``block_rows=k`` — a ``[R, k, C]`` table, one whole ``(k, C)`` block per
  index. It compiles for a TPU when the block covers whole native row
  tiles (a KV page of the paged pool) or when ``k == 1`` on a 32-bit
  table. :func:`gather_ff` runs every row gather this way, on a 32-bit
  ``[R, 1, C']`` view of the table (narrower dtypes are bitcast in pairs
  or quads into uint32 lanes).

A true-MLCD variant of this op (gather from a table the same kernel is
scattering into) is *rejected* by core.check_no_mlcd and deliberately has no
kernel here — the paper's legality restriction.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.pipe import Pipe
from repro.core.program import ScalarIn, Stream, StreamProgram, \
    compile_program

_ROWS = 8   # base chunks per pipe word (one f32 sublane granule)


def build_program(n: int, cols: int, *, block_rows: Optional[int] = None,
                  blocks_per_word: Optional[int] = None, dtype=jnp.float32,
                  depth: int = 4, streams: int = 1) -> StreamProgram:
    """Declare the gather stream program: ``n`` indexed chunks pulled from
    a ``[R, cols]`` table (``block_rows=None``, one row each) or a
    ``[R, block_rows, cols]`` table (one block each). A word bundles
    ``blocks_per_word`` chunks (default ``8 * streams``); ``n`` must be a
    multiple of it."""
    bpw = blocks_per_word or _ROWS * streams
    assert n % bpw == 0, (n, bpw)
    block = () if block_rows is None else (block_rows,)

    def chunk_slicer(ctx, word, r):
        i = ctx.ref("idx")[word * bpw + r]
        if block_rows is None:
            return ctx.ref("table").at[pl.ds(i, 1), :]
        return ctx.ref("table").at[i]

    def consumer(ctx):
        ctx.out[...] = ctx.word("table")[...]

    return StreamProgram(
        name="ff_gather",
        n_words=n // bpw,
        inputs=(
            ScalarIn("idx"),
            Stream("table",
                   Pipe(tile=(bpw, *block, cols), dtype=dtype, depth=depth),
                   chunk_slicer, gather=True),
        ),
        consumer=consumer,
        out_shape=(n, *block, cols),
        out_dtype=dtype,
        out_block=(bpw, *block, cols),
        out_index_map=lambda g, idx: (g,) + (0,) * (1 + len(block)),
    )


def _as_words(table: jnp.ndarray) -> jnp.ndarray:
    """``[R, C]`` -> ``[R, 1, C']`` 32-bit view (zero-padded so ``C'`` is a
    multiple of 8; sub-32-bit dtypes bitcast ``4 // itemsize`` lanes into
    one uint32)."""
    r, c = table.shape
    k = max(1, 4 // table.dtype.itemsize)
    pad = -c % (8 * k)
    if pad:
        table = jnp.pad(table, ((0, 0), (0, pad)))
    if k > 1:
        table = jax.lax.bitcast_convert_type(
            table.reshape(r, -1, k), jnp.uint32)
    return table[:, None, :]


def _from_words(rows: jnp.ndarray, dtype, cols: int) -> jnp.ndarray:
    """Inverse of :func:`_as_words` on the gathered ``[n, 1, C']`` rows."""
    rows = rows[:, 0, :]
    if rows.dtype != jnp.dtype(dtype):
        rows = jax.lax.bitcast_convert_type(rows, dtype)
        rows = rows.reshape(rows.shape[0], -1)
    return rows[:, :cols]


@functools.partial(jax.jit, static_argnames=("depth", "streams", "interpret"))
def gather_ff(table: jnp.ndarray, idx: jnp.ndarray, *, depth: int = 4,
              streams: int = 1, interpret: Optional[bool] = None
              ) -> jnp.ndarray:
    """table: [R, C]; idx: [n] int32 with n % (8 * streams) == 0.
    Returns [n, C]."""
    words = _as_words(table)
    n = idx.shape[0]
    program = build_program(n, words.shape[-1], block_rows=1,
                            dtype=words.dtype, depth=depth, streams=streams)
    rows = compile_program(program, interpret=interpret)(idx, words)
    return _from_words(rows, table.dtype, table.shape[1])
