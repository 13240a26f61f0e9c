"""Ring-pipe emitter: the shared runtime every ff_* kernel emits through.

The paper splits each kernel into a *memory kernel* (address generation +
loads) and a *compute kernel*, connected by an on-chip pipe. On TPU the pipe
is a VMEM ring buffer fed by async DMAs, and historically each Pallas kernel
hand-rolled the same idiom: slot rotation, a depth-word warmup prologue,
paired ``start``/``wait`` calls, and a refill after consumption. MKPipe
(arXiv 2002.01614) argues this duplication belongs in a compiler/runtime
layer; this module is that layer for the repo.

A :class:`RingPipe` is constructed at trace time from a :class:`core.Pipe`
spec. It *owns* the scratch shapes its ring needs (VMEM buffer + DMA
semaphore array), is bound to the concrete scratch refs inside the kernel,
and then exposes the four emission primitives:

  start(word)        producer: issue the async copies for ``word``
  wait(word)         consumer: block until ``word`` has landed
  slot(word)         VMEM ref of the landed word (the pipe read endpoint)
  prologue(g, n)     warmup: at grid step 0, fill the ring ``depth`` deep
                     (``depth == 1`` degenerates to the synchronous
                     copy-then-compute baseline: start word ``g`` now)

plus the release primitive ``refill(g, n)`` (word ``g`` consumed; start
``g + depth``) and the whole-schedule conveniences ``acquire``/``release``
that iterate a set of pipes. Two access patterns are covered:

* :class:`RingPipe` — regular block copies. ``streams > 1`` splits each
  word into disjoint row ranges issued as concurrent DMAs (the paper's
  multi-producer M2C2 design, static load balancing).
* :class:`GatherRingPipe` — irregular gathers. Each word is a bundle of
  ``tile[0]`` DMAs (single rows, or whole blocks such as KV pages) whose
  sources come from a dynamically-indexed slicer (scalar-prefetched
  indices); the bundle is the stream decomposition, giving
  ``depth x tile[0]`` outstanding requests of memory-level parallelism (the
  burst-coalesced-LSU analogue).

The source slicer can depend only on the word index (and scalar-prefetch
values), never on consumer state — the feed-forward restriction, enforced
structurally.

Kernel skeleton::

    ring = RingPipe(pipe_spec)                      # trace time
    pl.pallas_call(kernel, ...,
                   scratch_shapes=[..., *ring.scratch_shapes])

    def kernel(..., buf, sems):                     # inside the kernel
        p = ring.bind(buf, sems, lambda word: hbm.at[...])
        acquire(g, n_words, [p])
        compute(p.slot(g)[...])
        release(g, n_words, [p])
"""

from __future__ import annotations

from typing import Callable, Sequence, Tuple

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.pipe import Pipe


class RingPipe:
    """Emitter for one regular (block-copy) ring pipe.

    Constructed from the :class:`Pipe` spec at trace time; bound to its
    scratch refs and HBM slicer inside the kernel via :meth:`bind`.
    """

    def __init__(self, spec: Pipe):
        self.spec = spec
        self._buf = None
        self._sems = None
        self._slicer: Callable | None = None

    # -- scratch ownership (trace time) ------------------------------------

    @property
    def n_dmas(self) -> int:
        """Concurrent DMAs per word."""
        return self.spec.streams

    @property
    def n_sems(self) -> int:
        """DMA semaphores per ring slot."""
        return self.n_dmas

    @property
    def scratch_shapes(self) -> Tuple:
        """The scratch this pipe owns: (VMEM ring buffer, DMA semaphores)."""
        return (
            pltpu.VMEM(self.spec.buffer_shape, self.spec.dtype),
            pltpu.SemaphoreType.DMA((self.spec.depth, self.n_sems)),
        )

    # -- binding (in kernel) ------------------------------------------------

    def bind(self, buf, sems, src_slicer: Callable) -> "RingPipe":
        """Attach the scratch refs and the memory kernel's address stream.

        ``src_slicer(word) -> hbm-ref-slice`` names the HBM region of pipe
        word ``word`` and may depend only on the word index.
        """
        self._buf = buf
        self._sems = sems
        self._slicer = src_slicer
        return self

    # -- emission primitives -------------------------------------------------

    def _copies(self, word):
        """The async-copy descriptors of one word (one per stream)."""
        slot = word % self.spec.depth
        src = self._slicer(word)
        rows = self.spec.tile[0] // self.spec.streams
        for s in range(self.spec.streams):
            lo = s * rows
            yield pltpu.make_async_copy(
                src.at[pl.ds(lo, rows)],
                self._buf.at[slot, pl.ds(lo, rows)],
                self._sems.at[slot, s],
            )

    def start(self, word) -> None:
        """Producer: issue the (possibly multi-stream) copy for ``word``."""
        for c in self._copies(word):
            c.start()

    def wait(self, word) -> None:
        """Consumer: block until ``word`` landed (paper: blocking read)."""
        for c in self._copies(word):
            c.wait()

    def slot(self, word):
        """VMEM ref of the landed word (the pipe read endpoint)."""
        return self._buf.at[word % self.spec.depth]

    def prologue(self, g, n_words: int) -> None:
        """Warmup fill at grid step ``g`` of ``n_words``.

        depth == 1: start word ``g`` (synchronous baseline, no lookahead).
        depth >= 2: at g == 0, start the first ``depth`` words (the pipe's
        full lookahead); later steps issue nothing here (refill happens in
        :meth:`refill`).
        """
        if self.spec.depth == 1:
            self.start(g)
            return

        @pl.when(g == 0)
        def _():
            for d in range(self.spec.depth):
                @pl.when(d < n_words)
                def _(d=d):
                    self.start(d)

    def refill(self, g, n_words: int) -> None:
        """Word ``g`` consumed; refill its slot with word ``g + depth``.

        Must run *after* the compute that reads ``slot(g)`` — refilling
        earlier would let the DMA clobber the word being consumed.
        """
        if self.spec.depth == 1:
            return

        @pl.when(g + self.spec.depth < n_words)
        def _():
            self.start(g + self.spec.depth)


class GatherRingPipe(RingPipe):
    """Emitter for one irregular (gather) ring pipe.

    Each pipe word is a bundle of ``tile[0]`` chunks fetched from
    dynamically indexed locations; ``bind`` takes a *chunk* slicer
    ``chunk_slicer(word, r) -> hbm-ref-slice``, typically indexed through a
    scalar-prefetched index vector. For a rank-2 tile ``(rows, cols)`` a
    chunk is one row (it lands in row ``r`` of the slot); for a rank-3 tile
    ``(chunks, rows, cols)`` a chunk is a whole ``(rows, cols)`` block (it
    lands in ``slot[r]``). Only the rank-3 form compiles for a TPU: its DMA
    engine moves whole native row tiles, such as a KV page, or the single
    row of an ``[N, 1, C]`` 32-bit table. Chunks are the stream
    decomposition (spec.streams is ignored for DMA splitting): a word
    issues ``tile[0]`` concurrent DMAs, so the ring sustains
    ``(depth-1) * tile[0]`` outstanding irregular requests.

    The chunks of one word share their slot's semaphore (each wait
    consumes one chunk's bytes): a semaphore per chunk would outgrow the
    TPU's few KiB of semaphore memory at deep rings of wide bundles.
    """

    @property
    def rows(self) -> int:
        return self.spec.tile[0]

    @property
    def n_dmas(self) -> int:
        return self.rows

    @property
    def n_sems(self) -> int:
        return 1

    def bind(self, buf, sems,
             chunk_slicer: Callable) -> "GatherRingPipe":
        return super().bind(buf, sems, chunk_slicer)

    def _copies(self, word):
        slot = word % self.spec.depth
        blocks = len(self.spec.tile) > 2
        for r in range(self.rows):
            dst = (self._buf.at[slot, r] if blocks
                   else self._buf.at[slot, pl.ds(r, 1)])
            yield pltpu.make_async_copy(
                self._slicer(word, r), dst, self._sems.at[slot, 0])


# -- whole-schedule helpers (the DAE word schedule) --------------------------


def acquire(g, n_words: int, pipes: Sequence[RingPipe]) -> None:
    """Acquire phase at grid step ``g``: prologue fills, then block on word
    ``g`` of every pipe. All starts issue before any wait, so multi-pipe
    warmups overlap. Pipes may have different depths."""
    for p in pipes:
        p.prologue(g, n_words)
    for p in pipes:
        p.wait(g)


def release(g, n_words: int, pipes: Sequence[RingPipe]) -> None:
    """Release phase: word ``g`` consumed on every pipe; refill the slots."""
    for p in pipes:
        p.refill(g, n_words)


# -- tiling utilities ---------------------------------------------------------


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def pad_to(x: jnp.ndarray, multiple: int, axis: int) -> jnp.ndarray:
    """Zero-pad ``axis`` of x up to a multiple (TPU tile alignment)."""
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


# -- lane packing: narrow heads share one 128-lane word ------------------------
#
# The TPU's DMA engine moves whole 128-lane rows: a copy whose minor dim is
# 64 is refused even when 64 is the array's full extent. Attention kernels
# therefore stream K/V in words of ``p * hd`` lanes, ``p`` adjacent heads
# side by side (``p = 2`` at hd 64), instead of padding hd in HBM, which
# would double the bytes decode streams. A query meets only its own head's
# lanes: it is zero in the other heads' lanes (block-diagonal for decode,
# one lane block per q head for prefill), so every score is exactly its
# own head's dot product, and the output's diagonal blocks are the heads'.


def lane_pack(heads: int, hd: int) -> int:
    """Heads per lane word: the largest divisor ``p`` of ``heads`` with
    ``p * hd <= 128`` (1 when ``hd >= 128``)."""
    p = max(1, min(heads, 128 // hd))
    while heads % p:
        p -= 1
    return p


def pack_heads(x: jnp.ndarray, p: int) -> jnp.ndarray:
    """``[..., H, S, D]`` -> ``[..., H/p, S, p*D]``: heads ``j*p .. j*p+p-1``
    side by side in the lanes of row group ``j``."""
    *lead, h, s, d = x.shape
    x = x.reshape(*lead, h // p, p, s, d)
    x = jnp.swapaxes(x, -3, -2)
    return x.reshape(*lead, h // p, s, p * d)


def pack_blockdiag(q: jnp.ndarray, p: int) -> jnp.ndarray:
    """``[..., H, G, D]`` -> ``[..., H/p, p*G, p*D]``: head ``a`` of each
    group owns rows ``a*G ..`` and lanes ``a*D ..``; every other lane is 0."""
    *lead, h, g, d = q.shape
    q = q.reshape(*lead, h // p, p, g, 1, d)
    eye = jnp.eye(p, dtype=bool)[:, None, :, None]       # [p, 1, p, 1]
    out = jnp.where(eye, q, jnp.zeros((), q.dtype))      # [.., p, g, p, d]
    return out.reshape(*lead, h // p, p * g, p * d)


def unpack_blockdiag(o: jnp.ndarray, p: int) -> jnp.ndarray:
    """Inverse of :func:`pack_blockdiag` on a kernel output: keep each
    head's diagonal block. ``[..., H/p, p*G, p*D]`` -> ``[..., H, G, D]``."""
    *lead, j, pg, pd = o.shape
    g, d = pg // p, pd // p
    o = o.reshape(*lead, j, p, g, p, d)
    o = jnp.diagonal(o, axis1=-4, axis2=-2)              # [.., j, g, d, p]
    o = jnp.moveaxis(o, -1, -3)                          # [.., j, p, g, d]
    return o.reshape(*lead, j * p, g, d)


def spread_lanes(x: jnp.ndarray, p: int, groups: int) -> jnp.ndarray:
    """``[BH, S, D]`` q rows -> ``[BH, S, p*D]``: row ``bh`` reads KV row
    ``bh // groups``, so it sits in that row's lane block
    ``(bh // groups) % p`` and is zero elsewhere."""
    bh, s, d = x.shape
    lane = (jnp.arange(bh) // groups) % p
    mask = lane[:, None, None, None] == jnp.arange(p)[None, None, :, None]
    out = jnp.where(mask, x[:, :, None, :], jnp.zeros((), x.dtype))
    return out.reshape(bh, s, p * d)


def gather_lanes(o: jnp.ndarray, p: int, groups: int) -> jnp.ndarray:
    """Inverse of :func:`spread_lanes` on a kernel output."""
    bh, s, pd = o.shape
    lane = (jnp.arange(bh) // groups) % p
    o = o.reshape(bh, s, p, pd // p)
    return jnp.take_along_axis(o, lane[:, None, None, None], axis=2)[:, :, 0]
