"""Overlap-friendly collectives as Stream producers/consumers.

The feed-forward model at mesh scale: communication is the producer, the
MXU is the consumer, and ``ppermute`` rings are the pipes. This module
expresses the collective-overlap paths on an explicit ring abstraction —
:class:`RingStream`, the mesh-scale analogue of the kernel emitter's
``RingPipe`` — so the word schedule reads exactly like a
:class:`~repro.core.program.StreamProgram` body::

    for word in ring.words():
        part = consume(cur)        # compute kernel on the landed word
        cur = ring.hop(cur)        # producer: next word's transfer in
                                   # flight while `part` retires

``allgather_matmul`` and ``matmul_reducescatter`` interleave each ring hop
with the partial matmul it feeds (hop k+1 is in flight while chunk k
multiplies; XLA overlaps the independent ppermute with the dot). The local
dot is pluggable: pass a :class:`~repro.core.program.PipePolicy` to route
it through the tuned ``repro.ops.matmul`` stream kernel — the consumer of
the mesh-level pipe is then itself a pipe-structured kernel, planned at
the *local shard shapes* and cache-keyed by the mesh topology.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class RingStream:
    """The inter-device pipe of one mapped mesh axis.

    The mesh-scale analogue of :class:`repro.core.emitter.RingPipe`: a
    ``ppermute`` hop is the producer DMA moving the next word into this
    device's (single) ring slot, the loop body is the consumer, and the
    ring has ``n_words() == axis_size`` words — one per source shard.
    ``reverse`` flips the ring direction (gather rings shift forward,
    reduce-scatter rings shift partial sums backward).
    """

    axis_name: str
    reverse: bool = False

    def n_words(self) -> int:
        return jax.lax.axis_size(self.axis_name)

    def index(self):
        return jax.lax.axis_index(self.axis_name)

    def hop(self, x: jnp.ndarray) -> jnp.ndarray:
        """Issue the next word's transfer: shift ``x`` one hop around the
        ring (the producer stage; independent of the consumer's dot, so
        XLA schedules them concurrently)."""
        n = self.n_words()
        if self.reverse:
            perm = [(i, (i - 1) % n) for i in range(n)]
        else:
            perm = [(i, (i + 1) % n) for i in range(n)]
        return jax.lax.ppermute(x, self.axis_name, perm)


def _local_matmul(policy=None) -> Callable[[jnp.ndarray, jnp.ndarray],
                                           jnp.ndarray]:
    """The consumer's dot: plain XLA by default; with a policy, the tuned
    ``repro.ops.matmul`` stream kernel under that policy (mesh-tagged via
    :func:`repro.runtime.streams.mesh_policy`, so the per-shard plan is
    keyed by the topology it runs under)."""
    if policy is None:
        return lambda x, w: jnp.dot(
            x, w, preferred_element_type=jnp.promote_types(x.dtype, w.dtype))
    import repro.ops
    from repro.runtime.streams import mesh_policy
    pol = mesh_policy(policy)

    def dot(x, w):
        out_dtype = jnp.promote_types(x.dtype, w.dtype)
        return repro.ops.matmul(x, w, policy=pol,
                                out_dtype=out_dtype).astype(out_dtype)
    return dot


def ring_allgather(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """All-gather along ``axis_name`` via the ring (shard_map body).
    Returns the concatenation over devices along dim 0."""
    ring = RingStream(axis_name)
    n = ring.n_words()
    idx = ring.index()
    out = jnp.zeros((n, *x.shape), x.dtype)
    cur = x
    for word in range(n):
        src = (idx - word) % n            # word `word` holds shard `src`
        out = out.at[src].set(cur)        # consume: bank the landed word
        if word + 1 < n:
            cur = ring.hop(cur)           # produce: next word in flight
    return out.reshape(n * x.shape[0], *x.shape[1:])


def allgather_matmul(x_shard: jnp.ndarray, w: jnp.ndarray,
                     axis_name: str,
                     policy=None) -> jnp.ndarray:
    """Compute (allgather(x) @ w) with per-hop overlap.

    x_shard: [m_shard, k] (sharded on rows over ``axis_name``); w: [k, n]
    replicated. Returns [m_shard * n_dev, n] — each hop's chunk multiplies
    while the next hop's ppermute is in flight. ``policy`` routes the
    per-word dot through the ``repro.ops.matmul`` stream kernel.
    """
    ring = RingStream(axis_name)
    dot = _local_matmul(policy)
    n_dev = ring.n_words()
    idx = ring.index()
    m = x_shard.shape[0]
    out = jnp.zeros((n_dev, m, w.shape[1]),
                    jnp.promote_types(x_shard.dtype, w.dtype))
    cur = x_shard
    for word in range(n_dev):
        src = (idx - word) % n_dev
        part = dot(cur, w)                         # consumer
        out = out.at[src].set(part)
        if word + 1 < n_dev:
            cur = ring.hop(cur)                    # producer
    return out.reshape(n_dev * m, w.shape[1])


def matmul_reducescatter(x: jnp.ndarray, w_shard: jnp.ndarray,
                         axis_name: str,
                         policy=None) -> jnp.ndarray:
    """Compute reduce_scatter(x @ allgathered-w) in ring form: each word
    multiplies one weight shard and shifts the partial sum — the ring
    reduce-scatter fused with the matmul that produces it.

    x: [m, k_shard] (k sharded); w_shard: [k_shard, n]. Output: [m, n]
    reduced over the axis, scattered by rows: returns [m // n_dev, n].
    ``policy`` routes the per-word dot through ``repro.ops.matmul``.
    """
    ring = RingStream(axis_name, reverse=True)
    dot = _local_matmul(policy)
    n_dev = ring.n_words()
    idx = ring.index()
    m = x.shape[0]
    rows = m // n_dev
    acc = jnp.zeros((rows, w_shard.shape[1]),
                    jnp.promote_types(x.dtype, w_shard.dtype))
    for word in range(n_dev):
        blk = (idx + 1 + word) % n_dev
        x_blk = jax.lax.dynamic_slice_in_dim(x, blk * rows, rows, axis=0)
        acc = acc + dot(x_blk, w_shard)            # consumer
        if word + 1 < n_dev:
            acc = ring.hop(acc)                    # producer (reverse ring)
    return acc
