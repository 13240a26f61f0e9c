"""Pipe: the on-chip FIFO connecting a memory (producer) stage to a compute
(consumer) stage.

This is the TPU realization of the paper's OpenCL pipe / Intel channel:

* FPGA: a BRAM FIFO of configurable depth, one scalar word per read/write.
* TPU (here): a VMEM ring buffer of ``depth`` slots, each slot holding one
  *tile* (the TPU "word" is a VREG-aligned block, not a scalar), with one DMA
  semaphore per (slot, stream).

``streams`` models the paper's multiple-producers/multiple-consumers (M2C2):
each tile is split into ``streams`` disjoint sub-copies issued as concurrent
DMAs, exactly like the paper's static index-parity load balancing.

The pipe's "resource utilization" analogue (paper: BRAM / logic) is VMEM
bytes, exposed as :meth:`Pipe.vmem_bytes` and budget-checked by the planner.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax.numpy as jnp
import numpy as np

# TPU tiling granularity for f32: (8 sublanes, 128 lanes). Smaller dtypes pack
# more sublanes; we keep the conservative f32 granule for validation.
_SUBLANE = 8
_LANE = 128


@dataclasses.dataclass(frozen=True)
class Pipe:
    """Configuration of one producer→consumer pipe.

    Attributes:
      tile: block shape carried per pipe word (last two dims TPU-aligned).
      dtype: element dtype carried by the pipe.
      depth: ring-buffer slots (paper: channel depth). depth=1 degenerates to
        the synchronous copy-then-compute baseline (no lookahead); depth>=2
        enables the feed-forward overlap (double/multi-buffering).
      streams: concurrent producer DMAs per word (paper: #producers). The
        tile's leading dim is split ``streams`` ways.
    """

    tile: Tuple[int, ...]
    dtype: jnp.dtype = jnp.float32
    depth: int = 2
    streams: int = 1

    def __post_init__(self):
        if self.depth < 1:
            raise ValueError(f"pipe depth must be >= 1, got {self.depth}")
        if self.streams < 1:
            raise ValueError(f"pipe streams must be >= 1, got {self.streams}")
        if len(self.tile) < 2:
            raise ValueError(f"pipe tile must be >= 2-D for TPU, got {self.tile}")
        if self.tile[0] % self.streams != 0:
            raise ValueError(
                f"tile leading dim {self.tile[0]} not divisible by streams={self.streams}"
            )
        # Full 128-lane tiles are the efficient case; narrower pipes are legal
        # (VMEM pads lanes physically) but must stay 8-aligned so the DMA
        # stays a whole-sublane copy. The planner prefers >=128-lane words.
        if self.tile[-1] % _SUBLANE != 0:
            raise ValueError(f"tile lane dim {self.tile[-1]} must be a multiple of {_SUBLANE}")
        # a single-row sublane dim is the full extent of a [N, 1, C] operand
        # (the row-gather layout the TPU's DMA engine accepts)
        if self.tile[-2] != 1 and self.tile[-2] % _SUBLANE != 0:
            raise ValueError(f"tile sublane dim {self.tile[-2]} must be 1 or a multiple of {_SUBLANE}")

    # -- resource accounting (the BRAM analogue) ---------------------------

    @property
    def word_bytes(self) -> int:
        return int(np.prod(self.tile)) * jnp.dtype(self.dtype).itemsize

    @property
    def vmem_bytes(self) -> int:
        """VMEM consumed by the ring buffer (depth slots of one word)."""
        return self.depth * self.word_bytes

    # -- derived shapes ----------------------------------------------------

    @property
    def buffer_shape(self) -> Tuple[int, ...]:
        """Scratch shape for the ring buffer: [depth, *tile]."""
        return (self.depth, *self.tile)

    @property
    def stream_tile(self) -> Tuple[int, ...]:
        """Per-stream sub-copy shape (tile split on the leading dim)."""
        return (self.tile[0] // self.streams, *self.tile[1:])

    def with_depth(self, depth: int) -> "Pipe":
        return dataclasses.replace(self, depth=depth)

    def with_streams(self, streams: int) -> "Pipe":
        return dataclasses.replace(self, streams=streams)


def sublane_rows(dtype) -> int:
    """Rows of one native TPU tile of ``dtype``: 8 for 32-bit types, 16 for
    16-bit and 32 for 8-bit ones (narrow types pack along sublanes)."""
    return _SUBLANE * max(1, 4 // jnp.dtype(dtype).itemsize)


def split_ok(rows: int, streams: int, dtype) -> bool:
    """Can a ``rows``-row tile split into ``streams`` DMAs of whole native
    tiles? The TPU compiler refuses a DMA whose rows are not a multiple of
    :func:`sublane_rows`, so a split below that is never planned."""
    return streams == 1 or (rows % streams == 0
                            and (rows // streams) % sublane_rows(dtype) == 0)


def clamp_streams(rows: int, streams: int, dtype) -> int:
    """Largest halving of ``streams`` that :func:`split_ok` accepts."""
    s = max(1, int(streams))
    while not split_ok(rows, s, dtype):
        s //= 2
    return max(1, s)


# Single source of the planning VMEM budget (v5e has ~128 MiB; keep slack
# for Mosaic's own buffers). The planner, the autotuner, and the graph
# compiler's split-budget logic all key off this one constant.
DEFAULT_VMEM_BUDGET_BYTES = 96 * 1024 * 1024


def vmem_budget_ok(pipes,
                   budget_bytes: int = DEFAULT_VMEM_BUDGET_BYTES) -> bool:
    """Check a set of pipes against a VMEM budget (v5e ~128MiB, keep slack)."""
    return sum(p.vmem_bytes for p in pipes) <= budget_bytes


def required_depth(dma_latency_s: float, word_service_time_s: float, cap: int = 8) -> int:
    """Min ring depth that hides DMA issue latency behind word service time.

    Paper finding ("channel depth does not significantly affect performance")
    holds when service time >= latency, i.e. required depth saturates at 2.
    """
    if word_service_time_s <= 0:
        return cap
    need = 1 + math.ceil(dma_latency_s / word_service_time_s)
    return max(2, min(cap, need))
