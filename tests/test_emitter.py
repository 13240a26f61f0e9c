"""Ring-pipe emitter tests.

Two layers: (1) the emitter primitives driven directly by tiny streaming-
copy kernels (regular, multi-stream, mixed-depth, gather, deep-ring /
short-grid warmup); (2) every registered ff_* kernel against its ref.py
oracle across pipe depths 1/2/4 and stream counts 1/2 (interpret mode) —
the acceptance bar for the shared-emitter refactor."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core import GatherRingPipe, Pipe, RingPipe, acquire, release
from repro.kernels.registry import all_kernels

SPECS = {s.name: s for s in all_kernels()}


# ---------------------------------------------------------------------------
# emitter primitives: streaming-copy kernels
# ---------------------------------------------------------------------------

def _copy_kernel(x_hbm, o_ref, buf, sems, *, ring, n_words):
    g = pl.program_id(0)
    rows = ring.spec.tile[0]
    p = ring.bind(buf, sems, lambda w: x_hbm.at[pl.ds(w * rows, rows), :])
    acquire(g, n_words, [p])
    o_ref[...] = p.slot(g)[...]
    release(g, n_words, [p])


def ring_copy(x, depth, streams=1, rows=8):
    n_words = x.shape[0] // rows
    ring = RingPipe(Pipe(tile=(rows, x.shape[1]), dtype=x.dtype,
                         depth=depth, streams=streams))
    return pl.pallas_call(
        functools.partial(_copy_kernel, ring=ring, n_words=n_words),
        grid=(n_words,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((rows, x.shape[1]), lambda g: (g, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[*ring.scratch_shapes],
        interpret=True,
    )(x)


@pytest.mark.parametrize("depth", [1, 2, 3, 4])
@pytest.mark.parametrize("streams", [1, 2, 4])
def test_ring_copy_roundtrip(depth, streams):
    x = jax.random.normal(jax.random.key(0), (64, 128), jnp.float32)
    np.testing.assert_array_equal(np.asarray(ring_copy(x, depth, streams)),
                                  np.asarray(x))


def test_ring_deeper_than_grid():
    """Warmup prologue must clamp to n_words when the ring is deeper than
    the whole word stream (auto-planned depths hit this at tiny shapes)."""
    x = jax.random.normal(jax.random.key(1), (16, 128), jnp.float32)  # 2 words
    np.testing.assert_array_equal(np.asarray(ring_copy(x, depth=6)),
                                  np.asarray(x))


def _two_pipe_kernel(a_hbm, b_hbm, o_ref, a_buf, a_sems, b_buf, b_sems,
                     *, a_ring, b_ring, n_words):
    g = pl.program_id(0)
    pipes = [a_ring.bind(a_buf, a_sems, lambda w: a_hbm.at[pl.ds(w * 8, 8), :]),
             b_ring.bind(b_buf, b_sems, lambda w: b_hbm.at[pl.ds(w * 8, 8), :])]
    acquire(g, n_words, pipes)
    o_ref[...] = a_ring.slot(g)[...] + b_ring.slot(g)[...]
    release(g, n_words, pipes)


def test_mixed_depth_pipes():
    """Pipes in one kernel may have different depths (the emitter schedules
    each ring's warmup and refill independently)."""
    a = jax.random.normal(jax.random.key(2), (64, 128), jnp.float32)
    b = jax.random.normal(jax.random.key(3), (64, 128), jnp.float32)
    n_words = 8
    a_ring = RingPipe(Pipe(tile=(8, 128), dtype=a.dtype, depth=2))
    b_ring = RingPipe(Pipe(tile=(8, 128), dtype=b.dtype, depth=4, streams=2))
    out = pl.pallas_call(
        functools.partial(_two_pipe_kernel, a_ring=a_ring, b_ring=b_ring,
                          n_words=n_words),
        grid=(n_words,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((8, 128), lambda g: (g, 0)),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        scratch_shapes=[*a_ring.scratch_shapes, *b_ring.scratch_shapes],
        interpret=True,
    )(a, b)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(a + b))


def test_gather_ring_scratch_shapes():
    """The gather emitter issues one DMA per row of a word; the rows of a
    slot share one semaphore (each wait consumes one row's bytes)."""
    ring = GatherRingPipe(Pipe(tile=(8, 128), dtype=jnp.float32, depth=3))
    assert ring.n_dmas == 8 and ring.n_sems == 1
    buf, sems = ring.scratch_shapes
    assert buf.shape == (3, 8, 128)


# ---------------------------------------------------------------------------
# refactored kernels vs. oracles across (depth, streams)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("streams", [1, 2])
@pytest.mark.parametrize("depth", [1, 2, 4])
@pytest.mark.parametrize("name", sorted(SPECS))
def test_kernel_matches_oracle(name, depth, streams):
    spec = SPECS[name]
    args, kw = spec.make_inputs(jax.random.key(7))
    out = np.float32(spec.op(*args, **kw, mode="ff", depth=depth,
                             streams=streams, interpret=True))
    ref = np.float32(spec.op(*args, **kw, mode="ref"))
    if spec.tol == 0:
        np.testing.assert_array_equal(out, ref)
    else:
        np.testing.assert_allclose(out, ref, rtol=spec.tol, atol=spec.tol)


@pytest.mark.parametrize("name", sorted(SPECS))
def test_kernel_auto_plan_matches_oracle(name):
    """depth="auto"/streams="auto" (planner-sized pipes) stay correct."""
    spec = SPECS[name]
    args, kw = spec.make_inputs(jax.random.key(11))
    out = np.float32(spec.op(*args, **kw, mode="ff", depth="auto",
                             streams="auto", interpret=True))
    ref = np.float32(spec.op(*args, **kw, mode="ref"))
    if spec.tol == 0:
        np.testing.assert_array_equal(out, ref)
    else:
        np.testing.assert_allclose(out, ref, rtol=spec.tol, atol=spec.tol)
