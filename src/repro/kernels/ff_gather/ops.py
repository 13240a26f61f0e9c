"""Public op wrapper + cost model for ff_gather."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import autotune
from repro.core.emitter import cdiv
from repro.core.pipe import Pipe, vmem_budget_ok
from repro.core.pipeline_model import Workload
from repro.core.program import PipePolicy, make_entrypoint
from repro.kernels.ff_gather.kernel import _ROWS, build_program, gather_ff
from repro.kernels.ff_gather.ref import gather_ref
from repro.kernels.registry import KernelCost, register_kernel


def gather_cost(n: int, cols: int, *, depth: int = 4,
                dtype=jnp.float32) -> KernelCost:
    itemsize = jnp.dtype(dtype).itemsize
    return KernelCost(
        flops=0.0,
        hbm_bytes=float(2 * n * cols * itemsize + n * 4),
        vmem_bytes=depth * _ROWS * cols * itemsize,
    )


def gather_workload(n: int, cols: int, *, block_rows: int = 1,
                    blocks_per_word: int = _ROWS,
                    dtype=jnp.float32) -> Tuple[Workload, Tuple[int, int]]:
    """One word per bundle of ``blocks_per_word`` irregular loads of
    ``block_rows`` rows each (single rows by default) — the paper's IR
    access pattern: latency per word, hidden by (depth-1) x bundle
    outstanding DMAs. The planner's ``streams`` choice is modeled as
    concurrent 8-row producers; emission realizes it by widening the bundle
    to ``8 * streams`` rows per word (budget re-checked in ``_apply``)."""
    itemsize = jnp.dtype(dtype).itemsize
    rows = blocks_per_word * block_rows
    w = Workload(
        n_words=max(cdiv(n, blocks_per_word), 1),
        word_bytes=float(rows * cols * itemsize),
        flops_per_word=0.0,
        regular=False,
        store_bytes_per_word=float(rows * cols * itemsize),
    )
    return w, (rows, cols)


def _apply(table, idx, *, policy: PipePolicy):
    """rows = table[idx];
    policy.mode="ff"|"autotune"(measured plan)|"baseline"(depth=1)|"ref".

    The planned (or explicit) ``streams`` value widens the per-word row
    bundle to ``8 * streams`` concurrent row DMAs — the irregular-stream
    analogue of the paper's multi-producer design — so it is no longer
    silently dropped. There is no separate tile knob: the row bundle *is*
    the tile, so the autotuner searches (depth, streams) only.
    """
    if policy.mode == "ref":
        return gather_ref(table, idx)
    n = idx.shape[0]
    cols = table.shape[1]

    def _run(depth, streams):
        # The planner models 8-row words ("streams" = concurrent 8-row
        # producers); emission merges them into one 8*streams-row bundle.
        # Clamp to the bundles the index stream can actually fill (a wider
        # word than n rows is pure padding traffic), then re-check the
        # *emitted* ring against the VMEM budget and shed streams if the
        # widened word would blow it.
        streams = max(1, min(streams, n // _ROWS))
        while streams > 1 and not vmem_budget_ok(
                [Pipe(tile=(_ROWS * streams, cols), dtype=table.dtype,
                      depth=depth)]):
            streams //= 2
        rows_per_word = _ROWS * streams
        pad = (-n) % rows_per_word
        idx_p = jnp.pad(idx.astype(jnp.int32), (0, pad))
        return gather_ff(table, idx_p, depth=depth, streams=streams,
                         interpret=policy.interpret)

    w, tile = gather_workload(n, cols, dtype=table.dtype)
    # Clamp the tuner's search space to the streams the index stream can
    # fill, so candidates are distinct *effective* configs and the
    # persisted plan names the streams value that actually executes
    # (_run's clamp then only sheds on the VMEM re-check).
    max_streams = max(1, n // _ROWS)
    so = tuple(sorted({min(int(s), max_streams)
                       for s in policy.stream_options}))
    pol = policy if so == tuple(policy.stream_options) \
        else policy.replace(stream_options=so)
    choice = autotune.resolve_call(
        "ff_gather", pol, workload=w, tile=tile, dtype=table.dtype,
        workload_fn=lambda tk: gather_workload(n, cols, dtype=table.dtype),
        runner=None if autotune.has_tracers(table, idx) else
        lambda tk, dep, st: lambda: _run(dep, st),
        site={"rows": table.shape[0], "cols": cols, "n": n},
        site_dynamic=("rows", "n"))
    out = _run(choice.depth, choice.streams)
    return out[:n]


gather = make_entrypoint("ff_gather", _apply)


def _make_inputs(key):
    tab = jax.random.normal(key, (96, 128), jnp.float32)
    idx = jax.random.randint(jax.random.fold_in(key, 1), (52,), 0, 96)
    return (tab, idx), {}


def _sweep_inputs(key, site):
    # rebuild concrete operands at a recorded call-site shape (plan sweep)
    rows, cols, n = int(site["rows"]), int(site["cols"]), int(site["n"])
    dt = jnp.dtype(site.get("dtype", "float32"))
    tab = jax.random.normal(key, (rows, cols), dt)
    idx = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, rows)
    return (tab, idx), {}


def _smoke_program(*, depth: int = 4, streams: int = 1, tile=None):
    # the smoke shape point of _make_inputs (52 rows padded to the bundle);
    # no tile knob: the 8*streams row bundle is the tile
    del tile
    n = -(-52 // (_ROWS * streams)) * (_ROWS * streams)
    return build_program(n, 128, dtype=jnp.float32, depth=depth,
                         streams=streams)


register_kernel(
    name="ff_gather",
    alias="gather",
    op=gather,
    ref=gather_ref,
    cost=gather_cost,
    workload=gather_workload,
    program=_smoke_program,
    make_inputs=_make_inputs,
    bench_kwargs={"n": 1 << 20, "cols": 512, "dtype": jnp.float32},
    regular=False,
    tol=0.0,
    doc="irregular row gather (embedding / MoE dispatch)",
    shard_dims=(None, 0),        # table replicated, index rows split
    shard_out_dim=0,
    sweep_inputs=_sweep_inputs,
)
