"""Kernel registry: one KernelSpec per ff_* op, a single interface for the
benchmarks, the planner, the generated ``repro.ops`` namespace, and the
tests (style: models/registry.py).

Each kernel subpackage registers itself at import time with
:func:`register_kernel`, declaring:

  op          public entrypoint generated by
              :func:`repro.core.program.make_entrypoint` — accepts
              ``policy=PipePolicy(...)``, the session ``repro.policy``
              context, and the deprecated ``mode``/``depth``/``streams``/
              ``interpret`` keywords (shimmed, warn-once)
  alias       short name exposed as ``repro.ops.<alias>``
  ref         pure-jnp oracle
  cost        exact tile-schedule cost model -> KernelCost
  workload    Workload builder: call-site shapes -> (core.Workload, tile),
              the planner's input for depth/streams auto-sizing
  program     StreamProgram builder at the smoke shape point:
              ``program(depth=, streams=, tile=None) -> core.StreamProgram``
              — the declarative producer→pipe→consumer graph the kernel
              compiles through :func:`repro.core.program.compile_program`;
              ``tile`` takes one of the kernel's ``tile_options`` dicts
  tile_options candidate tile overrides (kernel-specific kwargs dicts, e.g.
              ``{"block": (256, 128, 128)}``) the measured autotuner
              (:mod:`repro.core.autotune`) may search in
              ``PipePolicy(mode="autotune")``; empty = the tile is fixed
  make_inputs tiny-input builder for smoke/equivalence runs

so adding a sixth kernel is a ~50-line StreamProgram declaration plus one
``register_kernel`` call — the benchmark harness, the ``--smoke`` mode,
``repro.ops``, and the registry tests all pick it up by enumeration,
nothing else changes.

Registration is lazy: the five built-in subpackages are imported on first
lookup, so ``import repro.kernels.registry`` alone stays cheap and the
subpackages can import this module without a cycle.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class KernelCost:
    """Exact tile-schedule cost of one kernel call (used by the roofline:
    Pallas custom calls are opaque to XLA cost analysis, so each op reports
    its own deterministic FLOP/byte counts)."""

    flops: float
    hbm_bytes: float
    vmem_bytes: int


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One registered kernel. ``bench_kwargs`` is the shape point used by
    benchmarks/kernel_bench.py and must be accepted by both ``cost`` and
    ``workload``.

    ``shard_dims`` declares the kernel's data-parallel decomposition: one
    entry per positional operand of ``op`` — the dim index the operand
    shards along under a data mesh axis, or ``None`` for a replicated
    operand (weights, tables). Kernels whose rows are independent along
    those dims (all five built-ins) run under ``shard_map`` with each
    shard calling the op at local shapes; :func:`run_sharded_smoke` and
    ``benchmarks/run.py --sharded`` build on it. ``shard_out_dim`` is the
    output's sharded dim. ``None`` opts the kernel out of the sharded
    harness."""

    name: str
    alias: str
    op: Callable[..., Any]
    ref: Callable[..., Any]
    cost: Callable[..., KernelCost]
    workload: Callable[..., Tuple[Any, Tuple[int, ...]]]
    program: Callable[..., Any]
    make_inputs: Callable[..., Tuple[tuple, dict]]
    bench_kwargs: Mapping[str, Any]
    tile_options: Tuple[Mapping[str, Any], ...] = ()
    regular: bool = True
    tol: float = 1e-4
    doc: str = ""
    shard_dims: Optional[Tuple[Optional[int], ...]] = None
    shard_out_dim: int = 0
    # ``sweep_inputs(key, site) -> (args, kwargs)`` synthesizes random
    # operands for ``op`` at a recorded call-site shape dict (the ``site=``
    # the op passes to autotune.resolve_call, possibly shape-bucketed) —
    # how the offline plan sweep (repro.plans.sweep) re-creates traffic
    # shapes without the original data. None opts the kernel out.
    sweep_inputs: Optional[Callable[..., Tuple[tuple, dict]]] = None


_REGISTRY: Dict[str, KernelSpec] = {}
_VERSION = 0    # bumped on every (re-)registration; lets caches invalidate

# the five built-in subpackages; their ops.py modules self-register on import
_BUILTIN = (
    "repro.kernels.ff_matmul.ops",
    "repro.kernels.ff_attention.ops",
    "repro.kernels.ff_decode_attention.ops",
    "repro.kernels.ff_chunk_scan.ops",
    "repro.kernels.ff_gather.ops",
)


def register_kernel(**fields) -> KernelSpec:
    """Register one kernel (keyword form of KernelSpec). Re-registration
    under the same name replaces the entry (supports module reloads)."""
    global _VERSION
    spec = KernelSpec(**fields)
    _REGISTRY[spec.name] = spec
    _VERSION += 1
    return spec


def registry_version() -> int:
    """Monotonic counter of (re-)registrations, for cache invalidation."""
    return _VERSION


def _ensure_loaded() -> None:
    for mod in _BUILTIN:
        importlib.import_module(mod)


def kernel_names() -> Tuple[str, ...]:
    _ensure_loaded()
    return tuple(sorted(_REGISTRY))


def all_kernels() -> Tuple[KernelSpec, ...]:
    _ensure_loaded()
    return tuple(_REGISTRY[n] for n in sorted(_REGISTRY))


def get_kernel(name: str) -> KernelSpec:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown kernel {name!r}; registered: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


# ---------------------------------------------------------------------------
# Graph registry: multi-kernel pipe graphs (repro.core.graph)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GraphSpec:
    """One registered multi-kernel graph workload.

    ``build(depth=2, streams=1, **tile_kwargs) -> core.StreamGraph``
    declares the graph at its shape point; the graph compiler / joint
    autotuner rebuild through it for candidate configurations (a different
    shared tile is a different graph, like a different kernel tile is a
    different program). ``make_inputs`` returns the compiled graph's
    external operands in ``CompiledGraph.arg_names`` order; ``ref`` is the
    pure-XLA oracle over the same operands; ``unfused`` runs the same
    computation as separate ``repro.ops`` calls — the two-calls baseline
    BENCH_graph.json compares fused/staged lowering against.
    """

    name: str
    build: Callable[..., Any]
    make_inputs: Callable[..., tuple]
    ref: Callable[..., Any]
    unfused: Callable[..., Any]
    tile_options: Tuple[Mapping[str, Any], ...] = ()
    tol: float = 1e-4
    doc: str = ""
    # plan-service sweep contract (repro.plans.sweep), mirroring
    # KernelSpec.sweep_inputs: ``op`` is the policy-accepting entrypoint
    # that funnels into resolve_graph at *call-site* shapes (run_graph
    # resolves at the declared smoke shapes, so its plan keys would never
    # match recorded traffic); ``sweep_inputs(key, site) -> (args, kwargs)``
    # rebuilds its operands from a recorded call-site shape dict
    op: Optional[Callable[..., Any]] = None
    sweep_inputs: Optional[Callable[..., Tuple[tuple, dict]]] = None


_GRAPHS: Dict[str, GraphSpec] = {}

# graph declarations live next to the model workloads they prove out;
# these modules self-register on import
_BUILTIN_GRAPHS = (
    "repro.models.moe",
    "repro.models.layers",
    "repro.runtime.paged_kv",
)


def register_graph(**fields) -> GraphSpec:
    """Register one multi-kernel graph (keyword form of GraphSpec)."""
    global _VERSION
    spec = GraphSpec(**fields)
    _GRAPHS[spec.name] = spec
    _VERSION += 1
    return spec


def _ensure_graphs_loaded() -> None:
    for mod in _BUILTIN_GRAPHS:
        importlib.import_module(mod)


def graph_names() -> Tuple[str, ...]:
    _ensure_graphs_loaded()
    return tuple(sorted(_GRAPHS))


def all_graphs() -> Tuple[GraphSpec, ...]:
    _ensure_graphs_loaded()
    return tuple(_GRAPHS[n] for n in sorted(_GRAPHS))


def get_graph(name: str) -> GraphSpec:
    _ensure_graphs_loaded()
    if name not in _GRAPHS:
        raise KeyError(
            f"unknown graph {name!r}; registered: {sorted(_GRAPHS)}")
    return _GRAPHS[name]


def run_graph(spec: GraphSpec, args: tuple, *, policy=None,
              prefer: Optional[str] = None):
    """Compile and run a registered graph under ``policy``.

    mode="ref" short-circuits to the XLA oracle. Measured policies
    (mode="autotune", depth/streams="measured") resolve the *joint*
    (shared tile, depth, streams) through the graph-keyed autotuner —
    each candidate rebuilds and recompiles the whole graph and is timed
    end to end — then compile under the tuned ints. Everything else
    compiles directly (planner-sized per node under the split VMEM
    budget). Returns ``(out, CompiledGraph | None)``; the compiled graph's
    ``plan`` carries the per-edge fused/staged decisions.
    """
    from repro.core import autotune
    from repro.core import graph as graphlib
    from repro.core.program import current_policy

    policy = current_policy() if policy is None else policy
    if policy.mode == "ref":
        return spec.ref(*args), None
    g = spec.build()
    if autotune.wants_measured(policy) and not autotune.has_tracers(*args):
        w, tile = graphlib.graph_workload(g)
        sig = graphlib.graph_signature(g)
        dtype = g.topo_order()[0].program.streams[0].spec.dtype

        def workload_fn(tk):
            return graphlib.graph_workload(spec.build(**dict(tk)))

        def runner(tk, depth, streams):
            gg = spec.build(depth=depth, streams=streams, **dict(tk))
            cg = graphlib.compile_graph(
                gg, policy=policy.replace(mode="ff", depth=depth,
                                          streams=streams), prefer=prefer)
            return lambda: cg(*args)

        choice = autotune.resolve_graph(
            spec.name, policy, workload=w, tile=tile, dtype=dtype,
            signature=sig, workload_fn=workload_fn, runner=runner,
            tile_options=spec.tile_options)
        g = spec.build(depth=choice.depth, streams=choice.streams,
                       **dict(choice.tile_kwargs))
        policy = policy.replace(mode="ff", depth=choice.depth,
                                streams=choice.streams)
    cg = graphlib.compile_graph(g, policy=policy, prefer=prefer)
    return cg(*args), cg


def run_graph_smoke(spec: GraphSpec, *, policy=None,
                    prefer: Optional[str] = None, seed: int = 0):
    """Run ``spec`` at its declared shapes against its XLA oracle.
    Returns (out, ref, max_abs_err, compiled)."""
    import jax

    args = spec.make_inputs(jax.random.key(seed))
    out, compiled = run_graph(spec, args, policy=policy, prefer=prefer)
    ref = spec.ref(*args)
    out_f, ref_f = np.float32(out), np.float32(ref)
    err = float(np.max(np.abs(out_f - ref_f))) if out_f.size else 0.0
    return out, ref, err, compiled


def sharded_inputs(spec: KernelSpec, n_shards: int, seed: int = 0
                   ) -> Tuple[tuple, dict]:
    """Global operands for a ``n_shards``-way sharded run of ``spec``.

    Each shard's slice is an independent draw of the kernel's smoke inputs
    (``make_inputs`` with a distinct key), concatenated along the declared
    ``shard_dims``; replicated operands (dim ``None``) come from shard 0.
    Because every built-in kernel is row-independent along its shard dim,
    the global call over the concatenation equals the per-shard calls —
    the parity the sharded tests assert.
    """
    import jax
    import jax.numpy as jnp

    if spec.shard_dims is None:
        raise ValueError(f"{spec.name} declares no shard_dims")
    per = [spec.make_inputs(jax.random.key(seed + i)) for i in range(n_shards)]
    kw = per[0][1]
    args = []
    for j, dim in enumerate(spec.shard_dims):
        if dim is None:
            args.append(per[0][0][j])
        else:
            args.append(jnp.concatenate([p[0][j] for p in per], axis=dim))
    return tuple(args), kw


def shard_partition_specs(spec: KernelSpec, args: tuple, out_ndim: int,
                          axis_name: str = "data"):
    """(in_specs, out_spec) PartitionSpecs for ``spec``'s declared shard
    decomposition: each operand sharded along its ``shard_dims`` entry
    (``None`` = replicated), the output along ``shard_out_dim``."""
    from jax.sharding import PartitionSpec as P

    if spec.shard_dims is None:
        raise ValueError(f"{spec.name} declares no shard_dims")

    def pspec(arg, dim):
        if dim is None:
            return P()
        parts = [None] * arg.ndim
        parts[dim] = axis_name
        return P(*parts)

    in_specs = tuple(pspec(a, d) for a, d in zip(args, spec.shard_dims))
    out_parts = [None] * out_ndim
    out_parts[spec.shard_out_dim] = axis_name
    return in_specs, P(*out_parts)


def run_sharded_smoke(spec: KernelSpec, mesh, *, axis_name: str = "data",
                      policy=None, seed: int = 0):
    """Run ``spec`` under ``shard_map`` on ``mesh`` against the unsharded
    op and the XLA oracle.

    The mesh axis ``axis_name`` shards each operand along its declared
    ``shard_dims`` entry; inside the body each shard calls the op at its
    *local* shapes under the policy tagged with ``mesh``'s topology, so
    the planner derives per-shard workloads and every plan is keyed by
    the topology (no ambient ``use_sharding`` context required). Returns
    ``(sharded, unsharded, ref, max |sharded-unsharded|, max |sharded-ref|)``
    as float32 numpy arrays / floats.
    """
    from repro.core.meshspec import MeshSpec
    from repro.core.program import PipePolicy
    from repro.runtime.streams import shard_streams

    n_shards = int(mesh.shape[axis_name])
    args, kw = sharded_inputs(spec, n_shards, seed)
    policy = PipePolicy() if policy is None else policy
    # the explicit per-call policy bypasses the session policy the
    # shard_streams wrapper installs, so it must carry the mesh tag itself
    policy = policy.replace(mesh=MeshSpec.from_mesh(mesh))

    out0 = spec.op(*args, **kw, policy=policy.replace(mesh=None))
    in_specs, out_spec = shard_partition_specs(spec, args, out0.ndim,
                                               axis_name)
    f = shard_streams(lambda *a: spec.op(*a, **kw, policy=policy),
                      in_specs=in_specs, out_specs=out_spec, mesh=mesh)
    sharded = f(*args)
    ref = spec.op(*args, **kw, policy=PipePolicy(mode="ref"))
    sh, un, rf = np.float32(sharded), np.float32(out0), np.float32(ref)
    err_un = float(np.max(np.abs(sh - un))) if sh.size else 0.0
    err_ref = float(np.max(np.abs(sh - rf))) if sh.size else 0.0
    return sh, un, rf, err_un, err_ref


def run_smoke(spec: KernelSpec, *, policy=None, depth=None, streams=None,
              seed: int = 0,
              interpret=None) -> Tuple[np.ndarray, np.ndarray, float]:
    """Run ``spec`` at its tiny smoke shapes against its oracle.

    Exercises the full planned path by default (depth/streams "auto" go
    through plan_pipe). Pass ``policy=PipePolicy(...)`` to pin a mode/
    sizing, or the legacy depth/streams/interpret kwargs to build one —
    mixing both raises, like the op entrypoints. Returns
    (out, ref, max_abs_err).
    """
    import jax

    from repro.core.program import PipePolicy

    legacy = {k: v for k, v in
              (("depth", depth), ("streams", streams),
               ("interpret", interpret)) if v is not None}
    if policy is None:
        policy = PipePolicy(mode="ff", depth=legacy.get("depth", "auto"),
                            streams=legacy.get("streams", "auto"),
                            interpret=legacy.get("interpret"))
    elif legacy:
        raise TypeError(
            f"run_smoke({spec.name}): pass either policy= or the "
            f"{sorted(legacy)} keywords, not both")
    args, kw = spec.make_inputs(jax.random.key(seed))
    out = np.float32(spec.op(*args, **kw, policy=policy))
    ref = np.float32(spec.op(*args, **kw, policy=PipePolicy(mode="ref")))
    err = float(np.max(np.abs(out - ref))) if out.size else 0.0
    return out, ref, err
