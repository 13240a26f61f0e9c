"""Roofline-driven pipe planner.

The paper leaves (depth, #producers, #consumers) to the programmer, guided
by profiler output, and reports two empirical rules: depth barely matters
once latency is hidden, and >2x2 streams saturate the memory system. The
planner encodes exactly that reasoning on top of the analytic model, so the
framework can size pipes automatically per kernel call site.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Sequence, Tuple, Union

import jax.numpy as jnp

from repro import obs
from repro.core import profiling
from repro.core.meshspec import MeshSpec, SINGLE_DEVICE, resolve_mesh
from repro.core.pipe import DEFAULT_VMEM_BUDGET_BYTES, Pipe, \
    required_depth, split_ok, sublane_rows, vmem_budget_ok
from repro.core.pipeline_model import (
    HardwareModel,
    TPU_V5E,
    Workload,
    estimate_feedforward,
)


class PlanError(RuntimeError):
    """No feasible (depth, streams) candidate under the VMEM budget.

    Raised (never asserted: asserts vanish under ``python -O``) with the
    full search context attached, so autotune/bench callers can report the
    search space instead of a bare failure:

    Attributes:
      workload: the :class:`~repro.core.pipeline_model.Workload` planned for.
      vmem_budget_bytes: the budget every candidate was checked against.
      rejected: one human-readable line per rejected candidate.
    """

    def __init__(self, workload: Workload, vmem_budget_bytes: int,
                 rejected: Sequence[str]):
        self.workload = workload
        self.vmem_budget_bytes = vmem_budget_bytes
        self.rejected = tuple(rejected)
        lines = "; ".join(self.rejected) or "(no candidates generated)"
        super().__init__(
            f"no feasible pipe under the {vmem_budget_bytes}-byte VMEM "
            f"budget for workload {workload}; rejected: {lines}")


@dataclasses.dataclass(frozen=True)
class Plan:
    pipe: Pipe
    consumers: int
    predicted_s: float
    predicted_bw: float
    rationale: str
    skipped: Tuple[str, ...] = ()    # rejected candidates, one line each
    # what the plan was sized against: the (local, per-shard) workload and
    # the mesh topology the call site ran under — introspectable via
    # last_plan() so sharded tests can assert local-shape planning
    workload: Optional[Workload] = None
    mesh: MeshSpec = SINGLE_DEVICE


def plan_pipe(
    w: Workload,
    tile: Tuple[int, ...],
    dtype,
    hw: HardwareModel = TPU_V5E,
    stream_options: Sequence[int] = (1, 2, 4),
    depth_cap: int = 17,     # (cap-1) outstanding = burst-LSU parity

    vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET_BYTES,
) -> Plan:
    """Pick (depth, streams) minimizing modeled time under the VMEM budget.

    Ties break toward fewer streams and shallower pipes (the paper's
    "limit the number of channels" guidance).
    """
    base_pipe = Pipe(tile=tile, dtype=dtype, depth=2, streams=1)
    service = w.word_bytes / hw.stream_bandwidth(1, w.regular)
    depth = required_depth(hw.dma_latency_s, service, cap=depth_cap)

    best: Plan | None = None
    skipped = []
    for streams in stream_options:
        if tile[0] % streams != 0:
            skipped.append(
                f"streams={streams}: tile[0]={tile[0]} not divisible")
            continue
        # regular words split across DMAs; a split below the dtype's native
        # row tiling is refused by the TPU compiler (gathers widen instead)
        if w.regular and not split_ok(tile[0], streams, dtype):
            skipped.append(
                f"streams={streams}: {tile[0] // streams}-row DMAs split "
                f"the {sublane_rows(dtype)}-row tiling")
            continue
        pipe = base_pipe.with_depth(depth).with_streams(streams)
        if not vmem_budget_ok([pipe], vmem_budget_bytes):
            skipped.append(
                f"streams={streams} depth={depth}: ring vmem "
                f"{pipe.vmem_bytes}B > budget {vmem_budget_bytes}B")
            continue
        est = estimate_feedforward(w, hw, pipe)
        cand = Plan(
            pipe=pipe,
            consumers=streams,
            predicted_s=est.total_s,
            predicted_bw=est.achieved_bw,
            workload=w,
            rationale=(
                f"depth={depth} hides dma latency "
                f"({hw.dma_latency_s*1e9:.0f}ns over {service*1e9:.0f}ns/word); "
                f"streams={streams} bottleneck={est.bottleneck}"),
        )
        # require a >2% modeled win to take on more streams (channel-count
        # frugality, per the paper)
        if best is None or cand.predicted_s < best.predicted_s * 0.98:
            best = cand
    if best is None:
        raise PlanError(w, vmem_budget_bytes, skipped)
    if skipped:
        best = dataclasses.replace(
            best, skipped=tuple(skipped),
            rationale=best.rationale + f"; skipped: {'; '.join(skipped)}")
    return best


# -- call-site auto-sizing (depth="auto" / streams="auto") --------------------
#
# Every kernel's public op wrapper routes through here: the op builds its
# Workload from the call-site shapes and the planner returns the (depth,
# streams) the analytic model picks. Plans are memoized: the key is
# (op, workload, tile, dtype, hw, mesh, knobs) — workload and tile are pure
# functions of (op, shape, dtype), so this is the per-(op, shape, dtype, hw,
# mesh) plan cache with no risk of shape aliasing, and plans sized under one
# mesh topology are never served to call sites running under another.
#
# The cache is a hand-rolled insertion-ordered dict (not functools.lru_cache)
# so the resilience layer can *selectively* invalidate: an elastic remesh
# drops exactly the entries keyed by meshes that no longer exist
# (invalidate_mesh_plans) instead of nuking plans that are still valid.


class _CacheInfo(NamedTuple):
    hits: int
    misses: int
    maxsize: int
    currsize: int


_PLAN_MAXSIZE = 1024
_PLANS: "dict[tuple, Plan]" = {}    # insertion-ordered: FIFO eviction
_PLAN_HITS = 0
_PLAN_MISSES = 0


def _plan_cached(op: str, w: Workload, tile: Tuple[int, ...],
                 dtype_name: str, hw: HardwareModel,
                 stream_options: Tuple[int, ...], depth_cap: int,
                 vmem_budget_bytes: int, mesh: MeshSpec) -> Plan:
    global _PLAN_HITS, _PLAN_MISSES
    key = (op, w, tile, dtype_name, hw, stream_options, depth_cap,
           vmem_budget_bytes, mesh)
    plan = _PLANS.get(key)
    if plan is not None:
        _PLAN_HITS += 1
        return plan
    _PLAN_MISSES += 1
    plan = plan_pipe(w, tile, jnp.dtype(dtype_name), hw,
                     stream_options=stream_options, depth_cap=depth_cap,
                     vmem_budget_bytes=vmem_budget_bytes)
    plan = dataclasses.replace(plan, mesh=mesh)
    if len(_PLANS) >= _PLAN_MAXSIZE:
        _PLANS.pop(next(iter(_PLANS)))
    _PLANS[key] = plan
    return plan


def invalidate_mesh_plans(keep: MeshSpec, *,
                          keep_single: bool = True) -> int:
    """Drop every cached plan keyed by a mesh other than ``keep``.

    The elastic-recovery hook: after a remesh the surviving topology is
    ``keep`` — plans sized under the lost topology must never be served
    again, while plans for the surviving mesh (and, by default, the
    topology-independent :data:`~repro.core.meshspec.SINGLE_DEVICE`
    entries) stay warm. ``last_plan`` entries for dropped meshes are
    cleared too. Returns the number of plans dropped.
    """
    kept_meshes = {keep} | ({SINGLE_DEVICE} if keep_single else set())
    stale = [k for k, p in _PLANS.items() if p.mesh not in kept_meshes]
    for k in stale:
        del _PLANS[k]
    for op in [op for op, p in _LAST_PLAN.items()
               if p.mesh not in kept_meshes]:
        del _LAST_PLAN[op]
    return len(stale)


_LAST_PLAN: "dict[str, Plan]" = {}   # op -> most recent plan resolved


def last_plan(op: str) -> Optional[Plan]:
    """The most recent plan resolved for ``op`` (introspection hook: its
    ``workload``/``mesh`` record what the call site was actually sized
    against — the sharded-stream tests assert local-shape planning here)."""
    return _LAST_PLAN.get(op)


def planned_pipe(
    op: str,
    w: Workload,
    tile: Tuple[int, ...],
    dtype,
    hw: HardwareModel = TPU_V5E,
    stream_options: Sequence[int] = (1, 2, 4),
    depth_cap: int = 17,
    vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET_BYTES,
    mesh: MeshSpec = SINGLE_DEVICE,
) -> Plan:
    """Memoized :func:`plan_pipe` for one kernel call site."""
    pre_misses = _PLAN_MISSES
    with obs.span("plan_pipe", op=op, mesh=mesh.token) as sp:
        plan = _plan_cached(op, w, tuple(tile), jnp.dtype(dtype).name, hw,
                            tuple(stream_options), depth_cap,
                            vmem_budget_bytes, mesh)
        sp.set(depth=plan.pipe.depth, streams=plan.pipe.streams,
               predicted_s=plan.predicted_s,
               cached=_PLAN_MISSES == pre_misses)
    _LAST_PLAN[op] = plan
    return plan


def resolve_auto(
    op: str,
    depth: Union[int, str],
    streams: Union[int, str],
    *,
    workload: Workload,
    tile: Tuple[int, ...],
    dtype,
    hw: HardwareModel = TPU_V5E,
    stream_options: Sequence[int] = (1, 2, 4),
    mesh: MeshSpec = SINGLE_DEVICE,
) -> Tuple[int, int]:
    """Resolve ``depth="auto"`` / ``streams="auto"`` to planned integers.

    Explicit integers pass through untouched (the paper's programmer-chosen
    sizing stays available); the planner only runs when at least one of the
    two is ``"auto"``, and its Plan is served from the per-(op, shape,
    dtype, hw, mesh) cache on repeat call sites. ``"measured"`` is accepted
    as a synonym for ``"auto"`` here: it is the analytic *fallback* for call
    sites the autotuner (:mod:`repro.core.autotune`) cannot measure (traced
    arguments, no runner) — measured resolution itself never reaches this
    function.
    """
    for label, val in (("depth", depth), ("streams", streams)):
        if isinstance(val, str) and val not in ("auto", "measured"):
            raise ValueError(
                f"{label} must be an int or 'auto'/'measured', got {val!r}")
    depth = "auto" if depth == "measured" else depth
    streams = "auto" if streams == "measured" else streams
    if depth != "auto" and streams != "auto":
        return int(depth), int(streams)
    plan = planned_pipe(op, workload, tile, dtype, hw,
                        stream_options=stream_options, mesh=mesh)
    d = plan.pipe.depth if depth == "auto" else int(depth)
    s = plan.pipe.streams if streams == "auto" else int(streams)
    return d, s


def resolve_policy(
    op: str,
    policy,
    *,
    workload: Workload,
    tile: Tuple[int, ...],
    dtype,
    mesh: Optional[MeshSpec] = None,
) -> Tuple[int, int]:
    """Planner entry for :class:`repro.core.program.PipePolicy` call sites.

    Duck-typed over anything exposing ``mode`` / ``depth`` / ``streams`` /
    ``hw`` / ``stream_options`` (and optionally ``mesh``): resolves "auto"
    fields against the policy's hardware model and mesh topology (so plans
    are cache-keyed by policy *and* topology, not just shape) and applies
    the mode semantics — ``baseline`` forces the synchronous depth=1 pipe
    after planning, exactly like the legacy per-kernel keyword plumbing
    did. When the policy carries no explicit mesh, the ambient
    :class:`~repro.runtime.sharding.ShardingContext` is consulted — a call
    site running inside ``use_sharding`` plans under that topology without
    any keyword plumbing.
    """
    if mesh is None:
        mesh = resolve_mesh(getattr(policy, "mesh", None))
    if profiling.recording():
        # planner-origin traffic record: suppressed when the call came
        # through autotune.resolve_call (which already recorded it)
        profiling.emit_planner(op=op, policy=policy, workload=workload,
                               tile=tile, dtype=jnp.dtype(dtype).name,
                               mesh=mesh)
    with obs.span("resolve_policy", op=op, mode=policy.mode,
                  mesh=mesh.token) as sp:
        depth, streams = resolve_auto(
            op, policy.depth, policy.streams, workload=workload, tile=tile,
            dtype=dtype, hw=policy.hw,
            stream_options=tuple(policy.stream_options), mesh=mesh)
        if policy.mode == "baseline":
            depth = 1
        sp.set(depth=depth, streams=streams)
    return depth, streams


# -- multi-kernel graphs (repro.core.graph) ----------------------------------
#
# A fused graph runs several stream programs inside one pallas_call, so the
# single-kernel VMEM budget must be *split* across the fused stages: each
# node plans its pipes against its share, and the fuser re-checks the
# combined footprint of a fused pair (producer rings + the in-VMEM
# intermediate ring + consumer rings + scratch) before committing to fusion.


def split_graph_budget(names: Sequence[str],
                       vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET_BYTES,
                       ) -> "dict[str, int]":
    """Split the VMEM budget evenly across a graph's nodes.

    Even split is deliberate: the budget bounds the *worst case* where every
    adjacent edge fuses and all stages cohabit one kernel. A node that plans
    under its share is guaranteed composable into any fused segment.
    """
    if not names:
        return {}
    share = vmem_budget_bytes // len(names)
    return {n: share for n in names}


def check_fused_vmem(edge: str, parts: "dict[str, int]",
                     vmem_budget_bytes: int) -> Tuple[bool, str]:
    """Check one fused pair's combined VMEM footprint against its budget.

    ``parts`` itemizes the footprint (producer rings, intermediate ring,
    consumer rings, scratch). Returns (feasible, rationale-line); the
    caller turns an infeasible *requested* fusion into a :class:`PlanError`
    with this line in ``rejected`` and an auto fusion into a staged
    fallback with the line as the edge rationale.
    """
    del edge    # callers prefix the edge label when surfacing the line
    total = sum(parts.values())
    detail = " + ".join(f"{k}={v}B" for k, v in parts.items())
    if total <= vmem_budget_bytes:
        return True, (f"fused vmem {total}B ({detail}) fits the "
                      f"{vmem_budget_bytes}B fused-stage budget")
    return False, (f"fused vmem {total}B ({detail}) exceeds the "
                   f"{vmem_budget_bytes}B fused-stage budget")


def plan_cache_info() -> _CacheInfo:
    """Hit/miss stats of the planner's plan cache (CacheInfo-shaped)."""
    return _CacheInfo(_PLAN_HITS, _PLAN_MISSES, _PLAN_MAXSIZE, len(_PLANS))


def plan_cache_clear() -> None:
    global _PLAN_HITS, _PLAN_MISSES
    _PLANS.clear()
    _PLAN_HITS = 0
    _PLAN_MISSES = 0
    _LAST_PLAN.clear()
