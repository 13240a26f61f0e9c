"""Mesh-aware streams: run the StreamProgram stack inside ``shard_map``.

This is the runtime bridge the distributed layer was missing: before it,
every sharded path (collectives, pipeline parallelism, the launch drivers)
bypassed the pipe machinery entirely and the planner only ever saw
single-device call sites. The bridge is deliberately thin:

* :func:`mesh_policy` tags a :class:`~repro.core.program.PipePolicy` with
  the ambient mesh topology (:class:`~repro.core.meshspec.MeshSpec`), so
  every plan and tuned-plan cache entry resolved under it is scoped to the
  topology — plans never leak across meshes;
* :func:`shard_streams` wraps any stream-kernel callable (a ``repro.ops``
  entrypoint, a compiled program, a whole model step) in ``shard_map``
  with the mesh-tagged policy installed as the session default inside the
  body. The body sees *local shard shapes*, so the planner automatically
  derives per-shard local workloads — the kernel running on 1/Nth of the
  batch plans 1/Nth of the word schedule, not the global one.

Example — a registry kernel under an 8-way data mesh::

    mesh = make_mesh((8,), ("data",))        # repro.launch.mesh
    with sharding.use_sharding(mesh):
        f = shard_streams(repro.ops.matmul,
                          in_specs=(P("data"), P(None, None)),
                          out_specs=P("data"))
        y = f(a, b)       # each shard plans (and caches) at local shapes
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax

from repro import obs
from repro.core.meshspec import MeshSpec
from repro.core.program import PipePolicy, current_policy
from repro.core.program import policy as policy_ctx
from repro.runtime import sharding as shlib


def mesh_policy(policy: Optional[PipePolicy] = None,
                ctx: Optional[shlib.ShardingContext] = None) -> PipePolicy:
    """Tag a policy with the mesh topology it will run under.

    ``policy`` defaults to the session policy, ``ctx`` to the ambient
    :class:`~repro.runtime.sharding.ShardingContext`. Without either mesh
    source the policy is returned unchanged (single-device call sites need
    no tag). The tag makes the topology explicit in every plan cache key
    even where the thread-local context is not visible (e.g. a policy
    captured at trace time and resolved later).
    """
    pol = current_policy() if policy is None else policy
    ctx = ctx or shlib.current()
    if pol.mesh is not None or ctx is None:
        return pol
    return pol.replace(mesh=MeshSpec.from_mesh(ctx.mesh))


def shard_streams(fn: Callable[..., Any], *, in_specs, out_specs,
                  ctx: Optional[shlib.ShardingContext] = None,
                  mesh=None, policy: Optional[PipePolicy] = None,
                  check: bool = False) -> Callable[..., Any]:
    """Wrap a stream-kernel callable in ``shard_map`` with mesh-aware
    planning inside the body.

    ``fn`` is any callable built on the StreamProgram stack (a
    ``repro.ops`` entrypoint, a ``compile_program`` result, a model step).
    The mesh comes from ``mesh``, else ``ctx``, else the ambient
    :func:`repro.runtime.sharding.use_sharding` context. Inside the body
    the session policy is the mesh-tagged ``policy`` (default: the current
    session policy), so:

    * the planner sizes pipes against the body's *local shard shapes*
      (per-shard word schedules — the shapes ``shard_map`` hands the body
      are already local), and
    * every plan / tuned plan is cache-keyed by the mesh topology.

    ``in_specs`` / ``out_specs`` are ordinary ``PartitionSpec`` pytrees.
    """
    ctx = ctx or shlib.current()
    if mesh is None:
        if ctx is None:
            raise ValueError(
                "shard_streams: no mesh — pass mesh=/ctx= or enter "
                "repro.runtime.sharding.use_sharding(mesh) first")
        mesh = ctx.mesh
    # the mesh actually running the body wins over the ambient context's
    # (they differ when an explicit mesh= overrides an installed context)
    pol = (policy or current_policy()).replace(
        mesh=MeshSpec.from_mesh(mesh))

    def body(*args):
        with policy_ctx(pol):
            return fn(*args)

    with obs.span("shard_streams", mesh=pol.mesh.token,
                  devices=pol.mesh.device_count):
        return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=check)
