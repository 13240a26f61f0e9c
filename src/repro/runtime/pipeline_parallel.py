"""Pipeline parallelism over the ``pod`` axis: the paper's pipes at pod
scale, expressed as a Stream producer/consumer schedule.

GPipe-style schedule under shard_map: each pod holds a contiguous stage of
layers; activations flow stage->stage through a :class:`StageHandoff` —
the pod-scale analogue of a *staged* :class:`repro.core.graph.GraphEdge`
(the intermediate leaves the producer stage, crosses the interconnect, and
lands in the consumer stage's buffer; one microbatch per pipe word). With
M microbatches and S stages the bubble is (S-1)/(M+S-1) — the driver picks
M >= 4*S.

Each tick runs the same acquire → consume → release word schedule the
kernel emitter runs (:mod:`repro.core.emitter`):

* **acquire** — select this stage's input word for tick ``t`` (stage 0
  reads microbatch ``t`` from the feed; later stages read the handoff
  buffer their upstream released last tick);
* **consume** — ``stage_fn`` computes on the word. A ``policy`` threads
  the mesh-tagged session :class:`~repro.core.program.PipePolicy` around
  the stage body, so stream kernels inside the stage plan at local shard
  shapes with topology-keyed caches;
* **release** — push the output one hop down the ring
  (:meth:`StageHandoff.push`) while the next tick's compute proceeds —
  compute/comm overlap identical in shape to the kernel DAE schedule.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp



@dataclasses.dataclass(frozen=True)
class StageHandoff:
    """The inter-stage pipe: a staged GraphEdge across the mesh axis.

    ``push`` is the release step of the word schedule — it moves every
    stage's freshly produced word to its successor's buffer (stage s ->
    s+1; the last stage's word leaves the pipeline and is banked by the
    caller). Double-buffering falls out of the schedule: the ppermute of
    tick t is in flight while tick t+1's compute runs.
    """

    axis_name: str

    def n_stages(self) -> int:
        return jax.lax.axis_size(self.axis_name)

    def stage(self):
        return jax.lax.axis_index(self.axis_name)

    def push(self, y: jnp.ndarray) -> jnp.ndarray:
        perm = [(i, i + 1) for i in range(self.n_stages() - 1)]
        return jax.lax.ppermute(y, self.axis_name, perm)


def pipeline_apply(stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   stage_params: Any,
                   microbatches: jnp.ndarray,
                   axis_name: str,
                   policy=None) -> jnp.ndarray:
    """Run a GPipe pipeline under shard_map.

    stage_fn(params, x) -> x           one stage's forward
    stage_params                       this device's stage params (sharded)
    microbatches: [M, mb, ...]         this *pipeline's* input, replicated
                                       (stage 0 consumes them in order)
    policy                             optional PipePolicy installed (mesh-
                                       tagged) around the stage body, so
                                       stream kernels inside it plan per
                                       shard with topology-keyed caches
    Returns [M, mb, ...] final-stage outputs (valid on the last stage;
    replicated back by the caller if needed).
    """
    pipe = StageHandoff(axis_name)
    n_stage = pipe.n_stages()
    stage = pipe.stage()
    m = microbatches.shape[0]
    ticks = m + n_stage - 1

    if policy is not None:
        from repro.core.program import policy as policy_ctx
        from repro.runtime.streams import mesh_policy
        pol = mesh_policy(policy)

        def consume(p, x):
            with policy_ctx(pol):
                return stage_fn(p, x)
    else:
        consume = stage_fn

    buf = jnp.zeros_like(microbatches[0])     # this stage's handoff slot
    outs = jnp.zeros_like(microbatches)

    def tick(t, carry):
        buf, outs = carry
        mb_idx = t - stage                    # word at this stage this tick
        # -- acquire: stage 0 pulls from the feed, others from the handoff
        feed = jax.lax.dynamic_index_in_dim(
            microbatches, jnp.clip(t, 0, m - 1), keepdims=False)
        x_in = jnp.where(stage == 0, feed, buf)
        active = (mb_idx >= 0) & (mb_idx < m)
        # -- consume: the stage's compute kernel
        y = consume(stage_params, x_in)
        y = jnp.where(active, y, buf)
        # -- release: last stage banks its word; others push it one hop
        outs = jax.lax.cond(
            active & (stage == n_stage - 1),
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y, jnp.clip(mb_idx, 0, m - 1), 0),
            lambda o: o, outs)
        buf = pipe.push(y)
        return buf, outs

    _, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
    return outs
